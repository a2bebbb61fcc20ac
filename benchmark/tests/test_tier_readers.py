"""The readers of ``tier_postings_share``, ``tier_host_share``,
``index_path_ms_mean``, ``host_index_build_s`` and
``group_keyspace_cells_mean`` (PR 47) on a recorded pair of ``/metrics``
snapshots: which rung of the ladder answered the window's queries, what a
postings answer cost, the postings built up to the window, the cells a
group-by's plan sized its group space at; nothing where the program has
no such series (the parent of PR 47) or the window gave nothing to read;
and their entries in the manifest, looked up by name and by membership.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402

NAMES = ("tier_postings_share", "tier_host_share", "index_path_ms_mean", "host_index_build_s", "group_keyspace_cells_mean")
READERS = {name: run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py")) for name in NAMES}
CELL = "ssb_flat_drilldown_closed"
TIER, INDEX, TRIM = "server.meter.tier.answered.", "server.timer.phase.indexPath", "server.timer.phase.groupTrim"
CELLS = "server.meter.groupby.keySpaceCells"


def _run(before, after, after_setup=None):
    return types.SimpleNamespace(before=before, after=after, after_setup=after if after_setup is None else after_setup,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def test_the_windows_numbers():
    # the warm-up and the rehearsal ran two rounds of the seven shapes, whose first postings answers built the
    # indexes in 14 s; the window 40 rounds more: two shapes a round by postings at 50 and 3 ms, five by the device
    planned = 4_375 + 437_500 + 175 + 4_375 + 1_750_000
    before = {TIER + "postings": 4, TIER + "device": 10, TIER + "host": 0, TIER + "bitsliced": 0,
              INDEX + ".ms": 14_000.0, INDEX + ".n": 4, TRIM + ".n": 10, CELLS: 2 * planned}
    after = {TIER + "postings": 84, TIER + "device": 210, TIER + "host": 0, TIER + "bitsliced": 0,
             INDEX + ".ms": 14_000.0 + 40 * 53.0, INDEX + ".n": 84, TRIM + ".n": 210, CELLS: 42 * planned}
    r = _run(before, after, after_setup=before)
    assert READERS["tier_postings_share"].read(r) == pytest.approx(200.0 / 7)
    assert READERS["tier_host_share"].read(r) == 0.0
    assert READERS["index_path_ms_mean"].read(r) == pytest.approx(26.5)
    assert READERS["host_index_build_s"].read(r) == pytest.approx(14.0)  # up to the window
    assert READERS["group_keyspace_cells_mean"].read(r) == pytest.approx(planned / 5)


def test_a_host_made_answer_shows():
    # a quarantine, or a group-by the planner sends to the host: one query in ten
    before = {TIER + "postings": 0, TIER + "device": 0, TIER + "host": 0}
    after = {TIER + "postings": 0, TIER + "device": 9, TIER + "host": 1}
    assert READERS["tier_host_share"].read(_run(before, after)) == 10.0
    assert READERS["tier_postings_share"].read(_run(before, after)) == 0.0
    assert READERS["index_path_ms_mean"].read(_run(before, after)) is None  # no query was answered there


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_is_none_and_never_raises(name):
    read = READERS[name].read
    if name == "host_index_build_s":  # PR 24's reader: the timers' sum, 0 where the program has none
        assert read(_run({}, {})) == 0.0
        return
    assert read(_run({}, {})) is None  # a program without the series: the parent
    # the parent's own series: group-by launches and trims, postings answers timed, no tier mark, no cells meter
    parent = {TRIM + ".n": 30, TRIM + ".ms": 60.0, INDEX + ".ms": 500.0, INDEX + ".n": 10, "server.meter.groupby.groups.live": 30}
    assert read(_run({}, parent)) is None
    same = {TIER + "postings": 4, TIER + "device": 10, TRIM + ".n": 10, CELLS: 100, INDEX + ".ms": 5.0}
    assert read(_run(same, same)) is None  # the series are there and the window answered nothing


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_lists_it_for_the_cell(name):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    entry = by_name[name]
    assert CELL in entry["workloads"]  # by membership
    want = {"tier_postings_share": ("%", "higher", "program_counter", "host tiers", "latency_p50_ms"),
            "tier_host_share": ("%", "lower", "program_counter", "host tiers", "latency_p50_ms"),
            "index_path_ms_mean": ("ms", "lower", "program_span", "host tiers", "latency_p50_ms"),
            "host_index_build_s": ("s", "lower", "program_span", "host tiers", "setup_s"),
            "group_keyspace_cells_mean": ("count", "lower", "program_counter", "D2H and finalize", "latency_p50_ms")}[name]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == want
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    reported = {m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert entry["moves"] in reported and cell["chips"] == 1
