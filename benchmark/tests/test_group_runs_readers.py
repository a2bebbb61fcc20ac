"""The readers of ``groupby_runs_share``, ``group_state_fetch_kb_mean`` and
``global_dict_build_s`` (PR 43) on a recorded pair of ``/metrics``
snapshots: the window's share of group-by launches through the runs
lowering, the group state a reply's finalize was handed, the table
dictionaries' build up to the window; nothing where the program has no
such series (the parent of PR 43) or the window gave nothing to read;
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

NAMES = ("groupby_runs_share", "group_state_fetch_kb_mean", "global_dict_build_s")
READERS = {name: run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py")) for name in NAMES}
CELL = "hits_top_users_closed"
LOWERING, TRIM = "server.meter.groupby.lowering.", "server.timer.phase.groupTrim"
FETCH, BUILD = "server.meter.groupby.stateFetchBytes", "server.timer.phase.globalDictBuild"


def _run(before, after, after_setup=None):
    return types.SimpleNamespace(before=before, after=after, after_setup=after if after_setup is None else after_setup,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def test_the_windows_numbers():
    # the warm-up and the rehearsal launched line 16 four times; the window 110 more, each handed 80,800 B of
    # candidates; UserID's dictionary of 17.6M values took 21.5 s, once, before the window
    before = {LOWERING + "runs": 4, LOWERING + "radix": 0, TRIM + ".n": 4, TRIM + ".ms": 2.0, FETCH: 4 * 80_800,
              BUILD + ".ms": 21_500.0, BUILD + ".n": 1}
    after = {LOWERING + "runs": 114, LOWERING + "radix": 0, TRIM + ".n": 114, TRIM + ".ms": 40.0, FETCH: 114 * 80_800,
             BUILD + ".ms": 21_500.0, BUILD + ".n": 1}
    r = _run(before, after, after_setup=before)
    assert READERS["groupby_runs_share"].read(r) == 100.0
    assert READERS["group_state_fetch_kb_mean"].read(r) == pytest.approx(80.8)
    assert READERS["global_dict_build_s"].read(r) == pytest.approx(21.5)  # up to the window: the window builds nothing


def test_a_dense_state_coming_back_shows():
    # Q15 as lineitem_topsupplier_closed launches it: radix marks alone, 1.76 MB of state a reply
    before = {LOWERING + "radix": 8, TRIM + ".n": 8, FETCH: 8 * 1_760_008}
    after = {LOWERING + "radix": 1408, TRIM + ".n": 1408, FETCH: 1408 * 1_760_008}
    assert READERS["groupby_runs_share"].read(_run(before, after)) == 0.0  # launches, none through the runs lowering
    assert READERS["group_state_fetch_kb_mean"].read(_run(before, after)) == pytest.approx(1760.008)
    mixed = {LOWERING + "runs": 1, LOWERING + "onehot": 1, LOWERING + "radix": 1, LOWERING + "scatter": 1}
    assert READERS["groupby_runs_share"].read(_run({}, mixed)) == 25.0  # the fourth of the family: all four divide


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_is_none_and_never_raises(name):
    read = READERS[name].read
    assert read(_run({}, {})) is None  # a program without the series: the parent
    # the parent's own series: group-by launches and trims, no runs mark, no fetch meter, no build timer
    parent = {LOWERING + "radix": 30, TRIM + ".n": 30, TRIM + ".ms": 60.0, "server.meter.groupby.groups.live": 30}
    if name != "groupby_runs_share":  # that one reads 0 of the parent's launches: none went through a lowering it lacks
        assert read(_run({}, parent)) is None
    if name != "global_dict_build_s":  # the series are there and the window launched or finalized nothing
        same = {LOWERING + "runs": 4, TRIM + ".n": 4, FETCH: 4 * 80_800}
        assert read(_run(same, same)) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_lists_it_for_the_cell(name):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    entry = by_name[name]
    assert CELL in entry["workloads"]  # by membership
    want = {"groupby_runs_share": ("%", "higher", "program_counter", "kernel_ms_per_query", "latency_p50_ms"),
            "group_state_fetch_kb_mean": ("KB", "lower", "program_counter", "finalize_ms_mean", "latency_p50_ms"),
            "global_dict_build_s": ("s", "lower", "program_span", "staging_s", "setup_s")}[name]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == want[:3] + (
        by_name[want[3]]["layer"], want[4])
    cells = {w["name"]: w for w in manifest["workloads"]}
    reported = {m["name"]: m.get("workloads", list(cells)) for m in manifest["end_to_end"]}
    assert CELL in cells and CELL in reported[entry["moves"]]
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
