"""The readers of ``group_trim_ms_mean``, ``groups_live_mean`` and
``groups_kept_mean`` (PR 37) on a recorded pair of ``/metrics``
snapshots: the window's trim time and group counts per group-by query,
nothing where the program has no such series (the parent of PR 37) or
the window finalized no group-by, and their entries in the manifest,
looked up by name.

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

NAMES = ("group_trim_ms_mean", "groups_live_mean", "groups_kept_mean")
READERS = {name: run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py")) for name in NAMES}
CELL = "lineitem_topsupplier_closed"
TRIM, LIVE, KEPT = "server.timer.phase.groupTrim", "server.meter.groupby.groups.live", "server.meter.groupby.groups.kept"


def _run(before, after):
    return types.SimpleNamespace(before=before, after=after,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def test_the_windows_means_per_group_by_query():
    # the warm-up and rehearsal finalized 12 group-bys, the window 300 more: 220,000 live, 100 kept, 14.5 ms each
    before = {TRIM + ".n": 12, TRIM + ".ms": 180.0, LIVE: 12 * 220_000, KEPT: 12 * 100}
    after = {TRIM + ".n": 312, TRIM + ".ms": 180.0 + 300 * 14.5, LIVE: 312 * 220_000, KEPT: 312 * 100 + 3}
    got = {name: READERS[name].read(_run(before, after)) for name in NAMES}
    assert got["group_trim_ms_mean"] == pytest.approx(14.5)
    assert got["groups_live_mean"] == 220_000 and got["groups_kept_mean"] == pytest.approx(100.01)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_is_none_and_never_raises(name):
    read = READERS[name].read
    assert read(_run({}, {})) is None  # a program without the series: the parent
    assert read(_run({}, {"server.timer.phase.finalize.n": 3, "server.timer.phase.finalize.ms": 1.0})) is None
    same = {TRIM + ".n": 12, TRIM + ".ms": 180.0, LIVE: 5, KEPT: 5}
    assert read(_run(same, same)) is None  # the window finalized no group-by on the device


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_lists_it_for_the_cell_under_d2h_and_finalize(name):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    entry = by_name[name]
    assert CELL in entry["workloads"] and entry["moves"] == "latency_p50_ms" and entry["better"] == "lower"
    assert entry["layer"] == by_name["finalize_ms_mean"]["layer"] == "D2H and finalize"
    assert (entry["unit"], entry["source"]) == (("ms", "program_span") if name == "group_trim_ms_mean"
                                                 else ("count", "program_counter"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    reported = {m["name"]: m.get("workloads", list(cells)) for m in manifest["end_to_end"]}
    assert CELL in cells and CELL in reported["latency_p50_ms"]
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
