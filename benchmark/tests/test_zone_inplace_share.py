"""The reader of ``zone_inplace_share`` (PR 35) on a recorded pair of
``/metrics`` snapshots: in-place launches over the window's zone-tier
launches, nothing where the program has no such counters (the parent of
PR 35) or the window launched no zone program, and its entry in the
manifest, looked up by name.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402

READER = run.load_module(os.path.join(BENCH, "layer_metrics", "zone_inplace_share.py"))
Z = "server.meter.zone.blocks."
CELLS = ["lineitem_groupby_closed", "lineitem_tpch_q1q6_closed"]


def _run(before, after):
    return types.SimpleNamespace(before=before, after=after,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def test_inplace_marks_over_the_windows_zone_launches():
    # the closed cell: the warm-up launched Q5 eleven times, the window 432 times
    warm = {Z + "inplace": 11, "server.meter.groupby.lowering.radix": 33}
    assert READER.read(_run(warm, {Z + "inplace": 443, "server.meter.groupby.lowering.radix": 1329})) == 100.0
    # every zone launch of the window a selection: the gathered view
    assert READER.read(_run(warm, {Z + "inplace": 11, Z + "gathered": 57})) == 0.0
    assert READER.read(_run({Z + "inplace": 11, Z + "gathered": 2}, {Z + "inplace": 41, Z + "gathered": 12})) == 75.0


def test_nothing_to_read_is_none_and_never_raises():
    assert READER.read(_run({}, {})) is None  # a program without the counters: the parent
    assert READER.read(_run({}, {"server.meter.groupby.lowering.radix": 3})) is None
    same = {Z + "inplace": 11}
    assert READER.read(_run(same, same)) is None  # the window launched no zone program: the open cells


def test_the_manifest_lists_it_for_the_two_closed_cells_under_the_kernels_layer():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert by_name["zone_inplace_share"] == {
        "name": "zone_inplace_share", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "latency_p50_ms", "workloads": CELLS}
    assert by_name["zone_inplace_share"]["layer"] == by_name["kernel_ms_per_query"]["layer"]
    cells = {w["name"]: w for w in manifest["workloads"]}
    reported = {m["name"]: m.get("workloads", list(cells)) for m in manifest["end_to_end"]}
    for cell in CELLS:  # each reports the end-to-end metric the reader moves, and is closed-loop
        assert cell in cells and cell in reported["latency_p50_ms"] and cell in reported["throughput_qps"]
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", "zone_inplace_share.py"))
