"""The readers of ``expr_device_share`` and ``groupby_loop_share`` (PR
34) on recorded counters, nothing where the program has no such counters
(the parent of PR 34 has none of ``agg.expr.*``), and their entries in
the manifest, looked up by name.

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

EXPR = run.load_module(os.path.join(BENCH, "layer_metrics", "expr_device_share.py"))
LOOP = run.load_module(os.path.join(BENCH, "layer_metrics", "groupby_loop_share.py"))
CELL = "lineitem_tpch_q1q6_closed"
E = "server.meter.agg.expr."
G = "server.meter.groupby."


def _run(before, after):
    return types.SimpleNamespace(before=before, after=after,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def test_expr_device_share_is_device_marks_over_all_marks_of_the_window():
    warm = {E + "device": 40, E + "host": 0}
    assert EXPR.read(_run(warm, {E + "device": 3640, E + "host": 0})) == 100.0
    assert EXPR.read(_run(warm, {E + "device": 70, E + "host": 10})) == 75.0
    assert EXPR.read(_run(warm, {E + "device": 40, E + "host": 9})) == 0.0  # a quarantine: the host answers all


def test_groupby_loop_share_is_loop_marks_over_all_group_by_launches():
    warm = {G + "operands.loop": 30, G + "lowering.onehot": 30}
    assert LOOP.read(_run(warm, {G + "operands.loop": 2430, G + "lowering.onehot": 2430})) == 100.0
    # Q1 without shared slots: 66 cells, the staged one-hot contraction, no loop mark
    assert LOOP.read(_run(warm, {G + "operands.loop": 30, G + "lowering.onehot": 2430})) == 0.0
    # on the CPU the lowering is the scatter and the meter of the loop was never made
    assert LOOP.read(_run({G + "lowering.scatter": 2}, {G + "lowering.scatter": 26})) == 0.0
    mixed = {G + "operands.loop": 40, G + "lowering.onehot": 40, G + "lowering.radix": 30}
    assert LOOP.read(_run(warm, mixed)) == 25.0


def test_nothing_to_read_is_none_and_never_raises():
    for reader in (EXPR, LOOP):
        assert reader.read(_run({}, {})) is None  # a program without the counters: the parent
        assert reader.read(_run({}, {"server.meter.plan.prepared.hit": 3})) is None
    assert EXPR.read(_run({}, {E + "device": 3})) is None  # half of the pair is another program's
    same = {E + "device": 3, E + "host": 0, G + "operands.loop": 3, G + "lowering.onehot": 3}
    assert EXPR.read(_run(same, same)) is None and LOOP.read(_run(same, same)) is None  # an empty window


def test_the_manifest_lists_both_for_the_cell_under_the_kernels_layer():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("expr_device_share", "groupby_loop_share"):
        assert by_name[name] == {"name": name, "unit": "%", "better": "higher", "source": "program_counter",
                                 "layer": "kernels", "moves": "latency_p50_ms", "workloads": [CELL]}
        assert by_name[name]["layer"] == by_name["kernel_ms_per_query"]["layer"]
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    # the deployment serves from one chip; the cell holds the host's four for steadiness alone (PERF.md section 4)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tpch_lineitem_spec_1chip", "tpch_q1q6_closed", 4)
    (config,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert json.load(open(os.path.join(ROOT, config["file"])))["chips"] == 1
    assert config["reduced"] == [] and os.path.isfile(os.path.join(ROOT, config["file"]))
    listed = {m["name"] for kind in ("end_to_end", "per_layer") for m in manifest[kind] if CELL in m.get("workloads", [CELL])}
    assert {"latency_p50_ms", "throughput_qps", "hbm_bytes_per_row", "setup_s", "scan_roofline"} <= listed
    assert not {"latency_p95_ms", "loadgen_late_p95_ms"} & listed
    # every list that names the other closed cell names this one too
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            if "lineitem_groupby_closed" in m.get("workloads", []):
                assert CELL in m["workloads"], m["name"]
