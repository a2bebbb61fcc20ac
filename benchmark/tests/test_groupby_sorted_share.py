"""The reader of ``groupby_sorted_share`` (PR 38) on recorded counters,
nothing where the program has no such counters or the window launched no
group-by, and its entry in the manifest, looked up by name and its cell
by membership.

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

NAME = "groupby_sorted_share"
READER = run.load_module(os.path.join(BENCH, "layer_metrics", NAME + ".py"))
CELL = "lineitem_topsupplier_closed"
G = "server.meter.groupby."


def _run(before, after):
    return types.SimpleNamespace(before=before, after=after,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def test_sorted_marks_over_all_group_by_launches_of_the_window():
    warm = {G + "operands.sorted": 12, G + "lowering.radix": 12}
    assert READER.read(_run(warm, {G + "operands.sorted": 612, G + "lowering.radix": 612})) == 100.0
    # the closed cell's 2,000 groups: the contraction over the rows as they stand, no mark
    assert READER.read(_run(warm, {G + "operands.sorted": 12, G + "lowering.radix": 1341})) == 0.0
    # on the CPU the lowering is the scatter and the meter was never made
    assert READER.read(_run({G + "lowering.scatter": 2}, {G + "lowering.scatter": 26})) == 0.0
    mixed = {G + "operands.sorted": 22, G + "lowering.radix": 32, G + "lowering.onehot": 20, G + "operands.loop": 20}
    assert READER.read(_run(warm, mixed)) == 25.0


def test_nothing_to_read_is_none_and_never_raises():
    assert READER.read(_run({}, {})) is None  # a program without the counters
    assert READER.read(_run({}, {"server.meter.plan.prepared.hit": 3})) is None
    same = {G + "operands.sorted": 3, G + "lowering.radix": 3}
    assert READER.read(_run(same, same)) is None  # the window launched no group-by
    # the parent of PR 38: its launches are marked, none of them sorted
    assert READER.read(_run({G + "lowering.scatter": 12}, {G + "lowering.scatter": 312})) == 0.0


def test_the_manifest_lists_it_for_the_cell_under_the_kernels_layer():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    entry = by_name[NAME]
    assert CELL in entry["workloads"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "kernels",
        "moves": "latency_p50_ms"}
    assert entry["layer"] == by_name["kernel_ms_per_query"]["layer"] == by_name["groupby_loop_share"]["layer"]
    cells = {w["name"]: w for w in manifest["workloads"]}
    reported = {m["name"]: m.get("workloads", list(cells)) for m in manifest["end_to_end"]}
    assert all(cell in cells and cell in reported["latency_p50_ms"] for cell in entry["workloads"])
    # the cell's other group-by readers keep reading beside it
    for other in ("groupby_contraction_share", "zone_inplace_share"):
        assert CELL in by_name[other].get("workloads", [CELL])
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", NAME + ".py"))
