"""The readers of ``selection_sort_share``, ``selection_rows_ms_mean`` and
``selection_candidates_mean`` (PR 50) on a recorded pair of ``/metrics``
snapshots: the window's share of selection launches that sorted every
row, the time from a selection's fetched candidates to its rows, the
valid candidates the device handed the host; nothing where the program
has no such series (the parent of PR 50) or the window gave nothing to
read; and their entries in the manifest, looked up by name and by
membership.

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

NAMES = ("selection_sort_share", "selection_rows_ms_mean", "selection_candidates_mean")
READERS = {name: run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py")) for name in NAMES}
CELL = "hits_search_selection_closed"
LOWERING, ROWS, CANDIDATES = "server.meter.selection.lowering.", "server.timer.phase.selectionRows", "server.meter.selection.candidates"


def _run(before, after):
    return types.SimpleNamespace(before=before, after=after, after_setup=before,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def test_the_windows_numbers():
    # the warm-up and the rehearsal launched each shape twice; the window 20 rounds of the three: by_time and
    # by_phrase through top_k, by_time_phrase through the sort; 120 candidates a query, 0.9 ms to gather their rows
    before = {LOWERING + "topk": 4, LOWERING + "sort": 2, LOWERING + "first": 0, ROWS + ".n": 6, ROWS + ".ms": 6.0, CANDIDATES: 720}
    after = {LOWERING + "topk": 44, LOWERING + "sort": 22, LOWERING + "first": 0, ROWS + ".n": 66, ROWS + ".ms": 60.0,
             CANDIDATES: 66 * 120}
    r = _run(before, after)
    assert READERS["selection_sort_share"].read(r) == pytest.approx(100.0 / 3)
    assert READERS["selection_rows_ms_mean"].read(r) == pytest.approx(0.9)
    assert READERS["selection_candidates_mean"].read(r) == 120.0


def test_a_window_without_a_sort_and_one_of_sorts_alone():
    packed = {LOWERING + "topk": 30, LOWERING + "first": 10}
    assert READERS["selection_sort_share"].read(_run({}, packed)) == 0.0  # launches, none sorted a segment
    assert READERS["selection_sort_share"].read(_run({}, {LOWERING + "sort": 7})) == 100.0
    # a later PR that prunes segments by a sort column's min and max: fewer candidates a query
    pruned = {ROWS + ".n": 10, ROWS + ".ms": 2.0, CANDIDATES: 300}
    assert READERS["selection_candidates_mean"].read(_run({}, pruned)) == 30.0


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_is_none_and_never_raises(name):
    read = READERS[name].read
    assert read(_run({}, {})) is None  # a program without the series: the parent
    # the parent's own series of a window of selections: launches, finalizes, none of the three families
    parent = {"server.timer.phase.finalize.n": 60, "server.timer.phase.finalize.ms": 90.0,
              "server.meter.tier.answered.device": 60, "server.meter.groupby.lowering.radix": 0}
    assert read(_run({}, parent)) is None
    same = {LOWERING + "topk": 4, LOWERING + "sort": 2, ROWS + ".n": 6, ROWS + ".ms": 6.0, CANDIDATES: 720}
    assert read(_run(same, same)) is None  # the series are there and the window launched or finalized no selection


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_lists_it_for_the_cell(name):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    entry = by_name[name]
    assert entry["workloads"] == [CELL] or CELL in entry["workloads"]  # by membership
    want = {"selection_sort_share": ("%", "lower", "program_counter", "kernel_ms_per_query"),
            "selection_rows_ms_mean": ("ms", "lower", "program_span", "finalize_ms_mean"),
            "selection_candidates_mean": ("count", "lower", "program_counter", "finalize_ms_mean")}[name]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == want[:3] + (
        by_name[want[3]]["layer"], "latency_p50_ms")
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    cells = {w["name"]: w for w in manifest["workloads"]}
    reported = {m["name"]: m.get("workloads", list(cells)) for m in manifest["end_to_end"]}
    assert CELL in cells and CELL in reported[entry["moves"]]
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
