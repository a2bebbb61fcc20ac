"""The reader of ``segments_value_pruned_share`` (PR 48) on a recorded
pair of ``/metrics`` snapshots: the segments the value pruner left out of
the window's queries over those the queries were handed; nothing where
the program has no such meters (the parent of PR 48) or the window
offered nothing; and its entry in the manifest.

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

NAME, CELL = "segments_value_pruned_share", "ssb_flat_drilldown_closed"
READ = run.load_module(os.path.join(BENCH, "layer_metrics", NAME + ".py")).read
OFFERED, VALUE = "server.meter.prune.segments.offered", "server.meter.prune.segments.value"


def _run(before, after):
    return types.SimpleNamespace(before=before, after=after, delta=lambda key: after.get(key, 0) - before.get(key, 0))


@pytest.mark.parametrize("rounds", [1, 40])
def test_the_cells_rounds_read_42_of_112(rounds):
    # a round of the seven shapes over sixteen date ranges: 1 + 1 + 1 + 15 + 0 + 12 + 12 of 7 x 16 pairs are dead
    before = {OFFERED: 2 * 112, VALUE: 2 * 42}
    after = {OFFERED: (2 + rounds) * 112, VALUE: (2 + rounds) * 42}
    assert READ(_run(before, after)) == pytest.approx(37.5)


def test_a_window_without_a_dead_segment_reads_zero():
    # the lineitem cells: every segment holds the whole range of l_shipdate; the meter of the dead was never made
    assert READ(_run({OFFERED: 160}, {OFFERED: 1600})) == 0.0


@pytest.mark.parametrize("before,after", [
    ({}, {}),  # a program without the meters: the parent
    ({"server.meter.tier.answered.device": 5}, {"server.meter.tier.answered.device": 50}),
    ({OFFERED: 160, VALUE: 60}, {OFFERED: 160, VALUE: 60}),  # the meters are there and the window offered nothing
])
def test_nothing_to_read_is_none_and_never_raises(before, after):
    assert READ(_run(before, after)) is None


def test_the_manifest_lists_it_for_the_cell_and_last():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = manifest["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "plan build",
                     "moves": "latency_p50_ms", "workloads": [CELL]}
    reported = {m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert entry["moves"] in reported
    assert any(m["layer"] == entry["layer"] for m in manifest["per_layer"][:-1])  # a layer the benchmark already names
