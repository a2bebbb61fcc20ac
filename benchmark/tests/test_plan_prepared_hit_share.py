"""The reader of ``plan_prepared_hit_share`` (PR 32): hits over the
window's ``plan.prepared.*`` marks, and nothing where the program has no
such counters (the parent of PR 32) or no query reached the ladder.

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

READER = run.load_module(os.path.join(BENCH, "layer_metrics", "plan_prepared_hit_share.py"))
M = "server.meter.plan.prepared."


def _run(before, after):
    return types.SimpleNamespace(before=before, after=after,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def test_hits_over_the_windows_marks():
    warm = {M + "hit": 90, M + "miss": 4, M + "stale": 0}
    assert READER.read(_run(warm, {M + "hit": 1482, M + "miss": 4, M + "stale": 0})) == 100.0
    assert READER.read(_run(warm, {M + "hit": 96, M + "miss": 5, M + "stale": 1})) == 75.0
    assert READER.read(_run(warm, {M + "hit": 90, M + "miss": 8, M + "stale": 0})) == 0.0


def test_nothing_to_read_is_none_and_never_raises():
    assert READER.read(_run({}, {})) is None  # a program without the counters
    assert READER.read(_run({}, {M + "hit": 3})) is None  # or with another program's of that name
    same = {M + "hit": 3, M + "miss": 1, M + "stale": 0}
    assert READER.read(_run(same, same)) is None  # no query in the window


def test_the_manifest_lists_it_for_every_cell_under_the_plan_build_layer():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == "plan_prepared_hit_share"]
    assert entry == {"name": "plan_prepared_hit_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "plan build", "moves": "latency_p50_ms",
                     "workloads": [w["name"] for w in manifest["workloads"]]}
    assert entry["layer"] == next(m["layer"] for m in manifest["per_layer"] if m["name"] == "plan_build_ms_mean")
