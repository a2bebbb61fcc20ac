"""The reader of ``groupby_contraction_share`` (PR 26): a share of the
window's ``groupby.lowering.*`` marks, and nothing where the program has
none (the parent of PR 26) or the window launched no group-by.

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

READER = run.load_module(os.path.join(BENCH, "layer_metrics", "groupby_contraction_share.py"))


def _run(before, after):
    return types.SimpleNamespace(before=before, after=after,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def test_share_of_the_windows_marks():
    m = "server.meter.groupby.lowering."
    assert READER.read(_run({m + "radix": 5}, {m + "radix": 25})) == 100.0
    assert READER.read(_run({m + "radix": 5, m + "scatter": 1},
                            {m + "radix": 8, m + "onehot": 6, m + "scatter": 4})) == 75.0
    assert READER.read(_run({}, {m + "scatter": 4})) == 0.0


def test_nothing_to_read_is_none_and_never_raises():
    assert READER.read(_run({}, {})) is None  # a program without the counters
    m = "server.meter.groupby.lowering.radix"
    assert READER.read(_run({m: 3}, {m: 3})) is None  # no group-by in the window


def test_manifest_entry_is_last_and_lists_every_cell():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = manifest["per_layer"][-1]
    assert entry == {"name": "groupby_contraction_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels", "moves": "latency_p50_ms",
                     "workloads": [w["name"] for w in manifest["workloads"]]}
