"""The readers of ``hll_sort_share``, ``hll_estimate_ms_mean`` and
``hll_derive_s`` (PR 41) on a recorded pair of ``/metrics`` snapshots: the
window's share of HLL launches on the sort lowering, the registers'
estimates per group-by query, the streams' derivation up to the window;
nothing where the program has no such series (the parent of PR 41) or the
window gave nothing to read; and their entries in the manifest, looked up
by name and by membership.

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

NAMES = ("hll_sort_share", "hll_estimate_ms_mean", "hll_derive_s")
READERS = {name: run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py")) for name in NAMES}
CELL = "hits_distinct_users_closed"
LOWERING, ESTIMATE, DERIVE = "server.meter.hll.lowering.", "server.timer.phase.hllEstimate", "server.timer.phase.hllDerive"


def _run(before, after, after_setup=None):
    return types.SimpleNamespace(before=before, after=after, after_setup=after if after_setup is None else after_setup,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def test_the_windows_numbers():
    # the warm-up and rehearsal launched each shape twice; the window 30 rounds of the three: the ungrouped shape on
    # the contraction, the two group-bys on the sort, which each estimate 9,040 groups in 11.5 ms
    before = {LOWERING + "matmul": 2, LOWERING + "sort": 4, LOWERING + "scatter": 0, ESTIMATE + ".n": 4, ESTIMATE + ".ms": 50.0,
              DERIVE + ".ms": 9_250.0, DERIVE + ".n": 3}
    after = {LOWERING + "matmul": 32, LOWERING + "sort": 64, LOWERING + "scatter": 0, ESTIMATE + ".n": 64,
             ESTIMATE + ".ms": 50.0 + 60 * 11.5, DERIVE + ".ms": 9_250.0, DERIVE + ".n": 3}
    r = _run(before, after, after_setup=before)
    assert READERS["hll_sort_share"].read(r) == pytest.approx(100.0 * 60 / 90)
    assert READERS["hll_estimate_ms_mean"].read(r) == pytest.approx(11.5)
    assert READERS["hll_derive_s"].read(r) == pytest.approx(9.25)  # up to the window: the window derives nothing
    # every launch on the scatter (the CPU backend's ungrouped form) reads 0, not nothing
    assert READERS["hll_sort_share"].read(_run({}, {LOWERING + "scatter": 5})) == 0.0
    assert READERS["hll_sort_share"].read(_run({}, {LOWERING + "sort": 2, LOWERING + "pairs": 2})) == 50.0


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_is_none_and_never_raises(name):
    read = READERS[name].read
    assert read(_run({}, {})) is None  # a program without the series: the parent
    assert read(_run({}, {"server.timer.phase.finalize.n": 3, "server.meter.groupby.lowering.radix": 3})) is None
    if name != "hll_derive_s":  # the series are there and the window launched or estimated nothing
        same = {LOWERING + "sort": 4, LOWERING + "matmul": 2, ESTIMATE + ".n": 4, ESTIMATE + ".ms": 50.0}
        assert read(_run(same, same)) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_lists_it_for_the_cell(name):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    entry = by_name[name]
    assert entry["workloads"] == [CELL]
    want = {"hll_sort_share": ("%", "higher", "program_counter", "kernels_layer", "latency_p50_ms"),
            "hll_estimate_ms_mean": ("ms", "lower", "program_span", "finalize_ms_mean", "latency_p50_ms"),
            "hll_derive_s": ("s", "lower", "program_span", "staging_s", "setup_s")}[name]
    layer = by_name["kernel_ms_per_query"]["layer"] if want[3] == "kernels_layer" else by_name[want[3]]["layer"]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == want[:3] + (layer, want[4])
    cells = {w["name"]: w for w in manifest["workloads"]}
    reported = {m["name"]: m.get("workloads", list(cells)) for m in manifest["end_to_end"]}
    assert CELL in cells and CELL in reported[entry["moves"]]
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
