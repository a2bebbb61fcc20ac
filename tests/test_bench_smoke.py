"""bench.py end-to-end smoke at tiny scale: the driver runs bench.py on
real hardware at round end — a bitrotted bench means no recorded
number, so the harness itself is regression-tested here (CPU, tiny
config, all phases: kernel marginal, broker latencies, the selective
path matrix, and the extra workload shapes)."""
import json
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_bench_end_to_end_smoke(tmp_path):
    env = dict(os.environ)
    env.update(
        PINOT_TPU_BENCH_SEGMENTS="1",
        PINOT_TPU_BENCH_ROWS_PER_SEGMENT="50000",
        PINOT_TPU_BENCH_ITERS="2",
        JAX_PLATFORMS="cpu",  # asked for: bench.py refuses a CPU it was not
    )
    out = subprocess.run(
        [sys.executable, "bench.py"],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    j = json.loads(line)
    assert j["metric"] == "tpch_q1_rows_scanned_per_sec_per_chip"
    assert j["value"] > 0
    d = j["detail"]
    assert d["platform"] == "cpu"  # every result names where it ran
    for key in (
        "broker_p50_ms",
        "broker_p99_ms",
        "sel_clustered_p50_ms_invindex",
        "sel_clustered_p50_ms_zonemap",
        "sel_clustered_p50_ms_fullscan",
        "sel_shuffled_p50_ms_invindex",
        "sel_shuffled_p50_ms_fullscan",
        "q6_p50_ms",
        "hll_groupby_p50_ms",
    ):
        assert key in d and d[key] > 0, key

    # perf regression gate (tools/perf_gate.py) on the fresh output:
    # vs itself the bands must hold trivially (pass), and vs a document
    # of another size the gate must detect the workload config mismatch
    # and SKIP rather than compare apples to oranges
    from pinot_tpu.tools.perf_gate import compare, load_bench

    fresh = load_bench(j)
    assert compare(fresh, fresh)["verdict"] == "pass"
    other = load_bench(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_doc_synthetic.json")
    )
    gated = compare(other, fresh)
    assert gated["verdict"] == "skipped"  # tiny smoke config != the other
    assert "detail.total_rows" in gated["configMismatch"]
