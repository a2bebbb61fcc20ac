"""Fast serving-curve smoke (tier-1, -m bench_smoke): bench.py's
concurrent serving mode end-to-end at tiny scale — closed-loop clients
at concurrency 8 over the pipelined and serial paths.  Guards the PR-2
tentpole invariants in CI: the device lane actually coalesces identical
dispatches under concurrency, and pipelined results never diverge from
the serial path.  (The full-scale bench smoke stays ``slow``.)"""
import json
import os
import subprocess
import sys

import pytest


@pytest.mark.bench_smoke
def test_serving_curve_smoke():
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        PINOT_TPU_BENCH_MODE="serving",
        PINOT_TPU_BENCH_SEGMENTS="1",
        PINOT_TPU_BENCH_ROWS_PER_SEGMENT="60000",
        PINOT_TPU_BENCH_SERVE_CLIENTS="8",
        PINOT_TPU_BENCH_SERVE_DURATION_S="1.5",
    )
    out = subprocess.run(
        [sys.executable, "bench.py"],
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout)
    assert doc["metric"] == "serving_closed_loop_qps_pipelined_vs_serial"

    # the pipelined lane must have coalesced identical dispatches under
    # 8 closed-loop clients of a repeated shape
    lane = doc["modes"]["pipelined"]["lane"]
    assert lane is not None and lane["coalesceHits"] > 0, lane
    assert lane["dispatches"] > 0
    # the serial mode must really be serial (no lane)
    assert doc["modes"]["serial"]["lane"] is None

    # no result divergence between the two execution paths
    assert doc["differential"]["identical_payloads"], doc["differential"]

    # utilization plane (PR 10): the pipelined lane's occupancy window
    # covers the measured ladder and must be busy under 8 closed-loop
    # clients; the D2H counter saw the result fetches; the CPU mesh
    # declares no peak so the roofline fraction is the explicit null
    util = doc["utilization"]["pipelined"]
    assert util["busyFraction"] > 0, util
    assert util["achievedBytesPerSec"] > 0 and util["d2hBytes"] > 0
    assert util["rooflineFraction"] is None
    # the serial mode has no lane, hence no occupancy fields — but its
    # device path still reports achieved bandwidth
    assert "busyFraction" not in doc["utilization"]["serial"]
    assert doc["utilization"]["serial"]["achievedBytesPerSec"] > 0

    # every curve step completed queries without errors
    for mode in ("serial", "pipelined"):
        for steps in doc["modes"][mode]["curves"].values():
            for step in steps:
                assert step["errors"] == 0, (mode, step)
                assert step["queries"] > 0, (mode, step)
