"""Warm-restart bench: the persistent-compile-cache + prewarm story in
numbers (ISSUE 16), producing one perf-gateable JSON document.

Three phases, each a FRESH python process (the jit/XLA executable
caches are process-local, so an in-process "restart" would overstate
warmth) sharing one persistent compile cache directory:

- ``cold``    — empty cache: first-query pays the genuine XLA compile
                (``compile.cold`` = shapes), then a steady closed loop
                measures the warmed p50.  Writes the broker's top-K
                workload snapshot (the prewarm feed) for phase 3.
- ``restart`` — same cache, fresh process, NO prewarm: the first query
                re-traces against the persistent cache
                (``compile.persistentHit``, ``compile.cold == 0``).
- ``prewarm`` — same cache, fresh process: the worker replays the
                phase-1 workload snapshot through
                ``build_prewarm_spec`` BEFORE any query, so the first
                serving query is ``compile.prewarmed``-backed.

The document's headline ``value`` is the prewarmed first-query latency;
``cold_free_restart`` is 1.0 only when BOTH restart phases kept
``compile.cold`` at zero (the gate's exact bar).  On a TPU the cold
compile is seconds per shape (PERF.md) and the warm-restart first query
is re-trace-only; CPU test runs keep the same mechanism at millisecond
scale.

Usage:
  the children share ``<compile cache root>/restart_bench`` (wiped at
  start) through JAX_COMPILATION_CACHE_DIR; just run
  python -m pinot_tpu.tools.restart_bench > RESTART_r16.json
  python -m pinot_tpu.tools.perf_gate RESTART_r16.json --baseline RESTART_r16.json
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

TABLE = "restartT"
PQL = f"SELECT sum(metInt), count(*) FROM {TABLE} GROUP BY dimStr TOP 5"
ROWS_PER_SEGMENT = 120
NUM_SEGMENTS = 4


def _build_broker():
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.tools.datagen import make_test_schema, random_rows
    from pinot_tpu.tools.cluster_harness import single_server_broker

    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, ROWS_PER_SEGMENT * NUM_SEGMENTS, seed=11)
    segs = [
        build_segment(
            schema,
            rows[i * ROWS_PER_SEGMENT : (i + 1) * ROWS_PER_SEGMENT],
            TABLE,
            f"seg{i}",
        )
        for i in range(NUM_SEGMENTS)
    ]
    return single_server_broker(TABLE, segs, pipeline=True)


def _meters(server) -> Dict[str, int]:
    snap = server.metrics.snapshot()["meters"]
    return {
        name: int(snap.get(name, {}).get("count", 0))
        for name in (
            "compile.cold",
            "compile.warm",
            "compile.persistentHit",
            "compile.persistentMiss",
            "compile.prewarmed",
            "prewarm.compiled",
            "prewarm.failed",
        )
    }


def run_phase(phase: str, workload_path: Optional[str], steady_n: int) -> Dict[str, Any]:
    broker = _build_broker()
    server = broker.local_servers[0]
    try:
        if phase == "prewarm":
            with open(workload_path) as f:
                entries = json.load(f)
            server.prewarm.workload_source = lambda tables, n: entries
            server.prewarm.request_prewarm(TABLE)
            deadline = time.monotonic() + 30.0
            while server.prewarm.warming and time.monotonic() < deadline:
                time.sleep(0.005)
            assert not server.prewarm.warming, "prewarm never finished"
        t0 = time.perf_counter()
        resp = broker.handle_pql(PQL)
        first_ms = (time.perf_counter() - t0) * 1000.0
        assert not resp.exceptions, resp.exceptions
        lat: List[float] = []
        for _ in range(steady_n):
            t0 = time.perf_counter()
            resp = broker.handle_pql(PQL)
            lat.append((time.perf_counter() - t0) * 1000.0)
            assert not resp.exceptions, resp.exceptions
        out = {
            "phase": phase,
            "firstQueryMs": round(first_ms, 3),
            "steadyP50Ms": round(statistics.median(lat), 3),
            "meters": _meters(server),
        }
        if phase == "cold" and workload_path:
            snapshot = broker.workload_snapshot(top=8)["topByCount"]
            with open(workload_path, "w") as f:
                json.dump(snapshot, f)
        return out
    finally:
        server.prewarm.stop()
        server.shutdown()


def _spawn_phase(
    phase: str, cache_dir: str, workload_path: str, steady_n: int
) -> Dict[str, Any]:
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "pinot_tpu.tools.restart_bench",
            "--phase",
            phase,
            "--workload",
            workload_path,
            "--steady-n",
            str(steady_n),
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"phase {phase} failed:\n{proc.stdout}\n{proc.stderr}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(prog="pinot_tpu-restart-bench")
    p.add_argument("--phase", choices=["cold", "restart", "prewarm"])
    p.add_argument("--workload", default=None)
    p.add_argument("--steady-n", type=int, default=40)
    p.add_argument("--cache-dir", default=None)
    args = p.parse_args(argv)

    if args.phase:
        out = run_phase(args.phase, args.workload, args.steady_n)
        print(json.dumps(out))
        return 0

    import jax

    from pinot_tpu.engine import compilecache

    # the bench's own cache, so its cold phase is cold
    cache_dir = args.cache_dir or compilecache.wiped_subroot("restart_bench")
    workload_path = os.path.join(cache_dir, "workload.json")
    cold = _spawn_phase("cold", cache_dir, workload_path, args.steady_n)
    restart = _spawn_phase("restart", cache_dir, workload_path, args.steady_n)
    prewarm = _spawn_phase("prewarm", cache_dir, workload_path, args.steady_n)

    cold_free = float(
        restart["meters"]["compile.cold"] == 0
        and prewarm["meters"]["compile.cold"] == 0
        and prewarm["meters"]["compile.prewarmed"] >= 1
        and restart["meters"]["compile.persistentHit"] >= 1
    )
    steady_p50 = prewarm["steadyP50Ms"]
    doc = {
        "metric": "restart_warm_first_query_ms",
        "value": prewarm["firstQueryMs"],
        "unit": "ms",
        "bench": "warm_restart_persistent_cache_prewarm",
        "platform": jax.devices()[0].platform,
        "total_rows": ROWS_PER_SEGMENT * NUM_SEGMENTS,
        "num_segments": NUM_SEGMENTS,
        "pql": PQL,
        "cold": cold,
        "restart": restart,
        "prewarm": prewarm,
        "cold_first_query_ms": cold["firstQueryMs"],
        "restart_first_query_ms": restart["firstQueryMs"],
        "steady_p50_ms": steady_p50,
        # structural ratios the gate bands: how much of the cold cliff
        # the persistent cache alone recovers, how much prewarm
        # recovers on top, and the first-query multiple of steady p50
        "restart_over_cold": round(
            restart["firstQueryMs"] / max(cold["firstQueryMs"], 1e-9), 4
        ),
        "prewarm_over_cold": round(
            prewarm["firstQueryMs"] / max(cold["firstQueryMs"], 1e-9), 4
        ),
        "first_query_over_steady_p50": round(
            prewarm["firstQueryMs"] / max(steady_p50, 1e-9), 4
        ),
        "cold_free_restart": cold_free,
    }
    print(json.dumps(doc, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
