"""Perf regression gate: fresh ``bench.py`` JSON vs a committed record.

``bench.py`` prints one JSON document per run.  This gate compares a
fresh run against a baseline document with per-metric tolerance bands,
so a perf regression fails CI instead of silently landing:

- higher-is-better metrics (rows/s throughput) must stay above
  ``baseline * min_ratio``;
- lower-is-better metrics (latencies, per-query ms) must stay below
  ``baseline * max_ratio``.

Bands are deliberately wide (CI machines are noisy; the committed
captures come from dedicated runs) — the gate catches the 2x cliff a
bad merge introduces, not 5% jitter.  ``PINOT_TPU_PERF_GATE_SCALE``
(or ``--tolerance-scale``) widens every band multiplicatively for even
noisier environments.

Runs are only comparable at the same workload size: when the two
documents disagree on ``total_rows`` / ``num_segments`` / ``platform``
the gate SKIPS (exit 0, verdict "skipped") rather than comparing apples
to oranges — pass ``--allow-config-mismatch`` to force the comparison
anyway (ratio semantics survive a platform change poorly; use only for
exploration).

Serving-mode documents (``PINOT_TPU_BENCH_MODE=serving``) gate their
own namespace — saturation QPS across serial/pipelined/cached configs,
the ISSUE 10 utilization fields (lane busy-fraction, achieved device
bytes/s, D2H volume), the ISSUE 11 sampling-overhead ratio (QPS with
the always-on tail sampler vs sampling off), and the ISSUE 13 batching
occupancy + result-cache hit rate against the committed
``SERVING_BATCH_r13.json`` — with the same direction-aware bands and
config-mismatch SKIP.  Multichip-mode documents
(``PINOT_TPU_BENCH_MODE=multichip``, the mesh execution plane) gate
per-config rows/s, the sharded-vs-single speedup, and per-lane
achieved bandwidth against the committed ``MULTICHIP_r06.json``.
Mixed kinds (default vs serving vs multichip) skip outright.

A default-mode ``bench.py`` document has no committed baseline (the
benchmark and its records are ROADMAP S0's to build): name one with
``--baseline``.

Usage:
  python -m pinot_tpu.tools.perf_gate current.json --baseline earlier.json

Exit codes: 0 pass/skip, 1 regression, 2 input error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

# metric path -> (direction, default band).  direction "higher": value
# must be >= baseline * band (band < 1).  direction "lower": value must
# be <= baseline * band (band > 1).
METRIC_SPECS: Dict[str, Tuple[str, float]] = {
    "value": ("higher", 0.40),  # headline rows/s
    "detail.vs_baseline_kernel_marginal": ("higher", 0.40),
    "detail.per_query_ms": ("lower", 2.5),
    "detail.batch_amortized_ms": ("lower", 2.5),
    "detail.broker_p50_ms": ("lower", 2.5),
    "detail.broker_p99_ms": ("lower", 3.0),
    "detail.broker_rows_per_sec_p50": ("higher", 0.40),
    "detail.sel_clustered_p50_ms_invindex": ("lower", 3.0),
    "detail.sel_clustered_p50_ms_zonemap": ("lower", 3.0),
    "detail.sel_clustered_p50_ms_fullscan": ("lower", 3.0),
    "detail.sel_shuffled_p50_ms_invindex": ("lower", 3.0),
    "detail.sel_shuffled_p50_ms_fullscan": ("lower", 3.0),
    "detail.q6_p50_ms": ("lower", 3.0),
    "detail.hll_groupby_p50_ms": ("lower", 3.0),
}

# config keys that must match for latency/throughput numbers to be
# comparable at all
CONFIG_KEYS = ("detail.total_rows", "detail.num_segments", "detail.platform")

# serving-mode documents (PINOT_TPU_BENCH_MODE=serving) carry their own
# metric namespace: saturation QPS + the utilization-plane fields
# (ISSUE 10 — lane occupancy and achieved bandwidth are the gated
# substrate for the throughput arc).  Occupancy/bandwidth bands are
# wide: closed-loop QPS on shared CI boxes swings, and these gate the
# 2x cliff (a lane suddenly idle, a bandwidth collapse), not jitter.
SERVING_METRIC_SPECS: Dict[str, Tuple[str, float]] = {
    "saturation_qps_repeated_q1.pipelined": ("higher", 0.40),
    "saturation_qps_repeated_q1.serial": ("higher", 0.40),
    "saturation_qps_mixed.pipelined": ("higher", 0.40),
    "saturation_qps_mixed.serial": ("higher", 0.40),
    "speedup_repeated_q1": ("higher", 0.50),
    "utilization.pipelined.busyFraction": ("higher", 0.30),
    "utilization.pipelined.achievedBytesPerSec": ("higher", 0.30),
    "utilization.serial.achievedBytesPerSec": ("higher", 0.30),
    "utilization.pipelined.d2hBytes": ("higher", 0.30),
    # sampling-overhead spec (ISSUE 11): qpsRatio = saturation QPS with
    # the always-on tail sampler + history recorder at defaults over
    # the same run with sampling off.  Near 1.0 by construction; the
    # band catches the sampler growing a real per-query cost (a ratio
    # collapse), not closed-loop jitter.  The absolute on-QPS also
    # rides the standard saturation band.
    "sampling_overhead.qpsRatio": ("higher", 0.60),
    "sampling_overhead.samplingOnQps": ("higher", 0.40),
    # cross-query batching + result cache (ISSUE 13): the batched
    # fraction and average batch size prove batches actually form on
    # the literal-mix ladder (a collapse means the tier silently
    # disengaged), the cache hit rate proves the ingest-aware cache
    # still serves repeats, and the cached-config ok-QPS rides the
    # same saturation bands as the other configs.  All absent in
    # pre-r13 baselines — the gate skips absent metrics.
    "saturation_qps_repeated_q1.cached": ("higher", 0.40),
    "saturation_qps_mixed.cached": ("higher", 0.40),
    "saturation_qps_literal_mix.cached": ("higher", 0.40),
    "saturation_qps_literal_mix.pipelined": ("higher", 0.40),
    "saturation_qps_literal_mix.serial": ("higher", 0.40),
    "batching.avgBatchSize": ("higher", 0.50),
    "batching.batchedQueryFraction": ("higher", 0.50),
    "rescache.hitRate": ("higher", 0.50),
}

SERVING_CONFIG_KEYS = ("total_rows", "num_segments", "platform")

SERVING_DEFAULT_BASELINE = "SERVING_BATCH_r13.json"

# multichip-mode documents (PINOT_TPU_BENCH_MODE=multichip, the mesh
# execution plane): per-execution-config scan-heavy rows/s, the
# sharded-vs-single speedup (the ISSUE 12 acceptance is >= 3x on an
# 8-device host — the band fails the gate if a merge collapses it
# below ~2.1x of the committed capture), and per-lane utilization.
# Direction-aware with the same config-mismatch SKIP as every kind.
MULTICHIP_METRIC_SPECS: Dict[str, Tuple[str, float]] = {
    "rows_per_sec.single_lane": ("higher", 0.40),
    "rows_per_sec.sharded": ("higher", 0.40),
    "rows_per_sec.lane_group": ("higher", 0.40),
    "sharded_vs_single": ("higher", 0.70),
    "lane_group_vs_single": ("higher", 0.60),
    "utilization.sharded.achievedBytesPerSec": ("higher", 0.30),
    "utilization.lane_group.achievedBytesPerSec": ("higher", 0.30),
}

MULTICHIP_CONFIG_KEYS = ("total_rows", "num_segments", "n_devices", "platform")

MULTICHIP_DEFAULT_BASELINE = "MULTICHIP_r06.json"

# join-mode documents (PINOT_TPU_BENCH_MODE=join, ISSUE 14): per-
# strategy closed-loop QPS over uniform and zipf-skewed keys, plus the
# two structural invariants the gate must never let collapse — the
# byte-identity differential against the host-reference join
# (identical == 1.0, exact) and the shuffle skew balance (max owner
# bytes / mean <= 2.0 under zipf with splitting on).
JOIN_METRIC_SPECS: Dict[str, Tuple[str, float]] = {
    "qps.colocated.uniform": ("higher", 0.40),
    "qps.broadcast.uniform": ("higher", 0.40),
    "qps.shuffle.uniform": ("higher", 0.40),
    "qps.shuffle.zipf": ("higher", 0.40),
    "differential.identical": ("higher", 1.0),
    "skew.balanceRatioSplit": ("lower", 1.30),
    "skew.heavyHitterSplits": ("higher", 1.0),
}

JOIN_CONFIG_KEYS = ("fact_rows", "dim_rows", "num_segments", "platform")

JOIN_DEFAULT_BASELINE = "JOIN_r14.json"

# ingest-mode documents (tools/ingest_bench.py --ladder, ISSUE 15): the
# partition-parallel consumer ladder.  Per-rung aggregate rows/s plus
# the two structural ratios — parallel_vs_single (same-host scaling; a
# collapse means partition-parallel ingest silently serialized) and
# vs_r5_single_consumer_ceiling (the arc's acceptance: aggregate must
# stay >= 1.5x the committed INGEST_r5 single-consumer LLC ceiling —
# the band is 1.5 / the committed INGEST_r15 capture's 2.531, so the
# gate floor sits exactly ON the acceptance bar).  Lag drains gate
# lower-is-better.  cpu_cores is a config key: ladder numbers are
# only comparable on an identically-sized host (config-mismatch SKIP).
INGEST_METRIC_SPECS: Dict[str, Tuple[str, float]] = {
    "value": ("higher", 0.40),
    "single_consumer_rows_per_sec": ("higher", 0.40),
    "ladder.c1.rows_per_sec": ("higher", 0.40),
    "ladder.c2.rows_per_sec": ("higher", 0.40),
    "ladder.c4.rows_per_sec": ("higher", 0.40),
    "ladder.c2.lag_drain_s": ("lower", 2.5),
    "ladder.c4.lag_drain_s": ("lower", 2.5),
    "parallel_vs_single": ("higher", 0.60),
    "vs_r5_single_consumer_ceiling": ("higher", 0.593),
}

INGEST_CONFIG_KEYS = (
    "partitions", "rows_per_partition", "cpu_cores", "platform",
)

INGEST_DEFAULT_BASELINE = "INGEST_r15.json"

# restart-mode documents (tools/restart_bench.py, ISSUE 16): the
# warm-restart story.  ``cold_free_restart`` is the exact structural
# bar — 1.0 only when both restart phases kept ``compile.cold`` at
# zero AND classified their first launches (persistentHit / prewarmed)
# — any cold compile on a restart fails the gate outright.  The ratio
# metrics band the recovered fraction of the cold cliff: the
# persistent cache alone must keep the first query under ~72% of cold,
# prewarming under ~2x its committed fraction (~5% of cold on the CPU
# capture; on a real TPU the cold side is ~25s so these ratios
# collapse toward zero).  first_query_over_steady_p50 rides a relative
# band: CPU steady p50 is broker overhead (~2ms) so the re-trace
# constant dominates the toy-scale ratio; the band catches it
# regressing toward the cold multiple (~180x), not jitter.
RESTART_METRIC_SPECS: Dict[str, Tuple[str, float]] = {
    "value": ("lower", 2.5),
    "cold_first_query_ms": ("lower", 2.5),
    "restart_first_query_ms": ("lower", 2.5),
    "steady_p50_ms": ("lower", 2.5),
    "restart_over_cold": ("lower", 1.6),
    "prewarm_over_cold": ("lower", 2.0),
    "first_query_over_steady_p50": ("lower", 2.0),
    "cold_free_restart": ("higher", 1.0),
}

RESTART_CONFIG_KEYS = ("total_rows", "num_segments", "platform")

RESTART_DEFAULT_BASELINE = "RESTART_r16.json"

# filter-matrix documents (tools/filter_matrix.py, ISSUE 17): the
# four-tier win map.  These are structural counts, not latencies: each
# tier must keep winning its region of the (selectivity, clustering)
# plane.  The 0.5 band on integer win counts means "keep at least half
# your cells, and never drop to zero when the baseline had any" — a
# tier's entire region collapsing (the bit-sliced tier silently
# disengaging, postings losing the needle cells) fails the gate, while
# a single boundary cell flapping between adjacent tiers does not.
# ``bitsliced_midsel_wins`` / ``value`` is the r17 acceptance bar: the
# bit-sliced tier must keep winning a shuffled mid-selectivity range
# cell (baseline >= 1, so the 0.5 band floors current at >= 1).
FILTERMATRIX_METRIC_SPECS: Dict[str, Tuple[str, float]] = {
    "value": ("higher", 0.5),
    "bitsliced_midsel_wins": ("higher", 0.5),
    "tier_wins.invindex": ("higher", 0.5),
    "tier_wins.zonemap": ("higher", 0.5),
    "tier_wins.bitsliced": ("higher", 0.5),
    "tier_wins.fullscan": ("higher", 0.5),
}

FILTERMATRIX_CONFIG_KEYS = ("total_rows", "num_segments", "platform")

FILTERMATRIX_DEFAULT_BASELINE = "FILTER_MATRIX_CPU_r17.json"

# tiered-residency documents (tools/cluster_harness.py hbm-pressure,
# ISSUE 18): the memory-pressure resilience story.  ``value`` /
# ``addressable_over_cap`` is the oversubscription factor the scenario
# actually sustained (addressable staged bytes over the HBM cap —
# ~8x by construction; shrinking means the scenario stopped proving
# pressure).  ``demotions`` / ``promotions`` / ``cold_loads`` are
# structural: the tiers must visibly CYCLE under the sweep (a silent
# residency manager that never demotes would pass a latency-only
# gate while the OOM heal path rots untested).  The hot-set latency
# bars ride wide bands — the hot table's closed loop runs concurrently
# with cold-table staging churn on a shared CPU box, so only an
# order-of-magnitude regression (hot set no longer protected by heat
# scoring) should fail the gate.
TIERED_METRIC_SPECS: Dict[str, Tuple[str, float]] = {
    "value": ("higher", 0.8),
    "addressable_over_cap": ("higher", 0.8),
    "hot_p99_ms": ("lower", 4.0),
    "hot_p99_over_baseline": ("lower", 4.0),
    "demotions": ("higher", 0.5),
    "promotions": ("higher", 0.5),
    "cold_loads": ("higher", 0.5),
}

TIERED_CONFIG_KEYS = ("num_tables", "platform")

TIERED_DEFAULT_BASELINE = "TIERED_r18.json"

# audit-plane documents (PINOT_TPU_BENCH_MODE=audit, ISSUE 19): the two
# promises the correctness/freshness audit plane must keep forever.
# ``value`` / ``audit_overhead.okQpsRatio`` is serving ok-QPS with the
# shipped audit defaults ON over audit fully OFF — the background
# shadow oracle + replica double-scatter must cost <= ~5% of serving
# throughput (baseline ratio ~1.0, band 0.95 floors it near 0.95; ratio
# is fresh-broker/pre-opened-window ok-QPS, same traps as the serving
# sampling_overhead spec).  ``detect_ms`` bounds how long the shadow
# auditor takes to flag + quarantine a seeded device-tier wrong answer
# under closed-loop load (milliseconds on the in-process harness; the
# wide band gates order-of-magnitude rot, not scheduler jitter).
# ``detected`` is structural: the seeded corruption must ALWAYS be
# caught — a gate run where it slipped through fails outright.
AUDIT_METRIC_SPECS: Dict[str, Tuple[str, float]] = {
    "value": ("higher", 0.95),
    "audit_overhead.okQpsRatio": ("higher", 0.95),
    "audit_overhead.auditOnQps": ("higher", 0.40),
    "detect_ms": ("lower", 50.0),
    "detected": ("higher", 1.0),
    "divergence.divergences": ("higher", 0.5),
}

AUDIT_CONFIG_KEYS = ("total_rows", "num_segments", "clients", "platform")

AUDIT_DEFAULT_BASELINE = "AUDIT_r19.json"


# disaster-recovery documents (cluster_harness --scenario
# disaster-recovery, ISSUE 20): the durability plane's forever
# promises.  Wall-clock rows (backup under load, restore-to-first-
# successful-query) get wide bands — they gate order-of-magnitude rot
# on the tiny harness cluster, not scheduler jitter.  The structural
# rows are absolute: restored answers must stay byte-identical to the
# pre-disaster payloads, and the scrubber must ALWAYS detect and
# repair the seeded corrupt store copy.  ``scrub.okQpsRatio`` is
# serving ok-QPS while a scrub round runs over the pre-scrub baseline
# window (clamped at 1.0) — scrubbing must never cost more than ~5%
# of serving throughput.
DR_METRIC_SPECS: Dict[str, Tuple[str, float]] = {
    "value": ("lower", 5.0),
    "backup.backupSeconds": ("lower", 5.0),
    "restore.restoreToFirstQuerySeconds": ("lower", 5.0),
    "restore.byteIdentical": ("higher", 1.0),
    "scrub.okQpsRatio": ("higher", 0.95),
    "scrub.detected": ("higher", 1.0),
    "scrub.repaired": ("higher", 1.0),
}

DR_CONFIG_KEYS = ("num_segments", "clients", "platform")

DR_DEFAULT_BASELINE = "DR_r20.json"


def _is_serving(doc: Dict[str, Any]) -> bool:
    return str(doc.get("metric", "")).startswith("serving_")


def _doc_kind(doc: Dict[str, Any]) -> str:
    metric = str(doc.get("metric", ""))
    if metric.startswith("serving_"):
        return "serving"
    if metric.startswith("multichip_"):
        return "multichip"
    if metric.startswith("join_"):
        return "join"
    if metric.startswith("ingest_"):
        return "ingest"
    if metric.startswith("restart_"):
        return "restart"
    if metric.startswith("filtermatrix_"):
        return "filtermatrix"
    if metric.startswith("tiered_"):
        return "tiered"
    if metric.startswith("audit_"):
        return "audit"
    if metric.startswith("dr_"):
        return "dr"
    return "default"


def _specs_for(doc: Dict[str, Any]):
    """(metric specs, config keys) for a bench document's kind."""
    kind = _doc_kind(doc)
    if kind == "serving":
        return SERVING_METRIC_SPECS, SERVING_CONFIG_KEYS
    if kind == "multichip":
        return MULTICHIP_METRIC_SPECS, MULTICHIP_CONFIG_KEYS
    if kind == "join":
        return JOIN_METRIC_SPECS, JOIN_CONFIG_KEYS
    if kind == "ingest":
        return INGEST_METRIC_SPECS, INGEST_CONFIG_KEYS
    if kind == "restart":
        return RESTART_METRIC_SPECS, RESTART_CONFIG_KEYS
    if kind == "filtermatrix":
        return FILTERMATRIX_METRIC_SPECS, FILTERMATRIX_CONFIG_KEYS
    if kind == "tiered":
        return TIERED_METRIC_SPECS, TIERED_CONFIG_KEYS
    if kind == "audit":
        return AUDIT_METRIC_SPECS, AUDIT_CONFIG_KEYS
    if kind == "dr":
        return DR_METRIC_SPECS, DR_CONFIG_KEYS
    return METRIC_SPECS, CONFIG_KEYS


def _get(doc: Dict[str, Any], path: str) -> Any:
    cur: Any = doc
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def load_bench(source) -> Dict[str, Any]:
    """A bench document from a dict, a path, or ``-`` (stdin).  Accepts
    both the raw ``bench.py`` output line and the committed capture
    wrapper (``{"parsed": {...}}``, the driver's record format); for a
    multi-line file the LAST JSON-parseable line wins (bench.py logs
    progress lines to stderr but belt-and-braces here)."""
    if isinstance(source, dict):
        doc = source
    else:
        text = sys.stdin.read() if source == "-" else open(source).read()
        try:
            doc = json.loads(text)
        except json.JSONDecodeError:
            doc = None
            for line in text.strip().splitlines():
                line = line.strip()
                if line.startswith("{"):
                    try:
                        doc = json.loads(line)
                    except json.JSONDecodeError:
                        continue
            if doc is None:
                raise
    if "parsed" in doc and isinstance(doc["parsed"], dict):
        doc = doc["parsed"]
    if doc.get("metric") is None:
        raise ValueError("not a bench.py document (no 'metric' field)")
    return doc


def compare(
    baseline: Dict[str, Any],
    current: Dict[str, Any],
    tolerance_scale: float = 1.0,
    allow_config_mismatch: bool = False,
) -> Dict[str, Any]:
    """Gate verdict: ``{"verdict": "pass"|"fail"|"skipped", ...}`` with
    one row per compared metric.  Pure — unit-testable without files.
    The spec set follows the document kind (default bench vs serving
    mode); mismatched kinds skip — there is nothing to compare."""
    if _doc_kind(baseline) != _doc_kind(current):
        return {
            "verdict": "skipped",
            "reason": "bench document kinds differ "
            "(default vs serving vs multichip mode)",
            "configMismatch": {
                "metric": {
                    "baseline": baseline.get("metric"),
                    "current": current.get("metric"),
                }
            },
            "metrics": [],
        }
    specs, config_keys = _specs_for(current)
    mismatches = {
        k: {"baseline": _get(baseline, k), "current": _get(current, k)}
        for k in config_keys
        if _get(baseline, k) != _get(current, k)
    }
    if mismatches and not allow_config_mismatch:
        return {
            "verdict": "skipped",
            "reason": "workload config mismatch (different scale/platform "
            "runs are not comparable)",
            "configMismatch": mismatches,
            "metrics": [],
        }
    rows: List[Dict[str, Any]] = []
    failures = 0
    for path, (direction, band) in specs.items():
        b, c = _get(baseline, path), _get(current, path)
        if not isinstance(b, (int, float)) or not isinstance(c, (int, float)):
            continue  # metric absent in one doc: nothing to gate
        if b <= 0:
            continue
        if direction == "higher":
            limit = b * band / tolerance_scale
            ok = c >= limit
        else:
            limit = b * band * tolerance_scale
            ok = c <= limit
        if not ok:
            failures += 1
        rows.append(
            {
                "metric": path,
                "direction": direction,
                "baseline": b,
                "current": c,
                "limit": round(limit, 4),
                "ratio": round(c / b, 4),
                "ok": ok,
            }
        )
    return {
        "verdict": "fail" if failures else "pass",
        "failures": failures,
        "compared": len(rows),
        "toleranceScale": tolerance_scale,
        **({"configMismatch": mismatches} if mismatches else {}),
        "metrics": rows,
    }


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="pinot_tpu-perf-gate", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("current", help="fresh bench.py JSON (file or - for stdin)")
    p.add_argument(
        "--baseline",
        default=None,
        help="document to gate against (required for a default-mode "
        f"bench.py document; defaults to {SERVING_DEFAULT_BASELINE} for a "
        f"serving-mode document, {MULTICHIP_DEFAULT_BASELINE} for a "
        "multichip-mode document, and so on per kind)",
    )
    p.add_argument(
        "--tolerance-scale",
        type=float,
        default=float(os.environ.get("PINOT_TPU_PERF_GATE_SCALE", "1.0")),
        help="widen every band multiplicatively (noisy CI)",
    )
    p.add_argument(
        "--allow-config-mismatch",
        action="store_true",
        help="compare even when workload size/platform differ",
    )
    args = p.parse_args(argv)
    try:
        current = load_bench(args.current)
        baseline_path = args.baseline
        if baseline_path is None:
            # default baseline follows the current document's kind
            baseline_path = {
                "serving": SERVING_DEFAULT_BASELINE,
                "multichip": MULTICHIP_DEFAULT_BASELINE,
                "join": JOIN_DEFAULT_BASELINE,
                "ingest": INGEST_DEFAULT_BASELINE,
                "restart": RESTART_DEFAULT_BASELINE,
                "filtermatrix": FILTERMATRIX_DEFAULT_BASELINE,
                "tiered": TIERED_DEFAULT_BASELINE,
                "audit": AUDIT_DEFAULT_BASELINE,
                "dr": DR_DEFAULT_BASELINE,
            }.get(_doc_kind(current))
            if baseline_path is None:
                raise ValueError(
                    "a default-mode bench.py document has no committed "
                    "baseline: pass --baseline"
                )
        baseline = load_bench(baseline_path)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(json.dumps({"verdict": "error", "error": str(e)}), file=sys.stderr)
        return 2
    out = compare(
        baseline,
        current,
        tolerance_scale=max(args.tolerance_scale, 1e-9),
        allow_config_mismatch=args.allow_config_mismatch,
    )
    print(json.dumps(out, indent=1))
    return 1 if out["verdict"] == "fail" else 0


if __name__ == "__main__":
    raise SystemExit(main())
