"""Micro benchmarks for individual engine components.

The pinot-perf JMH analog — one entry per reference benchmark class:

  bitpack    -> ForwardIndexReaderBenchmark.java:42 (fixed-bit codec)
  dictionary -> StringDictionaryPerfTest.java:46 (lookup throughput)
  filter     -> FilterOperatorBenchmark.java:51 (predicate over a segment)
  groupby    -> BenchmarkQueryEngine.java:50 (aggregation group-by kernel)
  realtime   -> BenchmarkRealtimeConsumptionSpeed.java:38 (index() rate)
  csv        -> ingest pipeline (columnar vs row-wise build)

Run: ``python -m pinot_tpu.tools.microbench [name ...] [-rows N]``.
Each benchmark prints one JSON line: {"bench", "value", "unit", detail}.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Dict, List

import numpy as np


def _time_best(fn: Callable[[], object], repeat: int = 5) -> float:
    """Best-of-N wall seconds (JMH SampleTime-ish, minus the forks)."""
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_bitpack(rows: int) -> Dict:
    from pinot_tpu.segment.bitpack import bits_required, pack_bits, unpack_bits

    rng = np.random.default_rng(7)
    vals = rng.integers(0, 4097, size=rows).astype(np.int32)
    nbits = bits_required(4097)
    packed = pack_bits(vals, nbits)
    t_pack = _time_best(lambda: pack_bits(vals, nbits))
    t_unpack = _time_best(lambda: unpack_bits(packed, nbits, rows))
    return {
        "bench": "bitpack",
        "value": round(rows / t_unpack / 1e6, 1),
        "unit": "M vals/s unpack",
        "detail": {"packMps": round(rows / t_pack / 1e6, 1), "nbits": nbits},
    }


def bench_dictionary(rows: int) -> Dict:
    from pinot_tpu.common.schema import DataType
    from pinot_tpu.segment.dictionary import Dictionary

    rng = np.random.default_rng(11)
    card = 100_000
    values = [f"value_{i:08d}" for i in range(card)]
    d = Dictionary(DataType.STRING, values)
    probe = [values[i] for i in rng.integers(0, card, size=10_000)]
    t_lookup = _time_best(lambda: [d.index_of(v) for v in probe])
    arr = np.asarray(
        [values[i] for i in rng.integers(0, card, size=rows)], dtype=object
    )
    t_index = _time_best(lambda: d.index_array(arr))
    return {
        "bench": "dictionary",
        "value": round(len(probe) / t_lookup / 1e3, 1),
        "unit": "K lookups/s",
        "detail": {"indexArrayMps": round(rows / t_index / 1e6, 2), "card": card},
    }


def _engine_fixture(rows: int):
    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.segment.columnar import build_segment_from_columns
    from pinot_tpu.tools.datagen import make_test_schema

    rng = np.random.default_rng(13)
    schema = make_test_schema(with_mv=False)
    cols = {
        "dimStr": np.asarray(
            [f"s{i}" for i in rng.integers(0, 50, size=rows)], dtype=object
        ),
        "dimInt": rng.integers(0, 1000, size=rows).astype(np.int32),
        "dimLong": rng.integers(0, 10_000, size=rows).astype(np.int64),
        "metInt": rng.integers(0, 10_000, size=rows).astype(np.int32),
        "metFloat": rng.random(rows, dtype=np.float32),
        "metDouble": rng.random(rows, dtype=np.float64),
        "daysSinceEpoch": rng.integers(17000, 17100, size=rows).astype(np.int32),
    }
    seg = build_segment_from_columns(schema, cols, rows, "mb", "mb0")
    return QueryExecutor(), [seg]


def _bench_query(executor, segments, pql: str, rows: int, name: str) -> Dict:
    from pinot_tpu.pql import parse_pql

    req = parse_pql(pql)
    executor.execute(segments, req)  # compile / warm
    t = _time_best(lambda: executor.execute(segments, req))
    return {
        "bench": name,
        "value": round(rows / t / 1e6, 1),
        "unit": "M rows/s",
        "detail": {"medianMs": round(t * 1000, 3), "pql": pql},
    }


def bench_filter(rows: int) -> Dict:
    ex, segs = _engine_fixture(rows)
    return _bench_query(
        ex,
        segs,
        "SELECT count(*) FROM testTable WHERE dimInt > 100 AND dimInt <= 900",
        rows,
        "filter",
    )


def bench_groupby(rows: int) -> Dict:
    ex, segs = _engine_fixture(rows)
    return _bench_query(
        ex,
        segs,
        "SELECT sum(metInt), max(metDouble) FROM testTable GROUP BY dimStr TOP 10",
        rows,
        "groupby",
    )


def bench_realtime(rows: int) -> Dict:
    from pinot_tpu.realtime.mutable import MutableSegment
    from pinot_tpu.tools.datagen import make_test_schema, random_rows

    schema = make_test_schema(with_mv=False)
    data = random_rows(schema, min(rows, 200_000), seed=5)

    def consume():
        # consumers fetch in batches (netstream/kafka fetch sizes);
        # index_batch is the production ingest call
        seg = MutableSegment(schema, "rt0", "rt")
        for i in range(0, len(data), 500):
            seg.index_batch(data[i : i + 500])
        return seg

    t = _time_best(consume, repeat=3)
    return {
        "bench": "realtime",
        "value": round(len(data) / t / 1e3, 1),
        "unit": "K rows/s indexed",
        "detail": {"rows": len(data)},
    }


def bench_csv(rows: int) -> Dict:
    import os
    import tempfile

    from pinot_tpu.segment.columnar import build_segment_from_csv
    from pinot_tpu.tools.datagen import make_test_schema, random_rows

    schema = make_test_schema(with_mv=False)
    data = random_rows(schema, rows, seed=3)
    names = [s.name for s in schema.all_fields()]
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "d.csv")
        with open(path, "w") as f:
            f.write(",".join(names) + "\n")
            for row in data:
                f.write(",".join(str(row[n]) for n in names) + "\n")
        t = _time_best(lambda: build_segment_from_csv(schema, path, "t", "b"), repeat=3)
    return {
        "bench": "csv",
        "value": round(rows / t / 1e3, 1),
        "unit": "K rows/s ingested",
        "detail": {"rows": rows},
    }


BENCHES: Dict[str, Callable[[int], Dict]] = {
    "bitpack": bench_bitpack,
    "dictionary": bench_dictionary,
    "filter": bench_filter,
    "groupby": bench_groupby,
    "realtime": bench_realtime,
    "csv": bench_csv,
}


def run(names: List[str], rows: int) -> List[Dict]:
    out = []
    for name in names:
        out.append(BENCHES[name](rows))
        print(json.dumps(out[-1]), flush=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser("pinot_tpu-microbench")
    ap.add_argument("benches", nargs="*", default=[], help=f"subset of {list(BENCHES)}")
    ap.add_argument("-rows", type=int, default=1_000_000)
    args = ap.parse_args()
    names = args.benches or list(BENCHES)
    for n in names:
        if n not in BENCHES:
            raise SystemExit(f"unknown bench {n!r}; choose from {list(BENCHES)}")
    run(names, args.rows)




def bench_staging_ab(rows: int) -> Dict:
    """A/B the agg-column staging policy on the current backend: narrow
    fwd + in-kernel dictionary gather vs dictionary-decoded float raw
    stream, on the TPC-H-Q1 kernel shape.  Run on the real chip to pick
    RAW_CARD_MIN (config.py); the gather's VMEM-table cost vs the raw
    stream's 2-4x HBM bytes is hardware-dependent."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pinot_tpu.engine.context import get_table_context
    from pinot_tpu.engine.device import segment_arrays, stage_segments
    from pinot_tpu.engine.kernel import make_table_kernel
    from pinot_tpu.engine.plan import build_query_inputs, build_static_plan
    from pinot_tpu.pql import optimize_request, parse_pql
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    segs = [synthetic_lineitem_segment(rows, seed=31 + i, name=f"ab{i}") for i in range(2)]
    pql = ("SELECT sum(l_quantity), sum(l_extendedprice), sum(l_discount), count(*) "
           "FROM lineitem WHERE l_shipdate <= '1998-09-02' "
           "GROUP BY l_returnflag, l_linestatus TOP 10")
    request = optimize_request(parse_pql(pql))
    ctx = get_table_context(segs)
    needed = sorted(set(request.referenced_columns()))

    def run_mode(raw_cols):
        staged = stage_segments(
            segs, needed, raw_columns=raw_cols,
            gfwd_columns=("l_returnflag", "l_linestatus"), ctx=ctx,
        )
        plan = build_static_plan(request, ctx, staged)
        q = build_query_inputs(request, plan, ctx, staged)

        def conv(x):
            if isinstance(x, np.ndarray):
                return jnp.asarray(x)
            if isinstance(x, list):
                return [conv(v) for v in x]
            if isinstance(x, dict):
                return {k: conv(v) for k, v in x.items()}
            return x

        qi = conv(q)
        arrays = segment_arrays(staged, needed)
        kernel = make_table_kernel(plan)
        # sync via device_get of the FULL output tree: the host needs
        # the values anyway, and a D2H transfer of every leaf is a
        # barrier no aliased pass-through leaf (num_docs) can satisfy
        # early.  The stream is FIFO, so fetching the last dispatch
        # covers all.
        jax.device_get(kernel(arrays, qi))  # compile
        n = 10
        out = None
        t0 = time.perf_counter()
        for _ in range(n):
            out = kernel(arrays, qi)
        jax.device_get(out)
        return (time.perf_counter() - t0) / n * 1000

    gather_ms = run_mode(())
    raw_ms = run_mode(("l_quantity", "l_extendedprice", "l_discount"))
    total = rows * 2
    return {
        "name": "staging_ab_q1",
        "rows": total,
        "gather_ms": round(gather_ms, 3),
        "raw_ms": round(raw_ms, 3),
        "gather_rows_per_sec": round(total / (gather_ms / 1000), 1),
        "raw_rows_per_sec": round(total / (raw_ms / 1000), 1),
    }


BENCHES["staging_ab"] = bench_staging_ab



def bench_pallas_ab(rows: int) -> Dict:
    """Pallas fused Q1 kernel vs the production XLA table kernel on one
    segment (commit the wiring decision with data).

    Both sides read the same arrays: interval filter on the date fwd,
    three raw float32 value feeds, 12-bucket one-hot matmul group-by.
    The XLA side is the actual serving kernel (make_table_kernel); the
    pallas side is engine/pallas_kernels.fused_filtered_groupby_sums,
    compiled for the TPU — this bench needs the chip.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pinot_tpu.engine.context import get_table_context
    from pinot_tpu.engine.device import segment_arrays, stage_segments
    from pinot_tpu.engine.kernel import make_table_kernel
    from pinot_tpu.engine.pallas_kernels import fused_filtered_groupby_sums
    from pinot_tpu.engine.plan import build_query_inputs, build_static_plan
    from pinot_tpu.pql import optimize_request, parse_pql
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    seg = synthetic_lineitem_segment(rows, seed=41, name="pab0")
    pql = ("SELECT sum(l_quantity), sum(l_extendedprice), sum(l_discount), count(*) "
           "FROM lineitem WHERE l_shipdate <= '1998-09-02' "
           "GROUP BY l_returnflag, l_linestatus TOP 10")
    request = optimize_request(parse_pql(pql))
    ctx = get_table_context([seg])
    needed = sorted(set(request.referenced_columns()))
    agg_cols = ("l_quantity", "l_extendedprice", "l_discount")
    staged = stage_segments(
        [seg], needed, raw_columns=agg_cols,
        gfwd_columns=("l_returnflag", "l_linestatus"), ctx=ctx,
    )
    plan = build_static_plan(request, ctx, staged)
    q = build_query_inputs(request, plan, ctx, staged)

    def timed(fn, n=10):
        jax.device_get(fn())  # compile; D2H of the result is the barrier
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn()
        jax.device_get(out)
        return (time.perf_counter() - t0) / n * 1000

    # XLA side: the serving kernel
    from pinot_tpu.engine.device import to_device_inputs

    qi = to_device_inputs(q)
    arrays = segment_arrays(staged, needed)
    xla_kernel = make_table_kernel(plan)
    xla_ms = timed(lambda: xla_kernel(arrays, qi))

    # pallas side: same arrays, fused single pass
    fwd = jnp.asarray(staged.columns["l_shipdate"].fwd[0].astype(np.int32))
    lo, hi = (int(v) for v in np.asarray(q["bounds"][0][0]))
    valid = jnp.ones(rows, dtype=bool)
    rf = staged.columns["l_returnflag"].gfwd[0].astype(np.int32)
    ls = staged.columns["l_linestatus"].gfwd[0].astype(np.int32)
    ls_card = ctx.column("l_linestatus").global_cardinality
    keys = jnp.asarray(rf * ls_card + ls)
    raws = [jnp.asarray(staged.columns[c].raw[0]) for c in agg_cols]
    capacity = ctx.column("l_returnflag").global_cardinality * ls_card

    fused = jax.jit(
        lambda f, v, k, r0, r1, r2: fused_filtered_groupby_sums(
            f, None, v, k, [None] * 3, [None] * 3, capacity,
            filter_bounds=(lo, hi), value_raws=[r0, r1, r2],
        )
    )
    pallas_ms = timed(lambda: fused(fwd, valid, keys, *raws))

    # cross-check: both paths must agree on matched docs and the total
    # grouped count before the timing comparison means anything
    xo = jax.device_get(xla_kernel(arrays, qi))
    po = jax.device_get(fused(fwd, valid, keys, *raws))
    pallas_docs = float(po[0])
    xla_docs = float(np.asarray(xo["num_docs"]).sum())
    agree = abs(pallas_docs - xla_docs) < 0.5 and abs(
        float(np.asarray(po[1]).sum()) - pallas_docs
    ) < 0.5

    return {
        "name": "pallas_ab_q1",
        "rows": rows,
        "xla_ms": round(xla_ms, 3),
        "pallas_ms": round(pallas_ms, 3),
        "xla_rows_per_sec": round(rows / (xla_ms / 1000), 1),
        "pallas_rows_per_sec": round(rows / (pallas_ms / 1000), 1),
        "matched_docs": pallas_docs,
        "paths_agree": bool(agree),
    }


BENCHES["pallas_ab"] = bench_pallas_ab


def bench_qinput_cache_ab(rows: int) -> Dict:
    """Per-query serving cost with vs without the device-resident
    query-input cache (executor._qinput_cache): the upload it skips is
    one host->device transfer per query.  Runs
    the SAME Q1-shaped query through the executor repeatedly, once with
    the cache cleared before every query and once warm."""
    import time as _time

    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.engine.reduce import reduce_to_response
    from pinot_tpu.pql import optimize_request, parse_pql
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    seg_rows = max(rows // 4, 1)
    segments = [
        synthetic_lineitem_segment(seg_rows, seed=61 + i, name=f"qc{i}")
        for i in range(4)
    ]
    pql = (
        "SELECT sum(l_quantity), sum(l_extendedprice), count(*) FROM lineitem "
        "WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag, l_linestatus TOP 10"
    )
    ex = QueryExecutor()

    def one() -> None:
        req = optimize_request(parse_pql(pql))
        reduce_to_response(req, [ex.execute(segments, req)])

    one()  # stage + compile
    n = 15

    # cold first, then warm, then a second cold pass — reporting the
    # BEST cold so steady-state drift can't masquerade as cache effect
    def cold_pass() -> float:
        t0 = _time.perf_counter()
        for _ in range(n):
            ex._qinput_cache.clear()
            ex._qinput_cache_bytes = 0
            one()
        return (_time.perf_counter() - t0) / n * 1000

    c1 = cold_pass()
    t0 = _time.perf_counter()
    for _ in range(n):
        one()
    warm_ms = (_time.perf_counter() - t0) / n * 1000
    cold_ms = min(c1, cold_pass())

    return {
        "bench": "qinput_cache_ab",
        "value": round(cold_ms - warm_ms, 3),
        "unit": "ms saved/query",
        "detail": {
            "rows": seg_rows * 4,
            "warm_ms_per_query": round(warm_ms, 3),
            "cold_ms_per_query": round(cold_ms, 3),
        },
    }


BENCHES["qinput_cache_ab"] = bench_qinput_cache_ab




def bench_hll_lowerings(rows: int) -> Dict:
    """A/B the grouped-HLL lowerings at the north-star register shape
    (capacity 1024, HLL_M=256): the r4 serialized scatter-max vs the r5
    packed int32 sort + searchsorted run-max (tools/probe_hll_e2e.py
    measured 12.4 vs 4.2 ns/row on v5e), plus the factored one-hot
    contraction vs the old M=1 form at the bench presence shape
    (K=2^14: 31.5 vs 0.8 ns/row on v5e).  Verifies bit-identical
    registers between scatter and sort."""
    import jax
    import jax.numpy as jnp

    from pinot_tpu.engine import config as engine_config
    from pinot_tpu.engine.kernel import _reduce_hll_sort, _value_state_counts

    rng = np.random.default_rng(3)
    cap, m = 1024, engine_config.HLL_M
    gid = rng.integers(0, cap, size=rows).astype(np.int32)
    bucket = rng.integers(0, m, size=rows).astype(np.int32)
    rho = np.minimum(1 + rng.geometric(0.5, size=rows), 40).astype(np.int32)
    packed = jnp.asarray(((gid * m + bucket) << 6) | rho)
    flat = jnp.asarray(gid * m + bucket)
    rho_u8 = jnp.asarray(rho.astype(np.uint8))

    def fetch(x):
        np.asarray(x)

    def scatter(fl, rh):
        return jnp.zeros(cap * m, jnp.uint8).at[fl].max(rh, mode="drop").reshape(cap, m)

    f_sort = jax.jit(lambda p: _reduce_hll_sort(p, cap))
    f_scat = jax.jit(scatter)
    fetch(f_sort(packed))
    fetch(f_scat(flat, rho_u8))
    t_sort = _time_best(lambda: fetch(f_sort(packed)))
    t_scat = _time_best(lambda: fetch(f_scat(flat, rho_u8)))
    identical = bool(
        (np.asarray(f_sort(packed)) == np.asarray(f_scat(flat, rho_u8))).all()
    )

    K = 1 << 14  # bench presence shape
    idx = jnp.asarray(rng.integers(0, K, size=rows).astype(np.int32))
    # time the XLA body DIRECTLY (bypassing the env gate) so the A/B
    # keeps its baseline even when PINOT_TPU_VALUE_STATE_PALLAS=1
    from pinot_tpu.engine.kernel import _value_state_counts_xla

    f_fac = jax.jit(lambda i: _value_state_counts_xla(i, K))
    fetch(f_fac(idx))
    t_fac = _time_best(lambda: fetch(f_fac(idx)))
    from pinot_tpu.engine.kernel import _value_state_counts_pallas

    f_pal = jax.jit(lambda i: _value_state_counts_pallas(i, K))
    fetch(f_pal(idx))
    t_pal = _time_best(lambda: fetch(f_pal(idx)))
    pallas_agrees = bool((np.asarray(f_pal(idx)) == np.asarray(f_fac(idx))).all())

    return {
        "bench": "hll_lowerings",
        "value": round(t_scat / max(t_sort, 1e-9), 2),
        "unit": "x sort-vs-scatter speedup",
        "detail": {
            "rows": rows,
            "sort_ms": round(t_sort * 1e3, 2),
            "scatter_ms": round(t_scat * 1e3, 2),
            "factored_contraction_K16384_ms": round(t_fac * 1e3, 2),
            "pallas_contraction_K16384_ms": round(t_pal * 1e3, 2),
            "pallas_agrees": pallas_agrees,
            "registers_bit_identical": identical,
            "platform": jax.devices()[0].platform,
        },
    }


BENCHES["hll_lowerings"] = bench_hll_lowerings


def bench_groupby_lowerings(rows: int) -> Dict:
    """The crossover that sets ``kernel.RADIX_GROUP_CAP``: Q3's shape
    (occupancy + one float32 sum over shuffled keys) on the serialised
    scatter against the two-level contraction, K = 2^11 to 2^18, in ns
    a row, with the contraction's widest relative gap from a float64
    sum (v5e, PR 26: 13.6 against 0.15, 0.76, 2.87, 11.33).  On the
    chip; the CPU runs the contraction in the Pallas interpreter."""
    import jax
    import jax.numpy as jnp

    from pinot_tpu.engine.kernel import _segment_add_radix

    rng = np.random.default_rng(26)
    prices = (rng.integers(0, 16384, size=rows) * 6.37 + 901.13).astype(np.float32)
    sweep = {}
    for K in (1 << 11, 1 << 14, 1 << 16, 1 << 18):
        idx = rng.integers(0, K, size=rows).astype(np.int32)
        want = np.bincount(idx, weights=prices.astype(np.float64), minlength=K)

        def scatter(i, w, K=K):
            occupied = jnp.zeros(K, jnp.int32).at[i].max(jnp.ones_like(i), mode="drop")
            return occupied, jnp.zeros(K, jnp.float32).at[i].add(w, mode="drop")

        f_scatter = jax.jit(scatter)
        f_radix = jax.jit(lambda i, w, K=K: _segment_add_radix(i, [w], K))
        args = (jnp.asarray(idx), jnp.asarray(prices))
        got = np.asarray(f_radix(*args)[1], dtype=np.float64)
        jax.block_until_ready(f_scatter(*args))
        sweep[K] = {
            "scatter_ns_per_row": round(
                _time_best(lambda: jax.block_until_ready(f_scatter(*args)), 3) * 1e9 / rows, 4),
            "radix_ns_per_row": round(
                _time_best(lambda: jax.block_until_ready(f_radix(*args)), 3) * 1e9 / rows, 4),
            "radix_sum_gap": float(np.max(np.abs(got - want) / np.maximum(want, 1.0))),
        }
    at_bound = sweep[1 << 16]
    return {
        "bench": "groupby_lowerings",
        "value": round(at_bound["scatter_ns_per_row"] / max(at_bound["radix_ns_per_row"], 1e-9), 2),
        "unit": "x radix-vs-scatter at K=2^16",
        "detail": {"rows": rows, "sweep": sweep, "platform": jax.devices()[0].platform},
    }


BENCHES["groupby_lowerings"] = bench_groupby_lowerings


if __name__ == "__main__":
    main()
