"""On-device TPU-platform correctness gate.

Run with::

    PINOT_TPU_TESTS=tpu python -m pytest tests/test_tpu_platform.py -m tpu -q

All other test files run on the virtual CPU mesh in float64; this file
runs the engine on the REAL chip in its production float32 config and
asserts device results match the host oracle within accumulation
tolerance — the check that catches f32 drift at scale, which the
CPU/x64 suite cannot.  The engine heals a device failure by answering
from the host, which would pass every comparison here, so each query
also has to show that no segment took the host path and nothing healed.
Asked for without a TPU, the gate fails; it does not skip.
"""
import json
import os

import numpy as np
import pytest

pytestmark = pytest.mark.tpu

if os.environ.get("PINOT_TPU_TESTS") != "tpu":
    pytest.skip(
        "TPU gate runs via PINOT_TPU_TESTS=tpu pytest -m tpu", allow_module_level=True
    )

import jax

if jax.devices()[0].platform != "tpu":
    raise RuntimeError(
        f"PINOT_TPU_TESTS=tpu asked for the on-device gate, but jax came up on "
        f"{jax.devices()[0].platform!r} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
    )

from pinot_tpu.engine.executor import QueryExecutor as _QueryExecutor
from pinot_tpu.engine.reduce import reduce_to_response
from pinot_tpu.pql import optimize_request, parse_pql
from pinot_tpu.tools.datagen import lineitem_schema, synthetic_lineitem_segment
from pinot_tpu.tools.scan_engine import ScanQueryProcessor



class QueryExecutor(_QueryExecutor):
    """The executor under test, held to the device: a reply any segment
    of which came from the host path, or after any healing, fails."""

    def execute(self, segments, request, deadline=None):
        res = super().execute(segments, request, deadline)
        assert not res.cost.get("segmentsHost"), ("host path served", res.cost)
        healed = {k: v for k, v in self.healing_stats().items() if v}
        assert not healed, ("device path healed", healed)
        return res


ROWS_PER_SEGMENT = int(os.environ.get("PINOT_TPU_GATE_ROWS", "250000"))
NUM_SEGMENTS = 3
RTOL = 1e-4  # f32 pairwise-tree accumulation over ~1M rows


@pytest.fixture(scope="module")
def cluster():
    segs = [
        synthetic_lineitem_segment(ROWS_PER_SEGMENT, seed=41 + i, name=f"tli{i}")
        for i in range(NUM_SEGMENTS)
    ]
    rows = [r for s in segs for r in s.rows()]
    oracle = ScanQueryProcessor(lineitem_schema(), rows)
    return segs, oracle


QUERIES = [
    "SELECT count(*) FROM lineitem",
    "SELECT sum(l_quantity), sum(l_extendedprice), min(l_discount), max(l_tax), avg(l_quantity) FROM lineitem",
    "SELECT sum(l_quantity), count(*) FROM lineitem WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag, l_linestatus TOP 10",
    "SELECT sum(l_extendedprice) FROM lineitem WHERE l_shipmode IN ('RAIL','FOB') GROUP BY l_shipmode TOP 10",
    "SELECT count(*) FROM lineitem WHERE l_shipdate BETWEEN '1994-01-01' AND '1994-06-30'",
    "SELECT distinctcount(l_shipmode), percentile50(l_quantity) FROM lineitem",
    "SELECT distinctcounthll(l_shipdate) FROM lineitem",
    "SELECT minmaxrange(l_extendedprice) FROM lineitem GROUP BY l_returnflag TOP 10",
    # selective point query: exercises the zone-map block path on-device
    "SELECT sum(l_extendedprice), count(*) FROM lineitem WHERE l_shipdate = '1995-06-14'",
]


def _close(a, b, rtol):
    if isinstance(a, dict) and isinstance(b, dict):
        return set(a) == set(b) and all(_close(a[k], b[k], rtol) for k in a)
    if isinstance(a, list) and isinstance(b, list):
        return len(a) == len(b) and all(_close(x, y, rtol) for x, y in zip(a, b))
    if isinstance(a, str) and isinstance(b, str):
        try:
            fa, fb = float(a), float(b)
        except ValueError:
            return a == b
        return abs(fa - fb) <= rtol * max(1.0, abs(fa), abs(fb))
    return a == b


@pytest.mark.parametrize("pql", QUERIES)
def test_device_matches_oracle_f32(cluster, pql):
    segs, oracle = cluster
    req = optimize_request(parse_pql(pql))
    req2 = optimize_request(parse_pql(pql))
    got = reduce_to_response(req, [QueryExecutor().execute(segs, req)]).to_json()
    want = oracle.execute(req2).to_json()
    # HLL is an estimator: identical registers either way, compare exact
    rtol = RTOL
    assert _close(got["aggregationResults"], want["aggregationResults"], rtol), (
        pql,
        json.dumps(got["aggregationResults"], default=str)[:500],
        json.dumps(want["aggregationResults"], default=str)[:500],
    )


def test_single_chip_mesh_shard_map(cluster):
    """The shard_map collective path on the real chip (mesh size 1 —
    the degenerate but on-device case of the multichip program)."""
    from pinot_tpu.parallel.multichip import default_mesh

    segs, oracle = cluster
    mesh = default_mesh(jax.devices()[:1])
    pql = "SELECT sum(l_quantity) FROM lineitem GROUP BY l_returnflag TOP 10"
    req = optimize_request(parse_pql(pql))
    req2 = optimize_request(parse_pql(pql))
    got = reduce_to_response(req, [QueryExecutor(mesh=mesh).execute(segs, req)]).to_json()
    want = oracle.execute(req2).to_json()
    assert _close(got["aggregationResults"], want["aggregationResults"], RTOL)


def test_selection_order_by_on_device(cluster):
    segs, oracle = cluster
    pql = "SELECT l_shipdate, l_quantity FROM lineitem ORDER BY l_quantity DESC, l_shipdate LIMIT 10"
    req = optimize_request(parse_pql(pql))
    req2 = optimize_request(parse_pql(pql))
    got = reduce_to_response(req, [QueryExecutor().execute(segs, req)]).to_json()
    want = oracle.execute(req2).to_json()
    assert got["selectionResults"] == want["selectionResults"]


def test_sum_accumulation_at_bench_scale():
    """f32 accumulation drift at the north-star scale:
    SUM/AVG and the group-by matmul SUM over >=100M rows vs an EXACT
    f64 oracle computed from dictionary bincounts (sum = sum_d count_d
    * value_d — no row scan, so the oracle itself carries no float
    error).  The reference aggregates in double everywhere
    (DoubleAggregationResultHolder); rtol here states how close the
    f32 device path gets at scale."""
    rows_per = int(os.environ.get("PINOT_TPU_SCALE_ROWS", str(8_388_608)))
    nseg = int(os.environ.get("PINOT_TPU_SCALE_SEGMENTS", "16"))
    RTOL_SCALE = 1e-5

    segs = [
        synthetic_lineitem_segment(rows_per, seed=61 + i, name=f"sc{i}")
        for i in range(nseg)
    ]
    # exact per-returnflag and total sums of l_extendedprice in f64
    total_sum = 0.0
    total_cnt = 0
    group_sums: dict = {}
    for s in segs:
        price = s.column("l_extendedprice")
        rf = s.column("l_returnflag")
        vals = np.asarray(price.dictionary.values, dtype=np.float64)
        card = price.dictionary.cardinality
        combined = rf.fwd.astype(np.int64) * card + price.fwd
        counts = np.bincount(
            combined, minlength=rf.dictionary.cardinality * card
        ).reshape(rf.dictionary.cardinality, card)
        per_rf = counts @ vals
        for local_id in range(rf.dictionary.cardinality):
            key = str(rf.dictionary.get(local_id))
            group_sums[key] = group_sums.get(key, 0.0) + float(per_rf[local_id])
        total_sum += float(per_rf.sum())
        total_cnt += s.num_docs
    assert total_cnt == rows_per * nseg

    ex = QueryExecutor()
    req = optimize_request(
        parse_pql(
            "SELECT sum(l_extendedprice), avg(l_extendedprice), count(*) FROM lineitem"
        )
    )
    got = reduce_to_response(req, [ex.execute(segs, req)]).to_json()
    g = got["aggregationResults"]
    # counts cross the segment axis as integers (config.row_count_dtype):
    # exact, where a float32 sum would stop being exact at 2^24
    assert int(float(g[2]["value"])) == total_cnt
    gsum, gavg = float(g[0]["value"]), float(g[1]["value"])
    assert abs(gsum - total_sum) <= RTOL_SCALE * abs(total_sum), (
        "scalar SUM drift", gsum, total_sum, abs(gsum - total_sum) / abs(total_sum),
    )
    want_avg = total_sum / total_cnt
    assert abs(gavg - want_avg) <= RTOL_SCALE * abs(want_avg)

    # group-by path: the one-hot MATMUL accumulation (MXU) at scale
    req2 = optimize_request(
        parse_pql(
            "SELECT sum(l_extendedprice) FROM lineitem GROUP BY l_returnflag TOP 10"
        )
    )
    got2 = reduce_to_response(req2, [ex.execute(segs, req2)]).to_json()
    rows = got2["aggregationResults"][0]["groupByResult"]
    assert len(rows) == len(group_sums)
    for row in rows:
        key = row["group"][0]
        want = group_sums[key]
        have = float(row["value"])
        assert abs(have - want) <= RTOL_SCALE * abs(want), (
            "group SUM drift", key, have, want, abs(have - want) / abs(want),
        )


def test_sort_pairs_distinct_on_device(cluster, monkeypatch):
    """High-cardinality exact distinct/percentile through the on-chip
    sort-dedup path (pair lexsort + stable compaction on the REAL
    chip's sort implementation); distinct counts are exact integers, so
    no float tolerance applies."""
    from pinot_tpu.engine import config as cfg
    from pinot_tpu.engine import kernel as kernel_mod

    segs, oracle = cluster
    monkeypatch.setattr(cfg, "MAX_VALUE_STATE", 1 << 10)
    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")
    kernel_mod.make_table_kernel.cache_clear()
    kernel_mod.make_packed_table_kernel.cache_clear()
    try:
        for pql in (
            "SELECT distinctcount(l_extendedprice) FROM lineitem GROUP BY l_returnflag TOP 10",
            "SELECT percentile50(l_extendedprice) FROM lineitem",
        ):
            req = optimize_request(parse_pql(pql))
            req2 = optimize_request(parse_pql(pql))
            got = reduce_to_response(req, [QueryExecutor().execute(segs, req)]).to_json()
            want = oracle.execute(req2).to_json()
            assert _close(got["aggregationResults"], want["aggregationResults"], RTOL), (
                pql,
                json.dumps(got["aggregationResults"], default=str)[:400],
                json.dumps(want["aggregationResults"], default=str)[:400],
            )
    finally:
        kernel_mod.make_table_kernel.cache_clear()
        kernel_mod.make_packed_table_kernel.cache_clear()


def test_repeated_query_uses_input_cache_on_device(cluster):
    """A repeated identical query reuses device-resident inputs (the
    q-input LRU) and MUST return bit-identical results — validates the
    cache keying on the real chip."""
    segs, _ = cluster
    ex = QueryExecutor()
    pql = (
        "SELECT sum(l_quantity), count(*) FROM lineitem "
        "WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag TOP 10"
    )
    req = optimize_request(parse_pql(pql))
    first = reduce_to_response(req, [ex.execute(segs, req)]).to_json()
    assert len(ex._qinput_cache) >= 1  # populated by the first run
    second = reduce_to_response(req, [ex.execute(segs, req)]).to_json()
    assert first["aggregationResults"] == second["aggregationResults"]
    # a DIFFERENT literal must miss the cache and answer differently
    req3 = optimize_request(parse_pql(pql.replace("1998-09-02", "1994-01-01")))
    third = reduce_to_response(req3, [ex.execute(segs, req3)]).to_json()
    assert third["aggregationResults"] != first["aggregationResults"]
