"""Engine edge cases: host-fallback group-by at huge key spaces,
MV order-by selection, offsets, empty segments, trace spans."""
import pytest

from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.engine.reduce import reduce_to_response
from pinot_tpu.pql import optimize_request, parse_pql
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.tools.datagen import make_test_schema, random_rows
from pinot_tpu.tools.scan_engine import ScanQueryProcessor

EX = QueryExecutor()


def run_both(schema, rows, segments, pql):
    req_e = optimize_request(parse_pql(pql))
    req_o = optimize_request(parse_pql(pql))
    got = reduce_to_response(req_e, [EX.execute(segments, req_e)]).to_json()
    want = ScanQueryProcessor(schema, rows).execute(req_o).to_json()
    for k in ("timeUsedMs", "cost", "numEntriesScannedInFilter", "numEntriesScannedPostFilter",
              "numSegmentsQueried", "numServersQueried", "numServersResponded"):
        got.pop(k, None)
        want.pop(k, None)
    return got, want


@pytest.mark.parametrize("aggregate,on_device", [("max(m)", False), ("sum(m)", True)])
def test_host_fallback_huge_keyspace(aggregate, on_device):
    """Group-by key space above MAX_GROUP_CAPACITY: an aggregate with no
    run form (``max``) routes to the host hash path (the LONG_MAP_BASED
    analog) and stays correct; a sum stays on the device through the runs
    lowering (PR 43), with the same answer."""
    schema = Schema(
        "big",
        dimensions=[
            FieldSpec("a", DataType.INT),
            FieldSpec("b", DataType.INT),
            FieldSpec("c", DataType.INT),
        ],
        metrics=[FieldSpec("m", DataType.INT, FieldType.METRIC)],
    )
    # 150^3 = 3.4M > 2^20 capacity cap
    rows = random_rows(schema, 800, seed=3, cardinality=150)
    seg = build_segment(schema, rows, "big", "bigseg")

    from pinot_tpu.engine.context import get_table_context
    from pinot_tpu.engine.device import get_staged
    from pinot_tpu.engine.plan import build_static_plan

    pql = f"SELECT {aggregate} FROM big GROUP BY a, b, c TOP 10"
    req = parse_pql(pql)
    ctx = get_table_context([seg])
    staged = get_staged([seg], ["a", "b", "c", "m"])
    plan = build_static_plan(req, ctx, staged)
    assert plan.on_device == on_device  # confirms the fallback triggers, and for what

    got, want = run_both(schema, rows, [seg], pql)
    assert got == want


def test_wide_key_order_by_stays_on_device(monkeypatch):
    """ORDER BY whose composite-key radix product overflows the key dtype
    uses the multi-operand lexicographic lax.sort path on device (no host
    fallback), and matches the oracle exactly."""
    from pinot_tpu.engine import config
    from pinot_tpu.engine.context import get_table_context
    from pinot_tpu.engine.device import get_staged
    from pinot_tpu.engine.plan import build_static_plan

    monkeypatch.setattr(config, "max_key_space", lambda: 10)

    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, 400, seed=77)
    seg = build_segment(schema, rows, "testTable", "wideksel")

    pql = "SELECT dimStr, metInt FROM testTable ORDER BY dimInt, metInt DESC LIMIT 12"
    req = parse_pql(pql)
    ctx = get_table_context([seg])
    staged = get_staged([seg], ["dimStr", "metInt", "dimInt"])
    plan = build_static_plan(req, ctx, staged)
    assert plan.on_device
    assert plan.selection is not None and not plan.selection.packed

    got, want = run_both(schema, rows, [seg], pql)
    assert got == want


def test_mv_order_by_selection():
    schema = make_test_schema()
    rows = random_rows(schema, 300, seed=21)
    seg = build_segment(schema, rows, "testTable", "mvsel")
    got, want = run_both(
        schema, rows, [seg], "SELECT dimStr FROM testTable ORDER BY dimStrMV LIMIT 10"
    )
    assert got == want


def test_selection_offset_window():
    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, 200, seed=33)
    seg = build_segment(schema, rows, "testTable", "offsel")
    got, want = run_both(
        schema, rows, [seg],
        "SELECT dimInt FROM testTable ORDER BY metInt DESC LIMIT 15, 10",
    )
    assert got == want


def test_empty_segment_pruned():
    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, 100, seed=4)
    seg = build_segment(schema, rows, "testTable", "full")
    empty = build_segment(schema, [], "testTable", "empty")
    req = parse_pql("SELECT count(*) FROM testTable")
    resp = reduce_to_response(req, [EX.execute([seg, empty], req)])
    assert resp.num_docs_scanned == 100
    assert resp.total_docs == 100


def test_time_pruning_skips_segments():
    from pinot_tpu.common.schema import TimeFieldSpec

    schema = Schema(
        "tp",
        metrics=[FieldSpec("m", DataType.INT, FieldType.METRIC)],
        time_field=TimeFieldSpec("days", DataType.INT, time_unit="DAYS"),
    )
    seg_old = build_segment(schema, [{"m": 1, "days": d} for d in range(100, 110)], "tp", "old")
    seg_new = build_segment(schema, [{"m": 2, "days": d} for d in range(200, 210)], "tp", "new")
    req = parse_pql("SELECT count(*) FROM tp WHERE days BETWEEN 200 AND 205")
    res = EX.execute([seg_old, seg_new], req)
    assert res.num_segments_queried == 1  # old segment pruned by time range
    assert res.num_docs_scanned == 6


def test_trace_spans_attached():
    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, 50, seed=6)
    seg = build_segment(schema, rows, "testTable", "traceseg")
    from pinot_tpu.server.instance import ServerInstance
    from pinot_tpu.common.datatable import serialize_instance_request, deserialize_result

    server = ServerInstance("traceServer")
    server.add_segment("testTable", seg)
    payload = serialize_instance_request(
        1, "SELECT count(*) FROM testTable", "testTable", [], 10_000, trace=True
    )
    res = deserialize_result(server.handle_request(payload))
    assert "traceServer" in res.trace
    assert any(s["span"] == "planAndExecute" for s in res.trace["traceServer"])


def test_host_fallback_vectorized_matches_row_path():
    """The vectorized numpy hash group-by (LONG_MAP_BASED fast-path
    analog) produces the same response as the row-wise accumulator path
    over multiple segments, filters, and every vectorizable agg."""
    import pinot_tpu.engine.host_fallback as hf

    schema = Schema(
        "big",
        dimensions=[
            FieldSpec("a", DataType.INT),
            FieldSpec("b", DataType.STRING),
            FieldSpec("c", DataType.INT),
        ],
        metrics=[FieldSpec("m", DataType.INT, FieldType.METRIC),
                 FieldSpec("f", DataType.DOUBLE, FieldType.METRIC)],
    )
    rows = random_rows(schema, 1200, seed=9, cardinality=130)
    segs = [
        build_segment(schema, rows[:600], "big", "vseg0"),
        build_segment(schema, rows[600:], "big", "vseg1"),
    ]
    pql = (
        "SELECT count(*), sum(m), min(f), max(m), avg(f), minmaxrange(m) "
        "FROM big WHERE a > 100 GROUP BY a, b, c TOP 12"
    )

    from pinot_tpu.engine.context import get_table_context

    req = optimize_request(parse_pql(pql))
    ctx = get_table_context(segs)
    assert hf._vectorizable_groupby(req, segs, ctx)

    got, want = run_both(schema, rows, segs, pql)
    assert got == want

    # row path forced: MV group column is not vectorizable
    schema_mv = make_test_schema()
    req_mv = optimize_request(
        parse_pql("SELECT count(*) FROM testTable GROUP BY dimStrMV TOP 5")
    )
    rows_mv = random_rows(schema_mv, 50, seed=2)
    seg_mv = build_segment(schema_mv, rows_mv, "testTable", "mvseg")
    assert not hf._vectorizable_groupby(req_mv, [seg_mv], get_table_context([seg_mv]))


def test_host_fallback_vectorized_scale():
    """~300k rows x ~1M-key group-by completes through the vectorized
    fallback quickly (the row path takes minutes at this scale)."""
    import time

    import numpy as np

    from pinot_tpu.segment.dictionary import Dictionary
    from pinot_tpu.segment.immutable import (
        ColumnData,
        ColumnMetadata,
        ImmutableSegment,
        SegmentMetadata,
    )
    from pinot_tpu.common.schema import DataType as DT

    n = 300_000
    rng = np.random.default_rng(0)
    cols = {}
    for name, card in (("a", 1250), ("b", 1250), ("m", 500)):  # 1.56M keys > 2^20 cap
        d = Dictionary(DT.INT, np.arange(card))
        fwd = rng.integers(0, card, n).astype(np.int32)
        meta = ColumnMetadata(
            name=name, data_type=DT.INT,
            field_type=FieldType.METRIC if name == "m" else FieldType.DIMENSION,
            single_value=True, cardinality=card, total_docs=n,
            is_sorted=False, total_number_of_entries=n,
            min_value=0, max_value=card - 1,
        )
        cols[name] = ColumnData(metadata=meta, dictionary=d, fwd=fwd)
    smeta = SegmentMetadata(
        segment_name="huge", table_name="big", num_docs=n,
        columns={c.metadata.name: c.metadata for c in cols.values()},
    )
    seg = ImmutableSegment(metadata=smeta, columns=cols)
    smeta.crc = 1

    req = optimize_request(
        parse_pql("SELECT sum(m), count(*) FROM big GROUP BY a, b TOP 10")
    )
    t0 = time.perf_counter()
    res = EX.execute([seg], req)
    took = time.perf_counter() - t0
    assert res.num_docs_scanned == n
    resp = reduce_to_response(req, [res])
    top = resp.to_json()["aggregationResults"][0]["groupByResult"]
    assert len(top) == 10
    assert took < 10.0, f"vectorized fallback too slow: {took:.1f}s"


def test_chunked_kernel_matches_unchunked(monkeypatch):
    """Segment-axis chunking (PINOT_TPU_CHUNK_ROWS) combines chunk
    outputs into bit-identical results — the capacity path for tables
    whose per-row kernel temporaries exceed HBM in one dispatch."""
    import json

    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.engine.reduce import reduce_to_response
    from pinot_tpu.pql import optimize_request, parse_pql
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    segs = [synthetic_lineitem_segment(4096, seed=41 + i, name=f"ck{i}") for i in range(6)]
    queries = [
        "SELECT sum(l_quantity), count(*), min(l_discount), max(l_tax) FROM lineitem "
        "WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag TOP 10",
        "SELECT avg(l_extendedprice) FROM lineitem",
        "SELECT distinctcounthll(l_shipdate) FROM lineitem GROUP BY l_linestatus TOP 10",
    ]
    for pql in queries:
        req = optimize_request(parse_pql(pql))
        outs = {}
        for chunk_rows in ("0", "8192"):  # off vs 2-segment chunks
            monkeypatch.setenv("PINOT_TPU_CHUNK_ROWS", chunk_rows)
            r = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
            outs[chunk_rows] = json.dumps(
                r.to_json()["aggregationResults"], sort_keys=True
            )
        assert outs["0"] == outs["8192"], pql


def test_host_fallback_vectorized_distinct_matches_oracle():
    """Beyond-capacity group-bys with distinctcount/distinctcounthll
    take the vectorized (group, gid) pair-dedup host path (the per-row
    Python loop took ~30 min at 134M rows); results must match the
    scan oracle exactly."""
    import json

    from pinot_tpu.engine import config as _config
    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.engine.reduce import reduce_to_response
    from pinot_tpu.pql import optimize_request, parse_pql
    from pinot_tpu.tools.datagen import lineitem_schema, synthetic_lineitem_segment
    from pinot_tpu.tools.scan_engine import ScanQueryProcessor

    segs = [synthetic_lineitem_segment(6000, seed=61 + i, name=f"hf{i}") for i in range(3)]
    oracle = ScanQueryProcessor(lineitem_schema(), [r for s in segs for r in s.rows()])
    queries = [
        "SELECT distinctcount(l_shipdate), count(*) FROM lineitem GROUP BY l_extendedprice TOP 10",
        "SELECT distinctcounthll(l_quantity) FROM lineitem GROUP BY l_extendedprice TOP 10",
        "SELECT distinctcount(l_shipmode) FROM lineitem "
        "WHERE l_returnflag = 'R' GROUP BY l_extendedprice TOP 5",
    ]
    saved = _config.MAX_GROUP_CAPACITY
    _config.MAX_GROUP_CAPACITY = 64  # force the host fallback
    try:
        for pql in queries:
            req = optimize_request(parse_pql(pql))
            got = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
            want = oracle.execute(parse_pql(pql))
            assert json.dumps(got.to_json()["aggregationResults"], sort_keys=True) == \
                json.dumps(want.to_json()["aggregationResults"], sort_keys=True), pql
    finally:
        _config.MAX_GROUP_CAPACITY = saved


def test_docrange_filter_on_group_column_skips_base_correctly():
    """Regression for the skip_base x docrange interplay: a sorted
    column filtered by RANGE and ALSO used as the group key stages only
    its gfwd stream (base fwd/dict skipped), the leaf resolves via doc
    bounds, and results match the oracle."""
    import json

    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.engine.reduce import reduce_to_response
    from pinot_tpu.pql import optimize_request, parse_pql
    from pinot_tpu.tools.datagen import lineitem_schema, synthetic_lineitem_segment
    from pinot_tpu.tools.scan_engine import ScanQueryProcessor

    segs = [synthetic_lineitem_segment(5000, seed=81 + i, name=f"dr{i}") for i in range(2)]
    oracle = ScanQueryProcessor(lineitem_schema(), [r for s in segs for r in s.rows()])
    # l_shipdate is sorted in every synthetic segment -> docrange leaf;
    # grouping by the same column forces the gfwd role stream
    pql = (
        "SELECT count(*), sum(l_quantity) FROM lineitem "
        "WHERE l_shipdate >= '1995-01-01' GROUP BY l_shipdate TOP 7"
    )
    req = optimize_request(parse_pql(pql))
    got = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
    want = oracle.execute(parse_pql(pql))
    assert json.dumps(got.to_json()["aggregationResults"], sort_keys=True) == \
        json.dumps(want.to_json()["aggregationResults"], sort_keys=True)


def test_host_fallback_factorization_branches_match_oracle(monkeypatch):
    """Both group-key factorization branches of the vectorized host path
    (peak-RSS satellite): the DENSE presence+cumsum-rank branch engages
    only when the key space is small relative to the matched rows (its
    space-sized transients are now bool + int32, not two int64 arrays);
    a SPARSE key space takes np.unique whose footprint scales with rows.
    Responses must match the scan oracle on both."""
    from pinot_tpu.engine import config

    monkeypatch.setattr(config, "MAX_GROUP_CAPACITY", 64)  # force host path

    schema = Schema(
        "big",
        dimensions=[
            FieldSpec("a", DataType.INT),
            FieldSpec("b", DataType.INT),
            FieldSpec("c", DataType.INT),
        ],
        metrics=[FieldSpec("m", DataType.INT, FieldType.METRIC)],
    )
    rows = random_rows(schema, 1600, seed=13, cardinality=20)
    seg = build_segment(schema, rows, "big", "fseg")

    # dense: space = 20*20 = 400 <= 8 * ~1600 matched rows
    got, want = run_both(
        schema, rows, [seg],
        "SELECT count(*), sum(m) FROM big GROUP BY a, b TOP 10",
    )
    assert got == want

    # sparse: space = 20^3 = 8000 > 8 * (few matched rows)
    needle = rows[0]["a"]
    got, want = run_both(
        schema, rows, [seg],
        f"SELECT count(*), sum(m) FROM big WHERE a = {needle} GROUP BY a, b, c TOP 10",
    )
    assert got == want


def test_row_counts_cross_the_segment_axis_as_integers():
    """A float32 sum stops being exact at 2^24, so 16 segments of 8.4M
    rows answered count(*) one short on the chip.  Every row-count state
    (num_docs, count, avg's count, percentile histograms) must leave the
    single-segment kernel in the integer row-count dtype, grouped or not,
    so that the merges over segments, chunks and chips are integer sums."""
    import jax
    import jax.numpy as jnp

    from pinot_tpu.engine import config
    from pinot_tpu.engine.context import get_table_context
    from pinot_tpu.engine.device import segment_arrays, stage_segments
    from pinot_tpu.engine.kernel import make_single_segment_kernel
    from pinot_tpu.engine.plan import build_query_inputs, build_static_plan
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    cdt = jnp.dtype(config.row_count_dtype())
    assert jnp.issubdtype(cdt, jnp.integer)
    seg = synthetic_lineitem_segment(256, seed=3, name="cnt0")
    for group_by in ("", " GROUP BY l_returnflag TOP 10"):
        request = optimize_request(
            parse_pql(
                "SELECT count(*), avg(l_quantity), percentile50(l_quantity), "
                "sum(l_quantity) FROM lineitem WHERE l_quantity > 10" + group_by
            )
        )
        ctx = get_table_context([seg])
        needed = sorted(set(request.referenced_columns()))
        staged = stage_segments([seg], needed, ctx=ctx)
        plan = build_static_plan(request, ctx, staged)
        assert plan.on_device
        q = build_query_inputs(request, plan, ctx, staged)
        one = lambda tree: jax.tree_util.tree_map(lambda x: x[0], tree)
        out = jax.eval_shape(
            make_single_segment_kernel(plan),
            one(segment_arrays(staged, needed)),
            one(q),
        )
        prefix = "gb_" if group_by else "agg_"
        assert out["num_docs"].dtype == cdt
        assert out[f"{prefix}0"].dtype == cdt  # count(*)
        avg_sum, avg_count = out[f"{prefix}1"]
        assert avg_count.dtype == cdt and avg_sum.dtype == config.float_dtype()
        assert out[f"{prefix}2"].dtype == cdt  # percentile histogram
        assert out[f"{prefix}3"].dtype == config.float_dtype()  # a sum stays float
