"""Star-tree tests: build, eligibility, traversal correctness vs oracle,
docs-scanned reduction, persistence, executor routing
(the StarTreeClusterIntegrationTest analog: star-tree answers must equal
non-star-tree answers)."""
import numpy as np
import pytest

from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.engine.reduce import reduce_to_response
from pinot_tpu.pql import optimize_request, parse_pql
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.format import read_segment, write_segment
from pinot_tpu.startree import (
    STAR,
    StarTreeBuilderConfig,
    build_star_tree,
    execute_star_tree,
    is_fit_for_star_tree,
)
from pinot_tpu.tools.datagen import random_rows
from pinot_tpu.tools.scan_engine import ScanQueryProcessor

SCHEMA = Schema(
    "st",
    dimensions=[
        FieldSpec("d1", DataType.STRING),
        FieldSpec("d2", DataType.STRING),
        FieldSpec("d3", DataType.INT),
    ],
    metrics=[
        FieldSpec("m1", DataType.INT, FieldType.METRIC),
        FieldSpec("m2", DataType.DOUBLE, FieldType.METRIC),
    ],
)


@pytest.fixture(scope="module")
def data():
    rows = random_rows(SCHEMA, 2000, seed=31, cardinality=8)
    seg = build_segment(SCHEMA, rows, "st", "stseg")
    build_star_tree(seg, SCHEMA, StarTreeBuilderConfig(max_leaf_records=10))
    oracle = ScanQueryProcessor(SCHEMA, rows)
    return rows, seg, oracle


STAR_QUERIES = [
    "SELECT sum(m1), sum(m2) FROM st",
    "SELECT count(*) FROM st",
    "SELECT sum(m1) FROM st WHERE d1 = '{d1v}'",
    "SELECT sum(m2), count(*) FROM st WHERE d1 = '{d1v}' AND d2 = '{d2v}'",
    "SELECT sum(m1) FROM st WHERE d1 IN ('{d1v}', '{d1w}')",
    "SELECT sum(m1) FROM st GROUP BY d2 TOP 50",
    "SELECT count(*), avg(m2) FROM st WHERE d2 = '{d2v}' GROUP BY d1 TOP 50",
    "SELECT sum(m1) FROM st GROUP BY d1, d2 TOP 1000",
    # RANGE on split dimensions routes to the cube (contiguous dictId
    # interval; StarTreeIndexOperator.java:53 mixed-filter parity)
    "SELECT sum(m1), count(*) FROM st WHERE d3 <= '{d3v}'",
    "SELECT sum(m2) FROM st WHERE d1 = '{d1v}' AND d3 > '{d3v}'",
    "SELECT count(*) FROM st WHERE d3 BETWEEN '{d3v}' AND '{d3w}' GROUP BY d1 TOP 50",
]


def _fill(q, rows):
    d3s = sorted(r["d3"] for r in rows)
    return q.format(
        d1v=rows[0]["d1"],
        d1w=rows[1]["d1"],
        d2v=rows[0]["d2"],
        d3v=d3s[len(d3s) // 3],
        d3w=d3s[2 * len(d3s) // 3],
    )


def _agg_close(a, b, tol=1e-6):
    """Numeric-tolerant compare: star-tree pre-sums in a different order,
    so the last float digit can differ."""
    if isinstance(a, dict) and isinstance(b, dict):
        return set(a) == set(b) and all(_agg_close(a[k], b[k], tol) for k in a)
    if isinstance(a, list) and isinstance(b, list):
        return len(a) == len(b) and all(_agg_close(x, y, tol) for x, y in zip(a, b))
    if isinstance(a, str) and isinstance(b, str):
        try:
            fa, fb = float(a), float(b)
            return abs(fa - fb) <= tol * max(1.0, abs(fa), abs(fb))
        except ValueError:
            return a == b
    return a == b


@pytest.mark.parametrize("template", STAR_QUERIES)
def test_star_tree_matches_oracle(data, template):
    rows, seg, oracle = data
    pql = _fill(template, rows)
    req = optimize_request(parse_pql(pql))
    assert is_fit_for_star_tree(req, seg), pql
    got = reduce_to_response(req, [execute_star_tree(seg, req)]).to_json()
    want = oracle.execute(optimize_request(parse_pql(pql))).to_json()
    assert _agg_close(got["aggregationResults"], want["aggregationResults"]), pql


def test_docs_scanned_collapses(data):
    rows, seg, _ = data
    req = parse_pql("SELECT sum(m1), sum(m2) FROM st")
    res = execute_star_tree(seg, req)
    # full-table SUM scans the fully-starred rows, not 2000 docs
    assert res.num_docs_scanned < 50
    assert res.total_docs == 2000


def test_not_eligible_falls_back(data):
    rows, seg, oracle = data
    # min / distinct / OR-shaped queries are not star-tree eligible
    # (ranges on split dims now are)
    for pql in [
        "SELECT min(m1) FROM st",
        "SELECT distinctcount(d1) FROM st",
        "SELECT sum(m1) FROM st WHERE d1 = 'x' OR d2 = 'y'",
    ]:
        req = optimize_request(parse_pql(pql))
        assert not is_fit_for_star_tree(req, seg), pql


def test_executor_routes_star_and_normal(data):
    rows, seg, oracle = data
    ex = QueryExecutor()
    # eligible -> star path (few docs scanned)
    req = parse_pql("SELECT sum(m1) FROM st")
    resp = reduce_to_response(req, [ex.execute([seg], req)])
    assert resp.num_docs_scanned < 50
    want = oracle.execute(parse_pql("SELECT sum(m1) FROM st"))
    assert resp.aggregation_results[0].value == want.aggregation_results[0].value

    # ineligible -> normal engine path (scans everything), still correct
    req2 = parse_pql("SELECT min(m1) FROM st")
    resp2 = reduce_to_response(req2, [ex.execute([seg], req2)])
    assert resp2.num_docs_scanned == 2000
    want2 = oracle.execute(parse_pql("SELECT min(m1) FROM st"))
    assert resp2.aggregation_results[0].value == want2.aggregation_results[0].value


def test_mixed_segments_merge(data):
    """One segment with star-tree + one without: partials must merge."""
    rows, seg, oracle = data
    rows2 = random_rows(SCHEMA, 500, seed=77, cardinality=8)
    seg2 = build_segment(SCHEMA, rows2, "st", "plain")  # no star tree
    ex = QueryExecutor()
    req = parse_pql("SELECT sum(m1), count(*) FROM st")
    resp = reduce_to_response(req, [ex.execute([seg, seg2], req)])
    both = ScanQueryProcessor(SCHEMA, rows + rows2)
    want = both.execute(parse_pql("SELECT sum(m1), count(*) FROM st"))
    assert resp.to_json()["aggregationResults"] == want.to_json()["aggregationResults"]
    assert resp.total_docs == 2500


def test_persistence_roundtrip(data, tmp_path):
    rows, seg, oracle = data
    write_segment(seg, str(tmp_path / "stseg"))
    loaded = read_segment(str(tmp_path / "stseg"))
    st = loaded.star_tree
    assert st.split_order == seg.star_tree.split_order
    np.testing.assert_array_equal(st.dims, seg.star_tree.dims)
    np.testing.assert_array_equal(st.counts, seg.star_tree.counts)

    pql = "SELECT sum(m1) FROM st GROUP BY d1 TOP 100"
    req = parse_pql(pql)
    got = reduce_to_response(req, [execute_star_tree(loaded, req)]).to_json()
    want = oracle.execute(parse_pql(pql)).to_json()
    assert got["aggregationResults"] == want["aggregationResults"]


def test_star_sentinel_rows_exist(data):
    _, seg, _ = data
    # star rows exist at the first split level and cover the whole table
    st = seg.star_tree
    level0_star = st.dims[:, 0] == STAR
    assert level0_star.sum() >= 1
    # the root's star child subtree aggregates every raw doc exactly once
    star_root = st.root.star_child
    assert star_root is not None
    assert st.counts[star_root.start : star_root.end].sum() == 2000


def test_builder_config_skip_star(data):
    rows, _, _ = data
    seg = build_segment(SCHEMA, rows, "st", "skipseg")
    build_star_tree(
        seg, SCHEMA, StarTreeBuilderConfig(max_leaf_records=10, skip_star_for_dims=["d1"])
    )
    lvl = seg.star_tree.split_order.index("d1")
    assert not np.any(seg.star_tree.dims[:, lvl] == STAR)


def test_hll_in_star_tree(tmp_path):
    """distinctcounthll answered from the cube's pre-merged registers
    (the HllConfig derived-column capability)."""
    schema = Schema(
        "sth",
        dimensions=[
            FieldSpec("dim", DataType.STRING),
            FieldSpec("member", DataType.INT),  # high-card counted column
        ],
        metrics=[FieldSpec("m", DataType.INT, FieldType.METRIC)],
    )
    rows = random_rows(schema, 3000, seed=5, cardinality=400)
    seg = build_segment(schema, rows, "sth", "hllseg")
    build_star_tree(
        seg,
        schema,
        StarTreeBuilderConfig(max_leaf_records=5, hll_columns=["member"]),
    )
    oracle = ScanQueryProcessor(schema, rows)
    ex = QueryExecutor()

    for pql in [
        "SELECT distinctcounthll(member) FROM sth",
        f"SELECT fasthll(member) FROM sth WHERE dim = '{rows[0]['dim']}'",
        "SELECT distinctcounthll(member), count(*) FROM sth GROUP BY dim TOP 100",
    ]:
        req = optimize_request(parse_pql(pql))
        assert is_fit_for_star_tree(req, seg), pql
        got = reduce_to_response(req, [execute_star_tree(seg, req)]).to_json()
        want = oracle.execute(optimize_request(parse_pql(pql))).to_json()
        assert got["aggregationResults"] == want["aggregationResults"], pql

    # full-table HLL comes from few pre-agg rows, not 3000 docs
    req = parse_pql("SELECT distinctcounthll(member) FROM sth")
    assert execute_star_tree(seg, req).num_docs_scanned < 100

    # persists + reloads
    write_segment(seg, str(tmp_path / "hllseg"))
    loaded = read_segment(str(tmp_path / "hllseg"))
    req = parse_pql("SELECT distinctcounthll(member) FROM sth")
    a = reduce_to_response(req, [execute_star_tree(loaded, req)]).to_json()
    b = oracle.execute(parse_pql("SELECT distinctcounthll(member) FROM sth")).to_json()
    assert a["aggregationResults"] == b["aggregationResults"]


def test_adevents_hll_cube_groupby_matches_engine():
    """The north-star HLL group-by answered from the star-tree cube
    (campaign split, HLL(user_id) pre-agg): identical to the engine
    path, independent of row count."""
    import json

    from pinot_tpu.startree.builder import StarTreeBuilderConfig, build_star_tree
    from pinot_tpu.tools.cluster_harness import single_server_broker
    from pinot_tpu.tools.datagen import adevents_schema, synthetic_adevents_segment

    segs = [
        synthetic_adevents_segment(
            60_000, seed=23 + i, name=f"ad{i}", user_card=5000, campaign_card=32
        )
        for i in range(2)
    ]
    cfg = StarTreeBuilderConfig(
        split_order=["campaign_id", "site_id"],
        hll_columns=["user_id"],
        max_leaf_records=16,
    )
    for s in segs:
        build_star_tree(s, adevents_schema(), cfg)
    broker = single_server_broker("adevents", segs)
    pql = "SELECT distinctcounthll(user_id), count(*) FROM adevents GROUP BY campaign_id TOP 5"
    with_tree = broker.handle_pql(pql)
    assert not with_tree.exceptions, with_tree.exceptions
    assert with_tree.num_docs_scanned < 120_000  # pre-agg rows, not raw rows
    for s in segs:
        s.star_tree = None
    engine = broker.handle_pql(pql)
    assert json.dumps(with_tree.to_json()["aggregationResults"], sort_keys=True) == \
        json.dumps(engine.to_json()["aggregationResults"], sort_keys=True)
