"""Inverted-index postings + selective-query fast path
(segment/invindex.py + engine/invindex_path.py).

Reference capability: ``BitmapInvertedIndexReader.java:28`` +
``BitmapBasedFilterOperator.java:34`` — O(matches) selective predicates
independent of doc order (the case zone maps cannot prune: values
shuffled across blocks)."""
import json

import numpy as np
import pytest

from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.engine.context import get_table_context
from pinot_tpu.engine.invindex_path import try_index_path
from pinot_tpu.engine.reduce import reduce_to_response
from pinot_tpu.pql import optimize_request, parse_pql
from pinot_tpu.segment.invindex import InvertedIndex, inverted_index
from pinot_tpu.tools.datagen import lineitem_schema, synthetic_lineitem_segment
from pinot_tpu.tools.scan_engine import ScanQueryProcessor

STRIP = (
    "timeUsedMs",
    "cost",
    "numEntriesScannedInFilter",
    "numEntriesScannedPostFilter",
    "numSegmentsQueried",
    "numServersQueried",
    "numServersResponded",
    "numDocsScanned",
)


def _norm(resp):
    j = resp.to_json()
    for k in STRIP:
        j.pop(k, None)
    return json.dumps(j, sort_keys=True, default=str)


@pytest.fixture(scope="module")
def cluster():
    segs = [
        synthetic_lineitem_segment(20000, seed=17 + i, name=f"ii{i}") for i in range(3)
    ]
    rows = [r for s in segs for r in s.rows()]
    return segs, ScanQueryProcessor(lineitem_schema(), rows)


# -- postings unit level ------------------------------------------------


def test_build_sv_round_trip():
    fwd = np.array([3, 1, 3, 0, 1, 3], dtype=np.int32)
    idx = InvertedIndex.build_sv(fwd, 4)
    assert idx.rows[idx.offsets[3] : idx.offsets[4]].tolist() == [0, 2, 5]
    assert idx.rows[idx.offsets[1] : idx.offsets[2]].tolist() == [1, 4]
    assert idx.rows[idx.offsets[2] : idx.offsets[3]].tolist() == []
    # a dictId range is one contiguous slice
    t = np.zeros(4, bool)
    t[1:3] = True
    assert idx.slices_for_table(t) == [(1, 3)]
    assert sorted(idx.resolve_table(t).tolist()) == [1, 4]


def test_build_mv_any_semantics():
    # rows: 0 -> [1, 2]; 1 -> []; 2 -> [2]
    mv_values = np.array([1, 2, 2], dtype=np.int32)
    mv_offsets = np.array([0, 2, 2, 3], dtype=np.int64)
    idx = InvertedIndex.build_mv(mv_values, mv_offsets, 3)
    t = np.zeros(3, bool)
    t[2] = True
    assert sorted(idx.resolve_table(t).tolist()) == [0, 2]
    # a doc matching SEVERAL predicate values resolves ONCE (regression:
    # per-(doc,value) postings must dedupe or aggregations double-count)
    t2 = np.ones(3, bool)
    assert idx.resolve_table(t2).tolist() == [0, 2]


def test_index_cached_on_segment(cluster):
    segs, _ = cluster
    a = inverted_index(segs[0], "l_extendedprice")
    b = inverted_index(segs[0], "l_extendedprice")
    assert a is b
    col = segs[0].column("l_extendedprice")
    # postings invert the forward index exactly
    d = np.random.default_rng(3).integers(0, col.dictionary.cardinality, 5)
    for dict_id in d:
        t = np.zeros(col.dictionary.cardinality, bool)
        t[dict_id] = True
        want = np.nonzero(np.asarray(col.fwd) == dict_id)[0]
        np.testing.assert_array_equal(a.resolve_table(t), want)


# -- fast path vs oracle ------------------------------------------------

SELECTIVE_QUERIES = [
    # point lookup on the SHUFFLED high-card column (zone maps can't
    # prune this; the reference answers it from the inverted index)
    "SELECT count(*) FROM lineitem WHERE l_extendedprice = {p0}",
    "SELECT sum(l_quantity), avg(l_tax) FROM lineitem WHERE l_extendedprice = {p0}",
    "SELECT min(l_quantity), max(l_quantity) FROM lineitem WHERE l_extendedprice IN ({p0}, {p1})",
    # AND residuals on the matched subset
    "SELECT count(*) FROM lineitem WHERE l_extendedprice = {p0} AND l_returnflag = 'R'",
    "SELECT sum(l_discount) FROM lineitem WHERE l_extendedprice = {p0} AND l_shipmode NOT IN ('RAIL')",
    # group-by and selection through the same path
    "SELECT sum(l_quantity) FROM lineitem WHERE l_extendedprice = {p0} GROUP BY l_returnflag TOP 10",
    "SELECT l_returnflag, l_quantity FROM lineitem WHERE l_extendedprice = {p0} ORDER BY l_quantity DESC LIMIT 5",
]


def _pvals(segs):
    d = segs[0].column("l_extendedprice").dictionary
    return repr(d.get(100)), repr(d.get(2000))


def test_index_path_matches_oracle(cluster):
    segs, oracle = cluster
    p0, p1 = _pvals(segs)
    ex = QueryExecutor()
    for q in SELECTIVE_QUERIES:
        pql = q.format(p0=p0, p1=p1)
        req = optimize_request(parse_pql(pql))
        req2 = optimize_request(parse_pql(pql))
        got = reduce_to_response(req, [ex.execute(segs, req)])
        want = oracle.execute(req2)
        assert _norm(got) == _norm(want), pql


def test_index_path_engages_and_is_o_matches(cluster):
    segs, _ = cluster
    p0, _ = _pvals(segs)
    req = optimize_request(
        parse_pql(f"SELECT count(*) FROM lineitem WHERE l_extendedprice = {p0}")
    )
    ctx = get_table_context(segs)
    total = sum(s.num_docs for s in segs)
    res = try_index_path(req, list(segs), ctx, total, None)
    assert res is not None
    # filter cost is O(postings), nowhere near the table
    assert res.num_entries_scanned_in_filter < total / 100


def test_unselective_predicate_stays_on_device(cluster):
    segs, _ = cluster
    # 20% of rows: must NOT take the needle path
    req = optimize_request(
        parse_pql("SELECT count(*) FROM lineitem WHERE l_returnflag = 'R'")
    )
    ctx = get_table_context(segs)
    total = sum(s.num_docs for s in segs)
    assert try_index_path(req, list(segs), ctx, total, None) is None


def test_kill_switch(cluster, monkeypatch):
    segs, _ = cluster
    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")
    p0, _ = _pvals(segs)
    req = optimize_request(
        parse_pql(f"SELECT count(*) FROM lineitem WHERE l_extendedprice = {p0}")
    )
    ctx = get_table_context(segs)
    assert try_index_path(req, list(segs), ctx, 1, None) is None


def test_threshold_bail(cluster, monkeypatch):
    segs, _ = cluster
    monkeypatch.setenv("PINOT_TPU_INDEX_MAX_MATCHES", "1")
    p0, _ = _pvals(segs)
    req = optimize_request(
        parse_pql(f"SELECT count(*) FROM lineitem WHERE l_extendedprice = {p0}")
    )
    ctx = get_table_context(segs)
    total = sum(s.num_docs for s in segs)
    assert try_index_path(req, list(segs), ctx, total, None) is None


def test_configured_inverted_index_columns_warm_at_load(tmp_path):
    """invertedIndexColumns table config (IndexingConfig parity): the
    server pre-builds configured postings at segment load instead of on
    the first needle query."""
    from pinot_tpu.common.tableconfig import IndexingConfig
    from pinot_tpu.tools.cluster_harness import InProcessCluster

    cluster = InProcessCluster(num_servers=1)
    physical = cluster.add_offline_table(
        lineitem_schema(),
        "lineitem",
        indexing=IndexingConfig(inverted_index_columns=["l_extendedprice"]),
    )
    seg = synthetic_lineitem_segment(5000, seed=5, name="warm0")
    cluster.controller.upload_segment(physical, seg)
    tdm = cluster.servers[0].data_manager.table(physical)
    acquired = tdm.acquire_segments(tdm.segment_names())
    try:
        seg_loaded = acquired[0].query_view()
        cache = getattr(seg_loaded, "_inv_cache", {})
        assert "l_extendedprice" in cache, "postings not warmed at load"
    finally:
        tdm.release_segments(acquired)


# -- compressed containers ----------------------------------------------


def test_compressed_blocks_roundtrip_clustered():
    """A sorted (clustered) column: postings are consecutive runs ->
    run containers; decode must be exact and memory far below raw."""
    n = 50_000
    fwd = np.sort(np.random.default_rng(3).integers(0, 100, n)).astype(np.int32)
    raw = InvertedIndex.build_sv(fwd, 100, compress=False)
    comp = InvertedIndex.build_sv(fwd, 100, compress=True)
    np.testing.assert_array_equal(raw.rows, comp.rows)
    t = np.zeros(100, bool)
    t[17] = True
    t[40:60] = True
    np.testing.assert_array_equal(raw.resolve_table(t), comp.resolve_table(t))
    # clustered postings collapse to run containers: >=20x cut on the
    # posting body (offsets overhead excluded by using a small card)
    assert comp.nbytes * 20 <= raw.nbytes, (comp.nbytes, raw.nbytes)


def test_compressed_blocks_roundtrip_shuffled():
    """Shuffled high-cardinality column: packed containers at
    ceil(log2(num_docs)) bits; decode exact, strictly below raw int32."""
    n = 40_000
    rng = np.random.default_rng(4)
    fwd = rng.integers(0, 7000, n).astype(np.int32)
    raw = InvertedIndex.build_sv(fwd, 7000, compress=False)
    comp = InvertedIndex.build_sv(fwd, 7000, compress=True)
    np.testing.assert_array_equal(raw.rows, comp.rows)
    for d in (0, 1234, 6999):
        t = np.zeros(7000, bool)
        t[d] = True
        np.testing.assert_array_equal(raw.resolve_table(t), comp.resolve_table(t))
    # 16 bits vs 32 on the body (40k docs): about 2x minus offsets
    body_raw = raw.nbytes - raw.offsets.nbytes
    body_comp = comp.nbytes - comp.offsets.nbytes
    assert body_comp * 1.9 <= body_raw, (body_comp, body_raw)


def test_compressed_mv_roundtrip():
    mv_offsets = np.arange(0, 3 * 9001, 3, dtype=np.int32)  # 9000 docs x 3 values
    rng = np.random.default_rng(5)
    mv_values = rng.integers(0, 50, mv_offsets[-1]).astype(np.int32)
    raw = InvertedIndex.build_mv(mv_values, mv_offsets, 50, compress=False)
    comp = InvertedIndex.build_mv(mv_values, mv_offsets, 50, compress=True)
    t = np.zeros(50, bool)
    t[7] = True
    t[31] = True
    np.testing.assert_array_equal(raw.resolve_table(t), comp.resolve_table(t))


def test_postings_budget_refusal_and_release(monkeypatch):
    """Over-budget builds are refused (engine falls back to scan) and
    unloading a segment returns its bytes to the budget."""
    from pinot_tpu.segment import invindex as ii
    from pinot_tpu.server.datamanager import SegmentDataManager

    seg = synthetic_lineitem_segment(3000, seed=31, name="bud0")
    monkeypatch.setattr(ii, "_postings_bytes", 0)
    monkeypatch.setenv("PINOT_TPU_INVINDEX_BUDGET_BYTES", "64")  # tiny
    assert inverted_index(seg, "l_extendedprice") is None
    cache = getattr(seg, "_inv_cache")
    refusal = cache["l_extendedprice"]
    assert refusal[0] == "refused"  # cached: no per-query rebuild
    assert inverted_index(seg, "l_extendedprice") is None
    assert cache["l_extendedprice"] is refusal  # same epoch: not retried

    seg2 = synthetic_lineitem_segment(3000, seed=32, name="bud1")
    monkeypatch.setenv("PINOT_TPU_INVINDEX_BUDGET_BYTES", str(64 << 20))
    idx = inverted_index(seg2, "l_extendedprice")
    assert idx is not None
    assert ii.postings_bytes_in_use() >= idx.nbytes
    sdm = SegmentDataManager(seg2)
    assert sdm.release() == 0  # owner ref dropped -> postings freed
    assert ii.postings_bytes_in_use() == 0

    # the release bumped the epoch: the earlier refusal re-evaluates and
    # (budget is now ample) the index builds
    assert inverted_index(seg, "l_extendedprice") is not None


def test_concurrent_index_builds_account_once(monkeypatch):
    """Race regression: concurrent cold builds of the same (segment,
    column) must account postings bytes exactly once — double-counting
    would eventually refuse all future builds."""
    import threading

    from pinot_tpu.segment import invindex as ii

    seg = synthetic_lineitem_segment(20000, seed=44, name="race0")
    monkeypatch.setattr(ii, "_postings_bytes", 0)
    monkeypatch.setenv("PINOT_TPU_INVINDEX_BUDGET_BYTES", str(64 << 20))
    results = []
    barrier = threading.Barrier(8)

    def hit():
        barrier.wait()
        results.append(inverted_index(seg, "l_extendedprice"))

    threads = [threading.Thread(target=hit) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is not None for r in results)
    cached = getattr(seg, "_inv_cache")["l_extendedprice"]
    assert all(r is cached for r in results)  # one winning index
    assert ii.postings_bytes_in_use() == cached.nbytes  # accounted ONCE
