"""Zone-map block skipping (engine/zonemap.py + block-gather kernel).

Reference capability: index-based skipping for selective queries
(``SortedInvertedIndexBasedFilterOperator.java``,
``BitmapInvertedIndexReader.java:28``) — here per-block dictId min/max
zones prune blocks host-side before the device gather.
"""
import json

import numpy as np
import pytest

from pinot_tpu.engine import zonemap
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.engine.plan import build_query_inputs, build_static_plan
from pinot_tpu.engine.context import get_table_context
from pinot_tpu.engine.device import stage_segments
from pinot_tpu.engine.reduce import reduce_to_response
from pinot_tpu.pql import optimize_request, parse_pql
from pinot_tpu.tools.datagen import lineitem_schema, synthetic_lineitem_segment
from pinot_tpu.tools.scan_engine import ScanQueryProcessor

BLOCK = 1024

QUERIES = [
    # clustered-date interval: one candidate block per segment
    "SELECT sum(l_quantity), count(*) FROM lineitem WHERE l_shipdate <= '1992-02-01' GROUP BY l_returnflag TOP 10",
    # point lookup on the clustered column
    "SELECT sum(l_extendedprice) FROM lineitem WHERE l_shipdate = '1995-06-14'",
    # AND with an unclustered match-table leaf
    "SELECT count(*) FROM lineitem WHERE l_shipmode IN ('RAIL','FOB') AND l_shipdate BETWEEN '1993-01-01' AND '1993-03-01'",
    # empty candidate set (date past the data)
    "SELECT max(l_discount) FROM lineitem WHERE l_shipdate > '1998-11-30'",
    # OR of two clustered ranges
    "SELECT count(*) FROM lineitem WHERE l_shipdate <= '1992-02-01' OR l_shipdate > '1998-10-01'",
    # IN points on the clustered column
    "SELECT sum(l_tax) FROM lineitem WHERE l_shipdate IN ('1994-01-05','1997-03-22')",
    # selection + order-by through the block path (docid remapping)
    "SELECT l_shipdate, l_quantity FROM lineitem WHERE l_shipdate = '1995-06-14' ORDER BY l_quantity DESC LIMIT 5",
    # NOT IN stays correct (conservative candidacy)
    "SELECT count(*) FROM lineitem WHERE l_shipdate NOT IN ('1995-06-14') AND l_shipdate BETWEEN '1995-06-01' AND '1995-06-30'",
]

STRIP = (
    "timeUsedMs",
    "cost",
    "numEntriesScannedInFilter",
    "numEntriesScannedPostFilter",
    "numSegmentsQueried",
    "numServersQueried",
    "numServersResponded",
)


@pytest.fixture(scope="module")
def cluster(monkeypatch_module=None):
    segs = [
        synthetic_lineitem_segment(20000, seed=7 + i, name=f"li{i}") for i in range(3)
    ]
    rows = [r for s in segs for r in s.rows()]
    oracle = ScanQueryProcessor(lineitem_schema(), rows)
    return segs, oracle


@pytest.fixture(autouse=True)
def small_zone_block(monkeypatch):
    monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", str(BLOCK))
    # these tests exercise the zone-map BLOCK path; the postings fast
    # path (engine/invindex_path.py) would swallow the selective
    # queries first
    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")


def _norm(resp):
    j = resp.to_json()
    for k in STRIP:
        j.pop(k, None)
    return json.dumps(j, sort_keys=True, default=str)


def test_block_path_matches_oracle(cluster):
    segs, oracle = cluster
    ex = QueryExecutor()
    for q in QUERIES:
        req = optimize_request(parse_pql(q))
        req2 = optimize_request(parse_pql(q))
        got = reduce_to_response(req, [ex.execute(segs, req)])
        want = oracle.execute(req2)
        assert _norm(got) == _norm(want), q


def test_selective_query_scans_candidate_blocks_only(cluster):
    segs, _ = cluster
    ex = QueryExecutor()
    req = optimize_request(
        parse_pql("SELECT sum(l_extendedprice) FROM lineitem WHERE l_shipdate = '1995-06-14'")
    )
    part = ex.execute(segs, req)
    # clustered dates: the one matching block per segment, not the table
    assert part.num_entries_scanned_in_filter <= 2 * BLOCK * len(segs)
    total = sum(s.num_docs for s in segs)
    assert part.num_entries_scanned_in_filter < total / 4


def test_zone_map_disabled_full_scan(cluster, monkeypatch):
    segs, oracle = cluster
    monkeypatch.setenv("PINOT_TPU_ZONEMAP", "0")
    ex = QueryExecutor()
    q = "SELECT sum(l_extendedprice) FROM lineitem WHERE l_shipdate = '1995-06-14'"
    req = optimize_request(parse_pql(q))
    req2 = optimize_request(parse_pql(q))
    got = reduce_to_response(req, [ex.execute(segs, req)])
    assert _norm(got) == _norm(oracle.execute(req2))


def test_candidate_blocks_conservative(cluster):
    """Every row the kernel would match must live in a candidate block."""
    segs, _ = cluster
    q = "SELECT count(*) FROM lineitem WHERE l_shipdate BETWEEN '1994-03-01' AND '1994-04-15'"
    req = optimize_request(parse_pql(q))
    ctx = get_table_context(segs)
    staged = stage_segments(segs, sorted(req.referenced_columns()), ctx=ctx)
    plan = build_static_plan(req, ctx, staged)
    q_np = build_query_inputs(req, plan, ctx, staged)
    cand = zonemap.candidate_blocks(plan, q_np, segs, staged.n_pad, block=BLOCK)
    assert cand is not None
    for si, seg in enumerate(segs):
        col = seg.column("l_shipdate")
        d = col.dictionary
        lo, hi = q_np["bounds"][0][si]
        match_rows = np.nonzero((col.fwd >= lo) & (col.fwd < hi))[0]
        for doc in match_rows:
            assert cand[si][doc // BLOCK], (si, doc)


def test_zones_cached_per_segment(cluster):
    segs, _ = cluster
    z1 = zonemap.column_zones(segs[0], "l_shipdate", BLOCK)
    z2 = zonemap.column_zones(segs[0], "l_shipdate", BLOCK)
    assert z1 is z2
    zmin, zmax = z1
    assert (zmin <= zmax).all()
    # clustered column: zones are narrow
    assert (zmax - zmin).mean() < segs[0].column("l_shipdate").metadata.cardinality / 8


def test_randomized_differential_through_block_path(monkeypatch):
    """Randomized PQL differential vs the scan oracle with the zone
    block small enough that the block-gather kernel engages on most
    filtered queries — the QueryGenerator net over the new path."""
    monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", "256")
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.tools.datagen import make_test_schema, random_rows
    from pinot_tpu.tools.query_gen import QueryGenerator
    from tests.test_engine import _values_close

    schema = make_test_schema()
    rows = random_rows(schema, 1500, seed=77, cardinality=10)
    # sort by a dimension so zones are selective for some columns
    rows.sort(key=lambda r: (r["dimStr"], r["dimInt"]))
    chunk = len(rows) // 3
    segs = [
        build_segment(schema, rows[i * chunk : (i + 1) * chunk if i < 2 else len(rows)],
                      "testTable", f"zseg{i}")
        for i in range(3)
    ]
    oracle = ScanQueryProcessor(schema, rows)
    gen = QueryGenerator(schema, rows, seed=99)
    ex = QueryExecutor()
    def canon(resp):
        # group order among EQUAL aggregate values is unspecified (both
        # engines sort by value; tie-break differs) — canonicalize
        for agg in resp.get("aggregationResults") or []:
            if "groupByResult" in agg:
                agg["groupByResult"].sort(key=lambda e: (str(e["value"]), e["group"]))
        return resp

    mismatches = []
    for _ in range(40):
        pql = gen.next_query()
        req = optimize_request(parse_pql(pql))
        req2 = optimize_request(parse_pql(pql))
        got = reduce_to_response(req, [ex.execute(segs, req)]).to_json()
        want = oracle.execute(req2).to_json()
        for k in STRIP:
            got.pop(k, None)
            want.pop(k, None)
        if not _values_close(canon(got), canon(want)):
            mismatches.append((pql, got, want))
    assert not mismatches, json.dumps(mismatches[0], default=str)[:3000]


def test_block_path_on_8_device_mesh(cluster):
    """Zone-map skipping composes with the sharded multi-chip kernel:
    block ids shard over the segment axis (parallel/multichip.py)."""
    from pinot_tpu.parallel import default_mesh

    segs, oracle = cluster
    total = sum(s.num_docs for s in segs)
    ex = QueryExecutor(mesh=default_mesh())
    for q in QUERIES:
        req = optimize_request(parse_pql(q))
        req2 = optimize_request(parse_pql(q))
        part = ex.execute(segs, req)
        got = reduce_to_response(req, [part])
        want = oracle.execute(req2)
        assert _norm(got) == _norm(want), q
    # the selective point query must actually have taken the skipping
    # path on the mesh, not fallen back to the full sharded scan
    req = optimize_request(
        parse_pql("SELECT sum(l_extendedprice) FROM lineitem WHERE l_shipdate = '1995-06-14'")
    )
    part = ex.execute(segs, req)
    assert part.num_entries_scanned_in_filter < total / 4


def test_docrange_classification_and_fallback(cluster):
    """RANGE/EQ on a column sorted in every segment classifies as a
    doc-interval predicate (no column read); a mixed table where one
    segment is unsorted falls back to the dictId-interval kind."""
    from pinot_tpu.engine.plan import build_static_plan
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    segs, _ = cluster

    def kinds(segments, pql):
        req = optimize_request(parse_pql(pql))
        ctx = get_table_context(segments)
        staged = stage_segments(segments, sorted(req.referenced_columns()), ctx=ctx)
        plan = build_static_plan(req, ctx, staged)
        return [l.eval_kind for l in plan.leaves]

    assert kinds(segs, "SELECT count(*) FROM lineitem WHERE l_shipdate <= '1995-01-01'") == ["docrange"]
    assert kinds(segs, "SELECT count(*) FROM lineitem WHERE l_shipdate = '1995-06-14'") == ["docrange"]
    # unsorted column: stays a dictId interval
    assert kinds(segs, "SELECT count(*) FROM lineitem WHERE l_quantity > 25") == ["interval"]
    # IN with several points is not contiguous: stays points
    assert kinds(
        segs, "SELECT count(*) FROM lineitem WHERE l_shipdate IN ('1994-01-05','1997-03-22')"
    ) == ["points"]

    # mixed sortedness across segments: fall back
    unsorted = synthetic_lineitem_segment(5000, seed=99, name="unsorted")
    object.__setattr__(unsorted.column("l_shipdate").metadata, "is_sorted", False)
    mixed = list(segs) + [unsorted]
    assert kinds(mixed, "SELECT count(*) FROM lineitem WHERE l_shipdate <= '1995-01-01'") == ["interval"]


def test_docrange_column_not_staged(cluster):
    """A column used only by docrange predicates never reaches device
    memory: the kernel compares row ids against host-computed bounds."""
    from pinot_tpu.engine.device import clear_staging_cache, _stage_cache

    segs, oracle = cluster
    clear_staging_cache()
    ex = QueryExecutor()
    q = "SELECT sum(l_quantity) FROM lineitem WHERE l_shipdate <= '1994-01-01'"
    req = optimize_request(parse_pql(q))
    req2 = optimize_request(parse_pql(q))
    got = reduce_to_response(req, [ex.execute(segs, req)])
    assert _norm(got) == _norm(oracle.execute(req2))
    staged_cols = {c for st in _stage_cache.values() for c in st.columns}
    assert "l_shipdate" not in staged_cols
    assert "l_quantity" in staged_cols
    clear_staging_cache()


def test_zone_maps_persisted_in_segment_file(tmp_path, monkeypatch):
    """write_segment stores per-block zones; read_segment preloads them
    so the first selective query does no O(n) zone scan."""
    from pinot_tpu.segment.format import read_segment, write_segment

    monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", "512")
    seg = synthetic_lineitem_segment(5000, seed=5, name="zp")
    d = write_segment(seg, str(tmp_path / "zp"))
    loaded = read_segment(str(tmp_path / "zp"))
    cache = getattr(loaded, "_zone_cache", {})
    assert ("l_shipdate", 512) in cache
    zmin, zmax = cache[("l_shipdate", 512)]
    ref_min, ref_max = zonemap.column_zones(seg, "l_shipdate", 512)
    np.testing.assert_array_equal(zmin, ref_min)
    np.testing.assert_array_equal(zmax, ref_max)
    # column_zones on the loaded segment returns the preloaded arrays
    got = zonemap.column_zones(loaded, "l_shipdate", 512)
    assert got[0] is zmin


def test_persisted_zones_reblock_to_coarser(tmp_path, monkeypatch):
    """Zones persisted at a fine write-time block derive coarser query
    blocks by grouped min/max — no column rescan."""
    from pinot_tpu.segment.format import read_segment, write_segment

    monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", "256")
    seg = synthetic_lineitem_segment(5000, seed=5, name="zr")
    write_segment(seg, str(tmp_path / "zr"))
    loaded = read_segment(str(tmp_path / "zr"))
    loaded.columns["l_shipdate"] = loaded.columns["l_shipdate"].__class__(
        metadata=loaded.column("l_shipdate").metadata,
        dictionary=loaded.column("l_shipdate").dictionary,
        fwd=None,  # prove the derivation never touches the column
    )
    monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", "1024")
    got = zonemap.column_zones(loaded, "l_shipdate", 1024)
    want = zonemap.column_zones(seg, "l_shipdate", 1024)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_selection_limit_beyond_candidate_window(cluster):
    """Regression (ADVICE r2): a selective filter with one candidate
    block but LIMIT+OFFSET > block rows must not feed top_k a k larger
    than the gathered view — the candidate window grows (or the plan
    falls back to the full scan) and results still match the oracle."""
    segs, oracle = cluster
    ex = QueryExecutor()
    q = (
        "SELECT l_shipdate, l_quantity FROM lineitem "
        "WHERE l_shipdate = '1995-06-14' "
        f"ORDER BY l_quantity DESC LIMIT {BLOCK + 200}"
    )
    req = optimize_request(parse_pql(q))
    req2 = optimize_request(parse_pql(q))
    got = reduce_to_response(req, [ex.execute(segs, req)])
    assert _norm(got) == _norm(oracle.execute(req2))


def test_runs_leaf_through_block_path(cluster):
    """Regression: a 'runs' eval-kind leaf (>16-value IN list) must
    compute real zone candidacy — treating it as a table leaf read the
    all-False dummy and pruned EVERY block (empty results)."""
    segs, oracle = cluster
    d = segs[0].column("l_shipdate").dictionary
    vals = ", ".join(repr(d.get(i)) for i in range(0, 60, 3))  # 20 points
    q = f"SELECT count(*), sum(l_quantity) FROM lineitem WHERE l_shipdate IN ({vals})"
    req = optimize_request(parse_pql(q))
    from pinot_tpu.engine.plan import build_static_plan

    ctx = get_table_context(segs)
    staged = stage_segments(segs, sorted(req.referenced_columns()), ctx=ctx)
    plan = build_static_plan(req, ctx, staged)
    assert plan.leaves[0].eval_kind == "runs"
    got = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
    want = oracle.execute(optimize_request(parse_pql(q)))
    assert _norm(got) == _norm(want)
    # sanity: the query matches something (the bug returned zero rows)
    assert int(got.to_json()["aggregationResults"][0]["value"]) > 0


# -- the block program reads its blocks where they are staged (PR 35) --------

# shape: (PQL, what kernel.zone_blocks answers, needs the chip's group-by lowerings)
YEAR = "l_shipdate BETWEEN '1994-01-01' AND '1994-12-31'"
ZONE_SHAPES = {
    "q6_sum_of_a_product": (
        "SELECT sum(l_extendedprice*l_discount) FROM lineitem WHERE l_shipdate >= '1994-01-01' AND "
        "l_shipdate < '1995-01-01' AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24", "inplace", False),
    "small_k_in_the_row_loop": (
        f"SELECT sum(l_quantity), avg(l_discount), count(*) FROM lineitem WHERE {YEAR} GROUP BY l_returnflag TOP 10",
        "inplace", True),
    "q5_by_the_sorted_column_radix": (
        f"SELECT sum(l_extendedprice) FROM lineitem WHERE {YEAR} GROUP BY l_shipdate TOP 10", "inplace", True),
    "q5_by_the_sorted_column_scatter": (
        f"SELECT sum(l_extendedprice) FROM lineitem WHERE {YEAR} GROUP BY l_shipdate TOP 10", "inplace", False),
    "min_max_group_by": (
        f"SELECT min(l_extendedprice), max(l_discount), minmaxrange(l_quantity) FROM lineitem WHERE {YEAR} "
        "GROUP BY l_returnflag TOP 10", "inplace", False),
    "selection": (
        f"SELECT l_shipdate, l_quantity FROM lineitem WHERE {YEAR} ORDER BY l_quantity DESC LIMIT 5", "gathered", False),
}


def _forget_block_programs():
    from pinot_tpu.engine import kernel as kernel_mod

    for cached in (kernel_mod.make_table_kernel, kernel_mod.make_packed_table_kernel,
                   kernel_mod.make_block_table_kernel, kernel_mod.make_packed_block_table_kernel):
        cached.cache_clear()


@pytest.fixture
def zone_launch(cluster, monkeypatch, request):
    """(plan, segment arrays, query inputs, block ids) of one shape's
    launch through the zone tier, as the executor makes it."""
    pql, _, contractions = ZONE_SHAPES[request.param]
    if contractions:
        monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    _forget_block_programs()
    launch = {}
    run_kernel = QueryExecutor._run_kernel

    def spy(self, kernel, args, plan, staged, digest, block_ids, *rest, **kw):
        launch.update(plan=plan, segs=args[0], q=args[1], ids=block_ids)
        return run_kernel(self, kernel, args, plan, staged, digest, block_ids, *rest, **kw)

    monkeypatch.setattr(QueryExecutor, "_run_kernel", spy)
    segs, oracle = cluster
    req = optimize_request(parse_pql(pql))
    got = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
    from tests.test_engine import _values_close

    want = oracle.execute(optimize_request(parse_pql(pql))).to_json()
    got = got.to_json()
    for k in STRIP:
        got.pop(k, None), want.pop(k, None)
    assert _values_close(got, want), request.param
    assert launch["ids"] is not None, "the zone tier did not take it"
    yield request.param, launch
    _forget_block_programs()


def _id_variants(ids: np.ndarray, nb_total: int):
    """The executor's ids (a run a segment) and hand-made ones beside
    them; every list still holds its segment's candidates (but 'dead',
    where one segment is left out whole), packed to the front."""
    run = [sorted(int(b) for b in row if b >= 0) for row in ids]
    extra = [b for b in (0, 1, nb_total // 2, nb_total - 2, nb_total - 1) if b not in set(sum(run, []))]

    def packed(rows):
        out = np.full((len(rows), max(8, max(len(r) for r in rows))), -1, dtype=np.int32)
        for s, r in enumerate(rows):
            out[s, : len(r)] = r
        return out

    return {
        "run": (ids, range(len(run))),
        "holes": (packed([sorted(r + extra[s % 2 :: 2]) for s, r in enumerate(run)]), range(len(run))),
        "dead": (packed([[] if s == 1 else r for s, r in enumerate(run)]), [s for s in range(len(run)) if s != 1]),
        "uneven": (packed([r if s == 0 else sorted(r + extra[: 1 + 2 * s]) for s, r in enumerate(run)]), range(len(run))),
        "all_dead": (packed([[] for _ in run]), []),
    }


@pytest.mark.parametrize("zone_launch", sorted(ZONE_SHAPES), indirect=True)
def test_block_program_equals_the_full_scan_on_the_same_segments(zone_launch):
    """Plan by plan: the zone tier's program over a launch's block ids
    against the full scan's single-segment kernel over every row of the
    segments those ids name.  Counts, occupancy and extremes exact, float
    sums to a sum of block sums; a segment whose ids are all -1 adds
    nothing, and segments may keep different numbers of blocks."""
    import jax

    from pinot_tpu.engine import kernel as kernel_mod

    shape, launch = zone_launch
    plan, segs, q, ids = launch["plan"], launch["segs"], launch["q"], launch["ids"]
    form = ZONE_SHAPES[shape][1]
    assert kernel_mod.zone_blocks(plan) == form
    if shape.endswith("radix"):
        assert kernel_mod.groupby_lowering(plan) == "radix"
    elif shape == "small_k_in_the_row_loop":
        assert kernel_mod.groupby_operands(plan) == "loop"
    elif plan.group_by is not None:
        assert kernel_mod.groupby_lowering(plan) == "scatter"
    reducers = kernel_mod.output_reducers(plan)
    merged = [k for k, op in reducers.items() if op != "none"]
    full = jax.vmap(kernel_mod.make_single_segment_kernel(plan))(segs, q)
    n_pad = next(v.shape[1] for k, v in segs.items() if kernel_mod._row_key(k))
    program = kernel_mod.make_block_table_kernel(plan, BLOCK)
    for variant, (variant_ids, kept) in _id_variants(np.asarray(ids), n_pad // BLOCK).items():
        if form == "gathered" and variant != "run":
            continue  # a selection's window is the executor's to size
        got = program(segs, q, jax.numpy.asarray(variant_ids))
        kept = np.asarray(list(kept), dtype=np.int32)
        if not kept.size:  # nothing named: what the kernel gives a segment with no valid row
            assert int(got["num_docs"]) == 0
            assert "gb_presence" not in got or not np.asarray(got["gb_presence"]).any()
            continue
        for key in merged:
            want = kernel_mod.apply_reduce(reducers[key], jax.tree_util.tree_map(lambda v: v[kept], full[key]))
            for g, w in zip(jax.tree_util.tree_leaves(got[key]), jax.tree_util.tree_leaves(want)):
                g, w = np.asarray(g), np.asarray(w)
                if np.issubdtype(g.dtype, np.integer) or reducers[key] in ("min", "max", "minmax_pair"):
                    np.testing.assert_array_equal(g, w, err_msg=f"{shape} {variant} {key}")
                else:  # the radix contraction accumulates in float32 on every backend
                    np.testing.assert_allclose(g, w, rtol=1e-6, err_msg=f"{shape} {variant} {key}")


@pytest.mark.parametrize(
    "zone_launch", ["q6_sum_of_a_product", "small_k_in_the_row_loop", "q5_by_the_sorted_column_scatter", "selection"],
    indirect=True)
def test_inplace_program_holds_no_array_of_the_gathered_length(zone_launch):
    """The mechanism and not only its answers: the lowered block program
    of an 'inplace' plan holds no array of nb_pad * block rows a segment
    (no gathered copy of a staged column, of ``valid`` or of ``rowid``)
    and no view of a column as [blocks, block]; a 'gathered' plan's
    holds both."""
    import re

    from pinot_tpu.engine import kernel as kernel_mod

    shape, launch = zone_launch
    plan, segs, q, ids = launch["plan"], launch["segs"], launch["q"], np.asarray(launch["ids"])
    n_seg, nb_pad = ids.shape
    n_pad = next(v.shape[1] for k, v in segs.items() if kernel_mod._row_key(k))
    rows = nb_pad * BLOCK
    assert rows not in (n_pad, BLOCK) and n_pad % BLOCK == 0
    text = kernel_mod.make_block_table_kernel(plan, BLOCK).lower(segs, q, ids).as_text()
    gathered_length = re.findall(rf"tensor<(?:{n_seg}x)?{rows}(?:x\d+)*x[a-z]+\d+>", text)
    by_blocks = re.findall(rf"tensor<(?:{n_seg}x)?(?:{n_pad // BLOCK}|{nb_pad})x{BLOCK}(?:x\d+)*x[a-z]+\d+>", text)
    if kernel_mod.zone_blocks(plan) == "inplace":
        assert not gathered_length and not by_blocks, (gathered_length[:3], by_blocks[:3])
        assert f"tensor<{n_seg}x{BLOCK}x" in text  # a step's slice of every segment
    else:
        assert gathered_length and by_blocks


@pytest.mark.parametrize("shape", ["q6_sum_of_a_product", "min_max_group_by", "selection"])
def test_launch_says_how_the_zone_tier_read_its_blocks(cluster, shape, monkeypatch):
    """``blocks=inplace|gathered`` on the launch's ``laneDispatch`` span
    and one ``zone.blocks.*`` mark a zone-tier launch: the answer of
    ``kernel.zone_blocks``, which the kernel builder asks too; a launch
    of another tier carries neither."""
    from pinot_tpu.engine import kernel as kernel_mod
    from pinot_tpu.tools.cluster_harness import single_server_broker

    pql, form, _ = ZONE_SHAPES[shape]
    segs, _ = cluster
    _forget_block_programs()
    built = []
    inplace_kernel = kernel_mod._make_inplace_block_kernel
    monkeypatch.setattr(kernel_mod, "_make_inplace_block_kernel",
                        lambda plan, block: built.append(plan) or inplace_kernel(plan, block))
    broker = single_server_broker("lineitem", segs)
    server = broker.local_servers[0]
    marks = lambda: {k: server.metrics.meter(f"zone.blocks.{k}").count for k in ("inplace", "gathered")}

    def launch_tags(text):
        resp = broker.handle_pql(text, trace=True)
        assert not resp.to_json()["exceptions"]
        (launch,) = [s for s in resp.trace_info["scopes"][server.name] if s["span"] == "laneDispatch"]
        return launch["tags"]

    try:
        tags = launch_tags(pql)
        assert tags["program"].startswith("pinot_zone_") and tags["blocks"] == form
        assert marks() == {"inplace": int(form == "inplace"), "gathered": int(form == "gathered")}
        assert len(built) == int(form == "inplace") and all(kernel_mod.zone_blocks(p) == "inplace" for p in built)
        # the full scan is no zone launch: no tag, no mark
        scan = launch_tags("SELECT sum(l_quantity) FROM lineitem WHERE l_shipdate <= '1998-09-02'")
        assert scan["program"].startswith("pinot_scan_") and "blocks" not in scan
        assert sum(marks().values()) == 1
        # the function answers otherwise: the tag, the mark and the program follow it together
        monkeypatch.setattr(kernel_mod, "zone_blocks", lambda plan: "gathered")
        _forget_block_programs()
        assert launch_tags(pql)["blocks"] == "gathered"
        assert marks()["gathered"] == 1 + int(form == "gathered") and len(built) == int(form == "inplace")
    finally:
        server.shutdown()
        _forget_block_programs()
