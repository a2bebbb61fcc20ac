"""Sort-dedup exact-distinct device path (StaticAgg.sort_pairs):
high-cardinality ``distinctcount`` stays on device via a global
(group, valueId) pair sort instead of the dense [capacity, gcard_pad]
holder or the host fallback.

Reference parity: the map-based group-by storage the reference switches
to beyond the dense array key space
(``DefaultGroupKeyGenerator.java:60-63``), re-designed for TPU — sorts
are vectorizable where hash maps are not."""
import json

import numpy as np
import pytest

from pinot_tpu.engine import config
from pinot_tpu.engine.context import get_table_context
from pinot_tpu.engine.device import clear_staging_cache, stage_segments
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.engine.plan import build_static_plan
from pinot_tpu.engine.reduce import reduce_to_response
from pinot_tpu.pql import optimize_request, parse_pql
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
        synthetic_lineitem_segment(15000, seed=23 + i, name=f"ds{i}") for i in range(3)
    ]
    rows = [r for s in segs for r in s.rows()]
    return segs, ScanQueryProcessor(lineitem_schema(), rows)


@pytest.fixture(autouse=True)
def small_dense_cap(monkeypatch):
    # l_extendedprice has ~16k global cardinality; force it past the
    # dense-state budget so the sort-dedup path engages
    monkeypatch.setattr(config, "MAX_VALUE_STATE", 1 << 10)
    # keep the selective-predicate host path out of the way: these
    # tests pin the DEVICE kernel path
    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")


def test_plan_selects_sort_pairs(cluster):
    segs, _ = cluster
    req = optimize_request(
        parse_pql(
            "SELECT distinctcount(l_extendedprice) FROM lineitem "
            "GROUP BY l_returnflag TOP 10"
        )
    )
    ctx = get_table_context(segs)
    staged = stage_segments(segs, sorted(req.referenced_columns()), ctx=ctx)
    plan = build_static_plan(req, ctx, staged)
    assert plan.on_device
    assert plan.aggs[0].sort_pairs


QUERIES = [
    "SELECT distinctcount(l_extendedprice) FROM lineitem GROUP BY l_returnflag TOP 10",
    "SELECT distinctcount(l_extendedprice) FROM lineitem",
    # exact percentile through the same pair-sort machinery (run-length
    # counts): any cardinality stays on device
    "SELECT percentile50(l_extendedprice), percentile95(l_extendedprice) FROM lineitem",
    "SELECT percentile90(l_extendedprice) FROM lineitem GROUP BY l_returnflag TOP 10",
    "SELECT percentile50(l_extendedprice), distinctcount(l_extendedprice) FROM lineitem "
    "WHERE l_shipmode = 'RAIL' GROUP BY l_linestatus TOP 10",
    "SELECT distinctcount(l_extendedprice), count(*) FROM lineitem "
    "WHERE l_shipmode IN ('RAIL','FOB') GROUP BY l_linestatus TOP 10",
    "SELECT distinctcount(l_extendedprice), sum(l_quantity) FROM lineitem "
    "GROUP BY l_returnflag, l_linestatus TOP 10",
    "SELECT distinctcount(l_extendedprice) FROM lineitem WHERE l_shipdate > '1998-10-01'",
]


def test_sort_path_matches_oracle(cluster):
    segs, oracle = cluster
    ex = QueryExecutor()
    for q in QUERIES:
        req = optimize_request(parse_pql(q))
        req2 = optimize_request(parse_pql(q))
        got = reduce_to_response(req, [ex.execute(segs, req)])
        want = oracle.execute(req2)
        assert _norm(got) == _norm(want), q


def test_cross_server_merge_stays_exact(cluster):
    """Partials from two executors over disjoint segment sets merge to
    the same exact distinct counts (DistinctPartial set semantics ride
    the pair buffers)."""
    segs, oracle = cluster
    q = (
        "SELECT distinctcount(l_extendedprice) FROM lineitem "
        "GROUP BY l_returnflag TOP 10"
    )
    req = optimize_request(parse_pql(q))
    ex = QueryExecutor()
    parts = [ex.execute(segs[:2], req), ex.execute(segs[2:], req)]
    got = reduce_to_response(req, parts)
    want = oracle.execute(optimize_request(parse_pql(q)))
    assert _norm(got) == _norm(want)


def test_overflow_falls_back_to_host(cluster, monkeypatch):
    from pinot_tpu.engine import kernel as kernel_mod

    segs, oracle = cluster
    monkeypatch.setattr(config, "DISTINCT_PAIR_CAP", 64)  # << unique pairs
    kernel_mod.make_table_kernel.cache_clear()
    kernel_mod.make_packed_table_kernel.cache_clear()
    try:
        # the filter keeps the query off the plan-time guaranteed-
        # overflow skip, so this exercises the RUNTIME overflow
        # detection (device pairs buffer too small -> host re-run)
        q = (
            "SELECT distinctcount(l_extendedprice) FROM lineitem "
            "WHERE l_shipdate > '1993-01-01' GROUP BY l_returnflag TOP 10"
        )
        req = optimize_request(parse_pql(q))
        ctx = get_table_context(segs)
        staged = stage_segments(segs, sorted(req.referenced_columns()), ctx=ctx)
        assert build_static_plan(req, ctx, staged).on_device
        got = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
        want = oracle.execute(optimize_request(parse_pql(q)))
        assert _norm(got) == _norm(want)
    finally:
        kernel_mod.make_table_kernel.cache_clear()
        kernel_mod.make_packed_table_kernel.cache_clear()
        clear_staging_cache()


def test_guaranteed_overflow_skips_device(cluster, monkeypatch):
    """With no filter and global cardinality beyond the pair buffer,
    every dictionary value lands in >= 1 pair — the device sort is
    doomed, so the planner goes straight to the host path (the r4
    north-star capture burned 32 minutes on the staged+compiled+sorted
    device attempt before falling back)."""
    segs, oracle = cluster
    monkeypatch.setattr(config, "DISTINCT_PAIR_CAP", 64)
    q = "SELECT distinctcount(l_extendedprice) FROM lineitem GROUP BY l_returnflag TOP 10"
    req = optimize_request(parse_pql(q))
    ctx = get_table_context(segs)
    staged = stage_segments(segs, sorted(req.referenced_columns()), ctx=ctx)
    plan = build_static_plan(req, ctx, staged)
    assert not plan.on_device
    got = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
    want = oracle.execute(optimize_request(parse_pql(q)))
    assert _norm(got) == _norm(want)


def test_trim_path_uses_pair_counts(cluster):
    """>100 groups engages trim ordering, which reads the per-slot
    distinct counts off the pair buffer (_PairsState.counts)."""
    segs, oracle = cluster
    for q in (
        "SELECT distinctcount(l_extendedprice) FROM lineitem GROUP BY l_shipdate TOP 5",
        "SELECT percentile50(l_extendedprice) FROM lineitem GROUP BY l_shipdate TOP 5",
    ):
        req = optimize_request(parse_pql(q))
        got = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
        want = oracle.execute(optimize_request(parse_pql(q)))
        assert _norm(got) == _norm(want), q


def test_mv_sort_pairs_matches_oracle(monkeypatch):
    """MV distinctcount through the pair-emission path (per-entry
    expansion, dedup across repeated values within a row)."""
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.tools.datagen import make_test_schema, random_rows

    schema = make_test_schema(with_mv=True)
    rows = random_rows(schema, 4000, seed=9)
    segs = [
        build_segment(schema, rows[:2000], "testTable", "mv0"),
        build_segment(schema, rows[2000:], "testTable", "mv1"),
    ]
    oracle = ScanQueryProcessor(schema, rows)
    # force the sort path for the MV column's cardinality too
    monkeypatch.setattr(config, "MAX_VALUE_STATE", 1)
    for q in [
        "SELECT distinctcountmv(dimIntMV) FROM testTable",
        "SELECT distinctcountmv(dimIntMV) FROM testTable GROUP BY dimStr TOP 10",
        "SELECT distinctcountmv(dimStrMV), count(*) FROM testTable "
        "WHERE dimInt > 300 GROUP BY dimStr TOP 10",
    ]:
        req = optimize_request(parse_pql(q))
        plan_probe = optimize_request(parse_pql(q))
        got = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
        want = oracle.execute(plan_probe)
        assert _norm(got) == _norm(want), q


def test_sort_pairs_through_block_skip_kernel(cluster, monkeypatch):
    """Zone-map block path + sort-pairs distinct/percentile compose:
    pairs emit from the gathered candidate blocks only."""
    monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", "1024")
    segs, oracle = cluster
    q = (
        "SELECT distinctcount(l_extendedprice), percentile50(l_extendedprice) "
        "FROM lineitem WHERE l_shipdate <= '1992-02-01'"
    )
    req = optimize_request(parse_pql(q))
    part = QueryExecutor().execute(segs, req)
    total = sum(s.num_docs for s in segs)
    # the block path engaged: filter scan cost is O(candidate rows)
    assert part.num_entries_scanned_in_filter < total / 2
    got = reduce_to_response(req, [part])
    want = oracle.execute(optimize_request(parse_pql(q)))
    assert _norm(got) == _norm(want)


def test_sort_pairs_on_mesh_matches_oracle(cluster):
    """The distinct-pairs collective: per-chip compacted buffers
    all_gather and re-merge across the mesh (counts of pairs seen on
    several chips sum) — high-cardinality exact distinct/percentile no
    longer drops to the host under a mesh."""
    import jax

    from pinot_tpu.parallel.multichip import default_mesh

    segs, oracle = cluster
    mesh = default_mesh(jax.devices()[:4])
    ex = QueryExecutor(mesh=mesh)
    for q in (
        "SELECT distinctcount(l_extendedprice) FROM lineitem GROUP BY l_returnflag TOP 10",
        "SELECT percentile50(l_extendedprice), count(*) FROM lineitem "
        "GROUP BY l_linestatus TOP 10",
        "SELECT distinctcount(l_extendedprice) FROM lineitem",
    ):
        req = optimize_request(parse_pql(q))
        got = reduce_to_response(req, [ex.execute(segs, req)])
        want = oracle.execute(optimize_request(parse_pql(q)))
        assert _norm(got) == _norm(want), q


def test_mesh_overflow_forces_host_fallback(cluster, monkeypatch):
    """A chip overflowing its pair buffer must poison the merged
    n_unique so the executor drops to the exact host path instead of
    silently losing pairs."""
    import jax

    from pinot_tpu.engine import kernel as kernel_mod
    from pinot_tpu.parallel.multichip import default_mesh

    segs, oracle = cluster
    monkeypatch.setattr(config, "DISTINCT_PAIR_CAP", 64)
    kernel_mod.make_table_kernel.cache_clear()
    kernel_mod.make_packed_table_kernel.cache_clear()
    try:
        mesh = default_mesh(jax.devices()[:4])
        q = "SELECT distinctcount(l_extendedprice) FROM lineitem GROUP BY l_returnflag TOP 10"
        req = optimize_request(parse_pql(q))
        got = reduce_to_response(req, [QueryExecutor(mesh=mesh).execute(segs, req)])
        want = oracle.execute(optimize_request(parse_pql(q)))
        assert _norm(got) == _norm(want)
    finally:
        kernel_mod.make_table_kernel.cache_clear()
        kernel_mod.make_packed_table_kernel.cache_clear()
        clear_staging_cache()


def test_grouped_hll_sort_pairs(cluster, monkeypatch):
    """Grouped HLL past the dense budget rides the same pair-sort
    machinery ((slot, bucket*64+rho) gids) instead of host-falling-back;
    registers reconstruct exactly at finalize so estimates match the
    oracle bit for bit."""
    segs, oracle = cluster
    monkeypatch.setattr(config, "MAX_VALUE_STATE", 1)  # force sort for HLL too
    for q in (
        "SELECT distinctcounthll(l_extendedprice) FROM lineitem GROUP BY l_returnflag TOP 10",
        "SELECT fasthll(l_shipdate), count(*) FROM lineitem GROUP BY l_shipdate TOP 5",
    ):
        req = optimize_request(parse_pql(q))
        got = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
        want = oracle.execute(optimize_request(parse_pql(q)))
        assert _norm(got) == _norm(want), q


def test_forced_host_is_subset_of_plan_decision(cluster):
    """plan_forced_host must NEVER claim host for a query the full plan
    would run on device (it may be narrower — it sees less than the
    planner — but a false positive silently degrades device queries to
    the host path).  Swept over capacity/overflow/filter combinations
    with shrunken caps so every branch fires."""
    from pinot_tpu.engine.plan import plan_forced_host

    segs, _ = cluster
    ctx = get_table_context(segs)
    queries = [
        "SELECT count(*) FROM lineitem GROUP BY l_returnflag TOP 10",
        "SELECT count(*) FROM lineitem GROUP BY l_extendedprice TOP 10",
        # over MAX_GROUP_CAPACITY a count rides the runs lowering (PR 43); a max has no run form: the host's
        "SELECT max(l_quantity) FROM lineitem GROUP BY l_extendedprice TOP 10",
        "SELECT distinctcount(l_extendedprice) FROM lineitem GROUP BY l_returnflag TOP 10",
        "SELECT distinctcount(l_extendedprice) FROM lineitem "
        "WHERE l_shipdate > '1993-01-01' GROUP BY l_returnflag TOP 10",
        "SELECT distinctcount(l_extendedprice) FROM lineitem",
        "SELECT percentile50(l_extendedprice) FROM lineitem GROUP BY l_shipmode TOP 5",
        "SELECT sum(l_quantity) FROM lineitem",
    ]
    for cap_name, cap_val in [
        (None, None),
        ("MAX_GROUP_CAPACITY", 100),
        ("DISTINCT_PAIR_CAP", 64),
        ("MAX_VALUE_STATE", 256),
    ]:
        # a PRIVATE patcher per case: the shared function-scoped
        # monkeypatch also carries the module's autouse cap shrink,
        # which an undo() would unwind
        with pytest.MonkeyPatch.context() as mp:
            if cap_name is not None:
                mp.setattr(config, cap_name, cap_val)
            forced_seen = 0
            for q in queries:
                req = optimize_request(parse_pql(q))
                forced = plan_forced_host(req, ctx)
                staged = stage_segments(segs, sorted(req.referenced_columns()), ctx=ctx)
                plan = build_static_plan(req, ctx, staged)
                if forced:
                    forced_seen += 1
                    assert not plan.on_device, (cap_name, q)
        if cap_name in ("MAX_GROUP_CAPACITY", "DISTINCT_PAIR_CAP"):
            assert forced_seen > 0, f"{cap_name} shrink should force some hosts"
