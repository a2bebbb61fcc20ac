"""TPU engine tests: sentinel golden values + differential vs the scan
oracle (the QueriesSentinelTest / H2-differential analogs, SURVEY §4)."""
import json
import math

import pytest

from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.engine.reduce import reduce_to_response
from pinot_tpu.pql import parse_pql, optimize_request
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.tools.datagen import make_test_schema, random_rows
from pinot_tpu.tools.query_gen import QueryGenerator
from pinot_tpu.tools.scan_engine import ScanQueryProcessor

SCHEMA = Schema(
    "t",
    dimensions=[
        FieldSpec("city", DataType.STRING),
        FieldSpec("tags", DataType.STRING_ARRAY, single_value=False),
    ],
    metrics=[
        FieldSpec("sales", DataType.INT, FieldType.METRIC),
        FieldSpec("price", DataType.DOUBLE, FieldType.METRIC),
    ],
)

ROWS = [
    {"city": "sf", "tags": ["a", "b"], "sales": 10, "price": 1.5},
    {"city": "sf", "tags": ["b"], "sales": 20, "price": 2.5},
    {"city": "ny", "tags": ["a"], "sales": 30, "price": 3.5},
    {"city": "la", "tags": ["c", "a"], "sales": 40, "price": 4.5},
    {"city": "ny", "tags": ["b", "c"], "sales": 50, "price": 5.5},
]

SEGMENT = build_segment(SCHEMA, ROWS, "t", "s0")
EXECUTOR = QueryExecutor()


def run_engine(pql, segments=None):
    req = optimize_request(parse_pql(pql))
    res = EXECUTOR.execute(segments or [SEGMENT], req)
    return reduce_to_response(req, [res])


def agg_values(resp):
    return [a.value for a in resp.aggregation_results]


# ------------------------------------------------------------- sentinels
def test_count_star():
    assert agg_values(run_engine("SELECT count(*) FROM t")) == [5]


def test_basic_aggs():
    resp = run_engine(
        "SELECT sum(sales), min(sales), max(sales), avg(sales), minmaxrange(sales) FROM t"
    )
    assert agg_values(resp) == [150.0, 10.0, 50.0, 30.0, 40.0]


def test_filters():
    assert agg_values(run_engine("SELECT count(*) FROM t WHERE city = 'sf'")) == [2]
    assert agg_values(run_engine("SELECT count(*) FROM t WHERE city IN ('sf','ny')")) == [4]
    assert agg_values(run_engine("SELECT count(*) FROM t WHERE sales > 20")) == [3]
    assert agg_values(run_engine("SELECT count(*) FROM t WHERE sales BETWEEN 20 AND 40")) == [3]
    assert agg_values(run_engine("SELECT count(*) FROM t WHERE city <> 'sf'")) == [3]
    assert agg_values(run_engine("SELECT count(*) FROM t WHERE city NOT IN ('sf','la')")) == [2]
    assert agg_values(
        run_engine("SELECT count(*) FROM t WHERE city = 'sf' OR sales = 40")
    ) == [3]


def test_mv_filters():
    assert agg_values(run_engine("SELECT count(*) FROM t WHERE tags = 'a'")) == [3]
    assert agg_values(run_engine("SELECT count(*) FROM t WHERE tags <> 'a'")) == [2]


def test_regex_filter():
    assert agg_values(run_engine("SELECT count(*) FROM t WHERE regexp_like(city, '^s')")) == [2]


def test_distinct_and_hll():
    assert agg_values(run_engine("SELECT distinctcount(city) FROM t")) == [3]
    assert agg_values(run_engine("SELECT distinctcountmv(tags) FROM t")) == [3]
    assert agg_values(run_engine("SELECT distinctcounthll(sales) FROM t")) == [5]


def test_percentiles():
    assert agg_values(run_engine("SELECT percentile50(sales) FROM t")) == [30.0]
    assert agg_values(run_engine("SELECT percentile90(sales) FROM t")) == [50.0]


def test_group_by():
    resp = run_engine("SELECT sum(sales) FROM t GROUP BY city TOP 2")
    gr = resp.aggregation_results[0].group_by_result
    assert [(g.group, g.value) for g in gr] == [(["ny"], 80.0), (["la"], 40.0)]


def test_group_by_min_asc():
    resp = run_engine("SELECT min(sales) FROM t GROUP BY city")
    gr = resp.aggregation_results[0].group_by_result
    assert [(g.group[0], g.value) for g in gr] == [("sf", 10.0), ("ny", 30.0), ("la", 40.0)]


def test_group_by_mv():
    resp = run_engine("SELECT count(*) FROM t GROUP BY tags")
    gr = {g.group[0]: g.value for g in resp.aggregation_results[0].group_by_result}
    assert gr == {"a": 3, "b": 3, "c": 2}


def test_group_by_multi():
    resp = run_engine("SELECT sum(sales) FROM t GROUP BY city, tags TOP 100")
    gr = {tuple(g.group): g.value for g in resp.aggregation_results[0].group_by_result}
    assert gr[("sf", "b")] == 30.0
    assert gr[("ny", "c")] == 50.0


def test_mv_aggregation():
    assert agg_values(run_engine("SELECT countmv(tags) FROM t")) == [8]


def test_selection():
    resp = run_engine("SELECT city, sales FROM t LIMIT 3")
    assert resp.selection_results.rows == [["sf", 10], ["sf", 20], ["ny", 30]]


def test_selection_order_by():
    resp = run_engine("SELECT city FROM t ORDER BY sales DESC LIMIT 2")
    assert resp.selection_results.rows == [["ny"], ["la"]]


def test_selection_star():
    resp = run_engine("SELECT * FROM t LIMIT 1")
    assert resp.selection_results.columns == ["city", "tags", "sales", "price"]


def test_empty_filter_result():
    resp = run_engine("SELECT count(*), sum(sales) FROM t WHERE city = 'zz'")
    assert agg_values(resp) == [0, 0.0]


def test_stats():
    resp = run_engine("SELECT count(*) FROM t WHERE city = 'sf'")
    assert resp.num_docs_scanned == 2
    assert resp.total_docs == 5
    assert resp.num_segments_queried == 1


# ------------------------------------------------- differential vs oracle
def _norm(resp):
    # cost carries wall-clock ms (path-dependent): never bit-identical
    return json.dumps(
        {k: v for k, v in resp.to_json().items() if k != "cost"}, sort_keys=True
    )


def _values_close(a, b, tol=1e-6):
    if isinstance(a, dict) and isinstance(b, dict):
        return set(a) == set(b) and all(_values_close(a[k], b[k], tol) for k in a)
    if isinstance(a, list) and isinstance(b, list):
        return len(a) == len(b) and all(_values_close(x, y, tol) for x, y in zip(a, b))
    if isinstance(a, str) and isinstance(b, str):
        try:
            fa, fb = float(a), float(b)
            if math.isinf(fa) or math.isinf(fb):
                return fa == fb
            return abs(fa - fb) <= tol * max(1.0, abs(fa), abs(fb))
        except ValueError:
            return a == b
    return a == b


def _run_differential(num_segments, seed, num_queries=40):
    schema = make_test_schema()
    rows = random_rows(schema, 1200, seed=seed, cardinality=15)
    if num_segments == 1:
        segments = [build_segment(schema, rows, "testTable", "seg0")]
    else:
        chunk = len(rows) // num_segments
        segments = [
            build_segment(
                schema,
                rows[i * chunk : (i + 1) * chunk if i < num_segments - 1 else len(rows)],
                "testTable",
                f"seg{i}",
            )
            for i in range(num_segments)
        ]
    oracle = ScanQueryProcessor(schema, rows)
    gen = QueryGenerator(schema, rows, seed=seed)
    mismatches = []
    for qi in range(num_queries):
        pql = gen.next_query()
        req_e = optimize_request(parse_pql(pql))
        req_o = optimize_request(parse_pql(pql))
        got = reduce_to_response(req_e, [EXECUTOR.execute(segments, req_e)])
        want = oracle.execute(req_o)
        gj, wj = got.to_json(), want.to_json()
        for k in ("timeUsedMs", "cost", "numEntriesScannedInFilter", "numEntriesScannedPostFilter",
                  "numSegmentsQueried", "numServersQueried", "numServersResponded"):
            gj.pop(k, None)
            wj.pop(k, None)
        if not _values_close(gj, wj):
            mismatches.append((pql, gj, wj))
    assert not mismatches, f"{len(mismatches)} mismatches; first: " + json.dumps(
        mismatches[0], indent=2, default=str
    )[:4000]


def test_differential_single_segment():
    _run_differential(1, seed=11)


def test_differential_multi_segment():
    _run_differential(3, seed=23)


def test_differential_more_queries():
    _run_differential(2, seed=47, num_queries=60)


def test_runs_eval_kind_regex_and_large_in():
    """Table-kind leaves with few dictId runs evaluate as interval
    unions (plan eval_kind 'runs'): regex on ordered values, >16-value
    IN lists, and their negations match the oracle."""
    from pinot_tpu.engine.context import get_table_context
    from pinot_tpu.engine.device import stage_segments
    from pinot_tpu.engine.plan import build_static_plan

    schema = make_test_schema(with_mv=True)
    rows = random_rows(schema, 3000, seed=31, cardinality=60)
    segs = [
        build_segment(schema, rows[:1500], "testTable", "r0"),
        build_segment(schema, rows[1500:], "testTable", "r1"),
    ]
    oracle = ScanQueryProcessor(schema, rows)
    in_vals = ", ".join(str(v) for v in range(0, 40))  # 40 points > _MAX_POINTS
    queries = [
        f"SELECT count(*), sum(metInt) FROM testTable WHERE dimInt IN ({in_vals})",
        f"SELECT count(*) FROM testTable WHERE dimInt NOT IN ({in_vals})",
        "SELECT count(*) FROM testTable WHERE REGEXP_LIKE(dimStr, 's1.*')",
        f"SELECT count(*) FROM testTable WHERE dimIntMV IN ({in_vals})",
    ]
    for pql in queries:
        req = optimize_request(parse_pql(pql))
        req2 = optimize_request(parse_pql(pql))
        got = reduce_to_response(req, [EXECUTOR.execute(segs, req)])
        want = oracle.execute(req2)
        gj, wj = got.to_json(), want.to_json()
        for k in ("timeUsedMs", "cost", "numEntriesScannedInFilter", "numEntriesScannedPostFilter",
                  "numSegmentsQueried", "numServersQueried", "numServersResponded"):
            gj.pop(k, None)
            wj.pop(k, None)
        assert _values_close(gj, wj), (pql, gj, wj)

    # the plan actually selected the runs kind for the big IN list
    req = optimize_request(parse_pql(queries[0]))
    ctx = get_table_context(segs)
    staged = stage_segments(segs, sorted(req.referenced_columns()), ctx=ctx)
    plan = build_static_plan(req, ctx, staged)
    kinds = {l.eval_kind for l in plan.leaves}
    assert "runs" in kinds, kinds


def test_matmul_holder_paths_forced(monkeypatch):
    """The MXU one-hot paths (fused group contraction + combined-key
    dense presence/hist holders) are off on the CPU backend by default;
    force them on so CPU CI locks their correctness against the oracle
    (they are the production TPU paths)."""
    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    schema = make_test_schema(with_mv=True)
    rows = random_rows(schema, 2500, seed=55, cardinality=30)
    segs = [
        build_segment(schema, rows[:1250], "testTable", "mm0"),
        build_segment(schema, rows[1250:], "testTable", "mm1"),
    ]
    oracle = ScanQueryProcessor(schema, rows)
    for pql in [
        "SELECT sum(metInt), count(*), avg(metFloat) FROM testTable GROUP BY dimStr TOP 10",
        "SELECT distinctcount(dimInt) FROM testTable GROUP BY dimStr TOP 10",
        "SELECT percentile90(metInt) FROM testTable GROUP BY dimStr TOP 10",
        "SELECT distinctcount(dimInt), percentile50(metInt) FROM testTable",
        "SELECT distinctcountmv(dimIntMV) FROM testTable GROUP BY dimStr TOP 10",
        "SELECT distinctcount(dimLong) FROM testTable WHERE dimInt > 400 GROUP BY dimStr TOP 10",
        "SELECT distinctcounthll(dimLong), fasthll(dimInt) FROM testTable",
        "SELECT distinctcounthllmv(dimIntMV) FROM testTable WHERE dimInt <= 700",
    ]:
        req = optimize_request(parse_pql(pql))
        req2 = optimize_request(parse_pql(pql))
        got = reduce_to_response(req, [EXECUTOR.execute(segs, req)])
        want = oracle.execute(req2)
        gj, wj = got.to_json(), want.to_json()
        for k in ("timeUsedMs", "cost", "numEntriesScannedInFilter", "numEntriesScannedPostFilter",
                  "numSegmentsQueried", "numServersQueried", "numServersResponded"):
            gj.pop(k, None)
            wj.pop(k, None)
        assert _values_close(gj, wj), (pql, gj, wj)


# ------------------------------------------- the two-level (radix) group-by
RADIX_SCHEMA = Schema(
    "rx",
    dimensions=[
        FieldSpec("day", DataType.INT),
        FieldSpec("a", DataType.INT),
        FieldSpec("b", DataType.INT),
        FieldSpec("flag", DataType.INT),
        FieldSpec("tags", DataType.INT_ARRAY, single_value=False),
    ],
    metrics=[
        FieldSpec("price", DataType.DOUBLE, FieldType.METRIC),
        FieldSpec("qty", DataType.INT, FieldType.METRIC),
    ],
)


def _radix_rows(K, n, order, a_card=4, tag_card=8, seed=5):
    """n rows whose ``day`` takes every value of range(K) (so that the
    dense capacity is K), from 16,384 distinct prices that bfloat16
    cannot hold: a sum of values rounded to it misses by 1e-4 and more.
    Rows with ``flag`` 1 all fall on day 7."""
    import random

    rng = random.Random(seed)
    rows = []
    for i in range(n):
        flag = 1 if i >= K and rng.random() < 0.1 else 0
        rows.append({
            "day": 7 if flag else (i if i < K else rng.randrange(K)),
            "a": i % a_card if i < a_card else rng.randrange(a_card),
            "b": i % 256 if i < 256 else rng.randrange(256),
            "flag": flag,
            "tags": sorted({(i % tag_card) if i < tag_card else rng.randrange(tag_card)
                            for _ in range(rng.randint(1, 3))}),
            "price": 901.13 + 6.37 * rng.randrange(16384),
            "qty": rng.randint(1, 50),
        })
    if order == "sorted":
        rows.sort(key=lambda r: r["day"])
    else:
        rng.shuffle(rows)
    return rows


def _group_table(resp):
    """{function: {group tuple: value string}} of a group-by response."""
    return {
        agg["function"]: {tuple(e["group"]): e["value"] for e in agg["groupByResult"]}
        for agg in resp.to_json()["aggregationResults"]
    }


# id: (K, rows, order, datagen kwargs, PQL after FROM, expected lowering, tier)
RADIX_CASES = {
    "k513_sum_sorted": (513, 1500, "sorted", {}, "SELECT sum(price) FROM rx GROUP BY day", "radix", "scan"),
    "k2000_sum_sorted": (2000, 5000, "sorted", {}, "SELECT sum(price) FROM rx GROUP BY day", "radix", "scan"),
    "k2000_two_sums_shuffled": (2000, 5000, "shuffled", {},
                                "SELECT sum(price), sum(qty) FROM rx GROUP BY day", "radix", "scan"),
    "k2000_count": (2000, 5000, "shuffled", {}, "SELECT count(*) FROM rx GROUP BY day", "radix", "scan"),
    "k2000_avg": (2000, 5000, "shuffled", {}, "SELECT avg(price), count(*) FROM rx GROUP BY day", "radix", "scan"),
    "k2000_sum_beside_min": (2000, 5000, "shuffled", {},
                             "SELECT sum(price), min(price), max(qty) FROM rx GROUP BY day", "radix", "scan"),
    "k2000_all_in_one_group": (2000, 5000, "shuffled", {},
                               "SELECT sum(price), count(*) FROM rx WHERE flag = 1 GROUP BY day", "radix", "scan"),
    "k2000_filtered_zone_tier": (2000, 6000, "sorted", {},
                                 "SELECT sum(price), count(*) FROM rx WHERE day BETWEEN 300 AND 420 GROUP BY day",
                                 "radix", "zone"),
    "k2000_empty_match": (2000, 5000, "shuffled", {},
                          "SELECT sum(price), count(*) FROM rx WHERE flag = 1 AND day = 3 GROUP BY day",
                          "radix", "zone"),
    "k2049_sum_shuffled": (2049, 5000, "shuffled", {}, "SELECT sum(price) FROM rx GROUP BY day", "radix", "scan"),
    "k700_multi_value_key": (7, 2500, "shuffled", {"tag_card": 700},
                             "SELECT sum(price), count(*) FROM rx GROUP BY tags", "radix", "scan"),
    "k4200_multi_value_key_pair": (7, 2500, "shuffled", {"tag_card": 600},
                                   "SELECT sum(qty) FROM rx GROUP BY day, tags", "radix", "scan"),
    "bound_65536_two_keys": (7, 3000, "shuffled", {"a_card": 256},
                             "SELECT sum(price), count(*) FROM rx GROUP BY a, b", "radix", "scan"),
    "above_bound_sorted": (7, 3000, "shuffled", {"a_card": 257},
                           "SELECT sum(price), count(*) FROM rx GROUP BY a, b", "radix", "scan"),
    "k256_onehot_untouched": (256, 1500, "shuffled", {}, "SELECT sum(price), count(*) FROM rx GROUP BY day",
                              "onehot", "scan"),
}


def _forced_and_scattered(monkeypatch, name, rows, pql, split=None):
    """One group-by over two segments of ``rows``, with the contractions
    forced on (``PINOT_TPU_GROUPBY_MATMUL=1``) and on the scatter: what
    each launch was (plan, tier, lowering, where the operands are
    built, the kernel's states) and each reply, beside the scan oracle's."""
    from pinot_tpu.engine import kernel as kernel_mod

    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")  # the host's postings tier would answer the selective shapes
    split = len(rows) // 2 if split is None else split
    segs = [build_segment(RADIX_SCHEMA, rows[:split], "rx", f"{name}0"),
            build_segment(RADIX_SCHEMA, rows[split:], "rx", f"{name}1")]
    oracle = ScanQueryProcessor(RADIX_SCHEMA, rows).execute(optimize_request(parse_pql(pql)))

    seen = {}
    run_kernel = QueryExecutor._run_kernel

    def spy(self, kernel, args, plan, staged, digest, block_ids, *rest, **kw):
        outs = run_kernel(self, kernel, args, plan, staged, digest, block_ids, *rest, **kw)
        seen.update(plan=plan, outs=outs, tier="scan" if block_ids is None else "zone",
                    lowering=kernel_mod.groupby_lowering(plan), operands=kernel_mod.groupby_operands(plan))
        return outs

    monkeypatch.setattr(QueryExecutor, "_run_kernel", spy)

    def forget_programs():
        for cached in ("make_table_kernel", "make_packed_table_kernel", "make_block_table_kernel",
                       "make_packed_block_table_kernel"):
            getattr(kernel_mod, cached).cache_clear()

    def run(force):
        monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", force)
        forget_programs()  # the caches key on the plan, which does not state the switch
        req = optimize_request(parse_pql(pql))
        resp = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
        return dict(seen, reply=resp)

    try:
        return run("1"), run("0"), oracle
    finally:
        forget_programs()


def _assert_states_and_reply(forced, scattered, oracle):
    """Occupancy and every count state are the scatter's, exactly; the
    reply is the float64 oracle's: groups, counts and ``numDocsScanned``
    exact, sums within 2e-6."""
    a, b = forced["outs"], scattered["outs"]
    assert (a["gb_presence"] == b["gb_presence"]).all()
    assert int(a["num_docs"]) == int(b["num_docs"])
    for i, agg in enumerate(forced["plan"].aggs):
        if agg.base == "count":
            assert a[f"gb_{i}"].dtype == b[f"gb_{i}"].dtype and (a[f"gb_{i}"] == b[f"gb_{i}"]).all()
        elif agg.base == "avg":
            assert (a[f"gb_{i}"][1] == b[f"gb_{i}"][1]).all()
    assert forced["reply"].to_json()["numDocsScanned"] == oracle.to_json()["numDocsScanned"]
    got, want = _group_table(forced["reply"]), _group_table(oracle)
    assert set(got) == set(want)
    for fn in want:
        assert set(got[fn]) == set(want[fn]), fn
        for group, value in want[fn].items():
            if fn.startswith(("count", "distinctcount")):
                assert got[fn][group] == value, (fn, group)
            else:
                g, w = float(got[fn][group]), float(value)
                assert abs(g - w) <= 2e-6 * max(abs(w), 1.0), (fn, group, g, w)


@pytest.mark.parametrize("case", sorted(RADIX_CASES))
def test_radix_groupby_forced(monkeypatch, case):
    """Dense group-bys above the one-level gate ride the two-level
    (radix-128) contraction on the chip, above ``RADIX_GROUP_CAP`` over
    the rows in key order; forced on here so that CPU CI holds it to
    the oracle: the lowering the gate names, occupancy equal to the
    scatter's, counts exact, and float32 sums within 2e-6 of a float64
    sum over prices that bfloat16 cannot hold."""
    from pinot_tpu.engine import kernel as kernel_mod

    K, n, order, gen, pql, lowering, tier = RADIX_CASES[case]
    monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", "256")
    forced, scattered, oracle = _forced_and_scattered(
        monkeypatch, case, _radix_rows(K, n, order, **gen), pql + " TOP 100000")
    assert forced["lowering"] == lowering and scattered["lowering"] == "scatter"
    assert forced["tier"] == scattered["tier"] == tier
    cap = forced["plan"].group_by.capacity
    assert forced["operands"] == ("sorted" if cap > kernel_mod.RADIX_GROUP_CAP else "staged")
    assert (cap > kernel_mod.RADIX_GROUP_CAP) == case.startswith("above_bound") and scattered["operands"] == "staged"
    if case.startswith("bound"):
        assert cap == kernel_mod.RADIX_GROUP_CAP
    _assert_states_and_reply(forced, scattered, oracle)
    if case == "k2000_empty_match":
        assert not any(_group_table(oracle).values()) and not forced["outs"]["gb_presence"].any()


# id: (rows of _radix_rows, PQL after FROM, tier, rows a step of the sorted
# contraction).  GROUP BY a, b has a_card x 256 cells; rows with ``flag`` 1
# all fall on (a, b) = (3, 5), about a tenth of them: a run of one key
SORTED_CASES = {
    "uniform_keys": (dict(K=7, n=3000, order="shuffled", a_card=300),
                     "SELECT sum(price), count(*) FROM rx WHERE flag = 0 GROUP BY a, b", "scan", 256),
    "every_row_one_key": (dict(K=7, n=6000, order="shuffled", a_card=300),
                          "SELECT sum(price), count(*) FROM rx WHERE flag = 1 GROUP BY a, b", "scan", 128),
    # 2^20 keys and 5,000 rows in blocks of 512: a block spans some 100,000 keys, a dozen windows of 8,192
    "few_rows_over_the_whole_range_k_2_20": (dict(K=5000, n=5000, order="shuffled", a_card=4096),
                                             "SELECT sum(price), count(*) FROM rx GROUP BY a, b", "scan", 512),
    # the run of (3, 5) is some 300 rows in each segment, a block 128: it fills blocks and crosses their edges
    "a_run_crosses_block_edges": (dict(K=7, n=6000, order="shuffled", a_card=300),
                                  "SELECT sum(price), count(*) FROM rx GROUP BY a, b", "scan", 128),
    "empty_match": (dict(K=7, n=3000, order="shuffled", a_card=300),
                    "SELECT sum(price), count(*) FROM rx WHERE flag = 1 AND day = 3 GROUP BY a, b", "scan", 256),
    "multi_value_key": (dict(K=7, n=2500, order="shuffled", a_card=300, tag_card=300),
                        "SELECT sum(price), count(*) FROM rx GROUP BY a, tags", "scan", 256),
    # the slots rule: sum(price) and avg(price) read one row, the avg's count the occupancy: m = 3
    "two_sums_and_an_avg_share_rows": (dict(K=7, n=3000, order="shuffled", a_card=300),
                                       "SELECT sum(price), sum(qty), avg(price), count(*) FROM rx GROUP BY a, b",
                                       "scan", 256),
    "max_beside_a_sum": (dict(K=7, n=3000, order="shuffled", a_card=300),
                         "SELECT sum(price), max(qty), min(price) FROM rx GROUP BY a, b", "scan", 256),
    # 65,537 is prime: one key column of that many values
    "k_one_over_the_bound": (dict(K=65537, n=66000, order="shuffled"),
                             "SELECT sum(price), count(*) FROM rx GROUP BY day", "scan", 8192),
    # a filter on the sorted column: the zone tier's loop in place sorts a block of rows a step
    "zone_tier_in_place": (dict(K=2000, n=6000, order="sorted", a_card=300),
                           "SELECT sum(price), count(*) FROM rx WHERE day BETWEEN 300 AND 420 GROUP BY a, b",
                           "zone", 256),
}


@pytest.mark.parametrize("case", sorted(SORTED_CASES))
def test_sorted_groupby_forced(monkeypatch, case):
    """A dense group-by over more keys than ``RADIX_GROUP_CAP`` sorts its
    rows by group id and contracts each block over a window of keys on
    the chip (``groupby_operands`` 'sorted'); forced on here and held to
    the scatter's states (occupancy and counts exact) and the float64
    oracle's reply (sums within 2e-6).  ``min`` and ``max`` keep
    ``_group_state``; the CPU's own answer stays the scatter."""
    from pinot_tpu.engine import kernel as kernel_mod

    gen, pql, tier, block = SORTED_CASES[case]
    rows = _radix_rows(**gen)
    for row in rows:
        if row["flag"]:
            row["a"], row["b"] = 3, 5
    monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", "256")
    monkeypatch.setattr(kernel_mod, "_SORTED_BLOCK", block)
    forced, scattered, oracle = _forced_and_scattered(monkeypatch, case, rows, pql + " TOP 2000000")
    cap = forced["plan"].group_by.capacity
    assert cap > kernel_mod.RADIX_GROUP_CAP and forced["tier"] == scattered["tier"] == tier
    assert (forced["lowering"], forced["operands"]) == ("radix", "sorted")
    assert (scattered["lowering"], scattered["operands"]) == ("scatter", "staged")
    if case.startswith("k_one_over"):
        assert cap == kernel_mod.RADIX_GROUP_CAP + 1
    if case.endswith("k_2_20"):
        assert cap == 1 << 20
    if case == "zone_tier_in_place":
        assert kernel_mod.zone_blocks(forced["plan"]) == "inplace"
    if case == "two_sums_and_an_avg_share_rows":
        assert kernel_mod._contraction_slots(forced["plan"]) == ({0: [1], 1: [2], 2: [1, 0], 3: [0]}, 3)
    _assert_states_and_reply(forced, scattered, oracle)
    live = int(forced["outs"]["gb_presence"].sum())
    if case == "every_row_one_key":
        assert live == 1
    elif case == "empty_match":
        assert live == 0 and not any(_group_table(oracle).values())
    else:
        assert live > 100


# id: (rows of _radix_rows, PQL, tier, what the case sets: the zone tier's
# block, the contraction's block, where the two segments split)
LOOP_CASES = {
    # the open cell's K=6 shape: a docrange leaf on the sorted column, two key columns, three sums and count(*)
    "k6_docrange_two_keys": (dict(K=400, n=3000, order="sorted", a_card=3),
                             "SELECT sum(price), sum(qty), sum(b), count(*) FROM rx WHERE day <= 300 "
                             "GROUP BY a, flag TOP 100", "scan", {"chunk": 512}),
    # upstream Q6's: IN + between, K = 7, TOP 10
    "q6_in_between_top10": (dict(K=400, n=3000, order="shuffled", a_card=7),
                            "SELECT sum(price) FROM rx WHERE a IN (1, 2) AND day BETWEEN 100 AND 250 "
                            "GROUP BY a TOP 10", "scan", {"chunk": 512}),
    "avg_pair_state": (dict(K=400, n=3000, order="shuffled", a_card=5),
                       "SELECT avg(price), avg(qty), count(*) FROM rx GROUP BY a TOP 100", "scan", {}),
    "k16_two_columns": (dict(K=400, n=3000, order="shuffled", a_card=16),
                        "SELECT sum(price), count(*) FROM rx WHERE day < 350 GROUP BY a TOP 1000", "scan", {"chunk": 512}),
    "cells_at_the_gate": (dict(K=400, n=3000, order="shuffled", a_card=32),
                          "SELECT sum(qty), count(*) FROM rx GROUP BY a TOP 1000", "scan", {}),
    "rows_no_multiple_of_the_block": (dict(K=400, n=3000, order="sorted", a_card=3),
                                      "SELECT sum(price), count(*) FROM rx WHERE day <= 300 GROUP BY a, flag TOP 100",
                                      "scan", {"chunk": 96}),
    "zone_tier_gathered_view": (dict(K=2000, n=6000, order="sorted", a_card=6),
                                "SELECT sum(price), count(*) FROM rx WHERE day BETWEEN 300 AND 420 GROUP BY a TOP 100",
                                "zone", {"zone_block": "256", "chunk": 640}),
    "two_segments_of_unequal_length": (dict(K=400, n=3000, order="sorted", a_card=3),
                                       "SELECT sum(price), count(*) FROM rx WHERE day <= 300 GROUP BY a, flag TOP 100",
                                       "scan", {"split": 700, "chunk": 256}),
    "empty_match": (dict(K=400, n=3000, order="shuffled", a_card=6),
                    "SELECT sum(price), count(*) FROM rx WHERE flag = 1 AND day = 3 GROUP BY a TOP 100", "scan", {}),
}


@pytest.mark.parametrize("case", sorted(LOOP_CASES))
def test_onehot_groupby_operands_built_in_the_loop(monkeypatch, case):
    """Group-bys of up to _LOOP_CELLS (group, column) cells whose every
    output adds over blocks of rows build mask, key and weight columns
    inside the row loop (``groupby_operands`` 'loop'): forced on here and
    held to the scatter's states and the oracle's reply."""
    from pinot_tpu.engine import kernel as kernel_mod

    gen, pql, tier, sets = LOOP_CASES[case]
    if "zone_block" in sets:
        monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", sets["zone_block"])
    if "chunk" in sets:
        monkeypatch.setattr(kernel_mod, "_MATMUL_CHUNK", sets["chunk"])
    rows = _radix_rows(**gen)
    forced, scattered, oracle = _forced_and_scattered(monkeypatch, case, rows, pql, split=sets.get("split"))
    assert (forced["lowering"], forced["operands"]) == ("onehot", "loop")
    assert (scattered["lowering"], scattered["operands"]) == ("scatter", "staged")
    assert forced["tier"] == scattered["tier"] == tier
    plan = forced["plan"]
    cells = plan.group_by.capacity * kernel_mod._contraction_slots(plan)[1]
    assert cells <= kernel_mod._LOOP_CELLS
    if case in ("k6_docrange_two_keys", "rows_no_multiple_of_the_block", "two_segments_of_unequal_length"):
        assert [leaf.eval_kind for leaf in plan.leaves] == ["docrange"]
        assert plan.group_by.capacity == 6
    if case == "cells_at_the_gate":
        assert cells == kernel_mod._LOOP_CELLS
    _assert_states_and_reply(forced, scattered, oracle)
    if case == "empty_match":
        assert not any(_group_table(oracle).values()) and not forced["outs"]["gb_presence"].any()
    else:
        assert any(_group_table(oracle).values())


# group-bys at or under the one-level gate with an output that does not add over
# blocks of rows, or with more cells than the row loop takes
STAGED_CASES = {
    "k64_two_columns": (dict(a_card=64), "SELECT sum(price), count(*) FROM rx GROUP BY a TOP 1000"),
    "k512_the_one_level_gate": (dict(a_card=512), "SELECT sum(price), count(*) FROM rx GROUP BY a TOP 1000"),
    "min_max_beside_a_sum": (dict(a_card=6), "SELECT sum(price), min(price), max(qty) FROM rx GROUP BY a TOP 100"),
    "grouped_distinctcount": (dict(a_card=6), "SELECT distinctcount(b), sum(price) FROM rx GROUP BY a TOP 100"),
    "multi_value_key": (dict(tag_card=8), "SELECT sum(price), count(*) FROM rx GROUP BY tags TOP 100"),
}


@pytest.mark.parametrize("case", sorted(STAGED_CASES) + ["selection_part"])
def test_onehot_groupby_keeps_staged_operands(monkeypatch, case):
    """min, max, a grouped distinctcount, a multi-value key and a
    selection part do not add over blocks of rows, and 128 cells are more
    than the row loop takes: the predicate says 'staged', the one-level
    contraction is fed whole-segment operands as before, and the
    answers hold."""
    import dataclasses

    from pinot_tpu.engine import kernel as kernel_mod

    rows_of = lambda **gen: _radix_rows(400, 4000, "shuffled", **gen)
    if case == "selection_part":
        # no PQL states both parts: hand a loop-built plan the selection of a selection query
        forced, _, _ = _forced_and_scattered(monkeypatch, case, rows_of(a_card=6),
                                             "SELECT sum(price) FROM rx GROUP BY a TOP 100")
        sel, _, _ = _forced_and_scattered(monkeypatch, case, rows_of(a_card=6),
                                          "SELECT a, price FROM rx ORDER BY price LIMIT 5")
        assert sel["plan"].selection is not None and sel["operands"] is None
        monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")  # the predicate reads the switch
        assert kernel_mod.groupby_operands(forced["plan"]) == "loop"
        both = dataclasses.replace(forced["plan"], selection=sel["plan"].selection)
        assert kernel_mod.groupby_lowering(both) == "onehot" and kernel_mod.groupby_operands(both) == "staged"
        return
    gen, pql = STAGED_CASES[case]
    forced, scattered, oracle = _forced_and_scattered(monkeypatch, case, rows_of(**gen), pql)
    assert (forced["lowering"], forced["operands"]) == ("onehot", "staged")
    _assert_states_and_reply(forced, scattered, oracle)


def test_grouped_hll_mxu_contraction(monkeypatch):
    """The grouped-HLL occupancy contraction (small group spaces) vs
    the oracle — the cap is raised and kernel caches cleared so the
    branch PROVABLY executes (the default gate admits capacity <= 16)."""
    from pinot_tpu.engine import kernel as kernel_mod

    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    monkeypatch.setattr(kernel_mod, "_MATMUL_HLL_CAP", 1 << 24)
    kernel_mod.make_table_kernel.cache_clear()
    kernel_mod.make_packed_table_kernel.cache_clear()
    try:
        schema = make_test_schema(with_mv=True)
        rows = random_rows(schema, 600, seed=66, cardinality=5)
        segs = [
            build_segment(schema, rows[:300], "testTable", "hm0"),
            build_segment(schema, rows[300:], "testTable", "hm1"),
        ]
        oracle = ScanQueryProcessor(schema, rows)
        for pql in [
            "SELECT distinctcounthll(dimLong) FROM testTable GROUP BY dimStr TOP 15",
            "SELECT fasthllmv(dimIntMV), count(*) FROM testTable GROUP BY dimStr TOP 15",
        ]:
            req = optimize_request(parse_pql(pql))
            req2 = optimize_request(parse_pql(pql))
            got = reduce_to_response(req, [EXECUTOR.execute(segs, req)])
            want = oracle.execute(req2)
            gj, wj = got.to_json(), want.to_json()
            for k in ("timeUsedMs", "cost", "numEntriesScannedInFilter", "numEntriesScannedPostFilter",
                      "numSegmentsQueried", "numServersQueried", "numServersResponded"):
                gj.pop(k, None)
                wj.pop(k, None)
            assert _values_close(gj, wj), (pql, gj, wj)
    finally:
        kernel_mod.make_table_kernel.cache_clear()
        kernel_mod.make_packed_table_kernel.cache_clear()
        from pinot_tpu.engine.device import clear_staging_cache

        clear_staging_cache()


def test_regex_table_cache_and_qinput_cache(monkeypatch):
    """Repeated regex queries scan the dictionary once (plan._regex_tables
    LRU) and repeated identical queries reuse device-resident inputs
    (executor query-input cache) — both per-query upload/scan costs are
    paid once on a served workload."""
    from pinot_tpu.engine import plan as plan_mod

    plan_mod._regex_tables.clear()
    calls = {"n": 0}
    real = plan_mod.match_table

    def counting(leaf, d, card_pad):
        calls["n"] += 1
        return real(leaf, d, card_pad)

    monkeypatch.setattr(plan_mod, "match_table", counting)
    ex = QueryExecutor()
    req = optimize_request(parse_pql("SELECT count(*) FROM t WHERE regexp_like(city, '^s')"))
    r1 = ex.execute([SEGMENT], req)
    first = calls["n"]
    assert first >= 1
    r2 = ex.execute([SEGMENT], req)
    assert calls["n"] == first  # second query: all regex tables cached
    assert reduce_to_response(req, [r1]).aggregation_results[0].value == \
        reduce_to_response(req, [r2]).aggregation_results[0].value == 2

    # the device-input cache is populated and keyed by plan+content
    assert len(ex._qinput_cache) >= 1


def test_having_engine_sentinel():
    """Direct engine+reduce HAVING: groups failing the predicate drop
    from every agg list (SQL semantics), exact sentinel values."""
    resp = run_engine(
        "SELECT sum(sales), count(*) FROM t GROUP BY city HAVING sum(sales) > 35 TOP 10"
    )
    by_city = {
        tuple(g.group)[0]: (g.value, None)
        for g in resp.aggregation_results[0].group_by_result
    }
    # sums: sf=30, ny=80, la=40 -> only ny and la pass
    assert set(by_city) == {"ny", "la"}
    counts = {
        tuple(g.group)[0]: g.value
        for g in resp.aggregation_results[1].group_by_result
    }
    assert set(counts) == {"ny", "la"}  # count list filtered too
    assert float(counts["ny"]) == 2 and float(counts["la"]) == 1


def test_grouped_hll_three_lowerings_bit_identical(monkeypatch):
    """The grouped-HLL matmul / packed-sort / scatter lowerings must be
    interchangeable: same registers, same estimates, byte-identical
    responses (the sort path's sum of each sorted run's last rank is
    the replacement for scatter-max, under the tests' switch as the
    matmul is: the CPU's own answer is the scatter; the matmul
    occupancy is the small-capacity fast path)."""
    from pinot_tpu.engine import kernel as kernel_mod
    from pinot_tpu.engine.device import clear_staging_cache

    schema = make_test_schema(with_mv=True)
    rows = random_rows(schema, 3000, seed=77, cardinality=40)
    segs = [
        build_segment(schema, rows[:1500], "testTable", "hl0"),
        build_segment(schema, rows[1500:], "testTable", "hl1"),
    ]
    pqls = [
        "SELECT distinctcounthll(dimLong) FROM testTable GROUP BY dimStr TOP 10",
        "SELECT fasthll(dimLong), count(*) FROM testTable "
        "GROUP BY dimStr, dimInt TOP 12",
    ]
    variants = {
        # (GROUPBY_MATMUL, _MATMUL_HLL_CAP, _HLL_SORT_CAP) -> path
        # 1<<25 covers BOTH queries' K = capacity * 16384 (the two-dim
        # group space is 40*39=1560 -> K ~= 25.6M) so the matmul
        # variant genuinely takes the matmul lowering for each
        "matmul": ("1", 1 << 25, 1 << 16),
        "sort": ("1", 1 << 18, 1 << 16),
        "scatter": ("0", 1 << 18, 0),
    }
    results = {}
    monkeypatch.setenv("PINOT_TPU_HLL_PRESENCE", "0")  # the register streams: 40 values would ride a presence holder
    summed, run_ends = [], kernel_mod._hll_sorted_registers
    monkeypatch.setattr(kernel_mod, "_hll_sorted_registers", lambda *a: summed.append(name) or run_ends(*a))
    try:
        for name, (mm, hll_cap, sort_cap) in variants.items():
            monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", mm)
            monkeypatch.setattr(kernel_mod, "_MATMUL_HLL_CAP", hll_cap)
            monkeypatch.setattr(kernel_mod, "_HLL_SORT_CAP", sort_cap)
            kernel_mod.make_table_kernel.cache_clear()
            kernel_mod.make_packed_table_kernel.cache_clear()
            clear_staging_cache()
            out = []
            for q in pqls:
                req = optimize_request(parse_pql(q))
                resp = reduce_to_response(req, [QueryExecutor().execute(segs, req)])
                assert not resp.exceptions, (name, q, resp.exceptions)
                out.append(_norm(resp))
            results[name] = out
    finally:
        kernel_mod.make_table_kernel.cache_clear()
        kernel_mod.make_packed_table_kernel.cache_clear()
        clear_staging_cache()
    assert set(summed) == {"sort"}  # the variant that is named for it, and no other, took the sorted form
    assert results["matmul"] == results["sort"] == results["scatter"]
