"""Arithmetic inside an aggregate (PR 34): TPC-H Q1 and Q6 as the
specification writes them, and seeded random expressions, through broker
and server against the benchmark's plain reference
(``benchmark/reference_tpch_spec.py``: numpy, float64, nothing of the
program); the same plans on the host tier and under the shadow auditor;
the slots a dense group-by's aggregates share; what tells two
expressions apart; what is refused, by name."""
import importlib.util
import json
import os
import random
import time

import jax
import numpy as np
import pytest

from pinot_tpu.common.request import expr_text
from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.engine import kernel as kernel_mod, ladder
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.engine.mesh import build_topology
from pinot_tpu.engine.plandigest import plan_shape_digest
from pinot_tpu.engine.reduce import reduce_to_response
from pinot_tpu.engine.rescache import ResultCache
from pinot_tpu.pql import PqlParseError, optimize_request, parse_pql
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.tools.cluster_harness import InProcessCluster, single_server_broker
from pinot_tpu.tools.datagen import lineitem_schema, random_rows, synthetic_lineitem_segment

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SUM_RTOL = 1e-5  # float64 on the CPU: the gap is the reply's five decimals


def _load(path: str):
    spec = importlib.util.spec_from_file_location("expr_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


spec_ref = _load(os.path.join(BENCH, "reference_tpch_spec.py"))
SHAPES = {s["name"]: s for s in json.load(open(os.path.join(BENCH, "traffic", "tpch_q1q6_closed.json")))["shapes"]}
Q1 = spec_ref.render_pql("lineitem", SHAPES["q1"])
Q6 = spec_ref.render_pql("lineitem", SHAPES["q6"])


def test_the_cell_sends_q1_and_q6_as_the_specification_writes_them():
    assert Q1 == (
        "SELECT sum(l_quantity), sum(l_extendedprice), sum(l_extendedprice*(1-l_discount)), "
        "sum(l_extendedprice*(1-l_discount)*(1+l_tax)), avg(l_quantity), avg(l_extendedprice), avg(l_discount), "
        "count(*) FROM lineitem WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag, l_linestatus TOP 10"
    )
    assert Q6 == (
        "SELECT sum(l_extendedprice*l_discount) FROM lineitem WHERE l_shipdate >= '1994-01-01' AND "
        "l_shipdate < '1995-01-01' AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24"
    )


@pytest.fixture(scope="module")
def lineitem_segments():
    return [synthetic_lineitem_segment(6000, seed=3400 + i, name=f"li{i}") for i in range(4)]


@pytest.fixture(scope="module")
def lineitem_reference(lineitem_segments):
    ref = spec_ref.Reference(SHAPES)
    for seg in lineitem_segments:
        ref.add(seg)
    return ref


def forget_programs():
    kernel_mod.make_table_kernel.cache_clear()
    kernel_mod.make_packed_table_kernel.cache_clear()


@pytest.fixture
def contractions_forced(monkeypatch):
    """The chip's group-by lowerings on the CPU (the row loop for Q1)."""
    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    forget_programs()
    yield
    forget_programs()


def held(reply: dict, shape: dict, ref, name: str) -> dict:
    got = spec_ref.compare(reply, shape, ref.answers[name], ref.rows)
    assert got["count_errors"] == got["key_errors"] == got["reply_errors"] == 0, (got, reply.get("exceptions"))
    assert got["sum_gap"] <= SUM_RTOL, got
    return got


# -- Q1 and Q6 through broker and server --------------------------------------

PLACEMENTS = {
    "one_device": lambda: {},
    "mesh_1x4": lambda: {"topology": build_topology(jax.devices()[:4], 1, 4)},
}


@pytest.mark.parametrize("lowering", ["scatter", "loop"])
@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
def test_q1_and_q6_equal_the_reference(lineitem_segments, lineitem_reference, placement, lowering, monkeypatch):
    if lowering == "loop":
        monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    forget_programs()
    broker = single_server_broker("lineitem", lineitem_segments, **PLACEMENTS[placement]())
    server = broker.local_servers[0]
    try:
        for name, pql in (("q1", Q1), ("q6", Q6)):
            resp = broker.handle_pql(pql, trace=True)
            reply = resp.to_json()
            held(reply, SHAPES[name], lineitem_reference, name)
            assert reply["cost"]["exprAggs"] == (2 if name == "q1" else 1)
            (launch,) = [s for s in resp.trace_info["scopes"][server.name] if s["span"] == "laneDispatch"]
            if name == "q1":
                assert launch["tags"]["expr"] == 2 and launch["tags"]["cells"] == 36
                assert launch["tags"]["operands"] == ("loop" if lowering == "loop" else "staged")
            else:
                assert launch["tags"]["expr"] == 1 and "cells" not in launch["tags"]
        names = [a["function"] for a in broker.handle_pql(Q1).to_json()["aggregationResults"]]
        assert names[2:4] == ["sum_l_extendedprice*(1-l_discount)", "sum_l_extendedprice*(1-l_discount)*(1+l_tax)"]
        assert server.metrics.meter("agg.expr.device").count == 3 and server.metrics.meter("agg.expr.host").count == 0
        # Q1 would take 11 rows unshared (1 + 4 sums + 3 avgs of 2) and takes 6
        assert server.metrics.meter("groupby.slots.shared").count == 2 * 5
        assert server.executor.healing_stats()["hostFailovers"] == 0
    finally:
        server.shutdown()
        forget_programs()


def test_q1_and_q6_over_the_brokers_http_port(tmp_path, lineitem_segments, lineitem_reference, contractions_forced):
    import urllib.request

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path), http=True)
    try:
        physical = cluster.add_offline_table(lineitem_schema())
        for seg in lineitem_segments:
            cluster.upload(physical, seg)
        for name, pql in (("q1", Q1), ("q6", Q6)):
            req = urllib.request.Request(f"http://{cluster.http.host}:{cluster.http.port}/query",
                                         data=json.dumps({"pql": pql}).encode(), method="POST")
            with urllib.request.urlopen(req, timeout=120) as f:
                reply = json.loads(f.read())
            held(reply, SHAPES[name], lineitem_reference, name)
            assert not reply["cost"].get("segmentsHost") and reply["cost"]["deviceMs"] > 0
        explained = cluster.query("EXPLAIN PLAN FOR " + Q6).to_json()["explain"]["servers"][0]
        assert explained["expressions"] == [{"aggregate": "sum", "expression": "l_extendedprice*l_discount",
                                             "columns": ["l_extendedprice", "l_discount"]}]
    finally:
        cluster.stop()


# -- the host tier, the failover and the auditor's oracle ----------------------

def _payload(request, result) -> list:
    return reduce_to_response(request, [result]).to_json()["aggregationResults"]


@pytest.mark.parametrize("name", ["q1", "q6"])
def test_host_oracle_and_failover_answer_in_float64(lineitem_segments, lineitem_reference, name, monkeypatch):
    pql = {"q1": Q1, "q6": Q6}[name]
    request = optimize_request(parse_pql(pql))
    executor = QueryExecutor()
    oracle = executor.execute_host_oracle(lineitem_segments, request)
    reply = reduce_to_response(request, [oracle]).to_json()
    reply["cost"] = {}  # a host answer by design: what is held here is its arithmetic
    held(dict(reply, numServersQueried=0, numServersResponded=0), SHAPES[name], lineitem_reference, name)
    # the failover: the device section fails for good, the same implementation answers
    monkeypatch.setattr(QueryExecutor, "_device_section",
                        lambda self, *a, **k: (_ for _ in ()).throw(RuntimeError("INTERNAL: device lost")))
    healed = executor.execute(lineitem_segments, request)
    assert healed.cost["segmentsHost"] == len(lineitem_segments) and healed.cost["exprAggs"] >= 1
    assert _payload(request, healed) == _payload(request, oracle)
    assert executor.metrics.meter("agg.expr.host").count == 1 and executor.metrics.meter("agg.expr.device").count == 0


def test_shadow_auditor_re_derives_expressions_without_divergence(tmp_path, lineitem_segments, monkeypatch,
                                                                  contractions_forced):
    monkeypatch.setenv("PINOT_TPU_AUDIT_SAMPLE_N", "1")
    monkeypatch.setenv("PINOT_TPU_AUDIT_BUDGET_PER_S", "1000")
    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    try:
        physical = cluster.add_offline_table(lineitem_schema())
        for seg in lineitem_segments:
            cluster.upload(physical, seg)
        metrics = cluster.servers[0].metrics
        for pql in (Q1, Q6, Q1):
            assert not cluster.query(pql).exceptions
        deadline = time.monotonic() + 60
        while metrics.meter("audit.samples").count < 3:
            assert time.monotonic() < deadline, metrics.snapshot()["meters"]
            time.sleep(0.02)
        assert metrics.meter("audit.divergences").count == 0 and metrics.meter("audit.errors").count == 0
    finally:
        cluster.stop()


# -- seeded random expressions over random small tables ------------------------

RANDOM_SCHEMA = Schema(
    "exprT",
    dimensions=[FieldSpec("d1", DataType.STRING), FieldSpec("d2", DataType.INT)],
    metrics=[FieldSpec("m1", DataType.INT, FieldType.METRIC), FieldSpec("m2", DataType.DOUBLE, FieldType.METRIC),
             FieldSpec("m3", DataType.FLOAT, FieldType.METRIC), FieldSpec("m4", DataType.LONG, FieldType.METRIC)],
)
METRICS = ["m1", "m2", "m3", "m4", "d2"]


def random_expression(rng: random.Random, depth: int = 0) -> str:
    roll = rng.random()
    if depth >= 3 or roll < 0.3:
        return rng.choice(METRICS) if depth == 0 or rng.random() < 0.7 else rng.choice(["1", "0.5", "2.25", "3e-2", "7"])
    if roll < 0.4:
        inner = random_expression(rng, depth + 1)
        return "-(" + inner + ")" if inner.startswith("-") else "-" + inner  # ``--`` opens a comment
    if roll < 0.55:
        return "(" + random_expression(rng, depth + 1) + ")"
    op = rng.choice(["+", "-", "*", "*"])
    right = random_expression(rng, depth + 1)
    return (random_expression(rng, depth + 1) + rng.choice(["", " "]) + op + rng.choice(["", " "])
            + ("(" + right + ")" if right.startswith("-") and op == "-" else right))


def random_shape(rng: random.Random, m1_values: list) -> dict:
    def expression():
        while True:
            text = random_expression(rng)
            if spec_ref.expr_columns(spec_ref.parse_expr(text)):  # an aggregate has to read a column
                return text

    aggs = [[rng.choice(["sum", "avg"]), {"expr": expression()}] for _ in range(rng.randint(1, 3))]
    aggs.insert(rng.randint(0, len(aggs)), [rng.choice(["sum", "avg"]), rng.choice(METRICS)])
    if rng.random() < 0.5:
        aggs.append(["count", "*"])
    shape = {"aggs": aggs}
    if rng.random() < 0.6:
        shape["filter"] = [["m1", rng.choice(["<=", ">="]), m1_values[rng.randint(len(m1_values) // 4, len(m1_values) // 2)]]]
    if rng.random() < 0.6:
        shape.update(group_by=rng.sample(["d1", "d2"], rng.randint(1, 2)), top=1000)
    return shape


@pytest.mark.parametrize("lowering", ["scatter", "contraction"])
@pytest.mark.parametrize("seed", [3401, 3402, 3403, 3404, 3405, 3406])
def test_random_expressions_equal_the_reference(seed, lowering, monkeypatch):
    rng = random.Random(seed)
    if lowering == "contraction":
        monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    forget_programs()
    tables = [random_rows(RANDOM_SCHEMA, rng.randint(300, 900), seed=seed * 10 + i, cardinality=rng.choice([3, 5, 8]))
              for i in range(rng.randint(1, 3))]
    segments = [build_segment(RANDOM_SCHEMA, rows, "exprT", f"r{i}") for i, rows in enumerate(tables)]
    m1_values = sorted(row["m1"] for row in tables[0])
    shapes = {f"s{i}": random_shape(rng, m1_values) for i in range(4)}
    ref = spec_ref.Reference(shapes)
    for seg in segments:
        ref.add(seg)
    broker = single_server_broker("exprT", segments)
    try:
        for name, shape in shapes.items():
            pql = spec_ref.render_pql("exprT", shape)
            reply = broker.handle_pql(pql).to_json()
            assert not reply["exceptions"], (pql, reply["exceptions"])
            held(reply, shape, ref, name)
            # the host tier is the same arithmetic in float64
            request = optimize_request(parse_pql(pql))
            oracle = reduce_to_response(request, [QueryExecutor().execute_host_oracle(segments, request)]).to_json()
            held(dict(oracle, cost={}, numServersQueried=0, numServersResponded=0), shape, ref, name)
    finally:
        broker.local_servers[0].shutdown()
        forget_programs()


# -- the slots of a dense group-by ---------------------------------------------

def _static_plan(segments, pql):
    from pinot_tpu.engine.context import get_table_context
    from pinot_tpu.engine.device import get_staged
    from pinot_tpu.engine.plan import build_static_plan

    request = optimize_request(parse_pql(pql))
    ctx = get_table_context(segments)
    raw, gfwd, hll, _skip_base = ladder.roles(request, segments, ctx)
    staged = get_staged(segments, request.referenced_columns(), raw_columns=raw, gfwd_columns=gfwd, hll_columns=hll,
                        ctx=ctx)
    return build_static_plan(request, ctx, staged)


SLOT_CASES = {
    # PQL select list: (the rows each aggregate reads, m)
    "q1": (Q1.split(" FROM ")[0][len("SELECT "):], {0: [1], 1: [2], 2: [3], 3: [4], 4: [1, 0], 5: [2, 0], 6: [5, 0], 7: [0]}, 6),
    "k6": ("sum(l_quantity), sum(l_extendedprice), sum(l_discount), count(*)", {0: [1], 1: [2], 2: [3], 3: [0]}, 4),
    "two_sums": ("sum(l_extendedprice), sum(l_quantity)", {0: [1], 1: [2]}, 3),
    "avg_alone": ("avg(l_tax)", {0: [1, 0]}, 2),
    "sum_and_avg_of_one_expression": ("avg(l_tax*2), sum(l_tax*2), sum(2*l_tax)", {0: [1, 0], 1: [1], 2: [2]}, 3),
    "min_keeps_its_own_state": ("min(l_tax), sum(l_tax)", {1: [1]}, 2),
}


@pytest.mark.parametrize("case", sorted(SLOT_CASES))
def test_contraction_slots_belong_to_what_they_sum(lineitem_segments, contractions_forced, case):
    select, slots, m = SLOT_CASES[case]
    plan = _static_plan(lineitem_segments, f"SELECT {select} FROM lineitem GROUP BY l_returnflag, l_linestatus TOP 10")
    assert kernel_mod._contraction_slots(plan) == (slots, m)
    unshared = 1 + sum(len(rows) for rows in slots.values() if rows != [0])
    assert kernel_mod.groupby_cells(plan) == (6 * m, unshared - m)
    if case == "q1":
        # 36 cells ride the row loop; one row an aggregate and two an avg would be 66, over the gate
        assert 6 * m <= kernel_mod._LOOP_CELLS < 6 * unshared
        assert kernel_mod.groupby_operands(plan) == "loop"
    if case == "min_keeps_its_own_state":
        assert kernel_mod.groupby_operands(plan) == "staged"


# -- what tells two expressions apart ------------------------------------------

DISTINCT_PAIRS = {
    "one_operator": ("sum(l_extendedprice*(1-l_discount))", "sum(l_extendedprice*(1+l_discount))"),
    "one_constant": ("sum(l_extendedprice*(1-l_discount))", "sum(l_extendedprice*(2-l_discount))"),
    "one_grouping": ("sum(l_extendedprice*l_discount*l_tax)", "sum(l_extendedprice*(l_discount*l_tax))"),
}


@pytest.mark.parametrize("grouped", [False, True])
@pytest.mark.parametrize("pair", sorted(DISTINCT_PAIRS))
def test_two_expressions_share_no_program_entry_or_cache_key(lineitem_segments, pair, grouped):
    tail = " FROM lineitem WHERE l_quantity < 24" + (" GROUP BY l_returnflag TOP 10" if grouped else "")
    pqls = ["SELECT " + select + tail for select in DISTINCT_PAIRS[pair]]
    requests = [optimize_request(parse_pql(pql)) for pql in pqls]
    assert plan_shape_digest(requests[0]) != plan_shape_digest(requests[1])
    keys = [ResultCache.key_for(r, lineitem_segments, "lineitem") for r in requests]
    assert keys[0] is not None and keys[0] != keys[1]
    broker = single_server_broker("lineitem", lineitem_segments)
    server = broker.local_servers[0]
    try:
        programs = []
        for pql in pqls + pqls:
            resp = broker.handle_pql(pql, trace=True)
            assert not resp.exceptions
            (launch,) = [s for s in resp.trace_info["scopes"][server.name] if s["span"] == "laneDispatch"]
            programs.append(launch["tags"]["program"])
        assert programs[0] != programs[1] and programs[:2] == programs[2:]
        assert len(server.executor._prepared) == 2
        assert server.metrics.meter("plan.prepared.miss").count == 2 and server.metrics.meter("plan.prepared.hit").count == 2
    finally:
        server.shutdown()
    plans = [_static_plan(lineitem_segments, pql) for pql in pqls]
    assert plans[0] != plans[1] and repr(plans[0]) != repr(plans[1])


def test_a_plan_without_an_expression_keeps_its_digest(lineitem_segments):
    """``StaticAgg.expr`` is not in the repr: the name a program is
    jitted under (PERF.md's ``pinot_scan_gb6_fd9a6467``) stays."""
    from pinot_tpu.engine.plan import StaticAgg

    assert "expr" not in repr(StaticAgg("sum", "sum", "l_extendedprice", False, "scalar", use_raw=True))
    plan = _static_plan(lineitem_segments, "SELECT sum(l_quantity*1) FROM lineitem")
    assert plan.aggs[0].expr == ("*", ("col", "l_quantity"), ("lit", 1.0)) and "l_quantity*1" in repr(plan)


def test_sum_of_a_column_and_of_the_column_times_one_agree_to_the_bit(lineitem_segments, contractions_forced):
    broker = single_server_broker("lineitem", lineitem_segments)
    try:
        for tail in ("", " WHERE l_quantity < 24", " GROUP BY l_returnflag, l_linestatus TOP 10"):
            plain = broker.handle_pql("SELECT sum(l_extendedprice), avg(l_discount) FROM lineitem" + tail).to_json()
            times_one = broker.handle_pql("SELECT sum(l_extendedprice*1), avg(1*l_discount) FROM lineitem" + tail).to_json()
            assert not plain["exceptions"] and not times_one["exceptions"]
            strip = lambda reply: [{k: v for k, v in a.items() if k != "function"} for a in reply["aggregationResults"]]
            assert strip(plain) == strip(times_one)
            assert [a["function"] for a in times_one["aggregationResults"]] == ["sum_l_extendedprice*1", "avg_1*l_discount"]
    finally:
        broker.local_servers[0].shutdown()


# -- what is refused, by name --------------------------------------------------

REFUSED = {
    "division": ("SELECT sum(l_extendedprice/l_quantity) FROM lineitem", "division"),
    "under_min": ("SELECT min(l_extendedprice*l_discount) FROM lineitem", "inside min()"),
    "under_max": ("SELECT max(l_extendedprice-l_discount) FROM lineitem", "inside max()"),
    "under_distinctcount": ("SELECT distinctcount(l_quantity+1) FROM lineitem", "inside distinctcount()"),
    "under_distinctcounthll": ("SELECT distinctcounthll(l_quantity*2) FROM lineitem", "inside distinctcounthll()"),
    "under_percentile": ("SELECT percentile90(l_quantity*2) FROM lineitem", "inside percentile90()"),
    "in_where": ("SELECT count(*) FROM lineitem WHERE l_extendedprice*l_discount > 5", "expression in WHERE"),
    "in_group_by": ("SELECT count(*) FROM lineitem GROUP BY l_quantity*2", "expression in GROUP BY"),
    "in_having": ("SELECT sum(l_tax) FROM lineitem GROUP BY l_returnflag HAVING sum(l_tax*2) > 1", "expression in HAVING"),
    "in_a_join": ("SELECT sum(a.l_tax*b.o_total) FROM lineitem a JOIN orders b ON a.l_orderkey = b.o_orderkey",
                  "in a join query"),
    "a_function_call": ("SELECT sum(abs(l_tax)) FROM lineitem", "function call"),
    "no_column": ("SELECT sum(1+2) FROM lineitem", "reads no column"),
    "unclosed": ("SELECT sum((l_tax+1) FROM lineitem", "expected ')'"),
}


@pytest.mark.parametrize("form", sorted(REFUSED))
def test_refused_forms_raise_a_parse_error_that_names_them(form):
    pql, named = REFUSED[form]
    with pytest.raises(PqlParseError, match=named.replace("(", r"\(").replace(")", r"\)")):
        parse_pql(pql)


def test_an_expression_over_a_multi_value_or_string_column_is_refused_where_the_schema_is_known():
    from pinot_tpu.tools.datagen import make_test_schema

    schema = make_test_schema(with_mv=True)
    mv = next(f.name for f in schema.dimensions if not f.single_value and f.data_type == DataType.INT_ARRAY)
    text = next(f.name for f in schema.dimensions if f.single_value and f.data_type == DataType.STRING)
    metric = schema.metrics[0].name
    segment = build_segment(schema, random_rows(schema, 200, seed=34), schema.schema_name, "mv0")
    for column, named in ((mv, "multi-value column"), (text, "not numeric")):
        request = optimize_request(parse_pql(f"SELECT sum({metric}*{column}) FROM {schema.schema_name}"))
        with pytest.raises(PqlParseError, match=named):
            QueryExecutor().execute([segment], request)
    broker = single_server_broker(schema.schema_name, [segment])
    try:
        reply = broker.handle_pql(f"SELECT sum({metric}*{mv}) FROM {schema.schema_name}").to_json()
        assert "multi-value column" in json.dumps(reply["exceptions"])
    finally:
        broker.local_servers[0].shutdown()


def test_canonical_text_gives_its_tree_back():
    rng = random.Random(34)
    for _ in range(200):
        text = random_expression(rng)
        if not spec_ref.expr_columns(spec_ref.parse_expr(text)):
            continue
        agg = parse_pql(f"SELECT sum({text}) FROM t").aggregations[0]
        again = parse_pql(f"SELECT sum({agg.column}) FROM t").aggregations[0]
        assert (again.column, again.expr) == (agg.column, agg.expr), text
        if agg.expr is not None:
            assert expr_text(agg.expr) == agg.column and " " not in agg.column


# -- tiers that cannot multiply decline, and the answer is right all the same ---

def test_a_star_tree_segment_declines_and_still_answers_right():
    from pinot_tpu.startree import StarTreeBuilderConfig, build_star_tree, is_fit_for_star_tree

    rows = random_rows(RANDOM_SCHEMA, 1500, seed=341, cardinality=6)
    segment = build_segment(RANDOM_SCHEMA, rows, "exprT", "st0")
    build_star_tree(segment, RANDOM_SCHEMA, StarTreeBuilderConfig(max_leaf_records=10))
    shape = {"aggs": [["sum", {"expr": "m1*m2"}], ["sum", "m1"]], "group_by": ["d1"], "top": 100}
    ref = spec_ref.Reference({"s": shape})
    ref.add(segment)
    plain = optimize_request(parse_pql("SELECT sum(m1) FROM exprT GROUP BY d1 TOP 100"))
    request = optimize_request(parse_pql(spec_ref.render_pql("exprT", shape)))
    assert is_fit_for_star_tree(plain, segment) and not is_fit_for_star_tree(request, segment)
    result = QueryExecutor().execute([segment], request)
    assert not result.cost.get("segmentsStarTree")
    reply = reduce_to_response(request, [result]).to_json()
    held(dict(reply, numServersQueried=0, numServersResponded=0), shape, ref, "s")


def test_the_bit_sliced_tier_declines_and_still_answers_right(lineitem_segments, monkeypatch):
    from pinot_tpu.engine.bitsliced import bitsliced_decision
    from pinot_tpu.engine.context import get_table_context

    monkeypatch.setenv("PINOT_TPU_BITSLICED", "force")
    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")
    shape = {"aggs": [["sum", {"expr": "l_quantity*2"}], ["count", "*"]],
             "filter": [["l_extendedprice", "between", [10000, 50000]]]}
    ref = spec_ref.Reference({"s": shape})
    for seg in lineitem_segments:
        ref.add(seg)
    plain = optimize_request(parse_pql("SELECT sum(l_quantity), count(*) FROM lineitem WHERE l_extendedprice BETWEEN 10000 AND 50000"))
    request = optimize_request(parse_pql(spec_ref.render_pql("lineitem", shape)))
    ctx = get_table_context(lineitem_segments)
    total = sum(s.num_docs for s in lineitem_segments)
    assert bitsliced_decision(plain, lineitem_segments, ctx, total)[1] is not None
    decision, state = bitsliced_decision(request, lineitem_segments, ctx, total)
    assert state is None and "expression" in decision["reason"]
    executor = QueryExecutor()
    assert executor.execute(lineitem_segments, plain).cost.get("segmentsBitsliced") == len(lineitem_segments)
    result = executor.execute(lineitem_segments, request)
    assert not result.cost.get("segmentsBitsliced") and not result.cost.get("segmentsHost")
    reply = reduce_to_response(request, [result]).to_json()
    held(dict(reply, numServersQueried=0, numServersResponded=0), shape, ref, "s")


def test_the_postings_tier_answers_an_expression_on_the_host(lineitem_segments):
    """A selective filter rides the host's postings; the expression is
    then float64, the meter says host, and the answer is the reference's."""
    shape = {"aggs": [["sum", {"expr": "l_extendedprice*(1-l_discount)"}]], "filter": [["l_quantity", "=", 7.0]]}
    ref = spec_ref.Reference({"s": shape})
    for seg in lineitem_segments:
        ref.add(seg)
    request = optimize_request(parse_pql(spec_ref.render_pql("lineitem", shape)))
    executor = QueryExecutor()
    result = executor.execute(lineitem_segments, request)
    reply = reduce_to_response(request, [result]).to_json()
    held(dict(reply, cost={}, numServersQueried=0, numServersResponded=0), shape, ref, "s")
    tier = "host" if result.cost.get("segmentsPostings") or result.cost.get("segmentsHost") else "device"
    assert executor.metrics.meter(f"agg.expr.{tier}").count == 1
