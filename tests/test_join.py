"""Distributed join subsystem (ISSUE 14): PQL grammar edge cases, the
engine's device-vs-host differential, skew-aware shuffle partitioning,
and the three broker strategies end-to-end — byte-identical results
across every strategy and execution tier, under replica failover, with
a poisoned join plan healing transparently, and with the result-cache /
batching interop guards held.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from pinot_tpu.common.datatable import (
    deserialize_instance_request,
    deserialize_result,
    serialize_instance_request,
    serialize_result,
)
from pinot_tpu.common.request import FilterOperator
from pinot_tpu.common.response import ErrorCode
from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.common.tableconfig import PartitionConfig
from pinot_tpu.engine import join as jm
from pinot_tpu.engine.plandigest import plan_shape_digest
from pinot_tpu.engine.results import IntermediateResult
from pinot_tpu.pql import PqlParseError, parse_pql
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.tools.cluster_harness import InProcessCluster

# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------


def test_parse_join_with_aliases_and_reversed_on():
    r1 = parse_pql(
        "SELECT sum(f.v) FROM fact f JOIN dim AS d ON f.k = d.dk WHERE d.cat = 'x'"
    )
    r2 = parse_pql(
        "SELECT sum(x.v) FROM fact x JOIN dim y ON y.dk = x.k WHERE y.cat = 'x'"
    )
    for r in (r1, r2):
        assert r.join is not None
        assert r.join.right_table == "dim"
        assert r.join.left_key == "k" and r.join.right_key == "dk"
        # right-side refs canonicalize to the TABLE name, not the alias
        leaves = [n for n in r.filter.walk() if n.is_leaf]
        assert leaves[0].column == "dim.cat"
    # alias spelling does not fork the plan shape
    assert plan_shape_digest(r1) == plan_shape_digest(r2)
    # ...but a joined scan is a different shape from a plain scan
    assert plan_shape_digest(r1) != plan_shape_digest(
        parse_pql("SELECT sum(v) FROM fact WHERE cat = 'x'")
    )


def test_parse_join_group_order_top():
    r = parse_pql(
        "SELECT sum(f.v), count(*) FROM fact f JOIN dim d ON f.k = d.k "
        "WHERE f.v > 3 GROUP BY d.cat, f.g ORDER BY d.cat TOP 7"
    )
    assert r.group_by.columns == ["dim.cat", "g"]
    assert r.group_by.top_n == 7
    assert r.aggregations[0].column == "v"


@pytest.mark.parametrize(
    "pql,needle",
    [
        ("SELECT a.x FROM a, b", "cross join"),
        ("SELECT a.x FROM a CROSS JOIN b ON a.k = b.k", "cross join"),
        ("SELECT a.x FROM a LEFT JOIN b ON a.k = b.k", "INNER equi-join"),
        ("SELECT a.x FROM a JOIN b ON a.k < b.k", "equi-join"),
        ("SELECT a.x FROM a JOIN b ON a.k = a.j", "EACH side"),
        ("SELECT a.x FROM a JOIN b ON a.k = b.k JOIN c ON a.k = c.k", "two tables"),
        ("SELECT a.x FROM a JOIN b ON a.k = b.k AND a.j = b.j", "compound ON"),
        ("SELECT x FROM a JOIN b ON a.k = b.k", "qualified"),
        ("SELECT * FROM a JOIN b ON a.k = b.k", "name the"),
        ("SELECT q.x FROM a JOIN b ON a.k = b.k", "unknown table alias"),
        ("SELECT a.b FROM plain", "only valid in a join"),
        ("SELECT a.x FROM a INNER b", "expected JOIN"),
        ("SELECT a.x FROM a JOIN b ON k = b.k", "qualified"),
    ],
)
def test_parse_join_typed_errors(pql, needle):
    with pytest.raises(PqlParseError) as ei:
        parse_pql(pql)
    assert needle.lower() in str(ei.value).lower()


def test_parse_errors_surface_as_4xx_not_crash():
    """Through the whole broker front door: a join parse error is a
    typed 150, never an unhandled exception."""
    from pinot_tpu.broker.broker import BrokerRequestHandler
    from pinot_tpu.transport.local import LocalTransport

    broker = BrokerRequestHandler(LocalTransport(), {}, name="jerr")
    try:
        resp = broker.handle_pql("SELECT a.x FROM a CROSS JOIN b")
        assert [e.error_code for e in resp.exceptions] == [ErrorCode.PQL_PARSING]
    finally:
        broker.shutdown()


# ---------------------------------------------------------------------------
# engine units
# ---------------------------------------------------------------------------


def _mk_side(keys, stored=DataType.LONG, **cols):
    out_cols = {}
    for name, (vals, st) in cols.items():
        out_cols[name] = jm._dict_encode(np.asarray(vals, dtype=object if st == DataType.STRING else None), st)
    return jm.SideRows(
        n=len(keys), key=jm._dict_encode(np.asarray(keys), stored), cols=out_cols
    )


def test_side_rows_wire_roundtrip_with_strings():
    side = _mk_side(
        [3, 1, 3, 9],
        cols_num=([1, 2, 3, 4], DataType.INT),
        cols_str=(["a", "b", "a", "c"], DataType.STRING),
    )
    back = jm.decode_side(
        deserialize_instance_request(
            serialize_instance_request(
                "rid", "pql", "t", [], 100.0, join={"x": jm.encode_side(side)}
            )
        )["join"]["x"]
    )
    assert back.n == side.n
    assert np.array_equal(back.key.ids, side.key.ids)
    assert list(back.cols["cols_str"].values) == ["a", "b", "c"]
    # join payload on the result wire too
    res = IntermediateResult(num_docs_scanned=1)
    res.join_payload = jm.encode_side(side)
    rt = deserialize_result(serialize_result(res))
    assert np.array_equal(
        jm.decode_side(rt.join_payload).key.ids, side.key.ids
    )


def test_split_join_filter_sides_and_mixed_rejection():
    r = parse_pql(
        "SELECT count(*) FROM f JOIN d ON f.k = d.k "
        "WHERE f.a > 1 AND d.b = 2 AND (f.c = 3 OR f.e = 4)"
    )
    left, right = jm.split_join_filter(r)
    assert {n.column for n in left.walk() if n.is_leaf} == {"a", "c", "e"}
    assert [n.column for n in right.walk() if n.is_leaf] == ["b"]  # stripped
    bad = parse_pql(
        "SELECT count(*) FROM f JOIN d ON f.k = d.k WHERE f.a = 1 OR d.b = 2"
    )
    with pytest.raises(jm.JoinValidationError):
        jm.split_join_filter(bad)


def test_host_join_matches_bruteforce_with_duplicate_keys():
    rng = np.random.default_rng(5)
    pk = rng.integers(0, 20, 400)
    pv = rng.integers(0, 50, 400)
    bk = rng.integers(0, 25, 60)  # duplicate build keys: M:N join
    bw = rng.integers(0, 9, 60)
    probe = _mk_side(pk, cols_v=(pv, DataType.INT))
    probe.cols["v"] = probe.cols.pop("cols_v")
    build = _mk_side(bk, cols_w=(bw, DataType.INT))
    build.cols["d.w"] = build.cols.pop("cols_w")
    req = parse_pql("SELECT count(*), sum(f.v), sum(d.w) FROM f JOIN d ON f.k = d.k")
    res = jm.host_join(req, build, probe)
    exp_cnt = exp_sv = exp_sw = 0
    for k, v in zip(pk, pv):
        for k2, w in zip(bk, bw):
            if k == k2:
                exp_cnt += 1
                exp_sv += v
                exp_sw += w
    vals = [p.finalize() for p in res.aggregations]
    assert vals == [exp_cnt, float(exp_sv), float(exp_sw)]
    assert res.num_docs_scanned == exp_cnt


def test_device_join_differential_vs_host():
    """The device hash-join kernel must match the exact host join for
    every eligible shape — scalar aggs, probe-side groups, build-side
    groups (unique keys), string join keys."""
    from pinot_tpu.engine.executor import QueryExecutor

    rng = np.random.default_rng(0)
    N, B = 4000, 400
    pk = rng.integers(0, 300, N)
    pv = rng.integers(0, 100, N)
    pg = np.asarray([f"p{int(x) % 4}" for x in pk], dtype=object)
    bk = np.concatenate([np.arange(250), rng.integers(0, 250, B - 250)])
    bw = rng.integers(0, 50, B)
    probe = jm.SideRows(
        n=N,
        key=jm._dict_encode(pk, DataType.LONG),
        cols={
            "v": jm._dict_encode(pv, DataType.LONG),
            "g": jm._dict_encode(pg, DataType.STRING),
        },
    )
    build = jm.SideRows(
        n=B,
        key=jm._dict_encode(bk, DataType.LONG),
        cols={"d.w": jm._dict_encode(bw, DataType.LONG)},
    )
    ub = np.arange(250)
    build_u = jm.SideRows(
        n=250,
        key=jm._dict_encode(ub, DataType.LONG),
        cols={
            "d.w": jm._dict_encode(rng.integers(0, 50, 250), DataType.LONG),
            "d.cat": jm._dict_encode(
                np.asarray([f"c{k % 6}" for k in ub], dtype=object), DataType.STRING
            ),
        },
    )
    # string join keys exercise the shared-vocabulary id space
    spk = np.asarray([f"k{int(x)}" for x in pk], dtype=object)
    sbk = np.asarray([f"k{int(x)}" for x in ub], dtype=object)
    probe_s = jm.SideRows(
        n=N,
        key=jm._dict_encode(spk, DataType.STRING),
        cols={"v": jm._dict_encode(pv, DataType.LONG)},
    )
    build_s = jm.SideRows(
        n=250,
        key=jm._dict_encode(sbk, DataType.STRING),
        cols={"d.w": jm._dict_encode(rng.integers(0, 50, 250), DataType.LONG)},
    )

    ex = QueryExecutor()
    cases = [
        (
            "SELECT count(*), sum(f.v), sum(d.w), avg(f.v), min(d.w), "
            "max(f.v), minmaxrange(d.w) FROM f JOIN d ON f.k = d.k",
            build,
            probe,
        ),
        (
            "SELECT sum(f.v), count(*) FROM f JOIN d ON f.k = d.k GROUP BY f.g",
            build,
            probe,
        ),
        (
            "SELECT sum(f.v), min(d.w) FROM f JOIN d ON f.k = d.k "
            "GROUP BY d.cat, f.g",
            build_u,
            probe,
        ),
        (
            "SELECT count(*), sum(f.v) FROM f JOIN d ON f.k = d.k",
            build_s,
            probe_s,
        ),
    ]

    def norm(r):
        if r.groups is not None:
            return {k: [p.finalize() for p in v] for k, v in r.groups.items()}
        return [p.finalize() for p in (r.aggregations or [])]

    for pql, b, p in cases:
        req = parse_pql(pql)
        dev = ex.execute_join(req, b, p)
        assert "deviceBytes" in dev.cost, f"device path not taken for {pql}"
        host = jm.host_join(req, b, p)
        assert norm(dev) == norm(host), pql
        assert dev.num_docs_scanned == host.num_docs_scanned
        assert dev.cost.get("buildRows") == b.n
        assert dev.cost.get("probeRows") == p.n
    assert ex.healing_stats()["hostFailovers"] == 0


def test_shuffle_partitions_preserve_join_and_balance_skew():
    rng = np.random.default_rng(7)
    # zipf s=1.2 on the join key — the acceptance distribution
    zk = (np.minimum(rng.zipf(1.2, 30000), 400) - 1).astype(np.int64)
    probe = jm.SideRows(
        n=zk.size,
        key=jm._dict_encode(zk, DataType.LONG),
        cols={"v": jm._dict_encode(rng.integers(0, 10, zk.size), DataType.LONG)},
    )
    build = jm.SideRows(
        n=400,
        key=jm._dict_encode(np.arange(400), DataType.LONG),
        cols={"d.w": jm._dict_encode(np.arange(400) % 7, DataType.LONG)},
    )
    req = parse_pql("SELECT count(*), sum(f.v) FROM f JOIN d ON f.k = d.k")
    full = jm.host_join(req, build, probe)

    def run(split):
        owners, n_heavy = jm.plan_shuffle_partitions(
            build, probe, 4, split_heavy=split
        )
        parts = []
        sizes = []
        for b_idx, p_idx in owners:
            b_sub, p_sub = jm.side_take(build, b_idx), jm.side_take(probe, p_idx)
            sizes.append(p_sub.nbytes() + b_sub.nbytes())
            parts.append(jm.host_join(req, b_sub, p_sub))
        merged = parts[0]
        for p in parts[1:]:
            merged.merge(p)
        return merged, sizes, n_heavy

    merged, sizes, n_heavy = run(split=True)
    # inner-join correctness is partition-invariant
    assert [p.finalize() for p in merged.aggregations] == [
        p.finalize() for p in full.aggregations
    ]
    assert n_heavy > 0
    ratio = max(sizes) / (sum(sizes) / len(sizes))
    assert ratio <= 2.0, sizes
    _m2, sizes_ns, _h = run(split=False)
    ratio_ns = max(sizes_ns) / (sum(sizes_ns) / len(sizes_ns))
    assert ratio <= ratio_ns  # splitting never worsens balance


# ---------------------------------------------------------------------------
# cluster end-to-end
# ---------------------------------------------------------------------------

NPART = 4


def _fact_schema(name):
    return Schema(
        name,
        dimensions=[
            FieldSpec("k", DataType.INT, FieldType.DIMENSION),
            FieldSpec("grp", DataType.STRING, FieldType.DIMENSION),
        ],
        metrics=[FieldSpec("v", DataType.INT, FieldType.METRIC)],
    )


def _dim_schema(name):
    return Schema(
        name,
        dimensions=[
            FieldSpec("k", DataType.INT, FieldType.DIMENSION),
            FieldSpec("cat", DataType.STRING, FieldType.DIMENSION),
        ],
        metrics=[FieldSpec("w", DataType.INT, FieldType.METRIC)],
    )


def _make_rows(seed=3, n=1500, keys=60):
    rng = np.random.default_rng(seed)
    fact = [
        {"k": int(k), "grp": f"g{int(k) % 3}", "v": int(v)}
        for k, v in zip(rng.integers(0, keys, n), rng.integers(0, 100, n))
    ]
    dim = [{"k": k, "cat": f"c{k % 5}", "w": (k * 3) % 41} for k in range(keys)]
    return fact, dim


def _oracle(fact, dim):
    import collections

    dmap = collections.defaultdict(list)
    for d in dim:
        dmap[d["k"]].append(d)
    return [(f, d) for f in fact for d in dmap.get(f["k"], [])]


@pytest.fixture(scope="module")
def join_cluster():
    cl = InProcessCluster(num_servers=2)
    fact, dim = _make_rows()
    part = PartitionConfig(column="k", num_partitions=NPART)
    cl.add_offline_table(
        _fact_schema("factT"), table_name="factT", replication=2, partitioning=part
    )
    cl.add_offline_table(
        _dim_schema("dimT"), table_name="dimT", replication=2, partitioning=part
    )
    fs, ds = _fact_schema("factT"), _dim_schema("dimT")
    for p in range(NPART):
        cl.upload(
            "factT_OFFLINE",
            build_segment(
                fs,
                [r for r in fact if r["k"] % NPART == p],
                "factT_OFFLINE",
                segment_name=f"factT_{p}_p{p}",
            ),
        )
        cl.upload(
            "dimT_OFFLINE",
            build_segment(
                ds,
                [r for r in dim if r["k"] % NPART == p],
                "dimT_OFFLINE",
                segment_name=f"dimT_{p}_p{p}",
            ),
        )
    yield cl, fact, dim
    cl.stop()


_STRATS = ("colocated", "broadcast", "shuffle")


def _result_payload(resp) -> str:
    """Result sections only: work accounting is strategy-dependent by
    construction (the PR 3 heal contract), results are not."""
    keep = ("aggregationResults", "selectionResults", "exceptions",
            "partialResponse", "planDigest")
    return json.dumps(
        {k: v for k, v in resp.to_json().items() if k in keep}, sort_keys=True
    )


def test_all_strategies_end_to_end_byte_identical(join_cluster):
    cl, fact, dim = join_cluster
    joined = _oracle(fact, dim)
    exp = [len(joined), float(sum(f["v"] for f, _ in joined)),
           float(sum(d["w"] for _, d in joined))]
    q = "SELECT count(*), sum(f.v), sum(d.w) FROM factT f JOIN dimT d ON f.k = d.k"
    payloads = set()
    for strat in _STRATS:
        resp = cl.broker.handle_pql(q, debug_options={"joinStrategy": strat})
        assert not resp.exceptions, (strat, resp.exceptions)
        got = [a.value for a in resp.aggregation_results]
        assert [got[0], float(got[1]), float(got[2])] == exp, strat
        payloads.add(_result_payload(resp))
        # join cost keys are additive and present
        assert resp.cost.get("buildRows", 0) > 0
        assert resp.cost.get("probeRows", 0) > 0
        if strat == "shuffle":
            assert resp.cost.get("shuffleBytes", 0) > 0
        if strat == "broadcast":
            assert resp.cost.get("broadcastBytes", 0) > 0
    # forced-host reference produces the same payload (debugOptions ride
    # the literal digest, not the shape, so planDigest matches too)
    import os

    os.environ["PINOT_TPU_JOIN_DEVICE"] = "0"
    try:
        for strat in _STRATS:
            resp = cl.broker.handle_pql(q, debug_options={"joinStrategy": strat})
            assert not resp.exceptions
            payloads.add(_result_payload(resp))
    finally:
        os.environ.pop("PINOT_TPU_JOIN_DEVICE")
    assert len(payloads) == 1, payloads


def test_join_cost_vector_broker_equals_sum_of_servers(join_cluster):
    """The additive-cost invariant extends to joins: the broker's merged
    vector equals the key-wise sum of every server reply's vector, over
    every phase of the most phase-heavy strategy (shuffle)."""
    cl, _f, _d = join_cluster

    class _Spy:
        def __init__(self, inner):
            self.inner = inner
            self.replies = []

        def request(self, address, payload, timeout=15.0):
            reply = self.inner.request(address, payload, timeout)
            self.replies.append(reply)
            return reply

        def __getattr__(self, name):
            return getattr(self.inner, name)

    spy = _Spy(cl.broker.transport)
    cl.broker.transport = spy
    try:
        resp = cl.broker.handle_pql(
            "SELECT sum(f.v), count(*) FROM factT f JOIN dimT d ON f.k = d.k "
            "WHERE d.cat IN ('c1','c3') GROUP BY d.cat",
            debug_options={"joinStrategy": "shuffle"},
        )
        assert not resp.exceptions, resp.exceptions
        summed: dict = {}
        docs = 0
        for raw in spy.replies:
            part = deserialize_result(raw)
            docs += part.num_docs_scanned
            for k, v in part.cost.items():
                summed[k] = summed.get(k, 0) + v
        assert resp.num_docs_scanned == docs
        for k in set(summed) | set(resp.cost):
            assert resp.cost.get(k, 0) == pytest.approx(summed.get(k, 0)), k
    finally:
        cl.broker.transport = spy.inner


def test_join_group_by_having_order_and_selection(join_cluster):
    cl, fact, dim = join_cluster
    joined = _oracle(fact, dim)
    # group-by with HAVING, identical across strategies
    q = (
        "SELECT sum(f.v), count(*) FROM factT f JOIN dimT d ON f.k = d.k "
        "WHERE f.v > 20 GROUP BY d.cat HAVING count(*) > 10 TOP 5"
    )
    seen = {
        _result_payload(cl.broker.handle_pql(q, debug_options={"joinStrategy": s}))
        for s in _STRATS
    }
    assert len(seen) == 1
    # selection join with order/limit (host tier)
    qsel = (
        "SELECT f.v, d.w FROM factT f JOIN dimT d ON f.k = d.k "
        "WHERE d.cat = 'c2' ORDER BY f.v DESC LIMIT 5"
    )
    top_v = sorted(
        (f["v"] for f, d in joined if d["cat"] == "c2"), reverse=True
    )[:5]
    for s in _STRATS:
        resp = cl.broker.handle_pql(qsel, debug_options={"joinStrategy": s})
        assert not resp.exceptions, (s, resp.exceptions)
        assert resp.selection_results.columns == ["v", "dimT.w"]
        # sort-key ties admit any row order (strategies partition rows
        # differently, like routing draws do for scans) — the ordered
        # sort-column values are the deterministic contract
        assert [int(r[0]) for r in resp.selection_results.rows] == top_v


def test_join_key_referenced_as_value_column(join_cluster):
    """sum/group over the join key itself: the key doubles as a value
    column and must be read ONCE per segment (regression: duplicated
    extraction doubled host results and crashed the device packing)."""
    cl, fact, dim = join_cluster
    joined = _oracle(fact, dim)
    q = "SELECT count(*), sum(f.k) FROM factT f JOIN dimT d ON f.k = d.k"
    for strat in _STRATS:
        resp = cl.broker.handle_pql(q, debug_options={"joinStrategy": strat})
        assert not resp.exceptions, (strat, resp.exceptions)
        vals = [a.value for a in resp.aggregation_results]
        assert int(vals[0]) == len(joined), strat
        assert float(vals[1]) == float(sum(f["k"] for f, _ in joined)), strat


def test_join_empty_filtered_side_returns_empty_not_type_error(join_cluster):
    """A right-side filter matching nothing yields an empty inner join
    (count 0), never a spurious key-type validation error from the
    empty-extract placeholder (regression)."""
    cl, _f, _d = join_cluster
    for strat in _STRATS:
        resp = cl.broker.handle_pql(
            "SELECT count(*) FROM factT f JOIN dimT d ON f.k = d.k "
            "WHERE d.cat = 'nomatch'",
            debug_options={"joinStrategy": strat},
        )
        assert not resp.exceptions, (strat, resp.exceptions)
        assert int(resp.aggregation_results[0].value) == 0


def test_bogus_join_strategy_is_typed_4xx(join_cluster):
    cl, _f, _d = join_cluster
    resp = cl.broker.handle_pql(
        "SELECT count(*) FROM factT f JOIN dimT d ON f.k = d.k",
        debug_options={"joinStrategy": "bogus"},
    )
    assert [e.error_code for e in resp.exceptions] == [ErrorCode.QUERY_VALIDATION]


def test_join_validation_errors_are_typed_4xx(join_cluster):
    cl, _f, _d = join_cluster
    # mixed-side OR
    resp = cl.broker.handle_pql(
        "SELECT count(*) FROM factT f JOIN dimT d ON f.k = d.k "
        "WHERE f.v = 1 OR d.cat = 'c1'"
    )
    assert [e.error_code for e in resp.exceptions] == [ErrorCode.QUERY_VALIDATION]
    # unknown right table
    resp = cl.broker.handle_pql(
        "SELECT count(*) FROM factT f JOIN nosuch d ON f.k = d.k"
    )
    assert [e.error_code for e in resp.exceptions] == [ErrorCode.QUERY_VALIDATION]
    # forcing colocated where ineligible (partition column mismatch)
    resp = cl.broker.handle_pql(
        "SELECT count(*) FROM factT f JOIN dimT d ON f.v = d.k",
        debug_options={"joinStrategy": "colocated"},
    )
    assert [e.error_code for e in resp.exceptions] == [ErrorCode.QUERY_VALIDATION]


def test_join_explain_strategy_and_digest_match_execution(join_cluster):
    cl, _f, _d = join_cluster
    q = "SELECT count(*), sum(f.v) FROM factT f JOIN dimT d ON f.k = d.k"
    executed = cl.broker.handle_pql(q)
    assert not executed.exceptions
    plan = cl.broker.handle_pql("EXPLAIN " + q)
    node = plan.explain["join"]
    # the partition-aligned tables pick colocated, EXPLAIN and real
    # execution agree, and the plan digest matches exactly
    assert node["strategy"] == "colocated"
    assert node["colocated"]["eligible"] is True
    assert plan.explain["planDigest"] == executed.plan_digest
    analyze = cl.broker.handle_pql("EXPLAIN ANALYZE " + q)
    actual = analyze.explain["join"]["actual"]
    assert actual["strategy"] == "colocated"
    assert actual["buildRows"] > 0 and actual["probeRows"] > 0
    # forced shuffle: EXPLAIN names it, ANALYZE carries the split info
    analyze = cl.broker.handle_pql(
        "EXPLAIN ANALYZE " + q, debug_options={"joinStrategy": "shuffle"}
    )
    actual = analyze.explain["join"]["actual"]
    assert actual["strategy"] == "shuffle"
    assert actual["shuffleBytes"] > 0
    assert "heavyHitterSplits" in actual
    # explain_dump renders the join node
    from pinot_tpu.tools.explain_dump import render_explain

    text = render_explain(analyze.to_json())
    assert "join: shuffle" in text and "colocated:" in text


def test_join_shapes_reach_planstats(join_cluster):
    cl, _f, _d = join_cluster
    q = "SELECT max(f.v) FROM factT f JOIN dimT d ON f.k = d.k"
    resp = cl.broker.handle_pql(q)
    assert not resp.exceptions
    top = cl.broker.planstats.top(50, by="count")
    entry = next(e for e in top if e["digest"] == resp.plan_digest)
    assert "join dimT" in entry["summary"]


def test_join_excluded_from_micro_batching(join_cluster):
    """ISSUE 14 guard: join dispatches never enter the PR 13 batching
    tier — no batchHits on any join response, no batched launches on
    the lanes beyond what scans formed."""
    cl, _f, _d = join_cluster
    before = [
        (s.lanes.stats()["batchLaunches"] if s.lanes else 0) for s in cl.servers
    ]
    for t in (5, 15, 25, 35):
        resp = cl.broker.handle_pql(
            f"SELECT sum(f.v) FROM factT f JOIN dimT d ON f.k = d.k "
            f"WHERE f.v > {t}"
        )
        assert not resp.exceptions
        assert "batchHits" not in resp.cost
    after = [
        (s.lanes.stats()["batchLaunches"] if s.lanes else 0) for s in cl.servers
    ]
    assert after == before


def test_join_traces_show_exchange_phases(join_cluster):
    cl, _f, _d = join_cluster
    resp = cl.broker.handle_pql(
        "SELECT count(*) FROM factT f JOIN dimT d ON f.k = d.k",
        trace=True,
        debug_options={"joinStrategy": "shuffle"},
    )
    from pinot_tpu.tools.trace_dump import render_waterfall

    text = render_waterfall(resp.trace_info)
    for span in ("joinPlan", "joinBuildExtract", "joinProbeExtract",
                 "joinShuffleExec", "joinExec"):
        assert span in text, span


# ---------------------------------------------------------------------------
# failover + healing (chaos)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.parametrize("strategy", ["broadcast", "shuffle"])
def test_join_survives_replica_failure(strategy, tmp_path):
    """Replication 2: one server's transport dies mid-fleet; every
    strategy still answers exactly (failover to the live replica — for
    shuffle, owner re-dispatch onto the remaining owners)."""
    cl = InProcessCluster(num_servers=2, data_dir=str(tmp_path))
    try:
        fact, dim = _make_rows(seed=9, n=600, keys=30)
        cl.add_offline_table(_fact_schema("fA"), table_name="fA", replication=2)
        cl.add_offline_table(_dim_schema("dA"), table_name="dA", replication=2)
        cl.upload("fA_OFFLINE", build_segment(_fact_schema("fA"), fact, "fA_OFFLINE", segment_name="fA_0"))
        cl.upload("dA_OFFLINE", build_segment(_dim_schema("dA"), dim, "dA_OFFLINE", segment_name="dA_0"))
        q = "SELECT count(*), sum(f.v) FROM fA f JOIN dA d ON f.k = d.k"
        ok = cl.broker.handle_pql(q, debug_options={"joinStrategy": strategy})
        assert not ok.exceptions, ok.exceptions
        expected = _result_payload(ok)

        # sever server0's transport: every request to it now fails
        dead = cl.servers[0]
        cl.transport.register(
            (dead.name, 0),
            lambda payload: (_ for _ in ()).throw(ConnectionError("severed")),
        )
        resp = cl.broker.handle_pql(q, debug_options={"joinStrategy": strategy})
        assert not resp.exceptions, (strategy, resp.exceptions)
        assert not resp.partial_response
        assert _result_payload(resp) == expected
    finally:
        cl.stop()


@pytest.mark.chaos
def test_poisoned_join_plan_heals_to_host(tmp_path):
    """A join plan that deterministically fails on device quarantines
    and serves from the exact host join — byte-identical, transparent,
    exactly like a poisoned scan (shared heal counters + poison map)."""
    from pinot_tpu.common.faults import DeviceFaultInjector
    from pinot_tpu.server.instance import ServerInstance
    from pinot_tpu.server.starter import ServerStarter
    from pinot_tpu.controller.controller import Controller
    from pinot_tpu.broker.broker import BrokerRequestHandler
    from pinot_tpu.broker.starter import BrokerStarter
    from pinot_tpu.transport.local import LocalTransport

    controller = Controller(str(tmp_path))
    transport = LocalTransport()
    injector = DeviceFaultInjector(seed=1)
    server = ServerInstance("s0", device_fault_injector=injector)
    starter = ServerStarter(server, controller.resources)
    starter.start()
    transport.register(("s0", 0), server.handle_request)
    broker = BrokerRequestHandler(transport, {"s0": ("s0", 0)}, name="jb")
    BrokerStarter(broker, controller.resources).start()
    try:
        fact, dim = _make_rows(seed=2, n=500, keys=25)
        controller.add_schema(_fact_schema("fP"))
        controller.add_schema(_dim_schema("dP"))
        from pinot_tpu.common.tableconfig import TableConfig

        controller.add_table(TableConfig(table_name="fP", table_type="OFFLINE"))
        controller.add_table(TableConfig(table_name="dP", table_type="OFFLINE"))
        controller.upload_segment(
            "fP_OFFLINE", build_segment(_fact_schema("fP"), fact, "fP_OFFLINE", segment_name="fP_0")
        )
        controller.upload_segment(
            "dP_OFFLINE", build_segment(_dim_schema("dP"), dim, "dP_OFFLINE", segment_name="dP_0")
        )
        q = "SELECT count(*), sum(f.v) FROM fP f JOIN dP d ON f.k = d.k"
        healthy = broker.handle_pql(q, debug_options={"joinStrategy": "broadcast"})
        assert not healthy.exceptions, healthy.exceptions
        assert "deviceBytes" in healthy.cost  # device path proven

        # the next device launch fails DETERMINISTICALLY (non-retryable:
        # the executor quarantines the join plan without a device retry)
        injector.fail_next(1, retryable=False)
        resp = broker.handle_pql(q, debug_options={"joinStrategy": "broadcast"})
        assert not resp.exceptions, resp.exceptions
        assert _result_payload(resp) == _result_payload(healthy)
        heal = server.executor.healing_stats()
        assert heal["hostFailovers"] >= 1
        assert heal["poisonedPlans"] >= 1
        # quarantined: the next query skips the device outright
        resp2 = broker.handle_pql(q, debug_options={"joinStrategy": "broadcast"})
        assert not resp2.exceptions
        assert _result_payload(resp2) == _result_payload(healthy)
        assert server.executor.healing_stats()["poisonSkips"] >= 1
    finally:
        broker.shutdown()
        server.shutdown()
        controller.stop()


# ---------------------------------------------------------------------------
# zipf skew acceptance (chaos tier)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_zipf_shuffle_join_balances_heavy_hitters(tmp_path):
    """ISSUE 14 acceptance: a zipf s=1.2 shuffle join completes with no
    single server receiving >2x the mean shuffle bytes, the split is
    visible in metrics + EXPLAIN, and disabling the split degrades
    balance (proving the mechanism, not luck)."""
    import os

    cl = InProcessCluster(num_servers=4)
    try:
        rng = np.random.default_rng(21)
        keys = (np.minimum(rng.zipf(1.2, 12000), 300) - 1).astype(int)
        fact = [
            {"k": int(k), "grp": "g", "v": int(v)}
            for k, v in zip(keys, rng.integers(0, 50, keys.size))
        ]
        dim = [{"k": k, "cat": f"c{k % 5}", "w": k % 17} for k in range(300)]
        cl.add_offline_table(_fact_schema("fZ"), table_name="fZ", replication=1)
        cl.add_offline_table(_dim_schema("dZ"), table_name="dZ", replication=4)
        fs = _fact_schema("fZ")
        for i in range(4):
            cl.upload(
                "fZ_OFFLINE",
                build_segment(
                    fs, fact[i::4], "fZ_OFFLINE", segment_name=f"fZ_{i}"
                ),
            )
        cl.upload(
            "dZ_OFFLINE",
            build_segment(_dim_schema("dZ"), dim, "dZ_OFFLINE", segment_name="dZ_0"),
        )
        q = "SELECT count(*), sum(f.v) FROM fZ f JOIN dZ d ON f.k = d.k"
        joined = _oracle(fact, dim)
        before_splits = cl.broker.metrics.meter("join.heavyHitterSplits").count
        resp = cl.broker.handle_pql(
            "EXPLAIN ANALYZE " + q, debug_options={"joinStrategy": "shuffle"}
        )
        assert not resp.exceptions, resp.exceptions
        # exact answer under the skewed exchange
        assert resp.num_docs_scanned >= len(joined)  # joined + extraction scans
        vals = [a.value for a in resp.aggregation_results]
        assert int(vals[0]) == len(joined)
        assert float(vals[1]) == float(sum(f["v"] for f, _ in joined))
        actual = resp.explain["join"]["actual"]
        assert actual["heavyHitterSplits"] > 0
        assert (
            cl.broker.metrics.meter("join.heavyHitterSplits").count
            > before_splits
        )
        per = actual["shuffleBytesPerServer"]
        assert len(per) == 4
        mean = sum(per.values()) / len(per)
        assert max(per.values()) <= 2.0 * mean, per
        # mechanism check: with splitting disabled the hot owner is
        # strictly worse than with it on
        os.environ["PINOT_TPU_JOIN_SPLIT"] = "0"
        try:
            resp_ns = cl.broker.handle_pql(
                "EXPLAIN ANALYZE " + q, debug_options={"joinStrategy": "shuffle"}
            )
            per_ns = resp_ns.explain["join"]["actual"]["shuffleBytesPerServer"]
            mean_ns = sum(per_ns.values()) / len(per_ns)
            assert resp_ns.explain["join"]["actual"]["heavyHitterSplits"] == 0
            assert max(per.values()) / mean < max(per_ns.values()) / mean_ns
        finally:
            os.environ.pop("PINOT_TPU_JOIN_SPLIT")
    finally:
        cl.stop()


# ---------------------------------------------------------------------------
# result-cache interop guard
# ---------------------------------------------------------------------------


def test_colocated_join_result_cache_keys_both_tables(tmp_path, monkeypatch):
    monkeypatch.setenv("PINOT_TPU_RESULT_CACHE", "1")
    cl = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    try:
        fact, dim = _make_rows(seed=4, n=400, keys=20)
        part = PartitionConfig(column="k", num_partitions=1)
        cl.add_offline_table(
            _fact_schema("fC"), table_name="fC", replication=1, partitioning=part
        )
        cl.add_offline_table(
            _dim_schema("dC"), table_name="dC", replication=1, partitioning=part
        )
        cl.upload("fC_OFFLINE", build_segment(_fact_schema("fC"), fact, "fC_OFFLINE", segment_name="fC_0_p0"))
        cl.upload("dC_OFFLINE", build_segment(_dim_schema("dC"), dim, "dC_OFFLINE", segment_name="dC_0_p0"))
        q = "SELECT count(*), sum(f.v) FROM fC f JOIN dC d ON f.k = d.k"
        r1 = cl.broker.handle_pql(q)
        assert not r1.exceptions and "rescacheHits" not in r1.cost
        r2 = cl.broker.handle_pql(q)
        # hit: zero device/host work, identical payload
        assert r2.cost == {"rescacheHits": 1}, r2.cost
        assert _result_payload(r2) == _result_payload(r1)
        # an ingest/segment change on the BUILD side invalidates: the
        # next query re-executes against the grown build side (upload
        # through the controller so routing learns the new segment)
        evictions_before = (
            cl.servers[0].metrics.meter("rescache.staleEvictions").count
        )
        dim2 = dim + [{"k": 5, "cat": "c0", "w": 40}]
        cl.upload(
            "dC_OFFLINE",
            build_segment(_dim_schema("dC"), dim2[-1:], "dC_OFFLINE", segment_name="dC_1_p0"),
        )
        assert (
            cl.servers[0].metrics.meter("rescache.staleEvictions").count
            > evictions_before
        )
        r3 = cl.broker.handle_pql(q)
        assert not r3.exceptions
        assert r3.cost != {"rescacheHits": 1}
        exp = len(_oracle(fact, dim2))
        assert int(r3.aggregation_results[0].value) == exp
        # broadcast/shuffle joins never cache server-side
        r4 = cl.broker.handle_pql(q, debug_options={"joinStrategy": "broadcast"})
        r5 = cl.broker.handle_pql(q, debug_options={"joinStrategy": "broadcast"})
        assert not r5.exceptions and "rescacheHits" not in r5.cost
    finally:
        cl.stop()


# ---------------------------------------------------------------------------
# networked broker -> server path
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_join_strategies_over_networked_cluster(tmp_path):
    """All three strategies end-to-end over REAL protocol endpoints
    (HTTP registration/heartbeats, TCP scatter) — the acceptance's
    networked broker->server path, partitioning propagated through the
    clusterstate poll."""
    from pinot_tpu.common.tableconfig import TableConfig
    from pinot_tpu.tools.cluster_harness import NetworkedCluster

    cl = NetworkedCluster(num_servers=2, data_dir=str(tmp_path))
    try:
        fact, dim = _make_rows(seed=6, n=500, keys=24)
        part = PartitionConfig(column="k", num_partitions=2)
        cl.controller.add_schema(_fact_schema("fN"))
        cl.controller.add_schema(_dim_schema("dN"))
        fphys = cl.controller.add_table(
            TableConfig(table_name="fN", table_type="OFFLINE", replication=2,
                        partitioning=part)
        )
        dphys = cl.controller.add_table(
            TableConfig(table_name="dN", table_type="OFFLINE", replication=2,
                        partitioning=part)
        )
        for p in range(2):
            cl.controller.upload_segment(
                fphys,
                build_segment(
                    _fact_schema("fN"),
                    [r for r in fact if r["k"] % 2 == p],
                    fphys,
                    segment_name=f"fN_{p}_p{p}",
                ),
            )
            cl.controller.upload_segment(
                dphys,
                build_segment(
                    _dim_schema("dN"),
                    [r for r in dim if r["k"] % 2 == p],
                    dphys,
                    segment_name=f"dN_{p}_p{p}",
                ),
            )
        joined = _oracle(fact, dim)
        q = "SELECT count(*), sum(f.v) FROM fN f JOIN dN d ON f.k = d.k"

        def serving():
            # the default strategy answers as soon as one replica of every
            # segment is ONLINE, by whichever strategy is eligible then; the
            # forced ``colocated`` below also needs both tables' partitioning
            # and, on every server of the probe's cover, the build side's
            # partition: every replica ONLINE in the broker's view (lost
            # under load: "srv1 lacks local build partitions [1]")
            known = cl.broker.joinplan.partitions
            if known.get("fN") != ("k", 2) or known.get("dN") != ("k", 2):
                return False
            for phys in (fphys, dphys):
                view = cl.broker.routing.view_of(phys) or {}
                if len(view) != 2 or any(sorted(r.values()) != ["ONLINE", "ONLINE"] for r in view.values()):
                    return False
            r = cl.query(q)
            return not r.exceptions and int(
                r.aggregation_results[0].value
            ) == len(joined)

        cl.wait(serving, what="join serving over the network")
        payloads = set()
        for strat in _STRATS:
            r = cl.broker.handle_pql(q, debug_options={"joinStrategy": strat})
            assert not r.exceptions, (strat, r.exceptions)
            assert int(r.aggregation_results[0].value) == len(joined)
            payloads.add(_result_payload(r))
        assert len(payloads) == 1
        # partitioning reached the networked broker via the poll
        assert cl.broker.joinplan.partitions.get("fN") == ("k", 2)
    finally:
        cl.stop()
