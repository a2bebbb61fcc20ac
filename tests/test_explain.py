"""Workload introspection plane (PR 8): EXPLAIN / EXPLAIN ANALYZE,
the per-plan-digest stats registry, and the compile timeline.

Tier-1 guards: the EXPLAIN JSON top-level schema is golden (clients
script against it), plain EXPLAIN performs ZERO device work (no lane
submissions, no cost meters marked — safe to call in production), a
poisoned plan's EXPLAIN reports the host tier it will ACTUALLY serve
from, and /debug/plans tier mixes reconcile exactly with the
cost-vector tier counters after a mixed workload."""
import json
import math
import struct

import pytest

from pinot_tpu.common.datatable import MAGIC, deserialize_result, serialize_result
from pinot_tpu.engine.plandigest import plan_shape_digest, plan_shape_summary
from pinot_tpu.engine.results import IntermediateResult
from pinot_tpu.pql import parse_pql, optimize_request
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.tools.cluster_harness import InProcessCluster, single_server_broker
from pinot_tpu.tools.datagen import make_test_schema, random_rows


# --------------------------------------------------------------- parser
def test_explain_parser_prefix_variants():
    assert parse_pql("EXPLAIN SELECT count(*) FROM t").explain == "plan"
    assert parse_pql("EXPLAIN PLAN FOR SELECT count(*) FROM t").explain == "plan"
    assert parse_pql("EXPLAIN ANALYZE SELECT count(*) FROM t").explain == "analyze"
    assert parse_pql("SELECT count(*) FROM t").explain is None
    # the inner query still parses fully (filters, group by...)
    req = parse_pql("EXPLAIN SELECT sum(m) FROM t WHERE a > 5 GROUP BY b TOP 3")
    assert req.explain == "plan" and req.is_group_by
    # a broken inner query still raises a parse error
    from pinot_tpu.pql import PqlParseError

    with pytest.raises(PqlParseError):
        parse_pql("EXPLAIN SELECT FROM t")


# --------------------------------------------------------------- digest
def test_plan_shape_digest_erases_literals_not_shape():
    def dig(pql):
        return plan_shape_digest(optimize_request(parse_pql(pql)))

    # literals erased: same shape, different constants -> same digest
    assert dig("SELECT sum(m) FROM t WHERE a > 5") == dig(
        "SELECT sum(m) FROM t WHERE a > 999"
    )
    assert dig("SELECT count(*) FROM t WHERE a IN (1, 2)") == dig(
        "SELECT count(*) FROM t WHERE a IN (7, 8)"
    )
    # physical suffix stripped: broker (logical) and server (physical)
    # key the same series
    assert dig("SELECT sum(m) FROM t WHERE a > 5") == dig(
        "SELECT sum(m) FROM t_OFFLINE WHERE a > 5"
    )
    # shape changes change the digest
    assert dig("SELECT sum(m) FROM t WHERE a > 5") != dig(
        "SELECT sum(m) FROM t WHERE b > 5"
    )
    assert dig("SELECT sum(m) FROM t") != dig("SELECT max(m) FROM t")
    assert dig("SELECT sum(m) FROM t GROUP BY a") != dig(
        "SELECT sum(m) FROM t GROUP BY b"
    )
    # the EXPLAIN prefix itself does not change the shape
    assert dig("EXPLAIN SELECT sum(m) FROM t WHERE a > 5") == dig(
        "SELECT sum(m) FROM t WHERE a > 5"
    )
    s = plan_shape_summary(optimize_request(parse_pql(
        "SELECT sum(m) FROM t WHERE a > 5 GROUP BY b"
    )))
    assert "sum_m" in s and "from t" in s


# ----------------------------------------------------------------- wire
def test_plan_info_wire_roundtrip_and_backward_compat():
    res = IntermediateResult(plan_info=[{"server": "s0", "tierCounts": {"segmentsHost": 1}}])
    out = deserialize_result(serialize_result(res))
    assert out.plan_info == res.plan_info
    # a payload from a pre-introspection peer (no trailing plan list)
    # must still deserialize: chop the trailing empty list (b"l"+i64(0))
    # plus the later join-payload None (b"N") and freshness None (b"N")
    data = serialize_result(IntermediateResult(num_docs_scanned=3))
    payload = data[16:-11]
    old = MAGIC + struct.pack("<Q", len(payload)) + payload
    back = deserialize_result(old)
    assert back.num_docs_scanned == 3 and back.plan_info == []


# --------------------------------------------------- golden shape guard
EXPLAIN_TOP_KEYS = {
    "mode", "planDigest", "summary", "numServers", "tierCounts",
    "estimatedCost", "servers",
}
NODE_REQUIRED_KEYS = {
    "server", "table", "planDigest", "summary", "numSegments", "totalDocs",
    "tierCounts", "segments", "staged", "estimatedCost",
}


_FIXTURE_SEQ = __import__("itertools").count()


@pytest.fixture()
def explain_broker():
    # unique segment names per instantiation: the HBM ledger is
    # process-global and keys entries by segment name, so reused names
    # from an earlier test's staging would pollute the zero-staged guard
    n = next(_FIXTURE_SEQ)
    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, 1600, seed=5)
    segs = [
        build_segment(schema, rows[:800], "expTable", f"xg{n}a"),
        build_segment(schema, rows[800:], "expTable", f"xg{n}b"),
    ]
    broker = single_server_broker("expTable", segs, pipeline=True)
    broker.test_seg_names = [s.segment_name for s in segs]
    yield broker
    broker.local_servers[0].shutdown()


def test_explain_golden_schema_and_zero_device_work(explain_broker):
    """Schema-stability guard: the EXPLAIN JSON top level is golden,
    and plain EXPLAIN launches nothing — zero lane submissions, zero
    cost meters marked — on a COLD server (nothing ever staged)."""
    broker = explain_broker
    server = broker.local_servers[0]
    resp = broker.handle_pql(
        "EXPLAIN SELECT sum(metInt) FROM expTable WHERE dimInt > 40"
    )
    assert not resp.exceptions, resp.exceptions

    j = resp.to_json()
    assert set(j["explain"].keys()) == EXPLAIN_TOP_KEYS
    assert j["explain"]["mode"] == "plan"
    assert j["planDigest"] == j["explain"]["planDigest"]
    # EXPLAIN returns the plan INSTEAD of results
    assert "aggregationResults" not in j and "selectionResults" not in j

    node = j["explain"]["servers"][0]
    assert NODE_REQUIRED_KEYS.issubset(node.keys())
    assert node["tierCounts"] and sum(node["tierCounts"].values()) == 2
    for seg in node["segments"]:
        assert {"segment", "tier", "reason"}.issubset(seg.keys())

    # ZERO device work: no lane submission happened, no cost marked,
    # nothing got staged into HBM on this query's behalf
    lane = server.lane.stats()
    assert lane["dispatches"] == 0 and lane["depth"] == 0
    assert lane["coalesceHits"] == 0 and lane["shed"] == 0
    assert server.metrics.meter("cost.docsScanned").count == 0
    assert server.metrics.meter("cost.bytesScanned").count == 0
    for k in server._TIER_KEYS:
        assert server.metrics.meter(f"cost.tier.{k}").count == 0, k
    assert node["staged"]["hbmBytes"] == 0  # nothing staged by EXPLAIN
    # and the plan-stats registry did NOT count it as an execution
    assert server.plan_stats.snapshot()["plans"] == []
    assert server.metrics.meter("plan.explains").count == 1


# a filter no block's zone can hold, with the host tiers out of its way
_ZONE_FILTER = {"PINOT_TPU_ZONE_BLOCK": "32", "PINOT_TPU_INVINDEX": "0", "PINOT_TPU_BITSLICED": "0"}


@pytest.mark.parametrize(
    "settings, where, tier",
    [
        ({}, "dimInt > 40", "segmentsBitsliced"),
        (_ZONE_FILTER, "dimInt > 1000000", "segmentsZonemap"),
        # the zone program has no chunked form: a table over the
        # per-dispatch row budget (2 x 1,024 staged rows here) drops its
        # block ids, in EXPLAIN as at execution (ladder.inputs)
        (dict(_ZONE_FILTER, PINOT_TPU_CHUNK_ROWS="1024"), "dimInt > 1000000", "segmentsFullScan"),
    ],
    ids=["bitsliced", "zone_filter", "zone_filter_over_chunk_rows"],
)
def test_explain_device_digest_matches_real_execution(explain_broker, monkeypatch, settings, where, tier):
    """The phantom-staged StaticPlan digest must equal the digest the
    real execution hands the lane — else the compile registry and the
    poison-honesty lookup would silently miss — and the tier EXPLAIN
    names is the tier that serves."""
    for name, value in settings.items():
        monkeypatch.setenv(name, value)
    if settings:
        # such a filter empties every dictionary too: with the value
        # verdict (PR 48) in force no tier would be asked at all, and the
        # zone program is what this case is about
        monkeypatch.setattr("pinot_tpu.engine.pruner.value_dead", lambda seg, tree: None)
    broker = explain_broker
    server = broker.local_servers[0]
    pql = "SELECT sum(metInt) FROM expTable WHERE " + where
    pre = broker.handle_pql("EXPLAIN " + pql)
    assert pre.explain["servers"][0]["tierCounts"] == {tier: 2}
    dev = pre.explain["servers"][0]["device"]
    assert dev["compile"]["state"] == "cold"  # never launched here

    real = broker.handle_pql(pql)
    assert not real.exceptions
    assert real.cost[tier] == 2
    assert server.lane.stats()["compiledPlans"] >= 1
    assert server.lane.compile_info(dev["planDigest"]) is not None, (
        "phantom plan digest diverged from the real staged plan"
    )
    post = broker.handle_pql("EXPLAIN " + pql)
    comp = post.explain["servers"][0]["device"]["compile"]
    assert comp["state"] == "warm" and comp["firstCallMs"] > 0


def test_compile_timeline_cold_then_warm(explain_broker):
    broker = explain_broker
    server = broker.local_servers[0]
    pql = "SELECT max(metFloat) FROM expTable WHERE dimInt > 10"
    broker.handle_pql(pql)
    cold0 = server.metrics.meter("compile.cold").count
    assert cold0 >= 1
    assert server.metrics.timer("compile.firstCallMs").count == cold0
    broker.handle_pql(pql)
    assert server.metrics.meter("compile.cold").count == cold0  # no re-compile
    assert server.metrics.meter("compile.warm").count >= 1


def test_explain_analyze_actuals_match_cost(explain_broker):
    broker = explain_broker
    pql = "SELECT sum(metInt) FROM expTable GROUP BY dimStr TOP 5"
    resp = broker.handle_pql("EXPLAIN ANALYZE " + pql)
    assert not resp.exceptions
    ex = resp.explain
    assert ex["mode"] == "analyze"
    # results ARE returned for analyze (it executed)
    assert resp.aggregation_results is not None
    # node actuals sum exactly to the merged BrokerResponse.cost
    summed = {}
    for node in ex["servers"]:
        for k, v in node["actualCost"].items():
            summed[k] = summed.get(k, 0) + v
    assert set(summed) == set(resp.cost)
    for k, v in resp.cost.items():
        assert math.isclose(summed[k], v, rel_tol=1e-9), k
    assert ex["actualDocsScanned"] == resp.num_docs_scanned


# ----------------------------------------------- honesty under healing
@pytest.mark.chaos
def test_explain_honest_about_poison_quarantine():
    """A poisoned (quarantined) plan's EXPLAIN must report the host
    tier it will ACTUALLY serve from — not the device tier it would
    have picked — and flip back after clear_poisoned()."""
    from pinot_tpu.common.faults import DeviceFaultInjector

    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, 2000, seed=31)
    segs = [
        build_segment(schema, rows[:1000], "poisT", "q0"),
        build_segment(schema, rows[1000:], "poisT", "q1"),
    ]
    inj = DeviceFaultInjector(seed=7)
    broker = single_server_broker(
        "poisT", segs, pipeline=True, device_fault_injector=inj
    )
    server = broker.local_servers[0]
    try:
        pql = "SELECT sum(metInt) FROM poisT GROUP BY dimStr TOP 5"
        assert not broker.handle_pql(pql).exceptions
        pre = broker.handle_pql("EXPLAIN " + pql).explain["servers"][0]
        assert "segmentsHost" not in pre["tierCounts"]
        device_digest = pre["device"]["planDigest"]
        assert device_digest == inj.launches[-1].digest

        inj.poison_plan(device_digest)
        failed_over = broker.handle_pql(pql)  # quarantines + host-serves
        assert not failed_over.exceptions
        assert failed_over.cost.get("segmentsHost") == 2

        post = broker.handle_pql("EXPLAIN " + pql).explain["servers"][0]
        assert post["tierCounts"] == {"segmentsHost": 2}, post["tierCounts"]
        assert post["device"]["quarantined"] is True
        assert all(
            s["tier"] == "host" and "quarantined" in s["reason"]
            for s in post["segments"]
        )

        # re-admission: EXPLAIN flips back to the device tier
        inj.heal()
        server.executor.clear_poisoned()
        cleared = broker.handle_pql("EXPLAIN " + pql).explain["servers"][0]
        assert "segmentsHost" not in cleared["tierCounts"]
        assert cleared["device"]["quarantined"] is False
    finally:
        server.shutdown()


# ------------------------------------------ stats registry reconciliation
MIXED_WORKLOAD = [
    "SELECT count(*) FROM testTable",
    "SELECT count(*) FROM testTable",
    "SELECT sum(metInt), max(metFloat) FROM testTable WHERE dimInt > 40",
    "SELECT sum(metInt) FROM testTable GROUP BY dimStr TOP 5",
    "SELECT dimStr, metInt FROM testTable ORDER BY metInt DESC LIMIT 5",
    "SELECT sum(metInt), max(metFloat) FROM testTable WHERE dimInt > 80",
]


def test_plan_stats_reconcile_with_cost_tier_counters(tmp_path):
    """Acceptance: after a mixed workload, /debug/plans per-digest exec
    counts and tier mixes reconcile exactly with the cost-vector tier
    counters (cost.tier.* meters) and with the summed responses."""
    cluster = InProcessCluster(num_servers=2, data_dir=str(tmp_path))
    try:
        schema = make_test_schema(with_mv=False)
        physical = cluster.add_offline_table(schema, replication=2)
        rows = random_rows(schema, 2400, seed=13)
        for i in range(4):
            cluster.upload(
                physical,
                build_segment(
                    schema, rows[i * 600 : (i + 1) * 600], physical, f"w{i}"
                ),
            )
        expected_cost = {}
        for pql in MIXED_WORKLOAD:
            resp = cluster.query(pql)
            assert not resp.exceptions, (pql, resp.exceptions)
            for k, v in resp.cost.items():
                expected_cost[k] = expected_cost.get(k, 0) + v

        tier_keys = (
            "segmentsPruned", "segmentsPostings", "segmentsZonemap",
            "segmentsFullScan", "segmentsHost", "segmentsStarTree",
        )
        # per-server: plan-stats tier mixes == cost.tier.* meters
        for server in cluster.servers:
            snap = server.plan_stats.snapshot(top=50)
            assert snap["digests"] >= 1
            mix_sum = {}
            execs = 0
            for plan in snap["plans"]:
                execs += plan["count"]
                for k, v in plan["tierMix"].items():
                    mix_sum[k] = mix_sum.get(k, 0) + v
            assert execs == server.metrics.meter("plan.recorded").count
            for k in tier_keys:
                assert mix_sum.get(k, 0) == server.metrics.meter(
                    f"cost.tier.{k}"
                ).count, k
        # cluster-wide: server tier meters sum to the responses' tiers
        for k in tier_keys:
            total = sum(
                s.metrics.meter(f"cost.tier.{k}").count for s in cluster.servers
            )
            assert total == expected_cost.get(k, 0), k

        # broker workload roll-up: distinct shapes, counts, both orders
        wl = cluster.broker.workload_snapshot()
        distinct = len({plan_shape_digest(optimize_request(parse_pql(p)))
                        for p in MIXED_WORKLOAD})
        assert wl["digests"] == distinct
        assert sum(p["count"] for p in wl["topByCount"]) == len(MIXED_WORKLOAD)
        top = wl["topByCount"][0]
        assert top["count"] == 2  # the repeated count(*) leads by frequency
        assert {p["digest"] for p in wl["topByCost"]} == {
            p["digest"] for p in wl["topByCount"]
        }

        # querylog cross-link: entries carry the digest of their shape
        from pinot_tpu.broker.querylog import SlowQueryLog

        old_log = cluster.broker.querylog
        cluster.broker.querylog = SlowQueryLog(threshold_ms=0.0)
        try:
            resp = cluster.query(MIXED_WORKLOAD[0], trace=True)
            entry = cluster.broker.querylog.entries()[0]
            assert entry["planDigest"] == resp.plan_digest
            assert any(
                p["digest"] == entry["planDigest"] for p in wl["topByCount"]
            )
            # trace_dump footer renders the tier decisions + the digest
            from pinot_tpu.tools.trace_dump import render_tiers

            footer = render_tiers(resp.to_json())
            assert f"planDigest={resp.plan_digest}" in footer
            assert "=" in footer and footer.startswith("tiers: ")
        finally:
            cluster.broker.querylog = old_log
    finally:
        cluster.stop()


# --------------------------------------------------- endpoints + pages
def test_workload_endpoints_and_dashboard(tmp_path):
    import urllib.request

    from pinot_tpu.controller.controller import (
        ControllerHttpServer,
        collect_workload,
    )
    from pinot_tpu.server.network_starter import ServerAdminHttpServer

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path), http=True)
    admin = None
    http = None
    try:
        schema = make_test_schema(with_mv=False)
        physical = cluster.add_offline_table(schema)
        rows = random_rows(schema, 600, seed=19)
        cluster.upload(physical, build_segment(schema, rows, physical, "wd0"))
        for _ in range(3):
            assert not cluster.query(
                "SELECT sum(metInt) FROM testTable WHERE dimInt > 5"
            ).exceptions

        # broker /debug/workload over HTTP
        base = f"http://{cluster.http.host}:{cluster.http.port}"
        with urllib.request.urlopen(base + "/debug/workload", timeout=10) as r:
            wl = json.loads(r.read())
        assert wl["digests"] == 1 and wl["topByCount"][0]["count"] == 3
        assert wl["topByCount"][0]["cost"]["bytesScanned"] > 0

        # server /debug/plans over the admin surface
        admin = ServerAdminHttpServer(cluster.servers[0])
        admin.start()
        with urllib.request.urlopen(admin.url + "/debug/plans", timeout=10) as r:
            plans = json.loads(r.read())
        assert plans["digests"] == 1
        assert plans["plans"][0]["count"] == 3
        assert plans["plans"][0]["tierMix"]
        with urllib.request.urlopen(
            admin.url + "/debug/plans?by=cost", timeout=10
        ) as r:
            assert json.loads(r.read())["orderedBy"] == "cost"
        # and in status() for in-process harnesses
        assert cluster.servers[0].status()["plans"]["digests"] == 1

        # controller roll-up + dashboard page
        wl2 = collect_workload(cluster.controller)
        assert wl2["brokers"] == 1 and wl2["digests"] == 1
        assert wl2["topByCount"][0]["count"] == 3
        http = ControllerHttpServer(cluster.controller)
        http.start()
        cbase = f"http://127.0.0.1:{http.port}"
        with urllib.request.urlopen(cbase + "/debug/workload", timeout=10) as r:
            over = json.loads(r.read())
        assert over["digests"] == 1
        with urllib.request.urlopen(cbase + "/dashboard/workload", timeout=10) as r:
            page = r.read().decode()
        assert "Workload" in page and over["topByCount"][0]["digest"] in page
    finally:
        if http is not None:
            http.stop()
        if admin is not None:
            admin.stop()
        cluster.stop()


# -------------------------------------------------------- explain_dump
def test_explain_dump_renders_plan_and_analyze(explain_broker):
    from pinot_tpu.tools.explain_dump import render_explain

    broker = explain_broker
    pql = "SELECT sum(metInt) FROM expTable WHERE dimInt > 40"
    plan = broker.handle_pql("EXPLAIN " + pql)
    out = render_explain(plan.to_json())
    assert out.startswith("EXPLAIN ")
    assert "digest=" in out and "server benchServer" in out
    for name in broker.test_seg_names:
        assert name in out

    analyze = broker.handle_pql("EXPLAIN ANALYZE " + pql)
    out2 = render_explain(analyze.to_json())
    assert "EXPLAIN ANALYZE" in out2
    assert "actual:" in out2 and "est=" in out2 and "x)" in out2

    # graceful on a non-explain response
    assert render_explain({"numDocsScanned": 5}).startswith("(no explain tree")


def test_explain_names_the_value_dead_segments_and_the_launch_over_the_rest(monkeypatch):
    """The value pruner (PR 48) in EXPLAIN: a segment the filter's
    literals are not in the dictionaries of is recorded as pruned with the
    leaf, the column and the segment's [min, max]; the device record
    prints the segments the program runs over and L of S; what is served
    counts the same; and the prewarm worker's spec compiles the program
    the ladder derives for that launch, at its L."""
    import numpy as np

    from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema
    from pinot_tpu.engine import explain as explain_mod
    from pinot_tpu.segment.columnar import build_segment_from_columns

    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")
    schema = Schema("dated", dimensions=[FieldSpec("yr", DataType.INT), FieldSpec("g", DataType.INT)],
                    metrics=[FieldSpec("v", DataType.INT, FieldType.METRIC)])
    n, rng = 512, np.random.default_rng(48)
    segs = [  # segment i holds the years 1990 + i and 1991 + i
        build_segment_from_columns(
            schema, {"yr": (1990 + i + rng.integers(0, 2, size=n)).astype(np.int32),
                     "g": rng.integers(0, 5, size=n).astype(np.int32), "v": rng.integers(1, 9, size=n).astype(np.int32)},
            n, "dated", f"vp{next(_FIXTURE_SEQ)}_{i}")
        for i in range(8)
    ]
    broker = single_server_broker("dated", segs, pipeline=True)
    server = broker.local_servers[0]
    try:
        pql = "SELECT sum(v) FROM dated WHERE yr = 1996 OR yr = 1998 GROUP BY g TOP 5"  # segments 5, 6 and 7: L = 4 of 8
        node = broker.handle_pql("EXPLAIN " + pql).explain["servers"][0]
        assert node["tierCounts"] == {"segmentsPruned": 5, "segmentsFullScan": 3} and node["totalDocs"] == 8 * n
        by_name = {r["segment"]: r for r in node["segments"]}
        dead = by_name[segs[0].segment_name]
        assert dead["tier"] == "pruned" and dead["reason"].endswith("(ValueSegmentPruner)")
        assert "yr" in dead["reason"] and "[1990,1991]" in dead["reason"] and "1996" in dead["reason"] and "1998" in dead["reason"]
        assert [by_name[s.segment_name]["tier"] for s in segs[5:]] == ["fullScan"] * 3
        launch = node["device"]["launch"]
        assert launch == {"segments": 4, "ofStaged": 8, "scanned": [s.segment_name for s in segs[5:]]}
        whole = broker.handle_pql("EXPLAIN SELECT sum(v) FROM dated WHERE yr > 1990 GROUP BY g TOP 5").explain["servers"][0]
        assert whole["device"]["launch"]["segments"] == whole["device"]["launch"]["ofStaged"] == 8

        # the prewarm worker's spec: the same program, lowered with the launch's four positions
        lowered = []
        real_program = explain_mod.ladder.program

        def spy(plan, staged, q_np, block_ids, mesh):
            kernel = real_program(plan, staged, q_np, block_ids, mesh)
            lowered.append((kernel, q_np["segments"]["slots"].tolist(), int(q_np["segments"]["first"])))
            return kernel

        monkeypatch.setattr(explain_mod.ladder, "program", spy)
        spec = explain_mod.build_prewarm_spec(server.executor, segs, optimize_request(parse_pql(pql)))
        # a launch size is a compile of its own: the lane's timeline keeps it under the plan's digest with L
        assert spec is not None and spec["planDigest"] == node["device"]["planDigest"] + ".L4"
        assert node["device"]["compile"]["state"] == "cold"
        spec["compile"]()
        assert lowered[-1][1:] == ([-1, 5, 6, 7], 4)  # the four last neighbours of the eight, one slice

        reply = broker.handle_pql(pql).to_json()
        assert lowered[-1][0] is lowered[0][0] and len(lowered) == 2  # the serving launch asks for the program prewarmed
        assert reply["totalDocs"] == 8 * n and reply["numSegmentsQueried"] == 3
        assert reply["cost"]["segmentsPruned"] == 5 and reply["cost"]["segmentsFullScan"] == 3
        want = {}
        for s in segs[5:]:
            yr, g, v = (np.asarray(s.column(c).dictionary.values)[s.column(c).fwd] for c in ("yr", "g", "v"))
            for key in range(5):
                want[key] = want.get(key, 0) + int(v[(g == key) & np.isin(yr, (1996, 1998))].sum())
        got = {int(r["group"][0]): float(r["value"]) for r in reply["aggregationResults"][0]["groupByResult"]}
        assert got == {k: float(x) for k, x in want.items()}

        # the same plan with literals that leave two neighbours: L = 2 has not compiled, and nothing says it has
        lane = spec["lane"]
        assert lane.compile_info(spec["planDigest"])["launches"] == 1 and lane.compile_info(node["device"]["planDigest"]) is None
        assert broker.handle_pql("EXPLAIN " + pql).explain["servers"][0]["device"]["compile"]["state"] == "warm"
        two = "SELECT sum(v) FROM dated WHERE yr = 1997 OR yr = 1998 GROUP BY g TOP 5"  # segments 6 and 7
        node2 = broker.handle_pql("EXPLAIN " + two).explain["servers"][0]
        assert node2["device"]["planDigest"] == node["device"]["planDigest"] and node2["device"]["launch"]["segments"] == 2
        assert node2["device"]["compile"]["state"] == "cold"
        spec2 = explain_mod.build_prewarm_spec(server.executor, segs, optimize_request(parse_pql(two)))
        assert spec2 is not None and spec2["planDigest"] == node["device"]["planDigest"] + ".L2"
        cold = server.metrics.meter("compile.cold").count
        assert broker.handle_pql(two).to_json()["numSegmentsQueried"] == 2
        assert server.metrics.meter("compile.cold").count == cold + 1 and lane.compile_info(spec2["planDigest"])["via"] == "cold"
        assert explain_mod.build_prewarm_spec(server.executor, segs, optimize_request(parse_pql(two))) is None  # launched: warm
    finally:
        server.shutdown()
