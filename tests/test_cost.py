"""Cost-accounting plane (PR 6): per-query cost vector wire + merge
invariants (broker totals == sum of server totals, under failover /
hedging / partial responses / kill-server chaos), device-vs-host cost
consistency, HBM staging-ledger byte accuracy, ingest lag draining, the
perf regression gate, and pre-registered series."""
import json
import math
import os
import struct
import time

import pytest

from pinot_tpu.common.datatable import MAGIC, deserialize_result, serialize_result
from pinot_tpu.engine.results import IntermediateResult
from pinot_tpu.pql import parse_pql
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.tools.cluster_harness import InProcessCluster, single_server_broker
from pinot_tpu.tools.datagen import make_test_schema, random_rows



# ------------------------------------------------------------------ wire
def test_cost_vector_wire_roundtrip_and_additive_merge():
    a = IntermediateResult(
        num_docs_scanned=5,
        cost={"bytesScanned": 100, "deviceMs": 1.5, "segmentsFullScan": 2},
    )
    b = deserialize_result(serialize_result(a))
    assert b.cost == a.cost
    b.merge(
        IntermediateResult(cost={"bytesScanned": 11, "hostMs": 2.0, "segmentsHost": 1})
    )
    assert b.cost == {
        "bytesScanned": 111,
        "deviceMs": 1.5,
        "hostMs": 2.0,
        "segmentsFullScan": 2,
        "segmentsHost": 1,
    }


def test_cost_wire_backward_compat_old_payload_without_cost():
    """A payload from a pre-cost peer (no trailing cost field) must
    still deserialize — mixed-version operation."""
    data = serialize_result(IntermediateResult(num_docs_scanned=7))
    # the trailing optional fields are empty cost dict (b"d"+i64(0) = 9
    # bytes), empty backpressure dict (9), empty plan-info list (9), the
    # join-payload None (b"N" = 1) and the freshness None (1); chop all
    # five and fix the length header to emulate the pre-cost wire format
    payload = data[16:-29]
    old = MAGIC + struct.pack("<Q", len(payload)) + payload
    res = deserialize_result(old)
    assert res.num_docs_scanned == 7
    assert res.cost == {}


# ------------------------------------------ invariant: broker == Σ servers
class _SpyTransport:
    """Wraps a transport, recording every successful reply's bytes (a
    raised attempt never delivered data, so it cannot count)."""

    def __init__(self, inner, delay_for=None, delay_s=0.0):
        self.inner = inner
        self.replies = []
        self.delay_for = delay_for
        self.delay_s = delay_s

    def request(self, address, payload, timeout=15.0):
        if self.delay_for is not None and address == self.delay_for:
            time.sleep(self.delay_s)
        reply = self.inner.request(address, payload, timeout)
        self.replies.append(reply)
        return reply

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _sum_replies(replies):
    docs, cost = 0, {}
    for raw in replies:
        res = deserialize_result(raw)
        docs += res.num_docs_scanned
        for k, v in res.cost.items():
            cost[k] = cost.get(k, 0) + v
    return docs, cost


def _assert_invariant(resp, replies):
    docs, cost = _sum_replies(replies)
    assert resp.num_docs_scanned == docs
    assert set(resp.cost) == set(cost)
    for k, v in cost.items():
        assert math.isclose(resp.cost[k], v, rel_tol=1e-9), (k, resp.cost[k], v)
    # served-tier counts partition the queried segments exactly
    tiers = sum(
        resp.cost.get(k, 0)
        for k in (
            "segmentsPostings",
            "segmentsBitsliced",
            "segmentsZonemap",
            "segmentsFullScan",
            "segmentsHost",
            "segmentsStarTree",
        )
    )
    assert tiers == resp.num_segments_queried


@pytest.fixture(scope="module")
def cost_cluster():
    cluster = InProcessCluster(num_servers=2)
    schema = make_test_schema(with_mv=False)
    physical = cluster.add_offline_table(schema, replication=2)
    rows = random_rows(schema, 2400, seed=13)
    total = 0
    for i in range(4):
        seg = rows[i * 600 : (i + 1) * 600]
        cluster.upload(physical, build_segment(schema, seg, physical, f"cseg{i}"))
        total += len(seg)
    spy = _SpyTransport(cluster.transport)
    cluster.broker.transport = spy
    yield cluster, spy, total
    cluster.broker.transport = spy.inner
    cluster.stop()


COST_QUERIES = [
    "SELECT count(*) FROM testTable",
    "SELECT sum(metInt), max(metFloat) FROM testTable WHERE dimInt > 40",
    "SELECT sum(metInt) FROM testTable GROUP BY dimStr TOP 5",
    "SELECT dimStr, metInt FROM testTable ORDER BY metInt DESC LIMIT 5",
]


@pytest.mark.parametrize("pql", COST_QUERIES)
def test_broker_cost_equals_sum_of_server_costs(cost_cluster, pql):
    cluster, spy, total = cost_cluster
    spy.replies.clear()
    resp = cluster.query(pql)
    assert not resp.exceptions
    _assert_invariant(resp, spy.replies)
    assert resp.cost.get("bytesScanned", 0) > 0
    assert len(spy.replies) >= 2  # genuinely scattered across servers


def test_cost_invariant_under_replica_failover(cost_cluster):
    """A dead replica's attempts raise (no data): the broker re-covers
    on the alternate and the invariant holds over the merged replies."""
    cluster, spy, total = cost_cluster
    victim = cluster.servers[0].name
    spy.inner.set_down((victim, 0))
    try:
        spy.replies.clear()
        resp = cluster.query("SELECT count(*) FROM testTable")
        assert not resp.exceptions
        assert resp.num_retries >= 1
        assert not resp.partial_response
        assert resp.num_docs_scanned == total
        _assert_invariant(resp, spy.replies)
    finally:
        spy.inner.set_down((victim, 0), down=False)


def _sum_node_actuals(resp):
    summed = {}
    for node in resp.explain["servers"]:
        for k, v in (node.get("actualCost") or {}).items():
            summed[k] = summed.get(k, 0) + v
    return summed


def test_explain_analyze_actuals_sum_to_merged_cost(cost_cluster):
    """EXPLAIN ANALYZE per-server plan-node actuals sum EXACTLY to the
    merged BrokerResponse.cost (the introspection plane's core honesty
    invariant, sibling of the broker == Σ servers cost invariant)."""
    cluster, spy, total = cost_cluster
    resp = cluster.query("EXPLAIN ANALYZE SELECT count(*) FROM testTable")
    assert not resp.exceptions
    assert resp.explain["mode"] == "analyze"
    summed = _sum_node_actuals(resp)
    assert set(summed) == set(resp.cost)
    for k, v in resp.cost.items():
        assert math.isclose(summed[k], v, rel_tol=1e-9), k
    assert resp.explain["actualDocsScanned"] == resp.num_docs_scanned == total


def test_explain_analyze_actuals_sum_under_replica_failover(cost_cluster):
    """A dead replica's attempts deliver no data (and no plan node):
    after failover only the MERGED replies' nodes survive, so the
    actuals still sum exactly to the merged cost."""
    cluster, spy, total = cost_cluster
    victim = cluster.servers[0].name
    spy.inner.set_down((victim, 0))
    try:
        spy.replies.clear()
        resp = cluster.query("EXPLAIN ANALYZE SELECT count(*) FROM testTable")
        assert not resp.exceptions
        assert resp.num_retries >= 1 and not resp.partial_response
        summed = _sum_node_actuals(resp)
        assert set(summed) == set(resp.cost)
        for k, v in resp.cost.items():
            assert math.isclose(summed[k], v, rel_tol=1e-9), k
        assert resp.explain["actualDocsScanned"] == total
        # exactly the merged replies carry nodes: no phantom/duplicate
        # attribution from the failed attempts
        assert len(resp.explain["servers"]) == len(spy.replies)
    finally:
        spy.inner.set_down((victim, 0), down=False)


def test_explain_analyze_actuals_sum_under_partial_response(tmp_path):
    """Replication=1 with a dead server: the response degrades honestly
    AND the surviving servers' plan-node actuals still equal the merged
    cost — unserved segments attribute to nobody."""
    cluster = InProcessCluster(num_servers=2, data_dir=str(tmp_path))
    try:
        schema = make_test_schema(with_mv=False)
        physical = cluster.add_offline_table(schema, replication=1)
        rows = random_rows(schema, 1200, seed=17)
        for i in range(4):
            cluster.upload(
                physical,
                build_segment(
                    schema, rows[i * 300 : (i + 1) * 300], physical, f"xseg{i}"
                ),
            )
        spy = _SpyTransport(cluster.transport)
        cluster.broker.transport = spy
        victim = cluster.servers[0].name
        spy.inner.set_down((victim, 0))
        resp = cluster.query("EXPLAIN ANALYZE SELECT count(*) FROM testTable")
        assert resp.partial_response and resp.num_segments_unserved > 0
        summed = _sum_node_actuals(resp)
        assert set(summed) == set(resp.cost)
        for k, v in resp.cost.items():
            assert math.isclose(summed[k], v, rel_tol=1e-9), k
        assert 0 < resp.explain["actualDocsScanned"] < 1200
    finally:
        cluster.stop()


def test_cost_invariant_under_hedging(cost_cluster):
    """A hedged attempt's winner covers the identical segment set: the
    response cost must match the steady-state answer exactly for the
    integer components (a hedge must never double-count)."""
    cluster, spy, total = cost_cluster
    baseline = cluster.query("SELECT count(*) FROM testTable")
    broker = cluster.broker
    old_delay = broker.hedge_delay_ms
    victim = cluster.servers[0].name
    spy.delay_for, spy.delay_s = (victim, 0), 0.25
    broker.hedge_delay_ms = 30.0
    try:
        resp = cluster.query("SELECT count(*) FROM testTable")
        assert not resp.exceptions
        assert resp.num_hedges >= 1
        assert resp.num_docs_scanned == baseline.num_docs_scanned == total
        for k in ("segmentsPostings", "segmentsBitsliced", "segmentsZonemap",
                  "segmentsFullScan", "segmentsHost", "segmentsStarTree",
                  "segmentsPruned"):
            assert resp.cost.get(k, 0) == baseline.cost.get(k, 0), k
        assert resp.num_segments_queried == baseline.num_segments_queried
    finally:
        broker.hedge_delay_ms = old_delay
        spy.delay_for, spy.delay_s = None, 0.0


def test_cost_invariant_under_partial_response(tmp_path):
    """Replication=1 and a dead server: the response degrades honestly
    AND its cost equals the sum of what the surviving servers served."""
    cluster = InProcessCluster(num_servers=2, data_dir=str(tmp_path))
    try:
        schema = make_test_schema(with_mv=False)
        physical = cluster.add_offline_table(schema, replication=1)
        rows = random_rows(schema, 1200, seed=17)
        for i in range(4):
            cluster.upload(
                physical,
                build_segment(
                    schema, rows[i * 300 : (i + 1) * 300], physical, f"pseg{i}"
                ),
            )
        spy = _SpyTransport(cluster.transport)
        cluster.broker.transport = spy
        victim = cluster.servers[0].name
        spy.inner.set_down((victim, 0))
        spy.replies.clear()
        resp = cluster.query("SELECT count(*) FROM testTable")
        assert resp.partial_response and resp.num_segments_unserved > 0
        _assert_invariant(resp, spy.replies)
        assert 0 < resp.num_docs_scanned < 1200
    finally:
        cluster.stop()


@pytest.mark.chaos
def test_cost_invariant_under_kill_server_chaos(tmp_path):
    """Acceptance: the merge invariant holds through the kill-server
    scenario — a server dies, the stabilizer re-replicates, and every
    post-heal response's cost still equals the sum of its server
    replies with zero docs lost."""
    cluster = InProcessCluster(num_servers=3, data_dir=str(tmp_path))
    try:
        cluster.controller.stabilizer.grace_s = 0.0
        schema = make_test_schema(with_mv=False)
        physical = cluster.add_offline_table(schema, replication=2)
        rows = random_rows(schema, 1500, seed=23)
        total = 0
        for i in range(5):
            seg = rows[i * 300 : (i + 1) * 300]
            cluster.upload(physical, build_segment(schema, seg, physical, f"kseg{i}"))
            total += len(seg)
        spy = _SpyTransport(cluster.transport)
        cluster.broker.transport = spy

        victim = cluster.servers[0].name
        spy.inner.set_down((victim, 0))
        cluster.controller.resources.set_instance_alive(victim, False)
        for _ in range(2):
            cluster.controller.stabilizer.run_once()

        for pql in COST_QUERIES:
            spy.replies.clear()
            resp = cluster.query(pql)
            assert not resp.exceptions, (pql, resp.exceptions)
            assert not resp.partial_response
            _assert_invariant(resp, spy.replies)
        final = cluster.query("SELECT count(*) FROM testTable")
        assert final.num_docs_scanned == total
    finally:
        cluster.stop()


# ------------------------------------------- device vs host consistency
@pytest.mark.chaos
def test_host_failover_cost_consistent_with_device_path():
    """The same query served via host failover reports the same docs
    and result payload as the device run; only the tier/timing parts of
    the cost vector move (device -> host)."""
    from pinot_tpu.common.faults import DeviceFaultInjector

    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, 2000, seed=31)
    segs = [
        build_segment(schema, rows[:1000], "costHeal", "ch0"),
        build_segment(schema, rows[1000:], "costHeal", "ch1"),
    ]
    inj = DeviceFaultInjector(seed=7)
    broker = single_server_broker(
        "costHeal", segs, pipeline=True, device_fault_injector=inj
    )
    try:
        pql = "SELECT sum(metInt) FROM costHeal GROUP BY dimStr TOP 5"
        healthy = broker.handle_pql(pql)
        assert not healthy.exceptions
        assert healthy.cost.get("segmentsFullScan", 0) + healthy.cost.get(
            "segmentsZonemap", 0
        ) == len(segs)
        assert healthy.cost.get("deviceMs", 0) > 0
        assert "segmentsHost" not in healthy.cost

        digest = inj.launches[-1].digest
        assert digest is not None
        inj.poison_plan(digest)
        failed_over = broker.handle_pql(pql)
        assert not failed_over.exceptions
        assert failed_over.cost.get("segmentsHost", 0) == len(segs)
        assert failed_over.cost.get("hostMs", 0) > 0
        # identical answer + docs accounting, path-independent
        assert failed_over.num_docs_scanned == healthy.num_docs_scanned
        hj, fj = healthy.to_json(), failed_over.to_json()
        for k in ("timeUsedMs", "requestId", "cost",
                  "numEntriesScannedInFilter", "numEntriesScannedPostFilter"):
            hj.pop(k, None)
            fj.pop(k, None)
        assert hj == fj
    finally:
        broker.local_servers[0].shutdown()


# ------------------------------------------------------- HBM ledger
def _independent_staged_bytes(staged) -> int:
    """Re-derive a staged table's device bytes straight off its arrays
    (independent of the ledger's own measurement helper)."""
    total = int(staged.num_docs_arr.nbytes)
    if staged._valid is not None:
        total += int(staged._valid.nbytes)
    for sc in staged.columns.values():
        for attr in ("fwd", "mv", "mv_counts", "dict_vals", "raw", "gfwd",
                     "hll_bucket", "hll_rho", "mv_raw", "bsi", "bsiv"):
            arr = getattr(sc, attr)
            if arr is not None:
                total += int(arr.nbytes)
    return total


def test_hbm_ledger_matches_staged_array_bytes_within_1pct():
    from pinot_tpu.engine import device as device_mod
    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.pql import optimize_request

    device_mod.clear_staging_cache()
    assert device_mod.LEDGER.total_bytes() == 0

    schema = make_test_schema(with_mv=True)
    rows = random_rows(schema, 1500, seed=41)
    segs = [
        build_segment(schema, rows[:750], "ledgerTable", "ls0"),
        build_segment(schema, rows[750:], "ledgerTable", "ls1"),
    ]
    ex = QueryExecutor()
    for pql in (
        "SELECT count(*) FROM ledgerTable WHERE dimInt > 10",
        "SELECT sum(metInt) FROM ledgerTable GROUP BY dimStr TOP 5",
    ):
        req = optimize_request(parse_pql(pql))
        ex.execute(segs, req)

    expected = sum(
        _independent_staged_bytes(st) for st in device_mod._stage_cache.values()
    )
    got = device_mod.LEDGER.total_bytes()
    assert expected > 0
    assert abs(got - expected) <= 0.01 * expected, (got, expected)

    snap = device_mod.LEDGER.snapshot()
    assert snap["stagedBytes"] == got
    assert snap["highWatermarkBytes"] >= got
    assert "ledgerTable" in snap["byTable"]
    assert snap["byTable"]["ledgerTable"] == got  # only table staged
    assert snap["stagedTables"] == len(device_mod._stage_cache)
    assert sum(snap["byRole"].values()) == got

    # eviction visibility: quarantining a segment releases its bytes
    ev0, evb0 = snap["evictions"], snap["evictedBytes"]
    dropped = device_mod.evict_staged_segment("ls0")
    assert dropped >= 1
    snap2 = device_mod.LEDGER.snapshot()
    assert snap2["stagedBytes"] < got
    assert snap2["evictions"] > ev0
    assert snap2["evictedBytes"] > evb0
    device_mod.clear_staging_cache()
    assert device_mod.LEDGER.total_bytes() == 0


# ------------------------------------------------------- ingest lag
def test_ingest_lag_drains_to_zero_after_commit(tmp_path):
    from pinot_tpu.realtime.llc import make_segment_name
    from pinot_tpu.realtime.stream import MemoryStreamProvider

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    try:
        schema = _rsvp_schema()
        stream = MemoryStreamProvider(num_partitions=1)
        physical = cluster.add_realtime_table(schema, stream, rows_per_segment=50)
        server = cluster.servers[0]
        gauge = server.metrics.gauge(f"ingest.lag.{physical}.p0")

        for i in range(70):
            stream.produce(_rsvp_row(i))
        # nothing consumed yet: lag = full backlog (live set_fn read)
        assert gauge.value == 70

        seg0 = make_segment_name(physical, 0, 0)
        dm = cluster.controller.realtime_manager.consumers_of(seg0)[0]
        dm.consume_step(max_rows=1000)  # seals at the 50-row threshold
        assert gauge.value == 20
        assert dm.try_commit() == "KEEP"

        # post-commit: the rollover consumer owns the gauge; catching up
        # provably drains the lag to 0
        seg1 = make_segment_name(physical, 0, 1)
        dm1 = cluster.controller.realtime_manager.consumers_of(seg1)[0]
        assert dm1.offset == 50
        dm1.consume_step(max_rows=1000)
        assert gauge.value == 0

        assert server.metrics.meter("ingest.rowsConsumed").count == 70
        assert server.metrics.timer("ingest.commitMs").count >= 1
        assert cluster.controller.metrics.meter("segmentCommits").count == 1
        assert cluster.controller.metrics.timer("segmentCommitMs").count == 1

        # a STOPPED consumer detaches its gauge: its frozen offset must
        # not keep reporting phantom lag as producers write on
        cluster.controller.realtime_manager.release_segment_consumers(seg1)
        for i in range(70, 80):
            stream.produce(_rsvp_row(i))
        assert gauge.value == 0
    finally:
        cluster.stop()


def _rsvp_schema():
    from pinot_tpu.common.schema import (
        DataType, FieldSpec, FieldType, Schema, TimeFieldSpec,
    )

    return Schema(
        "costRsvp",
        dimensions=[FieldSpec("venue", DataType.STRING)],
        metrics=[FieldSpec("n", DataType.INT, FieldType.METRIC)],
        time_field=TimeFieldSpec("ts", DataType.LONG, time_unit="MILLISECONDS"),
    )


def _rsvp_row(i):
    return {"venue": f"v{i % 3}", "n": i % 5, "ts": 1_000_000 + i}


# ----------------------------------------------- pre-registered series
def test_cost_and_hbm_series_preregistered_at_zero():
    from pinot_tpu.broker.broker import BrokerRequestHandler
    from pinot_tpu.server.instance import ServerInstance
    from pinot_tpu.transport.local import LocalTransport
    from pinot_tpu.utils.metrics import prometheus_text

    server = ServerInstance("freshServer")
    try:
        text = server.metrics_text()
        for needle in (
            "cost_docsScanned_total",
            "cost_bytesScanned_total",
            "hbm_stagedBytes",
            "hbm_highWatermarkBytes",
            "hbm_qinputCacheBytes",
            "ingest_rowsConsumed_total",
            "cost_deviceMs_ms_count",
            "ingest_commitMs_ms_count",
        ):
            assert needle in text, needle
    finally:
        server.shutdown()

    broker = BrokerRequestHandler(LocalTransport(), {}, name="freshBroker")
    text = prometheus_text(broker.metrics)
    for needle in ("cost_docsScanned_total", "cost_bytesScanned_total",
                   "cost_hostMs_ms_count"):
        assert needle in text, needle


# ------------------------------------------------- slow-query log + dump
def test_querylog_and_trace_dump_render_cost(cost_cluster):
    from pinot_tpu.broker.querylog import SlowQueryLog
    from pinot_tpu.tools.trace_dump import render_cost, render_waterfall

    cluster, spy, total = cost_cluster
    broker = cluster.broker
    old_log = broker.querylog
    broker.querylog = SlowQueryLog(threshold_ms=0.0)  # record everything
    try:
        resp = cluster.query("SELECT count(*) FROM testTable", trace=True)
        entry = broker.querylog.entries()[0]
        assert entry["numDocsScanned"] == total
        assert entry["cost"].get("bytesScanned", 0) > 0
    finally:
        broker.querylog = old_log

    j = resp.to_json()
    out = render_waterfall(j["traceInfo"]) + render_cost(j)
    assert f"docs={total}" in out
    assert "bytes=" in out
    # device or host ms: whichever path served, the split is rendered
    assert ("deviceMs=" in out) or ("hostMs=" in out)


# ------------------------------------------------- capacity rollup
def test_debug_capacity_rollup_and_dashboard(tmp_path):
    """Controller /debug/capacity aggregates server HBM ledgers +
    ingest lag and broker per-table cost rates cluster-wide; the
    dashboard page renders it."""
    import urllib.request

    from pinot_tpu.controller.controller import (
        ControllerHttpServer,
        collect_capacity,
    )
    from pinot_tpu.server.network_starter import ServerAdminHttpServer

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path), http=True)
    admin = None
    http = None
    try:
        schema = make_test_schema(with_mv=False)
        physical = cluster.add_offline_table(schema)
        rows = random_rows(schema, 600, seed=19)
        cluster.upload(physical, build_segment(schema, rows, physical, "capseg0"))
        for _ in range(2):
            resp = cluster.query("SELECT sum(metInt) FROM testTable WHERE dimInt > 5")
            assert not resp.exceptions

        # give the in-process server an admin HTTP surface and register
        # it as the instance url, the way the networked starter does
        admin = ServerAdminHttpServer(cluster.servers[0])
        admin.start()
        cluster.controller.resources.instances["server0"].url = admin.url

        cap = collect_capacity(cluster.controller)
        assert "server0" in cap["servers"]
        hbm = cap["servers"]["server0"]["hbm"]
        assert hbm["stagedBytes"] > 0
        # ledger attributes by PHYSICAL table (what is actually staged);
        # broker cost rates attribute by logical table (what was asked)
        assert physical in hbm["byTable"]
        assert cap["totals"]["stagedBytes"] == hbm["stagedBytes"]
        t = cap["tables"]["testTable"]
        assert t["docsScanned"] > 0 and t["bytesScanned"] > 0

        http = ControllerHttpServer(cluster.controller)
        http.start()
        base = f"http://127.0.0.1:{http.port}"
        with urllib.request.urlopen(base + "/debug/capacity", timeout=10) as r:
            over_http = json.loads(r.read())
        assert over_http["servers"]["server0"]["hbm"]["stagedBytes"] > 0
        with urllib.request.urlopen(base + "/dashboard/capacity", timeout=10) as r:
            page = r.read().decode()
        assert "Capacity" in page and "testTable" in page and "server0" in page
    finally:
        if http is not None:
            http.stop()
        if admin is not None:
            admin.stop()
        cluster.stop()


