"""Elastic fleet breadth (ISSUE 15): partition-parallel ingest,
proactive skew-aware rebalancing, zero-downtime movement at 100+ tables.

Chaos acceptance (``-m chaos``, tier-1): the full ``elastic-fleet``
harness scenario — 100+ tables under mixed ingest+query closed-loop
load sustain a forced skew-triggered live rebalance AND a mid-rebalance
controller restart with zero failed queries, zero lost/duplicate rows,
and exactly one committed copy per sequence.

Plus unit coverage: the IngestConsumerPool scheduler (bounded workers,
done-removal, error parking, kick, live resize), the rebalance
planner's hysteresis / make-before-break ordering / ERROR-destination
abort / cost-rate weighting / disable switch, per-partition lag-gauge
continuity across segment rollover and pool resize (satellite 1),
drain racing a CONSUMING-segment handoff (satellite 3), and the
version-keyed cluster-state snapshot cache (control-plane scale).
"""
import threading
import time

import pytest

from pinot_tpu.common.tableconfig import TableConfig
from pinot_tpu.controller.network import ParticipantGateway
from pinot_tpu.controller.resource_manager import (
    ClusterResourceManager,
    InstanceState,
    Participant,
)
from pinot_tpu.controller.stabilizer import SelfStabilizer
from pinot_tpu.realtime.llc import make_segment_name
from pinot_tpu.realtime.pool import IngestConsumerPool
from pinot_tpu.realtime.stream import MemoryStreamProvider
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.immutable import SegmentMetadata
from pinot_tpu.tools.cluster_harness import (
    InProcessCluster,
    run_elastic_fleet_scenario,
)
from pinot_tpu.tools.datagen import make_test_schema, random_rows
from pinot_tpu.utils.metrics import ControllerMetrics


# ------------------------------------------------------------------
# chaos acceptance — the same scenario code the CLI runs
# ------------------------------------------------------------------
@pytest.mark.chaos
def test_elastic_fleet_acceptance(tmp_path):
    out = run_elastic_fleet_scenario(data_dir=str(tmp_path))
    assert out["failedQueries"] == 0, out.get("failures")
    assert out["tables"] >= 100
    assert out["okQueries"] > 0
    assert out["coverageNeverLost"]
    # the restart genuinely interrupted an in-flight rebalance
    assert out["movesStartedBeforeRestart"] > 0
    assert out["pendingMovesAtRestart"] > 0 or out["surplusReplicasAtRestart"] > 0
    assert out["movesCompletedAfterRestart"] > 0
    # zero lost/duplicate rows, exactly one committed copy per sequence
    assert out["rtRowsServed"] == [out["rtRowsExpected"]] * len(out["rtRowsServed"])
    assert out["oneCommittedCopyPerSequence"]
    assert out["finalImbalanceRatio"] < out["skewRatioThreshold"]


def test_elastic_fleet_smoke(tmp_path):
    """Scaled-down tier-1 smoke of the same scenario path (16 tables)."""
    out = run_elastic_fleet_scenario(num_tables=16, data_dir=str(tmp_path))
    assert out["failedQueries"] == 0, out.get("failures")
    assert out["oneCommittedCopyPerSequence"]
    assert out["coverageNeverLost"]


# ------------------------------------------------------------------
# IngestConsumerPool scheduler
# ------------------------------------------------------------------
class _ScriptedConsumer:
    """step() pops scripted return values; records who ran it."""

    def __init__(self, script):
        self.script = list(script)
        self.calls = 0
        self.threads = set()

    def step(self):
        self.calls += 1
        self.threads.add(threading.current_thread().name)
        if not self.script:
            return None
        out = self.script.pop(0)
        if out == "raise":
            raise RuntimeError("scripted failure")
        return out


def _wait(cond, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


def test_pool_runs_consumers_and_removes_done():
    pool = IngestConsumerPool(workers=2, name="t1")
    a = _ScriptedConsumer([0.0, 0.0, None])
    b = _ScriptedConsumer([0.0, None])
    pool.add(a, key="a")
    pool.add(b, key="b")
    assert _wait(lambda: not pool.snapshot()["consumers"])
    assert a.calls == 3 and b.calls == 2
    assert pool.snapshot()["steps"] == 5
    pool.stop()


def test_pool_bounded_workers():
    """More consumers than workers: everything still runs, on at most
    ``workers`` distinct threads."""
    pool = IngestConsumerPool(workers=2, name="t2")
    consumers = [_ScriptedConsumer([0.0, None]) for _ in range(8)]
    for i, c in enumerate(consumers):
        pool.add(c, key=i)
    assert _wait(lambda: not pool.snapshot()["consumers"])
    threads = set().union(*(c.threads for c in consumers))
    assert len(threads) <= 2
    assert all(c.calls == 2 for c in consumers)
    pool.stop()


def test_pool_error_parks_consumer_not_worker():
    """A raising consumer is parked with a backoff; the OTHER consumer
    keeps stepping on the shared workers."""
    pool = IngestConsumerPool(workers=1, name="t3")
    bad = _ScriptedConsumer(["raise", None])
    good = _ScriptedConsumer([0.0] * 5 + [None])
    pool.add(bad, key="bad")
    pool.add(good, key="good")
    assert _wait(lambda: good.calls == 6)
    assert pool.snapshot()["errors"] == 1
    pool.kick()  # pull `bad` out of its error park immediately
    assert _wait(lambda: not pool.snapshot()["consumers"])
    pool.stop()


def test_pool_parked_consumer_costs_nothing_until_eligible():
    pool = IngestConsumerPool(workers=1, name="t4")
    slow = _ScriptedConsumer([30.0, None])  # parks itself for 30s
    pool.add(slow, key="slow")
    assert _wait(lambda: slow.calls == 1)
    time.sleep(0.15)
    assert slow.calls == 1  # still parked
    pool.kick()
    assert _wait(lambda: slow.calls == 2)
    pool.stop()


def test_pool_live_resize():
    pool = IngestConsumerPool(workers=1, name="t5")
    c = _ScriptedConsumer([0.05] * 40 + [None])
    pool.add(c, key="c")
    assert _wait(lambda: c.calls >= 2)
    pool.resize(3)
    assert pool.snapshot()["workers"] == 3
    pool.resize(1)
    assert pool.snapshot()["workers"] == 1
    assert _wait(lambda: c.calls >= 5)  # still being driven after shrink
    pool.stop()
    # leak guard: stopped pool's workers exit (asserted by conftest too)
    from pinot_tpu.realtime.pool import leaked_pool_threads

    assert leaked_pool_threads(grace_s=2.0) == []


# ------------------------------------------------------------------
# rebalance planner units (make-before-break over raw resources)
# ------------------------------------------------------------------
def _planner_rig(cold_participant_result=True):
    """Two servers, two 100-doc segments pinned on srvA: ratio 2.0
    (a single-segment skew is unmovable by design — the half-gap rule
    refuses moves that would only invert the imbalance).
    ``cold_participant_result``: what srvB's transition executor
    returns (True=ONLINE now, None=pending, False=ERROR)."""
    res = ClusterResourceManager()
    log = []

    def exec_a(table, seg, target, info):
        log.append(("srvA", seg, target))
        return True

    def exec_b(table, seg, target, info):
        log.append(("srvB", seg, target))
        return cold_participant_result

    res.register_instance(InstanceState("srvA", role="server"), Participant("srvA", exec_a))
    res.register_instance(InstanceState("srvB", role="server"), Participant("srvB", exec_b))
    res.add_table(TableConfig(table_name="t", table_type="OFFLINE", replication=1))
    for name in ("s0", "s1"):
        meta = SegmentMetadata(segment_name=name, table_name="t_OFFLINE", num_docs=100)
        res.add_segment("t_OFFLINE", meta, {"dir": "/nope"}, servers=["srvA"])
    st = SelfStabilizer(res, grace_s=0.0)
    st.rebalance_skew_ratio = 1.5
    st.rebalance_hysteresis = 2
    st.rebalance_max_moves = 2
    return res, st, log


def _moved_segment(res):
    """The (single) segment currently holding a surplus replica."""
    ideal = res.get_ideal_state("t_OFFLINE")
    moved = [s for s, r in ideal.items() if len(r) > 1]
    assert len(moved) == 1, ideal
    return moved[0]


def test_rebalance_hysteresis_defers_then_moves():
    res, st, log = _planner_rig()
    st.run_once()  # evaluation 1: skewed, deferred
    assert st.metrics.meter("rebalance.skewDeferrals").count == 1
    assert st.metrics.meter("rebalance.movesStarted").count == 0
    assert all(
        r == {"srvA": "ONLINE"}
        for r in res.get_ideal_state("t_OFFLINE").values()
    )
    st.run_once()  # evaluation 2: hysteresis satisfied -> phase 1
    assert st.metrics.meter("rebalance.movesStarted").count == 1
    # make-before-break: BOTH replicas in the ideal state now
    moved = _moved_segment(res)
    assert set(res.get_ideal_state("t_OFFLINE")[moved]) == {"srvA", "srvB"}
    assert ("srvB", moved, "ONLINE") in log
    st.run_once()  # phase 2: view shows srvB ONLINE -> src trimmed
    assert st.metrics.meter("rebalance.movesCompleted").count == 1
    ideal = res.get_ideal_state("t_OFFLINE")
    assert set(ideal[moved]) == {"srvB"}
    # balanced now: one segment per server, no further moves
    st.run_once()
    assert st.metrics.meter("rebalance.movesStarted").count == 1
    # the event ring distinguishes rebalance moves from heal moves
    classes = {e["event"]: e["class"] for e in st.events()}
    assert classes["rebalanceMoveStarted"] == "rebalance"
    assert classes["rebalanceMoveCompleted"] == "rebalance"


def test_rebalance_never_breaks_coverage_while_destination_pending():
    """With the destination transition PENDING (remote participant),
    the source replica must survive every round until the external
    view proves the new copy serves."""
    res, st, log = _planner_rig(cold_participant_result=None)
    st.run_once()
    st.run_once()  # phase 1: srvB added, view entry OFFLINE (pending)
    moved = _moved_segment(res)
    assert set(res.get_ideal_state("t_OFFLINE")[moved]) == {"srvA", "srvB"}
    for _ in range(3):
        st.run_once()  # trim must WAIT: srvB never reported ONLINE
        assert set(res.get_ideal_state("t_OFFLINE")[moved]) == {"srvA", "srvB"}
    assert st.metrics.meter("rebalance.movesCompleted").count == 0
    # the current-state report lands (the ack): NOW the trim may run
    res.report_state("srvB", "t_OFFLINE", moved, "ONLINE")
    st.run_once()
    assert set(res.get_ideal_state("t_OFFLINE")[moved]) == {"srvB"}
    assert st.metrics.meter("rebalance.movesCompleted").count == 1


def test_rebalance_error_destination_aborts_move():
    """A destination that fails its load (ERROR in the view) is dropped
    instead of the source — the move aborts, coverage holds."""
    res, st, log = _planner_rig(cold_participant_result=False)
    st.run_once()
    st.run_once()  # phase 1: add fails on srvB -> view ERROR
    moved = _moved_segment(res)
    assert set(res.get_ideal_state("t_OFFLINE")[moved]) == {"srvA", "srvB"}
    st.run_once()  # abort: drop the ERROR destination
    assert set(res.get_ideal_state("t_OFFLINE")[moved]) == {"srvA"}
    assert st.metrics.meter("rebalance.movesAborted").count == 1
    assert st.metrics.meter("rebalance.movesCompleted").count == 0


def test_rebalance_disabled_switch():
    res, st, log = _planner_rig()
    st.rebalance_enabled = False
    for _ in range(4):
        st.run_once()
    assert st.metrics.meter("rebalance.evaluations").count == 0
    assert all(
        r == {"srvA": "ONLINE"}
        for r in res.get_ideal_state("t_OFFLINE").values()
    )
    # the kill switch freezes phase 2 too: an existing surplus (e.g.
    # an in-flight move interrupted by the operator flipping the
    # switch) must NOT keep being trimmed
    res.add_segment_replica("t_OFFLINE", "s0", "srvB")
    for _ in range(2):
        st.run_once()
    assert set(res.get_ideal_state("t_OFFLINE")["s0"]) == {"srvA", "srvB"}
    assert st.metrics.meter("rebalance.movesCompleted").count == 0
    # re-enabling completes the move from derived state
    st.rebalance_enabled = True
    st.run_once()
    assert len(res.get_ideal_state("t_OFFLINE")["s0"]) == 1
    assert st.metrics.meter("rebalance.movesCompleted").count == 1


def test_rebalance_cost_rate_weights_hot_table_first(tmp_path):
    """Two equal-doc tables concentrated on server0; the cost-rate
    provider names one as the hot query tenant — the planner's first
    moves spread THAT table's segments."""
    cluster = InProcessCluster(num_servers=2, data_dir=str(tmp_path))
    res = cluster.controller.resources
    st = cluster.controller.stabilizer
    st.grace_s = 0.0
    st.rebalance_skew_ratio = 1.2
    st.rebalance_hysteresis = 1
    st.rebalance_max_moves = 1
    st.cost_rate_fn = lambda: {"hotq": 10.0, "coldq": 0.0}
    st.busy_fn = None
    schema_h = make_test_schema(with_mv=False)
    schema_h.schema_name = "hotq"
    schema_c = make_test_schema(with_mv=False)
    schema_c.schema_name = "coldq"
    rows = random_rows(schema_h, 50, seed=3)
    import os as _os

    for schema, prefix in ((schema_h, "h"), (schema_c, "c")):
        physical = cluster.add_offline_table(schema, replication=1)
        for i in range(2):
            seg = build_segment(schema, rows, physical, f"{prefix}{i}")
            path = cluster.controller.store.save(physical, seg)
            res.add_segment(
                physical, seg.metadata,
                {"dir": path, "downloadUri": "file://" + _os.path.abspath(path)},
                servers=["server0"],
            )
    st.run_once()
    started = [e for e in st.events() if e["event"] == "rebalanceMoveStarted"]
    assert started and started[0]["table"] == "hotq_OFFLINE"
    cluster.stop()


# ------------------------------------------------------------------
# satellite 1: per-partition lag gauges across rollover / pool resize
# ------------------------------------------------------------------
def test_lag_gauges_continuous_across_rollover_and_resize(tmp_path):
    """Multi-consumer case: two partitions on one server, pool-driven.
    Rolling partition 0 to its next sequence re-registers the SAME
    ``ingest.lag.<table>.p0`` series bound to the successor's probe;
    the predecessor's detach (equality-guarded) must not clear it, and
    partition 1's series must be untouched.  A pool resize changes
    worker count only — every gauge binding survives."""
    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    rm = cluster.controller.realtime_manager
    pool = IngestConsumerPool(workers=2, name="lagtest")
    rm.ingest_pool = pool
    try:
        schema = make_test_schema(with_mv=False)
        schema.schema_name = "lagT"
        stream = MemoryStreamProvider(num_partitions=2)
        physical = cluster.add_realtime_table(
            schema, stream, rows_per_segment=50
        )
        rows = random_rows(schema, 70, seed=5)
        for row in rows:
            stream.produce(row, partition=0)  # 70 rows: one roll + 20
        for row in rows[:30]:
            stream.produce(row, partition=1)  # 30 rows: no roll

        server = cluster.servers[0]
        seg01 = make_segment_name(physical, 0, 1)

        def rolled():
            dms = rm.consumers_of(seg01)
            return bool(dms) and dms[0].offset == 70

        assert _wait(rolled, timeout_s=15.0), "partition 0 did not roll"
        # mid-test resize: gauges must survive a live worker change
        pool.resize(1)
        pool.resize(3)

        dms1 = rm.consumers_of(make_segment_name(physical, 1, 0))
        assert _wait(lambda: dms1[0].offset == 30, timeout_s=10.0)

        g0 = server.metrics.gauge(f"ingest.lag.{physical}.p0")
        g1 = server.metrics.gauge(f"ingest.lag.{physical}.p1")
        successor = rm.consumers_of(seg01)[0]
        # the p0 series is bound to the SUCCESSOR's probe (not cleared,
        # not the predecessor's frozen offset)
        assert g0._fn is successor._lag_probe
        assert g1._fn is dms1[0]._lag_probe
        assert g0.value == 0 and g1.value == 0
        # a late duplicate detach from the (already stopped) first
        # consumer must be a no-op thanks to the equality guard
        stopped = [
            dm
            for dm in [successor]
            if False
        ]
        seg00 = make_segment_name(physical, 0, 0)
        # the seq-0 consumer was stopped + deregistered at commit; its
        # stop() is idempotent and must not clobber the live series
        assert rm.consumers_of(seg00) == []
        g0_before = g0._fn
        # simulate the stale detach directly: clear_fn with a foreign
        # probe is the exact call path RemoteConsumer/DM stop() takes
        g0.clear_fn(lambda: 999)
        assert g0._fn is g0_before

        resp = cluster.query("SELECT count(*) FROM lagT")
        assert resp.num_docs_scanned == 100 and not resp.exceptions
    finally:
        pool.stop()
        cluster.stop()


# ------------------------------------------------------------------
# satellite 3: drain racing a CONSUMING-segment handoff
# ------------------------------------------------------------------
def test_drain_races_consuming_handoff_zero_loss(tmp_path):
    """Draining the server holding the ONLY consumer for a partition
    must re-create the consumer on a live server at the last COMMITTED
    offset: uncommitted rows re-consume from the stream (zero lost,
    zero duplicate), and the drain completes."""
    cluster = InProcessCluster(num_servers=2, data_dir=str(tmp_path))
    rm = cluster.controller.realtime_manager
    res = cluster.controller.resources
    try:
        schema = make_test_schema(with_mv=False)
        schema.schema_name = "drainRace"
        stream = MemoryStreamProvider(num_partitions=1)
        physical = cluster.add_realtime_table(
            schema, stream, rows_per_segment=50
        )
        for row in random_rows(schema, 70, seed=9):
            stream.produce(row)

        seg0 = make_segment_name(physical, 0, 0)
        dm = rm.consumers_of(seg0)[0]
        dm.consume_step(max_rows=1000)
        assert dm.try_commit() == "KEEP"  # committed at offset 50

        seg1 = make_segment_name(physical, 0, 1)
        holder = next(iter(res.get_ideal_state(physical)[seg1]))
        dm1 = next(c for c in rm.consumers_of(seg1) if c.server.name == holder)
        dm1.consume_step(max_rows=20)  # 20 UNCOMMITTED rows (50..69)

        # the race: drain lands while the consumer holds uncommitted
        # rows — no grace for operator intent, handoff this round
        cluster.controller.drain_instance(holder)
        st = cluster.controller.stabilizer
        st.grace_s = 0.0
        st.run_once()
        st.run_once()

        ideal = res.get_ideal_state(physical)
        assert seg1 in ideal
        new_holder = next(iter(ideal[seg1]))
        assert new_holder != holder
        assert ideal[seg1][new_holder] == "CONSUMING"
        new_dm = rm.consumers_of(seg1)
        assert len(new_dm) == 1 and new_dm[0].server.name == new_holder
        assert new_dm[0].offset == 50  # committed offset, NOT the lost 70

        # drain completes: nothing (committed or consuming) left behind
        st.run_once()
        status = cluster.controller.drain_status(holder)
        assert status["drained"], status

        new_dm[0].consume_step(max_rows=100)  # re-consume the 20 rows
        resp = cluster.query("SELECT count(*) FROM drainRace")
        assert resp.num_docs_scanned == 70 and not resp.exceptions
        assert resp.partial_response is False
    finally:
        cluster.stop()


# ------------------------------------------------------------------
# control-plane scale: version-keyed cluster-state snapshot cache
# ------------------------------------------------------------------
def test_clusterstate_snapshot_cached_per_version():
    res = ClusterResourceManager()
    res.register_instance(
        InstanceState("srv0", role="server", addr=("127.0.0.1", 9000))
    )
    res.add_table(TableConfig(table_name="t", table_type="OFFLINE", replication=1))
    metrics = ControllerMetrics("controller")
    gw = ParticipantGateway(res, metrics=metrics)

    first = gw.cluster_state()
    second = gw.cluster_state()
    assert second is first  # served from the cache, no rebuild
    assert metrics.meter("clusterStateCacheHits").count == 1
    assert metrics.meter("clusterStatePolls").count == 2

    res.bump_version()  # any change invalidates by version key
    third = gw.cluster_state()
    assert third is not first
    assert third["version"] > first["version"]
    assert metrics.meter("clusterStateCacheHits").count == 1
    # and the new snapshot is cached in turn
    assert gw.cluster_state() is third
    assert metrics.meter("clusterStateCacheHits").count == 2
