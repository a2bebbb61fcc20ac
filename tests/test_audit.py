"""Correctness & freshness audit plane (ISSUE 19): the differential
comparator, shadow-audit eligibility + quarantine, the replica/CRC
sweeps, event-time freshness watermarks end to end, and the seeded
wrong-answer chaos twin."""
import json
import time

import pytest

from pinot_tpu.common.schema import (
    DataType,
    FieldSpec,
    FieldType,
    Schema,
    TimeFieldSpec,
)
from pinot_tpu.realtime.llc import make_segment_name
from pinot_tpu.realtime.stream import MemoryStreamProvider
from pinot_tpu.tools.cluster_harness import InProcessCluster
from pinot_tpu.tools.datagen import make_test_schema, random_rows
from pinot_tpu.utils.audit import (
    ACCOUNTING_FIELDS,
    SamplerBudget,
    ShadowAuditor,
    payloads_equivalent,
    strip_accounting,
)


# ------------------------------------------------------- comparator
def test_payloads_equivalent_absorbs_float32_noise():
    """The device float32 / host float64 accumulation wobble must NOT
    read as divergence: last-printed-digit noise and sqrt(n)-scaled
    relative error both sit far inside the tolerance band."""
    a = {"aggregationResults": [{"function": "sum_m", "value": "118.37801"}]}
    b = {"aggregationResults": [{"function": "sum_m", "value": "118.37800"}]}
    assert payloads_equivalent(a, b)
    # 1M-row Q1-scale sum: ~1e-4 relative tree-reduction error is honest
    assert payloads_equivalent(
        {"v": "3578694016.00000"}, {"v": "3578694400.00000"}
    )


def test_payloads_equivalent_catches_real_divergence():
    """A genuinely wrong answer (corrupted partial, dropped rows) is
    orders of magnitude outside the band and must fail."""
    good = {"aggregationResults": [{"function": "sum_m", "value": "2048.00000"}]}
    bad = {"aggregationResults": [{"function": "sum_m", "value": "2148.00000"}]}
    assert not payloads_equivalent(good, bad)
    # counts are exact: off-by-one on an integer aggregate diverges
    assert not payloads_equivalent({"numDocs": 300}, {"numDocs": 301})


def test_payloads_equivalent_structure_is_exact():
    """Only numeric LEAVES get tolerance: keys, list lengths, group
    labels, and non-numeric strings remain byte-exact."""
    assert not payloads_equivalent({"a": 1}, {"a": 1, "b": 2})
    assert not payloads_equivalent([1, 2], [1, 2, 3])
    assert not payloads_equivalent({"group": ["x"]}, {"group": ["y"]})
    assert payloads_equivalent(
        {"g": [["k1"], "5.00000"]}, {"g": [["k1"], "5.00000"]}
    )


def _ranked(*groups):
    return {"aggregationResults": [{
        "function": "sum_m", "groupByColumns": ["flag", "status"],
        "groupByResult": [{"value": v, "group": list(g)} for g, v in groups],
    }]}


@pytest.mark.parametrize("produced,expected,same", [
    # the reading of PR 29's chip run: two groups 3e-7 apart, ranked the other way round by a float32 sum
    (_ranked((("A", "F"), "1118647.75000"), (("N", "O"), "1118612.87500"), (("R", "O"), "1118606.50000"),
             (("A", "O"), "1118606.37500"), (("R", "F"), "1118500.12500")),
     _ranked((("A", "F"), "1118548.26000"), (("N", "O"), "1118513.31000"), (("A", "O"), "1118506.87000"),
             (("R", "O"), "1118506.53000"), (("R", "F"), "1118400.57000")), True),
    # a rank taken from a group outside the band: the order is wrong, whatever the values
    (_ranked((("b",), "90.00000"), (("a",), "100.00000")), _ranked((("a",), "100.00000"), (("b",), "90.00000")), False),
    # tied ranks, but one group's own value is off
    (_ranked((("b",), "100.00010"), (("a",), "100.00000")), _ranked((("a",), "100.00000"), (("b",), "50.00000")), False),
    # a label altered among ties, a group missing, a group twice
    (_ranked((("b",), "100.00010"), (("c",), "100.00000")), _ranked((("a",), "100.00000"), (("b",), "100.00010")), False),
    (_ranked((("b",), "100.00010")), _ranked((("a",), "100.00000"), (("b",), "100.00010")), False),
    (_ranked((("b",), "100.00010"), (("b",), "100.00000")), _ranked((("a",), "100.00000"), (("b",), "100.00010")), False),
    # the same order: the leaves' tolerance alone, as before
    (_ranked((("a",), "100.00100"), (("b",), "90.00000")), _ranked((("a",), "100.00000"), (("b",), "90.00000")), True),
    (_ranked((("a",), "101.00000"), (("b",), "90.00000")), _ranked((("a",), "100.00000"), (("b",), "90.00000")), False),
], ids=["tie_swapped", "rank_outside_band", "tie_value_off", "label_altered", "group_missing", "group_twice",
        "same_order_close", "same_order_off"])
def test_payloads_equivalent_group_rank_among_ties(produced, expected, same):
    """Groups whose values tie within the band may stand in either
    order (a float32 and a float64 sum rank them differently); nothing
    else about a ranked group list is forgiven, in either direction."""
    assert payloads_equivalent(produced, expected) is same
    assert payloads_equivalent(expected, produced) is same


def test_unstripped_field_difference_still_fails():
    """Negative differential guard (satellite 1): stripping accounting
    must not widen the contract — two payloads differing in any
    NON-stripped field still compare unequal after the strip."""
    a = {"totalDocs": 300, "numDocsScanned": 300, "freshnessMs": 11.0}
    b = {"totalDocs": 299, "numDocsScanned": 250, "freshnessMs": 99.0}
    sa, sb = strip_accounting(a), strip_accounting(b)
    # the accounting fields (incl. freshnessMs) are gone ...
    assert "freshnessMs" in ACCOUNTING_FIELDS
    assert "freshnessMs" not in sa and "numDocsScanned" not in sa
    # ... but the surviving totalDocs difference still fails the check
    assert not payloads_equivalent(sa, sb)


# -------------------------------------------- shadow-audit sampling
class _StubResult:
    def __init__(self, tier="device"):
        self.exceptions = []
        self._served_tier = tier


class _StubRequest:
    explain = False
    join = None


def _stub_instance():
    from pinot_tpu.utils.metrics import ServerMetrics

    class _Exec:
        @staticmethod
        def audit_quarantined_snapshot():
            return []

    class _Inst:
        name = "stub"
        metrics = ServerMetrics("stub-audit-test")
        executor = _Exec()

    return _Inst()


def test_shadow_offer_eligibility_and_budget():
    inst = _stub_instance()
    auditor = ShadowAuditor(inst, sample_n=1, budget=SamplerBudget(per_s=0.0))
    try:
        req = {"requestId": "r1", "table": "t"}
        # host-served replies ARE the oracle: never sampled
        assert not auditor.offer(req, _StubRequest(), [], _StubResult("host"))
        # eligible tier but an exhausted budget -> dropped, not queued
        assert not auditor.offer(req, _StubRequest(), [], _StubResult("device"))
        assert inst.metrics.meter("audit.dropped").count >= 1
        # sampling counter: 1-in-N means N-1 of N offers are free no-ops
        auditor.sample_n = 1000
        auditor._count = 0
        assert not auditor.offer(req, _StubRequest(), [], _StubResult("device"))
    finally:
        auditor.stop()


def test_shadow_auditor_disabled_when_sample_n_zero():
    inst = _stub_instance()
    auditor = ShadowAuditor(inst, sample_n=0)
    try:
        assert not auditor.enabled
        assert not auditor.offer({}, _StubRequest(), [], _StubResult("device"))
        snap = auditor.snapshot()
        assert snap["enabled"] is False and snap["samples"] == 0
    finally:
        auditor.stop()


def test_sampler_budget_refills():
    b = SamplerBudget(per_s=1000.0, burst=2.0)
    assert b.take() and b.take()
    assert not b.take()  # burst exhausted
    time.sleep(0.01)  # 1000/s refills ~10 tokens in 10ms
    assert b.take()


# ------------------------------------------------- chaos twin (e2e)
def test_audit_divergence_scenario_chaos_twin(tmp_path):
    """Tier-1 twin of ``--scenario audit-divergence``: a seeded device
    fault injector corrupts served aggregates under closed-loop load;
    the shadow auditor must detect within budget, quarantine the
    (shape, tier), and the cluster must serve byte-correct answers
    after — with ZERO failed queries throughout."""
    from pinot_tpu.tools.cluster_harness import run_audit_divergence_scenario

    res = run_audit_divergence_scenario(
        load_s=1.0, detect_budget_s=20.0, data_dir=str(tmp_path)
    )
    assert res["detected"], res
    assert res["quarantined"] and res["quarantined"][0]["tier"] == "device"
    assert res["failedQueries"] == 0
    assert res["postQuarantineMismatches"] == 0
    assert res["divergences"] >= 1


# --------------------------------------------------- freshness plane
def _fresh_schema(name: str) -> Schema:
    return Schema(
        name,
        dimensions=[FieldSpec("d", DataType.STRING)],
        metrics=[FieldSpec("m", DataType.INT, FieldType.METRIC)],
        time_field=TimeFieldSpec("ts", DataType.LONG, time_unit="MILLISECONDS"),
    )


def test_freshness_ms_monotone_consistent_with_watermarks(tmp_path):
    """BrokerResponse.freshnessMs must equal (reduce-time now) − the
    table's MIN partition watermark — bounded by wall clocks read
    around the query — and must shrink when fresher events land."""
    from pinot_tpu.broker.freshness import WATERMARKS, now_ms

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    schema = _fresh_schema("freshT")
    stream = MemoryStreamProvider(num_partitions=1)
    physical = cluster.add_realtime_table(schema, stream, rows_per_segment=500)
    try:
        t0 = now_ms()
        for i in range(20):
            stream.produce({"d": f"a{i % 3}", "m": i, "ts": int(t0 - 60_000 + i)})
        dm = cluster.controller.realtime_manager.consumers_of(
            make_segment_name(physical, 0, 0)
        )[0]
        dm.consume_step(max_rows=100)

        wm = WATERMARKS.table_min_ms(physical)
        assert wm == int(t0 - 60_000 + 19)  # max event-time consumed

        before = now_ms()
        resp = cluster.query("SELECT count(*) FROM freshT")
        after = now_ms()
        assert not resp.exceptions
        assert resp.freshness_ms is not None
        # consistency band: computed between the two wall-clock reads
        assert before - wm - 1e-6 <= resp.freshness_ms <= after - wm + 1e-6
        assert resp.to_json()["freshnessMs"] == round(resp.freshness_ms, 3)

        # fresher events -> watermark advances -> freshnessMs shrinks
        stream.produce({"d": "z", "m": 1, "ts": int(now_ms() - 2_000)})
        dm.consume_step(max_rows=100)
        wm2 = WATERMARKS.table_min_ms(physical)
        assert wm2 > wm
        resp2 = cluster.query("SELECT count(*) FROM freshT")
        assert resp2.freshness_ms < resp.freshness_ms

        # the watermark itself is monotone: a stale replay cannot
        # regress it (so freshnessMs can never lie fresher->staler
        # without wall time passing)
        WATERMARKS.advance(physical, 0, wm2 - 50_000)
        assert WATERMARKS.get(physical, 0) == wm2

        # offline-only replies carry NO freshness stamp
        schema_off = make_test_schema(with_mv=False)
        from pinot_tpu.segment.builder import build_segment

        off = cluster.add_offline_table(schema_off, replication=1)
        cluster.upload(
            off, build_segment(schema_off, random_rows(schema_off, 50, seed=3), off, "s0")
        )
        resp_off = cluster.query("SELECT count(*) FROM testTable")
        assert resp_off.freshness_ms is None
        assert "freshnessMs" not in resp_off.to_json()
    finally:
        cluster.stop()
        WATERMARKS.drop_table(physical)


def test_freshness_gauge_survives_rollover_and_pool_resize(tmp_path):
    """The per-(table, partition) freshness.lag gauge is a continuous
    series: segment rollover hands it to the successor consumer, and
    an ingest-pool resize must not detach it."""
    from pinot_tpu.broker.freshness import WATERMARKS, now_ms
    from pinot_tpu.realtime.pool import IngestConsumerPool

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    schema = _fresh_schema("freshRoll")
    stream = MemoryStreamProvider(num_partitions=1)
    physical = cluster.add_realtime_table(schema, stream, rows_per_segment=50)
    pool = IngestConsumerPool(workers=2, name="auditFreshPool")
    try:
        t0 = now_ms()
        for i in range(60):
            stream.produce({"d": "x", "m": i, "ts": int(t0 - 30_000 + i)})
        dm = cluster.controller.realtime_manager.consumers_of(
            make_segment_name(physical, 0, 0)
        )[0]
        dm.consume_step(max_rows=1000)
        gauge = cluster.servers[0].metrics.gauge(f"freshness.lag.{physical}.p0")
        v_before = gauge.value
        assert isinstance(v_before, (int, float)) and v_before > 0

        # rollover: seq 0 commits, seq 1 consumes — same series name,
        # successor re-registers, predecessor's detach is a no-op
        assert dm.threshold_reached
        dm.try_commit()
        dm1 = cluster.controller.realtime_manager.consumers_of(
            make_segment_name(physical, 0, 1)
        )[0]
        v_after_roll = gauge.value
        assert isinstance(v_after_roll, (int, float)) and v_after_roll > 0

        # drive the successor through the shared pool, then resize it:
        # the watermark keeps advancing and the gauge stays attached
        pool.add(dm1, key=("freshRoll", 0))
        stream.produce({"d": "y", "m": 1, "ts": int(now_ms() - 3_000)})
        pool.kick()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            w = WATERMARKS.get(physical, 0)
            if w is not None and w >= t0 - 4_000:
                break
            time.sleep(0.02)
        assert WATERMARKS.get(physical, 0) >= t0 - 4_000

        pool.resize(1)
        stream.produce({"d": "y", "m": 2, "ts": int(now_ms() - 1_000)})
        pool.kick()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            w = WATERMARKS.get(physical, 0)
            if w is not None and w >= t0 - 2_000:
                break
            time.sleep(0.02)
        assert WATERMARKS.get(physical, 0) >= t0 - 2_000
        v_final = gauge.value
        # gauge live and reporting the (small) fresh lag
        assert isinstance(v_final, (int, float)) and 0 < v_final < 60_000
    finally:
        pool.stop()
        cluster.stop()
        WATERMARKS.drop_table(physical)


def test_datatable_freshness_roundtrip_and_mixed_version():
    """The freshness stamp rides a TRAILING optional DataTable field:
    round-trips when present, tolerates None, and a payload truncated
    to the pre-audit wire shape still deserializes (older peer)."""
    from pinot_tpu.common.datatable import deserialize_result, serialize_result
    from pinot_tpu.engine.results import IntermediateResult

    res = IntermediateResult()
    res.num_docs_scanned = 7
    res.total_docs = 7
    res.freshness = {"minEventMs": 1234.5}
    back = deserialize_result(serialize_result(res))
    assert back.freshness == {"minEventMs": 1234.5}
    assert back.num_docs_scanned == 7

    res2 = IntermediateResult()
    assert deserialize_result(serialize_result(res2)).freshness is None


def test_results_merge_min_combines_freshness():
    """An answer is only as fresh as its STALEST contributing
    partition: merge takes the min watermark, and a None side never
    clobbers a stamped one."""
    from pinot_tpu.engine.results import IntermediateResult

    a, b, c = IntermediateResult(), IntermediateResult(), IntermediateResult()
    b.freshness = {"minEventMs": 5_000.0}
    c.freshness = {"minEventMs": 2_000.0}
    a.merge(b)
    assert a.freshness == {"minEventMs": 5_000.0}
    a.merge(c)
    assert a.freshness["minEventMs"] == 2_000.0
    a.merge(IntermediateResult())  # unstamped (offline) side: no-op
    assert a.freshness["minEventMs"] == 2_000.0


def test_worst_freshness_tables_ranking():
    from pinot_tpu.broker.freshness import worst_freshness_tables

    snap = {
        "tables": {
            "a_REALTIME": {"lagMs": 100.0},
            "b_REALTIME": {"lagMs": 90_000.0},
            "c_REALTIME": {"lagMs": 7_000.0},
        }
    }
    ranked = worst_freshness_tables(snap, top=2)
    assert [r["table"] for r in ranked] == ["b_REALTIME", "c_REALTIME"]


# ------------------------------------------------------ freshness SLO
def test_slo_freshness_objective_burn():
    """freshnessMs rides the SLO burn machinery as a third objective:
    breaches count only when a threshold is set, and evaluate() emits
    a freshness burn entry alongside latency/availability."""
    from pinot_tpu.utils.metrics import MetricsRegistry
    from pinot_tpu.utils.slo import SloTracker
    from pinot_tpu.utils.timeseries import HistoryRecorder

    reg = MetricsRegistry("slo-fresh-test")
    clk = [0.0]
    hist = HistoryRecorder(
        reg, interval_s=5, capacity=64, clock=lambda: clk[0], start=False
    )
    slo = SloTracker(history=hist, metrics=reg,
                     fast_window_s=10.0, slow_window_s=100.0)
    hist.register_provider(slo.series)
    slo.set_objective("t", {"latencyMs": 1e9,
                            "freshnessMs": 1000.0, "freshnessTarget": 0.9})
    # baseline sample: window deltas need a pre-window tick to diff from
    slo.observe("t", 1.0, False, freshness_ms=50.0)
    hist.tick()
    clk[0] += 10.0
    for _ in range(8):
        slo.observe("t", 1.0, False, freshness_ms=50.0)  # fresh: no breach
    for _ in range(2):
        slo.observe("t", 1.0, False, freshness_ms=5_000.0)  # stale: breach
    hist.tick()
    assert slo.series()["slo.t.freshnessBreaches"] == 2
    ev = slo.evaluate(consume_crossings=False)
    fresh = ev["tables"]["t"]["windows"]["burnRate5m"]["freshness"]
    assert fresh["bad"] == 2 and fresh["queries"] == 10
    assert fresh["burnRate"] == pytest.approx(0.2 / 0.1, rel=1e-3)

    # threshold 0 (offline fleet): freshness never breaches, and
    # evaluate() contributes NO freshness entry (budget zeroed)
    slo.set_objective("u", {"latencyMs": 1e9})
    slo.observe("u", 1.0, False, freshness_ms=1e12)
    hist.tick()
    assert slo.series()["slo.u.freshnessBreaches"] == 0
    ev2 = slo.evaluate(consume_crossings=False)
    assert ev2["tables"]["u"]["windows"]["burnRate5m"]["freshness"] is None


# --------------------------------------------------- querylog x-link
def test_querylog_freshness_and_audit_ref_annotation():
    from pinot_tpu.broker.querylog import SlowQueryLog

    log = SlowQueryLog(threshold_ms=0.0)
    log.observe({"requestId": "rq-1", "table": "t", "timeUsedMs": 5.0,
                 "freshnessMs": 123.4})
    assert log.annotate("rq-1", auditRef="audit-rq-1")
    assert not log.annotate("rq-missing", auditRef="x")
    entry = [e for e in log.entries() if e["requestId"] == "rq-1"][0]
    assert entry["freshnessMs"] == 123.4
    assert entry["auditRef"] == "audit-rq-1"


# --------------------------------------------------- CRC sweep plane
def test_crc_audit_manager_detects_replica_divergence(tmp_path):
    """The controller sweep compares every replica's claimed segment
    CRC against the other replicas AND the property-store metadata: a
    clean cluster sweeps zero mismatches; one corrupted replica claim
    is flagged with the full evidence row."""
    from pinot_tpu.controller.managers import CrcAuditManager
    from pinot_tpu.segment.builder import build_segment

    cluster = InProcessCluster(num_servers=2, data_dir=str(tmp_path))
    schema = make_test_schema(with_mv=False)
    physical = cluster.add_offline_table(schema, replication=2)
    rows = random_rows(schema, 120, seed=7)
    cluster.upload(physical, build_segment(schema, rows[:60], physical, "s1"))
    cluster.upload(physical, build_segment(schema, rows[60:], physical, "s2"))
    try:
        by_name = {s.name: s for s in cluster.servers}
        # in-process servers register no admin URL; give the sweep one
        for name, inst in cluster.controller.resources.instances.items():
            if inst.role == "server":
                inst.url = f"inproc://{name}"

        claims = {
            name: dict(srv.segment_crcs()["segments"])
            for name, srv in by_name.items()
        }
        mgr = CrcAuditManager(
            cluster.controller.resources,
            crc_fn=lambda name, url: claims[name],
        )
        mgr.run_once()
        snap = mgr.snapshot()
        assert snap["mismatches"] == [] and snap["segmentsChecked"] == 2

        # corrupt ONE replica's claim for s1: flagged with evidence
        victim = next(
            n for n, c in claims.items() if c.get(physical, {}).get("s1")
        )
        claims[victim] = {physical: dict(claims[victim][physical], s1=0xBAD)}
        mgr.run_once()
        snap = mgr.snapshot()
        assert len(snap["mismatches"]) == 1
        row = snap["mismatches"][0]
        assert row["segment"] == "s1"
        assert row["replicaCrcs"][victim] == 0xBAD
        assert row["expectedCrc"] is not None
        assert mgr.metrics.gauge("audit.crcMismatches").value == 1
        mgr.stop()
    finally:
        cluster.stop()


# --------------------------------------------------- debug surfaces
def test_server_and_controller_audit_debug_surfaces(tmp_path):
    """/debug/audit answers on every role, pre-registered with zeros
    before any sample — the doctor's rollup sources."""
    from pinot_tpu.segment.builder import build_segment

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    schema = make_test_schema(with_mv=False)
    physical = cluster.add_offline_table(schema, replication=1)
    cluster.upload(
        physical,
        build_segment(schema, random_rows(schema, 40, seed=5), physical, "s0"),
    )
    try:
        s = cluster.servers[0]
        snap = s.auditor.snapshot()
        assert snap["samples"] == 0 and snap["divergences"] == 0
        assert snap["quarantined"] == []
        ctrl_snap = cluster.controller.crc_audit.snapshot()
        assert "mismatches" in ctrl_snap and "intervalS" in ctrl_snap
        rep = cluster.broker.replica_audit.snapshot()
        assert rep["divergences"] == 0
    finally:
        cluster.stop()
