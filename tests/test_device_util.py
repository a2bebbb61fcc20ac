"""Device utilization & profiling plane (PR 10): per-plan roofline
accounting, lane occupancy, transfer counters, and the on-demand
profiler bracket.

Tier-1 guards: the lane launch path performs ZERO occupancy-related
allocations while no sampler runs (the PR 4 zero-alloc trace-guard
analog), the static XLA cost analysis degrades to None — never an
exception — on backends that report nothing, /debug/plans' roofline is
computed from the SAME wall time the phase timers report, occupancy
reads 0 on an idle lane, the profiler endpoint honors ref-count +
auto-stop semantics, and the controller /debug/utilization rollup
equals the per-server snapshots it fetched."""
import itertools
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pinot_tpu.segment.builder import build_segment
from pinot_tpu.tools.cluster_harness import InProcessCluster, single_server_broker
from pinot_tpu.tools.datagen import make_test_schema, random_rows

# unique segment names per fixture instantiation: the HBM ledger and
# staging cache are process-global and key by segment name
_SEQ = itertools.count()


def _mk_broker(pipeline=True, rows_n=1200, table="utilTable"):
    n = next(_SEQ)
    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, rows_n, seed=23)
    half = rows_n // 2
    segs = [
        build_segment(schema, rows[:half], table, f"du{n}a"),
        build_segment(schema, rows[half:], table, f"du{n}b"),
    ]
    return single_server_broker(table, segs, pipeline=pipeline)


@pytest.fixture()
def util_broker():
    broker = _mk_broker()
    yield broker
    broker.local_servers[0].shutdown()


# ------------------------------------------------------ transfer stats
def test_transfer_stats_accumulate_and_ignore_nonpositive():
    from pinot_tpu.engine.device import TransferStats

    ts = TransferStats()
    ts.record_h2d(100)
    ts.record_h2d(0)
    ts.record_h2d(-5)
    ts.record_d2h(40)
    snap = ts.snapshot()
    # process identity rides every snapshot so fleet rollups can dedupe
    # co-resident servers' shared counters
    assert isinstance(snap.pop("processToken"), str)
    assert snap == {
        "h2dBytes": 100,
        "h2dTransfers": 1,
        "d2hBytes": 40,
        "d2hTransfers": 1,
    }


def test_device_query_counts_d2h_transfer_bytes(util_broker):
    from pinot_tpu.engine.device import TRANSFERS

    before = TRANSFERS.snapshot()
    resp = util_broker.handle_pql("SELECT sum(metInt) FROM utilTable")
    assert not resp.exceptions
    after = TRANSFERS.snapshot()
    # the packed result fetch is a real D2H transfer
    assert after["d2hBytes"] > before["d2hBytes"]
    assert after["d2hTransfers"] > before["d2hTransfers"]


# --------------------------------------------------- static cost analysis
def test_normalize_cost_analysis_none_and_partial():
    """The CPU-backend contract: None / empty / partial / non-dict
    analysis outputs all degrade gracefully, never raise."""
    from pinot_tpu.engine.packing import _normalize_cost_analysis as norm

    assert norm(None) is None
    assert norm({}) is None
    assert norm([]) is None
    assert norm("nope") is None
    assert norm({"utilization": 0.5}) is None  # no usable keys
    # partial dict: flops without bytes (and vice versa) both survive
    assert norm({"flops": 10.0}) == {"flops": 10.0}
    assert norm({"bytes accessed": 64}) == {"bytesAccessed": 64.0}
    assert norm({"flops": 3, "bytes accessed": 9}) == {
        "flops": 3.0,
        "bytesAccessed": 9.0,
    }
    # negative / junk values are dropped, not propagated
    assert norm({"flops": -1, "bytes accessed": "junk"}) is None


def test_kernel_cost_analysis_graceful_fallbacks(monkeypatch):
    from pinot_tpu.engine.packing import kernel_cost_analysis

    # no .lower on the kernel: nothing to analyze
    assert kernel_cost_analysis(lambda x: x, (1,)) is None

    # a lower() that raises degrades to None, never an exception
    class _Boom:
        def lower(self, *a):
            raise RuntimeError("no AOT path")

    assert kernel_cost_analysis(_Boom(), (1,)) is None

    # explicit opt-out
    monkeypatch.setenv("PINOT_TPU_COST_ANALYSIS", "off")
    import jax

    k = jax.jit(lambda x: x * 2.0)
    assert kernel_cost_analysis(k, (np.ones(8),)) is None
    monkeypatch.delenv("PINOT_TPU_COST_ANALYSIS")

    # the real CPU path: either a usable dict or the explicit None
    out = kernel_cost_analysis(k, (np.ones(8),))
    if out is not None:
        assert out["source"] in ("lowered", "compiled")
        assert set(out) <= {"flops", "bytesAccessed", "peakMemoryBytes", "source"}


def test_explain_compile_block_carries_cost_analysis(util_broker):
    """Acceptance: EXPLAIN's compile block carries static flops/bytes
    once the async analysis lands, or the explicit 'unavailable' —
    never a silent absence."""
    broker = util_broker
    server = broker.local_servers[0]
    pql = "SELECT sum(metInt) FROM utilTable WHERE dimInt > 40"

    cold = broker.handle_pql("EXPLAIN " + pql)
    dev = cold.explain["servers"][0]["device"]
    assert dev["compile"]["state"] == "cold"
    assert dev["compile"]["costAnalysis"] == "unavailable"

    assert not broker.handle_pql(pql).exceptions
    digest = dev["planDigest"]
    deadline = time.time() + 15
    while time.time() < deadline:
        ci = server.lane.compile_info(digest)
        assert ci is not None
        if "costAnalysis" in ci:
            break
        time.sleep(0.05)
    warm = broker.handle_pql("EXPLAIN " + pql)
    ca = warm.explain["servers"][0]["device"]["compile"]["costAnalysis"]
    # the tri-state contract: a dict with the static estimates, or the
    # explicit string states — "pending" only while the helper runs
    if isinstance(ca, dict):
        assert ("flops" in ca) or ("bytesAccessed" in ca)
    else:
        assert ca in ("unavailable", "pending")


# ----------------------------------------------------------- occupancy
def test_occupancy_idle_reads_zero_then_busy_positive(util_broker):
    broker = util_broker
    server = broker.local_servers[0]
    # idle lane, fresh gauge window: both gauges read 0
    gauges = server.metrics.snapshot()["gauges"]
    assert gauges["device.util.busyFraction"] == 0.0
    assert gauges["device.util.avgQueueDepth"] == 0.0

    for _ in range(3):
        assert not broker.handle_pql(
            "SELECT sum(metInt) FROM utilTable WHERE dimInt > 10"
        ).exceptions
    # a fresh reader's first window spans lane construction -> now and
    # must see the launches that just happened
    occ = server.lane.occupancy_read("test-busy")
    assert occ["busyFraction"] > 0.0
    assert 0.0 <= occ["busyFraction"] <= 1.0
    assert occ["depth"] == 0 and occ["inflight"] == 0
    # same reader, idle interval: the next window reads 0 again
    time.sleep(0.05)
    assert server.lane.occupancy_read("test-busy")["busyFraction"] == 0.0


def test_occupancy_zero_allocations_without_sampler(util_broker):
    """Zero-overhead contract (the PR 4 SPAN_ALLOCATIONS analog): with
    no sampler running, serving queries performs no occupancy-related
    allocations on the launch path."""
    import pinot_tpu.engine.dispatch as dispatch_mod

    broker = util_broker
    broker.handle_pql("SELECT count(*) FROM utilTable")  # warm
    before = dispatch_mod.OCCUPANCY_ALLOCATIONS
    for _ in range(5):
        assert not broker.handle_pql("SELECT count(*) FROM utilTable").exceptions
    assert dispatch_mod.OCCUPANCY_ALLOCATIONS == before, (
        "occupancy sampling allocated during serving with no sampler running"
    )


def test_serial_server_has_no_lane_occupancy():
    broker = _mk_broker(pipeline=False, rows_n=600)
    server = broker.local_servers[0]
    try:
        assert server.lane is None and server.occupancy_sampler is None
        gauges = server.metrics.snapshot()["gauges"]
        assert gauges["device.util.busyFraction"] == 0
        dev = server.device_utilization()
        assert dev["occupancy"] is None and "sampler" not in dev
    finally:
        server.shutdown()


def test_occupancy_sampler_lifecycle(util_broker):
    """start/stop idempotency + ring accumulation; the conftest
    thread-leak guard proves the sampler thread dies with the lane."""
    from pinot_tpu.engine.dispatch import OccupancySampler

    server = util_broker.local_servers[0]
    sampler = OccupancySampler(server.lane, interval_s=0.03)
    assert not sampler.running
    sampler.stop()  # stop before start: no-op
    sampler.start()
    sampler.start()  # idempotent join
    assert sampler.running
    deadline = time.time() + 5
    while sampler.samples_taken < 3 and time.time() < deadline:
        time.sleep(0.02)
    sampler.stop()
    assert not sampler.running
    taken = sampler.samples_taken
    assert taken >= 3
    snap = sampler.snapshot()
    assert snap["samplesTaken"] == taken and not snap["running"]
    for s in snap["samples"]:
        assert {"ts", "busyFraction", "avgQueueDepth", "depth"} == set(s)
        assert s["busyFraction"] == 0.0  # idle lane throughout
    time.sleep(0.1)
    assert sampler.samples_taken == taken  # really stopped
    # restart works after a stop
    sampler.start()
    assert sampler.running
    sampler.stop()


def test_occupancy_sampler_refuses_closed_lane():
    from pinot_tpu.engine.dispatch import OccupancySampler

    broker = _mk_broker(rows_n=400)
    server = broker.local_servers[0]
    sampler = OccupancySampler(server.lane, interval_s=0.03)
    server.shutdown()
    sampler.start()  # closed lane: must not spin up a thread
    assert not sampler.running


# ------------------------------------------------------------ profiler
class _FakeTrace:
    def __init__(self, fail_start=False):
        self.starts = []
        self.stops = 0
        self.fail_start = fail_start

    def start(self, d):
        if self.fail_start:
            raise RuntimeError("backend says no")
        self.starts.append(d)

    def stop(self):
        self.stops += 1

    @property
    def api(self):
        return (self.start, self.stop)


def test_profiler_refcount_shares_one_capture(tmp_path):
    from pinot_tpu.server.profiler import DeviceProfiler

    fake = _FakeTrace()
    prof = DeviceProfiler(base_dir=str(tmp_path), trace_api=fake.api)
    s1 = prof.start()
    s2 = prof.start()  # joins: jax allows ONE active trace per process
    assert len(fake.starts) == 1
    assert s1["active"] and s2["refCount"] == 2
    assert s2["dir"] == s1["dir"]
    mid = prof.stop()
    assert mid["active"] and mid["refCount"] == 1 and fake.stops == 0
    done = prof.stop()
    assert not done["active"] and fake.stops == 1
    # idempotent stop on an inactive profiler (retry after timeout)
    again = prof.stop()
    assert not again["active"] and again["refCount"] == 0 and fake.stops == 1
    # a fresh capture starts cleanly afterwards
    prof.start()
    assert len(fake.starts) == 2
    prof.shutdown()
    assert fake.stops == 2


def test_profiler_auto_stop_force_stops_despite_refcount(tmp_path):
    from pinot_tpu.server.profiler import DeviceProfiler

    fake = _FakeTrace()
    prof = DeviceProfiler(base_dir=str(tmp_path), trace_api=fake.api)
    prof.start(timeout_s=0.15)
    prof.start(timeout_s=0.15)  # refcount 2: auto-stop must still fire
    deadline = time.time() + 5
    while prof.snapshot()["active"] and time.time() < deadline:
        time.sleep(0.02)
    snap = prof.snapshot()
    assert not snap["active"] and snap["refCount"] == 0
    assert snap["autoStops"] == 1 and fake.stops == 1


def test_profiler_bounded_captures_and_unavailable(tmp_path):
    from pinot_tpu.server.profiler import (
        DeviceProfiler,
        ProfilerUnavailableError,
    )

    fake = _FakeTrace()
    prof = DeviceProfiler(
        base_dir=str(tmp_path), trace_api=fake.api, max_captures=2
    )
    for _ in range(4):
        prof.start()
        prof.stop()
    assert len(prof.snapshot()["captures"]) <= 2  # oldest pruned

    broken = DeviceProfiler(
        base_dir=str(tmp_path / "b"), trace_api=_FakeTrace(fail_start=True).api
    )
    with pytest.raises(ProfilerUnavailableError):
        broken.start()
    # the failed start left no active capture behind
    assert not broken.snapshot()["active"]


def test_profiler_endpoints_and_sampler_bracket(util_broker, tmp_path):
    """POST /debug/profile/start|stop semantics over the admin surface:
    200 start/stop with the occupancy sampler bracketed to the capture,
    and the typed 404 when the backend has no profiler."""
    from pinot_tpu.server.network_starter import ServerAdminHttpServer
    from pinot_tpu.server.profiler import DeviceProfiler

    server = util_broker.local_servers[0]
    fake = _FakeTrace()
    server.profiler = DeviceProfiler(base_dir=str(tmp_path), trace_api=fake.api)
    server.profiler.on_capture_end = server.occupancy_sampler.stop
    admin = ServerAdminHttpServer(server)
    admin.start()

    def post(path, body=b"{}"):
        req = urllib.request.Request(
            admin.url + path, data=body, method="POST"
        )
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        code, snap = post("/debug/profile/start")
        assert code == 200 and snap["active"] and snap["refCount"] == 1
        assert server.occupancy_sampler.running  # bracketed capture
        with urllib.request.urlopen(
            admin.url + "/debug/profile", timeout=10
        ) as r:
            assert json.loads(r.read())["active"]
        # /debug/device reports the live profiler + sampler state
        with urllib.request.urlopen(
            admin.url + "/debug/device", timeout=10
        ) as r:
            dev = json.loads(r.read())
        assert dev["profiler"]["active"] and dev["sampler"]["running"]

        code, snap = post("/debug/profile/stop")
        assert code == 200 and not snap["active"]
        deadline = time.time() + 5
        while server.occupancy_sampler.running and time.time() < deadline:
            time.sleep(0.02)
        assert not server.occupancy_sampler.running  # parked with capture

        # bad JSON body is a 400, not a stack trace
        code, err = post("/debug/profile/start", body=b"{nope")
        assert code == 400

        # no usable profiler backend: typed 404
        server.profiler._trace_api = _FakeTrace(fail_start=True).api
        code, err = post("/debug/profile/start")
        assert code == 404
        assert err["errorType"] == "ProfilerUnavailableError"
    finally:
        admin.stop()


# ----------------------------------------------------- platform peaks
def test_platform_peaks_cpu_none_known_tpu_declared_unknown_tpu_raises(monkeypatch):
    from pinot_tpu.utils import platform
    from pinot_tpu.utils.platform import platform_peaks

    out = platform_peaks(refresh=True)
    # CPU test mesh: no declared peak — the roofline must say
    # "unavailable", not invent a number
    assert out["peakFlopsPerSec"] is None and out["peakBytesPerSec"] is None
    assert out["platform"] == "cpu" and out["source"] == "unknown"

    class _Dev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    import jax

    try:
        monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v5 lite")])
        v5e = platform_peaks(refresh=True)
        assert v5e["source"] == "declared"
        assert (v5e["peakFlopsPerSec"], v5e["peakBytesPerSec"]) == (197e12, 819e9)
        # a TPU the table does not know is an error, not a None peak
        monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v99")])
        with pytest.raises(LookupError, match="TPU v99"):
            platform_peaks(refresh=True)
    finally:
        monkeypatch.undo()
        platform._peaks_cache = None  # the next caller sees the real CPU device


# ------------------------------------------------- roofline consistency
def test_plan_roofline_consistent_with_phase_timers(util_broker):
    """Acceptance: /debug/plans' roofline entry is computed from the
    SAME wall time the phase timers / cost vector report — achieved
    bytes/s == deviceBytes / sum(per-response deviceMs) exactly."""
    broker = util_broker
    server = broker.local_servers[0]
    pql = "SELECT sum(metInt) FROM utilTable WHERE dimInt > 20"
    want_ms = 0.0
    want_bytes = 0
    for _ in range(4):
        resp = broker.handle_pql(pql)
        assert not resp.exceptions
        want_ms += float(resp.cost["deviceMs"])
        want_bytes += int(resp.cost["deviceBytes"])
    assert want_ms > 0 and want_bytes > 0

    snap = server.plan_stats.snapshot(top=10)
    [plan] = [p for p in snap["plans"] if p["count"] == 4]
    roof = plan["roofline"]
    assert roof["deviceMs"] == pytest.approx(want_ms, abs=0.01)
    assert roof["deviceBytes"] == want_bytes
    assert roof["achievedBytesPerSec"] == pytest.approx(
        want_bytes * 1000.0 / roof["deviceMs"], rel=1e-6
    )
    # CPU mesh declares no peak: explicit None, not a fake fraction
    assert roof["rooflineFraction"] is None
    # the per-tier latency window matches the execution count
    assert plan["tierLatencyMs"]["device"]["samples"] == 4
    assert plan["tierLatencyMs"]["host"]["samples"] == 0
    # and the server-wide recent window saw the same traffic
    recent = server.device_utilization()["recent"]
    assert recent["queries"] >= 4
    assert recent["deviceBytes"] >= want_bytes


def test_roofline_fractions_against_declared_peaks(monkeypatch, util_broker):
    """With peaks declared, the roofline fraction is the best-utilized
    resource's achieved/peak ratio."""
    from pinot_tpu.utils import platform

    monkeypatch.setattr(
        platform,
        "_peaks_cache",
        {
            "platform": "tpu",
            "deviceKind": "test",
            "peakFlopsPerSec": 1e15,
            "peakBytesPerSec": 1e12,
            "source": "declared",
        },
    )
    broker = util_broker
    server = broker.local_servers[0]
    for _ in range(2):
        assert not broker.handle_pql(
            "SELECT max(metFloat) FROM utilTable WHERE dimInt > 30"
        ).exceptions
    [plan] = server.plan_stats.snapshot(top=10)["plans"]
    roof = plan["roofline"]
    assert roof["bandwidthFraction"] == pytest.approx(
        roof["achievedBytesPerSec"] / 1e12, abs=1e-6
    )
    fractions = [roof["bandwidthFraction"]]
    if "flopsFraction" in roof:
        fractions.append(roof["flopsFraction"])
    assert roof["rooflineFraction"] == pytest.approx(max(fractions), abs=1e-6)
    recent = server.device_utilization()["recent"]
    assert recent["rooflineFraction"] is not None


def test_host_path_latency_attributed_per_digest(util_broker, monkeypatch):
    """The host tier records per-digest execution time too — a mixed
    workload's /debug/plans carries comparable latency on BOTH tiers."""
    broker = util_broker
    server = broker.local_servers[0]
    # postings path serves host-side; the range scan serves on device
    # (a value the table holds: a literal in no segment's dictionary leaves
    # every segment out of the work, and no tier serves: PR 48; one value of
    # twenty is over the postings tier's crossover, which is lifted here)
    held = random_rows(make_test_schema(with_mv=False), 1200, seed=23)[0]["dimStr"]
    host_pql = f"SELECT avg(metFloat) FROM utilTable WHERE dimStr = '{held}'"
    dev_pql = "SELECT sum(metInt) FROM utilTable WHERE dimInt > 40"
    for _ in range(2):
        monkeypatch.setenv("PINOT_TPU_INDEX_MAX_MATCHES", "100000")
        assert not broker.handle_pql(host_pql).exceptions
        monkeypatch.delenv("PINOT_TPU_INDEX_MAX_MATCHES")
        assert not broker.handle_pql(dev_pql).exceptions
    by_summary = {
        p["summary"]: p for p in server.plan_stats.snapshot(top=10)["plans"]
    }
    host_plan = next(
        p for s, p in by_summary.items() if "dimStr:EQUALITY" in s
    )
    dev_plan = next(p for s, p in by_summary.items() if "dimInt:RANGE" in s)
    assert host_plan["tierLatencyMs"]["host"]["samples"] == 2
    assert host_plan["tierLatencyMs"]["host"]["p95Ms"] > 0
    assert host_plan["tierLatencyMs"]["device"]["samples"] == 0
    assert host_plan["roofline"] is None  # never ran on device
    assert dev_plan["tierLatencyMs"]["device"]["samples"] == 2
    assert dev_plan["tierLatencyMs"]["host"]["samples"] == 0
    assert dev_plan["roofline"] is not None


def test_status_device_section(util_broker):
    server = util_broker.local_servers[0]
    dev = util_broker.local_servers[0].status()["device"]
    assert {"platform", "occupancy", "transfers", "recent", "profiler"} <= set(
        dev
    )
    assert dev["occupancy"]["busyFraction"] >= 0.0
    assert not dev["profiler"]["active"]
    # the device.util.* series are pre-registered at construction
    gauges = server.metrics.snapshot()["gauges"]
    for name in (
        "device.util.busyFraction",
        "device.util.avgQueueDepth",
        "device.util.h2dBytes",
        "device.util.d2hBytes",
        "device.util.achievedBytesPerSec",
        "device.util.achievedFlopsPerSec",
        "device.util.rooflineFraction",
        "profile.active",
    ):
        assert name in gauges, name


# ------------------------------------------------- controller rollup
def test_controller_utilization_rollup_and_dashboard(tmp_path):
    """Acceptance: /debug/utilization's totals equal the per-server
    snapshots it includes verbatim; unreachable servers degrade to a
    named entry; the dashboard page renders the rollup."""
    from pinot_tpu.controller.controller import (
        ControllerHttpServer,
        collect_utilization,
    )
    from pinot_tpu.controller.resource_manager import InstanceState
    from pinot_tpu.server.network_starter import ServerAdminHttpServer

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path), http=True)
    admin = None
    http = None
    try:
        schema = make_test_schema(with_mv=False)
        physical = cluster.add_offline_table(schema)
        rows = random_rows(schema, 800, seed=31)
        cluster.upload(
            physical, build_segment(schema, rows, physical, "util0")
        )
        for _ in range(3):
            assert not cluster.query(
                "SELECT sum(metInt) FROM testTable WHERE dimInt > 5"
            ).exceptions

        admin = ServerAdminHttpServer(cluster.servers[0])
        admin.start()
        cluster.controller.resources.instances["server0"].url = admin.url
        # a registered-but-dead admin surface must degrade, not fail
        cluster.controller.resources.register_instance(
            InstanceState(name="ghost", role="server", url="http://127.0.0.1:9")
        )

        util = collect_utilization(cluster.controller, timeout_s=5.0)
        assert "ghost" in util["unreachable"]
        dev = util["servers"]["server0"]["device"]
        # totals are computed from EXACTLY the snapshots included
        assert util["totals"]["h2dBytes"] == dev["transfers"]["h2dBytes"]
        assert util["totals"]["d2hBytes"] == dev["transfers"]["d2hBytes"]
        assert util["totals"]["deviceMs"] == dev["recent"]["deviceMs"]
        assert util["totals"]["deviceBytes"] == dev["recent"]["deviceBytes"]
        assert util["totals"]["queries"] == dev["recent"]["queries"] >= 3
        assert util["totals"]["achievedBytesPerSec"] == pytest.approx(
            dev["recent"]["deviceBytes"] * 1000.0 / dev["recent"]["deviceMs"],
            rel=1e-6,
        )
        assert util["occupancy"]["servers"] == 1
        assert util["occupancy"]["meanBusyFraction"] == pytest.approx(
            dev["occupancy"]["busyFraction"], abs=1e-9
        )
        assert util["profilesActive"] == 0
        plans = util["underutilizedPlans"]
        assert plans and plans[0]["server"] == "server0"
        assert {"digest", "deviceMs", "achievedBytesPerSec",
                "rooflineFraction"} <= set(plans[0])

        http = ControllerHttpServer(cluster.controller)
        http.start()
        base = f"http://127.0.0.1:{http.port}"
        with urllib.request.urlopen(
            base + "/debug/utilization", timeout=10
        ) as r:
            over = json.loads(r.read())
        assert "server0" in over["servers"] and "ghost" in over["unreachable"]
        with urllib.request.urlopen(
            base + "/dashboard/utilization", timeout=10
        ) as r:
            page = r.read().decode()
        assert "Device utilization" in page and "server0" in page
        assert "unreachable" in page  # the partial-rollup banner
    finally:
        if http is not None:
            http.stop()
        if admin is not None:
            admin.stop()
        cluster.stop()


# ------------------------------------------------------ explain_dump
def test_explain_dump_renders_cost_analysis_and_roofline():
    from pinot_tpu.tools.explain_dump import (
        render_cost_analysis,
        render_roofline,
    )

    dev = {
        "compile": {
            "state": "warm",
            "costAnalysis": {
                "flops": 2.5e9,
                "bytesAccessed": 1.5e6,
                "source": "lowered",
            },
        }
    }
    out = render_cost_analysis(dev)
    assert "est flops=2.50G" in out and "est bytes=1.50M" in out
    assert "(lowered)" in out
    assert render_cost_analysis(
        {"compile": {"costAnalysis": "unavailable"}}
    ).strip() == "cost-analysis: unavailable"
    assert render_cost_analysis({"compile": {}}) == ""

    est = {
        "roofline": {
            "achievedBytesPerSec": 3.2e9,
            "achievedFlopsPerSec": 1.1e12,
            "rooflineFraction": 0.125,
        }
    }
    line = render_roofline(est)
    assert "achieved=3.20GB/s" in line and "1.10TFLOP/s" in line
    assert "roofline=12.50%" in line
    nopeak = render_roofline({"roofline": {"achievedBytesPerSec": 1.0,
                                           "rooflineFraction": None}})
    assert "n/a (no peak declared)" in nopeak
    assert render_roofline({}) == ""


def test_explain_dump_footer_on_executed_shape(util_broker):
    """End-to-end: once a shape has executed, EXPLAIN's history
    estimate carries the roofline and the renderer shows it."""
    from pinot_tpu.tools.explain_dump import render_explain

    broker = util_broker
    pql = "SELECT sum(metInt) FROM utilTable WHERE dimInt > 60"
    for _ in range(2):
        assert not broker.handle_pql(pql).exceptions
    plan = broker.handle_pql("EXPLAIN " + pql)
    out = render_explain(plan.to_json())
    assert "utilization: achieved=" in out
    assert "roofline=n/a (no peak declared)" in out  # CPU mesh
    assert "cost-analysis:" in out
