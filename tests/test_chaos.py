"""Chaos tests: fault-injected scatter-gather plus real killed processes.

Two tiers:

- Deterministic fault injection (``-m chaos``, fast, in tier-1):
  ``FaultInjectingTransport`` over in-process servers exercises replica
  failover, hedged requests, the circuit breaker, partial-response
  accounting, and deadline propagation without sleeping through real
  heartbeat windows or spawning processes.
- The ChaosMonkeyIntegrationTest analog (slow, opt-in): real OS server
  processes killed with POSIX signals (``ChaosMonkeyIntegrationTest.
  java:41``, kill via signals :76, consistency assertion :206).
"""
import os
import signal
import subprocess
import sys
import time

import pytest

from pinot_tpu.broker.broker import BrokerRequestHandler
from pinot_tpu.broker.health import ServerHealthTracker
from pinot_tpu.broker.routing import RoutingTableProvider
from pinot_tpu.common.faults import FaultInjectingTransport
from pinot_tpu.common.response import ErrorCode
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.format import write_segment
from pinot_tpu.server.instance import ServerInstance
from pinot_tpu.tools.datagen import make_test_schema, random_rows
from pinot_tpu.transport.local import LocalTransport
from pinot_tpu.transport.tcp import TcpTransport

TABLE = "chaosTable_OFFLINE"
ADDR_A = ("sA", 0)
ADDR_B = ("sB", 0)


def _two_replica_cluster(**broker_kwargs):
    """Two in-process servers, each holding BOTH segments (replication
    2), behind a fault-injecting transport.  400 rows total."""
    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, 400, seed=13)
    segs = {
        "c0": build_segment(schema, rows[:200], TABLE, "c0"),
        "c1": build_segment(schema, rows[200:], TABLE, "c1"),
    }
    transport = FaultInjectingTransport(LocalTransport(), seed=7)
    addresses = {"sA": ADDR_A, "sB": ADDR_B}
    for name, addr in addresses.items():
        inst = ServerInstance(name)
        for seg in segs.values():
            inst.add_segment(TABLE, seg)
        transport.inner.register(addr, inst.handle_request)
    routing = RoutingTableProvider(num_tables=1)
    routing.update(
        TABLE,
        {
            "c0": {"sA": "ONLINE", "sB": "ONLINE"},
            "c1": {"sA": "ONLINE", "sB": "ONLINE"},
        },
    )
    broker_kwargs.setdefault("timeout_ms", 10_000)
    broker_kwargs.setdefault("retry_backoff_ms", 1.0)
    broker = BrokerRequestHandler(transport, addresses, routing=routing, **broker_kwargs)
    return broker, transport


# ------------------------------------------------------- failover
@pytest.mark.chaos
def test_one_dead_replica_failover_completes():
    """Acceptance: killing one replica of a 2-replica table still yields
    a COMPLETE response — the dead server's segment set re-issues to the
    surviving replica instead of degrading the query."""
    broker, transport = _two_replica_cluster()
    transport.set_fault(ADDR_A, down=True)
    resp = broker.handle_pql("SELECT count(*) FROM chaosTable")
    assert resp.num_docs_scanned == 400
    assert resp.partial_response is False
    assert resp.num_segments_unserved == 0
    # recovered-by-failover attempts do NOT surface client exceptions
    assert not resp.exceptions
    assert resp.num_servers_responded == 1  # only sB answered
    # sA absorbed at least one failed attempt before the failover
    assert any(c.outcome != "ok" for c in transport.calls_to(ADDR_A)) or (
        transport.calls_to(ADDR_A) == []
    )


@pytest.mark.chaos
def test_all_replicas_dead_partial_within_deadline():
    """Acceptance: with every replica dead the query returns WITHIN the
    deadline, flagged partial, with the unserved-segment count."""
    broker, transport = _two_replica_cluster(timeout_ms=2_000)
    transport.set_fault(ADDR_A, down=True)
    transport.set_fault(ADDR_B, down=True)
    t0 = time.monotonic()
    resp = broker.handle_pql("SELECT count(*) FROM chaosTable")
    elapsed = time.monotonic() - t0
    assert elapsed < 2.0  # transport errors are instant; no deadline wait
    assert resp.partial_response is True
    assert resp.num_segments_unserved == 2
    assert resp.exceptions  # the failures are reported, not hidden
    assert resp.num_docs_scanned == 0
    assert resp.num_servers_responded == 0


@pytest.mark.chaos
def test_blackholed_replica_fails_over_within_deadline():
    """A server that accepts the request and never replies (no RST,
    just silence) must not burn the whole deadline: with an untried
    replica available the attempt is capped at half the remaining
    budget, surfaces as a transport timeout, and fails over in time."""
    broker, transport = _two_replica_cluster(timeout_ms=2_000)
    broker.routing.update(TABLE, {"c0": {"sA": "ONLINE", "sB": "ONLINE"}})
    primary = next(iter(broker.routing.find_servers(TABLE)))
    black_addr = ADDR_A if primary == "sA" else ADDR_B
    transport.set_fault(black_addr, blackhole=True)
    t0 = time.monotonic()
    resp = broker.handle_pql("SELECT count(*) FROM chaosTable")
    elapsed = time.monotonic() - t0
    assert resp.num_docs_scanned == 200  # complete, via the live replica
    assert resp.partial_response is False
    assert not resp.exceptions
    assert elapsed < 1.9  # failover happened BEFORE the 2s deadline


@pytest.mark.chaos
def test_transient_blip_heals_via_failover():
    """A single transient transport failure costs a retry, not data."""
    broker, transport = _two_replica_cluster()
    transport.set_fault(ADDR_A, fail_next=1)
    transport.set_fault(ADDR_B, fail_next=1)
    resp = broker.handle_pql("SELECT count(*) FROM chaosTable")
    assert resp.num_docs_scanned == 400
    assert resp.partial_response is False
    assert not resp.exceptions


@pytest.mark.chaos
def test_saturated_server_reply_fails_over():
    """A typed 210 (scheduler saturated) reply is retryable: the broker
    re-issues the segment set on the replica instead of surfacing it."""
    from pinot_tpu.common.datatable import serialize_result
    from pinot_tpu.engine.results import IntermediateResult

    broker, transport = _two_replica_cluster()

    def saturated(_payload: bytes) -> bytes:
        return serialize_result(
            IntermediateResult(
                exceptions=[(ErrorCode.SERVER_SCHEDULER_DOWN, "saturated")]
            )
        )

    transport.inner.register(ADDR_A, saturated)
    resp = broker.handle_pql("SELECT count(*) FROM chaosTable")
    assert resp.num_docs_scanned == 400
    assert resp.partial_response is False
    assert not resp.exceptions


# ------------------------------------------------------- hedging
@pytest.mark.chaos
def test_slow_server_hedge_wins_under_deadline():
    """Acceptance: a straggler replica triggers a hedged request to the
    other replica; the fast reply wins well before the straggler (and
    far before the query deadline)."""
    broker, transport = _two_replica_cluster(
        timeout_ms=10_000, hedge_delay_ms=50.0
    )
    # single segment so the whole query is one hedgeable batch
    broker.routing.update(TABLE, {"c0": {"sA": "ONLINE", "sB": "ONLINE"}})
    primary = next(iter(broker.routing.find_servers(TABLE)))
    slow_addr, fast_addr = (ADDR_A, ADDR_B) if primary == "sA" else (ADDR_B, ADDR_A)
    transport.set_fault(slow_addr, delay_s=2.0)
    t0 = time.monotonic()
    resp = broker.handle_pql("SELECT count(*) FROM chaosTable")
    elapsed = time.monotonic() - t0
    assert resp.num_docs_scanned == 200  # segment c0 only
    assert resp.partial_response is False
    assert resp.num_hedges >= 1
    assert elapsed < 1.5  # hedge beat the 2s straggler
    assert transport.calls_to(fast_addr)  # the hedge actually went out


@pytest.mark.chaos
def test_hedge_skipped_near_quota():
    """Hedging amplifies load; a table brushing its QPS quota must not
    double its own traffic."""
    broker, transport = _two_replica_cluster(
        timeout_ms=3_000, hedge_delay_ms=10.0, hedge_min_quota_headroom=2.0
    )
    # headroom is at most 1.0 < 2.0, so hedging is always suppressed
    transport.set_fault(ADDR_A, delay_s=0.3)
    transport.set_fault(ADDR_B, delay_s=0.3)
    resp = broker.handle_pql("SELECT count(*) FROM chaosTable")
    assert resp.num_docs_scanned == 400
    assert resp.num_hedges == 0


# ------------------------------------------------------- circuit breaker
@pytest.mark.chaos
def test_circuit_breaker_open_probe_close():
    clock = [0.0]
    h = ServerHealthTracker(failure_threshold=3, penalty_ms=1_000, clock=lambda: clock[0])
    for _ in range(2):
        h.record_failure("s1")
    assert h.is_healthy("s1")  # below threshold
    h.record_failure("s1")
    assert h.state_of("s1") == "OPEN"
    assert not h.is_healthy("s1")
    assert not h.allow_request("s1")
    clock[0] = 1.1  # past the penalty window -> HALF_OPEN, one probe
    assert h.allow_request("s1") is True
    assert h.allow_request("s1") is False  # second concurrent probe refused
    h.record_success("s1")
    assert h.state_of("s1") == "CLOSED"
    # a failed probe re-opens with a fresh window
    for _ in range(3):
        h.record_failure("s1")
    clock[0] = 2.3
    assert h.allow_request("s1") is True
    h.record_failure("s1")
    assert h.state_of("s1") == "OPEN"
    assert not h.allow_request("s1")


@pytest.mark.chaos
def test_probe_claim_is_a_lease_not_a_permanent_mark():
    """A half-open probe whose holder vanished (attempt cancelled at
    query end, reply never read) must not quarantine the server forever:
    the claim expires after one penalty window."""
    clock = [0.0]
    h = ServerHealthTracker(failure_threshold=1, penalty_ms=1_000, clock=lambda: clock[0])
    h.record_failure("s1")  # OPEN at t=0
    clock[0] = 1.1
    assert h.allow_request("s1") is True  # probe claimed...
    assert h.is_healthy("s1") is False  # ...others steered away
    # holder never reports back; lease expires one penalty window later
    clock[0] = 2.2
    assert h.is_healthy("s1") is True
    assert h.allow_request("s1") is True  # a fresh probe may go out


@pytest.mark.chaos
def test_routing_prefers_healthy_replicas():
    h = ServerHealthTracker(failure_threshold=1, penalty_ms=60_000)
    routing = RoutingTableProvider(num_tables=4)
    routing.update(
        TABLE,
        {
            "c0": {"sA": "ONLINE", "sB": "ONLINE"},
            "c1": {"sA": "ONLINE", "sB": "ONLINE"},
        },
    )
    h.record_failure("sA")  # penalty box
    for _ in range(20):
        cover = routing.find_servers(TABLE, health=h)
        assert set(cover) == {"sB"}, cover
    # alternates excludes the tried server even when unhealthy ones remain
    assignment, unserved = routing.alternates(TABLE, ["c0"], {"sB"}, health=h)
    assert assignment == {"sA": ["c0"]} and unserved == []
    assignment, unserved = routing.alternates(TABLE, ["c0"], {"sA", "sB"})
    assert assignment == {} and unserved == ["c0"]


@pytest.mark.chaos
def test_controller_death_event_reaches_health_tracker():
    """Heartbeat-miss -> set_instance_alive(False) must reach the broker
    circuit breaker through the SAME event that rebuilds routing."""
    from pinot_tpu.broker.starter import BrokerStarter
    from pinot_tpu.controller.resource_manager import ClusterResourceManager

    resources = ClusterResourceManager()
    transport = LocalTransport()
    broker = BrokerRequestHandler(transport, {})
    starter = BrokerStarter(broker, resources)
    starter.start()
    from pinot_tpu.controller.resource_manager import InstanceState

    resources.register_instance(InstanceState("sX", role="server"))
    resources.set_instance_alive("sX", False)
    assert broker.health.state_of("sX") == "OPEN"
    resources.set_instance_alive("sX", True)
    assert broker.health.state_of("sX") == "CLOSED"


# ------------------------------------------------------- deadline + validation
@pytest.mark.chaos
def test_scheduler_sheds_expired_deadline_work():
    """Deadline propagation: a query whose broker-sent budget expired
    while queued is abandoned at dequeue, never executed."""
    from pinot_tpu.server.scheduler import QueryAbandonedError, QueryScheduler

    sched = QueryScheduler(num_workers=1)
    ran = []
    with pytest.raises(QueryAbandonedError):
        sched.run(lambda: ran.append(1), timeout_s=10.0, deadline=time.monotonic() - 0.001)
    assert ran == []
    assert sched.abandoned_count == 1
    sched.shutdown()


@pytest.mark.chaos
def test_invalid_timeout_override_rejected():
    broker, _ = _two_replica_cluster()
    for bad in (-5, 0, float("nan")):
        resp = broker.handle_pql("SELECT count(*) FROM chaosTable", timeout_ms=bad)
        assert resp.exceptions
        assert resp.exceptions[0].error_code == ErrorCode.QUERY_VALIDATION
    # valid override still works
    resp = broker.handle_pql("SELECT count(*) FROM chaosTable", timeout_ms=5_000)
    assert not resp.exceptions and resp.num_docs_scanned == 400


@pytest.mark.chaos
@pytest.mark.slow
def test_flaky_link_soak():
    """Soak-style (opt-in via -m slow): a 50%-lossy link to one replica
    must not lose a single query — failover absorbs every seeded fault,
    and the circuit breaker steers steady-state traffic to the clean
    replica after enough consecutive failures."""
    broker, transport = _two_replica_cluster(retry_attempts=3)
    transport.set_fault(ADDR_A, error_rate=0.5)
    for _ in range(50):
        resp = broker.handle_pql("SELECT count(*) FROM chaosTable")
        assert resp.num_docs_scanned == 400
        assert resp.partial_response is False


@pytest.mark.chaos
def test_parse_timeout_contract():
    from pinot_tpu.broker.broker import InvalidTimeoutError, _parse_timeout

    assert _parse_timeout(None) is None
    assert _parse_timeout("") is None
    assert _parse_timeout("1500") == 1500.0
    assert _parse_timeout(250) == 250.0
    for junk in ("abc", "-1", "0", True, False, "inf", "nan", -3, 0):
        with pytest.raises(InvalidTimeoutError):
            _parse_timeout(junk)


def _spawn_server(name, table, seg_dirs, repo_root):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "pinot_tpu.tools.run_server",
            "--name", name,
            "--table", table,
            "--segments", *seg_dirs,
        ],
        cwd=repo_root,
        env=env,
        stdout=subprocess.PIPE,
        text=True,
    )
    deadline = time.time() + 60
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line.startswith("READY"):
            return proc, int(line.split()[1])
    proc.kill()
    raise RuntimeError(f"server {name} did not become ready")


@pytest.mark.chaos
@pytest.mark.slow
def test_kill_and_restart_server(tmp_path):
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, 400, seed=13)

    seg_dirs = {}
    for i, name in enumerate(["c0", "c1"]):
        seg = build_segment(schema, rows[i * 200 : (i + 1) * 200], TABLE, name)
        d = str(tmp_path / name)
        write_segment(seg, d)
        seg_dirs[name] = d

    procs = {}
    ports = {}
    try:
        procs["sA"], ports["sA"] = _spawn_server("sA", TABLE, [seg_dirs["c0"]], repo_root)
        procs["sB"], ports["sB"] = _spawn_server("sB", TABLE, [seg_dirs["c1"]], repo_root)

        routing = RoutingTableProvider()
        routing.update(TABLE, {"c0": {"sA": "ONLINE"}, "c1": {"sB": "ONLINE"}})
        broker = BrokerRequestHandler(
            TcpTransport(),
            {"sA": ("127.0.0.1", ports["sA"]), "sB": ("127.0.0.1", ports["sB"])},
            routing=routing,
            timeout_ms=30_000,
        )

        resp = broker.handle_pql("SELECT count(*) FROM chaosTable")
        assert resp.num_docs_scanned == 400
        assert not resp.exceptions

        # SIGKILL one server: partial results + an exception, no hang
        procs["sB"].send_signal(signal.SIGKILL)
        procs["sB"].wait(timeout=10)
        broker2 = BrokerRequestHandler(  # fresh transport (no pooled dead socket)
            TcpTransport(),
            {"sA": ("127.0.0.1", ports["sA"]), "sB": ("127.0.0.1", ports["sB"])},
            routing=routing,
            timeout_ms=8_000,
        )
        resp = broker2.handle_pql("SELECT count(*) FROM chaosTable")
        assert resp.num_docs_scanned == 200
        assert len(resp.exceptions) == 1
        assert resp.num_servers_responded == 1

        # restart on a fresh port; routing repoints; full recovery
        procs["sB2"], new_port = _spawn_server("sB", TABLE, [seg_dirs["c1"]], repo_root)
        broker2.set_server_address("sB", ("127.0.0.1", new_port))
        resp = broker2.handle_pql("SELECT count(*) FROM chaosTable")
        assert resp.num_docs_scanned == 400
        assert not resp.exceptions
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
