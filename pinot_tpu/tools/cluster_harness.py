"""In-process cluster harness: controller + N servers + broker in one
process.

The reference's ``PerfBenchmarkDriver.java:61`` (starts the whole
cluster in-process, :160-162) and the integration tests' ``ClusterTest``
use the same trick; this is the standard harness for quickstarts, perf
runs, and integration tests.

``--scenario kill-server|drain|rolling-restart`` runs the cluster
self-stabilization chaos scenarios (closed-loop query load while a
server dies / drains / every server rolls): the SAME scenario code
drives manual chaos runs from this CLI and the deterministic tier-1
chaos tests (``tests/test_stabilizer.py``).

``--scenario partition-server|partition-controller|asymmetric-partition
|split-brain`` runs the network-partition chaos scenarios (ISSUE 9)
over a ``NetworkedCluster`` — controller + servers + broker as real
HTTP/TCP endpoints in one process, every link routed through a shared
``NetworkFaultInjector`` — proving lease-fenced serving and the
epoch-fenced commit plane under severed links (tier-1 twins in
``tests/test_partition.py``).

``--scenario rolling-restart-warm`` is the warm-start acceptance
(ISSUE 16): every server is replaced by a FRESH instance sharing only
the persistent compile cache while the steady workload replays — zero
failed queries, ``compile.cold == 0`` on restarted servers (persistent
ledger + fleet prewarming), and readiness-gated movement (trims wait
for warming destinations; the event ring proves it).  Tier-1 twin in
``tests/test_warmstart.py``.

``--scenario hbm-pressure`` runs the tiered-residency chaos acceptance
(ISSUE 18): addressable staged data ~8x the HBM cap under a hot
closed loop + cold-table sweep — zero failed queries, hot-set p99
bounded against its uncapped baseline, demotion/promotion/cold-load
counters proving HBM <-> host <-> disk cycled, and an injected
allocation failure healed by demotion (tier-1 twin in
``tests/test_chaos_hbm_pressure.py``).

``--scenario audit-divergence`` runs the correctness-audit chaos
acceptance (ISSUE 19): a seeded fault injector silently corrupts one
serving tier's aggregates under closed-loop load — the shadow
differential auditor must detect the divergence within budget,
quarantine the (plan digest, tier), and every answer after the
quarantine must be byte-identical to the pre-corruption reference
with zero failed queries (tier-1 twin in ``tests/test_audit.py``).

``--scenario elastic-fleet`` runs the fleet-breadth chaos acceptance
(ISSUE 15): 100+ tables under mixed ingest+query closed-loop load,
a forced hot-tenant skew, a live make-before-break rebalance, and a
mid-rebalance controller restart — zero failed queries, zero
lost/duplicate rows, exactly one committed copy per sequence (tier-1
twin in ``tests/test_elastic_fleet.py``).
"""
from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from pinot_tpu.broker.broker import BrokerHttpServer, BrokerRequestHandler
from pinot_tpu.broker.starter import BrokerStarter
from pinot_tpu.common.response import BrokerResponse
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.tableconfig import TableConfig
from pinot_tpu.controller.controller import Controller
from pinot_tpu.segment.immutable import ImmutableSegment
from pinot_tpu.server.instance import ServerInstance
from pinot_tpu.server.starter import ServerStarter
from pinot_tpu.transport.local import LocalTransport


class InProcessCluster:
    def __init__(
        self,
        num_servers: int = 2,
        data_dir: Optional[str] = None,
        mesh=None,
        http: bool = False,
        timeout_ms: float = 15_000.0,
        max_pending: int = 64,
    ) -> None:
        self.data_dir = data_dir or tempfile.mkdtemp(prefix="pinot_tpu_cluster_")
        self.controller = Controller(self.data_dir)
        self.transport = LocalTransport()

        self.servers: List[ServerInstance] = []
        self.server_starters: List[ServerStarter] = []
        addresses: Dict[str, tuple] = {}
        for i in range(num_servers):
            server = ServerInstance(f"server{i}", mesh=mesh, max_pending=max_pending)
            starter = ServerStarter(server, self.controller.resources)
            starter.start()
            address = (server.name, 0)
            self.transport.register(address, server.handle_request)
            addresses[server.name] = address
            self.servers.append(server)
            self.server_starters.append(starter)

        self.broker = BrokerRequestHandler(
            self.transport, addresses, name="broker0", timeout_ms=timeout_ms
        )
        self.http: Optional[BrokerHttpServer] = None
        broker_url = None
        if http:
            self.http = BrokerHttpServer(self.broker)
            self.http.start()
            broker_url = f"http://{self.http.host}:{self.http.port}"
        self.broker_starter = BrokerStarter(
            self.broker, self.controller.resources, url=broker_url
        )
        self.broker_starter.start()

    def add_server(self, name: Optional[str] = None, mesh=None) -> ServerInstance:
        """Join a new server into the running cluster (elastic scale-out;
        pair with controller.rebalance_table to move segments onto it)."""
        name = name or f"server{len(self.servers)}"
        server = ServerInstance(name, mesh=mesh)
        starter = ServerStarter(server, self.controller.resources)
        starter.start()
        address = (server.name, 0)
        self.transport.register(address, server.handle_request)
        self.broker.set_server_address(server.name, address)
        self.servers.append(server)
        self.server_starters.append(starter)
        return server

    # -- convenience API ---------------------------------------------
    def add_offline_table(
        self, schema: Schema, table_name: Optional[str] = None, **config_kwargs
    ) -> str:
        self.controller.add_schema(schema)
        config = TableConfig(
            table_name=table_name or schema.schema_name, table_type="OFFLINE", **config_kwargs
        )
        return self.controller.add_table(config)

    def add_realtime_table(
        self,
        schema: Schema,
        stream,
        table_name: Optional[str] = None,
        rows_per_segment: int = 1000,
        replication: int = 1,
    ) -> str:
        from pinot_tpu.common.tableconfig import StreamConfig

        self.controller.add_schema(schema)
        config = TableConfig(
            table_name=table_name or schema.schema_name,
            table_type="REALTIME",
            replication=replication,
            stream=StreamConfig(stream_type="memory", rows_per_segment=rows_per_segment),
        )
        return self.controller.add_realtime_table(config, stream)

    def upload(self, physical_table: str, segment: ImmutableSegment) -> None:
        self.controller.upload_segment(physical_table, segment)

    def query(self, pql: str, trace: bool = False) -> BrokerResponse:
        return self.broker.handle_pql(pql, trace=trace)

    def stop(self) -> None:
        if self.http is not None:
            self.http.stop()
        # history recorders are per-role daemon threads; stop them with
        # the cluster so tests don't accumulate tick loops (schedulers/
        # lanes are left as-is — stop() must not fail in-flight queries)
        self.broker.shutdown()
        for s in self.servers:
            s.history.stop()
            s.auditor.stop()
        self.controller.stop()


def single_server_broker(
    table: str,
    segments,
    timeout_ms: float = 600_000.0,
    max_pending: int = 64,
    **server_kwargs,
):
    """One in-process server + broker over LocalTransport — the
    minimal serving topology of the tests.  The generous default timeout
    covers the first query's staging + cold compile.  Extra kwargs
    reach the ServerInstance (e.g. ``pipeline=False`` for the serial
    executor path); the instance is reachable as
    ``broker.local_servers[0]`` so callers can read lane/scheduler
    counters."""
    from pinot_tpu.broker.broker import BrokerRequestHandler
    from pinot_tpu.broker.routing import RoutingTableProvider

    server = ServerInstance("benchServer", max_pending=max_pending, **server_kwargs)
    for seg in segments:
        server.add_segment(table, seg)
    transport = LocalTransport()
    transport.register(("benchServer", 0), server.handle_request)
    routing = RoutingTableProvider()
    routing.update(table, {s.segment_name: {"benchServer": "ONLINE"} for s in segments})
    broker = BrokerRequestHandler(
        transport,
        {"benchServer": ("benchServer", 0)},
        routing=routing,
        timeout_ms=timeout_ms,
    )
    broker.local_servers = [server]
    return broker


# ---------------------------------------------------------------------------
# Self-stabilization chaos scenarios (shared by the CLI and the tier-1
# chaos tests): closed-loop load over an in-process cluster while a
# server is killed / drained / the whole fleet rolling-restarts, with
# the SelfStabilizer driven explicitly (run_once — deterministic, no
# background sleeps).
# ---------------------------------------------------------------------------


class ClosedLoopLoad:
    """N client threads issuing the same query back-to-back, classifying
    every response: ok (complete + correct), partial (transient
    ``partialResponse`` — allowed during healing), failed (wrong count
    or exceptions on a response claiming to be complete).  Per-query
    latencies are recorded so overload scenarios can compare a tenant's
    loaded percentiles against its unloaded baseline."""

    def __init__(
        self, cluster: "InProcessCluster", pql: str, expected_docs: Optional[int],
        clients: int = 3,
    ) -> None:
        self.cluster = cluster
        self.pql = pql
        # None = "any complete answer is ok" (live realtime tables,
        # where the expected count grows while ingest runs)
        self.expected_docs = expected_docs
        self.clients = clients
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._lock = threading.Lock()
        self.total = 0
        self.ok = 0
        self.partials = 0
        self.failed = 0
        self.failures: List[str] = []  # first few failure descriptions
        self.latencies_ms: List[float] = []

    def _loop(self) -> None:
        while not self._stop.is_set():
            t0 = time.perf_counter()
            try:
                resp = self.cluster.broker.handle_pql(self.pql)
            except Exception as e:  # a raised handler is always a failure
                with self._lock:
                    self.total += 1
                    self.failed += 1
                    if len(self.failures) < 8:
                        self.failures.append(f"{type(e).__name__}: {e}")
                continue
            ms = (time.perf_counter() - t0) * 1000.0
            with self._lock:
                self.total += 1
                self.latencies_ms.append(ms)
                if resp.partial_response:
                    self.partials += 1
                elif resp.exceptions or (
                    self.expected_docs is not None
                    and resp.num_docs_scanned != self.expected_docs
                ):
                    self.failed += 1
                    if len(self.failures) < 8:
                        self.failures.append(
                            f"docs={resp.num_docs_scanned}/{self.expected_docs} "
                            f"exceptions={[e.message for e in resp.exceptions][:2]}"
                        )
                else:
                    self.ok += 1

    def start(self) -> "ClosedLoopLoad":
        for i in range(self.clients):
            t = threading.Thread(target=self._loop, name=f"load-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        return self

    @staticmethod
    def _pct(sorted_ms: List[float], p: float) -> float:
        if not sorted_ms:
            return 0.0
        i = min(len(sorted_ms) - 1, int(round(p / 100.0 * (len(sorted_ms) - 1))))
        return sorted_ms[i]

    def stop(self) -> Dict[str, Any]:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10)
        lat = sorted(self.latencies_ms)
        return {
            "queries": self.total,
            "okQueries": self.ok,
            "partialQueries": self.partials,
            "failedQueries": self.failed,
            "failures": list(self.failures),
            "p50Ms": round(self._pct(lat, 50), 3),
            "p99Ms": round(self._pct(lat, 99), 3),
        }


class FloodLoad:
    """Open-throttle tenant: N threads hammering one table back-to-back,
    classifying every reply by SHED TIER — the noisy neighbor whose
    overflow must come back as typed 429/210, never as timeouts."""

    def __init__(self, cluster: "InProcessCluster", pql: str, clients: int = 4) -> None:
        self.cluster = cluster
        self.pql = pql
        self.clients = clients
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._lock = threading.Lock()
        self.total = 0
        self.ok = 0
        self.shed_429 = 0  # broker admission (quota / concurrency / overload)
        self.shed_210 = 0  # server scheduler saturation (incl. 220 drain)
        self.timeouts = 0  # the failure mode overload protection must prevent
        self.other_failures = 0
        self.samples: List[str] = []

    def _classify(self, codes) -> str:
        from pinot_tpu.common.response import ErrorCode

        if ErrorCode.TOO_MANY_REQUESTS in codes:
            return "429"
        if (
            ErrorCode.SERVER_SCHEDULER_DOWN in codes
            or ErrorCode.SERVER_SHUTTING_DOWN in codes
        ):
            return "210"
        if (
            ErrorCode.EXECUTION_TIMEOUT in codes
            or ErrorCode.BROKER_TIMEOUT in codes
        ):
            return "timeout"
        return "other"

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                resp = self.cluster.broker.handle_pql(self.pql)
            except Exception as e:
                with self._lock:
                    self.total += 1
                    self.other_failures += 1
                    if len(self.samples) < 8:
                        self.samples.append(f"{type(e).__name__}: {e}")
                continue
            with self._lock:
                self.total += 1
                if not resp.exceptions:
                    self.ok += 1
                    continue
                kind = self._classify({e.error_code for e in resp.exceptions})
                if kind == "429":
                    self.shed_429 += 1
                elif kind == "210":
                    self.shed_210 += 1
                elif kind == "timeout":
                    self.timeouts += 1
                else:
                    self.other_failures += 1
                    if len(self.samples) < 8:
                        self.samples.append(
                            f"codes={[e.error_code for e in resp.exceptions]} "
                            f"{resp.exceptions[0].message[:120]}"
                        )

    def start(self) -> "FloodLoad":
        for i in range(self.clients):
            t = threading.Thread(target=self._loop, name=f"flood-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self) -> Dict[str, Any]:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10)
        return {
            "queries": self.total,
            "okQueries": self.ok,
            "shed429": self.shed_429,
            "shed210": self.shed_210,
            "timeouts": self.timeouts,
            "otherFailures": self.other_failures,
            "samples": list(self.samples),
        }


def _build_scenario_cluster(
    num_servers: int, replication: int, num_segments: int,
    data_dir: Optional[str] = None, seed: int = 5,
):
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.tools.datagen import make_test_schema, random_rows

    cluster = InProcessCluster(num_servers=num_servers, data_dir=data_dir)
    # scenarios drive rounds explicitly; act on death immediately
    cluster.controller.stabilizer.grace_s = 0.0
    schema = make_test_schema(with_mv=False)
    physical = cluster.add_offline_table(schema, replication=replication)
    rows = random_rows(schema, 260, seed=seed)
    total = 0
    for i in range(num_segments):
        # skewed sizes: the stabilizer's doc-weighted placement is what
        # keeps re-replication balanced under this skew
        n = 30 + 45 * (i % 5)
        cluster.upload(physical, build_segment(schema, rows[:n], physical, f"seg{i}"))
        total += n
    return cluster, physical, total


def _replication_state(cluster, physical: str, excluded=()) -> Dict[str, Any]:
    res = cluster.controller.resources
    ideal = res.get_ideal_state(physical)
    sizes = sorted({len(r) for r in ideal.values()}) if ideal else []
    return {
        "segments": len(ideal),
        "replicaSetSizes": sizes,
        "onExcluded": sum(
            1 for r in ideal.values() if any(s in r for s in excluded)
        ),
        "viewConverged": res.get_external_view(physical) == ideal,
    }


def run_kill_server_scenario(
    num_servers: int = 3, replication: int = 2, num_segments: int = 6,
    clients: int = 3, rounds: int = 2, victim: str = "server0",
    data_dir: Optional[str] = None,
) -> Dict[str, Any]:
    """Kill one server under closed-loop load: zero failed queries
    (replica failover absorbs the loss), full replication restored by
    the stabilizer within ``rounds`` rounds, dead replicas dropped."""
    cluster, physical, total = _build_scenario_cluster(
        num_servers, replication, num_segments, data_dir
    )
    try:
        load = ClosedLoopLoad(
            cluster, "SELECT count(*) FROM testTable", total, clients
        ).start()
        time.sleep(0.15)  # warm: some queries complete pre-fault
        # kill: data plane goes dark, then the control plane declares the
        # death (the heartbeat-expiry path calls the same liveness flip)
        cluster.transport.set_down((victim, 0))
        cluster.controller.resources.set_instance_alive(victim, False)
        for _ in range(rounds):
            cluster.controller.stabilizer.run_once()
        time.sleep(0.15)  # healed steady state under load
        summary = load.stop()
        state = _replication_state(cluster, physical, excluded=[victim])
        final = cluster.query("SELECT count(*) FROM testTable")
        want = min(replication, num_servers - 1)
        return {
            "scenario": "kill-server",
            "victim": victim,
            "rounds": rounds,
            **summary,
            **state,
            "replicationRestored": state["replicaSetSizes"] == [want]
            and state["onExcluded"] == 0,
            "finalDocs": final.num_docs_scanned,
            "expectedDocs": total,
            "finalComplete": not final.partial_response and not final.exceptions,
            "stabilizer": cluster.controller.stabilizer.metrics.snapshot()["meters"],
        }
    finally:
        cluster.stop()


def _drain_one(cluster, name: str, max_rounds: int = 6) -> int:
    """Drain ``name`` and run stabilizer rounds until its replicas are
    fully migrated; returns rounds used."""
    cluster.controller.drain_instance(name)
    used = 0
    while used < max_rounds:
        if cluster.controller.drain_status(name)["drained"]:
            break
        cluster.controller.stabilizer.run_once()
        used += 1
    return used


def run_drain_scenario(
    num_servers: int = 3, replication: int = 2, num_segments: int = 6,
    clients: int = 3, victim: str = "server0", data_dir: Optional[str] = None,
) -> Dict[str, Any]:
    """Drain one server under load: new routing stops covering it, the
    stabilizer migrates every replica off, the drain endpoint reports
    drained, and no query fails along the way."""
    cluster, physical, total = _build_scenario_cluster(
        num_servers, replication, num_segments, data_dir
    )
    try:
        load = ClosedLoopLoad(
            cluster, "SELECT count(*) FROM testTable", total, clients
        ).start()
        time.sleep(0.15)
        rounds = _drain_one(cluster, victim)
        status = cluster.controller.drain_status(victim)
        time.sleep(0.15)
        summary = load.stop()
        state = _replication_state(cluster, physical, excluded=[victim])
        final = cluster.query("SELECT count(*) FROM testTable")
        return {
            "scenario": "drain",
            "victim": victim,
            "roundsToDrain": rounds,
            "drainStatus": {k: status[k] for k in ("draining", "remainingSegments", "drained")},
            **summary,
            **state,
            "finalDocs": final.num_docs_scanned,
            "expectedDocs": total,
            "finalComplete": not final.partial_response and not final.exceptions,
        }
    finally:
        cluster.stop()


def run_rolling_restart_scenario(
    num_servers: int = 3, replication: int = 2, num_segments: int = 6,
    clients: int = 3, data_dir: Optional[str] = None,
) -> Dict[str, Any]:
    """Rolling restart of EVERY server under load, one at a time:
    drain -> (replicas migrate) -> restart (down+dead, then back) ->
    undrain -> next.  Zero failed queries, zero permanent segment loss."""
    cluster, physical, total = _build_scenario_cluster(
        num_servers, replication, num_segments, data_dir
    )
    res = cluster.controller.resources
    try:
        load = ClosedLoopLoad(
            cluster, "SELECT count(*) FROM testTable", total, clients
        ).start()
        time.sleep(0.1)
        rounds_per_server: Dict[str, int] = {}
        for server in [s.name for s in cluster.servers]:
            rounds_per_server[server] = _drain_one(cluster, server)
            assert cluster.controller.drain_status(server)["drained"], server
            # "restart": the process goes away (data plane down, death
            # declared) and comes back — it holds nothing, so this is
            # invisible to queries
            cluster.transport.set_down((server, 0))
            res.set_instance_alive(server, False)
            cluster.transport.set_down((server, 0), False)
            res.set_instance_alive(server, True)
            cluster.controller.undrain_instance(server)
            cluster.controller.stabilizer.run_once()
        time.sleep(0.1)
        summary = load.stop()
        state = _replication_state(cluster, physical)
        final = cluster.query("SELECT count(*) FROM testTable")
        return {
            "scenario": "rolling-restart",
            "roundsPerServer": rounds_per_server,
            **summary,
            **state,
            "noSegmentLoss": state["replicaSetSizes"] == [replication]
            and final.num_docs_scanned == total
            and not final.partial_response,
            "finalDocs": final.num_docs_scanned,
            "expectedDocs": total,
        }
    finally:
        cluster.stop()


def _mirror_warming(cluster) -> None:
    """In-process stand-in for the networked heartbeat readiness feed:
    copy each live server's ``prewarm.warming`` flag into the
    controller's InstanceState (what the stabilizer's trim gate
    consults) and the broker's health tracker (what routing
    deprioritizes on).  The networked starter does exactly this on
    every heartbeat; scenarios that drive stabilizer rounds explicitly
    mirror explicitly."""
    res = cluster.controller.resources
    for s in cluster.servers:
        w = bool(s.prewarm.warming)
        res.set_instance_warming(s.name, w)
        cluster.broker.health.set_warming(s.name, w)


def run_rolling_restart_warm_scenario(
    num_servers: int = 3, replication: int = 2, num_segments: int = 6,
    clients: int = 1, data_dir: Optional[str] = None,
    cache_dir: Optional[str] = None,
    steady_s: float = 0.7,
    prewarm_timeout_s: float = 10.0,
    p99_multiple: float = 8.0, p99_floor_ms: float = 150.0,
    max_rounds: int = 120,
) -> Dict[str, Any]:
    """Rolling restart with WARM starts (ISSUE 16): every server is
    drained, killed, and replaced by a genuinely fresh process image
    (new ``ServerInstance`` — empty lane compile registries) sharing
    only the persistent compile cache, while a closed-loop workload
    replays the steady query mix.

    Proves the full warm-start story end to end:

    - ZERO failed queries across the whole roll;
    - ``compile.cold == 0`` on every restarted server — the steady
      phase recorded each plan digest in the persistent ledger, so the
      restarts' first launches classify ``persistentHit``/``prewarmed``,
      never cold;
    - the stabilizer's movement waits for warming destinations: drain
      drops and rebalance phase-2 trims defer while the receiving
      server prewarms (``rebalanceTrimDeferred`` in the event ring),
      and complete once it reports ready;
    - prewarming never enters a serving lane: the lane watchdog/stall
      counters on restarted servers stay zero;
    - roll-phase p99 stays bounded vs the steady baseline.

    ``clients=1`` by default: a sequential replay keeps the plan-shape
    set exactly equal to the steady phase's (no micro-batched combo
    shapes appearing for the first time mid-roll), which is what makes
    the ``compile.cold == 0`` bar deterministic.
    """
    from pinot_tpu.engine import compilecache

    prev_root = compilecache.cache_root()
    # a cache of the scenario's own, so its steady phase starts cold
    compilecache.configure_jax_cache(
        cache_dir or compilecache.wiped_subroot("rolling_restart_warm")
    )
    cluster, physical, total = _build_scenario_cluster(
        num_servers, replication, num_segments, data_dir
    )
    res = cluster.controller.resources
    stab = cluster.controller.stabilizer
    stab.prewarm_timeout_s = prewarm_timeout_s
    stab.rebalance_hysteresis = 1  # rounds are driven explicitly here
    restarted: List[str] = []
    try:
        # fleet workload feed: in-process, the broker's own plan-stat
        # registry IS the fleet roll-up the controller would serve
        def workload_source(tables, n):
            return cluster.broker.workload_snapshot(top=n, tables=tables)[
                "topByCount"
            ]

        for s in cluster.servers:
            s.prewarm.workload_source = workload_source
            s.prewarm.timeout_s = prewarm_timeout_s

        pql = "SELECT sum(metInt), count(*) FROM testTable GROUP BY dimStr TOP 5"
        count_pql = "SELECT count(*) FROM testTable"
        # warm BOTH shapes the scenario ever issues before measuring:
        # the steady baseline must not include the one-time cold, and
        # every digest a restarted server can see must be in the
        # persistent ledger before the first restart
        for warm_pql in (pql, count_pql):
            r = cluster.broker.handle_pql(warm_pql)
            assert not r.exceptions, r.exceptions
        # steady phase: populates the broker's workload registry (the
        # prewarm feed) AND the persistent plan ledger (via this run's
        # genuine colds) before any restart happens
        steady_load = ClosedLoopLoad(cluster, pql, total, clients).start()
        time.sleep(steady_s)
        steady = steady_load.stop()
        assert steady["failedQueries"] == 0, steady["failures"]

        roll_load = ClosedLoopLoad(cluster, pql, total, clients).start()
        rounds_per_server: Dict[str, int] = {}
        for i in range(len(cluster.servers)):
            old = cluster.servers[i]
            name = old.name
            # drain: replicas migrate off; each destination flips to
            # warming as the moved segments load, so dropping the
            # draining copy is readiness-gated (the deferral events
            # below prove the wait happened)
            cluster.controller.drain_instance(name)
            used = 0
            while used < max_rounds:
                _mirror_warming(cluster)
                stab.run_once()
                used += 1
                if cluster.controller.drain_status(name)["drained"]:
                    break
                time.sleep(0.05)
            assert cluster.controller.drain_status(name)["drained"], name
            rounds_per_server[name] = used
            # restart: the process dies — a FRESH instance (empty
            # compile registries) comes back under the same name with
            # the same persistent cache dir
            cluster.transport.set_down((name, 0))
            res.set_instance_alive(name, False)
            old.shutdown()
            fresh = ServerInstance(name, max_pending=64)
            fresh.prewarm.timeout_s = prewarm_timeout_s
            starter = ServerStarter(fresh, res, workload_source=workload_source)
            starter.start()
            cluster.transport.register((name, 0), fresh.handle_request)
            cluster.transport.set_down((name, 0), False)
            res.set_instance_alive(name, True)
            cluster.controller.undrain_instance(name)
            cluster.servers[i] = fresh
            cluster.server_starters[i] = starter
            restarted.append(name)
            # recovery: proactive rebalance re-homes load onto the
            # empty restart; phase-2 trims wait for it to finish
            # warming before the surplus source copies drop.  The skew
            # bar drops only for this loop — an empty restart is a
            # ~1.5x skew this topology's default bar would tolerate —
            # so the steady phases stay free of rebalance churn
            default_skew = stab.rebalance_skew_ratio
            stab.rebalance_skew_ratio = 1.2
            used = 0
            while used < max_rounds:
                _mirror_warming(cluster)
                stab.run_once()
                _mirror_warming(cluster)
                hosts = any(
                    name in reps
                    for reps in res.get_ideal_state(physical).values()
                )
                if (
                    hosts
                    and not fresh.prewarm.warming
                    and not stab._pending_moves
                ):
                    break
                used += 1
                time.sleep(0.05)
            stab.rebalance_skew_ratio = default_skew
        time.sleep(0.15)  # steady tail under the recovered fleet
        roll = roll_load.stop()

        state = _replication_state(cluster, physical)
        events = stab.events()
        deferrals = [e for e in events if e["event"] == "rebalanceTrimDeferred"]
        timeouts = [e for e in events if e["event"] == "rebalancePrewarmTimeout"]
        per_server: Dict[str, Dict[str, Any]] = {}
        for s in cluster.servers:
            m = s.metrics.snapshot()["meters"]

            def count(name: str) -> int:
                return int(m.get(name, {}).get("count", 0))

            per_server[s.name] = {
                "compileCold": count("compile.cold"),
                "compileWarm": count("compile.warm"),
                "persistentHits": count("compile.persistentHit"),
                "prewarmed": count("compile.prewarmed"),
                "prewarmCompiled": count("prewarm.compiled"),
                "prewarmFailed": count("prewarm.failed"),
                "laneRestarts": count("lane.restarts"),
                "laneDeviceFailures": count("lane.deviceFailures"),
            }
        restarted_stats = [per_server[n] for n in restarted]
        p99_limit = p99_multiple * max(steady["p99Ms"], p99_floor_ms)
        by_class: Dict[str, int] = {}
        for e in deferrals:
            by_class[e["class"]] = by_class.get(e["class"], 0) + 1
        final = cluster.query(count_pql)
        return {
            "scenario": "rolling-restart-warm",
            "cacheDir": cache_dir,
            "roundsPerServer": rounds_per_server,
            "restarted": restarted,
            "steady": steady,
            **roll,
            **state,
            "servers": per_server,
            "coldCompilesOnRestarted": sum(
                s["compileCold"] for s in restarted_stats
            ),
            "warmStartsOnRestarted": sum(
                s["persistentHits"] + s["prewarmed"] for s in restarted_stats
            ),
            "laneWatchdogClean": all(
                s["laneRestarts"] == 0 and s["laneDeviceFailures"] == 0
                for s in restarted_stats
            ),
            "trimDeferrals": len(deferrals),
            "trimDeferralsByClass": by_class,
            "trimDeferralSample": deferrals[:3],
            "prewarmTimeouts": len(timeouts),
            "prewarmDeferralMeter": int(
                stab.metrics.snapshot()["meters"]
                .get("rebalance.prewarmDeferrals", {})
                .get("count", 0)
            ),
            "steadyP99Ms": steady["p99Ms"],
            "rollP99Ms": roll["p99Ms"],
            "p99LimitMs": round(p99_limit, 3),
            "p99Bounded": roll["p99Ms"] <= p99_limit,
            "finalDocs": final.num_docs_scanned,
            "expectedDocs": total,
            "finalComplete": not final.partial_response and not final.exceptions,
            "noSegmentLoss": state["replicaSetSizes"] == [replication]
            and final.num_docs_scanned == total
            and not final.partial_response,
        }
    finally:
        for s in cluster.servers:
            s.prewarm.stop()
        cluster.stop()
        compilecache.configure_jax_cache(prev_root)


# ---------------------------------------------------------------------------
# Overload-protection scenarios (ISSUE 7): multi-tenant noisy neighbor
# and ingest backpressure — shared by the CLI and tests/test_overload.py.
# ---------------------------------------------------------------------------


def _tenant_schema(name: str):
    from pinot_tpu.tools.datagen import make_test_schema

    schema = make_test_schema(with_mv=False)
    schema.schema_name = name
    return schema


def run_noisy_neighbor_scenario(
    num_servers: int = 2,
    replication: int = 1,
    num_segments: int = 3,
    clients: int = 3,
    flood_clients: int = 4,
    quota_qps: float = 8.0,
    baseline_s: float = 1.0,
    flood_s: float = 2.5,
    max_pending: int = 16,
    data_dir: Optional[str] = None,
    p99_floor_ms: float = 25.0,
    p99_multiple: float = 3.0,
) -> Dict[str, Any]:
    """Tenant A floods its table while tenant B runs a steady closed
    loop.  The overload plane must contain A end to end:

    - tenant B suffers ZERO failed queries and its p99 stays within a
      fixed multiple of its unloaded baseline (measured first);
    - tenant A's overflow is shed with TYPED errors (429 at the broker
      admission tiers, 210 at the server fair-share scheduler) — never
      client-visible timeouts;
    - the quota lands through the LIVE update path
      (``update_table_quota``), as a production operator would apply it.
    """
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.tools.datagen import random_rows

    cluster = InProcessCluster(
        num_servers=num_servers, data_dir=data_dir, max_pending=max_pending
    )
    try:
        totals: Dict[str, int] = {}
        physicals: Dict[str, str] = {}
        for tenant in ("tenantA", "tenantB"):
            schema = _tenant_schema(tenant)
            physical = cluster.add_offline_table(schema, replication=replication)
            physicals[tenant] = physical
            rows = random_rows(schema, 240, seed=7)
            total = 0
            for i in range(num_segments):
                n = 40 + 30 * (i % 3)
                cluster.upload(
                    physical, build_segment(schema, rows[:n], physical, f"{tenant}s{i}")
                )
                total += n
            totals[tenant] = total

        pql_a = "SELECT count(*) FROM tenantA"
        pql_b = "SELECT count(*) FROM tenantB"
        # warm both paths (staging + plan build) before measuring
        for pql in (pql_a, pql_b):
            r = cluster.broker.handle_pql(pql)
            assert not r.exceptions, r.exceptions

        # phase 1: tenant B's unloaded baseline
        base_load = ClosedLoopLoad(cluster, pql_b, totals["tenantB"], clients).start()
        time.sleep(baseline_s)
        baseline = base_load.stop()

        # phase 2: quota lands on tenant A through the LIVE update path
        cluster.controller.resources.update_table_quota(
            physicals["tenantA"], quota_qps
        )

        # phase 3: A floods (open throttle, >> 10x quota offered) while
        # B keeps its steady closed loop
        b_load = ClosedLoopLoad(cluster, pql_b, totals["tenantB"], clients).start()
        a_flood = FloodLoad(cluster, pql_a, clients=flood_clients).start()
        time.sleep(flood_s)
        a_summary = a_flood.stop()
        b_summary = b_load.stop()

        baseline_p99 = baseline["p99Ms"]
        loaded_p99 = b_summary["p99Ms"]
        # absolute floor absorbs scheduler jitter on a near-zero
        # baseline: 3x of 2ms is not a meaningful isolation bar.
        # Callers on CPU-starved boxes (the 2-core CI container under
        # full-suite load) widen floor/multiple rather than compare
        # wall clock against a baseline measured in a quieter window.
        p99_limit = p99_multiple * max(baseline_p99, p99_floor_ms)
        offered_qps = a_summary["queries"] / max(flood_s, 1e-9)
        return {
            "scenario": "noisy-neighbor",
            "quotaQps": quota_qps,
            "offeredQpsA": round(offered_qps, 1),
            "offeredMultiple": round(offered_qps / quota_qps, 1),
            "tenantA": a_summary,
            "tenantB": b_summary,
            "tenantBBaseline": baseline,
            "tenantBLoadedP99Ms": loaded_p99,
            "tenantBP99LimitMs": round(p99_limit, 3),
            "tenantBP99Within": loaded_p99 <= p99_limit,
            "sheddingTyped": a_summary["timeouts"] == 0
            and a_summary["otherFailures"] == 0,
            "admission": cluster.broker.admission.snapshot(),
            "scheduler": {
                s.name: s.scheduler.stats() for s in cluster.servers
            },
            # main()'s exit-code contract: any tenant-B failure OR any
            # untyped tenant-A overflow fails the scenario
            "failedQueries": b_summary["failedQueries"]
            + a_summary["timeouts"]
            + a_summary["otherFailures"],
        }
    finally:
        cluster.stop()


def run_join_under_flood_scenario(
    num_servers: int = 2,
    replication: int = 1,
    clients: int = 3,
    flood_clients: int = 4,
    quota_qps: float = 8.0,
    baseline_s: float = 1.0,
    flood_s: float = 2.5,
    max_pending: int = 16,
    data_dir: Optional[str] = None,
    p99_floor_ms: float = 25.0,
    p99_multiple: float = 3.0,
) -> Dict[str, Any]:
    """ISSUE 14 chaos: tenant A floods two-table JOINs at >>10x its
    quota while tenant B runs steady scans.  Joins fan out into
    multi-phase scatter traffic (extracts + exchange), so this proves
    the join plane rides the overload machinery end to end:

    - the broker admission front door sheds A's overflow BEFORE any
      join phase scatters (429s, typed);
    - the phase requests that do run queue under tenant A's tables in
      the server fair-share scheduler, so B's p99 holds within a fixed
      multiple of its unloaded baseline;
    - tenant B suffers ZERO failed queries.
    """
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.tools.datagen import random_rows

    cluster = InProcessCluster(
        num_servers=num_servers, data_dir=data_dir, max_pending=max_pending
    )
    try:
        from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema

        fact_schema = Schema(
            "aFact",
            dimensions=[FieldSpec("k", DataType.INT, FieldType.DIMENSION)],
            metrics=[FieldSpec("v", DataType.INT, FieldType.METRIC)],
        )
        dim_schema = Schema(
            "aDim",
            dimensions=[FieldSpec("k", DataType.INT, FieldType.DIMENSION)],
            metrics=[FieldSpec("w", DataType.INT, FieldType.METRIC)],
        )
        fact_phys = cluster.add_offline_table(fact_schema, replication=replication)
        dim_phys = cluster.add_offline_table(dim_schema, replication=replication)
        import numpy as _np

        rng = _np.random.default_rng(11)
        for i in range(2):
            frows = [
                {"k": int(k), "v": int(v)}
                for k, v in zip(rng.integers(0, 60, 150), rng.integers(0, 99, 150))
            ]
            cluster.upload(
                fact_phys, build_segment(fact_schema, frows, fact_phys, f"aFact_s{i}")
            )
        cluster.upload(
            dim_phys,
            build_segment(
                dim_schema,
                [{"k": k, "w": k * 2} for k in range(60)],
                dim_phys,
                "aDim_s0",
            ),
        )
        schema_b = _tenant_schema("tenantB")
        phys_b = cluster.add_offline_table(schema_b, replication=replication)
        rows_b = random_rows(schema_b, 240, seed=7)
        total_b = 0
        for i in range(3):
            n = 40 + 30 * (i % 3)
            cluster.upload(
                phys_b, build_segment(schema_b, rows_b[:n], phys_b, f"tenantBs{i}")
            )
            total_b += n

        pql_join = "SELECT count(*), sum(f.v) FROM aFact f JOIN aDim d ON f.k = d.k"
        pql_b = "SELECT count(*) FROM tenantB"
        for pql in (pql_join, pql_b):
            r = cluster.broker.handle_pql(pql)
            assert not r.exceptions, r.exceptions

        base_load = ClosedLoopLoad(cluster, pql_b, total_b, clients).start()
        time.sleep(baseline_s)
        baseline = base_load.stop()

        # quota lands on the join's LEFT table through the live path —
        # the broker admission front door keys joins on it
        cluster.controller.resources.update_table_quota(fact_phys, quota_qps)

        b_load = ClosedLoopLoad(cluster, pql_b, total_b, clients).start()
        a_flood = FloodLoad(cluster, pql_join, clients=flood_clients).start()
        time.sleep(flood_s)
        a_summary = a_flood.stop()
        b_summary = b_load.stop()

        baseline_p99 = baseline["p99Ms"]
        loaded_p99 = b_summary["p99Ms"]
        p99_limit = p99_multiple * max(baseline_p99, p99_floor_ms)
        offered_qps = a_summary["queries"] / max(flood_s, 1e-9)
        return {
            "scenario": "join-under-flood",
            "quotaQps": quota_qps,
            "offeredQpsA": round(offered_qps, 1),
            "offeredMultiple": round(offered_qps / quota_qps, 1),
            "tenantA": a_summary,
            "tenantB": b_summary,
            "tenantBBaseline": baseline,
            "tenantBLoadedP99Ms": loaded_p99,
            "tenantBP99LimitMs": round(p99_limit, 3),
            "tenantBP99Within": loaded_p99 <= p99_limit,
            "sheddingTyped": a_summary["timeouts"] == 0
            and a_summary["otherFailures"] == 0,
            "joinMeters": {
                k: v["count"]
                for k, v in cluster.broker.metrics.snapshot()
                .get("meters", {})
                .items()
                if k.startswith("join.")
            },
            "failedQueries": b_summary["failedQueries"]
            + a_summary["timeouts"]
            + a_summary["otherFailures"],
        }
    finally:
        cluster.stop()


def run_ingest_backpressure_scenario(
    rows: int = 400,
    rows_per_segment: int = 1000,
    hbm_high_bytes: float = 256.0,
    data_dir: Optional[str] = None,
) -> Dict[str, Any]:
    """Prove the ingest watermark contract end to end: a consumer
    pauses when the HBM staging ledger crosses the high watermark
    (query-driven staging — the 'query flood squeezes ingest' shape),
    its offset freezes while lag stays visible, and after the pressure
    clears it resumes and drains lag to 0 — no rows lost or skipped."""
    from pinot_tpu.engine.device import LEDGER, clear_staging_cache
    from pinot_tpu.realtime.backpressure import IngestBackpressure
    from pinot_tpu.realtime.llc import make_segment_name
    from pinot_tpu.realtime.stream import MemoryStreamProvider
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.tools.datagen import make_test_schema, random_rows

    clear_staging_cache()  # start from a known-empty ledger
    cluster = InProcessCluster(num_servers=1, data_dir=data_dir)
    try:
        server = cluster.servers[0]
        # tight watermarks wired to the REAL staging ledger, installed
        # BEFORE the consumer exists so it binds to this governor
        server.ingest_backpressure = IngestBackpressure(
            metrics=server.metrics,
            hbm_high_bytes=hbm_high_bytes,
            hbm_low_bytes=hbm_high_bytes / 2.0,
            poll_interval_s=0.0,
        )

        # an offline table whose staging will push the ledger over the
        # high watermark (the query side of the squeeze)
        offline_schema = _tenant_schema("pressure")
        offline_physical = cluster.add_offline_table(offline_schema)
        cluster.upload(
            offline_physical,
            build_segment(
                offline_schema, random_rows(offline_schema, 200, seed=3),
                offline_physical, "p0",
            ),
        )

        rt_schema = _tenant_schema("rtTable")
        stream = MemoryStreamProvider(num_partitions=1)
        physical = cluster.add_realtime_table(
            rt_schema, stream, rows_per_segment=rows_per_segment
        )
        for row in random_rows(rt_schema, rows, seed=5):
            stream.produce(row)
        dm = cluster.controller.realtime_manager.consumers_of(
            make_segment_name(physical, 0, 0)
        )[0]

        # phase 1: unpressured consumption advances
        consumed_free = dm.consume_step(max_rows=100)

        # phase 2: a query stages the offline table's columns -> ledger
        # crosses the high watermark -> the consumer PAUSES (offset
        # frozen).  A group-by aggregation stages forward + dictionary
        # arrays (a bare count(*) would stage only the doc counts).
        cluster.query("SELECT sum(metInt) FROM pressure GROUP BY dimStr TOP 5")
        staged_bytes = LEDGER.total_bytes()
        paused_consumed = dm.consume_step(max_rows=100)
        offset_at_pause = dm.offset
        dm.consume_step(max_rows=100)  # still paused: offset must not move
        paused_state = {
            "paused": server.ingest_backpressure.paused,
            "reason": server.ingest_backpressure.reason,
            "lagWhilePaused": dm.lag(),
            "offsetFrozen": dm.offset == offset_at_pause,
        }

        # phase 3: pressure clears -> resume -> lag drains to 0
        clear_staging_cache()
        drained = 0
        for _ in range(200):
            got = dm.consume_step(max_rows=100)
            drained += got
            if dm.lag() == 0:
                break
        return {
            "scenario": "ingest-backpressure",
            "hbmHighBytes": hbm_high_bytes,
            "stagedBytesAtPause": staged_bytes,
            "consumedBeforePressure": consumed_free,
            "consumedWhilePaused": paused_consumed,
            **paused_state,
            "resumed": not server.ingest_backpressure.paused,
            "consumedAfterResume": drained,
            "finalLag": dm.lag(),
            "governor": server.ingest_backpressure.snapshot(),
            "failedQueries": 0
            if (
                paused_state["offsetFrozen"]
                and paused_consumed == 0
                and dm.lag() == 0
            )
            else 1,
        }
    finally:
        cluster.stop()


# ---------------------------------------------------------------------------
# HBM-pressure scenario (ISSUE 18): addressable staged data ~8x the
# residency HBM cap under closed-loop mixed load — the tiered
# residency manager (engine/residency.py) must keep the hot set
# resident while cold tables cycle HBM <-> host <-> disk, and an
# injected allocation failure must heal by demotion, never by
# poisoning the plan.  Shared by the CLI and
# tests/test_chaos_hbm_pressure.py.
# ---------------------------------------------------------------------------


def run_hbm_pressure_scenario(
    num_tables: int = 10,
    rows_per_table: int = 96,
    clients: int = 3,
    baseline_s: float = 1.0,
    load_s: float = 4.0,
    data_dir: Optional[str] = None,
    seed: int = 421,
) -> Dict[str, Any]:
    """One server hosting ``num_tables`` identical tables whose total
    staged footprint is ~8x the HBM cap the scenario then imposes:

    - a hot table runs a closed loop while a sweeper cycles queries
      over every cold table, forcing continuous demotion (hot tier
      over cap), spill (warm tier over host cap) and promotion (cold
      tables re-queried) — the counters must prove all three tiers
      cycled, with ZERO failed queries and byte-exact counts;
    - the hot set stays protected: its p99 under pressure is compared
      against its own uncapped baseline (heat scoring must keep the
      closed-loop table out of the victim pool);
    - a seeded allocation failure (``DeviceFaultInjector
      .alloc_fail_next``) lands on a hot query mid-pressure: the
      executor must classify RESOURCE_EXHAUSTED, demote, retry and
      answer correctly — ``heal.resourceExhausted`` marks, nothing is
      poisoned, no host failover.

    Caps are measured, not assumed: the per-table footprint comes from
    the staging ledger delta of the first stage, so the scenario holds
    its ~8x oversubscription on any platform/dtype.
    """
    from pinot_tpu.common.faults import DeviceFaultInjector
    from pinot_tpu.engine.device import LEDGER, clear_staging_cache
    from pinot_tpu.engine.residency import RESIDENCY
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.tools.datagen import random_rows

    saved_env = {
        k: os.environ.get(k)
        for k in ("PINOT_TPU_HBM_CAP_BYTES", "PINOT_TPU_HOST_CAP_BYTES")
    }
    clear_staging_cache()  # measured footprints start from zero
    cluster = InProcessCluster(num_servers=1, data_dir=data_dir)
    try:
        names = [f"tierT{i}" for i in range(num_tables)]
        totals: Dict[str, int] = {}
        for name in names:
            schema = _tenant_schema(name)
            physical = cluster.add_offline_table(schema, replication=1)
            rows = random_rows(schema, rows_per_table, seed=seed)
            half = rows_per_table // 2
            cluster.upload(
                physical, build_segment(schema, rows[:half], physical, f"{name}s0")
            )
            cluster.upload(
                physical, build_segment(schema, rows[half:], physical, f"{name}s1")
            )
            totals[name] = rows_per_table

        hot = names[0]

        # aggregation over several columns so each table stages a real
        # packed footprint (a bare count(*) stages only the num-docs
        # array and would make the byte caps meaningless)
        def pql_for(name: str) -> str:
            return (
                "SELECT sum(metInt), sum(metFloat), sum(metDouble), "
                f"max(dimInt), max(dimLong) FROM {name} GROUP BY dimStr"
            )

        hot_pql = pql_for(hot)

        # measure the per-table staged footprint off the first stage's
        # ledger delta, then warm every table so "addressable" is the
        # real uncapped total
        before = LEDGER.total_bytes()
        r = cluster.broker.handle_pql(hot_pql)
        assert not r.exceptions, r.exceptions
        table_bytes = max(1, int(LEDGER.total_bytes() - before))
        for name in names[1:]:
            r = cluster.broker.handle_pql(pql_for(name))
            assert not r.exceptions, r.exceptions
        addressable = int(LEDGER.total_bytes())

        # phase 1: the hot table's UNCAPPED baseline
        base = ClosedLoopLoad(cluster, hot_pql, totals[hot], clients).start()
        time.sleep(baseline_s)
        baseline = base.stop()

        # phase 2: impose the caps — hot tier fits ~1.25 tables
        # (addressable/cap ~= 8x for the default 10 tables), warm tier
        # ~2.5 more, the rest lives on disk
        cap = max(1, int(table_bytes * num_tables / 8.0))
        os.environ["PINOT_TPU_HBM_CAP_BYTES"] = str(cap)
        os.environ["PINOT_TPU_HOST_CAP_BYTES"] = str(int(table_bytes * 2.5))
        # apply the new cap to the already-resident set (enforcement
        # otherwise runs on staging inserts, and everything is cached):
        # the operator's cap change takes effect immediately
        RESIDENCY.enforce()
        counters0 = {
            n: RESIDENCY.counter(n)
            for n in ("demotions", "promotions", "coldDemotions", "coldLoads")
        }

        # phase 3: hot closed loop + cold-table sweeper, concurrently
        stop = threading.Event()
        sweep_errors: List[str] = []
        sweeps = [0]

        def sweeper() -> None:
            i = 0
            while not stop.is_set():
                name = names[1 + (i % (num_tables - 1))]
                i += 1
                try:
                    resp = cluster.broker.handle_pql(pql_for(name))
                except Exception as e:
                    sweep_errors.append(f"{name}: {type(e).__name__}: {e}")
                    continue
                sweeps[0] += 1
                if resp.exceptions or resp.num_docs_scanned != totals[name]:
                    if len(sweep_errors) < 8:
                        sweep_errors.append(
                            f"{name}: docs={resp.num_docs_scanned}/{totals[name]} "
                            f"exc={[e.message for e in resp.exceptions][:2]}"
                        )

        hot_load = ClosedLoopLoad(cluster, hot_pql, totals[hot], clients).start()
        sweep_thread = threading.Thread(target=sweeper, daemon=True)
        sweep_thread.start()
        time.sleep(load_s)
        stop.set()
        hot_summary = hot_load.stop()
        sweep_thread.join(timeout=10)

        # phase 4: seeded allocation failure on a hot query, still
        # under pressure — must heal by demotion, never poison
        server = cluster.servers[0]
        inj = DeviceFaultInjector(seed=seed)
        lanes = server.lanes.lanes if server.lanes is not None else []
        for lane in lanes:
            lane.fault_injector = inj
        heal_before = dict(server.executor.healing_stats())
        inj.alloc_fail_next(1)
        try:
            resp = cluster.broker.handle_pql(hot_pql)
        finally:
            for lane in lanes:
                lane.fault_injector = None
        heal_after = dict(server.executor.healing_stats())
        oom_healed = (
            not resp.exceptions
            and resp.num_docs_scanned == totals[hot]
            and heal_after["resourceExhausted"]
            > heal_before["resourceExhausted"]
            and heal_after["hostFailovers"] == heal_before["hostFailovers"]
            and heal_after["poisonedPlans"] == 0
        )

        deltas = {
            n: RESIDENCY.counter(n) - counters0[n] for n in counters0
        }
        hot_p99 = hot_summary["p99Ms"]
        base_p99 = baseline["p99Ms"]
        failed = (
            hot_summary["failedQueries"]
            + len(sweep_errors)
            + (0 if oom_healed else 1)
        )
        return {
            "scenario": "hbm-pressure",
            "addressable_over_cap": round(addressable / cap, 3),
            "num_tables": num_tables,
            "tableBytes": table_bytes,
            "addressableBytes": addressable,
            "hbmCapBytes": cap,
            "hot_p99_ms": hot_p99,
            "baseline_p99_ms": base_p99,
            "demotions": deltas["demotions"],
            "promotions": deltas["promotions"],
            "cold_demotions": deltas["coldDemotions"],
            "cold_loads": deltas["coldLoads"],
            "coldSweeps": sweeps[0],
            "hotLoad": hot_summary,
            "hotBaseline": baseline,
            "sweepErrors": sweep_errors,
            "oomHealed": oom_healed,
            "selfHealing": heal_after,
            "residency": RESIDENCY.snapshot(),
            "failedQueries": failed,
        }
    finally:
        cluster.stop()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        clear_staging_cache()  # cap-era residue must not leak to callers


# ---------------------------------------------------------------------------
# Audit-divergence scenario (ISSUE 19): a seeded fault injector makes
# one serving tier return silently-wrong aggregates under closed-loop
# load — the shadow differential auditor must catch it, quarantine the
# (plan digest, tier), and the cluster must keep answering byte-
# correctly (served off the quarantined tier) with ZERO failed queries.
# Shared by the CLI and tests/test_audit.py.
# ---------------------------------------------------------------------------


def run_audit_divergence_scenario(
    num_segments: int = 2,
    rows: int = 96,
    clients: int = 2,
    load_s: float = 2.0,
    detect_budget_s: float = 12.0,
    corrupt_n: int = 3,
    data_dir: Optional[str] = None,
    seed: int = 1907,
) -> Dict[str, Any]:
    """One server, one offline table, a closed query loop — and a
    seeded ``DeviceFaultInjector.corrupt_results`` that perturbs the
    next ``corrupt_n`` served aggregates on whatever non-host tier
    answers.  The corruption raises no exception, so the self-healing
    ladder (PR 3) can never see it: only the shadow differential
    auditor can.  Acceptance:

    - the divergence is DETECTED (``audit.divergences``) within
      ``detect_budget_s`` and the (plan digest, tier) is quarantined;
    - every query after the quarantine is byte-identical (accounting
      stripped) to the pre-corruption reference — the quarantined tier
      is steered around, not retried;
    - zero failed queries: the wrong answers themselves complete
      without exceptions (that is the point), and nothing else breaks.
    """
    from pinot_tpu.common.faults import DeviceFaultInjector
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.tools.datagen import random_rows
    from pinot_tpu.utils.audit import (
        SamplerBudget,
        payloads_equivalent,
        strip_accounting,
    )

    # sample every completed query with an effectively-unmetered private
    # budget so detection latency measures the audit loop, not the
    # sampler (the process-wide default budget stays untouched)
    saved_env = {
        k: os.environ.get(k) for k in ("PINOT_TPU_AUDIT_SAMPLE_N",)
    }
    os.environ["PINOT_TPU_AUDIT_SAMPLE_N"] = "1"
    cluster = InProcessCluster(num_servers=1, data_dir=data_dir)
    inj = DeviceFaultInjector(seed=seed)
    server = cluster.servers[0]
    server.auditor.budget = SamplerBudget(per_s=1000.0, burst=64.0)
    lanes = server.lanes.lanes if server.lanes is not None else []
    try:
        schema = _tenant_schema("auditT")
        physical = cluster.add_offline_table(schema, replication=1)
        all_rows = random_rows(schema, rows, seed=seed)
        per = max(1, rows // num_segments)
        for i in range(num_segments):
            chunk = all_rows[i * per:(i + 1) * per] or all_rows[-per:]
            cluster.upload(
                physical, build_segment(schema, chunk, physical, f"audits{i}")
            )
        pql = (
            "SELECT sum(metInt), sum(metFloat), max(dimInt) "
            "FROM auditT GROUP BY dimStr"
        )

        # pre-corruption reference payload (accounting stripped — the
        # same strip the auditor itself compares under)
        ref_resp = cluster.broker.handle_pql(pql)
        assert not ref_resp.exceptions, ref_resp.exceptions
        reference = strip_accounting(ref_resp.to_json())
        expected_docs = ref_resp.num_docs_scanned

        load = ClosedLoopLoad(cluster, pql, expected_docs, clients).start()
        time.sleep(min(0.5, load_s))  # steady state before the fault

        for lane in lanes:
            lane.fault_injector = inj
        if not lanes and server.executor.lane is not None:
            server.executor.lane.fault_injector = inj
        # delta sized to dominate the auditor's float32-accumulation
        # tolerance band on these group sums by orders of magnitude — a
        # "wrong answer" here must be unambiguously wrong, not a rounding
        # argument (payloads_equivalent rel_tol is 5e-4)
        inj.corrupt_results(n=corrupt_n, delta=100.0)
        armed_at = time.monotonic()

        # wait for the audit plane to catch it
        detected_s: Optional[float] = None
        quarantined: List[Dict[str, Any]] = []
        while time.monotonic() - armed_at < detect_budget_s:
            quarantined = server.executor.audit_quarantined_snapshot()
            if quarantined:
                detected_s = time.monotonic() - armed_at
                break
            time.sleep(0.05)
        inj.heal()  # unfired corruption budget must not leak forward

        time.sleep(min(1.0, load_s))  # post-quarantine serving window
        summary = load.stop()

        # correctness after quarantine: repeated answers must be
        # byte-identical to EACH OTHER (one tier serves now — no
        # flapping) and equivalent to the pre-corruption reference
        # (float32-vs-float64 accumulation tolerance only; the injected
        # delta is orders of magnitude larger)
        post_mismatches = 0
        post_baseline = None
        for _ in range(8):
            resp = cluster.broker.handle_pql(pql)
            payload = strip_accounting(resp.to_json())
            if post_baseline is None:
                post_baseline = payload
            if (
                resp.exceptions
                or payload != post_baseline
                or not payloads_equivalent(payload, reference)
            ):
                post_mismatches += 1
        audit_snap = server.auditor.snapshot()
        heal = server.executor.healing_stats()
        divergences = audit_snap["divergences"]
        recent = audit_snap.get("recentDivergences") or []
        detect_ms = max((d.get("detectMs") or 0.0) for d in recent) if recent else None

        failed = (
            summary["failedQueries"]
            + (0 if detected_s is not None else 1)
            + post_mismatches
        )
        return {
            "scenario": "audit-divergence",
            "detected": detected_s is not None,
            "detectWallS": round(detected_s, 3) if detected_s is not None else None,
            "detectMs": detect_ms,
            "divergences": divergences,
            "quarantined": quarantined,
            "auditTierSkips": heal.get("auditTierSkips", 0),
            "postQuarantineMismatches": post_mismatches,
            "load": summary,
            "audit": audit_snap,
            "failedQueries": failed,
        }
    finally:
        for lane in lanes:
            lane.fault_injector = None
        if server.executor.lane is not None:
            server.executor.lane.fault_injector = None
        cluster.stop()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ---------------------------------------------------------------------------
# Elastic-fleet scenario (ISSUE 15): 100+ tables under mixed
# ingest+query closed-loop load, a forced hot-tenant skew, a live
# make-before-break rebalance, and a mid-rebalance controller restart.
# Shared by the CLI and tests/test_elastic_fleet.py.
# ---------------------------------------------------------------------------


def _fleet_loads_by_server(res, tables) -> Dict[str, float]:
    """Doc-weighted ideal-state load per server (the scenario's own
    balance check — deliberately independent of the planner's)."""
    load: Dict[str, float] = {}
    for table in tables:
        for seg, replicas in res.get_ideal_state(table).items():
            info = res.get_segment_metadata(table, seg)
            meta = info.get("metadata") if info else None
            docs = max(1, int(getattr(meta, "num_docs", 0) or 0))
            for s in replicas:
                load[s] = load.get(s, 0.0) + docs
    return load


def run_elastic_fleet_scenario(
    num_tables: int = 104,
    num_servers: int = 3,
    clients: int = 3,
    hot_segments: int = 6,
    hot_docs: int = 400,
    fleet_docs: int = 20,
    rt_tables: int = 2,
    rt_partitions: int = 2,
    rows_per_segment: int = 40,
    rt_segments_per_partition: int = 2,
    pool_workers: int = 4,
    max_rounds: int = 40,
    data_dir: Optional[str] = None,
) -> Dict[str, Any]:
    """The elastic-fleet chaos acceptance (ISSUE 15), end to end:

    1. **breadth** — ``num_tables`` tables on ``num_servers`` servers:
       mostly tiny offline tables (the 100-tenant fleet), plus
       ``rt_tables`` REALTIME tables whose partitions are consumed by
       the shared ``IngestConsumerPool`` (partition-parallel ingest);
    2. **mixed load** — closed-loop query clients over a fleet table,
       the hot table, and a live realtime table WHILE ingest runs;
    3. **forced skew** — ``hot_segments`` doc-heavy segments pinned
       onto server0 plus a cost-rate hint naming the hot table, so the
       stabilizer's skew evaluation must trip;
    4. **live rebalance** — the planner's make-before-break moves run
       under load; every round asserts no segment ever loses its last
       serving replica (coverage is checked against the external view,
       not hoped for);
    5. **mid-rebalance controller restart** — with moves still pending,
       the controller is torn down and a NEW incarnation recovers from
       the property store; servers and the broker re-wire to it and its
       stabilizer completes the remaining moves from DERIVED state.

    Acceptance: zero failed queries end to end, zero lost/duplicate
    rows (realtime counts exact), exactly one committed copy per
    (partition, sequence), and a final placement whose doc-weighted
    imbalance is back under the skew threshold.
    """
    from pinot_tpu.controller.controller import Controller
    from pinot_tpu.realtime.llc import make_segment_name
    from pinot_tpu.realtime.pool import IngestConsumerPool
    from pinot_tpu.realtime.stream import MemoryStreamProvider
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.server.starter import ServerStarter
    from pinot_tpu.tools.datagen import random_rows

    cluster = InProcessCluster(num_servers=num_servers, data_dir=data_dir)
    ctrl_a = cluster.controller
    res = ctrl_a.resources
    st = ctrl_a.stabilizer
    st.grace_s = 0.0
    # tight knobs so the scenario converges in bounded rounds (defaults
    # are production-paced: ratio 2.0, 3 rounds, 2 moves)
    st.rebalance_skew_ratio = 1.4
    st.rebalance_hysteresis = 2
    st.rebalance_max_moves = 4

    pool_a = IngestConsumerPool(workers=pool_workers, name="elasticA")
    ctrl_a.realtime_manager.ingest_pool = pool_a

    ctrl_b: Optional[Controller] = None
    pool_b: Optional[IngestConsumerPool] = None
    loads: List[ClosedLoopLoad] = []
    try:
        # -- 1. breadth: the 100-table fleet --------------------------
        template = _tenant_schema("fleet0")
        fleet_rows = random_rows(template, fleet_docs, seed=13)
        hot_rows = random_rows(template, hot_docs, seed=14)
        num_offline = num_tables - rt_tables - 1  # -1: the hot table
        fleet_physicals: List[str] = []
        for i in range(num_offline):
            schema = _tenant_schema(f"fleet{i}")
            physical = cluster.add_offline_table(schema, replication=1)
            fleet_physicals.append(physical)
            cluster.upload(
                physical,
                build_segment(schema, fleet_rows, physical, f"fleet{i}s0"),
            )

        # -- realtime tables on the shared consumer pool --------------
        rt_rows_per_partition = rows_per_segment * rt_segments_per_partition
        rt_physicals: List[str] = []
        rt_streams: List[MemoryStreamProvider] = []
        for i in range(rt_tables):
            schema = _tenant_schema(f"rtFleet{i}")
            stream = MemoryStreamProvider(num_partitions=rt_partitions)
            physical = cluster.add_realtime_table(
                schema, stream, rows_per_segment=rows_per_segment
            )
            rt_physicals.append(physical)
            rt_streams.append(stream)
            rows = random_rows(schema, rt_rows_per_partition, seed=20 + i)
            for p in range(rt_partitions):
                for row in rows:
                    stream.produce(row, partition=p)

        # -- forced hot-tenant skew -----------------------------------
        hot_schema = _tenant_schema("hotTable")
        hot_physical = cluster.add_offline_table(hot_schema, replication=1)
        for i in range(hot_segments):
            seg = build_segment(hot_schema, hot_rows, hot_physical, f"hot{i}")
            path = ctrl_a.store.save(hot_physical, seg)
            res.add_segment(
                hot_physical, seg.metadata,
                {"dir": path, "downloadUri": "file://" + os.path.abspath(path)},
                servers=["server0"],
            )
        # the cost axis: the hot table is also the hot QUERY tenant
        # (what /debug/capacity would report once brokers attribute it)
        st.cost_rate_fn = lambda: {"hotTable": 50.0}

        expected_hot = hot_segments * hot_docs
        expected_fleet = fleet_docs
        total_rt = rt_partitions * rt_rows_per_partition

        # -- 2. mixed ingest+query closed-loop load -------------------
        loads = [
            ClosedLoopLoad(
                cluster, "SELECT count(*) FROM hotTable", expected_hot, clients
            ).start(),
            ClosedLoopLoad(
                cluster, "SELECT count(*) FROM fleet0", expected_fleet, 1
            ).start(),
            # live realtime table: any complete answer is correct while
            # ingest advances the count
            ClosedLoopLoad(
                cluster, "SELECT count(*) FROM rtFleet0", None, 1
            ).start(),
        ]
        time.sleep(0.2)

        def coverage_ok(r=None) -> bool:
            """No segment may ever lose its last serving replica (checked
            against whichever controller incarnation owns the round)."""
            r = r or res
            for table in [hot_physical] + fleet_physicals[:3]:
                view = r.get_external_view(table)
                for seg, replicas in r.get_ideal_state(table).items():
                    if not any(
                        view.get(seg, {}).get(s) == "ONLINE" for s in replicas
                    ):
                        return False
            return True

        # -- 4. live rebalance, stopped MID-flight --------------------
        coverage_never_lost = True
        moves_started_at_restart = 0
        rounds_a = 0
        for _ in range(max_rounds):
            st.run_once()
            rounds_a += 1
            coverage_never_lost = coverage_never_lost and coverage_ok()
            moves_started_at_restart = st.metrics.meter(
                "rebalance.movesStarted"
            ).count
            if moves_started_at_restart and st._pending_moves:
                break  # mid-rebalance: phase-1 done, phase-2 pending
            time.sleep(0.02)
        pending_at_restart = len(st._pending_moves)
        surplus_at_restart = sum(
            1
            for table in [hot_physical] + fleet_physicals
            for replicas in res.get_ideal_state(table).values()
            if len(replicas) > 1
        )

        # realtime must be quiescent before the in-process restart (a
        # MEMORY stream's buffered rows die with the manager, so the
        # tip consumer must be empty = everything produced is durable)
        def rt_quiescent() -> bool:
            for physical in rt_physicals:
                ideal = res.get_ideal_state(physical)
                for p in range(rt_partitions):
                    for seq in range(rt_segments_per_partition):
                        seg = ideal.get(make_segment_name(physical, p, seq))
                        if not seg or "ONLINE" not in seg.values():
                            return False
            return True

        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not rt_quiescent():
            time.sleep(0.05)
        rt_committed = rt_quiescent()

        # -- 5. mid-rebalance controller restart ----------------------
        pool_a.stop()
        ctrl_a.stop()
        ctrl_b = Controller(cluster.data_dir)
        ctrl_b.stabilizer.grace_s = 0.0
        ctrl_b.stabilizer.rebalance_skew_ratio = st.rebalance_skew_ratio
        ctrl_b.stabilizer.rebalance_hysteresis = st.rebalance_hysteresis
        ctrl_b.stabilizer.rebalance_max_moves = st.rebalance_max_moves
        ctrl_b.stabilizer.cost_rate_fn = st.cost_rate_fn
        pool_b = IngestConsumerPool(workers=pool_workers, name="elasticB")
        ctrl_b.realtime_manager.ingest_pool = pool_b
        # servers first (their replays refill B's external views), THEN
        # the broker (which re-seeds routing from those views) — the
        # broker serves from its last routing meanwhile, and since
        # make-before-break never dropped a serving replica, no query
        # has anywhere to fail
        for server in cluster.servers:
            ServerStarter(server, ctrl_b.resources).start()
        BrokerStarter(cluster.broker, ctrl_b.resources).start()

        st_b = ctrl_b.stabilizer
        rounds_b = 0
        for _ in range(max_rounds):
            st_b.run_once()
            rounds_b += 1
            coverage_never_lost = coverage_never_lost and coverage_ok(
                ctrl_b.resources
            )
            surplus = sum(
                1
                for table in [hot_physical] + fleet_physicals
                for replicas in ctrl_b.resources.get_ideal_state(table).values()
                if len(replicas) > 1
            )
            if (
                surplus == 0
                and not st_b._pending_moves
                and st_b.metrics.gauge("rebalance.imbalanceRatio").value
                < st_b.rebalance_skew_ratio
            ):
                break
            time.sleep(0.02)
        time.sleep(0.2)
        summaries = [load.stop() for load in loads]
        loads = []

        # -- acceptance accounting ------------------------------------
        res_b = ctrl_b.resources
        final_hot = cluster.query("SELECT count(*) FROM hotTable")
        final_rt = [
            cluster.query(f"SELECT count(*) FROM rtFleet{i}")
            for i in range(rt_tables)
        ]
        rt_counts = [r.num_docs_scanned for r in final_rt]
        # exactly one committed copy per (partition, sequence): the
        # ideal state holds exactly the expected segment names, each
        # committed one with exactly one ONLINE replica
        one_copy_per_seq = True
        for physical in rt_physicals:
            ideal = res_b.get_ideal_state(physical)
            expected_names = set()
            for p in range(rt_partitions):
                for seq in range(rt_segments_per_partition):
                    name = make_segment_name(physical, p, seq)
                    expected_names.add(name)
                    replicas = ideal.get(name, {})
                    if list(replicas.values()).count("ONLINE") != 1:
                        one_copy_per_seq = False
                # the tip consuming segment (one per partition)
                expected_names.add(
                    make_segment_name(physical, p, rt_segments_per_partition)
                )
            if set(ideal) != expected_names:
                one_copy_per_seq = False

        balance = _fleet_loads_by_server(
            res_b, [hot_physical] + fleet_physicals
        )
        mean_load = sum(balance.values()) / max(1, len(balance))
        final_ratio = (
            max(balance.values()) / mean_load if mean_load > 0 else 0.0
        )

        failed = sum(s["failedQueries"] for s in summaries)
        rt_exact = rt_counts == [total_rt] * rt_tables
        ok = (
            failed == 0
            and coverage_never_lost
            and rt_committed
            and rt_exact
            and one_copy_per_seq
            and moves_started_at_restart > 0
            and (pending_at_restart > 0 or surplus_at_restart > 0)
            and final_ratio < st.rebalance_skew_ratio
            and final_hot.num_docs_scanned == expected_hot
            and not final_hot.exceptions
        )
        return {
            "scenario": "elastic-fleet",
            "tables": num_tables,
            "servers": num_servers,
            "load": summaries,
            "queries": sum(s["queries"] for s in summaries),
            "okQueries": sum(s["okQueries"] for s in summaries),
            "partialQueries": sum(s["partialQueries"] for s in summaries),
            "failures": [f for s in summaries for f in s["failures"]],
            "roundsBeforeRestart": rounds_a,
            "roundsAfterRestart": rounds_b,
            "movesStartedBeforeRestart": moves_started_at_restart,
            "pendingMovesAtRestart": pending_at_restart,
            "surplusReplicasAtRestart": surplus_at_restart,
            "movesCompletedAfterRestart": st_b.metrics.meter(
                "rebalance.movesCompleted"
            ).count,
            "coverageNeverLost": coverage_never_lost,
            "rtRowsExpected": total_rt,
            "rtRowsServed": rt_counts,
            "oneCommittedCopyPerSequence": one_copy_per_seq,
            "finalLoadByServer": {k: round(v, 1) for k, v in sorted(balance.items())},
            "finalImbalanceRatio": round(final_ratio, 3),
            "skewRatioThreshold": st.rebalance_skew_ratio,
            "ingestPool": {"a": pool_a.snapshot(), "b": pool_b.snapshot()},
            "failedQueries": 0 if ok else max(1, failed),
        }
    finally:
        for load in loads:
            load.stop()
        pool_a.stop()
        if pool_b is not None:
            pool_b.stop()
        if ctrl_b is not None:
            ctrl_b.stop()
        cluster.stop()


# ---------------------------------------------------------------------------
# Network-partition scenarios (ISSUE 9): controller + servers + broker
# as real HTTP/TCP endpoints in ONE process, every link routed through a
# shared NetworkFaultInjector — the topology where "unreachable" and
# "dead" are different things.  Shared by the CLI and tests/test_partition.py.
# ---------------------------------------------------------------------------


class NetworkedCluster:
    """One-process networked cluster wired for link-level chaos.

    Unlike ``InProcessCluster`` (direct callbacks), every role here
    talks over its real protocol — servers/broker register, heartbeat,
    poll, and scatter over HTTP/TCP — and every link consults one
    seedable ``NetworkFaultInjector``, so a scenario can cut exactly
    the broker->controller poll or exactly the controller->server reply
    direction.  Timing knobs default tight so partition scenarios run
    at tier-1 speed."""

    def __init__(
        self,
        num_servers: int = 3,
        data_dir: Optional[str] = None,
        seed: int = 0,
        lease_s: float = 2.5,
        heartbeat_interval_s: float = 0.2,
        heartbeat_timeout_s: float = 1.2,
        poll_interval_s: float = 0.1,
    ) -> None:
        from pinot_tpu.broker.network_starter import NetworkedBrokerStarter
        from pinot_tpu.common.faults import NetworkFaultInjector
        from pinot_tpu.controller.controller import Controller, ControllerHttpServer
        from pinot_tpu.server.network_starter import NetworkedServerStarter

        self.data_dir = data_dir or tempfile.mkdtemp(prefix="pinot_tpu_netchaos_")
        self.faults = NetworkFaultInjector(seed=seed)
        self.lease_s = lease_s
        # clients (starters + scatter transport) are injector-wired, so
        # the controller's gateway edge must NOT be: wiring both would
        # double-apply delay/error_rate/duplicate on controller links.
        # The gateway hook exists for harnesses that cannot reach the
        # client processes (OS-process chaos rigs).
        self.controller = Controller(self.data_dir, lease_s=lease_s)
        self.controller.gateway.heartbeat_timeout_s = heartbeat_timeout_s
        self.controller.gateway._check_interval_s = max(
            0.05, heartbeat_timeout_s / 4
        )
        self.http = ControllerHttpServer(self.controller)
        self.http.start()
        self.url = f"http://{self.http.host}:{self.http.port}"
        self.server_starters: List[NetworkedServerStarter] = []
        for i in range(num_servers):
            s = NetworkedServerStarter(
                self.url,
                f"srv{i}",
                data_dir=os.path.join(self.data_dir, f"cache{i}"),
                heartbeat_interval_s=heartbeat_interval_s,
                poll_interval_s=poll_interval_s,
                fault_injector=self.faults,
            )
            s.start()
            self.server_starters.append(s)
        self.broker_starter = NetworkedBrokerStarter(
            self.url,
            "brk0",
            heartbeat_interval_s=heartbeat_interval_s,
            poll_interval_s=poll_interval_s,
            fault_injector=self.faults,
        )
        self.broker_starter.start()

    @property
    def broker(self):
        """The broker request handler (ClosedLoopLoad compatibility)."""
        return self.broker_starter.handler

    def server(self, name: str):
        return next(s for s in self.server_starters if s.name == name)

    def query(self, pql: str) -> BrokerResponse:
        return self.broker.handle_pql(pql)

    def wait(self, cond, timeout_s: float = 25.0, what: str = "condition") -> None:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                if cond():
                    return
            except Exception:
                pass
            time.sleep(0.05)
        raise AssertionError(f"timed out waiting for {what}")

    def stop(self) -> None:
        self.faults.heal()  # never leave stop() racing injected cuts
        self.broker_starter.stop()
        for s in self.server_starters:
            s.stop()
            s.server.shutdown()
        self.http.stop()
        self.controller.stop()


def _build_partition_cluster(
    num_servers: int = 3,
    replication: int = 2,
    num_segments: int = 6,
    data_dir: Optional[str] = None,
    seed: int = 5,
    **cluster_kwargs: Any,
):
    """Offline table over a NetworkedCluster, fully converged (every
    replica ONLINE, broker serving the complete count) before any
    weather is injected."""
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.tools.datagen import make_test_schema, random_rows

    cluster = NetworkedCluster(
        num_servers=num_servers, data_dir=data_dir, seed=seed, **cluster_kwargs
    )
    # grace zero: the LEASE window is the guard these scenarios test
    cluster.controller.stabilizer.grace_s = 0.0
    schema = make_test_schema(with_mv=False)
    cluster.controller.add_schema(schema)
    physical = cluster.controller.add_table(
        TableConfig(
            table_name="testTable", table_type="OFFLINE", replication=replication
        )
    )
    rows = random_rows(schema, 260, seed=seed)
    total = 0
    for i in range(num_segments):
        n = 30 + 45 * (i % 5)
        cluster.controller.upload_segment(
            physical, build_segment(schema, rows[:n], physical, f"seg{i}")
        )
        total += n

    res = cluster.controller.resources

    def converged():
        ideal = res.get_ideal_state(physical)
        view = res.get_external_view(physical)
        return (
            len(ideal) == num_segments
            and view == ideal
            and all(len(r) == replication for r in ideal.values())
            and all(
                st == "ONLINE" for r in view.values() for st in r.values()
            )
        )

    cluster.wait(converged, what="all replicas ONLINE")

    def serving():
        r = cluster.query("SELECT count(*) FROM testTable")
        return (
            r.num_docs_scanned == total
            and not r.exceptions
            and not r.partial_response
        )

    cluster.wait(serving, what="broker serving the full count")
    return cluster, physical, total


def run_partition_server_scenario(
    num_servers: int = 3,
    replication: int = 2,
    num_segments: int = 6,
    clients: int = 3,
    lease_s: float = 3.0,
    victim: str = "srv0",
    data_dir: Optional[str] = None,
) -> Dict[str, Any]:
    """Sever one server's controller link (both directions) for longer
    than its lease under closed-loop load:

    - zero failed queries (the broker re-covers via replicas; the
      victim keeps answering in-flight work — it is alive, just
      unreachable from the controller);
    - its replicas move ONLY after the lease window (the stabilizer
      defers while the lease could still be live: leaseDeferrals > 0),
      never on the first missed heartbeat;
    - the victim self-fences (client-side lease expiry) and rides the
      outage visibly (controller.unreachable gauge);
    - on heal it rejoins cleanly: re-admitted, no duplicate replicas.
    """
    cluster, physical, total = _build_partition_cluster(
        num_servers, replication, num_segments, data_dir=data_dir,
        lease_s=lease_s,
    )
    res = cluster.controller.resources
    st = cluster.controller.stabilizer
    vsrv = cluster.server(victim).server
    try:
        load = ClosedLoopLoad(
            cluster, "SELECT count(*) FROM testTable", total, clients
        ).start()
        time.sleep(0.2)  # some queries complete pre-fault

        ideal_pre = res.get_ideal_state(physical)
        cluster.faults.partition(victim, "controller")
        cluster.wait(
            lambda: not res.instances[victim].alive,
            what="controller declaring the victim dead",
        )
        # single-missed-heartbeat point: dead at the gateway, but the
        # lease has NOT expired — a stabilizer round must move NOTHING
        # (the ideal state stays byte-identical, not merely "victim
        # still holds something": a drop+replace in one round would
        # otherwise pass)
        st.run_once()
        ideal_mid = res.get_ideal_state(physical)
        held_through_lease = ideal_mid == ideal_pre
        moved_on_heartbeat = ideal_mid != ideal_pre
        lease_deferrals = st.metrics.meter("stabilizer.leaseDeferrals").count

        # the victim notices on its side: lease expires, gauge flips
        cluster.wait(lambda: not vsrv.lease.held(), what="victim lease expiry")
        cluster.wait(
            lambda: vsrv.metrics.gauge("controller.unreachable").value == 1,
            what="victim unreachable gauge",
        )
        # controller side: wait out the lease window, then re-replicate
        cluster.wait(
            lambda: res.instances[victim].lease_until is not None
            and time.monotonic() >= res.instances[victim].lease_until,
            what="lease window elapsing",
        )
        def victim_dropped() -> bool:
            # make-before-break takes a round to add a replica and a
            # later one to drop the victim's, once the new copy serves:
            # how many rounds that is depends on how fast servers load,
            # so the wait drives the rounds (a fixed four lost the race
            # on a busy host and then waited 25 s for nobody)
            st.run_once()
            return not any(
                victim in r for r in res.get_ideal_state(physical).values()
            )

        cluster.wait(victim_dropped, what="victim replicas dropped after lease expiry")
        cluster.wait(
            lambda: res.get_external_view(physical)
            == res.get_ideal_state(physical)
            and all(
                len(r) == min(replication, num_servers - 1)
                for r in res.get_ideal_state(physical).values()
            ),
            what="re-replication converged",
        )

        # heal: the victim rejoins cleanly
        cluster.faults.heal()
        cluster.wait(
            lambda: res.instances[victim].alive, what="victim re-admitted"
        )
        cluster.wait(lambda: vsrv.lease.held(), what="victim lease renewed")
        st.run_once()
        time.sleep(0.2)
        summary = load.stop()

        ideal = res.get_ideal_state(physical)
        final = cluster.query("SELECT count(*) FROM testTable")
        no_duplicates = all(len(r) <= replication for r in ideal.values())
        return {
            "scenario": "partition-server",
            "victim": victim,
            "leaseSeconds": lease_s,
            **summary,
            "heldThroughLeaseWindow": held_through_lease,
            "movedOnFirstMissedHeartbeat": moved_on_heartbeat,
            "leaseDeferrals": lease_deferrals,
            "victimSelfFenced": True,  # waited on lease.held() == False
            "replicationRestored": all(
                len(r) == min(replication, num_servers - 1)
                for r in ideal.values()
            )
            or all(len(r) == replication for r in ideal.values()),
            "noDuplicateReplicas": no_duplicates,
            "victimReadmitted": res.instances[victim].alive,
            "finalDocs": final.num_docs_scanned,
            "expectedDocs": total,
            "finalComplete": not final.partial_response and not final.exceptions,
            "stabilizer": st.metrics.snapshot()["meters"],
        }
    finally:
        cluster.stop()


def run_partition_controller_scenario(
    num_servers: int = 2,
    replication: int = 2,
    num_segments: int = 4,
    clients: int = 3,
    lease_s: float = 1.2,
    data_dir: Optional[str] = None,
) -> Dict[str, Any]:
    """Sever the controller from EVERY other role: the whole data plane
    rides out the control-plane outage — the broker serves from its
    last versioned snapshot (controller.unreachable=1), servers
    self-fence writes but keep answering queries, the stabilizer moves
    NOTHING (no live target exists), and on heal everyone re-admits
    with the ideal state byte-identical to before the outage."""
    cluster, physical, total = _build_partition_cluster(
        num_servers, replication, num_segments, data_dir=data_dir,
        lease_s=lease_s,
    )
    res = cluster.controller.resources
    st = cluster.controller.stabilizer
    try:
        load = ClosedLoopLoad(
            cluster, "SELECT count(*) FROM testTable", total, clients
        ).start()
        time.sleep(0.2)
        ideal_before = res.get_ideal_state(physical)

        # cut the BROKER first: its last-applied snapshot must be the
        # healthy one (a poll racing the server cuts could otherwise
        # deliver a snapshot that already lists the servers dead)
        cluster.faults.partition("brk0", "controller")
        time.sleep(0.15)
        for s in cluster.server_starters:
            cluster.faults.partition(s.name, "controller")

        cluster.wait(
            lambda: all(
                not res.instances[s.name].alive
                for s in cluster.server_starters
            ),
            what="controller declaring every server dead",
        )
        cluster.wait(
            lambda: cluster.broker.metrics.gauge("controller.unreachable").value
            == 1,
            what="broker unreachable gauge",
        )
        cluster.wait(
            lambda: all(
                not s.server.lease.held() for s in cluster.server_starters
            ),
            what="server leases expiring",
        )
        # stabilizer rounds during the outage: nowhere to move anything
        for _ in range(3):
            st.run_once()
        unchanged_during = res.get_ideal_state(physical) == ideal_before

        cluster.faults.heal()
        cluster.wait(
            lambda: all(
                res.instances[s.name].alive for s in cluster.server_starters
            ),
            what="servers re-admitted",
        )
        cluster.wait(
            lambda: cluster.broker.metrics.gauge("controller.unreachable").value
            == 0,
            what="broker poll recovery",
        )
        cluster.wait(
            lambda: all(
                s.server.lease.held() for s in cluster.server_starters
            ),
            what="leases renewed",
        )
        # recovery is CONVERGED (not just re-admitted) once every
        # replica's ONLINE re-ack has landed: bounded unavailability
        # ends here, and the final query must be complete
        cluster.wait(
            lambda: res.get_external_view(physical)
            == res.get_ideal_state(physical),
            what="external view reconverged after heal",
        )
        st.run_once()
        # ... and the broker has applied it (one poll cycle): bounded
        # by the wait timeout, which IS the unavailability bound
        cluster.wait(
            lambda: (
                lambda r: r.num_docs_scanned == total
                and not r.partial_response
                and not r.exceptions
            )(cluster.query("SELECT count(*) FROM testTable")),
            what="broker serving the full count after heal",
        )
        summary = load.stop()
        final = cluster.query("SELECT count(*) FROM testTable")
        return {
            "scenario": "partition-controller",
            "leaseSeconds": lease_s,
            **summary,
            "idealUnchangedDuringOutage": unchanged_during,
            "idealUnchangedAfterHeal": res.get_ideal_state(physical)
            == ideal_before,
            "brokerServedFromSnapshot": True,  # waited on the gauge flip
            "finalDocs": final.num_docs_scanned,
            "expectedDocs": total,
            "finalComplete": not final.partial_response and not final.exceptions,
        }
    finally:
        cluster.stop()


def run_asymmetric_partition_scenario(
    data_dir: Optional[str] = None,
    lease_s: float = 1.2,
    rows_initial: int = 40,
    rows_appended: int = 30,
    rows_per_segment: int = 30,
    victim: str = "srv0",
) -> Dict[str, Any]:
    """One-way partition on the REALTIME commit plane: the victim's
    requests reach the controller (it keeps looking alive — heartbeats
    arrive) but every reply is lost, so only the victim knows it is
    partitioned.  Its client-side lease expires and self-fences write
    authority: completion rounds freeze with offsets intact, no
    replica moves (the controller sees a healthy server), reads keep
    serving, and the OTHER replica is elected committer after the hold
    window — exactly one committed segment, nothing lost or doubled.
    On heal the victim renews, downloads the committed copy
    (byte-identical CRC), and the lagging partition catches up."""
    import json as _json

    from pinot_tpu.common.schema import (
        DataType,
        FieldSpec,
        FieldType,
        Schema,
        TimeFieldSpec,
    )
    from pinot_tpu.common.tableconfig import StreamConfig
    from pinot_tpu.realtime.llc import make_segment_name
    from pinot_tpu.realtime.stream import FileBasedStreamProvider

    cluster = NetworkedCluster(
        num_servers=2, data_dir=data_dir, lease_s=lease_s
    )
    cluster.controller.stabilizer.grace_s = 0.0
    res = cluster.controller.resources
    st = cluster.controller.stabilizer
    try:
        schema = Schema(
            "rsvpNet",
            dimensions=[FieldSpec("venue", DataType.STRING)],
            metrics=[FieldSpec("rsvps", DataType.INT, FieldType.METRIC)],
            time_field=TimeFieldSpec(
                "mtime", DataType.LONG, time_unit="MILLISECONDS"
            ),
        )

        def _row(i: int) -> Dict[str, Any]:
            return {"venue": f"v{i % 3}", "rsvps": i % 5, "mtime": 10_000 + i}

        stream_path = os.path.join(cluster.data_dir, "stream_p0.jsonl")
        with open(stream_path, "w") as f:
            for i in range(rows_initial):
                f.write(_json.dumps(_row(i)) + "\n")

        cluster.controller.add_schema(schema)
        config = TableConfig(
            table_name="rsvpNet",
            table_type="REALTIME",
            replication=2,
            stream=StreamConfig(
                stream_type="file", rows_per_segment=rows_per_segment,
                properties={"paths": [stream_path]},
            ),
        )
        physical = cluster.controller.add_realtime_table(
            config, FileBasedStreamProvider([stream_path])
        )

        def count() -> int:
            r = cluster.query("SELECT count(*) FROM rsvpNet")
            return -1 if r.exceptions else r.num_docs_scanned

        # first segment commits (both replicas reachable), remainder
        # consumes into the next sequence
        seg0 = make_segment_name(physical, 0, 0)
        cluster.wait(
            lambda: res.get_ideal_state(physical).get(seg0, {})
            and all(
                stt == "ONLINE"
                for stt in res.get_ideal_state(physical)[seg0].values()
            ),
            what="first segment committed",
        )
        cluster.wait(
            lambda: count() == rows_initial, what="all initial rows served"
        )

        # one-way cut: victim -> controller REQUESTS still flow, every
        # controller -> victim REPLY is lost
        cluster.faults.cut("controller", victim)
        vsrv = cluster.server(victim).server
        cluster.wait(
            lambda: not vsrv.lease.held(), what="victim lease self-fencing"
        )
        blocked_before = vsrv.metrics.meter("lease.blockedCommits").count

        # next threshold arrives mid-partition: only the healthy
        # replica can run the completion protocol
        with open(stream_path, "a") as f:
            for i in range(rows_initial, rows_initial + rows_appended):
                f.write(_json.dumps(_row(i)) + "\n")

        seg1 = make_segment_name(physical, 0, 1)
        cluster.wait(
            lambda: res.get_ideal_state(physical).get(seg1, {})
            and any(
                stt == "ONLINE"
                for stt in res.get_ideal_state(physical)[seg1].values()
            ),
            timeout_s=30.0,
            what="mid-partition commit by the healthy replica",
        )
        st.run_once()
        controller_saw_alive = res.instances[victim].alive
        no_movement = st.metrics.meter("stabilizer.replicasAdded").count == 0
        blocked_commits = (
            vsrv.metrics.meter("lease.blockedCommits").count > blocked_before
        )
        total = rows_initial + rows_appended
        served_during = count()

        # heal: victim renews, downloads the committed copy, catches up
        cluster.faults.heal()
        cluster.wait(lambda: vsrv.lease.held(), what="victim lease renewal")
        cluster.wait(
            lambda: res.get_external_view(physical).get(seg1, {}).get(victim)
            == "ONLINE",
            timeout_s=30.0,
            what="victim downloading the committed copy",
        )
        cluster.wait(lambda: count() == total, what="full count after heal")

        # byte-identity: both replicas loaded the same committed bytes
        crcs = []
        for s in cluster.server_starters:
            tdm = s.server.data_manager.table(physical)
            acquired = tdm.acquire_segments([seg1])
            try:
                crcs.extend(d.segment.metadata.crc for d in acquired)
            finally:
                tdm.release_segments(acquired)
        byte_identical = len(crcs) == 2 and len(set(crcs)) == 1

        final = cluster.query("SELECT count(*) FROM rsvpNet")
        ok = (
            final.num_docs_scanned == total
            and not final.exceptions
            and blocked_commits
            and controller_saw_alive
            and no_movement
            and byte_identical
        )
        return {
            "scenario": "asymmetric-partition",
            "victim": victim,
            "leaseSeconds": lease_s,
            "victimSelfFenced": blocked_commits,
            "controllerSawVictimAlive": controller_saw_alive,
            "noReplicaMovement": no_movement,
            "servedDuringPartition": served_during,
            "committedByteIdentical": byte_identical,
            "finalDocs": final.num_docs_scanned,
            "expectedDocs": total,
            "failedQueries": 0 if ok else 1,
        }
    finally:
        cluster.stop()


def run_split_brain_scenario(data_dir: Optional[str] = None) -> Dict[str, Any]:
    """Two controllers over one property store: A builds the cluster,
    then B claims the store (epoch+1) — A is now a zombie.  EVERY write
    A attempts (drain, quota, upload, delete, stabilizer round) raises
    a typed StaleEpochError and mutates nothing durable; commit-plane
    calls carrying the wrong incarnation's lease epoch are rejected in
    BOTH directions; and the ideal state converges to B's fixpoint."""
    from pinot_tpu.common.fencing import StaleEpochError
    from pinot_tpu.controller.controller import Controller
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.server.starter import ServerStarter
    from pinot_tpu.tools.datagen import make_test_schema, random_rows

    data_dir = data_dir or tempfile.mkdtemp(prefix="pinot_tpu_splitbrain_")
    cluster_a = InProcessCluster(num_servers=2, data_dir=data_dir)
    ctrl_a = cluster_a.controller
    schema = make_test_schema(with_mv=False)
    physical = cluster_a.add_offline_table(schema, replication=2)
    rows = random_rows(schema, 120, seed=11)
    total = 0
    for i in range(3):
        n = 30 + 10 * i
        cluster_a.upload(physical, build_segment(schema, rows[:n], physical, f"sb{i}"))
        total += n
    ideal_a = ctrl_a.resources.get_ideal_state(physical)

    # B claims the store: A is fenced from this moment
    ctrl_b = Controller(data_dir)
    ctrl_b.stabilizer.grace_s = 0.0
    servers_b = {}
    for name in ("server0", "server1"):
        s = ServerInstance(name)
        ServerStarter(s, ctrl_b.resources).start()
        servers_b[name] = s

    stale_rejections: Dict[str, bool] = {}

    def _stale(label: str, fn) -> None:
        try:
            fn()
            stale_rejections[label] = False
        except StaleEpochError:
            stale_rejections[label] = True
        except Exception:
            stale_rejections[label] = False

    try:
        store_ideal_before = ctrl_b.property_store.get("idealstates", physical)
        # stabilizer first: later attempts corrupt the zombie's own
        # memory (fenced writes fail AFTER their in-memory mutation),
        # which could leave it nothing live to re-replicate onto
        _stale("stabilizerWrite", lambda: _zombie_stabilizer_write(ctrl_a, physical))
        _stale(
            "upload",
            lambda: ctrl_a.upload_segment(
                physical, build_segment(schema, rows[:20], physical, "zombie")
            ),
        )
        _stale(
            "quota",
            lambda: ctrl_a.resources.update_table_quota(physical, 5.0),
        )
        _stale("delete", lambda: ctrl_a.delete_segment(physical, "sb0"))
        _stale("drain", lambda: ctrl_a.drain_instance("server0"))
        # commit plane, both directions: B's epoch at A, A's epoch at B
        _stale(
            "commitPlaneAtZombie",
            lambda: ctrl_a.realtime_manager.completion.segment_consumed(
                f"{physical}__0__0", "server0", 10, epoch=ctrl_b.epoch
            ),
        )
        _stale(
            "commitPlaneAtLive",
            lambda: ctrl_b.realtime_manager.completion.segment_consumed(
                f"{physical}__0__0", "server0", 10, epoch=ctrl_a.epoch
            ),
        )
        store_ideal_after = ctrl_b.property_store.get("idealstates", physical)
        store_unchanged = store_ideal_before == store_ideal_after

        # the live controller converges to ITS fixpoint (kill a server
        # to force real stabilizer work post-fence)
        ctrl_b.resources.set_instance_alive("server0", False)
        for _ in range(3):
            ctrl_b.stabilizer.run_once()
        ideal_b = ctrl_b.resources.get_ideal_state(physical)
        converged = (
            all("server0" not in r for r in ideal_b.values())
            and all(len(r) == 1 for r in ideal_b.values())
            and ctrl_b.resources.get_external_view(physical) == ideal_b
        )
        # idempotent: one more round changes nothing
        ctrl_b.stabilizer.run_once()
        converged = converged and ctrl_b.resources.get_ideal_state(physical) == ideal_b

        all_rejected = all(stale_rejections.values())
        return {
            "scenario": "split-brain",
            "epochA": ctrl_a.epoch,
            "epochB": ctrl_b.epoch,
            "staleRejections": stale_rejections,
            "allStaleWritesRejected": all_rejected,
            "durableStoreUnchangedByZombie": store_unchanged,
            "liveControllerConverged": converged,
            "staleEpochRejectionsMetered": ctrl_a.metrics.meter(
                "fence.staleEpochRejections"
            ).count
            + ctrl_b.metrics.meter("fence.staleEpochRejections").count,
            "failedQueries": 0
            if (all_rejected and store_unchanged and converged)
            else 1,
        }
    finally:
        ctrl_b.stop()
        cluster_a.stop()


def _zombie_stabilizer_write(ctrl_a, physical: str) -> None:
    """Force the zombie's stabilizer to attempt a persisted write (its
    own view says a server died); must raise StaleEpochError."""
    ctrl_a.resources.set_instance_alive("server1", False)
    ctrl_a.stabilizer.grace_s = 0.0
    before = ctrl_a.resources.get_ideal_state(physical)
    ctrl_a.stabilizer.run_once()
    # a fenced run_once swallows nothing: add_segment_replica raises
    # through run_once — if we got here, no exception fired, so check
    # whether anything was durably persisted (it must not have been)
    after = ctrl_a.resources.get_ideal_state(physical)
    if before == after:
        raise RuntimeError("stabilizer made no write attempt (test rig issue)")


# ---------------------------------------------------------------------------
# Disaster-recovery scenario (ISSUE 20): consistent online backup under
# closed-loop load, a seeded deep-store corruption scrubbed + repaired
# from a live replica, then the controller property store DESTROYED
# mid-load and the cluster restored from archive + deep store alone —
# byte-identical answers, zero committed-row loss, drain flags and
# epoch fencing preserved.  Shared by the CLI and
# tests/test_disaster_recovery.py.
# ---------------------------------------------------------------------------


def run_disaster_recovery_scenario(
    num_servers: int = 3,
    replication: int = 2,
    num_segments: int = 6,
    clients: int = 3,
    rt_rows_per_segment: int = 40,
    window_s: float = 0.5,
    data_dir: Optional[str] = None,
    archive_path: Optional[str] = None,
    seed: int = 2020,
) -> Dict[str, Any]:
    import json
    import shutil as _shutil

    from pinot_tpu.common.fencing import StaleEpochError
    from pinot_tpu.common.tableconfig import StreamConfig
    from pinot_tpu.realtime.llc import RESP_KEEP, make_segment_name
    from pinot_tpu.realtime.stream import FileBasedStreamProvider
    from pinot_tpu.tools.backup import create_backup, restore_backup
    from pinot_tpu.tools.datagen import random_rows
    from pinot_tpu.utils.audit import SamplerBudget, strip_accounting

    cluster, physical, total = _build_scenario_cluster(
        num_servers, replication, num_segments, data_dir, seed=seed
    )
    old_ctrl = cluster.controller
    archive = archive_path or os.path.join(cluster.data_dir, "dr_backup.tar.gz")
    try:
        # -- 1. drain one server (the flag must survive the disaster) --
        drained = "server2" if num_servers >= 3 else None
        if drained:
            _drain_one(cluster, drained)

        # -- 2. realtime table: commit two segments' worth of rows -----
        rt_schema = _tenant_schema("rtTable")
        stream_file = os.path.join(cluster.data_dir, "rt_p0.jsonl")
        rt_rows = random_rows(rt_schema, rt_rows_per_segment * 3, seed=seed + 1)
        with open(stream_file, "w") as f:
            for r in rt_rows[: rt_rows_per_segment * 2]:
                f.write(json.dumps(r) + "\n")
        cluster.controller.add_schema(rt_schema)
        rt_config = TableConfig(
            table_name="rtTable",
            table_type="REALTIME",
            replication=1,
            stream=StreamConfig(rows_per_segment=rt_rows_per_segment),
        )
        rt_physical = cluster.controller.add_realtime_table(
            rt_config, FileBasedStreamProvider([stream_file])
        )
        rt_seg = [make_segment_name(rt_physical, 0, i) for i in range(3)]
        dm0 = cluster.controller.realtime_manager.consumers_of(rt_seg[0])[0]
        dm0.consume_step(max_rows=100_000)
        assert dm0.try_commit() == RESP_KEEP
        dm1 = cluster.controller.realtime_manager.consumers_of(rt_seg[1])[0]
        dm1.consume_step(max_rows=100_000)
        assert dm1.try_commit() == RESP_KEEP
        rt_committed = rt_rows_per_segment * 2
        rt_pql = "SELECT count(*) FROM rtTable"
        assert cluster.query(rt_pql).num_docs_scanned == rt_committed

        # -- 3. canonical pre-disaster payloads (byte-identity bar) ----
        canon = [
            "SELECT count(*) FROM testTable",
            "SELECT sum(metInt), max(dimInt) FROM testTable GROUP BY dimStr",
            rt_pql,
        ]
        baseline_payloads = {}
        for q in canon:
            resp = cluster.query(q)
            assert not resp.exceptions and not resp.partial_response, q
            baseline_payloads[q] = strip_accounting(resp.to_json())

        # -- 4. closed-loop load for the rest of the scenario ----------
        load = ClosedLoopLoad(
            cluster, "SELECT count(*) FROM testTable", total, clients
        ).start()
        t0 = time.monotonic()
        time.sleep(window_s)
        ok0, tA = load.ok, time.monotonic()
        baseline_qps = ok0 / max(1e-6, tA - t0)

        # -- 5. consistent online backup (timed, under load) -----------
        backup_stats = create_backup(cluster.data_dir, archive)

        # -- 6. seed deep-store corruption; scrub detects + repairs ----
        store = cluster.controller.store
        victim_seg = "seg0"
        victim_path = store.segment_file_path(physical, victim_seg)
        with open(victim_path, "r+b") as f:
            f.seek(-16, os.SEEK_END)
            f.write(b"\xde\xad\xbe\xef" * 4)

        def in_process_copy(name, url, table, segment):
            for s in cluster.servers:
                if s.name == name:
                    return s.segment_copy_bytes(table, segment)
            return None

        scrub = cluster.controller.deepstore_scrubber
        scrub.copy_fn = in_process_copy
        scrub.budget = SamplerBudget(per_s=100_000.0, burst=10_000.0)
        scrub_t0 = time.monotonic()
        okA = load.ok
        scrub.run_once()
        time.sleep(window_s)  # serving window with the scrub round in it
        scrub_t1, okB = time.monotonic(), load.ok
        scrub_qps = (okB - okA) / max(1e-6, scrub_t1 - scrub_t0)
        scrub_snap = scrub.snapshot()
        scrub_repaired = False
        try:
            info = cluster.controller.resources.get_segment_metadata(
                physical, victim_seg
            ) or {}
            store.verify_copy(
                physical, victim_seg,
                expected_crc=getattr(info.get("metadata"), "crc", None),
            )
            scrub_repaired = True
        except Exception:
            pass
        ok_qps_ratio = min(1.0, scrub_qps / max(1e-6, baseline_qps))

        # -- 7. DISASTER: property store destroyed mid-load ------------
        _shutil.rmtree(os.path.join(cluster.data_dir, "property_store"))
        time.sleep(0.2)  # queries keep flowing: broker routing survives
        old_ctrl.stop()

        # -- 8. restore: new controller from archive + deep store ------
        restore_t0 = time.monotonic()
        restore_stats = restore_backup(archive, cluster.data_dir)
        new_ctrl = Controller(cluster.data_dir)
        new_ctrl.stabilizer.grace_s = 0.0
        cluster.controller = new_ctrl
        # servers first (replays refill the external views), then the
        # broker (re-seeds routing from those views) — the elastic-fleet
        # in-process restart pattern
        for server in cluster.servers:
            ServerStarter(server, new_ctrl.resources).start()
        BrokerStarter(cluster.broker, new_ctrl.resources).start()
        first_query_s = None
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            resp = cluster.query("SELECT count(*) FROM testTable")
            if (
                not resp.exceptions
                and not resp.partial_response
                and resp.num_docs_scanned == total
            ):
                first_query_s = time.monotonic() - restore_t0
                break
            time.sleep(0.05)
        time.sleep(window_s)  # post-restore serving window under load
        summary = load.stop()

        # -- 9. acceptance accounting ----------------------------------
        byte_identical = True
        for q in canon:
            resp = cluster.query(q)
            if (
                resp.exceptions
                or resp.partial_response
                or strip_accounting(resp.to_json()) != baseline_payloads[q]
            ):
                byte_identical = False
        drain_preserved = (
            drained is None
            or drained in new_ctrl.resources._draining_flags
        )
        # fencing: the pre-disaster zombie's writes must still be
        # rejected against the restored store
        try:
            old_ctrl.property_store.put("tables", "zombieWrite", {"x": 1})
            fencing_preserved = False
        except StaleEpochError:
            fencing_preserved = True
        # realtime: committed rows exactly once, consumption resumes
        rt_after = cluster.query(rt_pql).num_docs_scanned
        rt_committed_preserved = rt_after == rt_committed
        rt_resumed = False
        try:
            with open(stream_file, "a") as f:
                for r in rt_rows[rt_rows_per_segment * 2 :]:
                    f.write(json.dumps(r) + "\n")
            dm2 = new_ctrl.realtime_manager.consumers_of(rt_seg[2])[0]
            dm2.consume_step(max_rows=100_000)
            rt_resumed = (
                dm2.try_commit() == RESP_KEEP
                and cluster.query(rt_pql).num_docs_scanned
                == rt_rows_per_segment * 3
            )
        except Exception:
            rt_resumed = False

        scrub_detected = scrub_snap["corruptCopies"] >= 1
        failed = (
            summary["failedQueries"]
            + (0 if first_query_s is not None else 1)
            + (0 if byte_identical else 1)
            + (0 if drain_preserved else 1)
            + (0 if fencing_preserved else 1)
            + (0 if rt_committed_preserved else 1)
            + (0 if rt_resumed else 1)
            + (0 if (scrub_detected and scrub_repaired) else 1)
        )
        return {
            "scenario": "disaster-recovery",
            "backup": backup_stats,
            "restore": {
                "restoreToFirstQuerySeconds": (
                    round(first_query_s, 4) if first_query_s else None
                ),
                "restoreSeconds": round(restore_stats["restoreSeconds"], 4),
                "segmentsVerified": restore_stats["segmentsVerified"],
                "segmentsMissing": restore_stats["segmentsMissing"],
                "segmentsCorrupt": restore_stats["segmentsCorrupt"],
                "byteIdentical": byte_identical,
                "drainFlagPreserved": drain_preserved,
                "fencingPreserved": fencing_preserved,
                "rtCommittedPreserved": rt_committed_preserved,
                "rtResumed": rt_resumed,
            },
            "scrub": {
                "detected": scrub_detected,
                "repaired": scrub_repaired,
                "okQpsRatio": round(ok_qps_ratio, 4),
                "baselineQps": round(baseline_qps, 2),
                "scrubQps": round(scrub_qps, 2),
                "snapshot": scrub_snap,
            },
            "load": summary,
            "failedQueries": failed,
        }
    finally:
        cluster.stop()


SCENARIOS = {
    "kill-server": run_kill_server_scenario,
    "drain": run_drain_scenario,
    "rolling-restart": run_rolling_restart_scenario,
    "rolling-restart-warm": run_rolling_restart_warm_scenario,
    "elastic-fleet": run_elastic_fleet_scenario,
    "noisy-neighbor": run_noisy_neighbor_scenario,
    "join-under-flood": run_join_under_flood_scenario,
    "ingest-backpressure": run_ingest_backpressure_scenario,
    "hbm-pressure": run_hbm_pressure_scenario,
    "audit-divergence": run_audit_divergence_scenario,
    "partition-server": run_partition_server_scenario,
    "partition-controller": run_partition_controller_scenario,
    "asymmetric-partition": run_asymmetric_partition_scenario,
    "split-brain": run_split_brain_scenario,
    "disaster-recovery": run_disaster_recovery_scenario,
}


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import json

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--scenario", choices=sorted(SCENARIOS), required=True)
    p.add_argument("--servers", type=int, default=3)
    p.add_argument("--replication", type=int, default=2)
    p.add_argument("--segments", type=int, default=6)
    p.add_argument("--clients", type=int, default=3)
    p.add_argument("--quota-qps", type=float, default=8.0)
    p.add_argument("--flood-clients", type=int, default=4)
    p.add_argument("--tables", type=int, default=104)
    args = p.parse_args(argv)
    if args.scenario == "elastic-fleet":
        out = run_elastic_fleet_scenario(
            num_tables=args.tables,
            num_servers=args.servers,
            clients=args.clients,
        )
        import json as _json

        print(_json.dumps(out, indent=2))
        return 0 if out["failedQueries"] == 0 else 1
    if args.scenario in (
        "ingest-backpressure",
        "hbm-pressure",
        "audit-divergence",
        "asymmetric-partition",
        "split-brain",
    ):
        out = SCENARIOS[args.scenario]()
    elif args.scenario == "partition-server":
        out = SCENARIOS[args.scenario](
            num_servers=args.servers,
            replication=args.replication,
            num_segments=args.segments,
            clients=args.clients,
        )
    elif args.scenario == "partition-controller":
        out = SCENARIOS[args.scenario](
            num_servers=min(args.servers, 3),
            replication=args.replication,
            num_segments=min(args.segments, 4),
            clients=args.clients,
        )
    elif args.scenario == "rolling-restart-warm":
        # sequential replay (clients=1): the compile.cold == 0 bar is
        # deterministic only when no novel micro-batched combo shape
        # can appear for the first time mid-roll
        out = SCENARIOS[args.scenario](
            num_servers=args.servers,
            replication=args.replication,
            num_segments=args.segments,
        )
    elif args.scenario == "noisy-neighbor":
        out = SCENARIOS[args.scenario](
            num_servers=min(args.servers, 2),
            replication=args.replication,
            num_segments=args.segments,
            clients=args.clients,
            flood_clients=args.flood_clients,
            quota_qps=args.quota_qps,
        )
    else:
        out = SCENARIOS[args.scenario](
            num_servers=args.servers,
            replication=args.replication,
            num_segments=args.segments,
            clients=args.clients,
        )
    print(json.dumps(out, indent=2))
    return 0 if out["failedQueries"] == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
