"""Metrics: meters, timers, gauges per role + Prometheus exposition.

The Yammer-metrics analog (pinot-common
``common/metrics/AbstractMetrics.java`` with ``BrokerMeter``,
``ServerMeter``, ``ServerQueryPhase`` etc.): typed registries per role,
timers keep recent samples for percentile queries (the
``AggregatedHistogram`` role), everything thread-safe and cheap.

Beyond the seed version:

- ``Meter`` keeps a 1-minute EWMA rate (5s ticks, the Yammer
  ``EWMA.oneMinuteEWMA`` scheme) next to the lifetime average — a meter
  marked heavily an hour ago no longer reports a misleading "rate".
- ``Timer.percentile`` interpolates between ranks and caches the sorted
  window (invalidated on update) instead of re-sorting the full window
  under the lock on every call; ``snapshot`` reads all percentiles from
  one sort.
- ``Gauge`` reads/writes under a lock and supports callable providers
  (``set_fn``) for live values.
- ``prometheus_text`` renders one or more registries in the Prometheus
  text exposition format (served at ``/metrics`` on the broker, server,
  and controller HTTP surfaces).
- Per-role metric-name CATALOGS are the single source of truth for
  series names; ``tools/metrics_lint.py`` asserts every name used in
  the codebase appears here, so a typo cannot silently fork a series.
"""
from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, Iterable, List, Optional, Sequence

_EWMA_TICK_S = 5.0
_EWMA_ALPHA_1M = 1.0 - math.exp(-_EWMA_TICK_S / 60.0)


def interpolated_percentile(s: Sequence[float], p: float) -> float:
    """Linear-interpolated percentile over a SORTED sample sequence —
    shared by Timer and the plan-stats registry (utils/planstats.py) so
    /metrics and /debug/plans percentiles can never drift apart."""
    if not s:
        return 0.0
    if len(s) == 1:
        return s[0]
    rank = (len(s) - 1) * min(max(p, 0.0), 100.0) / 100.0
    lo = int(rank)
    frac = rank - lo
    if lo + 1 >= len(s):
        return s[-1]
    return s[lo] + frac * (s[lo + 1] - s[lo])


class Meter:
    def __init__(self) -> None:
        self.count = 0
        self._t0 = time.time()
        self._lock = threading.Lock()
        # 1-minute EWMA state (Yammer Meter semantics): marks accumulate
        # in _uncounted; every 5s tick folds them into the decayed rate
        self._uncounted = 0
        self._ewma = 0.0  # events per second
        self._ewma_init = False
        self._last_tick = time.monotonic()

    def mark(self, n: int = 1) -> None:
        with self._lock:
            self._tick_locked(time.monotonic())
            self.count += n
            self._uncounted += n

    def _tick_locked(self, now: float) -> None:
        elapsed = now - self._last_tick
        if elapsed < _EWMA_TICK_S:
            return
        ticks = int(elapsed // _EWMA_TICK_S)
        # first tick consumes the accumulated marks; the rest decay
        instant = self._uncounted / _EWMA_TICK_S
        self._uncounted = 0
        if not self._ewma_init:
            self._ewma = instant
            self._ewma_init = True
            ticks -= 1
        else:
            self._ewma += _EWMA_ALPHA_1M * (instant - self._ewma)
            ticks -= 1
        for _ in range(min(ticks, 64)):  # cap idle catch-up work
            self._ewma += _EWMA_ALPHA_1M * (0.0 - self._ewma)
        if ticks > 64:
            self._ewma = 0.0
        self._last_tick += (int(elapsed // _EWMA_TICK_S)) * _EWMA_TICK_S

    @property
    def rate(self) -> float:
        """Lifetime average events/second (process-age denominator)."""
        dt = time.time() - self._t0
        return self.count / dt if dt > 0 else 0.0

    @property
    def rate_1m(self) -> float:
        """1-minute EWMA events/second — the windowed rate that tracks
        what the meter is doing NOW, not since process start."""
        with self._lock:
            self._tick_locked(time.monotonic())
            if not self._ewma_init:
                # under one tick of life: instantaneous average so short
                # tests/bursts still see a sane number
                dt = time.monotonic() - self._last_tick
                return self._uncounted / dt if dt > 0 else 0.0
            return self._ewma


class Timer:
    def __init__(self, window: int = 4096) -> None:
        self.count = 0
        self.total_ms = 0.0
        self._samples: Deque[float] = deque(maxlen=window)
        self._sorted: Optional[List[float]] = None  # cache, dropped on update
        self._lock = threading.Lock()

    def update(self, ms: float) -> None:
        with self._lock:
            self.count += 1
            self.total_ms += ms
            self._samples.append(ms)
            self._sorted = None

    def _sorted_locked(self) -> List[float]:
        if self._sorted is None:
            self._sorted = sorted(self._samples)
        return self._sorted

    # the ONE percentile implementation (module level above): timers
    # and the plan-stats registry must never drift apart
    _interp = staticmethod(interpolated_percentile)

    def percentile(self, p: float) -> float:
        with self._lock:
            return self._interp(self._sorted_locked(), p)

    def percentiles(self, ps: Iterable[float]) -> List[float]:
        """All requested percentiles from ONE cached sort/lock hold."""
        with self._lock:
            s = self._sorted_locked()
            return [self._interp(s, p) for p in ps]

    @property
    def mean_ms(self) -> float:
        return self.total_ms / self.count if self.count else 0.0


class Gauge:
    def __init__(self) -> None:
        self._value: Any = 0
        self._fn = None
        self._lock = threading.Lock()

    def set(self, v: Any) -> None:
        with self._lock:
            self._value = v
            self._fn = None

    def set_fn(self, fn) -> None:
        """Callable provider: the gauge reads live on every snapshot."""
        with self._lock:
            self._fn = fn

    def clear_fn(self, fn) -> None:
        """Detach a provider IF it is still the attached one (resets the
        gauge to 0).  The equality guard makes detach safe against a
        successor that already replaced the provider: last writer wins,
        a stale owner's detach is a no-op."""
        with self._lock:
            if self._fn == fn:
                self._fn = None
                self._value = 0

    @property
    def value(self) -> Any:
        with self._lock:
            fn = self._fn
            if fn is None:
                return self._value
        try:
            return fn()
        except Exception:
            return None


class MetricsRegistry:
    """Per-role metrics registry (AbstractMetrics analog)."""

    role = ""  # catalog key; set by typed subclasses

    def __init__(self, scope: str) -> None:
        self.scope = scope
        self._meters: Dict[str, Meter] = {}
        self._timers: Dict[str, Timer] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._lock = threading.Lock()

    # a series that exists is found without the lock (a dict read is
    # atomic): the boundaries of a query look up some forty timers, from
    # six threads, and one lock for the whole role is a queue

    def meter(self, name: str) -> Meter:
        m = self._meters.get(name)
        if m is None:
            with self._lock:
                m = self._meters.setdefault(name, Meter())
        return m

    def timer(self, name: str) -> Timer:
        t = self._timers.get(name)
        if t is None:
            with self._lock:
                t = self._timers.setdefault(name, Timer())
        return t

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            with self._lock:
                g = self._gauges.setdefault(name, Gauge())
        return g

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            meters = dict(self._meters)
            timers = dict(self._timers)
            gauges = dict(self._gauges)
        out: Dict[str, Any] = {
            "scope": self.scope,
            "meters": {
                k: {
                    "count": m.count,
                    "rate": round(m.rate, 3),
                    "rate1m": round(m.rate_1m, 3),
                }
                for k, m in meters.items()
            },
            "timers": {},
            "gauges": {k: g.value for k, g in gauges.items()},
        }
        for k, t in timers.items():
            p50, p95, p99 = t.percentiles((50, 95, 99))
            out["timers"][k] = {
                "count": t.count,
                "meanMs": round(t.mean_ms, 3),
                "p50Ms": round(p50, 3),
                "p95Ms": round(p95, 3),
                "p99Ms": round(p99, 3),
            }
        return out


class ServerMetrics(MetricsRegistry):
    """ServerMeter/ServerTimer/ServerQueryPhase namespace."""

    role = "server"


class BrokerMetrics(MetricsRegistry):
    """BrokerMeter/BrokerQueryPhase namespace."""

    role = "broker"


class ControllerMetrics(MetricsRegistry):
    """ControllerMeter/ControllerGauge namespace."""

    role = "controller"


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------


def _prom_name(name: str) -> str:
    """Metric name -> legal Prometheus name component."""
    out = []
    for ch in name:
        out.append(ch if ch.isalnum() or ch == "_" else "_")
    s = "".join(out)
    if s and s[0].isdigit():
        s = "_" + s
    return s


def _prom_label(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _prom_value(v: Any) -> Optional[str]:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, (int, float)):
        if isinstance(v, float) and (math.isnan(v) or math.isinf(v)):
            return str(v)
        return repr(float(v)) if isinstance(v, float) else str(v)
    return None  # non-numeric gauges are skipped in the exposition


def prometheus_text(registries, prefix: str = "pinot_tpu") -> str:
    """Render registries as Prometheus text format 0.0.4.

    Meters -> ``<prefix>_<role>_<name>_total`` counters (plus a
    ``..._rate1m`` gauge), timers -> summary-style ``..._ms`` families
    (``_count``/``_sum`` + quantile series), gauges -> gauges.  The
    registry scope rides as the ``scope`` label so multiple instances
    of a role can share one scrape."""
    if isinstance(registries, MetricsRegistry):
        registries = [registries]
    lines: List[str] = []
    typed: set = set()

    def _family(name: str, kind: str, help_text: str = "") -> None:
        if name in typed:
            return
        typed.add(name)
        if help_text:
            lines.append(f"# HELP {name} {_prom_label(help_text)}")
        lines.append(f"# TYPE {name} {kind}")

    for reg in registries:
        role = reg.role or "generic"
        catalog = METRIC_CATALOGS.get(role, {})
        base = f"{prefix}_{_prom_name(role)}"
        label = f'{{scope="{_prom_label(reg.scope)}"}}'
        snap_lock = reg._lock
        with snap_lock:
            meters = dict(reg._meters)
            timers = dict(reg._timers)
            gauges = dict(reg._gauges)
        for name in sorted(meters):
            m = meters[name]
            fam = f"{base}_{_prom_name(name)}"
            _family(f"{fam}_total", "counter", catalog.get(name, ""))
            lines.append(f"{fam}_total{label} {m.count}")
            _family(f"{fam}_rate1m", "gauge")
            lines.append(f"{fam}_rate1m{label} {m.rate_1m:.6g}")
        for name in sorted(timers):
            t = timers[name]
            fam = f"{base}_{_prom_name(name)}_ms"
            _family(fam, "summary", catalog.get(name, ""))
            p50, p95, p99 = t.percentiles((50, 95, 99))
            for q, v in (("0.5", p50), ("0.95", p95), ("0.99", p99)):
                lines.append(
                    f'{fam}{{scope="{_prom_label(reg.scope)}",quantile="{q}"}} {v:.6g}'
                )
            lines.append(f"{fam}_sum{label} {t.total_ms:.6g}")
            lines.append(f"{fam}_count{label} {t.count}")
        for name in sorted(gauges):
            v = _prom_value(gauges[name].value)
            if v is None:
                continue
            fam = f"{base}_{_prom_name(name)}"
            _family(fam, "gauge", catalog.get(name, ""))
            lines.append(f"{fam}{label} {v}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# Per-role metric-name catalogs — the single source of truth.
#
# Every ``meter("...")`` / ``timer("...")`` / ``gauge("...")`` name used
# in the codebase must appear here (``tools/metrics_lint.py`` enforces
# it as a tier-1 test).  Dynamic name parts are declared with ``*``
# (e.g. ``phase.*`` covers ``phase.staging``); entries are
# name -> one-line description (rendered as Prometheus HELP).
# ---------------------------------------------------------------------------

BROKER_METRIC_CATALOG: Dict[str, str] = {
    "queries": "queries received (post-parse routing attempts included)",
    "queriesDropped": "queries rejected by the admission front door "
    "(any tier: quota / concurrency / overload)",
    # adaptive admission plane (broker/admission.py)
    "admission.shedQuota": "queries shed by the per-table QPS token bucket",
    "admission.shedConcurrency": "queries shed by the per-table in-flight cap",
    "admission.shedOverload": "queries shed pre-scatter because every "
    "covering server's AIMD window was exhausted",
    "admission.windowDecreases": "AIMD multiplicative window decreases "
    "(saturation evidence observed)",
    "admission.inflight": "queries currently inside the broker, all tables",
    "slowQueries": "queries recorded into the slow-query log",
    "failoverRetries": "scatter batches re-issued to an alternate replica",
    "hedgesSent": "speculative duplicate attempts sent to a second replica",
    "queryTotal": "end-to-end broker latency per query",
    "phase.parse": "PQL parse + optimize time",
    "phase.route": "routing-table lookup + batch build time",
    "scatterGather": "scatter-gather wall time per query",
    "reduce": "partial-merge + finalize time per query",
    # a connection's life around the handler (broker.py _Connection),
    # per query: accept, head, httpTotal and close follow one another
    "phase.httpAccept": "accept() returned the socket -> the connection "
    "thread's first statement: the thread made, started and woken",
    "httpConnection": "connection thread's first statement to the socket "
    "closed",
    "phase.httpHead": "connection thread's first statement -> the query "
    "handler's entry: handler object, request line, header parse, route",
    "phase.httpClose": "last byte of the reply handed to the socket -> the "
    "socket closed (flush, shutdown, close)",
    # the boundaries around handle_pql (utils/trace.py boundary): with
    # the ones above they cover a query's wall time inside the broker
    "httpTotal": "HTTP handler entry to last byte of the reply written",
    "phase.httpRead": "reading and decoding the HTTP request",
    "phase.render": "resp.to_json() + json.dumps + writing the reply",
    "phase.bookkeeping": "planstats, tail sampler, SLO and query-log "
    "records, made before the reply is written",
    "phase.attemptSubmit": "one scatter attempt made ready: circuit-breaker "
    "claim, attempt budget, span id",
    "phase.poolQueue": "scatter attempt handed to the pool -> a pool thread "
    "runs it",
    "phase.gatherWake": "pool thread has the server's result -> the gather "
    "loop has it",
    "phase.serializeRequest": "InstanceRequest encode, per scatter attempt",
    "phase.deserializeResult": "DataTable decode, per scatter attempt",
    "serverLatency": "per-attempt server round-trip latency",
    # cost-accounting plane (merged per-query cost vector totals)
    "cost.docsScanned": "documents scanned, summed over merged responses",
    "cost.bytesScanned": "column bytes touched, summed over merged responses",
    "cost.deviceMs": "per-query device-kernel ms (merged cost vector)",
    "cost.hostMs": "per-query host-path ms (merged cost vector)",
    "table.*.docsScanned": "per-table documents scanned (cost attribution)",
    "table.*.bytesScanned": "per-table column bytes touched (cost attribution)",
    # workload-introspection plane (utils/planstats.py, /debug/workload)
    "workload.recorded": "responses folded into the per-plan-digest "
    "workload registry",
    "workload.digests": "distinct plan-shape digests currently tracked",
    "explain.queries": "EXPLAIN / EXPLAIN ANALYZE queries handled",
    # distributed-join plane (broker/joinplan.py planner + coordinator)
    "join.queries": "join queries planned by this broker",
    "join.failed": "join queries that completed with exceptions",
    "join.strategy.colocated": "joins executed with the colocated "
    "partitioned strategy (zero exchange bytes)",
    "join.strategy.broadcast": "joins executed by broadcasting the "
    "build side to every probe server",
    "join.strategy.shuffle": "joins executed through the key-hash "
    "shuffle exchange",
    "join.heavyHitterSplits": "heavy-hitter keys split-and-replicated "
    "across shuffle owners instead of hot-spotting one server",
    "join.shuffleBytes": "exchange bytes shipped to shuffle owners",
    "join.broadcastBytes": "build-side bytes shipped across all "
    "broadcast probe servers",
    "join.planMs": "join planning + coordination wall ms per query",
    # partition-tolerance plane (ISSUE 9): a partitioned broker keeps
    # serving from its last versioned snapshot and says so
    # SLO & tail-latency attribution plane (ISSUE 11)
    "history.ticks": "metric-history samples recorded into the ring "
    "(utils/timeseries.py, served at /debug/history)",
    "history.series": "distinct series in the latest history sample",
    "slo.burning": "tables currently burning their error budget on BOTH "
    "the fast and slow windows",
    "slo.worstBurnRate5m": "worst per-table burn rate over the fast "
    "(default 5m) window",
    "slo.worstBurnRate1h": "worst per-table burn rate over the slow "
    "(default 1h) window",
    "tails.observed": "completed queries offered to the tail sampler",
    "tails.retained": "tail traces kept (slow / failed / partial / "
    "1-in-N sampled)",
    "tails.ring": "retained tail traces currently held in the ring",
    "flightrec.dumps": "flight-recorder bundles written on notable events",
    "flightrec.bundles": "flight-recorder bundles currently on disk",
    "controller.unreachable": "1 while cluster-state polls are failing "
    "(serving from the last versioned snapshot)",
    "controller.pollFailures": "failed cluster-state polls (partition / "
    "controller outage; full-jitter retried)",
    "controller.allDeadSnapshotsHeld": "cluster-state snapshots listing "
    "NO live servers ignored in favor of the last routing (the "
    "controller may be the partitioned one)",
    "netfaults.*": "injected link faults observed by this role's "
    "transports (dropped/replyDropped/delayed/duplicated/flaky)",
    # correctness & freshness audit plane (ISSUE 19): replica
    # double-scatter sampling + event-time freshness on responses
    "audit.replicaChecks": "sampled queries double-scattered to an "
    "alternate covering replica and compared (accounting stripped)",
    "audit.replicaDivergences": "replica pairs whose stripped payloads "
    "differed — a real correctness signal, flight-recorded",
    "audit.replicaDropped": "replica-audit samples dropped (queue full "
    "or sampler budget exhausted — never blocks serving)",
    "audit.replicaErrors": "replica-audit probes that errored before a "
    "comparison (either side failed; not counted as divergence)",
    "freshness.lagMs": "event-time staleness of merged responses "
    "(now - min realtime watermark across merged parts)",
    "freshness.*.lagMs": "per-table freshnessMs of the latest "
    "realtime-serving response",
}

SERVER_METRIC_CATALOG: Dict[str, str] = {
    "queries": "instance requests handled",
    "queriesShed": "requests shed by the saturated scheduler (210)",
    "queriesAbandoned": "requests whose deadline expired while queued",
    "segmentsMissedServing": "requested segments this server could not serve",
    "crcFailures": "segment integrity (CRC) verification failures",
    "quarantinedSegments": "corrupt segment copies pulled out of serving",
    "queryExecution": "end-to-end server handle_request latency",
    "scheduler.pending": "queries queued-or-running on the scheduler",
    "phase.schedulerWait": "time from submit to worker dequeue",
    "phase.deserializeRequest": "InstanceRequest decode",
    "phase.serverParse": "the server's own PQL parse + optimize + plan "
    "digest",
    "phase.segmentAcquire": "segment refcounts taken, missing segments "
    "found, query views built",
    "phase.workerWake": "scheduler worker has the result -> the thread "
    "that waits for it runs again",
    "phase.laneWake": "lane thread delivered -> the waiting worker runs "
    "again",
    "phase.serverBookkeeping": "cost meters, plan stats, freshness and "
    "backpressure stamps, made before the reply is encoded",
    "phase.serializeResult": "DataTable encode",
    "phase.laneQueue": "lane submit to the launch call: queue and "
    "batch-formation wait, per dispatch",
    "phase.laneDispatch": "the launch call on the lane thread: jit "
    "dispatch + input H2D (a first launch: trace + compile)",
    "phase.laneDeliver": "launch call returned to waiters delivered: compile "
    "timeline, coalescing set and meters under the lane lock",
    "phase.kernelPrep": "kernel lookup, batch spec and eager input upload "
    "between plan build and lane submit",
    "phase.deviceWait": "block_until_ready on the launch's output",
    "phase.d2hUnpack": "np.asarray of the packed buffer + unpack",
    "phase.joinExtract": "join side extraction (remote or local)",
    "lane.deviceBusy": "closed occupancy windows, cumulative: launch call "
    "entered to output seen ready, the union over outstanding launches",
    # fair-share scheduling plane (per-table DRR queues)
    "fairshare.activeTables": "tables with a non-empty scheduler queue",
    "fairshare.shed": "submits shed by the global or per-table "
    "fair-share pending cap (210 on the wire)",
    "phase.*": "per-stage executor phase timers (staging, planBuild, "
    "prune, kernelPrep, laneWait, planExec, deviceWait, d2hUnpack, finalize, "
    "indexPath, bitslicedPath, hostPath, hostFailover)",
    "heal.deviceFailures": "device launch failures (classified)",
    "heal.deviceRetries": "transient device failures retried on device",
    "heal.hostFailovers": "queries transparently served via the host path",
    "heal.poisonSkips": "queries that skipped a quarantined device plan",
    "heal.resourceExhausted": "device allocation failures healed by "
    "residency demotion + retry (never poisoned)",
    "heal.auditQuarantines": "(plan digest, tier) pairs quarantined by "
    "the shadow differential auditor (wrong answer caught)",
    "heal.auditTierSkips": "queries steered off an audit-quarantined "
    "serving tier (answered by the next tier / host)",
    "lane.depth": "device-lane queue depth (lane-group servers: summed "
    "over every lane)",
    "lane.inflight": "device-lane launches currently inside the launch call",
    "lane.open": "completed dispatches still coalescible (program running)",
    "lane.dispatches": "kernel launches issued by the device lane(s)",
    "lane.coalesced": "queries coalesced onto an identical in-flight dispatch",
    "lane.shed": "lane waiters shed at dequeue (deadline expired)",
    "lane.deviceFailures": "launch failures surfaced by the lane",
    "lane.restarts": "lane threads restarted by the stall watchdog",
    # mesh execution plane (engine/mesh.py + dispatch.LaneGroup): lane
    # groups expose per-lane twins of every lane series at lane.<i>.*,
    # and the topology itself is gauged
    "lane.*.depth": "per-chip-group lane queue depth (lane.<i>.depth)",
    "lane.*.open": "per-lane completed dispatches still coalescible",
    "lane.*.inflight": "per-lane launches inside the launch call",
    "lane.*.*": "per-lane twins of the lane.* meters "
    "(lane.<i>.dispatches/coalesced/shed/deviceFailures/restarts)",
    "mesh.lanes": "chip-group lanes this server serves with",
    "mesh.devices": "devices across every chip group",
    "mesh.devicesPerLane": "chips per lane group (mesh shape)",
    # cross-query micro-batching tier (engine/dispatch.py BatchSpec):
    # same-plan distinct-literal dispatches stacked into one vmapped
    # launch; occupancy = batch.queries / batch.launches
    "batch.launches": "batched kernel launches (>= 2 members stacked)",
    "batch.queries": "queries carried by batched launches (members)",
    "batch.windowClosedFull": "batch windows closed by reaching the "
    "member cap (PINOT_TPU_BATCH_MAX / the per-plan row-budget cap)",
    "batch.windowClosedTimeout": "batch windows closed by the bounded "
    "formation window expiring (PINOT_TPU_BATCH_WINDOW_MS)",
    "batch.windowClosedIdle": "batches launched without a window wait "
    "(peers already queued; the lane never idles waiting for demand)",
    # ingest-aware result cache (engine/rescache.py; opt-in via
    # PINOT_TPU_RESULT_CACHE=1)
    "rescache.hits": "queries answered from the result cache (zero "
    "device/host work, freshness fenced by staging tokens)",
    "rescache.misses": "cacheable queries that executed (and stored)",
    "rescache.puts": "results stored into the cache",
    "rescache.invalidations": "invalidation events (LLC offset "
    "advancement or segment set change)",
    "rescache.staleEvictions": "cached entries dropped because the "
    "data that produced them was superseded (staleness fence)",
    "rescache.entries": "result-cache entries currently resident",
    "rescache.bytes": "bytes pinned by resident result-cache entries",
    "rescache.enabled": "1 while the result cache is enabled "
    "(PINOT_TPU_RESULT_CACHE)",
    # cost-accounting plane: per-query cost totals on this server
    "cost.docsScanned": "documents scanned by queries on this server",
    "cost.bytesScanned": "column bytes touched by queries on this server",
    "cost.deviceMs": "per-query device-kernel ms (cost vector)",
    "cost.hostMs": "per-query host-path ms (cost vector)",
    "cost.tier.*": "per-serving-tier segment counts from the cost vector "
    "(segmentsPruned/Postings/Bitsliced/Zonemap/FullScan/Host/StarTree) — "
    "the series /debug/plans tier mixes reconcile against",
    # the value pruner (engine/pruner.py): segments a query was handed,
    # and those its filter can match no row of, left out of every tier's
    # work and counted with the pruned
    "phase.prune": "segment pruning: upstream's three verdicts and the "
    "star-tree routing of every query (a span), and the value verdict where "
    "a query derives it, a repeated text finding it with its prepared entry "
    "(a second stretch of prune, between two of phase.staging)",
    "prune.segments.offered": "segments queries were handed, before any pruner "
    "(marked by the count, one query at a time)",
    "prune.segments.value": "segments whose own dictionaries the filter empties "
    "(pruner.value_dead): kept among the table's segments, scanned by no tier",
    # which rung of the ladder answered (engine/ladder.py TIERS; marked
    # in executor._finish_tier, one mark a query the ladder answered)
    "tier.answered.*": "queries answered by the serving tier of that name: "
    "postings, bitsliced, host (a forced host answer, a failover's, or a "
    "quarantined or off-device plan's from inside the device rung) or device",
    # bit-sliced bulk-bitwise filter tier (engine/bitsliced.py, r17)
    "filter.bitsliced.planes": "packed bit-planes evaluated by bit-sliced "
    "kernels (filter + fused-aggregate planes)",
    "filter.bitsliced.fusedAggs": "aggregates answered by popcount-fused "
    "plane sums inside the bit-sliced kernel (no index materialization)",
    "filter.bitsliced.bytes": "packed bit-plane bytes streamed by "
    "bit-sliced kernel launches",
    # workload-introspection plane (utils/planstats.py, /debug/plans)
    "plan.recorded": "instance requests folded into the per-plan-digest "
    "stats registry",
    "plan.explains": "EXPLAIN plan requests answered without execution",
    "plan.digests": "distinct plan-shape digests currently tracked",
    # the executor's prepared-query memo (engine/executor.py _Prepared):
    # one mark a query that reached the tier ladder, so the three add up
    # to those queries
    "plan.prepared.hit": "queries whose tier verdicts, static plan, query "
    "inputs and block ids were kept from an earlier query of the same text "
    "over the same segments, placement and settings",
    "plan.prepared.miss": "queries that derived them (and kept them)",
    "plan.prepared.stale": "queries that found them kept against a staged "
    "table that had since been demoted and staged anew, and derived the "
    "device part again",
    "plan.prepared.entries": "prepared queries currently kept",
    # which lowering a dense group-by's occupancy and sums took, one
    # mark a launch (engine/kernel.py groupby_lowering, marked where the
    # launch's laneDispatch span gets its ``groupby=`` tag)
    "groupby.lowering.onehot": "group-by launches on the one-level "
    "one-hot contraction (K <= MATMUL_GROUP_CAP)",
    "groupby.lowering.radix": "group-by launches on the two-level "
    "(radix-128) contraction with float32-faithful weights (every K above "
    "MATMUL_GROUP_CAP; above RADIX_GROUP_CAP over the rows in key order: "
    "groupby.operands.sorted)",
    "groupby.lowering.scatter": "group-by launches on the serialised "
    "scatter (the CPU backend)",
    "groupby.lowering.runs": "group-by launches over more keys than a "
    "dense holder takes (above MAX_GROUP_CAPACITY): the table's rows sorted "
    "by group id once, a run a group, and the trim's candidates, the live "
    "count and the digest made on the device (engine/kernel.py "
    "_reduce_group_runs; ``groupby=runs``)",
    "groupby.operands.loop": "group-by launches whose filter mask, key "
    "and weight columns are built inside the group-by's row loop "
    "(engine/kernel.py groupby_operands; the launch's ``operands=`` tag)",
    "groupby.operands.sorted": "group-by launches over more keys than "
    "RADIX_GROUP_CAP, whose rows are sorted by group id with their weight "
    "columns so that a block of them contracts over a window of keys "
    "(engine/kernel.py groupby_operands; ``operands=sorted``)",
    # how a zone-tier launch read its candidate blocks, one mark a launch
    # (engine/kernel.py zone_blocks; the launch's ``blocks=`` tag)
    "zone.blocks.inplace": "zone-tier launches whose program loops over "
    "the candidate block ids and slices the staged columns where they lie",
    "zone.blocks.gathered": "zone-tier launches whose program copies the "
    "candidate blocks out first (selection, distinct pairs, sorted HLL, or "
    "a dense holder too large to fold a block at a time)",
    "groupby.slots.shared": "rows of a dense group-by's float states "
    "that its aggregates share (a sum read by sum and avg, an avg's count "
    "on the occupancy row), one mark a row a launch (engine/kernel.py "
    "groupby_cells; the launch's ``cells=`` tag gives K x m)",
    # what a device group-by's finalize found and kept, marked by the
    # count, summed over queries (engine/executor.py _finalize, _kept_group_keys;
    # ``numGroupsLive``, ``numGroupsKept`` and the digest ``groupStateSumSq``
    # on the reply's cost vector, ``groups=<live>`` on the group-by's
    # ``finalize`` span)
    "groupby.groups.live": "groups with a row in the fetched state of a "
    "device group-by, before the per-server trim",
    "groupby.groups.kept": "groups left after the per-server trim "
    "(max(5 x TOP, 100) an aggregate, and boundary ties)",
    "groupby.keySpaceCells": "cells a device group-by's plan sized its group "
    "space at (the product of the group columns' table cardinalities, "
    "whatever the filter leaves; engine/plan.py group_capacity), marked by "
    "the count a reply beside groupby.groups.live",
    "groupby.stateFetchBytes": "bytes of group state a device group-by's "
    "finalize was handed from the chip, marked by the count a reply, every "
    "lowering: a dense holder's K cells an aggregate and the occupancy, or "
    "the runs lowering's candidates (kilobytes whatever K is)",
    "groupby.forcedHost.keySpace": "group-bys sent to the host before "
    "staging because the key space passes the key dtype (2^30 without x64)",
    "groupby.forcedHost.aggregate": "group-bys over more than "
    "MAX_GROUP_CAPACITY keys sent to the host because an aggregate has no "
    "run form (min, max, minmaxrange, distinctcount*, percentile*; "
    "engine/plan.py group_runs_host_reason names it in EXPLAIN)",
    "groupby.forcedHost.multiValueKey": "the same, because a group column "
    "is multi-valued",
    "groupby.forcedHost.noTopN": "the same, because the query has no TOP n "
    "to trim by",
    "groupby.forcedHost.mesh": "the same, because the query would run "
    "sharded over a mesh (PINOT_TPU_MESH_SHAPE): the sort is one chip's",
    "groupby.forcedHost.measures": "the same, because its sums and averages "
    "read more columns than the sort carries (kernel._SORTED_COLS_MAX)",
    "phase.globalDictBuild": "a column's table-level dictionary (the "
    "sorted union of the segments' dictionaries) and the remap of each "
    "segment's ids into it, built once a segment set by the first query "
    "that needs the column's global ids (engine/context.py), inside "
    "phase.staging; timer and annotation, no span",
    "phase.groupTrim": "inside phase.finalize of a device group-by: from "
    "the fetched state to the kept keys (nonzero over the occupancy, the "
    "order values and their sum of squares, and trim_group_candidates' "
    "selection around the cut: no sort of the state); timer and "
    "annotation, no span",
    # the form a launch's selection found its k candidates a segment in,
    # one mark a launch that carries one (engine/kernel.py
    # selection_lowering, which _selection_outputs asks too; the launch's
    # ``selection=`` tag), and what its finalize was handed
    "selection.lowering.first": "selection launches without an ORDER BY: "
    "the first k matching rows a segment, in doc order",
    "selection.lowering.topk": "selection launches whose sort columns' "
    "table ordinals pack into one key (their cardinalities' product at "
    "most config.max_key_space()): one lax.top_k a segment over all its rows",
    "selection.lowering.sort": "selection launches over a wider key: a "
    "stable multi-operand lax.sort of every row of every segment, one "
    "int32 operand a sort column, to keep k rows a segment",
    "selection.candidates": "valid candidate rows the device handed the "
    "host for a selection, marked by the count (segments x k as the "
    "program stands)",
    "phase.selectionRows": "inside phase.finalize of a device selection: "
    "each valid candidate's row gathered from its segment and decoded, "
    "the sort values and the selected columns "
    "(executor._finalize_selection); timer and annotation, no span",
    # which lowering a launch's HLL aggregates took, grouped or not, one
    # mark a launch that carries one (engine/kernel.py hll_lowering,
    # which the kernel builder and zone_blocks ask too; the launch's
    # ``hll=`` tag)
    "hll.lowering.matmul": "launches whose distinctcounthll registers come "
    "from the (group, register, rank) occupancy contraction on the matrix "
    "unit (ungrouped on the chip; up to 16 groups)",
    "hll.lowering.sort": "launches whose grouped distinctcounthll packs "
    "(group, register, rank) into one int32 key a row, sorts them a "
    "segment (in parts past _HLL_SORT_PART rows: hll.sort.parts) and "
    "sums each run's last rank on the matrix unit (17 to 65,536 groups, "
    "on the chip)",
    "hll.sort.parts": "on a launch that takes the sort lowering, the "
    "parts a segment's packed keys are sorted in, marked by the count "
    "(engine/kernel.py hll_sort_parts: 1 up to _HLL_SORT_PART keys a "
    "segment, so this meter over hll.lowering.sort is the parts a launch)",
    "hll.lowering.scatter": "launches whose distinctcounthll registers "
    "come from the serialised scatter-max (more groups than the packed "
    "key holds; every form on the CPU backend)",
    "hll.lowering.pairs": "launches whose grouped distinctcounthll emits "
    "(slot, register x 64 + rank) pairs for the sort-dedup reduce (a "
    "group space whose dense registers pass the value state's budget)",
    "phase.hllEstimate": "inside phase.finalize (and phase.groupTrim) of a "
    "device group-by with distinctcounthll: every live group's registers "
    "to its estimate, one numpy pass (engine/hll.py "
    "estimate_from_registers); timer and annotation, no span",
    "phase.hllDerive": "inside phase.staging: a column's per-row HLL "
    "(register, rank) streams derived on the host, the dictionaries "
    "hashed (engine/hll.py dictionary_tables) and fanned out through the "
    "forward indexes (engine/device.py _hll_streams); timer and "
    "annotation, no span",
    # arithmetic inside an aggregate (sum(a*(1-b))): one mark a query
    # whose plan holds a compound expression, by where it was answered
    "agg.expr.device": "queries with an expression inside an aggregate "
    "answered by a device program (the expression is evaluated in the "
    "kernel's row loop, engine/kernel.py _row_values)",
    "agg.expr.host": "queries with an expression inside an aggregate "
    "answered by a host tier (postings, forced host path, failover), in "
    "float64 (engine/host_fallback.py)",
    # compile timeline (engine/dispatch.py lane registry): first-call
    # launch of a device-plan digest pays trace + XLA compile
    "compile.cold": "device-plan digests launched for the first time "
    "(cold compile measured; persistent-cache hits and prewarmed shapes "
    "excluded — serving-path genuine colds only)",
    "compile.warm": "device launches that reused an already-compiled plan",
    "compile.firstCallMs": "first-call (compile-inclusive) launch wall ms "
    "per device-plan digest",
    # warm-start resilience (engine/compilecache.py + server/prewarm.py):
    # the persistent compile cache splits the first-launch timeline into
    # cold / persistent / prewarmed, and the prewarm worker drives
    # compiles off the serving path
    "compile.persistentHit": "first launches of a plan digest whose XLA "
    "binary the persistent compile cache already held (restart warmth)",
    "compile.persistentMiss": "genuine cold compiles while the persistent "
    "cache was enabled (the entry is written for the next restart)",
    "compile.prewarmed": "plan digests compiled by the background prewarm "
    "worker before any serving query needed them",
    "prewarm.shapes": "workload plan shapes considered by prewarm passes",
    "prewarm.compiled": "prewarm shapes actually compiled into a lane's "
    "registry (digest-exact, off the serving path)",
    "prewarm.skipped": "prewarm shapes skipped (already compiled, "
    "off-device plan, no exemplar, or deadline-capped)",
    "prewarm.failed": "prewarm shapes that errored (parse/build/compile); "
    "the shape compiles lazily — and honestly cold — on the serving path",
    "server.warming": "1 while the prewarm worker is rebuilding the "
    "compile working set (the heartbeat-reported readiness flag)",
    "compile.costAnalyses": "device-plan digests whose static XLA cost "
    "analysis (flops / bytes accessed) landed in the compile registry",
    "compile.costAnalysisUnavailable": "device-plan digests whose backend "
    "reported no usable static cost analysis (explicit 'unavailable')",
    # device utilization & profiling plane (ISSUE 10): windowed lane
    # occupancy, cumulative transfer totals, and achieved-vs-peak
    # roofline rates against utils/platform.py declared peaks
    "device.util.busyFraction": "fraction of the recent window the device "
    "lane spent inside kernel launch calls (0 when idle)",
    "device.util.avgQueueDepth": "time-weighted average device-lane queue "
    "depth over the recent window",
    "device.util.h2dBytes": "cumulative host->device transfer bytes "
    "(segment staging + batched query-input uploads)",
    "device.util.d2hBytes": "cumulative device->host transfer bytes "
    "(packed result fetches + raw-path output reads)",
    "device.util.achievedBytesPerSec": "achieved device scan bytes/s over "
    "the recent roofline window (deviceBytes / measured deviceMs)",
    "device.util.achievedFlopsPerSec": "achieved FLOP/s over the recent "
    "roofline window (static flops per exec x execs / measured deviceMs)",
    "device.util.rooflineFraction": "best-utilized-resource achieved/peak "
    "fraction (null when no platform peak is declared)",
    # on-demand deep profiling (server/profiler.py jax.profiler bracket)
    "profile.starts": "profile capture start requests (ref-counted joins "
    "included)",
    "profile.stops": "profile capture stop requests released",
    "profile.autoStops": "captures force-stopped by the auto-stop deadline "
    "(client died mid-capture)",
    "profile.failedStarts": "capture starts that failed inside the "
    "profiler trace backend",
    "profile.active": "1 while a jax.profiler trace capture is active",
    # HBM staging ledger (engine/device.py LEDGER; per-process)
    "hbm.stagedBytes": "bytes of segment arrays currently staged in HBM",
    "hbm.highWatermarkBytes": "high-watermark of staged HBM bytes",
    "hbm.stagedTables": "staged-table cache entries currently resident",
    "hbm.evictedBytes": "staged bytes released by cache evictions",
    "hbm.qinputCacheBytes": "bytes pinned by the device query-input cache",
    # tiered residency (engine/residency.py RESIDENCY; per-process):
    # hot = HBM, warm = host-RAM packed snapshots, cold = on-disk
    "residency.hotBytes": "staged bytes resident in the hot (HBM) tier",
    "residency.warmBytes": "packed snapshot bytes in the warm (host) tier",
    "residency.coldBytes": "packed snapshot bytes spooled to the cold "
    "(disk) tier",
    "residency.hotTables": "staged-table entries in the hot tier",
    "residency.warmTables": "staged-table entries in the warm tier",
    "residency.coldTables": "staged-table entries in the cold tier",
    "residency.pressure": "hot bytes / configured HBM cap (0 = uncapped)",
    "residency.demotions": "hot->warm demotions (HBM freed, layout kept)",
    "residency.promotions": "warm/cold->hot promotions (zero re-encode)",
    "residency.coldDemotions": "warm->cold disk spills",
    "residency.coldLoads": "cold->warm disk reads (promotion or prefetch)",
    "residency.pressureDemotions": "demotions forced by a "
    "RESOURCE_EXHAUSTED heal rather than a configured cap",
    "residency.prefetches": "async cold->warm lifts ahead of dispatch",
    # distributed-join plane (engine/join.py): per-phase server counters
    "join.extracts": "join side-extraction phase requests served",
    "join.execs": "join executions (hash build + probe) served",
    "join.buildRows": "build-side rows inserted into join hash tables",
    "join.probeRows": "probe-side rows probed against join hash tables",
    "join.shuffleBytes": "shuffle-exchange bytes RECEIVED by this server "
    "(the skew-balance observable: compare across servers)",
    "join.broadcastBytes": "broadcast build-side bytes received",
    # correctness & freshness audit plane (ISSUE 19): shadow
    # differential sampling against the host oracle + event-time
    # watermarks per consuming partition
    "audit.samples": "completed queries re-executed against the host "
    "oracle by the shadow auditor (1-in-N sampled, off the serving path), "
    "counted when the pass has finished",
    "audit.divergences": "shadow re-executions whose stripped payload "
    "differed from the served answer (wrong answer detected)",
    "audit.quarantines": "(plan digest, tier) quarantines placed by the "
    "shadow auditor on divergence",
    "audit.dropped": "audit samples dropped (queue full or sampler "
    "budget exhausted — auditing never blocks serving)",
    "audit.errors": "shadow re-executions that errored before a "
    "comparison (not counted as divergence)",
    "audit.offered": "every N-th eligible completed answer (the 1-in-N "
    "winners), counted before the sampler budget and the queue bound: "
    "samples + dropped + errors + those still queued or in their pass",
    "audit.queueDepth": "shadow-audit jobs currently queued",
    "audit.shadowMs": "wall ms of one whole pass of the streamed host "
    "oracle (all its steps, and what the worker waited between them); the "
    "pinot:auditPass annotation is the same interval",
    "audit.stepMs": "processor ms of the auditor's thread in one step of "
    "the oracle's pass (one block of config.HOST_BLOCK_ROWS rows): what "
    "it can have held the interpreter for; not wall time, which reads a "
    "stall of the machine into the step it falls in (the thread clock "
    "ticks in 10 ms on some hosts)",
    "audit.stepMaxMs": "longest step among audit.stepMs's retained samples",
    "audit.detectMs": "query-completion to divergence-detection wall ms",
    "freshness.lag.*": "per-(table, partition) event-time lag ms "
    "(now - max ingested event time)",
    # ingest observability (realtime consumers hosted on this server)
    "ingest.rowsConsumed": "stream rows consumed into mutable segments",
    "ingest.commitMs": "segment commit latency (convert + persist round)",
    "ingest.lag.*": "per-(table, partition) consumer lag in rows "
    "(latest available offset - consumed offset)",
    # ingest backpressure plane (realtime/backpressure.py governor)
    "ingest.paused": "1 while the ingest governor holds consumption "
    "above a memory watermark",
    "ingest.paused.*": "per-(table, partition) consumer pause flag "
    "(1 = held by the backpressure governor)",
    "ingest.pauses": "ingest pause events (high watermark crossed)",
    "ingest.resumes": "ingest resume events (back under low watermarks)",
    # partition-parallel ingest plane (realtime/pool.py, r15)
    "ingest.pool.steps": "cooperative consumer steps driven by the "
    "ingest pool's bounded workers",
    "ingest.pool.errors": "consumer steps that raised (consumer parked "
    "with a backoff, workers unaffected)",
    "ingest.pool.workers": "worker threads in the ingest consumer pool "
    "(PINOT_TPU_INGEST_CONSUMERS)",
    "ingest.pool.consumers": "realtime consumers currently registered "
    "with the ingest pool",
    # partition-tolerance plane (ISSUE 9): serving-lease fence on write
    # authority + controller reachability while riding out a partition
    "lease.held": "1 while this server holds (or never needed) a "
    "serving lease — write authority",
    "lease.renewals": "serving-lease renewals from heartbeat replies",
    "lease.expiries": "serving-lease expiries (partitioned past the "
    "lease window; write authority self-fenced)",
    "lease.blockedCommits": "completion/commit rounds frozen because "
    "the serving lease expired",
    "lease.blockedTransitions": "CONSUMING transitions deferred "
    "(unacked) while the serving lease was expired",
    # SLO & tail-latency attribution plane (ISSUE 11)
    "history.ticks": "metric-history samples recorded into the ring "
    "(utils/timeseries.py, served at /debug/history)",
    "history.series": "distinct series in the latest history sample",
    "flightrec.dumps": "flight-recorder bundles written on notable events",
    "flightrec.bundles": "flight-recorder bundles currently on disk",
    "controller.unreachable": "1 while heartbeats to the controller "
    "are failing (riding out a partition on local state)",
    "controller.heartbeatFailures": "failed controller heartbeats "
    "(full-jitter retried)",
    "netfaults.*": "injected link faults observed by this role's "
    "transports (dropped/replyDropped/delayed/duplicated/flaky)",
}

CONTROLLER_METRIC_CATALOG: Dict[str, str] = {
    "instanceRegistrations": "instance register calls accepted",
    "heartbeats": "instance heartbeats received",
    "instancesMarkedDead": "instances declared dead on missed heartbeats",
    "transitionAcks": "segment-transition acks processed",
    "clusterStatePolls": "full cluster-state snapshots served to brokers",
    "clusterStateCacheHits": "cluster-state polls answered from the "
    "version-keyed snapshot cache (no per-poll table walk)",
    "segmentUploads": "segments stored via the upload paths",
    "segmentCommits": "realtime segments committed through the LLC FSM",
    "segmentCommitMs": "controller-side commit persistence latency",
    "gateway.flaps": "dead->alive instance cycles admitted (flap hysteresis)",
    "manager.*.failures": "periodic-manager run_once failures, by manager",
    "stabilizer.rounds": "self-stabilizer convergence rounds executed",
    "stabilizer.replicasAdded": "replicas re-replicated onto live servers",
    "stabilizer.replicasDropped": "dead/draining replicas removed from ideal "
    "state after coverage was restored",
    "stabilizer.consumingReassigned": "consuming segments retired for "
    "re-creation on a live server at the committed offset",
    "stabilizer.graceDeferrals": "dead servers whose re-replication was "
    "deferred inside the grace window",
    "stabilizer.leaseDeferrals": "dead-looking servers whose replicas "
    "were NOT moved because their serving lease had not expired "
    "(possibly alive-but-partitioned)",
    "stabilizer.underReplicatedSegments": "segments currently below target "
    "replication on live servers",
    "stabilizer.drainingInstances": "instances currently draining",
    "stabilizer.deadServers": "servers currently tracked as dead",
    # proactive skew-aware rebalance plane (r15, controller/stabilizer.py)
    "rebalance.evaluations": "skew evaluations run (healthy rounds only — "
    "healing always yields first)",
    "rebalance.skewDeferrals": "skewed evaluations deferred inside the "
    "hysteresis window (one hot minute moves nothing)",
    "rebalance.movesStarted": "make-before-break phase-1 replica adds "
    "started by the rebalance planner",
    "rebalance.movesCompleted": "surplus source replicas dropped after "
    "the external view proved coverage (phase 2)",
    "rebalance.movesAborted": "moves cancelled by dropping an ERROR "
    "destination replica instead of the source",
    "rebalance.pendingMoves": "make-before-break moves currently between "
    "phase 1 (added) and phase 2 (source dropped)",
    "rebalance.imbalanceRatio": "worst per-tenant max/mean doc-x-cost "
    "load ratio seen by the last skew evaluation",
    "rebalance.prewarmDeferrals": "replica removals deferred because the "
    "surviving cover was still prewarming its compile working set "
    "(bounded by PINOT_TPU_PREWARM_TIMEOUT_S)",
    "aliveServers": "registered server instances currently alive",
    "aliveBrokers": "registered broker instances currently alive",
    "deadInstances": "registered instances currently marked dead",
    "tables": "physical tables managed",
    # partition-tolerance plane (ISSUE 9): serving leases + the
    # cluster-wide epoch fence on the commit plane / property store
    "lease.granted": "serving leases granted on heartbeat/registration "
    "replies",
    "fence.epoch": "this controller's fencing incarnation (property "
    "store cluster/epoch)",
    "fence.staleEpochRejections": "commit-plane calls typed-rejected "
    "for carrying a stale controller epoch",
    "fence.leaseRejections": "segmentCommit uploads rejected because "
    "the committer's serving lease had expired",
    "fence.committerReElections": "LLC committers re-elected after the "
    "elected one lost its serving lease mid-protocol",
    "netfaults.*": "injected link faults observed by this role's "
    "transports (dropped/replyDropped/delayed/duplicated/flaky)",
    # SLO & tail-latency attribution plane (ISSUE 11)
    "history.ticks": "metric-history samples recorded into the ring "
    "(utils/timeseries.py, served at /debug/history)",
    "history.series": "distinct series in the latest history sample",
    "flightrec.dumps": "flight-recorder bundles written on notable events",
    "flightrec.bundles": "flight-recorder bundles currently on disk",
    # correctness audit plane (ISSUE 19): periodic cross-replica
    # checksum sweep over registered segment CRCs
    "audit.sweep.runs": "cross-replica CRC sweep rounds completed",
    "audit.sweep.segmentsChecked": "segment replica-sets compared by "
    "the latest sweeps",
    "audit.sweep.skippedInstances": "instances skipped by sweeps "
    "(unreachable or no admin URL)",
    "audit.crcMismatches": "segments whose replicas currently disagree "
    "on content CRC (cross-replica divergence)",
    # disaster-recovery plane (ISSUE 20): journaled metadata durability
    "durability.journalAppends": "property-store mutations framed into "
    "the op journal (controller/journal.py)",
    "durability.snapshots": "full-state journal snapshots cut "
    "(periodic + forced backup-prep)",
    "durability.corruptRecords": "property-store record files found "
    "truncated/garbled and quarantined aside",
    "durability.recordsHealed": "property-store records regenerated "
    "from the journal-recovered state",
    "durability.journalTornTailTruncations": "torn journal tail frames "
    "truncated during recovery (crash mid-append)",
    "durability.corruptSnapshots": "journal snapshots found unreadable "
    "and quarantined (recovery fell back to the log)",
    # disaster-recovery plane (ISSUE 20): deep-store scrub + reverse
    # replication of lost/corrupt durable copies
    "deepstore.scrub.runs": "deep-store scrub rounds completed",
    "deepstore.scrub.copiesChecked": "durable copies CRC re-verified "
    "by scrub rounds",
    "deepstore.scrub.budgetDenied": "scrub checks skipped by the "
    "shared sampler budget (serving protected)",
    "deepstore.corruptCopies": "durable copies found lost or corrupt",
    "deepstore.repairs": "durable copies re-replicated from a live "
    "server's verified replica (reverse replication)",
    "deepstore.repairFailures": "corrupt durable copies with no "
    "healthy donor replica available",
    "deepstore.suspectsReported": "store-copy suspects reported by "
    "server fetch paths (CRC-failing downloads)",
    "deepstore.suspectsPending": "store-copy suspects queued for the "
    "next scrub round",
    "*.missingReplicas": "per-table replicas missing from the external view",
    "*.errorReplicas": "per-table replicas in ERROR state",
    "*.percentSegmentsAvailable": "per-table % of segments with a live replica",
    "*.segmentCount": "per-table segment count",
}

METRIC_CATALOGS: Dict[str, Dict[str, str]] = {
    "broker": BROKER_METRIC_CATALOG,
    "server": SERVER_METRIC_CATALOG,
    "controller": CONTROLLER_METRIC_CATALOG,
}
