"""Broker: PQL front door — parse, route, scatter-gather, reduce.

The reference flow (``BrokerRequestHandler.java:139``): compile PQL ->
optimize -> look up routing table -> scatter InstanceRequests ->
gather DataTables (per-server errors become response exceptions, the
healthy partials still reduce, :443-460) -> BrokerReduceService ->
JSON.  Hybrid tables federate into offline+realtime sub-queries split
at the time boundary (:280-329; see ``pinot_tpu.broker.time_boundary``).

Scatter-gather fans out on a thread pool with a per-request timeout
(``ScatterGatherImpl.java:80``); replica choice already happened when
the routing table was built.

RESILIENCE LAYER (beyond the reference, which degrades a query on any
server failure): the gather loop is an event loop over attempt futures
that (a) **fails over** — a transport error, per-attempt timeout, or
retryable server error (210 saturated / 220 shutting down) re-issues
the failed attempt's segment set to an alternate replica with capped
exponential backoff, under the query's total deadline; (b) **hedges** —
when enabled, a straggling attempt's segment set is speculatively
re-sent to a second replica after a percentile-based delay and the
first reply wins; (c) feeds a per-server **circuit breaker**
(``broker.health``) consulted by routing so repeat offenders drop out
of covers before they fail queries; (d) propagates the **remaining**
deadline into every (re-)issued InstanceRequest so servers shed work
the broker has already given up on; and (e) reports **graceful
degradation** honestly — segments still unserved after retries flip
``partialResponse`` and count into ``numSegmentsUnserved`` instead of
hiding inside exception strings.
"""
from __future__ import annotations

import concurrent.futures
import json
import logging
import math
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Set, Tuple
from urllib.parse import parse_qs, urlparse

from pinot_tpu.common.datatable import (
    deserialize_result,
    serialize_instance_request,
)
from pinot_tpu.common.request import BrokerRequest, FilterOperator, FilterQueryTree, RangeSpec
from pinot_tpu.common.response import BrokerResponse, ErrorCode, QueryException
from pinot_tpu.engine.reduce import reduce_to_response
from pinot_tpu.engine.results import IntermediateResult
from pinot_tpu.pql import PqlParseError, optimize_request, parse_pql
from pinot_tpu.broker.health import ServerHealthTracker
from pinot_tpu.broker.querylog import SlowQueryLog
from pinot_tpu.broker.routing import RoutingTableProvider
from pinot_tpu.broker.time_boundary import TimeBoundaryService
from pinot_tpu.utils.metrics import BrokerMetrics, prometheus_text
from pinot_tpu.utils.trace import NULL_TRACE, TraceContext, boundary, marked, measured, merge_scope

logger = logging.getLogger(__name__)

OFFLINE_SUFFIX = "_OFFLINE"
REALTIME_SUFFIX = "_REALTIME"

# server-reply error codes that mean "this replica cannot serve right
# now, another may" — the attempt fails over instead of degrading the
# query (fatal codes like QUERY_EXECUTION would fail identically on
# every replica and do not retry)
RETRYABLE_SERVER_CODES = frozenset(
    {
        ErrorCode.SERVER_SCHEDULER_DOWN,
        ErrorCode.SERVER_SHUTTING_DOWN,
        # "I don't hold the segments this request names" (e.g. a
        # colocated-join build side that moved): a replica may hold
        # them locally, so the broker re-covers there before degrading
        ErrorCode.SERVER_SEGMENT_MISSING,
    }
)


class _Batch:
    """One segment set bound for one server: the unit of scatter,
    failover, and hedging.  A failover spawns child batches (possibly
    splitting segments across replicas); the parent is then superseded."""

    __slots__ = (
        "table", "pql", "segments", "server", "excluded",
        "reissues", "errors", "done", "inflight",
        "hedged", "first_sent", "order",
    )

    # NOTE: join-phase context rides per-submit via _scatter_gather's
    # ``extra_fn(server)`` — derived from the target server at send
    # time so failover children automatically get the right build
    # segment list for THEIR server (broker/joinplan.py)

    def __init__(
        self,
        table: str,
        pql: str,
        segments: List[str],
        server: str,
        excluded: Optional[Set[str]] = None,
        reissues: int = 0,
        errors: Optional[List[QueryException]] = None,
        order: int = 0,
    ) -> None:
        self.table = table
        self.pql = pql
        self.segments = list(segments)
        self.server = server
        self.order = order
        self.excluded: Set[str] = set(excluded or ()) | {server}
        self.reissues = reissues
        self.errors: List[QueryException] = list(errors or ())
        self.done = False
        self.inflight = 0
        self.hedged = False
        self.first_sent = 0.0


class BrokerRequestHandler:
    def __init__(
        self,
        transport,
        server_addresses: Dict[str, Tuple[str, int]],
        routing: Optional[RoutingTableProvider] = None,
        time_boundary: Optional[TimeBoundaryService] = None,
        timeout_ms: float = 15_000.0,
        name: str = "broker0",
        retry_attempts: int = 2,
        retry_backoff_ms: float = 25.0,
        retry_backoff_cap_ms: float = 1_000.0,
        hedge_delay_ms: float = 0.0,
        hedge_latency_percentile: float = 95.0,
        hedge_min_quota_headroom: float = 0.1,
        health: Optional[ServerHealthTracker] = None,
        max_inflight_per_table: Optional[int] = None,
        admission_window_init: Optional[float] = None,
        admission_window_max: Optional[float] = None,
        admission_pending_high_water: Optional[float] = None,
    ) -> None:
        self.transport = transport
        self.server_addresses = dict(server_addresses)
        self.routing = routing or RoutingTableProvider()
        self.time_boundary = time_boundary or TimeBoundaryService()
        self.timeout_ms = timeout_ms
        self.name = name
        self.metrics = BrokerMetrics(name)
        self.querylog = SlowQueryLog()
        self.retry_attempts = max(0, retry_attempts)
        self.retry_backoff_ms = retry_backoff_ms
        self.retry_backoff_cap_ms = retry_backoff_cap_ms
        self.hedge_delay_ms = hedge_delay_ms  # 0 disables hedging
        self.hedge_latency_percentile = hedge_latency_percentile
        self.hedge_min_quota_headroom = hedge_min_quota_headroom
        self.health = health or ServerHealthTracker()
        # controller-declared draining servers (deliberate decommission,
        # NOT failures): routing views already exclude them; kept here so
        # /serverhealth can tell an operator drain from a sick circuit
        self.draining_servers: Set[str] = set()
        from pinot_tpu.broker.admission import AdmissionController
        from pinot_tpu.broker.quota import QueryQuotaManager

        self.quota = QueryQuotaManager()
        # adaptive admission: QPS bucket + per-table in-flight cap +
        # AIMD per-server windows fed by reply backpressure snapshots
        # (broker/admission.py) — ONE front door for every shed tier
        self.admission = AdmissionController(
            quota=self.quota,
            max_inflight_per_table=max_inflight_per_table,
            initial_window=admission_window_init,
            max_window=admission_window_max,
            pending_high_water=admission_pending_high_water,
            metrics=self.metrics,
        )
        self._request_id = 0
        self._id_lock = threading.Lock()
        # globally-unique request ids: broker name + a process-unique
        # token (two brokers sharing a default name, or one restarting,
        # can never reuse an id) + a per-broker sequence
        import uuid

        self._id_prefix = f"{name}-{uuid.uuid4().hex[:6]}"
        self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=16)
        # cost-accounting plane: broker-side totals of the merged per-
        # query cost vector, pre-registered so /metrics shows zeros
        # before first use (per-table table.<name>.* twins register on
        # the first query that names the table)
        for m in ("cost.docsScanned", "cost.bytesScanned"):
            self.metrics.meter(m)
        for t in ("cost.deviceMs", "cost.hostMs"):
            self.metrics.timer(t)
        # workload-introspection plane: per-plan-digest roll-up of every
        # merged response (utils/planstats.py) behind /debug/workload —
        # top-K by frequency and by cost, the "which plan shapes should
        # we batch?" answer.  Series pre-registered.
        from pinot_tpu.utils.planstats import PlanStatsStore

        self.planstats = PlanStatsStore()
        for m in ("workload.recorded", "explain.queries"):
            self.metrics.meter(m)
        self.metrics.gauge("workload.digests").set_fn(self.planstats.digest_count)
        # distributed join plane (broker/joinplan.py): strategy planner
        # + multi-phase exchange coordinator; registers its join.*
        # meters at construction
        from pinot_tpu.broker.joinplan import JoinCoordinator

        self.joinplan = JoinCoordinator(self)
        # SLO & tail-latency attribution plane (ISSUE 11): ONE history
        # thread snapshots this registry (+ the per-table SLO counters)
        # on a cadence; burn-rate evaluation and the flight-recorder
        # triggers ride its tick hook.  Tail sampling arms lightweight
        # tracing on EVERY query and keeps the merged span tree only
        # for slow/failed/partial/1-in-N completions (utils/tailsample).
        # All series pre-registered inside the constructors.
        from pinot_tpu.utils.flightrec import FlightRecorder
        from pinot_tpu.utils.slo import SloTracker
        from pinot_tpu.utils.tailsample import TailSampler
        from pinot_tpu.utils.timeseries import HistoryRecorder

        self.history = HistoryRecorder(self.metrics, metrics=self.metrics)
        self.slo = SloTracker(history=self.history, metrics=self.metrics)
        self.history.register_provider(self.slo.series)
        self.tail = TailSampler(metrics=self.metrics)
        self.flightrec = FlightRecorder(
            "broker",
            name,
            metrics=self.metrics,
            sources={
                "history": lambda: self.history.query(window_s=900),
                "slowQueries": self.querylog.snapshot,
                "tails": lambda: self.tail.snapshot(include_traces=True),
                "slo": self.slo.snapshot,
                "workload": lambda: self.workload_snapshot(top=20),
                "admission": self.admission.snapshot,
                # lazy: the replica auditor is constructed just below
                "audit": lambda: self.replica_audit.snapshot(),
            },
        )
        # correctness & freshness audit plane (ISSUE 19): background
        # replica divergence sampler (utils/audit.py, always-on unless
        # PINOT_TPU_AUDIT_REPLICA_N=0) + the event-time freshness
        # timer, pre-registered so /metrics shows the series at zero
        from pinot_tpu.utils.audit import ReplicaAuditor

        self.replica_audit = ReplicaAuditor(self)
        self.metrics.timer("freshness.lagMs")
        self._last_dropped = 0
        self._shed_burst_threshold = max(
            1, int(os.environ.get("PINOT_TPU_FLIGHTREC_SHED_BURST", "32"))
        )
        self.history.add_tick_hook(self._history_tick)

    @classmethod
    def from_conf(cls, transport, server_addresses, conf, **overrides) -> "BrokerRequestHandler":
        """Build a handler from a ``BrokerConf`` (pinot.broker.* keys),
        mapping the resilience knobs onto the scatter-gather layer."""
        kwargs = dict(
            timeout_ms=float(conf.timeout_ms),
            name=conf.instance_id,
            routing=RoutingTableProvider(num_tables=conf.routing_table_count),
            retry_attempts=conf.retry_attempts,
            retry_backoff_ms=conf.retry_backoff_ms,
            retry_backoff_cap_ms=conf.retry_backoff_cap_ms,
            hedge_delay_ms=conf.hedge_delay_ms,
            hedge_latency_percentile=conf.hedge_latency_percentile,
            hedge_min_quota_headroom=conf.hedge_min_quota_headroom,
            health=ServerHealthTracker(
                failure_threshold=conf.health_failure_threshold,
                penalty_ms=conf.health_penalty_ms,
            ),
            max_inflight_per_table=conf.admission_table_inflight,
            admission_window_init=conf.admission_window_init,
            admission_window_max=conf.admission_window_max,
            admission_pending_high_water=conf.admission_pending_high_water,
        )
        kwargs.update(overrides)
        return cls(transport, server_addresses, **kwargs)

    def set_server_address(self, server: str, address: Tuple[str, int]) -> None:
        self.server_addresses[server] = address

    def _next_request_id(self) -> str:
        with self._id_lock:
            self._request_id += 1
            n = self._request_id
        return f"{self._id_prefix}-{n}"

    # ------------------------------------------------------------------
    def open_trace(self) -> Tuple[str, TraceContext]:
        """A request id and its span tree for a front end that times
        boundaries outside ``handle_pql`` (the HTTP server's
        ``httpConnection`` and ``httpTotal``): enabled when the tail
        sampler is armed, as ``handle_pql`` would decide it."""
        request_id = self._next_request_id()
        if self.tail.armed:
            return request_id, TraceContext(enabled=True, scope=self.name, trace_id=request_id)
        return request_id, NULL_TRACE

    def handle_pql(
        self,
        pql: str,
        trace: bool = False,
        debug_options: Optional[Dict[str, str]] = None,
        timeout_ms: Optional[float] = None,
        request_id: Optional[str] = None,
        trace_ctx: Optional[TraceContext] = None,
    ) -> BrokerResponse:
        t0 = time.perf_counter()
        self.metrics.meter("queries").mark()
        if request_id is None:
            request_id = self._next_request_id()
        # with the tail sampler armed (default), EVERY query carries the
        # lightweight span tree so the retention decision can happen at
        # completion; with sampling off (PINOT_TPU_TAIL_TRACE=0),
        # untraced queries share the NULL context — no span allocation
        # anywhere on the handle path (the PR 4 zero-overhead contract).
        # A front end that opened the tree itself (open_trace) owns the
        # root; the spans outside ``query`` hang under it.
        rooted = trace_ctx is not None and trace_ctx.enabled
        if rooted:
            ctx = trace_ctx
        elif trace or self.tail.armed:
            ctx = TraceContext(enabled=True, scope=self.name, trace_id=request_id)
        else:
            ctx = NULL_TRACE
        resp: Optional[BrokerResponse] = None
        request = None
        plan_digest = ""
        plan_summary = ""
        with boundary("query", ctx, requestId=request_id, pql=pql[:200]):
            parse = boundary("parse", ctx, self.metrics.timer("phase.parse"))
            with parse:
                try:
                    request = parse_pql(pql)
                    if debug_options:
                        request.debug_options = dict(debug_options)
                    request = optimize_request(request)
                    from pinot_tpu.engine.plandigest import (
                        plan_shape_digest,
                        plan_shape_summary,
                    )

                    # the literal-erased shape digest rides EVERY response
                    # (cross-links /debug/queries -> /debug/plans/workload)
                    plan_digest = plan_shape_digest(request)
                    plan_summary = plan_shape_summary(request)
                    if request.explain:
                        self.metrics.meter("explain.queries").mark()
                except PqlParseError as e:
                    # InvalidQueryOptionsError subclasses this; internal
                    # ValueErrors now propagate instead of masquerading as
                    # client parse errors (ADVICE r1)
                    resp = BrokerResponse(
                        exceptions=[QueryException(ErrorCode.PQL_PARSING, str(e))]
                    )
            if resp is None:
                request.enable_trace = ctx.enabled
                resp = self.handle_request(
                    request,
                    pql,
                    timeout_ms=timeout_ms,
                    request_id=request_id,
                    trace_ctx=ctx,
                )
        if not trace and resp.trace_info:
            # tail arming traces every query internally, but the client
            # contract is unchanged: traceInfo rides the response only
            # when the caller asked (trace=true).  The armed span trees
            # reach the tail sampler via the _server_traces side channel
            # below, never an untraced client's payload (which must stay
            # byte-identical to the sampling-off response).
            resp.trace_info = {}
        resp.request_id = request_id
        resp.time_used_ms = (time.perf_counter() - t0) * 1000
        self.metrics.timer("queryTotal").update(resp.time_used_ms)
        # the four planes that record the query run before the reply is
        # written, so they are a boundary of their own.  Its span hangs
        # under a front end's root; a direct call has none to hang it
        # under and keeps the timer and the annotation.
        with boundary("bookkeeping", ctx if rooted else None,
                      self.metrics.timer("phase.bookkeeping")):
            self._record_query(
                pql, trace, ctx, request, request_id, resp, plan_digest,
                plan_summary, parse.ms,
            )
        return resp

    def _record_query(
        self,
        pql: str,
        trace: bool,
        ctx: TraceContext,
        request: Optional[BrokerRequest],
        request_id: str,
        resp: BrokerResponse,
        plan_digest: str,
        plan_summary: str,
        parse_ms: float,
    ) -> None:
        """``planstats.record``, ``tail.observe`` (and the span-tree
        merge it may ask for), ``slo.observe``, ``querylog.observe``."""
        shed_q = any(
            e.error_code == ErrorCode.TOO_MANY_REQUESTS
            for e in resp.exceptions
        )
        if plan_digest:
            resp.plan_digest = plan_digest
            if request is None or request.explain != "plan":
                # workload roll-up: every executed query lands in the
                # per-digest registry (plain EXPLAIN excluded — it did
                # no work and must not skew frequency/cost rankings)
                self.planstats.record(
                    plan_digest,
                    summary=plan_summary,
                    table=getattr(request, "table_name", "") or "",
                    latency_ms=resp.time_used_ms,
                    cost=resp.cost,
                    num_docs=resp.num_docs_scanned,
                    shed=shed_q,
                    failed=bool(resp.exceptions) and not shed_q,
                    pql=pql,
                )
                self.metrics.meter("workload.recorded").mark()
        failed_q = bool(resp.exceptions)
        tail_reason = None
        if ctx.enabled:

            def _build_scopes() -> Dict[str, Any]:
                # merge the per-server span trees under their scatter
                # attempts, next to this broker's own tree — ONE
                # waterfall.  Deliberately deferred: on the tail
                # sampler's NOT-retained path this merge (and its span
                # copies) never runs — the zero-overhead contract.
                scopes: Dict[str, Any] = {}
                merge_scope(scopes, ctx.to_dict())
                for attempt_id, server_trace in (
                    getattr(resp, "_server_traces", ()) or ()
                ):
                    merge_scope(scopes, server_trace, root_parent=attempt_id)
                return scopes

            built: Optional[Dict[str, Any]] = None
            if trace:
                built = _build_scopes()
                resp.trace_info = {"traceId": request_id, "scopes": built}
            if self.tail.armed:
                scopes_fn = (lambda b=built: b) if built is not None else _build_scopes
                # sheds are typed overload verdicts, not failures worth a
                # span tree: retaining them would do the MOST tail work
                # exactly during a 429 storm (and flood the bounded ring
                # with microsecond entries), inverting the zero-overhead
                # contract.  SLO availability still counts them below.
                tail_reason = self.tail.observe(
                    request_id,
                    resp.time_used_ms,
                    failed_q and not shed_q,
                    resp.partial_response,
                    scopes_fn,
                    table=getattr(request, "table_name", "") or "",
                    plan_digest=plan_digest,
                    summary=plan_summary,
                )
                # for the front end that owns the root: whether to hand
                # the finished tree to ``tail.complete``
                resp._tail_reason = tail_reason
        # per-table SLO counters (utils/slo.py): burn rates evaluate on
        # the history cadence over exactly these cumulative series
        self.slo.observe(
            getattr(request, "table_name", "") or "",
            resp.time_used_ms,
            failed_q,
            freshness_ms=resp.freshness_ms,
        )
        phases = dict(getattr(resp, "phase_ms", ()) or ())
        phases["parse"] = round(parse_ms, 3)
        if self.querylog.observe(
            {
                # tail cross-link: the retained span tree is fetchable by
                # this requestId (both directions: /debug/tails entries
                # carry the requestId back into this log)
                "traceRetained": bool(tail_reason),
                **(
                    {"traceRef": f"/debug/tails?requestId={request_id}"}
                    if tail_reason
                    else {}
                ),
                "requestId": request_id,
                "pql": pql[:500],
                # cross-link key into /debug/plans and /debug/workload
                "planDigest": plan_digest,
                "table": getattr(request, "table_name", None),
                "timeUsedMs": round(resp.time_used_ms, 3),
                # event-time staleness of the served answer (None for
                # offline-only queries): the /debug/queries twin of the
                # response's freshnessMs
                "freshnessMs": (
                    round(resp.freshness_ms, 3)
                    if resp.freshness_ms is not None
                    else None
                ),
                "phasesMs": phases,
                # the merged cost vector: "why was this slow" answerable
                # from the log entry alone (rows/bytes, device vs host)
                "numDocsScanned": resp.num_docs_scanned,
                "cost": {
                    k: (round(v, 3) if isinstance(v, float) else v)
                    for k, v in sorted(resp.cost.items())
                },
                "partialResponse": resp.partial_response,
                "numServersQueried": resp.num_servers_queried,
                "numServersResponded": resp.num_servers_responded,
                "numSegmentsUnserved": resp.num_segments_unserved,
                "numRetries": resp.num_retries,
                "numHedges": resp.num_hedges,
                "exceptions": [e.error_code for e in resp.exceptions],
                "traced": trace,
            }
        ):
            self.metrics.meter("slowQueries").mark()
        if failed_q and any(
            e.error_code
            not in (ErrorCode.TOO_MANY_REQUESTS, ErrorCode.PQL_PARSING)
            for e in resp.exceptions
        ):
            # notable event: a genuinely failed query (sheds are typed
            # overload verdicts, parse errors are client bugs) dumps the
            # observability state that explains it — rate-limited and
            # disabled unless PINOT_TPU_FLIGHTREC_DIR is set
            self.flightrec.maybe_dump(
                "failedQuery",
                {
                    "requestId": request_id,
                    "table": getattr(request, "table_name", None),
                    "codes": [e.error_code for e in resp.exceptions],
                },
            )

    def _history_tick(self, now: float) -> None:
        """Runs on every history sample (the recorder's cadence): SLO
        burn evaluation + the broker-side flight-recorder triggers."""
        ev = self.slo.evaluate()
        for table in ev.get("crossed", ()):
            t = ev["tables"].get(table, {})
            self.flightrec.maybe_dump(
                "sloBurn",
                {
                    "table": table,
                    "burnRate5m": t.get("burnRate5m"),
                    "burnRate1h": t.get("burnRate1h"),
                },
            )
        dropped = self.metrics.meter("queriesDropped").count
        delta = dropped - self._last_dropped
        self._last_dropped = dropped
        if delta >= self._shed_burst_threshold:
            self.flightrec.maybe_dump("shedBurst", {"droppedThisTick": delta})

    def shutdown(self) -> None:
        """Stop the history recorder thread (idempotent); the scatter
        pool's daemon workers die with the process as before."""
        self.replica_audit.stop()
        self.history.stop()

    def handle_request(
        self,
        request: BrokerRequest,
        pql: str,
        timeout_ms: Optional[float] = None,
        request_id: Optional[str] = None,
        trace_ctx: Optional[TraceContext] = None,
    ) -> BrokerResponse:
        ctx = trace_ctx if trace_ctx is not None else NULL_TRACE
        if request_id is None:
            request_id = self._next_request_id()
        # per-query override (reference: timeoutMs request parameter,
        # InstanceRequest carries it); the broker's configured timeout
        # is the CEILING so a client can shorten but never extend.  A
        # present-but-invalid override is a client error, not something
        # to silently replace with the default — same contract as the
        # HTTP layer (ONE validator: _parse_timeout).
        try:
            timeout_ms = _parse_timeout(timeout_ms)
        except InvalidTimeoutError as e:
            return BrokerResponse(
                exceptions=[QueryException(ErrorCode.QUERY_VALIDATION, str(e))],
                request_id=request_id,
            )
        timeout_ms = (
            self.timeout_ms if timeout_ms is None else min(timeout_ms, self.timeout_ms)
        )
        table = request.table_name
        # adaptive admission front door: QPS bucket + per-table
        # in-flight cap — both shed with a typed 429 naming the tier
        decision = self.admission.try_admit(table)
        if not decision.admitted:
            self.metrics.meter("queriesDropped").mark()
            return BrokerResponse(
                exceptions=[
                    QueryException(ErrorCode.TOO_MANY_REQUESTS, decision.message)
                ],
                request_id=request_id,
            )
        try:
            return self._handle_admitted(
                request, pql, timeout_ms, request_id, ctx, table
            )
        finally:
            # the in-flight slot frees when the query leaves the broker,
            # whatever path it took out
            self.admission.release(table)

    def _handle_admitted(
        self,
        request: BrokerRequest,
        pql: str,
        timeout_ms: float,
        request_id: str,
        ctx: TraceContext,
        table: str,
    ) -> BrokerResponse:
        if request.join is not None:
            # broker-planned distributed join (broker/joinplan.py):
            # strategy choice + multi-phase scatter, riding the same
            # resilient scatter-gather machinery per phase.  Admission
            # already happened (the left table's quota/in-flight slot).
            with ctx.span("joinPlan", table=table):
                resp = self.joinplan.handle(
                    request, pql, timeout_ms, request_id, ctx, table
                )
            resp.request_id = request_id
            resp._server_traces = getattr(resp, "_server_traces", [])
            return resp
        # timed even on the no-routing return: a silent phase.route
        # series during an external-view refill would hide exactly the
        # period when route behavior changed
        with boundary("route", ctx, self.metrics.timer("phase.route"), table=table):
            physical = self._physical_tables(table, pql)
            if not physical:
                return BrokerResponse(
                    exceptions=[
                        QueryException(
                            ErrorCode.BROKER_RESOURCE_MISSING, f"no routing for table {table}"
                        )
                    ],
                    request_id=request_id,
                )

            exceptions: List[QueryException] = []
            batches: List[_Batch] = []
            routing_gap = False
            for phys_table, sub_pql in physical:
                routing = self.routing.find_servers(phys_table, health=self.health)
                if not routing:
                    # None (table unknown) or {} (external view refilling
                    # after a restart): either way this physical table is
                    # currently unanswerable — surface a retriable error
                    # rather than silently dropping it from the result
                    routing_gap = True
                    exceptions.append(
                        QueryException(
                            ErrorCode.BROKER_RESOURCE_MISSING,
                            f"no servers currently serving table {phys_table}",
                        )
                    )
                    continue
                for server, segments in routing.items():
                    batches.append(
                        _Batch(phys_table, sub_pql, segments, server, order=len(batches))
                    )

        # AIMD pre-scatter overload check: when EVERY server covering the
        # table is past its congestion window, scattering could only end
        # in 210s or timeouts — shed here, at the cheapest tier (429)
        if batches:
            cover = self.admission.check_cover(
                table, sorted({b.server for b in batches})
            )
            if not cover.admitted:
                self.metrics.meter("queriesDropped").mark()
                return BrokerResponse(
                    exceptions=exceptions
                    + [QueryException(ErrorCode.TOO_MANY_REQUESTS, cover.message)],
                    request_id=request_id,
                )

        scatter = boundary("scatterGather", ctx, self.metrics.timer("scatterGather"),
                           batches=len(batches))
        with scatter:
            parts, sg = self._scatter_gather(
                request, batches, timeout_ms, table, request_id, ctx
            )
        exceptions.extend(sg["exceptions"])

        reduce = boundary("reduce", ctx, self.metrics.timer("reduce"), parts=len(parts))
        reduce.start()
        for p in parts:
            for code, msg in p.exceptions:
                exceptions.append(QueryException(code, msg))
        # plan nodes collected BEFORE reduce: the merge below folds
        # parts in place, and per-server attribution must survive it
        plan_nodes = (
            [n for p in parts for n in (p.plan_info or [])]
            if request.explain
            else []
        )
        if request.explain == "plan":
            # EXPLAIN returns the plan INSTEAD of results: nothing to
            # reduce (servers executed nothing, partials are empty)
            resp = BrokerResponse(exceptions=exceptions)
        else:
            resp = reduce_to_response(request, parts, exceptions)
        reduce.stop()
        resp.request_id = request_id
        # event-time freshness: now − the stalest realtime watermark
        # that contributed to this answer (server stamps min-combine
        # across the gather; broker derives the client-visible lag).
        # Offline-only answers have no stamped part and keep the key
        # absent — byte-identical to the pre-audit-plane payload.
        fmins = [
            p.freshness["minEventMs"]
            for p in parts
            if getattr(p, "freshness", None) is not None
            and p.freshness.get("minEventMs") is not None
        ]
        if fmins:
            from pinot_tpu.broker.freshness import now_ms

            resp.freshness_ms = max(0.0, now_ms() - min(fmins))
            self.metrics.timer("freshness.lagMs").update(resp.freshness_ms)
            self.metrics.gauge(f"freshness.{table}.lagMs").set(
                round(resp.freshness_ms, 3)
            )
        if request.explain:
            resp.explain = self._assemble_explain(request, plan_nodes, resp)
        # per-table cost attribution into the metrics registry: who is
        # burning the cluster, by logical table (rendered cluster-wide
        # on the controller's /debug/capacity rollup)
        self.metrics.meter("cost.docsScanned").mark(int(resp.num_docs_scanned))
        self.metrics.meter("cost.bytesScanned").mark(
            int(resp.cost.get("bytesScanned", 0))
        )
        self.metrics.meter(f"table.{table}.docsScanned").mark(
            int(resp.num_docs_scanned)
        )
        self.metrics.meter(f"table.{table}.bytesScanned").mark(
            int(resp.cost.get("bytesScanned", 0))
        )
        for key, timer in (("deviceMs", "cost.deviceMs"), ("hostMs", "cost.hostMs")):
            ms = resp.cost.get(key)
            if ms:
                self.metrics.timer(timer).update(float(ms))
        # the join planner's size estimator learns table totals from
        # every plain scan's merged reply (EXPLAIN of a join can then
        # name the strategy real execution will pick)
        if resp.total_docs:
            self.joinplan.stats.observe(table, resp.total_docs)
        resp.num_servers_queried = len(sg["servers_queried"])
        resp.num_servers_responded = len(sg["servers_responded"])
        resp.num_segments_unserved = len(sg["unserved"])
        resp.partial_response = bool(sg["unserved"]) or routing_gap
        resp.num_retries = sg["retries"]
        resp.num_hedges = sg["hedges"]
        # side-channel for handle_pql: per-server trace trees keyed by
        # the attempt span that carried them + the phase breakdown the
        # slow-query log records (not serialized into the response)
        resp._server_traces = sg["server_traces"]
        resp.phase_ms = {
            "scatterGather": round(scatter.ms, 3),
            "reduce": round(reduce.ms, 3),
        }
        # replica-divergence sampling hook (utils/audit.py): a cheap
        # counter for the non-sampled majority, a bounded background
        # re-issue for the winners
        self.replica_audit.offer(request, batches, request_id, timeout_ms, resp)
        return resp

    def _assemble_explain(
        self,
        request: BrokerRequest,
        nodes: List[Dict[str, Any]],
        resp: BrokerResponse,
    ) -> Dict[str, Any]:
        """Broker-side EXPLAIN tree: the per-server plan nodes under one
        roof, with summed tier counts and estimates.  For ANALYZE the
        top level carries the merged actuals (== BrokerResponse.cost,
        exactly: only merged replies' nodes reach here)."""
        from pinot_tpu.engine.plandigest import (
            plan_shape_digest,
            plan_shape_summary,
        )

        tier_counts: Dict[str, int] = {}
        est_bytes = 0.0
        for n in nodes:
            for k, v in (n.get("tierCounts") or {}).items():
                tier_counts[k] = tier_counts.get(k, 0) + int(v)
            est = n.get("estimatedCost") or {}
            if est.get("source") == "history":
                est_bytes += float((est.get("perQuery") or {}).get("bytesScanned", 0))
            else:
                est_bytes += float(est.get("bytesScanned", 0))
        out: Dict[str, Any] = {
            "mode": request.explain,
            "planDigest": plan_shape_digest(request),
            "summary": plan_shape_summary(request),
            "numServers": len(nodes),
            "tierCounts": tier_counts,
            "estimatedCost": {"bytesScanned": int(est_bytes)},
            "servers": nodes,
        }
        if request.explain == "analyze":
            out["actualCost"] = {
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in sorted(resp.cost.items())
            }
            out["actualDocsScanned"] = resp.num_docs_scanned
            if resp.freshness_ms is not None:
                out["freshnessMs"] = round(resp.freshness_ms, 3)
        return out

    def workload_snapshot(self, top: int = 20, tables=None) -> Dict[str, Any]:
        """``/debug/workload``: the per-plan-digest roll-up, top-K by
        frequency AND by total cost (the batching-candidate ranking).
        ``top`` at the registry capacity returns the FULL registry —
        the controller's fleet roll-up fetches that so cross-broker
        merging never ranks on truncated slices.  ``tables`` narrows
        the ranking to shapes touching those tables so a prewarming
        server only pulls plans it can actually stage."""
        return {
            "digests": self.planstats.digest_count(),
            "totalRecorded": self.planstats.total_recorded,
            "topByCount": self.planstats.top(top, by="count", tables=tables),
            "topByCost": self.planstats.top(top, by="cost", tables=tables),
        }

    # ------------------------------------------------------------------
    # resilient scatter-gather
    # ------------------------------------------------------------------
    def _hedge_delay_s(self) -> Optional[float]:
        """Hedge trigger delay: the observed server-latency percentile
        once enough samples exist, else the configured static floor.
        ``hedge_delay_ms <= 0`` disables hedging entirely."""
        if self.hedge_delay_ms <= 0:
            return None
        timer = self.metrics.timer("serverLatency")
        if timer.count >= 20:
            return max(timer.percentile(self.hedge_latency_percentile), 1.0) / 1000.0
        return self.hedge_delay_ms / 1000.0

    def _backoff_s(self, reissues: int) -> float:
        return (
            min(self.retry_backoff_ms * (2 ** max(0, reissues - 1)), self.retry_backoff_cap_ms)
            / 1000.0
        )

    def _scatter_gather(
        self,
        request: BrokerRequest,
        batches: List[_Batch],
        timeout_ms: float,
        logical_table: str,
        request_id: str,
        ctx: TraceContext,
        extra_fn=None,
    ) -> Tuple[List[IntermediateResult], Dict[str, Any]]:
        # request_id is REQUIRED: minting a fallback here would hand the
        # servers a different id than the one echoed to the client,
        # silently breaking the correlation contract
        deadline = time.monotonic() + timeout_ms / 1000.0
        # (batch.order, result): parts merge in BATCH CREATION order, not
        # completion order — ties in sort keys (and any other
        # order-sensitive reduce step) must not depend on which server
        # replied first
        ordered_parts: List[Tuple[int, IntermediateResult]] = []
        exceptions: List[QueryException] = []
        unserved: List[str] = []
        servers_queried: Set[str] = set()
        servers_responded: Set[str] = set()
        retries = 0
        hedges = 0
        hedge_delay_s = self._hedge_delay_s()
        if hedge_delay_s is not None and (
            self.quota.headroom(logical_table) < self.hedge_min_quota_headroom
        ):
            # hedging doubles this table's scatter traffic; near the QPS
            # quota that amplification would starve first-try queries
            hedge_delay_s = None

        # future -> (batch, server, is_hedge, sent_at, wall_sent_ms,
        # the attempt's span id, reserved at send so that the codec
        # spans of the pool thread can name it as their parent)
        pending: Dict[
            concurrent.futures.Future, Tuple[_Batch, str, bool, float, float, Optional[str]]
        ] = {}
        all_batches: List[_Batch] = list(batches)
        delayed: List[Tuple[float, _Batch]] = []  # (fire_time, batch) backoff queue
        open_lineages = len(batches)  # batches neither completed nor superseded
        # (attempt span id, {scope: spans}) per merged server reply —
        # handle_pql re-parents each tree under its attempt span
        server_traces: List[Tuple[Optional[str], Dict[str, Any]]] = []

        def attempt_span(
            batch: _Batch, server: str, hedge: bool, sent_at: float,
            wall_sent: float, aid: Optional[str], status: str, **tags
        ) -> Optional[str]:
            return ctx.add(
                "serverAttempt",
                (time.monotonic() - sent_at) * 1000.0,
                start_ms=wall_sent,
                span_id=aid,
                server=server,
                hedge=hedge,
                reissues=batch.reissues,
                segments=len(batch.segments),
                status=status,
                **tags,
            )

        def submit(batch: _Batch, server: str, hedge: bool = False) -> None:
            now = time.monotonic()
            wall_sent = time.time() * 1000.0
            aid = ctx.reserve()
            # the attempt made ready, up to the hand-over to the pool
            # (where its poolQueue starts): first child of serverAttempt
            readying = boundary("attemptSubmit", ctx, self.metrics.timer("phase.attemptSubmit"),
                                parent=aid).start()
            remaining_ms = max(1.0, (deadline - now) * 1000.0)
            servers_queried.add(server)
            # half-open probe claim: a penalty-boxed server chosen after
            # its window gets exactly ONE probe marked inflight, so
            # concurrent queries keep steering around it until the probe
            # reports back (no thundering herd onto a sick server)
            self.health.allow_request(server)
            # with retries in reserve AND an untried replica to fail over
            # to, wait only half the remaining budget on this attempt: a
            # hung (not refusing) replica then surfaces as a transport
            # timeout while there is still time to re-issue elsewhere.
            # With no alternate (or on the last attempt) waiting less
            # than the full budget could only turn a slow success into a
            # guaranteed miss.
            retries_left = self.retry_attempts - batch.reissues
            attempt_ms = remaining_ms
            if retries_left > 0 and not hedge and self.routing.has_alternate(
                batch.table, batch.segments, batch.excluded
            ):
                attempt_ms = remaining_ms / 2.0
            extra = extra_fn(server) if extra_fn is not None else None
            readying.stop()
            fut = self._pool.submit(
                self._send_one,
                server,
                batch.table,
                batch.pql,
                batch.segments,
                request.enable_trace,
                request.debug_options or None,
                remaining_ms,
                attempt_ms,
                request_id,
                extra,
                ctx=ctx,
                parent=aid,
                t_submit=time.perf_counter(),
            )
            # AIMD window accounting: the done-callback observes EVERY
            # attempt outcome exactly once — including attempts that
            # outlive this query's gather loop (deadline-abandoned
            # transports complete later and still decrement in-flight)
            self.admission.on_attempt_start(server)
            fut.add_done_callback(
                lambda f, s=server: self._observe_attempt(f, s)
            )
            batch.inflight += 1
            if not hedge:
                batch.first_sent = now
            pending[fut] = (batch, server, hedge, now, wall_sent, aid)

        def fail_batch(batch: _Batch) -> None:
            nonlocal open_lineages
            unserved.extend(batch.segments)
            exceptions.extend(batch.errors)
            batch.done = True
            open_lineages -= 1

        def failover(batch: _Batch) -> None:
            """All inflight attempts for this lineage failed: re-cover
            its segments on untried replicas, or declare them unserved."""
            nonlocal retries, open_lineages
            if batch.reissues >= self.retry_attempts:
                fail_batch(batch)
                return
            assignment, leftover = self.routing.alternates(
                batch.table, batch.segments, batch.excluded, health=self.health
            )
            child_errors = batch.errors
            if leftover:
                exceptions.extend(batch.errors)
                unserved.extend(leftover)
                # already reported above: children start clean so a later
                # child failure doesn't duplicate the ancestry in the
                # response's exceptions
                child_errors = []
            if not assignment:
                if not leftover:  # alternates() returned nothing at all
                    fail_batch(batch)
                else:
                    batch.done = True
                    open_lineages -= 1
                return
            batch.done = True  # superseded by its children
            open_lineages -= 1
            for server, segments in assignment.items():
                child = _Batch(
                    batch.table,
                    batch.pql,
                    segments,
                    server,
                    excluded=batch.excluded,
                    reissues=batch.reissues + 1,
                    errors=child_errors,
                    order=batch.order,  # failover keeps the merge slot
                )
                all_batches.append(child)
                open_lineages += 1
                retries += 1
                self.metrics.meter("failoverRetries").mark()
                ctx.event(
                    "failover",
                    fromServer=batch.server,
                    toServer=server,
                    segments=len(segments),
                    reissues=child.reissues,
                )
                fire = time.monotonic() + self._backoff_s(child.reissues)
                if fire >= deadline:
                    # no budget left to back off AND run the query; try
                    # immediately rather than guaranteeing a miss
                    submit(child, server)
                else:
                    delayed.append((fire, child))

        for batch in batches:
            submit(batch, batch.server)

        while open_lineages > 0 and (pending or delayed):
            now = time.monotonic()
            if now >= deadline:
                break
            # fire due backoff retries
            due = [(f, b) for f, b in delayed if f <= now]
            if due:
                delayed = [(f, b) for f, b in delayed if f > now]
                for _, batch in due:
                    submit(batch, batch.server)
            # arm hedges on stragglers
            next_hedge = math.inf
            if hedge_delay_s is not None:
                for batch, server, hedge, _sent, _wall, _aid in list(pending.values()):
                    if hedge or batch.done or batch.hedged:
                        continue
                    fire = batch.first_sent + hedge_delay_s
                    if fire > now:
                        next_hedge = min(next_hedge, fire)
                        continue
                    assignment, leftover = self.routing.alternates(
                        batch.table, batch.segments, batch.excluded, health=self.health
                    )
                    batch.hedged = True  # one hedge round per lineage
                    # a hedge reply REPLACES the primary's, so it must
                    # cover the identical segment set: a replica holding
                    # only part of it would win the race with silently
                    # missing data.  Split coverage -> no hedge (failover
                    # still handles an eventual primary failure).
                    if len(assignment) == 1 and not leftover:
                        alt_server = next(iter(assignment))
                        batch.excluded.add(alt_server)
                        hedges += 1
                        self.metrics.meter("hedgesSent").mark()
                        ctx.event(
                            "hedge", fromServer=server, toServer=alt_server,
                            segments=len(batch.segments),
                        )
                        submit(batch, alt_server, hedge=True)
            if not pending:
                # nothing inflight: sleep until the next backoff fire
                next_fire = min((f for f, _ in delayed), default=deadline)
                time.sleep(max(0.0, min(next_fire, deadline) - time.monotonic()))
                continue
            next_event = min(deadline, next_hedge, *(f for f, _ in delayed)) \
                if delayed else min(deadline, next_hedge)
            done, _ = concurrent.futures.wait(
                list(pending.keys()),
                timeout=max(0.0, next_event - time.monotonic()),
                return_when=concurrent.futures.FIRST_COMPLETED,
            )
            for fut in done:
                batch, server, hedge, sent_at, wall_sent, aid = pending.pop(fut)
                batch.inflight -= 1
                try:
                    result = fut.result()
                except concurrent.futures.CancelledError:
                    # a queued twin we cancelled after its batch already
                    # completed — not a server failure, not data
                    continue
                except Exception as e:
                    self.health.record_failure(server)
                    logger.warning("server %s failed: %s", server, e)
                    attempt_span(
                        batch, server, hedge, sent_at, wall_sent, aid,
                        "error", error=f"{type(e).__name__}: {e}"[:200],
                    )
                    batch.errors.append(
                        QueryException(
                            ErrorCode.BROKER_GATHER,
                            f"server {server}: {type(e).__name__}: {e}",
                        )
                    )
                    if not batch.done and batch.inflight == 0:
                        failover(batch)
                    continue
                retryable = result.exceptions and all(
                    code in RETRYABLE_SERVER_CODES for code, _ in result.exceptions
                )
                if retryable:
                    # the server answered "not me, not now" (saturated /
                    # draining): treat as failover-able, not as data
                    self.health.record_failure(server)
                    attempt_span(
                        batch, server, hedge, sent_at, wall_sent, aid,
                        "refused", errorCode=result.exceptions[0][0],
                    )
                    batch.errors.append(
                        QueryException(result.exceptions[0][0], result.exceptions[0][1])
                    )
                    if not batch.done and batch.inflight == 0:
                        failover(batch)
                    continue
                self.health.record_success(server)
                # the pool thread had the result -> this loop has it
                measured("gatherWake", (time.perf_counter() - result._t_done) * 1000.0, ctx,
                         self.metrics.timer("phase.gatherWake"), parent=aid)
                # per-ATTEMPT latency (a winning hedge measures from its
                # own send, not the primary's — else the percentile that
                # arms future hedges inflates itself)
                self.metrics.timer("serverLatency").update(
                    (time.monotonic() - sent_at) * 1000.0
                )
                if batch.done:
                    # hedge race loser: first reply already merged; the
                    # attempt still shows on the waterfall as the slower
                    # twin, but its data (and trace) is discarded
                    attempt_span(
                        batch, server, hedge, sent_at, wall_sent, aid, "hedgeLoser"
                    )
                    continue
                aid = attempt_span(batch, server, hedge, sent_at, wall_sent, aid, "ok")
                if result.trace:
                    # snapshot: reduce later merges parts IN PLACE, which
                    # would fold every later part's spans into the first
                    # reply's trace dict (aliased here)
                    server_traces.append(
                        (aid, {k: list(v) for k, v in result.trace.items()})
                    )
                batch.done = True
                open_lineages -= 1
                servers_responded.add(server)
                ordered_parts.append((batch.order, result))
                # server-reported unserved segments (dropped on that
                # server / quarantined pending re-fetch): the served
                # part merges above; the missing slice re-covers on an
                # untried replica or degrades honestly
                batch_set = set(batch.segments)
                missing = [s for s in result.unserved_segments if s in batch_set]
                if missing:
                    merr = QueryException(
                        ErrorCode.SERVER_SEGMENT_MISSING,
                        f"server {server}: segments unavailable: {sorted(missing)}",
                    )
                    assignment: Dict[str, List[str]] = {}
                    leftover = list(missing)
                    if batch.reissues < self.retry_attempts:
                        assignment, leftover = self.routing.alternates(
                            batch.table, missing, batch.excluded, health=self.health
                        )
                    if leftover:
                        exceptions.append(merr)
                        unserved.extend(leftover)
                    for alt_server, alt_segments in assignment.items():
                        child = _Batch(
                            batch.table,
                            batch.pql,
                            alt_segments,
                            alt_server,
                            excluded=batch.excluded,
                            reissues=batch.reissues + 1,
                            errors=[] if leftover else [merr],
                            order=batch.order,
                        )
                        all_batches.append(child)
                        open_lineages += 1
                        retries += 1
                        self.metrics.meter("failoverRetries").mark()
                        submit(child, alt_server)
                # best effort: free the loser's queued twin if it never started
                for other, (ob, _osrv, _oh, _osent, _owall, _oaid) in list(pending.items()):
                    if ob is batch:
                        other.cancel()

        # deadline expired (or queue drained): account every lineage that
        # never completed
        for fut, (pbatch, pserver, _h, _sent, _wall, _aid) in pending.items():
            if not pbatch.done and not fut.cancel():
                attempt_span(pbatch, pserver, _h, _sent, _wall, _aid, "timeout")
                # an attempt for a still-open lineage ran past the
                # deadline: the circuit breaker must learn about hung
                # servers too, or a blackholed replica would stay CLOSED
                # (and keep being routed to) forever — no exception ever
                # surfaces to the gather loop once the query returns.
                # (Hedge losers of COMPLETED batches are just slower,
                # not sick — they are skipped.)
                self.health.record_failure(pserver)
        for batch in all_batches:
            if not batch.done and batch.inflight > 0:
                batch.errors.append(
                    QueryException(
                        ErrorCode.BROKER_TIMEOUT,
                        f"server {batch.server}: no reply within {timeout_ms:.0f}ms budget",
                    )
                )
                fail_batch(batch)
            elif not batch.done:
                fail_batch(batch)

        ordered_parts.sort(key=lambda pair: pair[0])  # stable: children keep arrival order
        parts = [result for _, result in ordered_parts]
        return parts, {
            "exceptions": exceptions,
            "unserved": unserved,
            "servers_queried": servers_queried,
            "servers_responded": servers_responded,
            "retries": retries,
            "hedges": hedges,
            "server_traces": server_traces,
        }

    def _observe_attempt(self, fut: concurrent.futures.Future, server: str) -> None:
        """Feed one finished scatter attempt into the AIMD admission
        windows: transport failures and retryable (210/220) refusals are
        saturation evidence (multiplicative decrease); a healthy reply
        grows the window additively unless its backpressure snapshot
        shows the server's scheduler past the high-water mark."""
        if fut.cancelled():
            self.admission.on_attempt_cancelled(server)
            return
        exc = fut.exception()
        if exc is not None:
            self.admission.on_attempt_done(server, saturated=True)
            return
        result = fut.result()
        refused = bool(result.exceptions) and all(
            code in RETRYABLE_SERVER_CODES for code, _ in result.exceptions
        )
        self.admission.on_attempt_done(
            server, saturated=refused, backpressure=result.backpressure
        )

    # ------------------------------------------------------------------
    def _physical_tables(self, table: str, pql: str) -> List[Tuple[str, str]]:
        """Logical table -> [(physical table, sub-query pql)].

        Hybrid federation (BrokerRequestHandler.java:280-329): a table
        with both OFFLINE and REALTIME physical tables gets the query
        duplicated with a time-boundary filter added on each side.
        """
        known = set(self.routing.tables())
        if table in known:
            return [(table, pql)]
        offline = table + OFFLINE_SUFFIX
        realtime = table + REALTIME_SUFFIX
        if offline in known and realtime in known:
            boundary = self.time_boundary.get(offline)
            if boundary is not None:
                col, value = boundary
                return [
                    (offline, self._with_time_filter(pql, col, value, is_offline=True)),
                    (realtime, self._with_time_filter(pql, col, value, is_offline=False)),
                ]
            return [(offline, pql)]
        if offline in known:
            return [(offline, pql)]
        if realtime in known:
            return [(realtime, pql)]
        return []

    def _with_time_filter(self, pql: str, col: str, value: int, is_offline: bool) -> str:
        """Append the hybrid time-boundary predicate to the PQL text
        (offline: col <= boundary; realtime: col > boundary —
        HelixExternalViewBasedTimeBoundaryService.java:52-85)."""
        op = "<=" if is_offline else ">"
        upper = pql.upper()
        pred = f"{col} {op} {value}"
        if " WHERE " in upper:
            idx = upper.index(" WHERE ") + len(" WHERE ")
            rest = pql[idx:]
            # predicate list ends at the next clause keyword
            end = len(rest)
            for kw in (" GROUP BY ", " ORDER BY ", " HAVING ", " TOP ", " LIMIT "):
                j = rest.upper().find(kw)
                if j != -1:
                    end = min(end, j)
            return pql[:idx] + f"({rest[:end]}) AND {pred}" + rest[end:]
        # insert WHERE after FROM <table>
        ufrom = upper.index(" FROM ")
        after = pql[ufrom + len(" FROM ") :]
        stop = len(after)
        for kw in (" WHERE ", " GROUP BY ", " ORDER BY ", " HAVING ", " TOP ", " LIMIT "):
            j = after.upper().find(kw)
            if j != -1:
                stop = min(stop, j)
        return (
            pql[: ufrom + len(" FROM ")] + after[:stop] + f" WHERE {pred}" + after[stop:]
        )

    def _send_one(
        self,
        server: str,
        table: str,
        pql: str,
        segments: List[str],
        trace: bool,
        debug_options: Optional[Dict[str, str]],
        timeout_ms: float,
        attempt_timeout_ms: Optional[float],
        request_id: str,
        join: Optional[Dict[str, Any]] = None,
        ctx: Optional[TraceContext] = None,
        parent: Optional[str] = None,
        t_submit: Optional[float] = None,
    ) -> IntermediateResult:
        # timeout_ms is the REMAINING deadline budget at (re-)issue time,
        # already clamped by handle_request — the server's scheduler pins
        # it as its dequeue deadline (deadline propagation).
        # attempt_timeout_ms caps how long the BROKER waits on this one
        # attempt: when retries remain, it is a fraction of the budget so
        # a hung replica surfaces as a transport timeout early enough to
        # fail over (the server keeps the full budget — wasted work at
        # worst, not an early server-side timeout).
        # ``ctx``/``parent``: the request's tree and the serverAttempt
        # span this pool thread's spans hang under.  ``t_submit``: when
        # the gather loop handed this attempt to the pool.
        if t_submit is not None:
            measured("poolQueue", (time.perf_counter() - t_submit) * 1000.0, ctx,
                     self.metrics.timer("phase.poolQueue"), parent=parent)
        address = self.server_addresses[server]
        with boundary("serializeRequest", ctx, self.metrics.timer("phase.serializeRequest"),
                      parent=parent):
            payload = serialize_instance_request(
                request_id,
                pql,
                table,
                segments,
                timeout_ms,
                trace=trace,
                debug_options=debug_options,
                join=join,
            )
        wait_ms = timeout_ms if attempt_timeout_ms is None else attempt_timeout_ms
        reply = self.transport.request(address, payload, timeout=wait_ms / 1000.0)
        with boundary("deserializeResult", ctx, self.metrics.timer("phase.deserializeResult"),
                      parent=parent):
            result = deserialize_result(reply)
        result._t_done = time.perf_counter()  # for the gather loop's gatherWake
        return result


# ---------------------------------------------------------------------------
# HTTP front (PinotClientRequestServlet analog)
# ---------------------------------------------------------------------------


class InvalidTimeoutError(ValueError):
    """A timeoutMs override was present but not a positive number."""


def _parse_timeout(v) -> Optional[float]:
    """Strict per-query timeoutMs: absent (None/empty) means "use the
    broker default"; anything present must be a positive finite number
    or the query is rejected with a validation error — a silently
    ignored override is worse than a loud one (the client believes a
    budget it never got)."""
    if v is None or v == "":
        return None
    if isinstance(v, bool):  # float(True) == 1.0 — a flag is junk here
        raise InvalidTimeoutError(f"timeoutMs must be a positive number, got {v!r}")
    try:
        t = float(v)
    except (TypeError, ValueError):
        raise InvalidTimeoutError(f"timeoutMs must be a positive number, got {v!r}")
    if math.isnan(t) or math.isinf(t) or t <= 0:
        raise InvalidTimeoutError(f"timeoutMs must be a positive number, got {v!r}")
    return t


def _parse_debug_options(s: str) -> Optional[Dict[str, str]]:
    """``"k=v;k2=v2"`` -> dict (the reference's semicolon/equals debug
    option string, ``BrokerRequestHandler.java:156-159``)."""
    if not s:
        return None
    out: Dict[str, str] = {}
    for part in s.split(";"):
        part = part.strip()
        if not part or "=" not in part:
            continue
        k, v = part.split("=", 1)
        out[k.strip()] = v.strip()
    return out or None


class _Connection:
    """One connection's life inside the program, as four intervals that
    follow one another on one clock (``PERF.md`` section 3):

    ``httpAccept``      ``accept()`` has returned the socket on the accept
                        loop's thread, until the connection's own thread
                        runs its first statement: the thread made,
                        started and woken;
    ``httpHead``        from there to ``_query``'s entry: the handler
                        object, the request line, the header parse, the
                        route;
    ``httpTotal``       the handler's own, as it was;
    ``httpClose``       the last byte handed to the socket, until it is
                        closed: the flush, ``shutdown_request``;

    and around the last three ``httpConnection``, the root of the
    request's tree: the connection thread's first statement to its last.

    Whether the connection carries a query is known only at ``_query``'s
    entry: there ``query`` opens the request id and the tree and gives
    the root, a ``boundary`` open since the first statement, its span
    and timer (``boundary.attach``).  The three around the handler are
    ``measured``, each when it is over, from clock reads that a
    connection makes anyway, and ``marked`` for the profiler while a
    capture runs; ``httpAccept`` begins on one thread and ends on
    another, so it lies before its parent and ends where that begins,
    and its annotation closes when ``Thread.start()`` has returned on
    the accept loop, that is once the loop has the interpreter back.  A
    path that is not a query (``/metrics``, ``/health``, ``/debug/*``)
    never gets to ``query``: no tree, no timer marked."""

    __slots__ = ("_broker", "_timers", "_accepted_ms", "_began_ms", "_t_accepted", "_t_began", "_t_replied",
                 "_accept_mark", "_mark", "_root", "_ctx", "_retained")

    TIMERS = ("phase.httpAccept", "httpConnection", "phase.httpHead", "phase.httpClose")

    def __init__(self, broker: "BrokerRequestHandler", timers: tuple) -> None:
        self._broker = broker
        self._timers = timers  # the broker's, in the order of TIMERS: looked up once a server
        self._t_replied: Optional[float] = None  # set once a query's reply is out
        self._retained = False
        self._accepted_ms = time.time() * 1000.0
        self._t_accepted = time.perf_counter()
        self._accept_mark = marked("httpAccept")

    def accepted(self) -> None:
        """The accept loop is free to accept again."""
        if self._accept_mark is not None:
            self._accept_mark.stop()

    def begin(self) -> None:
        """The connection thread's first statement."""
        self._t_began = time.perf_counter()
        self._root = boundary("httpConnection").start()
        self._mark = marked("httpHead")

    def query(self) -> Tuple[str, TraceContext]:
        """``_query``'s entry: the request id and its tree, the root
        ``httpConnection``, under it ``httpAccept`` and ``httpHead``."""
        accept, connection, head, _ = self._timers
        rid, ctx = self._broker.open_trace()
        self._ctx = ctx
        accept_ms = (self._t_began - self._t_accepted) * 1000.0
        self._began_ms = began_ms = self._accepted_ms + accept_ms
        self._root.attach(ctx, connection, began_ms, requestId=rid)
        measured("httpAccept", accept_ms, ctx, accept, start_ms=self._accepted_ms)
        if self._mark is not None:
            self._mark.stop()
        # the clock is read last, so that what this entry costs is httpHead's and not between two spans
        measured("httpHead", (time.perf_counter() - self._t_began) * 1000.0, ctx, head, start_ms=began_ms)
        return rid, ctx

    def replied(self, retained: bool) -> None:
        """``httpTotal`` has ended: the reply is with the socket."""
        self._retained = retained
        self._t_replied = time.perf_counter()
        self._mark = marked("httpClose")

    def end(self) -> None:
        """The socket is closed: the connection thread's last statement."""
        if self._mark is not None:
            self._mark.stop()  # httpClose's, or httpHead's where no query came
        replied = self._t_replied
        if replied is not None:
            measured("httpClose", (time.perf_counter() - replied) * 1000.0, self._ctx, self._timers[3],
                     start_ms=self._began_ms + (replied - self._t_began) * 1000.0)
        self._root.stop()
        if self._retained:
            self._broker.tail.complete(self._ctx.trace_id, self._ctx.to_dict())


class BrokerHttpServer:
    """HTTP endpoint: GET /query?pql=... and POST /query {"pql": ...}
    (``PinotClientRequestServlet.java:54/:73``)."""

    def __init__(self, handler: BrokerRequestHandler, host: str = "127.0.0.1", port: int = 0):
        broker = handler

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def _respond(self, payload: Dict[str, Any], status: int = 200) -> None:
                body = json.dumps(payload).encode("utf-8")
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _respond_text(self, text: str, status: int = 200) -> None:
                body = text.encode("utf-8")
                self.send_response(status)
                self.send_header("Content-Type", "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _invalid_timeout(self, e: InvalidTimeoutError) -> None:
                self._respond(
                    BrokerResponse(
                        exceptions=[QueryException(ErrorCode.QUERY_VALIDATION, str(e))]
                    ).to_json()
                )

            def do_GET(self):
                url = urlparse(self.path)
                if url.path not in ("/query", "/"):
                    if url.path == "/health":
                        # "jax": a broker must never hold a device
                        from pinot_tpu.utils.platform import backend_state

                        return self._respond({"status": "ok", "jax": backend_state()})
                    if url.path == "/metrics":
                        # Prometheus text exposition (scrape target)
                        return self._respond_text(prometheus_text(broker.metrics))
                    if url.path == "/debug/metrics":
                        return self._respond(broker.metrics.snapshot())
                    if url.path == "/debug/queries":
                        return self._respond(broker.querylog.snapshot())
                    if url.path == "/debug/admission":
                        return self._respond(broker.admission.snapshot())
                    if url.path == "/debug/history":
                        return self._respond(
                            broker.history.query_from_qs(url.query)
                        )
                    if url.path == "/debug/slo":
                        return self._respond(broker.slo.snapshot())
                    if url.path == "/debug/tails":
                        qs = parse_qs(url.query)
                        rid = (qs.get("requestId") or [""])[0]
                        if rid:
                            entry = broker.tail.get(rid)
                            if entry is None:
                                return self._respond(
                                    {"error": f"no retained tail for {rid}"},
                                    404,
                                )
                            return self._respond(entry)
                        try:
                            top = int((qs.get("top") or ["20"])[0])
                        except ValueError:
                            top = 20
                        traces = (
                            (qs.get("traces") or ["false"])[0].lower() == "true"
                        )
                        return self._respond(
                            broker.tail.snapshot(
                                top=max(1, top), include_traces=traces
                            )
                        )
                    if url.path == "/debug/flightrec":
                        return self._respond(broker.flightrec.snapshot())
                    if url.path == "/debug/audit":
                        # correctness & freshness plane: replica-audit
                        # counters + the event-time watermark summary
                        from pinot_tpu.broker.freshness import WATERMARKS

                        return self._respond(
                            {
                                "replica": broker.replica_audit.snapshot(),
                                "freshness": WATERMARKS.snapshot(),
                            }
                        )
                    if url.path == "/debug/workload":
                        qs = parse_qs(url.query)
                        # ?n= is the prewarm-facing alias for ?top=
                        raw_top = (qs.get("n") or qs.get("top") or ["20"])[0]
                        try:
                            top = int(raw_top)
                        except ValueError:
                            top = 20
                        raw_tables = (qs.get("tables") or [""])[0]
                        tables = [
                            t.strip()
                            for t in raw_tables.split(",")
                            if t.strip()
                        ] or None
                        return self._respond(
                            broker.workload_snapshot(
                                top=max(1, top), tables=tables
                            )
                        )
                    if url.path == "/serverhealth":
                        return self._respond(
                            {
                                "circuits": broker.health.snapshot(),
                                "drainingServers": sorted(broker.draining_servers),
                                "warmingServers": sorted(
                                    broker.health.warming_servers()
                                ),
                            }
                        )
                    return self._respond({"error": "not found"}, 404)

                def read():
                    qs = parse_qs(url.query)
                    pql = (qs.get("pql") or qs.get("bql") or [""])[0]
                    trace = (qs.get("trace") or ["false"])[0].lower() == "true"
                    debug = _parse_debug_options((qs.get("debugOptions") or [""])[0])
                    timeout_ms = _parse_timeout((qs.get("timeoutMs") or [""])[0])
                    return pql, trace, debug, timeout_ms

                self._query(read)

            def do_POST(self):
                def read():
                    n = int(self.headers.get("Content-Length", "0"))
                    body = json.loads(self.rfile.read(n) or b"{}")
                    pql = body.get("pql") or body.get("bql") or ""
                    debug = body.get("debugOptions") or ""
                    if isinstance(debug, dict):
                        debug = {str(k): str(v) for k, v in debug.items()}
                    else:
                        # the reference's "k=v;k2=v2" string form; any other
                        # JSON type is ignored rather than crashing the handler
                        debug = _parse_debug_options(debug if isinstance(debug, str) else "")
                    return pql, bool(body.get("trace")), debug, _parse_timeout(body.get("timeoutMs"))

                self._query(read)

            def _query(self, read) -> None:
                """One query over HTTP, handler entry to last byte
                written (``httpTotal``): ``read`` gives ``handle_pql``'s
                arguments (``phase.httpRead``), the reply is rendered and
                written under ``phase.render``.  The request id and the
                tree are opened here, where the connection turns out to
                carry a query; its root is the connection's
                (``_Connection``), which hands a retained tail the
                finished tree once the socket is closed."""
                life = self.server.lives[self.request]
                rid, ctx = life.query()
                resp = None
                try:
                    with boundary("httpTotal", ctx, broker.metrics.timer("httpTotal"), requestId=rid):
                        try:
                            with boundary("httpRead", ctx, broker.metrics.timer("phase.httpRead")):
                                pql, trace, debug, timeout_ms = read()
                        except json.JSONDecodeError as e:
                            return self._respond(
                                {"exceptions": [{"errorCode": ErrorCode.JSON_PARSING, "message": str(e)}]}
                            )
                        except InvalidTimeoutError as e:
                            return self._invalid_timeout(e)
                        resp = broker.handle_pql(
                            pql,
                            trace=trace,
                            debug_options=debug,
                            timeout_ms=timeout_ms,
                            request_id=rid,
                            trace_ctx=ctx,
                        )
                        with boundary("render", ctx, broker.metrics.timer("phase.render")):
                            self._respond(resp.to_json())
                finally:
                    life.replied(bool(getattr(resp, "_tail_reason", None)))

        class _Httpd(ThreadingHTTPServer):
            # socketserver's default listen backlog of 5 is 170 ms of
            # arrivals at 30 queries/s: a stall of the accept loop that
            # long (the v5e's host shows 110 to 170 ms ones a few times
            # a minute) overflows it, the dropped SYNs come back after
            # 1, 3, 7... s, and the clients' retries pile onto the next
            # overflow (PERF.md, PR 24 and PR 25: one window in 23 went
            # 27 s behind and reset five connections)
            request_queue_size = 128

            def __init__(self, *args) -> None:
                self.lives: Dict[Any, _Connection] = {}  # by socket, from accept to close
                self.timers = tuple(broker.metrics.timer(name) for name in _Connection.TIMERS)
                super().__init__(*args)

            def process_request(self, request, client_address):
                """The accept loop's thread, ``accept()`` just returned:
                make and start the connection's thread."""
                life = self.lives[request] = _Connection(broker, self.timers)
                try:
                    super().process_request(request, client_address)
                except BaseException:  # no thread to take it out again
                    del self.lives[request]
                    raise
                finally:
                    life.accepted()

            def process_request_thread(self, request, client_address):
                """The connection's thread, first statement to last:
                the handler (``finish_request``), then the close."""
                life = self.lives[request]
                life.begin()
                try:
                    super().process_request_thread(request, client_address)
                finally:
                    life.end()
                    del self.lives[request]

        self._httpd = _Httpd((host, port), _Handler)
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
