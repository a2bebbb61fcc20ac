"""Server instance: request handling front for one query-serving node.

The reference chain (``ScheduledRequestHandler.java:55``): Netty bytes
-> Thrift InstanceRequest -> QueryScheduler -> QueryExecutor ->
serialized DataTable bytes.  Here: framed bytes -> InstanceRequest ->
scheduler -> TPU QueryExecutor -> DataTable bytes.  Errors come back as
a DataTable whose ``exceptions`` metadata is set (the broker still
reduces the healthy servers' partials —
``BrokerRequestHandler.java:443-460`` semantics).
"""
from __future__ import annotations

import concurrent.futures
import logging
import os
import time
from typing import List, Optional, Sequence, Tuple

from pinot_tpu.common.datatable import (
    deserialize_instance_request,
    serialize_result,
)
from pinot_tpu.common.response import ErrorCode
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.engine.results import SEGMENT_TIER_KEYS, IntermediateResult
from pinot_tpu.pql import optimize_request, parse_pql
from pinot_tpu.segment.immutable import ImmutableSegment
from pinot_tpu.server.datamanager import InstanceDataManager
from pinot_tpu.server.scheduler import (
    QueryAbandonedError,
    QueryScheduler,
    SchedulerSaturatedError,
    SchedulerShutdownError,
)
from pinot_tpu.utils.metrics import ServerMetrics, prometheus_text
from pinot_tpu.utils.trace import (
    NULL_TRACE,
    TraceContext,
    boundary,
    measured,
    reset_current,
    set_current,
)

logger = logging.getLogger(__name__)


class _RooflineWindow:
    """Rolling window of device-served query records backing the
    server-wide ``device.util.achieved*`` gauges: recent achieved
    HBM bytes/s and FLOP/s over the trailing ``window_s`` seconds,
    plus the roofline fraction against the declared platform peaks.
    Records happen on the request path (host side — the lane's
    zero-alloc contract is about the launch path, not here)."""

    def __init__(
        self, window_s: float = 300.0, capacity: int = 2048, peak_scale: int = 1
    ) -> None:
        import collections
        import threading

        self.window_s = window_s
        # how many chips this window's records aggregate over: the
        # roofline denominator scales with it (a lane driving 8 chips
        # measured against ONE chip's peak would overstate up to 8x)
        self.peak_scale = max(1, int(peak_scale))
        self._dq = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._cached: Optional[tuple] = None  # (monotonic t, snapshot)

    def record(self, device_ms: float, device_bytes: float, flops: float) -> None:
        with self._lock:
            self._dq.append(
                (time.monotonic(), float(device_ms), float(device_bytes), float(flops))
            )
            self._cached = None

    def snapshot(self) -> dict:
        now = time.monotonic()
        with self._lock:
            if self._cached is not None and now - self._cached[0] < 0.5:
                return dict(self._cached[1])
            horizon = now - self.window_s
            while self._dq and self._dq[0][0] < horizon:
                self._dq.popleft()
            records = list(self._dq)
            ms = sum(r[1] for r in records)
            nbytes = sum(r[2] for r in records)
            flops = sum(r[3] for r in records)
            n = len(records)
        out = {
            "windowS": self.window_s,
            "queries": n,
            "deviceMs": round(ms, 3),
            "deviceBytes": int(nbytes),
            "achievedBytesPerSec": round(nbytes * 1000.0 / ms, 3) if ms > 0 else 0.0,
            "achievedFlopsPerSec": round(flops * 1000.0 / ms, 3) if ms > 0 else 0.0,
        }
        from pinot_tpu.utils.platform import platform_peaks, roofline_fractions

        peaks = dict(platform_peaks())
        if self.peak_scale != 1:
            for k in ("peakFlopsPerSec", "peakBytesPerSec"):
                if peaks.get(k):
                    peaks[k] = peaks[k] * self.peak_scale
        out["rooflineFraction"] = roofline_fractions(
            out["achievedBytesPerSec"], out["achievedFlopsPerSec"], peaks=peaks
        )["rooflineFraction"]
        with self._lock:
            self._cached = (now, dict(out))
        return out


class ServerInstance:
    def __init__(
        self,
        name: str = "server0",
        mesh=None,
        num_workers: int = 4,
        max_pending: int = 64,
        pipeline: Optional[bool] = None,
        lane_stall_timeout_s: Optional[float] = None,
        device_fault_injector=None,
        topology=None,
    ) -> None:
        self.name = name
        self.data_manager = InstanceDataManager()
        self.metrics = ServerMetrics(name)
        # mesh execution plane (engine/mesh.py): the server's chips
        # carve into chip groups — one DeviceLane per group, queries
        # shape-hash-routed across them, each group executing as one
        # SPMD program over its own mesh.  ``topology`` wins; a legacy
        # ``mesh`` argument becomes a one-lane topology driving that
        # mesh; with neither, the env (PINOT_TPU_MESH_SHAPE /
        # PINOT_TPU_LANES) decides — unset env is the trivial single
        # lane, the exact pre-mesh path (and touches no jax state).
        from pinot_tpu.engine.mesh import MeshTopology

        if topology is None:
            topology = (
                MeshTopology.from_mesh(mesh)
                if mesh is not None
                else MeshTopology.from_env()
            )
        self.topology = topology
        self.metrics.gauge("mesh.lanes").set(topology.num_lanes)
        self.metrics.gauge("mesh.devices").set(topology.num_devices)
        self.metrics.gauge("mesh.devicesPerLane").set(topology.devices_per_lane)
        # three-stage serving pipeline (engine/dispatch.py): PREP on the
        # scheduler's worker pool, kernel launches on the per-chip-group
        # device lanes (coalescing identical dispatches), FINALIZE back
        # on the submitting worker.  On by default; PINOT_TPU_PIPELINE=0
        # (or pipeline=False) restores the serial per-worker path.
        # ``lane_stall_timeout_s`` arms the lane watchdogs (wedged-launch
        # restart); ``device_fault_injector`` is the deterministic-chaos
        # hook (common/faults.py DeviceFaultInjector), consulted by
        # every lane.
        if pipeline is None:
            pipeline = os.environ.get("PINOT_TPU_PIPELINE", "1") != "0"
        from pinot_tpu.engine.dispatch import LaneGroup

        self.lanes = (
            LaneGroup(
                topology,
                metrics=self.metrics,
                stall_timeout_s=lane_stall_timeout_s,
                fault_injector=device_fault_injector,
            )
            if pipeline
            else None
        )
        # back-compat handle: the primary lane (THE lane on single-lane
        # servers — the overwhelmingly common configuration)
        self.lane = self.lanes.primary if self.lanes is not None else None
        self.executor = QueryExecutor(
            mesh=topology.primary_mesh if self.lanes is None else None,
            metrics=self.metrics,
            lane=self.lane,
            lanes=self.lanes,
        )
        self.scheduler = QueryScheduler(
            num_workers=num_workers, max_pending=max_pending, metrics=self.metrics
        )
        # pre-register the serving/integrity series (zero > absent on a
        # scrape); lane.* and heal.* register in their constructors
        for m in ("queries", "queriesShed", "queriesAbandoned",
                  "segmentsMissedServing", "crcFailures", "quarantinedSegments"):
            self.metrics.meter(m)
        # cost-accounting plane (PR 6): per-query cost totals summed
        # into the registry, plus the HBM staging-ledger gauges — the
        # capacity signal admission control / multichip staging consume.
        # All pre-registered so /metrics shows zeros before first use.
        for m in ("cost.docsScanned", "cost.bytesScanned"):
            self.metrics.meter(m)
        for t in ("cost.deviceMs", "cost.hostMs"):
            self.metrics.timer(t)
        for m in ("ingest.rowsConsumed",):
            self.metrics.meter(m)
        self.metrics.timer("ingest.commitMs")
        # distributed-join plane (engine/join.py): extraction + hash
        # join execution counters, pre-registered
        for m in (
            "join.extracts", "join.execs", "join.buildRows",
            "join.probeRows", "join.shuffleBytes", "join.broadcastBytes",
        ):
            self.metrics.meter(m)
        # workload-introspection plane: per-plan-digest rolling stats
        # (utils/planstats.py) behind /debug/plans + status()["plans"],
        # with the plan.* series and the per-tier cost counters the
        # /debug/plans tier mixes reconcile against — all pre-registered
        from pinot_tpu.utils.planstats import PlanStatsStore

        self.plan_stats = PlanStatsStore()
        for m in ("plan.recorded", "plan.explains"):
            self.metrics.meter(m)
        self.metrics.gauge("plan.digests").set_fn(self.plan_stats.digest_count)
        # ingest-aware result cache (engine/rescache.py, opt-in via
        # PINOT_TPU_RESULT_CACHE=1): keyed on (plan shape digest,
        # literal digest, segment set + staging tokens) so a stale
        # realtime answer is structurally unreachable, and invalidated
        # eagerly by LLC offset advancement + segment set changes
        from pinot_tpu.engine.rescache import ResultCache

        self.result_cache = ResultCache(metrics=self.metrics)
        for k in self._TIER_KEYS:
            self.metrics.meter(f"cost.tier.{k}")
        # device utilization & profiling plane (PR 10): occupancy +
        # achieved-rate gauges, H2D/D2H transfer counters, and the
        # on-demand jax.profiler bracket.  All pre-registered; the
        # occupancy gauges are windowed lane reads (0 while idle), the
        # sampler is opt-in (zero per-launch overhead until started).
        from pinot_tpu.engine.device import TRANSFERS
        from pinot_tpu.engine.dispatch import OccupancySampler
        from pinot_tpu.server.profiler import DeviceProfiler

        # one roofline window per lane (chip group): /debug/device and
        # the fleet rollup attribute achieved rates per lane, with the
        # rollup computed FROM the per-lane snapshots so totals always
        # equal the sum of lane snapshots.  Single-lane servers see the
        # pre-mesh single-window shape verbatim.
        if self.lanes is not None and self.lanes.size > 1:
            # per-lane windows measure against the lane's OWN chip
            # count; the rollup then divides by the full device count
            scales = [g.size for g in topology.groups]
        else:
            # one window covering every chip this server drives (1 on
            # the trivial topology — the pre-mesh figures unchanged)
            scales = [max(1, topology.num_devices)]
        self._roofline_windows = [_RooflineWindow(peak_scale=s) for s in scales]
        self._roofline_window = self._roofline_windows[0]
        self.profiler = DeviceProfiler(name=name, metrics=self.metrics)
        # one occupancy sampler per lane: a profiler bracket on a
        # lane-group server must trace EVERY chip group's occupancy,
        # not just lane 0's
        self.occupancy_samplers = (
            [OccupancySampler(lane) for lane in self.lanes.lanes]
            if self.lanes is not None
            else []
        )
        self.occupancy_sampler = (
            self.occupancy_samplers[0] if self.occupancy_samplers else None
        )
        if self.occupancy_samplers:
            # a deep-profile bracket records the occupancy time series
            # (every lane's) alongside the XLA trace; the samplers park
            # again when the capture ends (stop OR auto-stop)
            self.profiler.on_capture_end = self._stop_samplers
        if self.lanes is not None:
            lanes = self.lanes
            self.metrics.gauge("device.util.busyFraction").set_fn(
                lambda: lanes.occupancy_read("gauge", min_interval_s=0.05)[
                    "busyFraction"
                ]
            )
            self.metrics.gauge("device.util.avgQueueDepth").set_fn(
                lambda: lanes.occupancy_read("gauge", min_interval_s=0.05)[
                    "avgQueueDepth"
                ]
            )
        else:
            self.metrics.gauge("device.util.busyFraction").set(0)
            self.metrics.gauge("device.util.avgQueueDepth").set(0)
        self.metrics.gauge("device.util.h2dBytes").set_fn(
            lambda: TRANSFERS.h2d_bytes
        )
        self.metrics.gauge("device.util.d2hBytes").set_fn(
            lambda: TRANSFERS.d2h_bytes
        )
        self.metrics.gauge("device.util.achievedBytesPerSec").set_fn(
            lambda: self._roofline_rollup()["achievedBytesPerSec"]
        )
        self.metrics.gauge("device.util.achievedFlopsPerSec").set_fn(
            lambda: self._roofline_rollup()["achievedFlopsPerSec"]
        )
        self.metrics.gauge("device.util.rooflineFraction").set_fn(
            lambda: self._roofline_rollup()["rooflineFraction"]
        )
        from pinot_tpu.engine.device import LEDGER

        # NOTE: the ledger (like the staging cache) is process-global —
        # one device per process; in-process multi-server harnesses see
        # the same figure on every instance
        self.metrics.gauge("hbm.stagedBytes").set_fn(LEDGER.total_bytes)
        self.metrics.gauge("hbm.highWatermarkBytes").set_fn(
            lambda: LEDGER.high_watermark
        )
        self.metrics.gauge("hbm.stagedTables").set_fn(LEDGER.table_count)
        self.metrics.gauge("hbm.evictedBytes").set_fn(lambda: LEDGER.evicted_bytes)
        self.metrics.gauge("hbm.qinputCacheBytes").set_fn(
            lambda: self.executor._qinput_cache_bytes
        )
        # tiered residency plane (engine/residency.py — process-global,
        # like the ledger): per-tier bytes/counts, cap pressure, and
        # the demotion/promotion cycle counters
        from pinot_tpu.engine.residency import RESIDENCY

        self.metrics.gauge("residency.hotBytes").set_fn(RESIDENCY.hot_bytes)
        self.metrics.gauge("residency.warmBytes").set_fn(RESIDENCY.warm_bytes)
        self.metrics.gauge("residency.coldBytes").set_fn(RESIDENCY.cold_bytes)
        self.metrics.gauge("residency.pressure").set_fn(RESIDENCY.pressure)
        for _rc in (
            "demotions",
            "promotions",
            "coldDemotions",
            "coldLoads",
            "pressureDemotions",
            "prefetches",
        ):
            self.metrics.gauge(f"residency.{_rc}").set_fn(
                (lambda name: lambda: RESIDENCY.counter(name))(_rc)
            )
        for _rt in ("hot", "warm", "cold"):
            self.metrics.gauge(f"residency.{_rt}Tables").set_fn(
                (lambda t: lambda: RESIDENCY.snapshot()[f"{t}Tables"])(_rt)
            )
        # ingest backpressure governor (realtime/backpressure.py):
        # watermark pause/resume against the HBM staging ledger and the
        # instance's consuming-segment memory, shared by every realtime
        # consumer hosted here (in-process llc.py + networked
        # RemoteConsumer).  Watermarks default off; env-configured.
        from pinot_tpu.realtime.backpressure import (
            IngestBackpressure,
            instance_mutable_bytes,
        )

        self.ingest_backpressure = IngestBackpressure(
            metrics=self.metrics,
            mutable_bytes_fn=lambda: instance_mutable_bytes(self),
        )
        self._table_schemas: dict = {}  # raw table name -> Schema
        # controller-acknowledged drain state (set from the heartbeat
        # reply by the networked starter): the instance keeps serving —
        # brokers simply stop routing new covers here — but ops can see
        # the drain in status()/debug output
        self.draining = False
        # serving lease (common/fencing.py): renewed from heartbeat
        # replies by the networked starter.  While expired this server
        # keeps SERVING (read path up) but has no WRITE authority —
        # consumers freeze their completion rounds and new CONSUMING
        # transitions are deferred.  Unleased (in-process, no gateway)
        # means implicit authority.  Registers lease.held/renewals/
        # expiries; the blocked-write counters are pre-registered here.
        from pinot_tpu.common.fencing import ServingLease

        self.lease = ServingLease(metrics=self.metrics)
        for m in ("lease.blockedCommits", "lease.blockedTransitions"):
            self.metrics.meter(m)
        # controller reachability (set by the networked starter's
        # heartbeat loop): 1 while consecutive heartbeats are failing —
        # the "partitioned but riding it out" observable
        self.metrics.gauge("controller.unreachable").set(0)
        self.metrics.meter("controller.heartbeatFailures")
        # SLO & tail-latency attribution plane (ISSUE 11): one history
        # thread snapshots this registry on a cadence (served at
        # /debug/history on the admin surface); heal events spotted on
        # its tick dump a flight-recorder bundle (disabled unless
        # PINOT_TPU_FLIGHTREC_DIR is set)
        from pinot_tpu.utils.flightrec import FlightRecorder
        from pinot_tpu.utils.timeseries import HistoryRecorder

        self.history = HistoryRecorder(self.metrics, metrics=self.metrics)
        self.flightrec = FlightRecorder(
            "server",
            name,
            metrics=self.metrics,
            sources={
                "history": lambda: self.history.query(window_s=900),
                "plans": lambda: self.plan_stats.snapshot(top=20),
                "device": self.device_utilization,
                "status": self.status,
                # lazy: the auditor is constructed a few lines below
                "audit": lambda: self.auditor.snapshot(),
            },
        )
        self._last_heal_total = 0
        self.history.add_tick_hook(self._history_tick)
        # warm-start plane (server/prewarm.py): background compile
        # driver for the fleet's hot plan shapes.  Inert until a starter
        # wires a workload source (and PINOT_TPU_PREWARM_TOP_K > 0);
        # segment loads then trigger passes and status()/heartbeats
        # report the warming/ready flag brokers and the rebalancer
        # consume.
        from pinot_tpu.server.prewarm import PrewarmWorker

        self.prewarm = PrewarmWorker(self)
        # continuous correctness audit (utils/audit.py): background
        # shadow differential sampler re-checking 1-in-N production
        # replies against the host oracle — always on by default
        # (PINOT_TPU_AUDIT_SAMPLE_N=0 disables)
        from pinot_tpu.utils.audit import ShadowAuditor

        self.auditor = ShadowAuditor(self)

    # serving-tier cost-vector keys mirrored into cost.tier.* meters —
    # the ONE source in engine/results.py, so a new tier cannot
    # silently miss the reconciliation surfaces
    _TIER_KEYS = SEGMENT_TIER_KEYS

    def _roofline_rollup(self) -> dict:
        """Recent achieved-rate window across every lane.  Single lane:
        the window's snapshot verbatim (pre-mesh shape).  Lane group:
        per-lane snapshots under ``lanes`` plus a rollup computed FROM
        those snapshots — totals and achieved rates are sums over the
        concurrent lanes, and the fleet roofline fraction divides by
        the per-chip peak times the server's device count."""
        if len(self._roofline_windows) == 1:
            return self._roofline_windows[0].snapshot()
        lanes = [w.snapshot() for w in self._roofline_windows]
        out = {
            "windowS": lanes[0]["windowS"],
            "queries": sum(l["queries"] for l in lanes),
            "deviceMs": round(sum(l["deviceMs"] for l in lanes), 3),
            "deviceBytes": sum(l["deviceBytes"] for l in lanes),
            "achievedBytesPerSec": sum(l["achievedBytesPerSec"] for l in lanes),
            "achievedFlopsPerSec": sum(l["achievedFlopsPerSec"] for l in lanes),
            "lanes": lanes,
        }
        from pinot_tpu.utils.platform import platform_peaks, roofline_fractions

        peaks = dict(platform_peaks())
        n_dev = max(1, self.topology.num_devices)
        for k in ("peakFlopsPerSec", "peakBytesPerSec"):
            if peaks.get(k):
                peaks[k] = peaks[k] * n_dev
        out["rooflineFraction"] = roofline_fractions(
            out["achievedBytesPerSec"], out["achievedFlopsPerSec"], peaks=peaks
        )["rooflineFraction"]
        return out

    # -- segment lifecycle -------------------------------------------
    @staticmethod
    def _raw_table(table: str) -> str:
        for suffix in ("_OFFLINE", "_REALTIME"):
            if table.endswith(suffix):
                return table[: -len(suffix)]
        return table

    def set_table_schema(self, table: str, schema) -> None:
        """Register (or evolve) the table schema.  Existing segments are
        patched with default columns for any schema-added fields, so old
        rows keep answering after schema growth instead of being pruned
        (reference: SegmentPreProcessor -> BaseDefaultColumnHandler)."""
        from pinot_tpu.segment.default_column import inject_default_columns

        raw = self._raw_table(table)
        if self._table_schemas.get(raw) == schema:
            return  # unchanged: skip the retro-patch loop (reload CRC-skip path)
        self._table_schemas[raw] = schema
        for tname in self.data_manager.table_names():
            if self._raw_table(tname) != raw:
                continue
            tdm = self.data_manager.table(tname)
            acquired = tdm.acquire_segments()
            try:
                for sdm in acquired:
                    # only sealed segments: a consuming MutableSegment's
                    # query_view() is a throwaway snapshot rebuilt from
                    # its own schema on the next row batch — patching it
                    # would silently un-patch; it keeps being pruned for
                    # queries on the new column until it seals (the
                    # reference likewise applies schema changes to
                    # consuming segments only at the next rollover)
                    if isinstance(sdm.segment, ImmutableSegment):
                        inject_default_columns(sdm.segment, schema)
            finally:
                tdm.release_segments(acquired)

    def add_segment(
        self, table: str, segment: ImmutableSegment, verify_crc: bool = False
    ) -> None:
        """``verify_crc=True`` (the disk-load paths) recomputes the
        column-data CRC against the metadata claim before the segment
        can serve; a mismatch raises ``SegmentIntegrityError`` and
        counts a ``crcFailures`` mark (the caller quarantines)."""
        if verify_crc:
            # BEFORE default-column injection: injected columns are not
            # part of the on-disk CRC claim and would skew the recompute
            from pinot_tpu.segment.format import verify_segment_crc

            try:
                verify_segment_crc(segment)
            except Exception:
                self.metrics.meter("crcFailures").mark()
                raise
        schema = self._table_schemas.get(self._raw_table(table))
        if schema is not None and isinstance(segment, ImmutableSegment):
            from pinot_tpu.segment.default_column import inject_default_columns

            inject_default_columns(segment, schema)
        self.data_manager.add_segment(table, segment)
        # segment set changed: cached answers over the old cover are
        # superseded (the staleness fence's segment-lifecycle edge)
        self.result_cache.invalidate_table(self._raw_table(table))
        # and the compile working set may have grown: kick a prewarm
        # pass (debounced; inert without a wired workload source)
        self.prewarm.request_prewarm(self._raw_table(table))

    def remove_segment(self, table: str, name: str) -> None:
        tdm = self.data_manager.table(table)
        if tdm is not None:
            tdm.remove_segment(name)
        self.result_cache.invalidate_table(self._raw_table(table))

    def record_crc_failure(self, table: str, name: str) -> None:
        """A disk copy failed its integrity check (load or fetch)."""
        logger.warning("segment %s/%s failed CRC verification", table, name)
        self.metrics.meter("crcFailures").mark()

    def quarantine_segment(self, table: str, name: str) -> None:
        """Pull a corrupt segment out of serving: drop it from the data
        manager AND evict any staged device arrays built from the
        corrupt load — the staging cache keys on (name, claimed crc),
        which a clean re-fetch would collide with."""
        from pinot_tpu.engine.device import evict_staged_segment

        self.remove_segment(table, name)
        evict_staged_segment(name)
        self.metrics.meter("quarantinedSegments").mark()
        logger.warning("segment %s/%s quarantined pending re-fetch", table, name)

    # -- query path ---------------------------------------------------
    def handle_request(self, payload: bytes) -> bytes:
        """Framed request bytes -> framed DataTable bytes."""
        t_start = time.perf_counter()
        # the two codecs lie outside the server's span tree by
        # construction (the request says whether there is one; the
        # reply carries it): timer and annotation only.  In the
        # broker's tree they are serverAttempt's self time.
        with boundary("deserializeRequest", None, self.metrics.timer("phase.deserializeRequest")):
            req = deserialize_instance_request(payload)
        rid = str(req.get("requestId") or "")
        # untraced requests share the NULL context: no span allocation
        # anywhere on this path (the zero-overhead contract)
        trace = (
            TraceContext(enabled=True, scope=self.name, trace_id=rid)
            if req.get("trace")
            else NULL_TRACE
        )
        with boundary("serverQuery", trace, requestId=rid, server=self.name) as root:
            result = self._answer(req, trace, root.span_id, t_start)
        if trace.enabled:
            result.trace.update(trace.to_dict())
        with boundary("serializeResult", None, self.metrics.timer("phase.serializeResult"),
                      requestId=rid):
            return serialize_result(result)

    def _answer(
        self, req: dict, trace: TraceContext, root_id: Optional[str], t_start: float
    ) -> IntermediateResult:
        """Queue, execute and account one request under its
        ``serverQuery`` root."""
        # ONE deadline for both queueing tiers: the scheduler checks it
        # at worker-dequeue time, the device lane at launch-dequeue time
        timeout_s = req["timeoutMs"] / 1000.0
        deadline = time.monotonic() + timeout_s
        outcome = "ok"  # vs "shed" / "failed": the plan-stats verdict
        try:
            # fair-share scheduling: each table queues separately and the
            # DRR dequeue guarantees a flooding tenant cannot starve the
            # others (server/scheduler.py).  The scheduler times the
            # wait (phase.schedulerWait, span queueWait under the root).
            result = self.scheduler.run(
                lambda: self._process(req, deadline, trace, root_id),
                timeout_s=timeout_s,
                deadline=deadline,
                table=req["table"],
                trace=trace,
                parent=root_id,
            )
            # the worker had the result -> this thread has it
            measured("workerWake", (time.perf_counter() - result._t_done) * 1000.0, trace,
                     self.metrics.timer("phase.workerWake"))
        except SchedulerSaturatedError as e:
            # overload shed: fast typed rejection, no stack spam — the
            # broker treats 210 as retryable and fails over to a replica
            self.metrics.meter("queriesShed").mark()
            outcome = "shed"
            result = IntermediateResult(
                exceptions=[(ErrorCode.SERVER_SCHEDULER_DOWN, str(e))]
            )
        except SchedulerShutdownError as e:
            # draining for restart: typed 220 so the broker retries the
            # segment set on a replica instead of failing the query
            outcome = "shed"
            result = IntermediateResult(
                exceptions=[(ErrorCode.SERVER_SHUTTING_DOWN, str(e))]
            )
        except QueryAbandonedError as e:
            # the broker-propagated deadline expired while this query sat
            # in the FCFS queue; reply cheaply without executing
            self.metrics.meter("queriesAbandoned").mark()
            outcome = "shed"
            result = IntermediateResult(
                exceptions=[(ErrorCode.EXECUTION_TIMEOUT, f"server {self.name}: {e}")]
            )
        except (concurrent.futures.TimeoutError, TimeoutError):
            logger.warning("query %s timed out", req.get("requestId"))
            outcome = "failed"
            result = IntermediateResult(
                exceptions=[
                    (
                        ErrorCode.EXECUTION_TIMEOUT,
                        f"server {self.name}: exceeded {req['timeoutMs']}ms",
                    )
                ]
            )
        except Exception as e:  # execution error
            logger.exception("query %s failed", req.get("requestId"))
            outcome = "failed"
            result = IntermediateResult(
                exceptions=[(ErrorCode.QUERY_EXECUTION, f"{type(e).__name__}: {e}")]
            )
        with boundary("serverBookkeeping", trace, self.metrics.timer("phase.serverBookkeeping")):
            # per-query cost totals summed into the registry (the server
            # half of the cost-accounting plane; the broker attributes the
            # merged vector per table) — error results carry zero cost
            self.metrics.meter("cost.docsScanned").mark(int(result.num_docs_scanned))
            self.metrics.meter("cost.bytesScanned").mark(
                int(result.cost.get("bytesScanned", 0))
            )
            for key, timer in (("deviceMs", "cost.deviceMs"), ("hostMs", "cost.hostMs")):
                ms = result.cost.get(key)
                if ms:
                    self.metrics.timer(timer).update(float(ms))
            # serving-tier counters: the cost-vector segment counts mirrored
            # into per-tier meters so /debug/plans tier mixes reconcile with
            # a registry-level series (all zero for plain EXPLAIN)
            for key in self._TIER_KEYS:
                n = result.cost.get(key)
                if n:
                    self.metrics.meter(f"cost.tier.{key}").mark(int(n))
            exec_ms = (time.perf_counter() - t_start) * 1000
            self._record_plan_stats(req, result, outcome, exec_ms)
            self.metrics.timer("queryExecution").update(exec_ms)
            self.metrics.meter("queries").mark()
            # event-time freshness stamp (broker/freshness.py): realtime
            # tables carry their stalest consumed partition watermark on the
            # reply so the broker can derive freshnessMs; offline tables
            # have no watermark entries and stamp nothing — their payloads
            # stay byte-identical to the pre-audit-plane wire format
            from pinot_tpu.broker.freshness import WATERMARKS

            wm = WATERMARKS.table_min_ms(req["table"])
            if wm is not None:
                result.freshness = {"minEventMs": wm}
            # backpressure snapshot on EVERY reply (including sheds): the
            # broker's AIMD admission window reads it to back off before
            # this server has to shed with 210s
            result.backpressure = {
                "pending": self.scheduler.pending,
                "maxPending": self.scheduler.max_pending,
                "laneDepth": 0
                if self.lanes is None
                else self.lanes.stats().get("depth", 0),
            }
        return result

    def _record_plan_stats(
        self, req: dict, result: IntermediateResult, outcome: str, exec_ms: float
    ) -> None:
        """Fold one handled request into the per-plan-digest registry.
        Plain EXPLAIN is excluded (it executed nothing and must mark no
        cost).  A result without a digest never got parsed: for SHED
        outcomes that is the overload fast-rejection path — re-parsing
        there would spend CPU exactly when the server is saturated, so
        un-keyed sheds are simply not per-digest-attributed (the
        aggregate queriesShed meter still counts them).  Failed
        outcomes (exceptional by definition) re-derive the digest so
        failures cross-link to their shape."""
        digest = getattr(result, "_plan_digest", None)
        summary = getattr(result, "_plan_summary", "")
        explain_mode = getattr(result, "_explain_mode", None)
        if digest is None:
            if outcome == "shed":
                return  # never parse on the overload fast path
            try:
                from pinot_tpu.engine.plandigest import (
                    plan_shape_digest,
                    plan_shape_summary,
                )

                preq = optimize_request(parse_pql(req["pql"]))
                digest = plan_shape_digest(preq)
                summary = plan_shape_summary(preq)
                explain_mode = preq.explain
            except Exception:
                return  # unparseable request: nothing to key on
        if explain_mode == "plan":
            return
        # utilization join: the device-plan digest (when this query ran
        # on device) links the shape's measured wall time to the lane's
        # static cost analysis — the per-digest roofline numerator
        device_ms = float(result.cost.get("deviceMs", 0) or 0)
        host_ms = float(result.cost.get("hostMs", 0) or 0)
        device_info = None
        ddigest = getattr(result, "_device_digest", None)
        lane_idx = int(getattr(result, "_lane_index", 0) or 0)
        lane_idx = min(lane_idx, len(self._roofline_windows) - 1)
        if ddigest is not None and self.lanes is not None:
            # the executor stamped which chip-group lane executed; that
            # lane's compile registry holds the digest's cost analysis
            lane = self.lanes.lanes[lane_idx]
            ci = lane.compile_info(ddigest)
            if ci is None:
                ci = self.lanes.compile_info(ddigest)
            if ci is not None:
                device_info = {"digest": ddigest}
                if self.lanes.size > 1:
                    device_info["lane"] = lane_idx
                analysis = ci.get("costAnalysis")
                if isinstance(analysis, dict):
                    device_info.update(
                        {
                            k: analysis[k]
                            for k in ("flops", "bytesAccessed", "peakMemoryBytes")
                            if k in analysis
                        }
                    )
        if device_ms > 0:
            self._roofline_windows[lane_idx].record(
                device_ms,
                float(result.cost.get("deviceBytes", 0) or 0),
                float((device_info or {}).get("flops", 0) or 0),
            )
        self.plan_stats.record(
            digest,
            summary=summary,
            table=req["table"],
            latency_ms=exec_ms,
            cost=result.cost,
            num_docs=result.num_docs_scanned,
            shed=(outcome == "shed"),
            failed=(outcome == "failed"),
            device_ms=device_ms or None,
            host_ms=host_ms or None,
            device_info=device_info,
        )
        self.metrics.meter("plan.recorded").mark()

    def _history_tick(self, now: float) -> None:
        """Flight-recorder trigger on the history cadence: any heal
        activity since the last sample (device failures healed over to
        host, lane restarts, CRC quarantines) is a notable event whose
        surrounding state is worth keeping."""
        total = (
            self.metrics.meter("heal.deviceFailures").count
            + self.metrics.meter("heal.hostFailovers").count
            + self.metrics.meter("crcFailures").count
            + (0 if self.lanes is None else self.lanes.restart_count)
        )
        delta = total - self._last_heal_total
        self._last_heal_total = total
        if delta > 0:
            self.flightrec.maybe_dump("healEvent", {"healEventsThisTick": delta})

    def status(self) -> dict:
        """Serving-surface snapshot: scheduler depth/shed, device-lane
        depth + coalesce/dispatch/shed counters, the per-stage phase
        timers (staging/planBuild/laneWait/planExec/finalize) inside the
        metrics snapshot, and the self-healing counters (device
        failures, host failovers, lane restarts, poisoned plans, CRC
        failures, quarantined segments)."""
        heal = self.executor.healing_stats()
        heal["laneRestarts"] = 0 if self.lanes is None else self.lanes.restart_count
        heal["crcFailures"] = self.metrics.meter("crcFailures").count
        heal["quarantinedSegments"] = self.metrics.meter("quarantinedSegments").count
        from pinot_tpu.engine.device import LEDGER
        from pinot_tpu.engine.residency import RESIDENCY

        hbm = LEDGER.snapshot()
        hbm["qinputCacheBytes"] = self.executor._qinput_cache_bytes
        return {
            "name": self.name,
            "draining": self.draining,
            "warming": self.prewarm.warming,
            "ready": not self.prewarm.warming,
            "prewarm": self.prewarm.state(),
            "lease": self.lease.snapshot(),
            "scheduler": self.scheduler.stats(),
            # single lane: the lane's stats verbatim; lane group: the
            # summed rollup with a per-lane list under "lanes"
            "lane": None if self.lanes is None else self.lanes.stats(),
            "mesh": self.topology.snapshot(),
            "selfHealing": heal,
            "hbm": hbm,
            "residency": RESIDENCY.snapshot(),
            "device": self.device_utilization(),
            "ingest": self.ingest_backpressure.snapshot(),
            "rescache": self.result_cache.snapshot(),
            "audit": self.auditor.snapshot(),
            "plans": self.plan_stats.snapshot(top=20),
            "metrics": self.metrics.snapshot(),
        }

    def audit_snapshot(self) -> dict:
        """``/debug/audit`` (admin surface): the shadow-audit sampler's
        counters + quarantined (digest, tier) pairs."""
        return self.auditor.snapshot()

    def segment_crcs(self) -> dict:
        """``/debug/segments``: every hosted sealed segment's claimed
        CRC, for the controller's cross-replica checksum sweep
        (``CrcAuditManager``).  Consuming mutable segments carry no CRC
        claim yet and are omitted."""
        out: Dict[str, Dict[str, int]] = {}
        for tname in self.data_manager.table_names():
            tdm = self.data_manager.table(tname)
            if tdm is None:
                continue
            acquired = tdm.acquire_segments()
            try:
                for sdm in acquired:
                    meta = getattr(sdm.segment, "metadata", None)
                    crc = getattr(meta, "crc", None)
                    if crc is not None:
                        out.setdefault(tname, {})[sdm.name] = int(crc)
            finally:
                tdm.release_segments(acquired)
        return {"segments": out}

    def segment_copy_bytes(self, table: str, segment: str) -> Optional[bytes]:
        """Serialize this server's loaded copy of a sealed segment for
        reverse replication (the ``DeepStoreScrubber`` repairing a
        lost/corrupt deep-store copy from a live replica).  The copy is
        CRC-verified BEFORE serialization — a donor must never launder
        its own rot into the durable store.  Returns None when the
        segment isn't hosted here, is still mutable (consuming), or
        fails verification."""
        import tempfile

        from pinot_tpu.segment.format import (
            SEGMENT_FILE_NAME,
            SegmentIntegrityError,
            verify_segment_crc,
            write_segment,
        )

        tdm = self.data_manager.table(table)
        if tdm is None:
            return None
        acquired = tdm.acquire_segments()
        try:
            for sdm in acquired:
                if sdm.name != segment:
                    continue
                seg = sdm.segment
                if getattr(seg, "metadata", None) is None or not hasattr(
                    seg, "columns"
                ):
                    return None  # mutable consuming segment: no durable form
                try:
                    verify_segment_crc(seg, source=f"donor:{self.name}")
                except SegmentIntegrityError:
                    return None
                with tempfile.TemporaryDirectory() as td:
                    write_segment(seg, td)
                    with open(os.path.join(td, SEGMENT_FILE_NAME), "rb") as f:
                        return f.read()
        finally:
            tdm.release_segments(acquired)
        return None

    def profile_start(self, timeout_s: Optional[float] = None) -> dict:
        """Begin (or join) an on-demand profile capture: the jax
        profiler trace starts/extends AND the lane occupancy sampler
        runs for the capture's duration.  Raises
        ``ProfilerUnavailableError`` (typed 404 on the admin surface)
        when the backend has no working profiler."""
        snap = self.profiler.start(timeout_s)
        for sampler in self.occupancy_samplers:
            sampler.start()
        return snap

    def _stop_samplers(self) -> None:
        for sampler in self.occupancy_samplers:
            sampler.stop()

    def profile_stop(self) -> dict:
        """Release one profile start; sampler parks when the capture
        actually ends (refcount zero — the on_capture_end hook)."""
        return self.profiler.stop()

    def device_utilization(self) -> dict:
        """Device utilization snapshot (the ``status()["device"]``
        section and the controller ``/debug/utilization`` rollup's
        per-server unit): declared platform peaks, windowed lane
        occupancy, cumulative H2D/D2H transfer totals, the recent
        achieved-rate window, profiler state, and
        (when the opt-in sampler is running) its queue-depth-over-time
        ring."""
        from pinot_tpu.engine.device import TRANSFERS
        from pinot_tpu.utils.platform import platform_peaks

        occupancy = None
        if self.lanes is not None:
            occupancy = self.lanes.occupancy_read("status")
            occupancy["open"] = self.lanes.stats().get("open", 0)
        out = {
            "platform": platform_peaks(),
            "mesh": self.topology.snapshot(),
            "occupancy": occupancy,
            "transfers": TRANSFERS.snapshot(),
            "recent": self._roofline_rollup(),
            "profiler": self.profiler.snapshot(),
        }
        if self.occupancy_sampler is not None and (
            self.occupancy_sampler.running
            or self.occupancy_sampler.samples_taken
        ):
            out["sampler"] = self.occupancy_sampler.snapshot()
        if len(self.occupancy_samplers) > 1 and any(
            s.running or s.samples_taken for s in self.occupancy_samplers
        ):
            out["samplers"] = [s.snapshot() for s in self.occupancy_samplers]
        return out

    def metrics_text(self) -> str:
        """Prometheus exposition of this server's registry (served at
        ``/metrics`` by the admin HTTP surface).  The lane/scheduler
        gauges update on activity; self-healing counters live in the
        same registry (heal.*, crcFailures, quarantinedSegments)."""
        return prometheus_text(self.metrics)

    def shutdown(self) -> None:
        """Idempotent: drain-stop the scheduler, close the device lane
        (queued lane waiters fail fast with LaneClosedError), stop the
        occupancy sampler, and force-stop any active profile capture."""
        self.scheduler.shutdown()
        self.prewarm.stop()
        self.auditor.stop()
        self.history.stop()
        self._stop_samplers()
        self.profiler.shutdown()
        if self.lanes is not None:
            self.lanes.close()

    def _process(
        self,
        req: dict,
        deadline: Optional[float] = None,
        trace: TraceContext = NULL_TRACE,
        parent: Optional[str] = None,
    ) -> IntermediateResult:
        """On a scheduler worker: ``trace`` is the request's tree and
        ``parent`` its ``serverQuery`` root, opened by the thread that
        waits for this one."""
        token = set_current(trace if trace.enabled else None, parent)
        try:
            with boundary("serverParse", trace, self.metrics.timer("phase.serverParse")):
                request = parse_pql(req["pql"])
                request.debug_options = dict(req.get("debugOptions") or {})
                request = optimize_request(request)
                # plan-stats keying, computed where the parsed request
                # exists so the recording path needs no second parse
                from pinot_tpu.engine.plandigest import plan_shape_digest, plan_shape_summary

                digest, summary = plan_shape_digest(request), plan_shape_summary(request)
            request.enable_trace = trace.enabled
            result = self._process_traced(req, request, trace, deadline)
        finally:
            reset_current(token)
        result._plan_digest = digest
        result._plan_summary = summary
        result._explain_mode = request.explain
        result._t_done = time.perf_counter()  # for the waiting thread's workerWake
        return result

    def _process_traced(
        self,
        req: dict,
        request,
        trace: TraceContext,
        deadline: Optional[float],
    ) -> IntermediateResult:
        tdm = self.data_manager.table(req["table"])
        if tdm is None:
            # fall through to the trace attach below: the span tree
            # for a misrouted query is exactly what an operator
            # debugging stale routing needs to see
            result = IntermediateResult(
                exceptions=[
                    (ErrorCode.SERVER_SCHEDULER_DOWN, f"table {req['table']} not on server {self.name}")
                ]
            )
            trace.event("tableNotHosted", table=req["table"])
            return result
        names: Optional[Sequence[str]] = req["segments"] or None
        # refcounts taken, missing segments found, query views built
        acquiring = boundary("segmentAcquire", trace, self.metrics.timer("phase.segmentAcquire")).start()
        acquired: list = []
        try:
            acquired = tdm.acquire_segments(names)
            # honest degradation: requested segments this server cannot
            # serve right now (dropped, quarantined pending re-fetch…)
            # are REPORTED, not silently skipped — the broker re-covers
            # them on a replica or flips partialResponse /
            # numSegmentsUnserved for the client
            missing: List[str] = []
            if names:
                held = {a.name for a in acquired}
                missing = [n for n in names if n not in held]
                if missing:
                    self.metrics.meter("segmentsMissedServing").mark(len(missing))
            views = [a.query_view() for a in acquired]
            acquiring.stop()
            if req.get("join"):
                # distributed-join phase request (broker/joinplan.py):
                # extraction or join execution over the local views,
                # through the SAME fair-share scheduler slot this
                # request already queued in — one tenant's join
                # traffic is bounded exactly like its scans
                result = self._process_join(
                    req, request, req["join"], views, deadline, trace
                )
                result.unserved_segments = missing
                return result
            if request.explain == "plan":
                # EXPLAIN: the physical plan INSTEAD of execution —
                # zero lane submissions, zero cost (safe to call in
                # production; tier-1 guarded)
                from pinot_tpu.engine.explain import build_explain_node

                with trace.span("explainPlan", segments=len(acquired)):
                    node = build_explain_node(
                        self.executor, views, request, req["table"],
                        self.name, plan_stats=self.plan_stats,
                        result_cache=self.result_cache,
                    )
                node["mode"] = "plan"
                self.metrics.meter("plan.explains").mark()
                result = IntermediateResult(
                    total_docs=int(node.get("totalDocs") or 0),
                    plan_info=[node],
                )
            else:
                # ingest-aware result cache: the key covers the
                # exact staged data generation (segment names +
                # process-unique staging tokens), so a hit is
                # provably as fresh as re-executing — and costs
                # zero device work.  Traced/EXPLAIN requests and
                # partial covers bypass (key_for + the missing
                # guard); results with exceptions are never stored.
                ckey = None
                cache = self.result_cache
                if cache.enabled and not missing:
                    ckey = cache.key_for(request, views, req["table"])
                result = cache.get(ckey) if ckey is not None else None
                if result is not None:
                    # the hit executed nothing: the live span tree
                    # records the verdict instead of phase spans
                    trace.event("rescacheHit")
                else:
                    with boundary("planAndExecute", trace, segments=len(acquired)):
                        result = self.executor.execute(
                            views, request, deadline=deadline
                        )
                    if ckey is not None and not result.exceptions:
                        cache.put(ckey, result)
                if request.explain == "analyze":
                    # EXPLAIN ANALYZE: the prediction is built AFTER
                    # execution (so quarantine/compile state reflects
                    # what just happened) and annotated with actuals
                    # straight off this reply's cost vector — the
                    # per-node actuals sum EXACTLY to the broker's
                    # merged cost because only merged replies'
                    # plan nodes survive the gather
                    from pinot_tpu.engine.explain import (
                        _json_safe,
                        build_explain_node,
                    )

                    node = build_explain_node(
                        self.executor, views, request, req["table"],
                        self.name, plan_stats=self.plan_stats,
                        result_cache=self.result_cache,
                    )
                    node["mode"] = "analyze"
                    node["actualCost"] = _json_safe(dict(result.cost))
                    node["actualDocsScanned"] = int(result.num_docs_scanned)
                    dev_node = node.get("device")
                    if isinstance(dev_node, dict) and "batching" in dev_node:
                        # batching ACTUAL off this very execution:
                        # how many same-shape peers the launch
                        # carried.  (No actualCacheHit field:
                        # ANALYZE always executes — the cache is
                        # keyed off for explain modes — so the
                        # standing-entry probe `cacheHit` is the
                        # honest cache signal here.)
                        dev_node["batching"]["actualBatchSize"] = int(
                            getattr(result, "_batch_size", 1) or 1
                        )
                    result.plan_info = [node]
            if not missing:
                # shadow-audit sampling hook (utils/audit.py): the
                # held views pin the exact served snapshot; the
                # offer itself is one counter increment for the
                # non-sampled 1-in-N losers
                self.auditor.offer(req, request, views, result)
            result.unserved_segments = missing
        finally:
            acquiring.stop()
            tdm.release_segments(acquired)
        return result

    # -- distributed joins (engine/join.py + broker/joinplan.py) ------
    def _extract_bytes(self, views, columns) -> int:
        total = 0
        for seg in views:
            for c in columns:
                col = seg.columns.get(c)
                if col is not None and getattr(col, "fwd", None) is not None:
                    total += col.fwd.nbytes
        return total

    def _process_join(
        self, req: dict, request, jctx: dict, views, deadline, trace
    ) -> IntermediateResult:
        """One join-phase request: ``extract`` returns the side's
        matched rows as a dict-encoded exchange payload; ``exec`` runs
        the hash join (device kernel with host heal) over local and/or
        shipped sides and returns normal mergeable partials."""
        from pinot_tpu.engine import join as join_mod

        spec = request.join
        if spec is None:
            return IntermediateResult(
                exceptions=[
                    (ErrorCode.QUERY_EXECUTION, "join context on a non-join query")
                ]
            )
        phase = jctx.get("phase")
        t0 = time.perf_counter()
        try:
            left_f, right_f = join_mod.split_join_filter(request)
            left_cols, right_cols = join_mod.side_columns(request)
            if phase == "extract":
                side_name = jctx.get("side")
                with self.executor._phase("joinExtract", side=side_name, segments=len(views)):
                    if side_name == "build":
                        stripped = [spec.strip_right(c) for c in right_cols]
                        name_of = {spec.strip_right(c): c for c in right_cols}
                        rows, matched = join_mod.extract_side(
                            views, right_f, spec.right_key, stripped, name_of
                        )
                        read_cols = [spec.right_key, *stripped]
                    else:
                        rows, matched = join_mod.extract_side(
                            views, left_f, spec.left_key, left_cols
                        )
                        read_cols = [spec.left_key, *left_cols]
                    res = IntermediateResult(
                        num_docs_scanned=matched,
                        total_docs=sum(v.num_docs for v in views),
                        num_segments_queried=len(views),
                    )
                    res.add_cost(
                        hostMs=round((time.perf_counter() - t0) * 1000, 3),
                        bytesScanned=self._extract_bytes(views, read_cols),
                    )
                    res.join_payload = join_mod.encode_side(rows)
                    self.metrics.meter("join.extracts").mark()
                    return res

            if phase != "exec":
                raise join_mod.JoinValidationError(
                    f"unknown join phase {phase!r}"
                )
            strategy = jctx.get("strategy")
            ckey = None
            cache = self.result_cache
            if strategy == "colocated":
                build_table = jctx.get("buildTable") or ""
                build_names = list(jctx.get("buildSegments") or ())
                tdm_b = self.data_manager.table(build_table)
                if tdm_b is None:
                    return IntermediateResult(
                        exceptions=[
                            (
                                ErrorCode.SERVER_SEGMENT_MISSING,
                                f"build table {build_table} not on server {self.name}",
                            )
                        ]
                    )
                b_acquired = tdm_b.acquire_segments(build_names or None)
                try:
                    held = {a.name for a in b_acquired}
                    miss_b = [n for n in build_names if n not in held]
                    if miss_b:
                        return IntermediateResult(
                            exceptions=[
                                (
                                    ErrorCode.SERVER_SEGMENT_MISSING,
                                    f"server {self.name}: build segments "
                                    f"unavailable: {sorted(miss_b)}",
                                )
                            ]
                        )
                    b_views = [a.query_view() for a in b_acquired]
                    # failover re-check: a child batch may land on a
                    # replica whose LOCAL build segments cover different
                    # partitions — serve only if every probe partition
                    # is locally buildable, else 230 so the broker
                    # re-covers elsewhere
                    probe_parts = {
                        join_mod.partition_of_segment(v.segment_name) for v in views
                    }
                    build_parts = {
                        join_mod.partition_of_segment(v.segment_name)
                        for v in b_views
                    }
                    if None in probe_parts or not probe_parts <= build_parts:
                        return IntermediateResult(
                            exceptions=[
                                (
                                    ErrorCode.SERVER_SEGMENT_MISSING,
                                    f"server {self.name}: local build side does "
                                    f"not cover probe partitions",
                                )
                            ]
                        )
                    # ingest-aware result cache, keyed on BOTH sides'
                    # segment sets + staging tokens: an ingest advance
                    # or segment change on EITHER table mints new
                    # tokens, so a stale joined answer is structurally
                    # unreachable (ISSUE 14 interop guard)
                    if cache.enabled:
                        ckey = cache.key_for_join(
                            request, views, b_views, req["table"], build_table
                        )
                    cached = cache.get(ckey) if ckey is not None else None
                    if cached is not None:
                        trace.event("rescacheHit")
                        return cached
                    result = self._join_exec(
                        request, spec, right_f, right_cols, b_views,
                        left_f, left_cols, views, deadline, trace,
                    )
                    result.num_segments_queried = len(views) + len(b_views)
                    if ckey is not None and not result.exceptions:
                        cache.put(ckey, result)
                finally:
                    tdm_b.release_segments(b_acquired)
            elif strategy == "broadcast":
                build = join_mod.decode_side(jctx["build"])
                result = self._join_exec(
                    request, spec, None, right_cols, None,
                    left_f, left_cols, views, deadline, trace, build=build,
                )
                result.num_segments_queried = len(views)
                bbytes = build.nbytes()
                result.add_cost(broadcastBytes=bbytes)
                self.metrics.meter("join.broadcastBytes").mark(bbytes)
            elif strategy == "shuffle":
                build = join_mod.decode_side(jctx["build"])
                probe = join_mod.decode_side(jctx["probe"])
                sbytes = build.nbytes() + probe.nbytes()
                with trace.span(
                    "joinExec", strategy="shuffle", buildRows=build.n,
                    probeRows=probe.n,
                ):
                    result = self.executor.execute_join(
                        request, build, probe, deadline=deadline
                    )
                result.add_cost(shuffleBytes=sbytes)
                self.metrics.meter("join.shuffleBytes").mark(sbytes)
            else:
                raise join_mod.JoinValidationError(
                    f"unknown join strategy {strategy!r}"
                )
            self.metrics.meter("join.execs").mark()
            self.metrics.meter("join.buildRows").mark(
                int(result.cost.get("buildRows", 0))
            )
            self.metrics.meter("join.probeRows").mark(
                int(result.cost.get("probeRows", 0))
            )
            return result
        except join_mod.JoinValidationError as e:
            # a typed client error, never a crash: the broker surfaces
            # it as QUERY_VALIDATION (4xx), and it is NOT retryable
            return IntermediateResult(
                exceptions=[(ErrorCode.QUERY_VALIDATION, str(e))]
            )

    def _join_exec(
        self, request, spec, right_f, right_cols, b_views,
        left_f, left_cols, views, deadline, trace, build=None,
    ) -> IntermediateResult:
        """Local probe-side extraction (+ build-side for colocated),
        then the healed hash join."""
        from pinot_tpu.engine import join as join_mod

        t0 = time.perf_counter()
        if build is None:
            stripped = [spec.strip_right(c) for c in right_cols]
            name_of = {spec.strip_right(c): c for c in right_cols}
            with trace.span("joinBuildLocal", segments=len(b_views)):
                build, _m = join_mod.extract_side(
                    b_views, right_f, spec.right_key, stripped, name_of
                )
        with trace.span("joinProbeLocal", segments=len(views)):
            probe, matched = join_mod.extract_side(
                views, left_f, spec.left_key, left_cols
            )
        self.metrics.timer("phase.joinExtract").update(
            (time.perf_counter() - t0) * 1000
        )
        with trace.span(
            "joinExec", buildRows=build.n, probeRows=probe.n
        ):
            result = self.executor.execute_join(
                request, build, probe, deadline=deadline
            )
        return result
