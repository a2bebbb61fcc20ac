"""Device lane: the single-threaded dispatch stage of the serving
pipeline, with identical-dispatch coalescing.

The whole table executes as ONE vmapped XLA program, so the chip is a
single serialized execution lane — unlike the reference's per-segment
operator trees, there is nothing to gain from launching kernels from
many threads, and every millisecond a scheduler worker spends on host
planning or finalize while *holding* the device is a millisecond the
chip idles.  The server query path is therefore a three-stage pipeline:

  PREP      (QueryScheduler worker pool): prune -> stage lookup ->
            StaticPlan -> QueryInputs -> H2D uploads
  DISPATCH  (this module, one thread): kernel launches only.  Launches
            are asynchronous — jax returns device buffers before the
            program finishes, so the lane keeps the device queue fed
            while earlier queries are still executing/finalizing.
  FINALIZE  (back on the worker that submitted): the first D2H read
            (``np.asarray`` on the packed output buffer) blocks until
            the program completes, then partials build host-side.

COALESCING: waiters whose (StaticPlan, staged-table identity,
query-inputs digest) match a dispatch that is queued, launching, or
still EXECUTING on device attach to it instead of enqueueing their own
— the one set of output buffers fans out to every waiter, so N
concurrent dashboard-style repeats of the same query cost ONE kernel
launch.  Identical key implies identical device inputs implies
identical outputs, and each waiter still runs its own FINALIZE, so
results stay independent per query.  The window ends the moment the
program's outputs are ready (``jax.Array.is_ready``): past that point
handing out the buffers would be result caching, which this
deliberately is not — a query arriving after the outputs exist always
re-dispatches.

BATCHING: coalescing only merges *identical* dispatches; the
micro-batching tier merges *similar* ones.  Dispatches that share a
batch key (same StaticPlan — the literal-bucketed device program, so
``a>5`` and ``a>999`` share it — same staged-table token, same
query-input signature) and carry a ``BatchSpec`` are collected at
dequeue time into ONE vmapped launch: the staged columns are read once
while every member's literals ride a stacked batch axis
(``kernel.make_packed_batched_table_kernel``), and each member's
FINALIZE slices its own row out of the one packed fetch — payloads stay
byte-identical to unbatched execution.  The batch window is adaptive:
an idle lane launches immediately (batching must never add latency when
the device is free), while demonstrated same-shape demand (>= 2 members
already queued — the lane-depth signal PR 7's admission plane feeds)
holds the window open up to ``PINOT_TPU_BATCH_WINDOW_MS`` for more
arrivals, filling to ``PINOT_TPU_BATCH_MAX``.

DEADLINES: each waiter carries the broker-propagated monotonic deadline
(server/scheduler.py semantics).  A waiter whose deadline expired while
its dispatch sat in the lane queue — or while its batch was forming —
is shed with the existing ``QueryAbandonedError`` before any device
work happens on its behalf, without poisoning batchmates; a dispatch
all of whose waiters expired is dropped without launching.

SUPERVISION: the lane is the server's single point of device contact,
so it is also where device faults are contained.  Every launch
exception is classified into a typed ``DeviceExecutionError``
(retryable transient vs deterministic poison) before it reaches a
waiter, and a watchdog thread detects an in-flight launch stalled past
``stall_timeout_s``: the wedged lane thread is abandoned (generation
bump — when its launch finally returns it discards the result and
exits), the stalled dispatch's waiters get a ``stalled`` error (the
executor fails them over to the host path), and a fresh lane thread is
spawned that re-drives everything still queued.  One bad kernel launch
never takes down serving.

Counters (surfaced via the server status/metrics snapshot):
lane depth gauge, dispatch/coalesce-hit/shed meters, device-failure /
restart / stale-completion counters, and the ``phase.laneDispatch``
timer for time spent inside launches.
"""
from __future__ import annotations

import atexit
import os
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Deque, Dict, Hashable, List, Optional, Tuple

from pinot_tpu.engine import compilecache
from pinot_tpu.utils.trace import boundary, measured

# completed dispatches kept open (still coalescible) at once; beyond
# this the oldest close early — a bound on pinned output buffers, not
# a correctness knob
_MAX_OPEN = 32


def batch_max() -> int:
    """Upper bound on batch members per launch (PINOT_TPU_BATCH_MAX,
    default 16; <= 1 disables the micro-batching tier)."""
    try:
        return int(os.environ.get("PINOT_TPU_BATCH_MAX", "16"))
    except ValueError:
        return 16


def batch_window_s() -> float:
    """Bounded batch-formation window in seconds
    (PINOT_TPU_BATCH_WINDOW_MS, default 2.0 ms; 0 disables the wait —
    only already-queued peers batch)."""
    try:
        return float(os.environ.get("PINOT_TPU_BATCH_WINDOW_MS", "2.0")) / 1000.0
    except ValueError:
        return 0.002
# poll period for closing open dispatches while the queue is idle; the
# check is a non-blocking is_ready() per open dispatch
_SWEEP_S = 0.005

# every lane ever constructed, for the test-suite thread-leak check
# (tests/conftest.py): a CLOSED lane must not keep threads alive
_all_lanes: "weakref.WeakSet[DeviceLane]" = weakref.WeakSet()

# Zero-overhead contract counter for the occupancy plane (the PR 4
# SPAN_ALLOCATIONS analog): incremented ONLY when an OccupancySampler
# records a sample.  The lane's own busy/depth accounting is plain
# float accumulation on state transitions — with no sampler running, a
# launch allocates nothing occupancy-related, and the tests hold this
# counter at zero to prove it.
OCCUPANCY_ALLOCATIONS = 0

# Interpreter-shutdown fence for the cost-analysis helper threads: a
# daemon thread mid-XLA-trace while the runtime's C++ statics destruct
# can abort the whole process (std::terminate), so at exit we stop
# spawning new analyses and drain the in-flight ones (bounded join —
# an analysis is a trace, not a compile, so this is fast).
_shutting_down = False
_cost_threads_lock = threading.Lock()
_cost_threads: List[threading.Thread] = []


def _drain_cost_analysis_threads() -> None:
    global _shutting_down
    _shutting_down = True
    with _cost_threads_lock:
        pending = [t for t in _cost_threads if t.is_alive()]
        _cost_threads.clear()
    deadline = time.monotonic() + 10.0
    for t in pending:
        t.join(timeout=max(0.0, deadline - time.monotonic()))


atexit.register(_drain_cost_analysis_threads)


class DeviceExecutionError(RuntimeError):
    """Typed device-dispatch failure — the lane-supervision contract.

    ``retryable=True``: transient (transfer hiccup, device busy) — one
    more device attempt is worth it.  ``retryable=False``: poison — the
    failure is deterministic for this (plan, inputs) shape (trace-time
    type error, compile failure, injected poison), so the executor
    quarantines the plan and serves via the host path.  ``stalled``
    marks watchdog-detected wedges (never device-retried: the retry
    would wedge the fresh lane thread for another full timeout).
    ``resource_exhausted`` marks device allocation failures — a
    DISTINCT heal class (engine/residency.py): retrying into the same
    full HBM would fail identically, so the executor demotes the
    coldest residents first, and never poisons the plan (the plan is
    healthy; the device was just full)."""

    def __init__(
        self,
        message: str,
        retryable: bool,
        cause: Optional[BaseException] = None,
        stalled: bool = False,
        resource_exhausted: bool = False,
    ) -> None:
        super().__init__(message)
        self.retryable = retryable
        self.cause = cause
        self.stalled = stalled
        self.resource_exhausted = resource_exhausted


# substrings that mark a launch failure as transient: PJRT/XLA status
# codes for resource pressure and transport trouble, plus connection
# wording.  Anything else (TypeError from tracing, lowering
# and shape errors, INVALID_ARGUMENT…) is deterministic for the plan —
# poison, not worth a device retry.
_RETRYABLE_MARKERS = (
    "resource_exhausted",
    "unavailable",
    "aborted",
    "data_loss",
    "cancelled",
    "deadline_exceeded",
    "connection",
    "transfer",
)

# substrings marking the failure as ALLOCATION pressure (PJRT's
# RESOURCE_EXHAUSTED status and XLA's allocator wording): retryable,
# but only after the residency manager has made room — see
# DeviceExecutionError.resource_exhausted above.
_OOM_MARKERS = (
    "resource_exhausted",
    "out of memory",
    "out-of-memory",
)


def classify_device_error(exc: BaseException) -> DeviceExecutionError:
    """Wrap a raw launch exception in the typed error (idempotent)."""
    if isinstance(exc, DeviceExecutionError):
        return exc
    text = f"{type(exc).__name__}: {exc}"
    low = text.lower()
    oom = any(marker in low for marker in _OOM_MARKERS)
    retryable = oom or any(marker in low for marker in _RETRYABLE_MARKERS)
    return DeviceExecutionError(
        text, retryable=retryable, cause=exc, resource_exhausted=oom
    )


def plan_digest(plan: Any) -> str:
    """Stable (within a process) digest of a StaticPlan — the handle the
    device fault injector and the executor's poison quarantine share.
    StaticPlan is a frozen dataclass, so repr is deterministic."""
    import hashlib

    return hashlib.blake2b(repr(plan).encode(), digest_size=8).hexdigest()


def leaked_lane_threads(grace_s: float = 2.0) -> List[threading.Thread]:
    """Threads still alive on CLOSED lanes — the post-test leak check
    guarding the watchdog-restart path (a restart must never leak one
    wedged thread per wedge once the wedge resolves and the lane is
    closed).  Open lanes (module-scoped fixtures) are exempt."""
    suspects: List[threading.Thread] = []
    for lane in list(_all_lanes):
        if not lane._closed:
            continue
        suspects.extend(t for t in lane._threads if t.is_alive())
    deadline = time.monotonic() + grace_s
    leaked = []
    for t in suspects:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
        if t.is_alive():
            leaked.append(t)
    return leaked


def outputs_pending(value: Any) -> bool:
    """True while any jax-array leaf of a launch's return value has not
    finished computing — the coalescibility window for a launch that
    already returned.  Values with no device arrays report False (no
    retention)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(value):
        is_ready = getattr(leaf, "is_ready", None)
        if is_ready is not None:
            try:
                if not is_ready():
                    return True
            except Exception:
                return False
    return False


class LaneClosedError(RuntimeError):
    """Submit after close(), or queued work drained by close()."""


class QueryAbandonedError(RuntimeError):
    """A query's deadline expired before its work started (in the
    scheduler's queue, or waiting for the lane): the broker already gave
    up on this reply.  ``server.scheduler`` re-exports it."""


class LaneTicket:
    """One waiter's slot: the submitting worker blocks on ``result`` and
    resumes FINALIZE when the lane delivers outputs (or an error).
    ``coalesced`` marks a ticket that attached to an identical in-flight
    dispatch instead of enqueueing its own (trace/metrics attribution);
    ``batch_size`` is the member count of the batched launch this
    ticket's dispatch rode (1 = unbatched)."""

    __slots__ = ("deadline", "coalesced", "batch_size", "delivered_at", "_event", "_value",
                 "_error", "_dispatch")

    def __init__(self, deadline: Optional[float]) -> None:
        self.deadline = deadline
        self.coalesced = False
        self.batch_size = 1
        self.delivered_at = 0.0  # perf_counter at delivery, for the waiter's laneWake
        self._dispatch: Optional["_Dispatch"] = None  # for DeviceLane.output_ready
        self._event = threading.Event()
        self._value: Any = None
        self._error: Optional[BaseException] = None

    def _deliver(self, value: Any = None, error: Optional[BaseException] = None) -> None:
        self._value = value
        self._error = error
        self.delivered_at = time.perf_counter()
        self._event.set()

    def result(self, deadline: Optional[float] = None) -> Any:
        """Block until the dispatch delivers; honors the query deadline
        (raises the builtin ``TimeoutError`` like ``QueryScheduler.run``
        so the instance's timeout reply path handles both stages)."""
        timeout = None
        if deadline is not None:
            timeout = max(0.0, deadline - time.monotonic())
        if not self._event.wait(timeout):
            raise TimeoutError("device lane result exceeded query deadline")
        if self._error is not None:
            raise self._error
        return self._value


class BatchSpec:
    """One dispatch's micro-batching contract (executor-built).

    ``key``: hashable batch-equivalence key — dispatches with equal keys
    stack into one launch.  The executor keys on (StaticPlan,
    staged-table token, query-input signature): one device program, one
    resident table, structurally identical input pytrees.
    ``inputs``: this query's HOST numpy query-input pytree (the
    pre-upload form — batched members upload ONCE, stacked).
    ``launch_batched``: callable(list of member input pytrees) ->
    ``(fetch, handle)`` launching the vmapped batched kernel; ``fetch``
    returns the whole batch's host outputs in one packed D2H.
    ``max_members``: per-plan cap below the lane-wide PINOT_TPU_BATCH_MAX
    (the executor bounds it so batch x rows stays under the per-dispatch
    row budget — batching must not blow HBM at compile time)."""

    __slots__ = ("key", "inputs", "launch_batched", "max_members")

    def __init__(
        self,
        key: Hashable,
        inputs: Any,
        launch_batched: Callable[[List[Any]], Any],
        max_members: int = 0,
    ) -> None:
        self.key = key
        self.inputs = inputs
        self.launch_batched = launch_batched
        self.max_members = max_members


class _BatchFetch:
    """Shared FINALIZE handle for one batched launch: the FIRST member
    to need outputs performs the ONE packed D2H fetch (counted once —
    the PR 10 transfer-accounting contract); every member then slices
    its leading-axis row from the cached host pytree.  Thread-safe:
    members finalize concurrently on their own scheduler workers."""

    def __init__(self, fetch: Callable, size: int) -> None:
        self._fetch = fetch
        self._lock = threading.Lock()
        self._outs: Any = None
        self._error: Optional[BaseException] = None
        self.size = size

    def _resolve(self, handle) -> Any:
        with self._lock:
            if self._error is not None:
                raise self._error
            if self._outs is None:
                try:
                    self._outs = self._fetch(handle, count_transfer=True)
                except BaseException as e:
                    self._error = e
                    raise
            return self._outs

    def member(self, index: int) -> Callable:
        def fetch_member(handle, count_transfer: bool = True) -> Any:
            # count_transfer is ignored by design: the one physical D2H
            # is counted inside _resolve exactly once per batch
            outs = self._resolve(handle)
            from pinot_tpu.engine.packing import slice_batched_outputs

            return slice_batched_outputs(outs, index)

        return fetch_member


class _Dispatch:
    __slots__ = (
        "key", "launch", "pending", "waiters", "completed", "value",
        "error", "plan_digest", "cost_provider", "batch", "batch_size",
        "t_submit", "trace", "parent", "program", "groupby", "operands", "expr", "cells", "blocks", "hll", "hll_parts", "segments", "selection", "launch_id",
    )

    def __init__(
        self,
        key: Hashable,
        launch: Callable[[], Any],
        pending: Callable[[Any], bool],
        plan_digest: Optional[str] = None,
        cost_provider: Optional[Callable[[], Optional[dict]]] = None,
        batch: Optional[BatchSpec] = None,
        trace=None,
        parent: Optional[str] = None,
        program: str = "",
        t_submit: float = 0.0,
        groupby: str = "",
        operands: str = "",
        expr: int = 0,
        cells: Tuple[int, int] = (0, 0),
        blocks: str = "",
        hll: str = "",
        hll_parts: int = 0,
        segments: str = "",
        selection: str = "",
    ) -> None:
        self.t_submit = t_submit  # phase.laneQueue runs from here to the launch call
        # the submitting query's span tree and the span (its laneWait)
        # that the lane thread's laneQueue and laneDispatch hang under
        self.trace = trace
        self.parent = parent
        self.program = program  # the jitted program's name, for the launch's tags
        self.groupby = groupby  # a group-by program's lowering (kernel.groupby_lowering)
        self.operands = operands  # and where its operands are built (kernel.groupby_operands)
        self.expr = expr  # aggregates of the plan whose argument is a compound expression
        self.cells = cells  # a group-by's K x m cells and the rows sharing saved (kernel.groupby_cells)
        self.blocks = blocks  # how a zone-tier program reads its candidate blocks (kernel.zone_blocks)
        self.hll = hll  # the lowering of the program's HLL aggregates (kernel.hll_lowering)
        self.hll_parts = hll_parts  # under 'sort', the parts a segment's keys are sorted in (kernel.hll_sort_parts)
        self.segments = segments  # "<L>/<S>": the segments the program runs over, of the staged table's (ladder.launch_segments)
        self.selection = selection  # the form a selection's candidates are found in (kernel.selection_lowering)
        self.launch_id: Optional[int] = None  # the physical launch this rode (occupancy)
        self.key = key
        self.launch = launch
        self.pending = pending
        self.plan_digest = plan_digest
        self.cost_provider = cost_provider
        self.batch = batch
        self.batch_size = 1  # members of the batched launch this rode
        self.waiters: List[LaneTicket] = []
        self.completed = False
        self.value: Any = None
        self.error: Optional[BaseException] = None


class DeviceLane:
    """Single-threaded asynchronous kernel-launch queue with
    identical-dispatch coalescing and watchdog supervision (see module
    docstring).

    ``stall_timeout_s`` arms the watchdog (default from
    ``PINOT_TPU_LANE_STALL_S``, 300s — above the worst observed cold
    compile; <= 0 disables it).
    ``fault_injector`` is an optional ``common.faults``
    ``DeviceFaultInjector`` consulted before every launch."""

    def __init__(
        self,
        metrics=None,
        stall_timeout_s: Optional[float] = None,
        fault_injector=None,
        index: Optional[int] = None,
    ) -> None:
        # lane-group membership (engine/mesh.py): ``index`` set means
        # this lane is one of several driving distinct chip groups —
        # its gauges move to the per-lane ``lane.<i>.*`` namespace and
        # its meters mark BOTH the aggregate lane.* series (marks sum
        # naturally across lanes) and the per-lane twin.  None (the
        # default, and every single-lane server) keeps the exact
        # pre-mesh metric names.
        self.index = index
        self.metrics = metrics
        if stall_timeout_s is None:
            # default well ABOVE the worst first-call compile: a
            # watchdog that fires during a legitimate cold compile
            # poisons a healthy plan, and the host answers the shape from
            # then on (chip run, PR 47: the sorted contraction over three
            # string keys, SSB Q3.2 at [16, 2^23] rows, compiles for
            # 126 to 135 s cold; 120 s failed a server's first Q3.2)
            stall_timeout_s = float(os.environ.get("PINOT_TPU_LANE_STALL_S", "300"))
        self.stall_timeout_s = stall_timeout_s
        self.fault_injector = fault_injector
        # persistent compile cache (engine/compilecache.py): on by
        # default, at JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache.
        # None only when that directory cannot be created.  The call is
        # idempotent, so every lane of a group paying it is free.
        self.persistent_cache_dir = compilecache.configure_jax_cache()
        # micro-batching tier config (module docstring): resolved once
        # at construction so a long-lived lane is immune to env churn
        self.batch_max = batch_max()
        self.batch_window_s = batch_window_s()
        self.batch_launches = 0
        self.batched_queries = 0
        self.batch_window_full = 0
        self.batch_window_timeout = 0
        self._cv = threading.Condition()
        self._queue: Deque[_Dispatch] = deque()
        self._by_key: Dict[Hashable, _Dispatch] = {}
        self._open: Deque[_Dispatch] = deque()  # launched, program still running
        self._thread: Optional[threading.Thread] = None
        self._watchdog: Optional[threading.Thread] = None
        # spawned threads still of interest to the leak check; dead
        # entries are pruned at each registration so repeated profile
        # captures / cost-analysis spawns don't grow this without bound
        self._threads: List[threading.Thread] = []
        self._threads_lock = threading.Lock()
        # restart fencing: a wedged thread that finally returns compares
        # its spawn-time generation against this and, when stale, drops
        # its result and exits without touching lane state
        self._generation = 0
        # (leader dispatch, started_at, members tuple) while a launch
        # (possibly batched) is in flight
        self._inflight: Optional[tuple] = None
        self._closed = False
        self.dispatch_count = 0
        self.coalesce_hits = 0
        self.shed_count = 0
        self.device_failure_count = 0
        self.restart_count = 0
        self.stale_completions = 0
        # compile timeline (workload introspection): per device-plan
        # digest, the FIRST launch's wall ms — on a cold jit cache that
        # launch pays trace + XLA compile, so firstCallMs IS the measured
        # compile cost; later launches of the same digest are warm.
        # Read by EXPLAIN (cold/warm verdict + measured ms) and exposed
        # as compile.* metrics + lane.stats()["compiledPlans"].
        # Entries also accumulate per-digest launch timers
        # (launchMsTotal) and, once the async analysis lands, the
        # static XLA cost analysis ("costAnalysis": {flops,
        # bytesAccessed, ...}) — the roofline numerator.
        self._compile: Dict[str, Dict[str, float]] = {}
        # -- occupancy accounting (utilization plane) ----------------
        # Plain float accumulation at state transitions — NO per-launch
        # allocations (OCCUPANCY_ALLOCATIONS contract above).  busy =
        # wall seconds with a launch's output outstanding: the window
        # opens before the launch call and closes when the output is
        # ready (JAX dispatch is asynchronous: the call returns while
        # the device still runs), held open as the union over
        # outstanding launches.  Each closed window also lands on the
        # cumulative timer ``lane.deviceBusy``.  depth-seconds
        # integrates queue depth over time.  Windowed readers (gauges,
        # status, sampler) each diff against their own last checkpoint.
        self._busy_s = 0.0
        self._busy_since: Optional[float] = None
        self._outstanding: set = set()  # launch ids whose output is not known ready
        self._launch_seq = 0
        self._depth_s = 0.0
        self._depth_mark = time.monotonic()
        self._created_at = self._depth_mark
        self._occ_reads: Dict[str, tuple] = {}  # reader key -> (t, busy, depth_s, last_result)
        if metrics is not None:
            # pre-register the lane series (depth/inflight gauges,
            # dispatch/coalesce/shed/restart meters) so /metrics shows
            # them at zero before the first device query
            for name in ("lane.dispatches", "lane.coalesced", "lane.shed",
                         "lane.deviceFailures", "lane.restarts",
                         "compile.cold", "compile.warm",
                         "compile.persistentHit", "compile.persistentMiss",
                         "compile.prewarmed",
                         "compile.costAnalyses",
                         "compile.costAnalysisUnavailable",
                         "batch.launches", "batch.queries",
                         "batch.windowClosedFull",
                         "batch.windowClosedTimeout",
                         "batch.windowClosedIdle"):
                metrics.meter(name)
            metrics.timer("compile.firstCallMs")
            for name in ("lane.deviceBusy", "phase.laneQueue", "phase.laneDispatch",
                         "phase.laneDeliver"):
                metrics.timer(name)
            if self.index is None:
                metrics.gauge("lane.depth").set(0)
                metrics.gauge("lane.open").set(0)
                metrics.gauge("lane.inflight").set(0)
            else:
                # per-lane twins (lane.<i>.*): the group registers the
                # aggregate gauges as set_fn rollups over every lane
                for suffix in ("dispatches", "coalesced", "shed",
                               "deviceFailures", "restarts"):
                    metrics.meter(f"lane.{self.index}.{suffix}")
                metrics.gauge(f"lane.{self.index}.depth").set(0)
                metrics.gauge(f"lane.{self.index}.open").set(0)
                metrics.gauge(f"lane.{self.index}.inflight").set(0)
        _all_lanes.add(self)

    def _lane_mark(self, suffix: str, n: int = 1) -> None:
        """Mark the aggregate lane.<suffix> meter and, on a lane-group
        member, its per-lane twin lane.<index>.<suffix>."""
        if self.metrics is None:
            return
        self.metrics.meter(f"lane.{suffix}").mark(n)
        if self.index is not None:
            self.metrics.meter(f"lane.{self.index}.{suffix}").mark(n)

    # -- producer side -------------------------------------------------
    def submit(
        self,
        key: Hashable,
        launch: Callable[[], Any],
        deadline: Optional[float] = None,
        pending: Callable[[Any], bool] = outputs_pending,
        plan_digest: Optional[str] = None,
        cost_provider: Optional[Callable[[], Optional[dict]]] = None,
        batch: Optional[BatchSpec] = None,
        trace=None,
        parent: Optional[str] = None,
        program: str = "",
        groupby: str = "",
        operands: str = "",
        expr: int = 0,
        cells: Tuple[int, int] = (0, 0),
        blocks: str = "",
        hll: str = "",
        hll_parts: int = 0,
        segments: str = "",
        selection: str = "",
    ) -> LaneTicket:
        """Enqueue a kernel launch, or coalesce onto an identical one
        that is queued, launching, or still executing on device.
        Returns immediately; the caller blocks on ``ticket.result`` when
        FINALIZE actually needs the outputs.

        ``trace``/``parent``/``program``: the query's span tree, the
        span (its ``laneWait``) under which the lane thread's
        ``laneQueue`` and ``laneDispatch`` hang, and the jitted
        program's name.  A coalesced ticket gets neither span: its wait
        is all ``laneWait``.  ``groupby``: a group-by program's lowering
        (``kernel.groupby_lowering``), the launch's ``groupby=`` tag and
        its ``groupby.lowering.*`` mark; ``operands``: how its operands
        reach it (``kernel.groupby_operands``), the ``operands=`` tag, and
        one ``groupby.operands.loop`` mark a launch that builds them in
        the row loop, one ``groupby.operands.sorted`` mark a launch that
        puts them in key order; ``cells``: its K x m cells (the ``cells=`` tag) and
        the rows that sharing saved (``kernel.groupby_cells``; one
        ``groupby.slots.shared`` mark a row); ``expr``: how many of the
        plan's aggregates take a compound expression (the ``expr=`` tag);
        ``blocks``: how a zone-tier program reads its candidate blocks
        (``kernel.zone_blocks``), the ``blocks=`` tag and one
        ``zone.blocks.inplace`` or ``zone.blocks.gathered`` mark a launch
        ("" for any other program); ``hll``: the lowering of the
        program's HLL aggregates (``kernel.hll_lowering``), the ``hll=``
        tag and one ``hll.lowering.matmul|sort|scatter|pairs`` mark a
        launch ("" for a program without one); ``hll_parts``: under
        'sort', in how many parts a segment's keys are sorted
        (``kernel.hll_sort_parts``), marked on ``hll.sort.parts`` a launch;
        ``segments``: ``<L>/<S>``, the segments the program runs over of
        those the staged table holds (``ladder.launch_segments``), the
        ``segments=`` tag ("" for a program that is no table scan).

        ``cost_provider`` (optional, utilization plane): a zero-arg
        callable returning the plan's static XLA cost analysis (or
        None).  Invoked ONCE per plan digest on an async helper thread
        after the digest's first successful launch — never on the lane
        thread, so a slow analysis cannot stall serving.

        ``batch`` (optional, micro-batching tier): a ``BatchSpec``
        marking this dispatch stackable with same-key peers into one
        vmapped launch.  Identical dispatches still coalesce FIRST (one
        member, many waiters); batching merges *distinct* members."""
        ticket = LaneTicket(deadline)
        t_submit = time.monotonic()  # before the lane's lock: waiting for it is queueing too
        with self._cv:
            if self._closed:
                raise LaneClosedError("device lane is closed")
            d = self._by_key.get(key)
            if d is not None and d.completed:
                # launched already: shareable only while the program is
                # still executing (never serve finished outputs anew)
                still = d.error is None and self._still_pending(d)
                if still:
                    self._hit()
                    ticket.coalesced = True
                    # a still-pending BATCHED member hands out its
                    # member slice — the late waiter rode that batch
                    # too, so it must report the same batch size
                    ticket.batch_size = d.batch_size
                    ticket._dispatch = d
                    ticket._deliver(value=d.value)
                    return ticket
                self._close_open(d)
                d = None
            if d is not None:
                d.waiters.append(ticket)
                ticket.coalesced = True
                self._hit()
            else:
                d = _Dispatch(key, launch, pending, plan_digest, cost_provider, batch,
                              trace, parent, program, t_submit, groupby, operands, expr, cells, blocks, hll, hll_parts, segments, selection)
                d.waiters.append(ticket)
                self._by_key[key] = d
                self._depth_tick_locked()
                self._queue.append(d)
                self._set_depth()
                # notify_all: the WATCHDOG also sleeps on this condition
                # — a single notify could wake it instead of the lane
                # thread and strand the queued dispatch
                self._cv.notify_all()
            if self._thread is None:
                # lazy start: instances that never run a device query
                # (host-path tables, unit tests) cost no thread
                self._spawn_lane_locked()
                if self.stall_timeout_s and self.stall_timeout_s > 0:
                    self._spawn_watchdog_locked()
            ticket._dispatch = d
        return ticket

    def output_ready(self, ticket: LaneTicket) -> None:
        """A waiter saw its dispatch's output ready (its ``deviceWait``
        ended): the launch no longer holds the occupancy window open."""
        d = ticket._dispatch
        if d is not None and d.launch_id in self._outstanding:
            with self._cv:
                self._busy_close_locked(d.launch_id)

    @property
    def depth(self) -> int:
        return len(self._queue)

    def stats(self) -> Dict[str, int]:
        return {
            "depth": len(self._queue),
            "open": len(self._open),
            "dispatches": self.dispatch_count,
            "coalesceHits": self.coalesce_hits,
            "shed": self.shed_count,
            "deviceFailures": self.device_failure_count,
            "restarts": self.restart_count,
            "staleCompletions": self.stale_completions,
            "compiledPlans": len(self._compile),
            # micro-batching tier: batched launches, the queries they
            # carried (occupancy = batchedQueries / batchLaunches), and
            # how the formation windows closed
            "batchLaunches": self.batch_launches,
            "batchedQueries": self.batched_queries,
            "batchWindowFull": self.batch_window_full,
            "batchWindowTimeout": self.batch_window_timeout,
        }

    def compile_info(self, digest: Optional[str]) -> Optional[Dict[str, float]]:
        """Compile-timeline entry for a device-plan digest: None when
        the digest has never launched here (a query would compile cold),
        else {firstCallMs, firstAt, launches, launchMsTotal[,
        costAnalysis]}.  ``costAnalysis`` is absent while the async
        analysis is still running, a dict once it landed, and None when
        the backend reported nothing (the explicit "unavailable")."""
        if digest is None:
            return None
        with self._cv:
            entry = self._compile.get(digest)
            return dict(entry) if entry is not None else None

    def record_prewarmed(self, digest: Optional[str], compile_ms: float) -> bool:
        """Register a background-prewarmed plan digest in the compile
        timeline WITHOUT touching the serving-path meters.  The prewarm
        worker (server/prewarm.py) calls this after an AOT
        ``lower().compile()`` of the phantom kernel: the executable now
        sits in the in-process jit cache (and the on-disk cache when
        enabled), so the digest's first serving launch runs warm.
        Counts on ``compile.prewarmed`` only — never compile.cold or
        firstCallMs (accounting honesty), and never near the stall
        watchdog (the compile ran off-lane).  No-op when the digest
        already launched or prewarmed here."""
        if digest is None:
            return False
        with self._cv:
            if digest in self._compile:
                return False
            if len(self._compile) > 4096:
                victim = min(
                    self._compile, key=lambda k: self._compile[k]["firstAt"]
                )
                self._compile.pop(victim, None)
            self._compile[digest] = {
                # firstCallMs here is the MEASURED prewarm compile wall
                # ms — the cost the serving path did NOT pay
                "firstCallMs": round(compile_ms, 3),
                "firstAt": round(time.time(), 3),
                "launches": 0,
                "launchMsTotal": 0.0,
                "via": "prewarmed",
            }
        if self.metrics is not None:
            self.metrics.meter("compile.prewarmed").mark()
        if self.persistent_cache_dir is not None:
            compilecache.record_plan(digest)
        return True

    # -- occupancy (utilization plane) --------------------------------
    def _depth_tick_locked(self, now: Optional[float] = None) -> None:
        """Integrate queue depth over time (lock held, called BEFORE
        every queue mutation): pure float accumulation, no
        allocations."""
        if now is None:
            now = time.monotonic()
        self._depth_s += len(self._queue) * (now - self._depth_mark)
        self._depth_mark = now

    def _busy_close_locked(self, launch_id: Optional[int]) -> None:
        """A launch's output is ready, or never will be (error, stall,
        close).  The window closes when no launch is outstanding."""
        if launch_id not in self._outstanding:
            return
        self._outstanding.discard(launch_id)
        if not self._outstanding:
            self._busy_bank_locked()

    def _busy_bank_locked(self, now: Optional[float] = None) -> None:
        self._outstanding.clear()
        if self._busy_since is None:
            return
        busy = max(0.0, (time.monotonic() if now is None else now) - self._busy_since)
        self._busy_s += busy
        self._busy_since = None
        if self.metrics is not None:
            self.metrics.timer("lane.deviceBusy").update(busy * 1000.0)

    def occupancy_read(
        self, key: str = "default", min_interval_s: float = 0.0
    ) -> Dict[str, float]:
        """Windowed occupancy read: busy-fraction and time-weighted
        average queue depth since THIS reader's previous call (first
        call windows from lane construction).  Distinct readers (the
        device.util gauges, status(), a sampler) pass distinct keys so
        their windows never clobber each other; ``min_interval_s``
        returns the cached last result for rapid re-reads (two gauges
        sharing one key read one consistent window).  Idle lanes read
        0.0 — there is no decay to wait out."""
        now = time.monotonic()
        with self._cv:
            prev = self._occ_reads.get(key)
            if (
                prev is not None
                and min_interval_s > 0
                and now - prev[0] < min_interval_s
            ):
                return dict(prev[3])
            busy = self._busy_s
            if self._busy_since is not None:
                # count the open window's elapsed time as busy so a long
                # cold compile or a long kernel doesn't read as an idle
                # device
                busy += max(0.0, now - self._busy_since)
            self._depth_tick_locked(now)
            depth_s = self._depth_s
            if prev is None:
                prev_t, prev_busy, prev_depth = self._created_at, 0.0, 0.0
            else:
                prev_t, prev_busy, prev_depth = prev[0], prev[1], prev[2]
            dt = max(now - prev_t, 1e-9)
            result = {
                "windowS": round(dt, 6),
                "busyFraction": round(
                    min(max((busy - prev_busy) / dt, 0.0), 1.0), 6
                ),
                "avgQueueDepth": round(max((depth_s - prev_depth) / dt, 0.0), 6),
                "depth": len(self._queue),
                "inflight": 1 if self._busy_since is not None else 0,
            }
            if len(self._occ_reads) > 32 and key not in self._occ_reads:
                # bounded reader registry: evict the least-recently-read
                # checkpoint only — clearing everything would reset every
                # established reader's window to lane construction
                oldest = min(self._occ_reads.items(), key=lambda kv: kv[1][0])[0]
                del self._occ_reads[oldest]
            self._occ_reads[key] = (now, busy, depth_s, result)
        return dict(result)

    def close(self) -> None:
        """Idempotent: stop accepting submits, fail queued waiters, and
        let the lane + watchdog threads exit after any in-flight
        launch."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            drained = list(self._queue)
            self._depth_tick_locked()
            self._queue.clear()
            self._open.clear()
            self._busy_bank_locked()
            self._by_key.clear()
            for d in drained:
                d.completed = True
            self._cv.notify_all()
        err = LaneClosedError("device lane closed while queued")
        for d in drained:
            for w in d.waiters:
                w._deliver(error=err)

    # -- internals -----------------------------------------------------
    def _track_thread(self, t: threading.Thread) -> None:
        """Register a spawned thread for the leak check.  Builds a new
        list (atomic reference swap) so concurrent leak-check readers
        never see a half-pruned list; the dedicated lock keeps two
        registrations (lane spawn under _cv, sampler start under its
        own lock) from losing one another's entry."""
        with self._threads_lock:
            alive = [x for x in self._threads if x.is_alive()]
            alive.append(t)
            self._threads = alive

    def _spawn_lane_locked(self) -> None:
        t = threading.Thread(
            target=self._run,
            args=(self._generation,),
            name=f"device-lane-g{self._generation}",
            daemon=True,
        )
        self._thread = t
        self._track_thread(t)
        t.start()

    def _spawn_cost_analysis_locked(self, digest: str, provider) -> None:
        """One short-lived helper thread per cold plan digest: runs the
        static XLA cost analysis off the serving path and stores the
        result (or the explicit None = "unavailable") into the compile
        registry.  Registered in the leak-check list like every lane
        thread, and in the module drain list so interpreter shutdown
        joins any still-tracing analysis before XLA statics destruct."""
        if _shutting_down:
            return
        t = threading.Thread(
            target=self._run_cost_analysis,
            args=(digest, provider),
            name=f"lane-cost-analysis-{digest[:8]}",
            daemon=True,
        )
        self._track_thread(t)
        with _cost_threads_lock:
            _cost_threads[:] = [x for x in _cost_threads if x.is_alive()]
            _cost_threads.append(t)
        t.start()

    def _run_cost_analysis(self, digest: str, provider) -> None:
        if _shutting_down:
            return
        try:
            analysis = provider()
        except Exception:
            analysis = None
        if analysis is not None and not isinstance(analysis, dict):
            analysis = None
        with self._cv:
            entry = self._compile.get(digest)
            if entry is not None:
                entry["costAnalysis"] = analysis
        if self.metrics is not None:
            name = (
                "compile.costAnalyses"
                if analysis is not None
                else "compile.costAnalysisUnavailable"
            )
            self.metrics.meter(name).mark()

    def _spawn_watchdog_locked(self) -> None:
        if self._watchdog is not None:
            return
        w = threading.Thread(
            target=self._watch, name="device-lane-watchdog", daemon=True
        )
        self._watchdog = w
        self._track_thread(w)
        w.start()

    def _watch(self) -> None:
        """Watchdog: restart the lane when the in-flight launch stalls
        past ``stall_timeout_s`` — abandon the wedged thread (generation
        bump), fail the stalled dispatch's waiters with a typed stall
        error, and respawn a lane thread that re-drives the queue.

        Sleeps under the lane condition variable, waking exactly at the
        in-flight dispatch's stall deadline (or a coarse idle poll) —
        no free-running high-frequency timer, and ``close()``'s
        notify_all wakes it immediately for a prompt exit."""
        idle_poll = max(0.05, self.stall_timeout_s / 4.0)
        while True:
            victims: List[LaneTicket] = []
            err: Optional[DeviceExecutionError] = None
            with self._cv:
                if self._closed:
                    return
                infl = self._inflight
                now = time.monotonic()
                if infl is None:
                    self._cv.wait(timeout=idle_poll)
                elif now - infl[1] <= self.stall_timeout_s:
                    self._cv.wait(
                        timeout=infl[1] + self.stall_timeout_s - now + 0.005
                    )
                else:
                    # a batched launch wedges as a unit: every member's
                    # waiters get the stall verdict (the executor fails
                    # each one over to the host path independently)
                    members = infl[2]
                    self._inflight = None
                    # bank the wedged launch's window as busy time; the
                    # abandoned thread sees itself stale later and
                    # leaves the accounting alone
                    self._busy_bank_locked(now)
                    self._generation += 1
                    self.restart_count += 1
                    self.device_failure_count += 1
                    err = DeviceExecutionError(
                        f"device dispatch stalled > {self.stall_timeout_s:.3f}s; "
                        "lane restarted",
                        retryable=False,
                        stalled=True,
                    )
                    victims = []
                    for d in members:
                        d.completed = True
                        if self._by_key.get(d.key) is d:
                            self._by_key.pop(d.key)
                        victims.extend(d.waiters)
                        d.waiters = []
                        d.error = err
                    self._spawn_lane_locked()
            if victims:
                self._lane_mark("restarts")
                self._lane_mark("deviceFailures")
                for w in victims:
                    w._deliver(error=err)

    def _hit(self) -> None:
        self.coalesce_hits += 1
        self._lane_mark("coalesced")

    def _set_depth(self) -> None:
        if self.metrics is not None:
            if self.index is None:
                self.metrics.gauge("lane.depth").set(len(self._queue))
                self.metrics.gauge("lane.open").set(len(self._open))
            else:
                self.metrics.gauge(f"lane.{self.index}.depth").set(len(self._queue))
                self.metrics.gauge(f"lane.{self.index}.open").set(len(self._open))

    def _set_inflight(self, n: int) -> None:
        if self.metrics is not None:
            if self.index is None:
                self.metrics.gauge("lane.inflight").set(n)
            else:
                self.metrics.gauge(f"lane.{self.index}.inflight").set(n)

    def _still_pending(self, d: _Dispatch) -> bool:
        if d.pending is None:
            return False
        try:
            return bool(d.pending(d.value))
        except Exception:
            return False

    def _close_open(self, d: _Dispatch) -> None:
        """Drop a completed dispatch from the coalescible set (lock
        held)."""
        if self._by_key.get(d.key) is d:
            self._by_key.pop(d.key, None)
        try:
            self._open.remove(d)
        except ValueError:
            pass

    def _sweep_open_locked(self) -> None:
        for d in list(self._open):
            if d.error is not None or not self._still_pending(d):
                self._close_open(d)
                self._busy_close_locked(d.launch_id)
        while len(self._open) > _MAX_OPEN:
            # leaves the set no sweep will look at again: give up its
            # hold on the occupancy window rather than risk a stuck one
            self._busy_close_locked(self._open[0].launch_id)
            self._close_open(self._open[0])

    # -- micro-batching formation (lock held) --------------------------
    def _gather_peers_locked(self, spec: BatchSpec, members: List[_Dispatch], cap: int) -> None:
        """Pull queued dispatches whose batch key equals ``spec.key``
        into ``members`` (up to ``cap``).  Coalescing already folded
        identical dispatches together, so every peer here is a DISTINCT
        (literals/inputs) instance of the same device program over the
        same staged table."""
        if len(members) >= cap:
            return
        taken = []
        for peer in self._queue:
            if len(members) + len(taken) >= cap:
                break
            pb = peer.batch
            if pb is not None and pb.key == spec.key:
                taken.append(peer)
        if not taken:
            return
        self._depth_tick_locked()
        for peer in taken:
            self._queue.remove(peer)
            members.append(peer)
        self._set_depth()

    def _form_batch_locked(self, d: _Dispatch, members: List[_Dispatch], gen: int) -> str:
        """Adaptive batch window (module docstring).  Gathers queued
        same-key peers immediately; an idle lane (no same-shape demand:
        fewer than 2 members) closes at once so batching never adds
        latency to a quiet server, while demonstrated demand holds the
        window open up to ``batch_window_s`` and fills to the cap.
        Returns the close reason ("full" | "timeout" | "idle")."""
        spec = d.batch
        cap = self.batch_max
        if spec.max_members:
            cap = max(1, min(cap, spec.max_members))
        self._gather_peers_locked(spec, members, cap)
        if len(members) >= cap:
            return "full"
        if len(members) < 2 or self.batch_window_s <= 0:
            return "idle"
        deadline_w = time.monotonic() + self.batch_window_s
        while (
            len(members) < cap
            and not self._closed
            and gen == self._generation
        ):
            remaining = deadline_w - time.monotonic()
            if remaining <= 0:
                return "timeout"
            # cv.wait releases the lock: submits keep landing and the
            # next gather sweep picks up fresh same-key arrivals
            self._cv.wait(remaining)
            self._gather_peers_locked(spec, members, cap)
        return "full" if len(members) >= cap else "timeout"

    def _run(self, gen: int) -> None:
        while True:
            with self._cv:
                if gen != self._generation:
                    return  # restarted away while we held no work
                self._sweep_open_locked()
                while not self._queue and not self._closed and gen == self._generation:
                    if self._open:
                        # finite wait: open dispatches must close (and
                        # release their buffers) soon after the device
                        # finishes even when no new work arrives
                        self._cv.wait(timeout=_SWEEP_S)
                        self._sweep_open_locked()
                    else:
                        self._cv.wait()
                if gen != self._generation:
                    return
                if self._closed and not self._queue:
                    return
                self._depth_tick_locked()
                d = self._queue.popleft()
                self._set_depth()
                # micro-batching: gather same-key peers (and, under
                # demonstrated demand, hold the bounded window open for
                # more) BEFORE the deadline sweep, so members expiring
                # during formation shed too
                members = [d]
                window_close = None
                if d.batch is not None and self.batch_max > 1:
                    window_close = self._form_batch_locked(d, members, gen)
                if self._closed or gen != self._generation:
                    # closed/restarted mid-formation: our members left
                    # the queue, so close()'s drain missed them — fail
                    # their waiters here
                    victims: List[LaneTicket] = []
                    closing_err: BaseException = LaneClosedError(
                        "device lane closed while batch was forming"
                    )
                    for m in members:
                        m.completed = True
                        m.error = closing_err
                        if self._by_key.get(m.key) is m:
                            self._by_key.pop(m.key)
                        victims.extend(m.waiters)
                        m.waiters = []
                    for w in victims:
                        w._deliver(error=closing_err)
                    return
                # deadline shed at lane-dequeue time, mirroring the
                # scheduler's dequeue check: the broker already failed
                # over or timed out, so device work for this waiter
                # would only delay queries that can still make it.  A
                # member expiring out of a forming batch sheds alone —
                # its batchmates launch unaffected.
                now = time.monotonic()
                dead = []
                live_members = []
                for m in members:
                    lv = [w for w in m.waiters if w.deadline is None or now < w.deadline]
                    dd = [w for w in m.waiters if w.deadline is not None and now >= w.deadline]
                    dead.extend(dd)
                    m.waiters = lv
                    if lv:
                        live_members.append(m)
                    else:
                        m.completed = True
                        if self._by_key.get(m.key) is m:
                            self._by_key.pop(m.key)
                members = live_members
                if members:
                    # watchdog window opens BEFORE the launch call: a
                    # wedge inside the fault injector or the launch
                    # itself both count as in-flight stalls; a batched
                    # launch is ONE in-flight unit (all members stall
                    # or complete together)
                    self._inflight = (members[0], now, tuple(members))
                    # occupancy: the launch's output is outstanding from
                    # here until it is seen ready
                    self._launch_seq += 1
                    for m in members:
                        m.launch_id = self._launch_seq
                    self._outstanding.add(self._launch_seq)
                    if self._busy_since is None:
                        self._busy_since = now
            if dead:
                self.shed_count += len(dead)
                self._lane_mark("shed", len(dead))
                err = QueryAbandonedError(
                    "deadline expired while queued in device lane; "
                    "broker already gave up"
                )
                for w in dead:
                    w._deliver(error=err)
            if not members:
                continue
            d = members[0]
            batched = len(members) > 1
            queue_timer = launch_timer = deliver_timer = None
            if self.metrics is not None:
                queue_timer = self.metrics.timer("phase.laneQueue")
                launch_timer = self.metrics.timer("phase.laneDispatch")
                deliver_timer = self.metrics.timer("phase.laneDeliver")
            now = time.monotonic()
            for m in members:
                # queue and batch-formation wait: submit -> the launch call below
                measured("laneQueue", (now - m.t_submit) * 1000.0, m.trace, queue_timer,
                         parent=m.parent)
            # launch OUTSIDE the lock: first-call compiles can take
            # seconds and coalescing submits must not block behind them.
            # ``via`` says before the call whether this plan digest has
            # launched here ("first": it may compile) and after it how
            # the first launch got its executable.
            tags = {"expr": d.expr} if d.expr else {}
            if d.groupby:
                tags = {"groupby": d.groupby, "operands": d.operands, "expr": d.expr, "cells": d.cells[0]}
            if d.blocks:
                tags["blocks"] = d.blocks
            if d.hll:
                tags["hll"] = d.hll
            if d.segments:
                tags["segments"] = d.segments
            if d.selection:
                tags["selection"] = d.selection
            launching = boundary(
                "laneDispatch", d.trace, launch_timer, parent=d.parent, program=d.program,
                via="warm" if d.plan_digest is None or d.plan_digest in self._compile else "first",
                **tags,
            ).start()
            if d.groupby and self.metrics is not None:
                self.metrics.meter(f"groupby.lowering.{d.groupby}").mark()
                if d.operands in ("loop", "sorted"):
                    self.metrics.meter(f"groupby.operands.{d.operands}").mark()
                if d.cells[1]:
                    self.metrics.meter("groupby.slots.shared").mark(d.cells[1])
            if d.blocks and self.metrics is not None:
                self.metrics.meter(f"zone.blocks.{d.blocks}").mark()
            if d.hll and self.metrics is not None:
                self.metrics.meter(f"hll.lowering.{d.hll}").mark()
                if d.hll_parts:
                    self.metrics.meter("hll.sort.parts").mark(d.hll_parts)
            if d.selection and self.metrics is not None:
                self.metrics.meter(f"selection.lowering.{d.selection}").mark()
            self._set_inflight(1)
            error: Optional[BaseException] = None
            value: Any = None
            member_values: List[Any] = []
            try:
                inj = self.fault_injector
                if inj is not None:
                    # one physical launch: the injector sees it once
                    # (members share the plan digest by construction)
                    inj.on_launch(d.plan_digest, d.key)
                if batched:
                    fetch_b, handle_b = d.batch.launch_batched(
                        [m.batch.inputs for m in members]
                    )
                    shared = _BatchFetch(fetch_b, len(members))
                    member_values = [
                        (shared.member(i), handle_b) for i in range(len(members))
                    ]
                    value = member_values[0]
                else:
                    value = d.launch()
            except Exception as e:  # typed delivery, lane stays alive
                error = classify_device_error(e)
            except BaseException as e:  # deliver raw, keep the lane alive:
                # a dead lane thread would strand every waiter and (with
                # self._thread non-None) never respawn
                error = e
            finally:
                self._set_inflight(0)
                launch_ms = launching.stop()
            # launch returned -> waiters delivered: the compile timeline,
            # the coalescing set and the meters, on every query's path
            delivering = boundary("laneDeliver", d.trace, deliver_timer, parent=d.parent).start()
            cold = False
            via = "cold"
            if (
                error is None
                and d.plan_digest is not None
                and self.persistent_cache_dir is not None
                and d.plan_digest not in self._compile
            ):
                # classify a first launch BEFORE taking the lane lock —
                # the plan-ledger lookup is disk I/O.  The unlocked
                # membership pre-check can only cost a spurious stat;
                # the authoritative entry check happens under _cv below.
                if compilecache.known_plan(d.plan_digest):
                    # the on-disk XLA cache served the binary: fast
                    # launch, and NOT a serving-path cold compile
                    via = "persistent"
            with self._cv:
                stale = gen != self._generation
                # occupancy: the window stays open past the launch call,
                # until the output is ready (below, output_ready, or the
                # sweep).  Stale threads must not touch it — the
                # watchdog already banked the wedged window.
                if not stale and self._inflight is not None and self._inflight[0] is d:
                    self._inflight = None
                if stale:
                    # the watchdog already failed our waiters and moved
                    # the lane on; delivering now would hand out a result
                    # nobody waits for (or double-deliver an error)
                    self.stale_completions += 1
                    delivering.stop()
                    return
                self.dispatch_count += 1
                if batched:
                    self.batch_launches += 1
                    self.batched_queries += len(members)
                    if window_close == "full":
                        self.batch_window_full += 1
                    elif window_close == "timeout":
                        self.batch_window_timeout += 1
                if error is None and d.plan_digest is not None:
                    # compile timeline: first successful launch of this
                    # digest measured cold (trace + XLA compile included)
                    entry = self._compile.get(d.plan_digest)
                    if entry is None:
                        cold = True
                        if len(self._compile) > 4096:
                            # bounded registry: evict the OLDEST entry
                            # only — a full clear would re-record every
                            # still-jit-cached plan as "cold" with a
                            # warm-speed firstCallMs, corrupting the
                            # compile series this registry exists for
                            victim = min(
                                self._compile, key=lambda k: self._compile[k]["firstAt"]
                            )
                            self._compile.pop(victim, None)
                        self._compile[d.plan_digest] = {
                            "firstCallMs": round(launch_ms, 3),
                            "firstAt": round(time.time(), 3),
                            "launches": 1,
                            "launchMsTotal": round(launch_ms, 3),
                            # how the first launch got its executable:
                            # "cold" (paid the XLA compile here),
                            # "persistent" (on-disk cache restored it),
                            # or "prewarmed" via record_prewarmed()
                            "via": via,
                        }
                        if d.cost_provider is not None:
                            # static cost analysis, once per digest, on
                            # a helper thread — never the lane thread
                            self._spawn_cost_analysis_locked(
                                d.plan_digest, d.cost_provider
                            )
                    else:
                        entry["launches"] += 1
                        entry["launchMsTotal"] = round(
                            entry.get("launchMsTotal", 0.0) + launch_ms, 3
                        )
                if error is not None:
                    self.device_failure_count += 1
                deliveries = []
                outstanding = False
                for i, m in enumerate(members):
                    m.completed = True
                    m.error = error
                    m.batch_size = len(members)
                    m.value = (
                        None
                        if error is not None
                        else (member_values[i] if batched else value)
                    )
                    waiters = list(m.waiters)
                    m.waiters = []
                    deliveries.append((m.value, waiters))
                    if error is None and not self._closed and self._still_pending(m):
                        # program still executing: keep coalescible
                        self._open.append(m)
                        outstanding = True
                    elif self._by_key.get(m.key) is m:
                        self._by_key.pop(m.key)
                if not outstanding:
                    # failed, or ready as the call returned
                    self._busy_close_locked(d.launch_id)
                self._sweep_open_locked()
            if self.metrics is not None:
                self._lane_mark("dispatches")
                if batched:
                    self.metrics.meter("batch.launches").mark()
                    self.metrics.meter("batch.queries").mark(len(members))
                    self.metrics.meter(
                        {
                            "full": "batch.windowClosedFull",
                            "timeout": "batch.windowClosedTimeout",
                        }.get(window_close, "batch.windowClosedIdle")
                    ).mark()
                if error is not None:
                    self._lane_mark("deviceFailures")
                elif d.plan_digest is not None:
                    if cold:
                        # accounting honesty (r16): only a launch that
                        # actually PAID the XLA compile on the serving
                        # path counts cold — a persistent-cache restore
                        # is its own meter, and firstCallMs keeps
                        # measuring compile cost, not restore cost
                        if via == "persistent":
                            self.metrics.meter("compile.persistentHit").mark()
                        else:
                            self.metrics.meter("compile.cold").mark()
                            self.metrics.timer("compile.firstCallMs").update(
                                launch_ms
                            )
                            if self.persistent_cache_dir is not None:
                                self.metrics.meter("compile.persistentMiss").mark()
                    else:
                        self.metrics.meter("compile.warm").mark()
            if cold:
                launching.tag(via=via)
            for m in members[1:]:
                # a batched launch is one interval in every member's tree
                measured("laneDispatch", launch_ms, m.trace, None, parent=m.parent,
                         program=d.program, batched=len(members))
            if cold and via == "cold" and self.persistent_cache_dir is not None:
                # the compile just wrote an XLA cache entry; ledger it so
                # the NEXT process classifies this digest as persistent
                compilecache.record_plan(d.plan_digest)
            n_members = len(members)
            for mvalue, waiters in deliveries:
                for w in waiters:
                    w.batch_size = n_members
                    w._deliver(value=mvalue, error=error)
            delivering.stop()


class LaneSelection:
    """One query's lane routing verdict: which lane executes it and
    which chip group (engine/mesh.py) that lane drives."""

    __slots__ = ("index", "lane", "group")

    def __init__(self, index: int, lane: DeviceLane, group) -> None:
        self.index = index
        self.lane = lane
        self.group = group


class LaneGroup:
    """One DeviceLane per chip group (engine/mesh.py MeshTopology) —
    the pod-scale generalization of the single serving lane.

    Lane selection is SHAPE-HASHED: a query routes by its literal-
    erased plan-shape digest (engine/plandigest.py), so every instance
    of a shape lands on the same lane and identical-dispatch coalescing
    keeps working exactly as on a single lane, while distinct shapes
    spread across the groups.  Deadline shedding, watchdog supervision,
    and poison classification are all per-lane (unchanged DeviceLane
    semantics): one wedged or poisoned lane heals via the host path
    while the other lanes keep serving their shapes.

    A single-group topology builds ONE lane with ``index=None`` — the
    byte-identical pre-mesh configuration (same metric names, same
    stats shape)."""

    def __init__(
        self,
        topology,
        metrics=None,
        stall_timeout_s: Optional[float] = None,
        fault_injector=None,
    ) -> None:
        self.topology = topology
        groups = list(topology.groups)
        n = len(groups)
        self.lanes: List[DeviceLane] = [
            DeviceLane(
                metrics=metrics,
                stall_timeout_s=stall_timeout_s,
                fault_injector=fault_injector,
                index=None if n == 1 else g.index,
            )
            for g in groups
        ]
        if metrics is not None and n > 1:
            # aggregate gauges become rollups over the group (per-lane
            # twins live at lane.<i>.*); meters need nothing — every
            # lane marks the shared aggregate series
            lanes = self.lanes
            metrics.gauge("lane.depth").set_fn(
                lambda: sum(l.depth for l in lanes)
            )
            metrics.gauge("lane.open").set_fn(
                lambda: sum(len(l._open) for l in lanes)
            )
            metrics.gauge("lane.inflight").set_fn(
                lambda: sum(1 for l in lanes if l._inflight is not None)
            )

    @property
    def size(self) -> int:
        return len(self.lanes)

    @property
    def primary(self) -> DeviceLane:
        return self.lanes[0]

    @property
    def restart_count(self) -> int:
        return sum(l.restart_count for l in self.lanes)

    def lane_index(self, shape_key) -> int:
        """Stable shape -> lane hash (blake2b, not the per-process-
        randomized builtin hash: the routing must be reproducible
        across runs for two runs of a cell to be comparable)."""
        if len(self.lanes) == 1:
            return 0
        import hashlib

        h = hashlib.blake2b(str(shape_key).encode(), digest_size=8).digest()
        return int.from_bytes(h, "little") % len(self.lanes)

    def select(self, shape_key) -> LaneSelection:
        i = self.lane_index(shape_key)
        return LaneSelection(i, self.lanes[i], self.topology.groups[i])

    def compile_info(self, digest: Optional[str]) -> Optional[Dict[str, float]]:
        """Compile-timeline entry across the group (a digest only ever
        launches on its shape-hashed lane, so at most one lane knows
        it)."""
        for lane in self.lanes:
            ci = lane.compile_info(digest)
            if ci is not None:
                return ci
        return None

    def stats(self) -> Dict[str, Any]:
        """Single lane: the lane's stats verbatim (pre-mesh shape).
        Group: summed rollup plus the per-lane list — the fleet-rollup
        totals are computed FROM the per-lane snapshots, so they equal
        the sum of lane snapshots by construction."""
        if len(self.lanes) == 1:
            return self.lanes[0].stats()
        per_lane = [l.stats() for l in self.lanes]
        rollup: Dict[str, Any] = {
            k: sum(s[k] for s in per_lane) for k in per_lane[0]
        }
        rollup["lanes"] = per_lane
        return rollup

    def occupancy_read(
        self, key: str = "default", min_interval_s: float = 0.0
    ) -> Dict[str, Any]:
        """Windowed occupancy across the group.  Single lane: verbatim
        lane read.  Group: per-lane reads under ``lanes`` plus a rollup
        whose summable fields equal the sum of the lane snapshots
        (busyFraction sums to "busy lanes" in [0, size] — the fleet
        busy measure; depth/inflight/avgQueueDepth sum likewise)."""
        if len(self.lanes) == 1:
            return self.lanes[0].occupancy_read(key, min_interval_s)
        reads = [l.occupancy_read(key, min_interval_s) for l in self.lanes]
        return {
            "windowS": max(r["windowS"] for r in reads),
            "busyFraction": round(sum(r["busyFraction"] for r in reads), 6),
            "avgQueueDepth": round(sum(r["avgQueueDepth"] for r in reads), 6),
            "depth": sum(r["depth"] for r in reads),
            "inflight": sum(r["inflight"] for r in reads),
            "lanes": reads,
        }

    def close(self) -> None:
        for lane in self.lanes:
            lane.close()


class OccupancySampler:
    """Periodic lane-occupancy sampler: a small thread recording
    (wall ts, busy-fraction, avg queue depth, instantaneous depth)
    samples into a bounded ring — the queue-depth-over-time series
    behind ``status()["device"]`` and the profiling workflow.

    STRICTLY opt-in: nothing starts it by default, and while it is not
    running the lane's launch path performs no occupancy-related
    allocations at all (the ``OCCUPANCY_ALLOCATIONS`` contract — the
    lane's own accounting is plain float accumulation).  ``start()`` /
    ``stop()`` are idempotent; the thread registers with its lane's
    leak-check list so the conftest thread-leak guard holds the
    lifecycle honest, and it exits on its own when the lane closes."""

    def __init__(self, lane: DeviceLane, interval_s: float = 0.25,
                 capacity: int = 240) -> None:
        self.lane = lane
        self.interval_s = max(0.02, float(interval_s))
        self._ring: Deque[tuple] = deque(maxlen=max(8, capacity))
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._key = f"sampler-{id(self):x}"
        self.samples_taken = 0

    @property
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive() and not self._stop.is_set()

    def start(self) -> None:
        with self._lock:
            if self.running or self.lane._closed:
                return
            prev = self._thread
            if prev is not None and prev.is_alive():
                # a stop() set the event but hasn't finished joining:
                # finish the join HERE before re-arming, else the old
                # thread could miss the cleared event and sample forever
                # alongside the new one
                self._stop.set()
                prev.join(timeout=2)
                if prev.is_alive():
                    return  # refuse to double-start; retry after it exits
            self._stop = threading.Event()  # fresh event per thread
            t = threading.Thread(
                target=self._run,
                args=(self._stop,),
                name="lane-occupancy-sampler",
                daemon=True,
            )
            self._thread = t
            self.lane._track_thread(t)
            t.start()

    def stop(self) -> None:
        with self._lock:
            self._stop.set()
            t = self._thread
        if t is not None:
            t.join(timeout=2)
        # drop this sampler's reader checkpoint so repeated sampler
        # lifecycles on a long-lived lane don't walk the registry cap
        with self.lane._cv:
            self.lane._occ_reads.pop(self._key, None)

    def _run(self, stop: threading.Event) -> None:
        global OCCUPANCY_ALLOCATIONS
        while not stop.wait(self.interval_s):
            if self.lane._closed:
                return
            occ = self.lane.occupancy_read(self._key)
            OCCUPANCY_ALLOCATIONS += 1
            self.samples_taken += 1
            self._ring.append(
                (
                    round(time.time(), 3),
                    occ["busyFraction"],
                    occ["avgQueueDepth"],
                    occ["depth"],
                )
            )

    def snapshot(self, last: int = 60) -> Dict[str, Any]:
        samples = list(self._ring)[-max(1, last):]
        return {
            "running": self.running,
            "intervalS": self.interval_s,
            "samplesTaken": self.samples_taken,
            "samples": [
                {
                    "ts": s[0],
                    "busyFraction": s[1],
                    "avgQueueDepth": s[2],
                    "depth": s[3],
                }
                for s in samples
            ],
        }
