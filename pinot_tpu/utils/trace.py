"""Per-request distributed tracing: hierarchical span trees.

The reference registers a requestId-scoped trace registry and wraps
worker threads so operators can log step latencies
(``core/util/trace/TraceContext.java:41``, ``TraceRunnable``); the trace
rides back in DataTable metadata and is merged per server
(``BrokerReduceService.java:84-87``).

Here each role builds a span TREE per request: every span carries a
scope-prefixed id, a parent id, a wall-clock anchor (epoch ms, so
broker and server trees align on one waterfall), a duration, and a
tag dict.  Spans serialize as plain dicts so they ride the DataTable
``trace`` metadata unchanged and merge broker-side into
``BrokerResponse.traceInfo`` (the broker re-parents each server tree
under the scatter attempt that carried it — ``broker/broker.py``).

Span dict schema (the wire/JSON contract, see README "Observability"):

    {"span": name, "id": "scope:n", "parent": "scope:m" | None,
     "startMs": epoch_ms, "ms": duration_ms, "tags": {..}}

``tags`` is omitted when empty; events are spans with ``ms == 0``.

ZERO-OVERHEAD WHEN DISABLED: a disabled context's ``span()`` returns a
shared no-op context manager and ``add``/``event`` return immediately —
no span dicts, no generator frames.  ``SPAN_ALLOCATIONS`` counts every
span dict ever built so tests can assert the disabled path allocates
none.  Parenting uses contextvars (a per-thread span stack), not thread
wrappers.

ONE HELPER FOR A LAYER BOUNDARY: ``boundary`` times an interval once
and reports it three ways — the role's timer, the span on the request's
tree, and a ``jax.profiler.TraceAnnotation`` ``pinot:<name>`` on the
profiler's host plane, which is on the clock of a capture's device
planes.  ``ctx.span`` is a boundary without a timer; ``phases`` is the
cursor for boundaries that follow one another; ``measured`` records an
interval that is known only when it is over (a wait in a queue), and
``marked`` gives such an interval its annotation while a capture runs.
"""
from __future__ import annotations

import contextvars
import sys
import threading
import time
from typing import Any, Dict, List, Optional

_current: contextvars.ContextVar[Optional["TraceContext"]] = contextvars.ContextVar(
    "pinot_tpu_trace", default=None
)
# stack of span ids for the current thread/task: the top is the parent
# of the next span opened on this thread
_stack: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "pinot_tpu_trace_stack", default=()
)

# module-wide count of span dicts ever allocated — the disabled-trace
# zero-overhead guard (tests assert no delta across an untraced query)
SPAN_ALLOCATIONS = 0


class _NullSpan:
    """Shared no-op context manager for disabled traces."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _parent_id() -> Optional[str]:
    stack = _stack.get()
    return stack[-1] if stack else None


class TraceContext:
    """One role's span tree for one request (requestId-scoped)."""

    __slots__ = ("enabled", "scope", "trace_id", "spans", "_seq", "_lock", "_open")

    def __init__(self, enabled: bool = False, scope: str = "", trace_id: str = "") -> None:
        self.enabled = enabled
        self.scope = scope
        self.trace_id = trace_id
        self.spans: List[Dict[str, Any]] = []
        self._seq = 0
        self._lock = threading.Lock()
        self._open: Dict[str, float] = {}  # span id -> perf_counter at its start

    # -- recording -----------------------------------------------------
    def _alloc(
        self,
        name: str,
        ms: float,
        start_ms: float,
        parent: Optional[str],
        tags: Dict[str, Any],
        sid: Optional[str] = None,
    ) -> Dict[str, Any]:
        global SPAN_ALLOCATIONS
        with self._lock:
            if sid is None:
                self._seq += 1
                sid = f"{self.scope}:{self._seq}"
            span: Dict[str, Any] = {
                "span": name,
                "id": sid,
                "parent": parent,
                "startMs": int(start_ms * 1000.0) / 1000.0,  # round() costs five times this
                "ms": ms,
            }
            if tags:
                span["tags"] = tags  # the caller's own **kwargs dict
            self.spans.append(span)
            SPAN_ALLOCATIONS += 1
            return span

    def span(self, name: str, **tags):
        """Open a timed child span (context manager).  Nesting on the
        same thread parents automatically via the contextvar stack."""
        if not self.enabled:
            return _NULL_SPAN
        return boundary(name, self, **tags)

    def add(
        self,
        name: str,
        ms: float,
        start_ms: Optional[float] = None,
        parent: Optional[str] = "__auto__",
        span_id: Optional[str] = None,
        **tags,
    ) -> Optional[str]:
        """Record an already-measured span; returns its id.  ``start_ms``
        defaults to now minus the duration; ``parent`` defaults to the
        calling thread's current span (pass ``None`` for a root);
        ``span_id`` is an id taken earlier with ``reserve``."""
        if not self.enabled:
            return None
        if start_ms is None:
            start_ms = time.time() * 1000.0 - ms
        p = _parent_id() if parent == "__auto__" else parent
        return self._alloc(name, round(ms, 3), start_ms, p, tags, span_id)["id"]

    def reserve(self) -> Optional[str]:
        """The id of a span that will be added when its interval ends
        (``add(..., span_id=)``), for children that run meanwhile on
        other threads and name it as their parent."""
        if not self.enabled:
            return None
        with self._lock:
            self._seq += 1
            return f"{self.scope}:{self._seq}"

    def event(self, name: str, **tags) -> Optional[str]:
        """Zero-duration marker span (retry / failover / coalesce-hit)."""
        if not self.enabled:
            return None
        return self._alloc(name, 0.0, time.time() * 1000.0, _parent_id(), tags)["id"]

    # -- export --------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        """{scope: [span dicts]} — the shape that rides DataTable
        ``trace`` metadata; empty when disabled or nothing recorded.  A
        span still open when the tree is cut (the root around the code
        that exports it) reads its milliseconds so far, tagged ``open``."""
        if not self.enabled or not self.spans:
            return {}
        with self._lock:
            spans = list(self.spans)
            still_open = dict(self._open)
        if still_open:
            now = time.perf_counter()
            spans = [
                dict(s, ms=round((now - still_open[s["id"]]) * 1000.0, 3),
                     tags=dict(s.get("tags", ()), open=True))
                if s["id"] in still_open else s
                for s in spans
            ]
        return {self.scope: spans}


# a single shared disabled context: callers on the untraced path reuse
# it instead of constructing a TraceContext per request
NULL_TRACE = TraceContext(enabled=False)

_AUTO = "__auto__"
_annotation_cls = None  # jax.profiler.TraceAnnotation once this process has jax
_capturing = None  # its is_enabled: TraceMe's own flag test


def capturing() -> bool:
    """Whether a profiler's capture runs in this process.  Only a
    process that has imported jax can hold one, so one that has not (a
    broker of its own) never imports it here; after that it is TraceMe's
    own flag test (0.05 us)."""
    global _annotation_cls, _capturing
    if _annotation_cls is None:
        if "jax" not in sys.modules:
            return False
        try:
            from jax.profiler import TraceAnnotation as _annotation_cls

            _capturing = _annotation_cls.is_enabled
        except Exception:  # a jax without its profiler: spans and timers still work
            _annotation_cls = False
    return bool(_annotation_cls) and _capturing()


def _annotation(name: str, rid: str, tags: Dict[str, Any]):
    """``pinot:<name>`` on the profiler's host plane, or None.  With no
    capture running TraceMe would record nothing: its flag is tested
    before the object is built (0.5 us)."""
    if not capturing():
        return None
    program = tags.get("program")
    if program:
        return _annotation_cls("pinot:" + name, rid=rid, program=program)
    digest = tags.get("digest")
    if digest:  # an audit pass: the plan's digest and the kind of query
        return _annotation_cls("pinot:" + name, rid=rid, digest=digest, shape=tags.get("shape", ""))
    return _annotation_cls("pinot:" + name, rid=rid)


class boundary:
    """THE way to time a layer boundary.  One ``with boundary(...)``:

    (a) updates ``timer`` (the role's ``phase.<name>`` Timer) with the
        interval's milliseconds,
    (b) adds the span ``name`` to the request's tree when ``ctx`` is
        enabled (no span dict otherwise: the ``SPAN_ALLOCATIONS``
        contract), parented like ``ctx.span`` or under ``parent``,
    (c) emits the ``jax.profiler.TraceAnnotation`` ``pinot:<name>``
        with ``rid=<requestId>`` (and ``program=``, or ``digest=`` and
        ``shape=``, where tagged), so a
        capture shows the same interval on the host plane, on the clock
        of its device planes.  With no capture running that is TraceMe's
        flag test and nothing else.

    ``start()``/``stop()`` are the two halves for code whose interval is
    not a block (``stop`` is idempotent; both on one thread).  ``ms`` is
    the duration once stopped.  ``attach`` gives an open boundary the
    tree and the timer that were not known when it began (a connection
    before its request is read).  ``relabel`` renames the timer and span of
    an open boundary whose outcome names it (the executor's first
    stretch is ``staging`` unless a host tier answers); the annotation
    keeps the name it was opened with and gains ``as=<name>``."""

    __slots__ = ("name", "ms", "_ctx", "_timer", "_parent", "_tags", "_span", "_token", "_ann", "_t0")

    def __init__(self, name: str, ctx: Optional[TraceContext] = None, timer=None,
                 parent: Optional[str] = _AUTO, **tags) -> None:
        self.name = name
        self.ms = 0.0
        self._ctx = ctx if ctx is not None and ctx.enabled else None
        self._timer = timer
        self._parent = parent
        self._tags = tags
        self._span = self._token = self._ann = None
        self._t0: Optional[float] = None

    def start(self) -> "boundary":
        # the clock is read first and (in stop) last, so that what the
        # boundary itself costs is inside its own interval and not in
        # its parent's self time
        self._t0 = time.perf_counter()
        ctx = self._ctx
        if ctx is not None:
            self._open_span(time.time() * 1000.0)
        self._ann = _annotation(self.name, self._rid(), self._tags)
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def _rid(self) -> str:
        return self._ctx.trace_id if self._ctx is not None else self._tags.get("requestId", "")

    def _open_span(self, start_ms: float) -> None:
        stack = _stack.get()
        parent = (stack[-1] if stack else None) if self._parent == _AUTO else self._parent
        self._span = self._ctx._alloc(self.name, 0.0, start_ms, parent, self._tags)
        self._token = _stack.set(stack + (self._span["id"],))
        self._ctx._open[self._span["id"]] = self._t0

    def attach(self, ctx: Optional[TraceContext], timer, start_ms: float, **tags) -> None:
        """The request's tree and the timer, for an open boundary that
        began before either was known (a connection's, before its
        request was read).  The span begins at ``start_ms``, the wall
        clock as the caller read it when the boundary began; the
        annotation gets the request id.  On the boundary's own thread."""
        self._timer = timer
        self._tags.update(tags)
        if self._t0 is None:
            return
        if ctx is not None and ctx.enabled:
            self._ctx = ctx
            self._open_span(start_ms)
        if self._ann is not None:
            self._ann.set_metadata(rid=self._rid())

    def stop(self) -> float:
        t0 = self._t0
        if t0 is None:
            return self.ms
        self._t0 = None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        span = self._span
        if span is not None:
            self._ctx._open.pop(span["id"], None)
            _stack.reset(self._token)
        self.ms = ms = (time.perf_counter() - t0) * 1000.0
        if span is not None:
            span["ms"] = int(ms * 1000.0 + 0.5) / 1000.0
        if self._timer is not None:
            self._timer.update(ms)
        return ms

    @property
    def span_id(self) -> Optional[str]:
        return self._span["id"] if self._span is not None else None

    def tag(self, **tags) -> None:
        """Tags known only inside the interval (``coalesced``, ``via``)."""
        if self._span is not None:
            self._span.setdefault("tags", {}).update(tags)

    def relabel(self, name: str, timer=None) -> None:
        self.name, self._timer = name, timer
        if self._span is not None:
            self._span["span"] = name
        if self._ann is not None:
            self._ann.set_metadata(**{"as": name})

    __enter__ = start

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False


class phases:
    """Boundaries that follow one another on one thread: ``enter``
    closes the open one and opens the next, ``stop`` closes the last
    (idempotent, so it can sit in a ``finally``).  ``make(name, **tags)``
    builds each boundary, unstarted (the executor's ``_phase``)."""

    __slots__ = ("_make", "current")

    def __init__(self, make) -> None:
        self._make = make
        self.current: Optional[boundary] = None

    def enter(self, name: str, **tags) -> boundary:
        self.stop()
        self.current = self._make(name, **tags).start()
        return self.current

    def relabel(self, name: str) -> None:
        """The open boundary turned out to be ``name``."""
        self.current.relabel(name, self._make(name)._timer)

    def stop(self) -> None:
        if self.current is not None:
            self.current.stop()


def measured(name: str, ms: float, ctx: Optional[TraceContext] = None, timer=None,
             parent: Optional[str] = _AUTO, start_ms: Optional[float] = None, **tags) -> None:
    """A boundary whose interval has already passed when it is known: a
    wait in a queue, measured at dequeue.  Timer and span as
    ``boundary``; no annotation, because the profiler takes no event
    after the fact (the waiting thread's own open ``pinot:`` span covers
    the interval on the host plane).  ``start_ms`` where the interval
    did not end just now (another thread's, reported here)."""
    if timer is not None:
        timer.update(ms)
    if ctx is not None and ctx.enabled:
        ctx.add(name, ms, start_ms=start_ms, parent=parent, **tags)


def marked(name: str) -> Optional[boundary]:
    """The annotation alone, for an interval that is ``measured``: while
    a capture runs an open boundary with no tree and no timer, which the
    caller stops on this thread where the interval ends; ``None``
    otherwise, so that a query outside a capture builds nothing for it."""
    return boundary(name).start() if capturing() else None


def current_trace() -> Optional[TraceContext]:
    return _current.get()


def set_current(ctx: Optional[TraceContext], parent: Optional[str] = None):
    """Install ``ctx`` as the thread's current trace, and ``parent`` (a
    span opened on the thread that waits for this one) as the parent of
    the spans it opens; returns the token for ``reset_current``.  Used
    by scheduler workers, which do not inherit the submitting thread's
    context."""
    return _current.set(ctx), _stack.set((parent,) if parent else ())


def reset_current(token) -> None:
    _current.reset(token[0])
    _stack.reset(token[1])


def merge_scope(
    scopes: Dict[str, List[Dict[str, Any]]],
    incoming: Dict[str, List[Dict[str, Any]]],
    root_parent: Optional[str] = None,
) -> None:
    """Merge one reply's {scope: spans} into an accumulating scope map.

    Root spans (parent None) of each incoming tree are re-parented onto
    ``root_parent`` (the broker's serverAttempt span), linking all trees
    into one.  When the same scope already exists (two batches answered
    by one server), the incoming tree is stored under ``scope#k`` with
    its internal ids rewritten, so parent links stay unambiguous."""
    for scope, spans in incoming.items():
        key = scope
        k = 1
        while key in scopes:
            k += 1
            key = f"{scope}#{k}"
        if key != scope:
            prefix = f"{scope}:"
            new_prefix = f"{key}:"

            def _remap(sid):
                if isinstance(sid, str) and sid.startswith(prefix):
                    return new_prefix + sid[len(prefix):]
                return sid

            spans = [
                dict(s, id=_remap(s.get("id")), parent=_remap(s.get("parent")))
                for s in spans
            ]
        else:
            spans = [dict(s) for s in spans]
        if root_parent is not None:
            for s in spans:
                if s.get("parent") is None:
                    s["parent"] = root_parent
        scopes[key] = spans
