"""On-demand deep profiling: bracketed ``jax.profiler`` trace capture.

The utilization plane's gauges (``device.util.*``) answer "HOW utilized
is the device"; when a lane saturates in production the next question
is "on WHAT" — and that needs an XLA/TensorBoard trace.  This module
makes capture an admin-endpoint action instead of a restart:

  POST /debug/profile/start   begin (or join) a capture
  POST /debug/profile/stop    release one start; capture ends at zero
  GET  /debug/profile         live state + capture directory listing

Semantics:

- **Ref-counted**: concurrent starts share ONE capture (jax allows a
  single active trace per process); each ``start`` must be paired with
  a ``stop``, and the trace stops when the count reaches zero.
- **Auto-stop timeout**: every start (re-)arms a deadline
  (``PINOT_TPU_PROFILE_AUTO_STOP_S``, default 120s); a client that
  dies mid-capture cannot leave the profiler running forever — the
  timer force-stops regardless of the count and marks
  ``profile.autoStops``.
- **Bounded on disk**: captures land under one base directory
  (``PINOT_TPU_PROFILE_DIR`` or a per-process tempdir), one
  subdirectory per capture, oldest pruned beyond ``max_captures``.
- **Typed unavailability**: a backend without a working profiler
  raises ``ProfilerUnavailableError``; the admin endpoint maps it to a
  404 with ``errorType`` so callers can distinguish "no profiler" from
  "bad request".

The hot path cost while idle is literally zero — nothing is consulted
per query; the profiler only acts inside start/stop.

**The summary.**  ``stop`` reduces the capture it just closed and
returns the result under ``summary`` (also written beside the trace as
``pinot_summary.json``): busy share per device, device seconds per
program name, and the idle seconds of the first device by the innermost
``pinot:<name>`` span (utils/trace.py ``boundary``) open on the host at
each instant of a gap, ``no_query_in_flight`` where none is.
``load_capture`` reads the ``.xplane.pb`` into plain lists,
``summarize`` is arithmetic on them.
"""
from __future__ import annotations

import glob
import heapq
import json
import logging
import os
import re
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)


class ProfilerUnavailableError(RuntimeError):
    """jax.profiler missing or its trace backend refused to start."""


def _default_trace_api():
    try:
        from jax import profiler as jprof

        def start(capture_dir: str) -> None:
            # the interpreter's own tracer (on by default) slows the host
            # it measures and swamps the trace; the host planes keep the
            # TraceMe events, the pinot: spans among them
            options = jprof.ProfileOptions()
            options.python_tracer_level = 0
            jprof.start_trace(capture_dir, profiler_options=options)

        return start, jprof.stop_trace
    except Exception as e:  # pragma: no cover - import environment
        raise ProfilerUnavailableError(f"jax.profiler unavailable: {e}")


SPAN_PREFIX = "pinot:"
NO_QUERY = "no_query_in_flight"
SUMMARY_FILE = "pinot_summary.json"
_FINGERPRINT = re.compile(r"\(\d+\)$")  # jit_<name>(<XLA's fingerprint>)


def load_capture(path_or_data) -> Dict[str, Any]:
    """``{"devices": {plane: {"ops": [(start_ns, end_ns)], "programs":
    [(name, start_ns, end_ns)]}}, "spans": [(name, start_ns, end_ns)]}``
    from an ``.xplane.pb`` (or a ``ProfileData``).  Device planes are
    ``/device:<kind>:<n>``, their ``XLA Ops`` line holds the executed
    operations and ``XLA Modules`` one event per program launch; the
    ``pinot:`` spans are on the host planes, on the same clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path_or_data) if isinstance(path_or_data, str) else path_or_data
    devices: Dict[str, Dict[str, list]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            devices[plane.name] = {
                "ops": [(float(e.start_ns), float(e.start_ns + e.duration_ns))
                        for e in lines["XLA Ops"].events],
                "programs": [(_FINGERPRINT.sub("", e.name), float(e.start_ns),
                              float(e.start_ns + e.duration_ns))
                             for e in lines["XLA Modules"].events] if "XLA Modules" in lines else [],
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)))
    return {"devices": devices, "spans": spans}


def _union(intervals: list) -> list:
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def innermost_segments(spans: list) -> list:
    """``[(start, end, name)]``, disjoint and sorted: for every instant
    at which some span is open, the one that opened last (across
    threads: a worker's ``planBuild`` inside the HTTP thread's
    ``scatterGather``)."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda x: x[1])
    open_heap: list = []  # (-start, end, name)
    out, i = [], 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i][1] <= t0:
            name, start, end = by_start[i]
            heapq.heappush(open_heap, (-start, end, name))
            i += 1
        while open_heap and open_heap[0][1] <= t0:
            heapq.heappop(open_heap)  # lazily: ended spans leave when they surface
        if open_heap:
            name = open_heap[0][2]
            if out and out[-1][2] == name and out[-1][1] == t0:
                out[-1][1] = t1
            else:
                out.append([t0, t1, name])
    return out


def summarize(loaded: Dict[str, Any]) -> Dict[str, Any]:
    """The capture in numbers.  The window runs from the first to the
    last event (operation or span); ``idle`` is the first device's."""
    planes = sorted(loaded["devices"])
    spans = loaded["spans"]
    edges = [t for p in planes for s, e in loaded["devices"][p]["ops"] for t in (s, e)]
    edges += [t for _, s, e in spans for t in (s, e)]
    if not planes or not edges:
        return {"windowS": 0.0, "devices": {}, "programs": {}, "idle": {}, "idleS": 0.0}
    lo, hi = min(edges), max(edges)
    devices, programs, busy0 = {}, {}, []
    for n, plane in enumerate(planes):
        busy = _union(loaded["devices"][plane]["ops"])
        if n == 0:
            busy0 = busy
        busy_s = sum(e - s for s, e in busy) / 1e9
        devices[plane] = {"busyS": busy_s, "busyShare": busy_s / ((hi - lo) / 1e9)}
        for name, start, end in loaded["devices"][plane]["programs"]:
            programs[name] = programs.get(name, 0.0) + (end - start) / 1e9 / len(planes)
    bounds = [lo] + [t for iv in busy0 for t in iv] + [hi]
    gaps = [(a, b) for a, b in zip(bounds[0::2], bounds[1::2]) if b > a]
    idle: Dict[str, float] = {}
    segments, k = innermost_segments(spans), 0
    for g0, g1 in gaps:
        covered = 0.0
        while k < len(segments) and segments[k][1] <= g0:
            k += 1
        j = k
        while j < len(segments) and segments[j][0] < g1:
            cover = min(segments[j][1], g1) - max(segments[j][0], g0)
            idle[segments[j][2]] = idle.get(segments[j][2], 0.0) + cover / 1e9
            covered += cover
            j += 1
        idle[NO_QUERY] = idle.get(NO_QUERY, 0.0) + (g1 - g0 - covered) / 1e9
    return {
        "windowS": (hi - lo) / 1e9,
        "devices": devices,
        "programs": dict(sorted(programs.items(), key=lambda x: -x[1])),
        "idle": dict(sorted(idle.items(), key=lambda x: -x[1])),
        "idleS": sum(b - a for a, b in gaps) / 1e9,
    }


def summarize_capture(capture_dir: str) -> Dict[str, Any]:
    """Reduce the newest trace under ``capture_dir`` and leave the
    result beside it; ``{"error": ...}`` where there is nothing to read."""
    paths = sorted(glob.glob(os.path.join(capture_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"error": f"no .xplane.pb under {capture_dir}"}
    try:
        summary = summarize(load_capture(paths[-1]))
        with open(os.path.join(capture_dir, SUMMARY_FILE), "w") as f:
            json.dump(summary, f, indent=1)
    except Exception as e:  # a capture nobody can reduce is still a capture
        logger.warning("profile summary failed: %s", e)
        return {"error": f"{type(e).__name__}: {e}"}
    return summary


class DeviceProfiler:
    """Ref-counted, auto-stopping ``jax.profiler`` capture manager.

    ``trace_api`` ((start_fn(dir), stop_fn()) tuple) and ``clock`` are
    injectable for unit tests; production uses ``jax.profiler`` and
    ``time.monotonic``."""

    def __init__(
        self,
        name: str = "server",
        base_dir: Optional[str] = None,
        metrics=None,
        auto_stop_s: Optional[float] = None,
        max_captures: int = 4,
        trace_api=None,
        clock=time.monotonic,
    ) -> None:
        if base_dir is None:
            base_dir = os.environ.get("PINOT_TPU_PROFILE_DIR")
        if base_dir is None:
            import tempfile

            base_dir = os.path.join(
                tempfile.gettempdir(), "pinot_tpu_profiles", f"{name}-{os.getpid()}"
            )
        self.base_dir = base_dir
        self.max_captures = max(1, max_captures)
        if auto_stop_s is None:
            auto_stop_s = float(
                os.environ.get("PINOT_TPU_PROFILE_AUTO_STOP_S", "120")
            )
        self.auto_stop_s = auto_stop_s
        self.metrics = metrics
        self._trace_api = trace_api
        self._clock = clock
        self._lock = threading.Lock()
        self._refcount = 0
        self._capture_dir: Optional[str] = None
        self._started_at: Optional[float] = None
        self._deadline: Optional[float] = None
        self._timer: Optional[threading.Timer] = None
        self._seq = 0
        # capture dirs are immutable once their trace stops, so their
        # sizes are computed once and cached — snapshot() sits on polled
        # paths (/debug/device, status()) and must not re-walk hundreds
        # of MB of trace files per scrape, let alone under self._lock
        self._size_cache: Dict[str, int] = {}
        self.auto_stops = 0
        # optional hook fired whenever a capture ends (stop or
        # auto-stop): the server uses it to park its occupancy sampler
        self.on_capture_end = None
        if metrics is not None:
            # pre-registered so /metrics shows zeros before first use
            for m in ("profile.starts", "profile.stops", "profile.autoStops",
                      "profile.failedStarts"):
                metrics.meter(m)
            metrics.gauge("profile.active").set(0)

    # -- public API ----------------------------------------------------
    def start(self, timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """Begin a capture, or join the active one (refcount++).  Every
        start re-arms the auto-stop deadline to now + timeout (capped
        callers extend; a second client cannot SHORTEN a running
        capture's remaining window below its own request)."""
        timeout = float(timeout_s) if timeout_s else self.auto_stop_s
        with self._lock:
            if self._refcount == 0:
                self._begin_capture_locked()
            self._refcount += 1
            now = self._clock()
            deadline = now + max(0.1, timeout)
            if self._deadline is None or deadline > self._deadline:
                self._deadline = deadline
                self._arm_timer_locked(self._deadline - now)
            if self.metrics is not None:
                self.metrics.meter("profile.starts").mark()
                self.metrics.gauge("profile.active").set(1)
            return self._snapshot_locked()

    def stop(self) -> Dict[str, Any]:
        """Release one start; the trace stops when the count hits zero.
        Stopping an inactive profiler is a no-op snapshot (idempotent
        — a retried stop after a timeout must not error)."""
        ended = False
        capture_dir = None
        with self._lock:
            if self._refcount > 0:
                self._refcount -= 1
                if self.metrics is not None:
                    self.metrics.meter("profile.stops").mark()
                if self._refcount == 0:
                    capture_dir = self._capture_dir
                    ended = self._end_capture_locked()
            snap = self._snapshot_locked()
        if ended:
            self._fire_capture_end()
            # outside the lock: reading a trace of a few seconds of
            # traffic takes seconds itself
            snap["summary"] = summarize_capture(capture_dir)
        return snap

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return self._snapshot_locked()

    def shutdown(self) -> None:
        """Force-stop any active capture (server shutdown path)."""
        ended = False
        with self._lock:
            if self._refcount > 0:
                self._refcount = 0
                ended = self._end_capture_locked()
        if ended:
            self._fire_capture_end()

    # -- internals -----------------------------------------------------
    def _begin_capture_locked(self) -> None:
        start_fn, _ = self._api()
        self._seq += 1
        capture_dir = os.path.join(
            self.base_dir, f"capture-{self._seq:04d}-{int(time.time())}"
        )
        try:
            # prune BEFORE creating the new dir: pruning after would
            # count the new capture among the victims-by-age candidates
            # (with max_captures=1 it would rmtree the dir the trace is
            # about to write into)
            self._prune_captures_locked(keep=self.max_captures - 1)
            os.makedirs(capture_dir, exist_ok=True)
            start_fn(capture_dir)
        except ProfilerUnavailableError:
            raise
        except Exception as e:
            if self.metrics is not None:
                self.metrics.meter("profile.failedStarts").mark()
            raise ProfilerUnavailableError(
                f"profiler trace failed to start: {type(e).__name__}: {e}"
            )
        self._capture_dir = capture_dir
        self._started_at = time.time()

    def _end_capture_locked(self) -> bool:
        """Returns True when an active capture actually ended — the
        caller fires ``on_capture_end`` AFTER releasing the lock (the
        hook may join the occupancy sampler thread for seconds, and a
        concurrent snapshot/start must not stall behind that)."""
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        self._deadline = None
        if self._capture_dir is None:
            return False
        _, stop_fn = self._api()
        try:
            stop_fn()
        except Exception as e:
            # a capture that failed mid-flight must still reset state:
            # the NEXT start has to be able to begin a fresh trace
            logger.warning("profiler stop_trace failed: %s", e)
        self._capture_dir = None
        self._started_at = None
        if self.metrics is not None:
            self.metrics.gauge("profile.active").set(0)
        return True

    def _fire_capture_end(self) -> None:
        if self.on_capture_end is not None:
            try:
                self.on_capture_end()
            except Exception:
                logger.exception("profiler on_capture_end hook failed")

    def _api(self):
        if self._trace_api is not None:
            return self._trace_api
        return _default_trace_api()

    def _arm_timer_locked(self, delay_s: float) -> None:
        if self._timer is not None:
            self._timer.cancel()
        t = threading.Timer(max(0.05, delay_s), self._auto_stop)
        t.daemon = True
        self._timer = t
        t.start()

    def _auto_stop(self) -> None:
        """Deadline fired: force-stop REGARDLESS of refcount — a dead
        client's unmatched start must not pin the profiler open."""
        ended = False
        with self._lock:
            if self._capture_dir is None:
                return
            if self._deadline is not None and self._clock() < self._deadline - 1e-3:
                # a later start extended the deadline after this timer
                # was armed; re-arm for the remainder instead
                self._arm_timer_locked(self._deadline - self._clock())
                return
            self._refcount = 0
            self.auto_stops += 1
            if self.metrics is not None:
                self.metrics.meter("profile.autoStops").mark()
            ended = self._end_capture_locked()
        if ended:
            self._fire_capture_end()

    def _prune_captures_locked(self, keep: int) -> None:
        try:
            entries = sorted(
                d
                for d in os.listdir(self.base_dir)
                if d.startswith("capture-")
                and os.path.isdir(os.path.join(self.base_dir, d))
            )
        except OSError:
            return
        for victim in entries[: max(0, len(entries) - max(0, keep))]:
            shutil.rmtree(os.path.join(self.base_dir, victim), ignore_errors=True)

    def _dir_bytes(self, path: str) -> int:
        nbytes = 0
        for root, _, files in os.walk(path):
            for f in files:
                try:
                    nbytes += os.path.getsize(os.path.join(root, f))
                except OSError:
                    pass
        return nbytes

    def _captures_locked(self) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        try:
            entries = sorted(
                d
                for d in os.listdir(self.base_dir)
                if d.startswith("capture-")
            )
        except OSError:
            return out
        live = set(entries)
        for stale in [k for k in self._size_cache if k not in live]:
            del self._size_cache[stale]
        for d in entries:
            path = os.path.join(self.base_dir, d)
            if path == self._capture_dir:
                # still being written: size unknown until the trace stops
                out.append({"name": d, "bytes": None})
                continue
            nbytes = self._size_cache.get(d)
            if nbytes is None:
                nbytes = self._dir_bytes(path)
                self._size_cache[d] = nbytes
            out.append({"name": d, "bytes": nbytes})
        return out

    def _snapshot_locked(self) -> Dict[str, Any]:
        now = self._clock()
        return {
            "active": self._capture_dir is not None,
            "refCount": self._refcount,
            "dir": self._capture_dir,
            "baseDir": self.base_dir,
            "startedAt": self._started_at,
            "autoStopS": self.auto_stop_s,
            "remainingS": (
                round(max(0.0, self._deadline - now), 3)
                if self._deadline is not None and self._capture_dir is not None
                else None
            ),
            "autoStops": self.auto_stops,
            "maxCaptures": self.max_captures,
            "captures": self._captures_locked(),
        }
