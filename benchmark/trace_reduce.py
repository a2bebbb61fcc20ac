"""From the profiler's ``.xplane.pb`` to busy time, idle gaps and
per-operation time.  The benchmark's own reduction, so that every PR
reads a trace the same way.

``load`` reads the file with ``jax.profiler.ProfileData`` into plain
lists; ``reduce`` is arithmetic on those lists and is what the tests
check on a small recorded trace.

Device planes are ``/device:TPU:<n>``.  On each, the line ``XLA Modules``
holds one event per program launch (``jit_pinot_scan_gb6_<plan>(<fingerprint>)``)
and ``XLA Ops`` one per executed HLO operation, named by its whole HLO
text.  An operation is named here ``<its module>/<its result>``, as in
``jit_pinot_scan_gb6_fd9a6467(7497...)/while.10``.  Busy is the union of
the ``XLA Ops`` intervals; kernel time is the same union, since a
program's operations do not overlap on one core.

The host planes carry two kinds of span on the device planes' clock:
the load generator's ``client:<shape>`` around each query, and the
program's ``pinot:<span>`` annotations, one per layer boundary
(``PERF.md`` section 3 lists them).  Every instant at which the first
device is idle goes to ``no_query_in_flight`` or, where a query's span
is open, to ``query_in_flight:<shape>:<span>``: the innermost ``pinot:``
span open on the host at that instant, the one that opened last across
threads.  What no span of the program covers (connect, accept, a
thread's start and the request line before the handler's entry; the
reply's last bytes after it) stays
``query_in_flight:<shape>:host_cause_not_attributed``.  The arithmetic
is that of the program's ``server/profiler.py``, written again here:
the benchmark imports nothing of the program.

Shape and span are each taken from the span that opened last, on their
own: the annotations' ``rid=`` is the broker's and the client's span
has none.  With one client (the closed cell) they are one query's.
With several queries in flight (the open cells) the shape is the query's that was
sent last and the span the one that opened last under any of them, so
the pair can name one query's shape beside another's span; the sums by
span alone (``idle_by_span``) do not depend on the pairing.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
from collections import Counter

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLIENT_PREFIX = "client:"
SPAN_PREFIX = "pinot:"
NO_QUERY = "no_query_in_flight"
NO_SPAN = "host_cause_not_attributed"


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path_or_data) -> dict:
    """``{"devices": {plane: [(name, start_ns, dur_ns)]}, "client":
    [(shape, start_ns, dur_ns)], "spans": [(span, start_ns, dur_ns)]}``
    from a file, or from a ``ProfileData``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path_or_data) if isinstance(path_or_data, str) else path_or_data
    devices, client, spans = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            modules = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
                             for e in lines[MODULES_LINE].events) if MODULES_LINE in lines else []
            ops, m = devices.setdefault(plane.name, []), 0
            for e in sorted(lines[OPS_LINE].events, key=lambda e: e.start_ns):
                start = float(e.start_ns)
                while m < len(modules) and modules[m][1] < start:
                    m += 1
                inside = m < len(modules) and modules[m][0] <= start
                op = e.name.split(" = ")[0].lstrip("%")  # the result's name, not the whole HLO text
                ops.append((f"{modules[m][2]}/{op}" if inside else op, start, float(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(CLIENT_PREFIX):
                        client.append((e.name[len(CLIENT_PREFIX):], float(e.start_ns), float(e.duration_ns)))
                    elif e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], float(e.start_ns), float(e.duration_ns)))
    return {"devices": devices, "client": client, "spans": spans}


def union(intervals: list) -> list:
    """Sorted, disjoint ``[start, end]`` covering the same points."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def innermost_segments(spans: list) -> list:
    """``[[start, end, name]]``, disjoint and sorted: for every instant at
    which some span is open, the one that opened last (across threads: a
    worker's ``planBuild`` inside the HTTP thread's ``scatterGather``)."""
    cuts = sorted({t for _, start, dur in spans for t in (start, start + dur)})
    by_start = sorted(spans, key=lambda x: x[1])
    open_heap: list = []  # (-start, end, name): the top opened last
    out, i = [], 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i][1] <= t0:
            name, start, dur = by_start[i]
            heapq.heappush(open_heap, (-start, start + dur, name))
            i += 1
        while open_heap and open_heap[0][1] <= t0:
            heapq.heappop(open_heap)  # an ended span leaves when it surfaces
        if open_heap:
            name = open_heap[0][2]
            if out and out[-1][2] == name and out[-1][1] == t0:
                out[-1][1] = t1
            else:
                out.append([t0, t1, name])
    return out


def pieces(segments: list, g0: float, g1: float) -> list:
    """``[(start, end, name)]`` covering ``[g0, g1]``: the disjoint,
    sorted ``segments`` cut to it, and ``None`` where none lies."""
    if g1 <= g0:
        return []
    out, t = [], g0
    i = max(bisect.bisect_right(segments, g0, key=lambda x: x[0]) - 1, 0)
    while i < len(segments) and segments[i][0] < g1:
        start, end, name = segments[i]
        if end > t:
            if start > t:
                out.append((t, start, None))
            t = max(start, t)
            out.append((t, min(end, g1), name))
            t = min(end, g1)
        i += 1
    if t < g1:
        out.append((t, g1, None))
    return out


def reduce(loaded: dict, window_ns: tuple, top: int = 10) -> dict:
    """Busy seconds averaged over the device planes, the window's length,
    the ``top`` operations by device seconds (averaged the same way), the
    ``top`` causes of the first device's idle seconds (the query in flight
    and the innermost span open on the host), and ``idle_by_span``: all
    the idle seconds with a query in flight, by that span alone.
    ``window_ns`` clips everything: (start, end) on the trace's clock."""
    lo, hi = window_ns
    planes = sorted(loaded["devices"])
    if not planes:
        return {"busy_s": 0.0, "window_s": (hi - lo) / 1e9, "queries": 0,
                "device_ops": [], "idle_gaps": [], "idle_by_span": {}}
    in_flight = innermost_segments(loaded["client"])
    spans = innermost_segments(loaded.get("spans", []))
    busy, per_op, gaps, by_span = 0.0, {}, {}, {}
    for n, plane in enumerate(planes):
        clipped = []
        for name, start, dur in loaded["devices"][plane]:
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                clipped.append((s, e))
                per_op[name] = per_op.get(name, 0.0) + (e - s)
        covered = union(clipped)
        busy += sum(e - s for s, e in covered)
        if n == 0:
            edges = [lo] + [x for iv in covered for x in iv] + [hi]
            for g0, g1 in zip(edges[0::2], edges[1::2]):
                for a, b, shape in pieces(in_flight, g0, g1):
                    if shape is None:
                        gaps[NO_QUERY] = gaps.get(NO_QUERY, 0.0) + (b - a)
                        continue
                    for c, d, span in pieces(spans, a, b):
                        span = span or NO_SPAN
                        name = f"query_in_flight:{shape}:{span}"
                        gaps[name] = gaps.get(name, 0.0) + (d - c)
                        by_span[span] = by_span.get(span, 0.0) + (d - c)
    k = len(planes) * 1e9
    inside = [c for c in loaded["client"] if c[1] >= lo and c[1] + c[2] <= hi]
    ranked = lambda d: sorted(d.items(), key=lambda x: -x[1])
    return {
        "busy_s": busy / k,
        "window_s": (hi - lo) / 1e9,
        "queries": len(inside),
        "queries_by_shape": dict(Counter(c[0] for c in inside)),
        "device_ops": [[n, s / k] for n, s in ranked(per_op)[:top]],
        "idle_gaps": [[n, s / 1e9] for n, s in ranked(gaps)[:top]],
        "idle_by_span": {n: s / 1e9 for n, s in ranked(by_span)},
    }
