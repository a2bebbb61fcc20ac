"""From the profiler's ``.xplane.pb`` to busy time, idle gaps and
per-operation time.  The benchmark's own reduction, so that every PR
reads a trace the same way.

``load`` reads the file with ``jax.profiler.ProfileData`` into plain
lists; ``reduce`` is arithmetic on those lists and is what the tests
check on a small recorded trace.

Device planes are ``/device:TPU:<n>``.  On each, the line ``XLA Modules``
holds one event per program launch (``jit_packed(<fingerprint>)``) and
``XLA Ops`` one per executed HLO operation, named by its whole HLO text.
An operation is named here ``<its module>/<its result>``, as in
``jit_packed(7497...)/while.10``.  Busy is the union of the ``XLA Ops``
intervals; kernel time is the same union, since a program's operations
do not overlap on one core.  The program
has no annotations, so the only host spans are the load generator's own,
``client:<shape>`` around each query: an idle gap is attributed to the
query in flight over most of it, or to no query in flight.
"""
from __future__ import annotations

import glob
import os
from collections import Counter

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLIENT_PREFIX = "client:"


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path_or_data) -> dict:
    """``{"devices": {plane: [(name, start_ns, dur_ns)]}, "client":
    [(shape, start_ns, dur_ns)]}`` from a file, or from a ``ProfileData``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path_or_data) if isinstance(path_or_data, str) else path_or_data
    devices, client = {}, []
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
    return {"devices": devices, "client": client}


def union(intervals: list) -> list:
    """Sorted, disjoint ``[start, end]`` covering the same points."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def reduce(loaded: dict, window_ns: tuple, top: int = 10) -> dict:
    """Busy seconds averaged over the device planes, the window's length,
    the ``top`` operations by device seconds (averaged the same way), and
    the idle gaps of the first device by what the client had in flight.
    ``window_ns`` clips everything: (start, end) on the trace's clock."""
    lo, hi = window_ns
    planes = sorted(loaded["devices"])
    if not planes:
        return {"busy_s": 0.0, "window_s": (hi - lo) / 1e9, "queries": 0,
                "device_ops": [], "idle_gaps": []}
    busy, per_op, gaps = 0.0, {}, {}
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
                if g1 > g0:
                    cause = _in_flight(loaded["client"], g0, g1)
                    gaps[cause] = gaps.get(cause, 0.0) + (g1 - g0)
    k = len(planes) * 1e9
    inside = [c for c in loaded["client"] if c[1] >= lo and c[1] + c[2] <= hi]
    return {
        "busy_s": busy / k,
        "window_s": (hi - lo) / 1e9,
        "queries": len(inside),
        "queries_by_shape": dict(Counter(c[0] for c in inside)),
        "device_ops": [[n, s / k] for n, s in sorted(per_op.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[n, s / 1e9] for n, s in sorted(gaps.items(), key=lambda x: -x[1])[:top]],
    }


def _in_flight(client: list, g0: float, g1: float) -> str:
    """The query whose span covers most of the gap, if that is half of
    it or more."""
    best, best_cover = "", 0.0
    for shape, start, dur in client:
        cover = min(start + dur, g1) - max(start, g0)
        if cover > best_cover:
            best, best_cover = shape, cover
    if best_cover * 2 >= g1 - g0:
        return f"query_in_flight:{best}__host_cause_not_attributed"
    return "no_query_in_flight"
