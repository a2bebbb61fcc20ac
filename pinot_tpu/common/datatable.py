"""DataTable: the server->broker binary wire format.

The reference ships per-server partial results as a custom versioned
binary ``DataTable`` (pinot-common ``common/utils/DataTable.java:44`` —
layout comment at :325) with special-cased serialization for
aggregation intermediates (``DataTableCustomSerDe.java:49``, which
Java-serializes HLL objects and value lists).

This implementation serializes ``IntermediateResult`` directly:

    [0:8]   magic  b"PTDTBL02" (02: the trace section is one JSON string)
    [8:16]  uint64 payload length
    payload: tagged binary encoding (below)

Aggregation intermediates are fixed-size numeric state wherever
possible: HLL -> raw 256-byte register array, percentiles -> value/count
histogram arrays, distinct-count -> typed value arrays — all strictly
smaller than the reference's Java-serialized objects, and losslessly
mergeable at the broker.

Value codec tags: N=None i=int(8) f=float(8) s=str T=True F=False
l=list t=tuple — length-prefixed, recursive.
"""
from __future__ import annotations

import json
import struct
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.engine.results import (
    AggPartial,
    AvgPartial,
    CountPartial,
    DistinctPartial,
    HistogramPartial,
    HllPartial,
    IntermediateResult,
    MaxPartial,
    MinMaxRangePartial,
    MinPartial,
    SumPartial,
)

MAGIC = b"PTDTBL02"


class _Writer:
    def __init__(self) -> None:
        self.parts: List[bytes] = []

    def u8(self, v: int) -> None:
        self.parts.append(struct.pack("<B", v))

    def i64(self, v: int) -> None:
        self.parts.append(struct.pack("<q", int(v)))

    def f64(self, v: float) -> None:
        self.parts.append(struct.pack("<d", float(v)))

    def blob(self, b: bytes) -> None:
        self.i64(len(b))
        self.parts.append(b)

    def string(self, s: str) -> None:
        self.blob(s.encode("utf-8"))

    def value(self, v: Any) -> None:
        """Tagged arbitrary (JSON-ish) value."""
        if v is None:
            self.parts.append(b"N")
        elif isinstance(v, bool):
            self.parts.append(b"T" if v else b"F")
        elif isinstance(v, (int, np.integer)):
            self.parts.append(b"i")
            self.i64(int(v))
        elif isinstance(v, (float, np.floating)):
            self.parts.append(b"f")
            self.f64(float(v))
        elif isinstance(v, str):
            self.parts.append(b"s")
            self.string(v)
        elif isinstance(v, (list, tuple)):
            self.parts.append(b"l")
            self.i64(len(v))
            for x in v:
                self.value(x)
        elif isinstance(v, dict):
            self.parts.append(b"d")
            self.i64(len(v))
            for k, x in v.items():
                self.string(str(k))
                self.value(x)
        elif isinstance(v, np.ndarray):
            # 'a': typed binary array — the join-exchange payloads ship
            # columnar key/value arrays through the same tagged codec
            # (orders of magnitude tighter than per-element 'i' tags)
            self.parts.append(b"a")
            self.array(v)
        else:
            raise TypeError(f"unsupported wire value {type(v)}")

    def array(self, a: np.ndarray) -> None:
        a = np.ascontiguousarray(a)
        self.string(str(a.dtype))
        self.i64(a.size)
        self.parts.append(a.tobytes())

    def getvalue(self) -> bytes:
        return b"".join(self.parts)


class _Reader:
    def __init__(self, data: bytes) -> None:
        self.data = data
        self.pos = 0

    def u8(self) -> int:
        v = struct.unpack_from("<B", self.data, self.pos)[0]
        self.pos += 1
        return v

    def i64(self) -> int:
        v = struct.unpack_from("<q", self.data, self.pos)[0]
        self.pos += 8
        return v

    def f64(self) -> float:
        v = struct.unpack_from("<d", self.data, self.pos)[0]
        self.pos += 8
        return v

    def blob(self) -> bytes:
        n = self.i64()
        b = self.data[self.pos : self.pos + n]
        self.pos += n
        return b

    def string(self) -> str:
        return self.blob().decode("utf-8")

    def value(self) -> Any:
        tag = self.data[self.pos : self.pos + 1]
        self.pos += 1
        if tag == b"N":
            return None
        if tag == b"T":
            return True
        if tag == b"F":
            return False
        if tag == b"i":
            return self.i64()
        if tag == b"f":
            return self.f64()
        if tag == b"s":
            return self.string()
        if tag == b"l":
            n = self.i64()
            return [self.value() for _ in range(n)]
        if tag == b"d":
            n = self.i64()
            return {self.string(): self.value() for _ in range(n)}
        if tag == b"a":
            return self.array()
        raise ValueError(f"bad value tag {tag!r} at {self.pos}")

    def array(self) -> np.ndarray:
        dtype = np.dtype(self.string())
        n = self.i64()
        nbytes = dtype.itemsize * n
        a = np.frombuffer(self.data[self.pos : self.pos + nbytes], dtype=dtype).copy()
        self.pos += nbytes
        return a


# ---------------------------------------------------------------------------
# Partial serde (type tag + state)
# ---------------------------------------------------------------------------

_PARTIAL_TAGS = {
    CountPartial: 1,
    SumPartial: 2,
    MinPartial: 3,
    MaxPartial: 4,
    AvgPartial: 5,
    MinMaxRangePartial: 6,
    DistinctPartial: 7,
    HllPartial: 8,
    HistogramPartial: 9,
}


def _write_partial(w: _Writer, p: AggPartial) -> None:
    tag = _PARTIAL_TAGS[type(p)]
    w.u8(tag)
    if isinstance(p, CountPartial):
        w.f64(p.count)
    elif isinstance(p, SumPartial):
        w.f64(p.total)
    elif isinstance(p, (MinPartial, MaxPartial)):
        w.f64(p.value)
    elif isinstance(p, AvgPartial):
        w.f64(p.total)
        w.f64(p.count)
    elif isinstance(p, MinMaxRangePartial):
        w.f64(p.mn)
        w.f64(p.mx)
    elif isinstance(p, DistinctPartial):
        w.i64(p.finalize())
        for v in p.iter_sorted():
            w.value(v)
    elif isinstance(p, HllPartial):
        w.blob(p.registers.tobytes())
    elif isinstance(p, HistogramPartial):
        w.i64(p.percentile)
        items = sorted(p.counts.items())
        w.array(np.asarray([v for v, _ in items], dtype=np.float64))
        w.array(np.asarray([c for _, c in items], dtype=np.int64))


def _read_partial(r: _Reader) -> AggPartial:
    tag = r.u8()
    if tag == 1:
        return CountPartial(r.f64())
    if tag == 2:
        return SumPartial(r.f64())
    if tag == 3:
        return MinPartial(r.f64())
    if tag == 4:
        return MaxPartial(r.f64())
    if tag == 5:
        return AvgPartial(r.f64(), r.f64())
    if tag == 6:
        return MinMaxRangePartial(r.f64(), r.f64())
    if tag == 7:
        n = r.i64()
        return DistinctPartial({r.value() for _ in range(n)})
    if tag == 8:
        regs = np.frombuffer(r.blob(), dtype=np.uint8).copy()
        return HllPartial(regs)
    if tag == 9:
        p = r.i64()
        vals = r.array()
        counts = r.array()
        return HistogramPartial(
            {float(v): int(c) for v, c in zip(vals, counts)}, percentile=p
        )
    raise ValueError(f"bad partial tag {tag}")


# ---------------------------------------------------------------------------
# IntermediateResult <-> bytes
# ---------------------------------------------------------------------------


def serialize_result(res: IntermediateResult) -> bytes:
    w = _Writer()
    w.i64(res.num_docs_scanned)
    w.i64(res.total_docs)
    w.i64(res.num_segments_queried)
    w.i64(res.num_entries_scanned_in_filter)
    w.i64(res.num_entries_scanned_post_filter)
    # the span tree rides as one JSON string: with the tail sampler armed
    # every reply carries some twenty spans, and the tagged codec below
    # costs 13 us a span each way where json costs under one (PERF.md,
    # PR 25: 0.6 ms a query at the median of lineitem_suite_open)
    w.string(json.dumps(res.trace, sort_keys=True, default=str) if res.trace else "")
    w.value([[int(c), str(m)] for c, m in res.exceptions])
    w.value([str(s) for s in res.unserved_segments])

    # sections present flags
    w.u8(1 if res.aggregations is not None else 0)
    if res.aggregations is not None:
        w.i64(len(res.aggregations))
        for p in res.aggregations:
            _write_partial(w, p)

    w.u8(1 if res.groups is not None else 0)
    if res.groups is not None:
        w.i64(len(res.groups))
        for key, partials in res.groups.items():
            w.value(list(key))
            w.i64(len(partials))
            for p in partials:
                _write_partial(w, p)

    w.u8(1 if res.selection_rows is not None else 0)
    if res.selection_rows is not None:
        w.value(res.selection_columns or [])
        w.i64(len(res.selection_rows))
        for sort_vals, row in res.selection_rows:
            w.value(sort_vals)
            w.value(row)

    # trailing optional cost vector (engine/results.py COST_KEYS): old
    # readers stop before it, old payloads simply end here — the same
    # mixed-version contract as InstanceRequest.debugOptions.  Keys are
    # written sorted so identical costs serialize byte-identically.
    w.value({k: res.cost[k] for k in sorted(res.cost)})

    # trailing optional backpressure snapshot (scheduler/lane saturation
    # of the answering server — the broker's AIMD admission signal):
    # same mixed-version contract, one more trailing value after cost
    w.value({k: res.backpressure[k] for k in sorted(res.backpressure)})

    # trailing optional plan-tree list (EXPLAIN / EXPLAIN ANALYZE
    # introspection nodes, engine/explain.py): JSON-safe dicts through
    # the tagged codec; empty for every normal query, absent for peers
    # predating the introspection plane
    w.value(list(res.plan_info))

    # trailing optional join-exchange payload (engine/join.py SideRows
    # wire dict — columnar arrays via the 'a' tag): None for every
    # non-join reply, absent for peers predating the join plane
    w.value(getattr(res, "join_payload", None))

    # trailing optional event-time freshness stamp ({"minEventMs": ...},
    # broker/freshness.py): None for offline-only replies, absent for
    # peers predating the audit plane — same mixed-version contract
    w.value(getattr(res, "freshness", None))

    payload = w.getvalue()
    return MAGIC + struct.pack("<Q", len(payload)) + payload


def deserialize_result(data: bytes) -> IntermediateResult:
    if data[:8] != MAGIC:
        raise ValueError("not a DataTable payload")
    (n,) = struct.unpack_from("<Q", data, 8)
    r = _Reader(data[16 : 16 + n])
    res = IntermediateResult()
    res.num_docs_scanned = r.i64()
    res.total_docs = r.i64()
    res.num_segments_queried = r.i64()
    res.num_entries_scanned_in_filter = r.i64()
    res.num_entries_scanned_post_filter = r.i64()
    trace = r.string()
    res.trace = json.loads(trace) if trace else {}
    res.exceptions = [(int(c), str(m)) for c, m in r.value()]
    res.unserved_segments = [str(s) for s in r.value()]

    if r.u8():
        cnt = r.i64()
        res.aggregations = [_read_partial(r) for _ in range(cnt)]
    if r.u8():
        cnt = r.i64()
        groups: Dict[Tuple[str, ...], List[AggPartial]] = {}
        for _ in range(cnt):
            key = tuple(r.value())
            np_ = r.i64()
            groups[key] = [_read_partial(r) for _ in range(np_)]
        res.groups = groups
    if r.u8():
        cols = r.value()
        res.selection_columns = list(cols) if cols else None
        cnt = r.i64()
        res.selection_rows = [(r.value(), r.value()) for _ in range(cnt)]
    if r.pos < len(r.data):
        # trailing cost vector (absent in payloads from older peers)
        res.cost = {str(k): v for k, v in (r.value() or {}).items()}
    if r.pos < len(r.data):
        # trailing backpressure snapshot (absent from older peers)
        res.backpressure = {str(k): v for k, v in (r.value() or {}).items()}
    if r.pos < len(r.data):
        # trailing EXPLAIN plan-tree list (absent from older peers)
        res.plan_info = [dict(n) for n in (r.value() or [])]
    if r.pos < len(r.data):
        # trailing join-exchange payload (absent from older peers)
        res.join_payload = r.value()
    if r.pos < len(r.data):
        # trailing event-time freshness stamp (absent from older peers)
        res.freshness = r.value()
    return res


# ---------------------------------------------------------------------------
# InstanceRequest (broker -> server)
# ---------------------------------------------------------------------------


def serialize_instance_request(
    request_id,
    pql: str,
    table: str,
    segments: List[str],
    timeout_ms: float,
    trace: bool = False,
    debug_options: Optional[Dict[str, str]] = None,
    join: Optional[Dict[str, Any]] = None,
) -> bytes:
    # request_id is the broker-assigned globally-unique id (a
    # broker-name-prefixed string, e.g. "broker0-3fa9c1-17"); it rides
    # the wire so server-side traces and logs correlate with the
    # broker's response/slow-query log.  Legacy integer ids stringify.
    w = _Writer()
    w.string(str(request_id))
    w.string(pql)
    w.string(table)
    w.value(list(segments))
    w.f64(timeout_ms)
    w.u8(1 if trace else 0)
    # per-query debug options ride to the server so its re-parse applies
    # the same optimizer flags (BrokerRequest.debugOptions thrift field)
    w.value(dict(debug_options or {}))
    # trailing optional join context (broker/joinplan.py): phase + spec
    # + shipped build/exchange payloads (columnar arrays via the 'a'
    # tag).  None for every single-table request; absent for peers
    # predating the join plane.
    w.value(join)
    return w.getvalue()


def deserialize_instance_request(data: bytes) -> Dict[str, Any]:
    r = _Reader(data)
    out = {
        "requestId": r.string(),
        "pql": r.string(),
        "table": r.string(),
        "segments": list(r.value()),
        "timeoutMs": r.f64(),
        "trace": bool(r.u8()),
    }
    # debugOptions is a trailing optional field: payloads from peers
    # predating it simply end here, and must stay readable during
    # mixed-version operation (ADVICE r1)
    if r.pos < len(data):
        out["debugOptions"] = dict(r.value() or {})
    else:
        out["debugOptions"] = {}
    # trailing optional join context (absent from older peers)
    out["join"] = r.value() if r.pos < len(data) else None
    return out
