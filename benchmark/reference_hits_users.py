"""The plain reference of ``clickbench_hits_users_1chip``: ClickBench's
distinct-user queries (``queries.sql`` lines 5, 9 and 10) as a Pinot
deployment answers them, ``distinctcounthll(UserID)`` alone and by
``RegionID``, beside a count, a sum and an average.

The interface is the other references', which ``run.py`` calls
(``render_pql``, ``Reference.add/answers/rows/shape_bytes``, ``compare``,
``control_gaps``); the query text and the bfloat16 rounding are
``reference_tpch_spec``'s, loaded from the file beside this one.  Nothing
here imports the program: the sketch is written out anew below.

**The sketch** (clearspring's HyperLogLog as upstream's
``distinctcounthll`` runs it, log2m 8: 256 registers).  A value's hash is
the configuration's, stated there in words: for an integral column the
splitmix64 finalizer of the value's 64 two's-complement bits
(``x += 0x9E3779B97F4A7C15; x ^= x >> 30; x *= 0xBF58476D1CE4E5B9;
x ^= x >> 27; x *= 0x94D049BB133111EB; x ^= x >> 31``, mod 2^64).  The
register is the hash's low 8 bits, the rank the trailing zeros of the
other 56 plus one (57 where they are all zero), a register the largest
rank it saw.  The estimate is ``alpha m^2 / sum(2^-register)`` with
``alpha = 0.7213 / (1 + 1.079 / m)``; linear counting ``m ln(m / V)``
where that is at most ``2.5 m`` and ``V`` registers are empty;
``-2^64 ln(1 - E / 2^64)`` above ``2^64 / 30``; rounded to the nearest
integer.  Every distinct user of a segment is hashed once (its
dictionary), the registers are kept by region as an array [regions, 256].

``answers[shape]`` is dense by key value, as ``reference_tpch_keys`` holds
its own: ``keys`` (the group column's values, ascending; one empty key for
an ungrouped shape), ``counts``, ``sums`` (a float64 array an aggregate,
None where it is not a sum) and ``registers`` (uint8 [keys, 256] an HLL
aggregate, None elsewhere).

``compare`` holds, for every group a reply returns, the distinct count
to the reference's **as an integer** (``count_errors``), counts exact,
sums and averages within ``sum_rtol`` (``sum_gap``), the regions
returned (``key_errors``: a region returned below a better one left out
counts, for a float sum as a gap); and for the groups it does not return,
what the server takes from its whole fetched state: ``numGroupsLive`` and
``groupStateHllSum`` (the sum of every live group's estimate, an integer
far under 2^53) exact under ``count_errors``, ``groupStateHllSumSq`` and
``groupStateSumSq`` (the squares of the estimates, and of every other
aggregate's values) under ``sum_gap``.  With more than one answering
server a group may be live on several, so the count is held between the
reference's and that times the servers and the other three are not held.

The control (``control="bfloat16"``) rounds the measures' values, a
segment's stored sums and the merge to bfloat16; a register has no lower
precision: what stands in for it is a reply that lost a segment's
registers or whose estimate is off by one (``benchmark/tests``).
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

LOG2M = 8
M = 1 << LOG2M
HLL = "distinctcounthll"


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("bench_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_spec = _beside("reference_tpch_spec")
render_pql = _spec.render_pql


def hash64(values: np.ndarray) -> np.ndarray:
    """The configuration's hash of an integral column's values, as uint64."""
    x = np.asarray(values).astype(np.int64).view(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def register_and_rank(hashes: np.ndarray) -> tuple:
    """(register index, rank) of every hash: the low 8 bits, and the
    trailing zeros of the other 56 plus one (57 where they are zero)."""
    register = (hashes & np.uint64(M - 1)).astype(np.int64)
    rest = hashes >> np.uint64(LOG2M)
    rank = np.full(hashes.shape, 64 - LOG2M + 1, dtype=np.int64)
    left = np.nonzero(rest)[0]
    zeros = np.zeros(left.size, dtype=np.int64)
    rest = rest[left]
    for step in (32, 16, 8, 4, 2, 1):  # count trailing zeros by halves
        low_clear = (rest & np.uint64((1 << step) - 1)) == 0
        zeros += np.where(low_clear, step, 0)
        rest = np.where(low_clear, rest >> np.uint64(step), rest)
    rank[left] = zeros + 1
    return register, rank


def fill_registers(registers: np.ndarray, cells: np.ndarray, ranks: np.ndarray) -> None:
    """``registers.flat[cell] = max(rank)`` over rows, without a sort: the
    ranks of a hash halve in number with every step up, so writing rank r
    into the cells of the rows that reach it, r ascending, leaves each
    cell at its largest (2 n writes in all)."""
    flat = registers.reshape(-1)
    level = 1
    while cells.size:
        flat[cells] = np.maximum(flat[cells], level)
        level += 1
        keep = ranks >= level
        cells, ranks = cells[keep], ranks[keep]


def estimate(registers: np.ndarray) -> np.ndarray:
    """clearspring's cardinality of every register row, int64."""
    raw = (0.7213 / (1.0 + 1.079 / M)) * M * M / np.sum(np.exp2(-registers.astype(np.float64)), axis=-1)
    empty = np.sum(registers == 0, axis=-1)
    out = np.array(raw, dtype=np.float64, ndmin=1)
    small = (out <= 2.5 * M) & (np.atleast_1d(empty) > 0)
    out[small] = M * np.log(M / np.atleast_1d(empty)[small].astype(np.float64))
    large = ~small & (out > 2.0**64 / 30.0)
    out[large] = -(2.0**64) * np.log(1.0 - out[large] / 2.0**64)
    return np.rint(out).astype(np.int64)


class Reference:
    """Answers of every shape over the segments given to ``add``."""

    def __init__(self, shapes: dict, control: str = "") -> None:
        if control not in ("", "bfloat16"):
            raise ValueError(f"unknown control {control!r}")
        for name, shape in shapes.items():
            if len(shape.get("group_by", [])) > 1 or shape.get("filter"):
                raise ValueError(f"shape {name}: this reference answers an unfiltered shape, ungrouped or by one column")
            for fn, _ in shape["aggs"]:
                if fn not in ("sum", "avg", "count", HLL):
                    raise ValueError(f"shape {name}: the reference has no aggregate {fn!r}")
        self.shapes = shapes
        self.control = control
        self.rows = 0
        self.cardinalities: dict = {}
        self.answers = {
            name: {
                "keys": np.empty(0), "counts": np.zeros(0, dtype=np.int64), "matched": 0, "sorted_matched": 0,
                "sums": [np.zeros(0) if fn in ("sum", "avg") else None for fn, _ in shape["aggs"]],
                "registers": [np.zeros((0, M), dtype=np.uint8) if fn == HLL else None for fn, _ in shape["aggs"]],
            }
            for name, shape in shapes.items()
        }

    def shape_bytes(self, name: str) -> int:
        """The least a shape has to read: a dictionary id a row for its
        key and for each measure it sums, and for a column it counts
        distinct the register index and the rank, a byte each (less than
        the column's own id)."""
        shape = self.shapes[name]
        per_row = sum(_spec.id_bytes(self.cardinalities[c]) for c in shape.get("group_by", []))
        per_row += sum(_spec.id_bytes(self.cardinalities[c]) for c in {arg for fn, arg in shape["aggs"] if fn in ("sum", "avg")})
        per_row += 2 * len({arg for fn, arg in shape["aggs"] if fn == HLL})
        return self.rows * per_row

    def add(self, segment) -> None:
        rounded = _spec.round_bfloat16 if self.control == "bfloat16" else (lambda x: x)

        def values(col):
            return np.asarray(segment.column(col).dictionary.values)

        def ids(col):
            return segment.column(col).fwd

        n = len(ids(next(iter(segment.columns))))
        self.rows += n
        for c in segment.columns:
            self.cardinalities[c] = max(self.cardinalities.get(c, 0), len(values(c)))
        sketched: dict = {}  # (key column, counted column) -> this segment's registers [keys, 256]
        for name, shape in self.shapes.items():
            ans = self.answers[name]
            group_by = shape.get("group_by", [])
            code = ids(group_by[0]) if group_by else np.zeros(n, dtype=np.int32)
            key_values = values(group_by[0]) if group_by else np.zeros(1)
            at = _positions(ans, key_values)
            ans["counts"][at] += np.bincount(code, minlength=key_values.size)
            ans["matched"] += n
            ans["sorted_matched"] += n
            summed: dict = {}
            for i, (fn, arg) in enumerate(shape["aggs"]):
                if fn in ("sum", "avg"):
                    if arg not in summed:
                        w = rounded(values(arg).astype(np.float64))[ids(arg)]
                        summed[arg] = np.bincount(code, weights=w, minlength=key_values.size)
                    if self.control == "bfloat16":
                        ans["sums"][i][at] = rounded(ans["sums"][i][at] + rounded(summed[arg]))
                    else:
                        ans["sums"][i][at] += summed[arg]
                elif fn == HLL:
                    which = (tuple(group_by), arg)
                    if which not in sketched:
                        register, rank = register_and_rank(hash64(values(arg)))  # every distinct value once
                        sketched[which] = np.zeros((key_values.size, M), dtype=np.uint8)
                        fill_registers(sketched[which], code.astype(np.int64) * M + register[ids(arg)], rank[ids(arg)])
                    ans["registers"][i][at] = np.maximum(ans["registers"][i][at], sketched[which])


def _positions(ans: dict, segment_keys: np.ndarray) -> np.ndarray:
    """Where each of a segment's dictionary values stands in the answer's
    ``keys``, which grow (and the dense arrays with them) when a segment
    brings values the answer has not seen."""
    if ans["keys"].size == segment_keys.size and np.array_equal(ans["keys"], segment_keys):
        return np.arange(segment_keys.size)
    merged = np.array(segment_keys) if ans["keys"].size == 0 else np.union1d(ans["keys"], segment_keys)
    old = np.searchsorted(merged, ans["keys"])

    def grown(holder):
        if holder is None:
            return None
        out = np.zeros((merged.size,) + holder.shape[1:], dtype=holder.dtype)
        out[old] = holder
        return out

    ans["counts"] = grown(ans["counts"])
    ans["sums"] = [grown(sums) for sums in ans["sums"]]
    ans["registers"] = [grown(registers) for registers in ans["registers"]]
    ans["keys"] = merged
    return np.searchsorted(merged, segment_keys)


def wanted(shape: dict, answer: dict) -> list:
    """Per aggregate, the dense values a reply should give by key: an
    ``avg`` is its sum over the key's count (0 where empty), a distinct
    count its registers' estimate (int64)."""
    out = []
    for (fn, _), sums, registers in zip(shape["aggs"], answer["sums"], answer["registers"]):
        if fn == "count":
            out.append(answer["counts"])
        elif fn == HLL:
            out.append(estimate(registers))
        elif fn == "avg":
            out.append(sums / np.maximum(answer["counts"], 1))
        else:
            out.append(sums)
    return out


def state_digest(shape: dict, answer: dict) -> dict:
    """What a server's cost vector says of its whole fetched state, from
    the reference's: the live groups, the sum and the sum of squares of
    their distinct counts, the sum of squares of their other values."""
    live = answer["counts"] > 0
    out = {"numGroupsLive": int(np.count_nonzero(live)), "groupStateSumSq": 0.0, "groupStateHllSum": 0, "groupStateHllSumSq": 0.0}
    for (fn, _), want in zip(shape["aggs"], wanted(shape, answer)):
        if fn == HLL:
            out["groupStateHllSum"] += int(want[live].sum())
            out["groupStateHllSumSq"] += float(np.square(want[live].astype(np.float64)).sum())
        else:
            out["groupStateSumSq"] += float(np.square(want[live], dtype=np.float64).sum())
    return out


def _state_gaps(out: dict, cost: dict, servers: int, shape: dict, answer: dict) -> None:
    want = state_digest(shape, answer)
    have = cost.get("numGroupsLive", 0)
    if servers != 1:
        out["count_errors"] += int(not want["numGroupsLive"] <= have <= servers * want["numGroupsLive"])
        return
    out["count_errors"] += int(have != want["numGroupsLive"])
    out["count_errors"] += int(float(cost.get("groupStateHllSum", 0)) != float(want["groupStateHllSum"]))
    for key in ("groupStateSumSq", "groupStateHllSumSq"):
        out["sum_gap"] = max(out["sum_gap"], abs(float(cost.get(key, 0.0)) - want[key]) / max(1.0, want[key]))


def _reply_gaps(out: dict, fn: str, keys, values, want: np.ndarray, answer: dict, top: int) -> None:
    """One aggregate's groups as a reply gives them, (``keys`` of the
    answer's key type, ``values`` float64), against ``want``."""
    live = answer["counts"] > 0
    at = np.minimum(np.searchsorted(answer["keys"], keys), max(answer["keys"].size - 1, 0))
    known = answer["keys"][at] == keys if answer["keys"].size else np.zeros(len(keys), dtype=bool)
    if len(keys) != min(top, int(live.sum())) or not known.all() or not live[at].all() or np.unique(at).size != at.size:
        out["key_errors"] += 1
        return
    if at.size == 0:
        return
    exact = fn in ("count", HLL)
    if exact:
        out["count_errors"] += int(np.count_nonzero(np.rint(values).astype(np.int64) != want[at]) + np.count_nonzero(values != np.rint(values)))
    else:
        out["sum_gap"] = max(out["sum_gap"], float(np.max(np.abs(values - want[at]) / np.maximum(1.0, np.abs(want[at])))))
    # TOP n: no group left out may beat one returned (a float sum: by
    # more than float32 could mistake them)
    left_out = live.copy()
    left_out[at] = False
    if left_out.any():
        best_left = float(np.max(want[left_out]))
        gap = (best_left - float(np.min(want[at]))) / max(1.0, abs(best_left))
        if exact:
            out["key_errors"] += int(gap > 0)
        else:
            out["sum_gap"] = max(out["sum_gap"], gap)


def compare(reply: dict, shape: dict, answer: dict, rows: int) -> dict:
    """Every number compared for one reply, under the four names
    ``run.py judge`` reads (the module's text says which holds what)."""
    out = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
    cost = reply.get("cost") or {}
    if (
        reply.get("exceptions")
        or reply.get("partialResponse")
        or reply.get("numSegmentsUnserved", 0)
        or reply.get("numServersResponded") != reply.get("numServersQueried")
        or cost.get("segmentsHost", 0)
    ):
        out["reply_errors"] += 1
        return out
    if reply.get("numDocsScanned") != answer["matched"] or reply.get("totalDocs") != rows:
        out["count_errors"] += 1
    results = reply.get("aggregationResults") or []
    if len(results) != len(shape["aggs"]):
        out["reply_errors"] += 1
        return out
    grouped = bool(shape.get("group_by"))
    if grouped:
        _state_gaps(out, cost, reply.get("numServersQueried", 1), shape, answer)
    for (fn, _), res, want in zip(shape["aggs"], results, wanted(shape, answer)):
        try:
            if grouped:
                groups = res.get("groupByResult") or []
                keys = np.asarray([g["group"][0] for g in groups], dtype=str).astype(answer["keys"].dtype)
                values = np.asarray([float(g["value"]) for g in groups], dtype=np.float64)
            else:
                keys, values = answer["keys"][:1], np.asarray([float(res["value"])], dtype=np.float64)
        except (KeyError, TypeError, ValueError):  # a result without its value, a key not of the column's type
            out["key_errors"] += 1
            continue
        _reply_gaps(out, fn, keys, values, want, answer, shape["top"] if grouped else 1)
    return out


def control_gaps(reference: Reference, control: Reference) -> dict:
    """Per shape, the ``sum_gap`` the control would show as a reply: its
    own TOP n by its own values and its own state, held to the reference."""
    gaps = {}
    for name, shape in reference.shapes.items():
        out = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
        answer, theirs = reference.answers[name], control.answers[name]
        live = np.nonzero(theirs["counts"])[0]
        grouped = bool(shape.get("group_by"))
        for (fn, _), want, have in zip(shape["aggs"], wanted(shape, answer), wanted(shape, theirs)):
            if fn in ("count", HLL):
                continue
            top = live[np.argsort(-have[live], kind="stable")[: shape["top"] if grouped else 1]]
            _reply_gaps(out, fn, theirs["keys"][top], have[top], want, answer, shape["top"] if grouped else 1)
        if grouped:
            _state_gaps(out, state_digest(shape, theirs), 1, shape, answer)
        gaps[name] = out["sum_gap"]
    return gaps
