"""The plain reference of ``tpch_lineitem_keys_1chip``: TPC-H Q15's view
(revenue by ``l_suppkey`` over three months) and its maximum as TOP n,
written for many groups.

The interface is ``reference_tpch_spec.py``'s, which ``run.py`` calls
(``render_pql``, ``Reference.add/answers/rows/shape_bytes``, ``compare``,
``control_gaps``), and its shape grammar, expression parser, predicate
operators and bfloat16 rounding are that module's own, loaded from the
file beside this one; nothing here imports the program.  What differs is
how a grouped answer is held and compared, because a Q15 shape has
220,000 groups where Q1 has six:

- ``answers[shape]`` is dense by key value: ``keys`` (the group column's
  values, ascending), ``counts`` (int64 rows matched a key) and ``sums``
  (one float64 array an aggregate, a ``count`` reading ``counts``), all
  of one length.  ``add`` merges a segment with ``bincount`` and one
  indexed add; it has no Python loop over a segment's groups.
- ``compare`` finds a reply's groups in ``keys`` with one
  ``searchsorted``, and its TOP-n check (no group left out beats one
  returned by more than float32 could mistake) is a masked maximum.
- ``compare`` also holds two numbers of the reply's cost vector that
  the server takes from its whole fetched state, so that a scatter or a
  finalize that drops, invents or misplaces updates is caught even where
  the TOP n it returns is right: ``numGroupsLive`` to the reference's
  count of non-empty groups, exact, under ``count_errors``, and
  ``groupStateSumSq`` to the sum of squares of every non-empty group's
  value of every aggregate, under ``sum_gap``.  Both are a server's own
  and the broker adds them: with one answering server they are held as
  said; with more, a group may be live on several, so the count is held
  between the reference's and that times the servers, and the squares
  (of each server's part of a sum) are not held.

A shape groups by one single-value column; an ungrouped shape or a key of
several columns is ``reference_tpch_spec``'s to answer, and is refused
here by name.

Tolerance: as ``reference_tpch_spec``.  Counts, keys, ``numDocsScanned``,
``totalDocs`` and the live-group count exact; a float sum, and the sum of
squares, within the configuration's ``sum_rtol`` of the float64 value,
the denominator held at 1 at the least.  The control (``control="bfloat16"``) rounds column
values, every product and sum inside an expression, a segment's stored
sums and the merge to bfloat16; ``control_gaps`` gives the gap its own
TOP-n reply would show, the wrong supplier at the top included.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("bench_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_spec = _beside("reference_tpch_spec")
render_pql = _spec.render_pql


class Reference:
    """Answers of every shape over the segments given to ``add``:
    ``answers[shape]`` is ``{"keys", "counts", "sums", "matched",
    "sorted_matched"}`` as the module's text has them."""

    def __init__(self, shapes: dict, control: str = "") -> None:
        if control not in ("", "bfloat16"):
            raise ValueError(f"unknown control {control!r}")
        for name, shape in shapes.items():
            if len(shape.get("group_by", [])) != 1:
                raise ValueError(f"shape {name}: this reference answers a group-by over one column")
            for fn, _ in shape["aggs"]:
                if fn not in ("sum", "avg", "count"):
                    raise ValueError(f"shape {name}: the reference has no aggregate {fn!r}")
        self.shapes = shapes
        self.control = control
        self.rows = 0
        self.sorted_columns: set = set()
        self.cardinalities: dict = {}
        self.answers = {
            name: {"keys": np.empty(0), "counts": np.zeros(0, dtype=np.int64),
                   "sums": [np.zeros(0) for _ in shape["aggs"]], "matched": 0, "sorted_matched": 0}
            for name, shape in shapes.items()
        }
        self._first = True

    def shape_bytes(self, name: str) -> int:
        return _spec.shape_bytes(self.shapes[name], self.answers[name], self.rows,
                                 self.cardinalities, self.sorted_columns)

    def add(self, segment) -> None:
        rounded = _spec.round_bfloat16 if self.control == "bfloat16" else (lambda x: x)

        def values(col):
            return np.asarray(segment.column(col).dictionary.values)

        def ids(col):
            return segment.column(col).fwd

        n = len(ids(next(iter(segment.columns))))
        self.rows += n
        is_sorted = {c for c in segment.columns if segment.column(c).metadata.is_sorted}
        self.sorted_columns = is_sorted if self._first else self.sorted_columns & is_sorted
        self._first = False
        for c in segment.columns:
            self.cardinalities[c] = max(self.cardinalities.get(c, 0), len(values(c)))
        for name, shape in self.shapes.items():
            mask = np.ones(n, dtype=bool)
            by_sorted = None
            for col, op, arg in shape.get("filter", []):
                m = _spec._OPS[op](values(col), arg)[ids(col)]
                mask &= m
                if col in is_sorted:
                    by_sorted = m if by_sorted is None else by_sorted & m
            rows = np.nonzero(mask)[0]
            (key_col,) = shape["group_by"]
            code = ids(key_col)[rows]
            card = len(values(key_col))

            def numeric(col):  # the matched rows' values as float64 (the control's: rounded)
                return rounded(values(col).astype(np.float64))[ids(col)[rows]]

            ans = self.answers[name]
            at = _positions(ans, values(key_col))
            counts = np.bincount(code, minlength=card)
            ans["counts"][at] += counts
            ans["matched"] += int(rows.size)
            ans["sorted_matched"] += n if by_sorted is None else int(by_sorted.sum())
            summed: dict = {}  # one pass an argument: a sum and an avg of one column read the same sums
            for i, (fn, arg) in enumerate(shape["aggs"]):
                if fn == "count":
                    continue
                if repr(arg) not in summed:
                    w = _spec.eval_expr(_spec.argument(arg), numeric, rounded)
                    summed[repr(arg)] = np.bincount(code, weights=w, minlength=card)
                part = summed[repr(arg)]
                if self.control == "bfloat16":
                    ans["sums"][i][at] = rounded(ans["sums"][i][at] + rounded(part))
                else:
                    ans["sums"][i][at] += part


def _positions(ans: dict, segment_keys: np.ndarray) -> np.ndarray:
    """Where each of a segment's dictionary values stands in the answer's
    ``keys``, which grow (and the dense arrays with them) when a segment
    brings values the answer has not seen."""
    if ans["keys"].size == segment_keys.size and np.array_equal(ans["keys"], segment_keys):
        return np.arange(segment_keys.size)
    merged = np.array(segment_keys) if ans["keys"].size == 0 else np.union1d(ans["keys"], segment_keys)
    old = np.searchsorted(merged, ans["keys"])

    def grown(holder: np.ndarray) -> np.ndarray:
        out = np.zeros(merged.size, dtype=holder.dtype)
        out[old] = holder
        return out

    ans["counts"] = grown(ans["counts"])
    ans["sums"] = [grown(sums) for sums in ans["sums"]]
    ans["keys"] = merged
    return np.searchsorted(merged, segment_keys)


def wanted(shape: dict, answer: dict) -> list:
    """Per aggregate, the dense values a reply should give by key: an
    ``avg`` is its sum over the key's matched count (0 where empty)."""
    out = []
    for (fn, _), sums in zip(shape["aggs"], answer["sums"]):
        if fn == "count":
            out.append(answer["counts"])
        elif fn == "avg":
            out.append(sums / np.maximum(answer["counts"], 1))
        else:
            out.append(sums)
    return out


def live_groups(answer: dict) -> int:
    """Groups with a row: what a finalize finds before any trim."""
    return int(np.count_nonzero(answer["counts"]))


def state_sum_sq(shape: dict, answer: dict) -> float:
    """The sum of squares of every non-empty group's value of every
    aggregate: what ``groupStateSumSq`` digests of a server's state."""
    live = answer["counts"] > 0
    return float(sum(np.square(want[live], dtype=np.float64).sum() for want in wanted(shape, answer)))


def _state_gaps(out: dict, cost: dict, servers: int, shape: dict, answer: dict) -> None:
    """The two numbers a server takes from its whole state, as the
    broker's sum over ``servers`` of them gives them."""
    live, have = live_groups(answer), cost.get("numGroupsLive", 0)
    if servers == 1:
        out["count_errors"] += int(have != live)
        want = state_sum_sq(shape, answer)
        out["sum_gap"] = max(out["sum_gap"], abs(float(cost.get("groupStateSumSq", 0.0)) - want) / max(1.0, want))
    else:
        out["count_errors"] += int(not live <= have <= servers * live)


def _reply_gaps(out: dict, fn: str, keys, values, want: np.ndarray, answer: dict, top: int) -> None:
    """One aggregate's groups as a reply gives them, (``keys`` of the
    answer's key type, ``values`` float64), against ``want``."""
    live = answer["counts"] > 0
    at = np.minimum(np.searchsorted(answer["keys"], keys), max(answer["keys"].size - 1, 0))
    known = answer["keys"][at] == keys if answer["keys"].size else np.zeros(len(keys), dtype=bool)
    if len(keys) != min(top, int(live.sum())) or not known.all() or not live[at].all() \
            or np.unique(at).size != at.size:
        out["key_errors"] += 1
        return
    if at.size == 0:
        return
    if fn == "count":
        out["count_errors"] += int(np.count_nonzero(values.astype(np.int64) != want[at]))
    else:
        out["sum_gap"] = max(out["sum_gap"], float(np.max(np.abs(values - want[at]) / np.maximum(1.0, np.abs(want[at])))))
    # TOP n: the worst group returned may not lie under the best one
    # left out by more than float32 could mistake them
    left_out = live.copy()
    left_out[at] = False
    if left_out.any():
        best_left = float(np.max(want[left_out]))
        gap = (best_left - float(np.min(want[at]))) / max(1.0, abs(best_left))
        if fn == "count":
            out["key_errors"] += int(gap > 0)
        else:
            out["sum_gap"] = max(out["sum_gap"], gap)


def compare(reply: dict, shape: dict, answer: dict, rows: int) -> dict:
    """Every number compared for one reply, under the four names
    ``run.py judge`` reads, as ``reference_tpch_spec.compare`` has them;
    ``count_errors`` also counts a reply whose cost vector's
    ``numGroupsLive`` (absent: 0) is not the reference's count of
    non-empty groups, and ``sum_gap`` holds its ``groupStateSumSq``
    (``_state_gaps``)."""
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
    _state_gaps(out, cost, reply.get("numServersQueried", 1), shape, answer)
    results = reply.get("aggregationResults") or []
    if len(results) != len(shape["aggs"]):
        out["reply_errors"] += 1
        return out
    for (fn, _), res, want in zip(shape["aggs"], results, wanted(shape, answer)):
        groups = res.get("groupByResult") or []
        keys = np.asarray([g["group"][0] for g in groups], dtype=str)
        if answer["keys"].dtype.kind in "iuf":  # a reply renders every key as text
            try:
                keys = keys.astype(answer["keys"].dtype)
            except ValueError:  # a key that is not of the column's type
                out["key_errors"] += 1
                continue
        values = np.asarray([float(g["value"]) for g in groups], dtype=np.float64)
        _reply_gaps(out, fn, keys, values, want, answer, shape["top"])
    return out


def control_gaps(reference: Reference, control: Reference) -> dict:
    """Per shape, the ``sum_gap`` the control would show as a reply: its
    own TOP n by its own values, held to the reference."""
    gaps = {}
    for name, shape in reference.shapes.items():
        out = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
        answer, theirs = reference.answers[name], control.answers[name]
        live = np.nonzero(theirs["counts"])[0]
        for (fn, _), want, have in zip(shape["aggs"], wanted(shape, answer), wanted(shape, theirs)):
            if fn == "count":
                continue
            top = live[np.argsort(-have[live], kind="stable")[: shape["top"]]]
            _reply_gaps(out, fn, theirs["keys"][top], have[top], want, answer, shape["top"])
        _state_gaps(out, {"numGroupsLive": live.size, "groupStateSumSq": state_sum_sq(shape, theirs)}, 1, shape, answer)
        gaps[name] = out["sum_gap"]
    return gaps
