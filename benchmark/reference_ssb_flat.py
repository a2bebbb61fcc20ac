"""The plain reference of ``ssb_lineorder_flat_1chip``: the Star Schema
Benchmark's queries over the denormalised table ``lineorder_flat``, the
first reference whose keys are tuples of strings and whose filters name
string values.

The interface is ``reference_tpch_keys.py``'s, which ``run.py`` calls
(``render_pql``, ``Reference.add/answers/rows/shape_bytes``, ``compare``,
``control_gaps``), and its checks of a dense answer (the groups returned,
the TOP-n rule, ``numGroupsLive``, ``groupStateSumSq``) are that module's
own, run here over the flattened cells; the expression parser, the
bfloat16 rounding and the width of a dictionary id are
``reference_tpch_spec.py``'s, loaded from the files beside this one.  Nothing here imports the program: a
segment is read through ``segment.column(name).dictionary.values``,
``.fwd`` and ``.metadata.is_sorted`` only.

A shape is data: ``filter`` is a conjunction of ``[column, op, value]``
with ``=``, ``<``, ``<=``, ``>``, ``>=``, ``in``, ``between`` and ``or``
(an OR of equalities on ONE column, rendered ``(c = a OR c = b)``), over string and integer columns,
evaluated by value on a segment's dictionary and carried to the rows
through the forward index; ``group_by`` is one to three columns;
an aggregate is ``["sum" | "avg" | "count", <column, "*", or {"expr":
"a - b"}>]``.

``answers[shape]`` is dense over the product of the key columns' values
seen so far: ``keys`` (one ascending array a column, strings as numpy
strings), ``counts`` (int64, one cell a tuple of keys) and ``sums`` (one
float64 array of that shape an aggregate).  ``add`` answers a segment
over the product of its own dictionaries with ``bincount`` and adds the
block in at the columns' positions (``np.ix_``); an axis grows when a
segment brings values not seen before (``d_year`` does, a segment being
a range of dates).  There is no Python loop over rows or groups.

``compare`` holds: the groups returned to the reference's live groups
(each shape states ``TOP n`` with n the count of groups its filter can
leave, so a reply holds the whole answer: a missing, repeated or
unknown tuple is a ``key_error``; under a smaller TOP the worst group
returned may not lie under the best one left out); each sum under
``sum_gap``; ``numDocsScanned`` and ``totalDocs`` exact; and the two
numbers the server takes from its whole group state, ``numGroupsLive``
(exact, ``count_errors``) and ``groupStateSumSq`` (``sum_gap``),
whichever tier answered.  ``sum_gap`` is relative to the reference's
value, the denominator held at 1 at the least.  The control
(``control="bfloat16"``) rounds the measures' values, every difference
inside an expression, a segment's sums and the merge to bfloat16.
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


_keys = _beside("reference_tpch_keys")
_spec = _keys._spec
# dense by key value there, dense by key tuple here: its elementwise helpers hold for any number of axes
wanted, live_groups, state_sum_sq, _state_gaps = _keys.wanted, _keys.live_groups, _keys.state_sum_sq, _keys._state_gaps

_OPS = {
    "=": lambda v, a: v == a,
    "<": lambda v, a: v < a,
    "<=": lambda v, a: v <= a,
    ">": lambda v, a: v > a,
    ">=": lambda v, a: v >= a,
    "in": lambda v, a: np.isin(v, a),
    "or": lambda v, a: np.isin(v, a),
    "between": lambda v, a: (v >= a[0]) & (v <= a[1]),
}


def render_pql(table: str, shape: dict) -> str:
    """The query text of a shape, as ClickHouse's page writes it over the
    flat table, in PQL: no ORDER BY, the groups under ``TOP n``."""

    def call(fn, arg):
        if fn == "count":
            return "count(*)"
        return f"{fn}({arg['expr'] if isinstance(arg, dict) else arg})"

    lit = _spec._literal
    pql = f"SELECT {', '.join(call(fn, arg) for fn, arg in shape['aggs'])} FROM {table}"
    preds = []
    for col, op, arg in shape.get("filter", []):
        if op == "in":
            preds.append(f"{col} IN ({','.join(lit(a) for a in arg)})")
        elif op == "or":
            preds.append("(" + " OR ".join(f"{col} = {lit(a)}" for a in arg) + ")")
        elif op == "between":
            preds.append(f"{col} BETWEEN {lit(arg[0])} AND {lit(arg[1])}")
        elif op in _OPS:
            preds.append(f"{col} {op} {lit(arg)}")
        else:
            raise ValueError(f"filter operator {op!r}: one of {sorted(_OPS)}")
    if preds:
        pql += " WHERE " + " AND ".join(preds)
    if shape.get("group_by"):
        pql += f" GROUP BY {', '.join(shape['group_by'])} TOP {shape['top']}"
    return pql


def _values(segment, col: str) -> np.ndarray:
    """A column's dictionary values: numpy strings, or int64."""
    values = segment.column(col).dictionary.values
    return np.asarray(values, dtype=str) if isinstance(values, list) else np.asarray(values)


class Reference:
    """Answers of every shape over the segments given to ``add``:
    ``answers[shape]`` is ``{"keys", "counts", "sums", "matched",
    "unpruned_rows"}`` as the module's text has them (an ungrouped shape
    has no key column and one cell)."""

    def __init__(self, shapes: dict, control: str = "") -> None:
        if control not in ("", "bfloat16"):
            raise ValueError(f"unknown control {control!r}")
        for name, shape in shapes.items():
            for fn, _ in shape["aggs"]:
                if fn not in ("sum", "avg", "count"):
                    raise ValueError(f"shape {name}: the reference has no aggregate {fn!r}")
            for _, op, _ in shape.get("filter", []):
                if op not in _OPS:
                    raise ValueError(f"shape {name}: the reference has no filter operator {op!r}")
        self.shapes = shapes
        self.control = control
        self.rows = 0
        self.sorted_columns: set = set()
        self.cardinalities: dict = {}
        self.answers = {name: None for name in shapes}
        self._first = True

    def shape_bytes(self, name: str) -> int:
        """The least a shape has to read: for the rows of the segments
        that no leaf of its filter empties (a segment is a range of
        dates, so a ``d_year`` or ``d_yearmonth`` leaf leaves some out
        whole), one dictionary id a row for every filter and key column
        at the narrowest width that holds the column's values (a filter
        column sorted in every segment and not a key is a binary search,
        and not read), and 4 B a row for every measure under a sum."""
        shape = self.shapes[name]
        touched = set(shape.get("group_by", []))
        touched |= {col for col, _, _ in shape.get("filter", []) if col not in self.sorted_columns}
        measures = set()
        for fn, arg in shape["aggs"]:
            if fn != "count":
                measures |= _spec.expr_columns(_spec.argument(arg))
        a_row = sum(_spec.id_bytes(self.cardinalities[c]) for c in touched) + 4 * len(measures)
        return self.answers[name]["unpruned_rows"] * a_row

    def add(self, segment) -> None:
        rounded = _spec.round_bfloat16 if self.control == "bfloat16" else (lambda x: x)

        def ids(col):
            return segment.column(col).fwd

        n = len(ids(next(iter(segment.columns))))
        self.rows += n
        is_sorted = {c for c in segment.columns if segment.column(c).metadata.is_sorted}
        self.sorted_columns = is_sorted if self._first else self.sorted_columns & is_sorted
        self._first = False
        for c in segment.columns:
            self.cardinalities[c] = max(self.cardinalities.get(c, 0), len(segment.column(c).dictionary.values))
        for name, shape in self.shapes.items():
            mask = np.ones(n, dtype=bool)
            pruned = False
            for col, op, arg in shape.get("filter", []):
                passing = _OPS[op](_values(segment, col), arg)
                pruned |= not passing.any()
                mask &= passing[ids(col)]
            rows = np.nonzero(mask)[0]
            group_cols = shape.get("group_by", [])
            seg_keys = [_values(segment, col) for col in group_cols]
            cards = tuple(len(k) for k in seg_keys)
            code = np.zeros(rows.size, dtype=np.int64)
            for col, card in zip(group_cols, cards):
                code = code * card + ids(col)[rows]
            size = int(np.prod(cards, dtype=np.int64)) if cards else 1

            def numeric(col):  # the matched rows' values as float64 (the control's: rounded)
                return rounded(_values(segment, col).astype(np.float64))[ids(col)[rows]]

            if self.answers[name] is None:
                self.answers[name] = {
                    "keys": [k[:0] for k in seg_keys], "counts": np.zeros((0,) * len(cards), dtype=np.int64),
                    "sums": [np.zeros((0,) * len(cards)) for _ in shape["aggs"]], "matched": 0, "unpruned_rows": 0}
            ans = self.answers[name]
            at = np.ix_(*[_positions(ans, axis, k) for axis, k in enumerate(seg_keys)])
            ans["counts"][at] += np.bincount(code, minlength=size).reshape(cards)
            ans["matched"] += int(rows.size)
            ans["unpruned_rows"] += 0 if pruned else n
            summed: dict = {}  # one pass an argument: a sum and an avg of one column read the same sums
            for i, (fn, arg) in enumerate(shape["aggs"]):
                if fn == "count":
                    continue
                if repr(arg) not in summed:
                    w = _spec.eval_expr(_spec.argument(arg), numeric, rounded)
                    summed[repr(arg)] = np.bincount(code, weights=w, minlength=size).reshape(cards)
                part = summed[repr(arg)]
                if self.control == "bfloat16":
                    ans["sums"][i][at] = rounded(ans["sums"][i][at] + rounded(part))
                else:
                    ans["sums"][i][at] += part


def _positions(ans: dict, axis: int, segment_keys: np.ndarray) -> np.ndarray:
    """Where each of a segment's dictionary values stands in the answer's
    ``keys[axis]``, which grows (and the dense arrays along that axis with
    it) when a segment brings values the answer has not seen."""
    have = ans["keys"][axis]
    if have.size == segment_keys.size and np.array_equal(have, segment_keys):
        return np.arange(segment_keys.size)
    merged = np.union1d(have, segment_keys)
    if merged.size != have.size:
        old = np.searchsorted(merged, have)

        def grown(holder: np.ndarray) -> np.ndarray:
            shape = list(holder.shape)
            shape[axis] = merged.size
            out = np.zeros(shape, dtype=holder.dtype)
            out[tuple(old if a == axis else slice(None) for a in range(holder.ndim))] = holder
            return out

        ans["counts"] = grown(ans["counts"])
        ans["sums"] = [grown(sums) for sums in ans["sums"]]
        ans["keys"][axis] = merged
    return np.searchsorted(merged, segment_keys)


def _cells(answer: dict, key_columns: list):
    """(flat cell of each key tuple, whether the tuple's every value is
    known) for ``key_columns``, one array of a reply's values a column."""
    flat = np.zeros(len(key_columns[0]) if key_columns else 1, dtype=np.int64)
    known = np.ones(flat.size, dtype=bool)
    for have, keys in zip(answer["keys"], key_columns):
        at = np.minimum(np.searchsorted(have, keys), max(have.size - 1, 0))
        known &= (have[at] == keys) if have.size else False
        flat = flat * have.size + at
    return flat, known


def _reply_gaps(out: dict, fn: str, key_columns: list, values: np.ndarray, want: np.ndarray, answer: dict, top: int) -> None:
    """One aggregate's groups as a reply gives them (``key_columns``: one
    array a key column, of the answer's key type; ``values`` float64)
    against ``want``, dense: ``reference_tpch_keys``'s check over the
    flattened cells, a tuple with an unknown value standing as a key the
    answer does not hold."""
    cells, known = _cells(answer, key_columns)
    flat = {"keys": np.arange(answer["counts"].size), "counts": answer["counts"].ravel()}
    _keys._reply_gaps(out, fn, np.where(known, cells, -1), values, want.ravel(), flat, top)


def compare(reply: dict, shape: dict, answer: dict, rows: int) -> dict:
    """Every number compared for one reply, under the four names
    ``run.py judge`` reads, as ``reference_tpch_keys.compare`` has them."""
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
    if not shape.get("group_by"):
        for (fn, _), res, want in zip(shape["aggs"], results, wanted(shape, answer)):
            value = float(res["value"])
            if fn == "count":
                out["count_errors"] += int(int(value) != int(want))
            else:
                out["sum_gap"] = max(out["sum_gap"], abs(value - float(want)) / max(1.0, abs(float(want))))
        return out
    _state_gaps(out, cost, reply.get("numServersQueried", 1), shape, answer)
    for (fn, _), res, want in zip(shape["aggs"], results, wanted(shape, answer)):
        groups = res.get("groupByResult") or []
        key_columns = []
        for axis, have in enumerate(answer["keys"]):
            keys = np.asarray([g["group"][axis] for g in groups], dtype=str)
            if have.dtype.kind in "iuf":  # a reply renders every key as text
                try:
                    keys = keys.astype(have.dtype)
                except ValueError:  # a key that is not of the column's type
                    keys = None
            key_columns.append(keys)
        if any(k is None for k in key_columns):
            out["key_errors"] += 1
            continue
        values = np.asarray([float(g["value"]) for g in groups], dtype=np.float64)
        _reply_gaps(out, fn, key_columns, values, want, answer, shape["top"])
    return out


def control_gaps(reference: Reference, control: Reference) -> dict:
    """Per shape, the ``sum_gap`` the control would show as a reply: its
    own TOP n by its own values and its own state, held to the reference."""
    gaps = {}
    for name, shape in reference.shapes.items():
        out = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
        answer, theirs = reference.answers[name], control.answers[name]
        if not shape.get("group_by"):
            for (fn, _), want, have in zip(shape["aggs"], wanted(shape, answer), wanted(shape, theirs)):
                if fn != "count":
                    out["sum_gap"] = max(out["sum_gap"], abs(float(have) - float(want)) / max(1.0, abs(float(want))))
            gaps[name] = out["sum_gap"]
            continue
        live = np.nonzero(theirs["counts"].ravel())[0]
        for (fn, _), want, have in zip(shape["aggs"], wanted(shape, answer), wanted(shape, theirs)):
            if fn == "count":
                continue
            have = have.ravel()
            top = live[np.argsort(-have[live], kind="stable")[: shape["top"]]]
            places = np.unravel_index(top, theirs["counts"].shape)
            key_columns = [keys[at] for keys, at in zip(theirs["keys"], places)]
            _reply_gaps(out, fn, key_columns, have[top], want, answer, shape["top"])
        _state_gaps(out, {"numGroupsLive": live.size, "groupStateSumSq": state_sum_sq(shape, theirs)}, 1, shape, answer)
        gaps[name] = out["sum_gap"]
    return gaps
