"""The plain reference: what each query shape must answer, and how a
reply is held to it.

A shape is data (``traffic/<name>.json``): filter predicates, group-by
columns, aggregates, TOP.  ``render_pql`` turns it into the PQL the
client sends; ``Reference`` answers it with numpy over the segments'
dictionaries and forward indexes, one segment at a time, accumulated in
float64 and python ints; ``compare`` holds a reply to that answer and
returns every number compared; ``shape_bytes`` says how many bytes the
shape has to read at the least.  Nothing here imports the program: the
segment is read through ``segment.column(name).dictionary.values`` and
``.fwd`` only, the two arrays the generator made.

Tolerance.  The configuration states it: counts and ``numDocsScanned``
exact, a float sum within ``sum_rtol`` (3e-4) of the float64 reference.
``PERF.md`` section 2 gives the readings the limit stands between: the
program's largest gap over a dozen seeds (0.9e-4, in the K=6 group-by,
which rounds values to bfloat16 before the matrix unit and accumulates
in float32; every other shape reads under 1e-5), and the smallest gap of
the control (3e-3).  The control is this
same class with ``control="bfloat16"``, the next precision down: what a
kernel written in bfloat16 keeps is bfloat16.  Column values are rounded
to bfloat16, a segment's sums are accumulated wide (as the matrix unit
does) and stored as bfloat16, and the segments' sums are merged in
bfloat16.  Rounding the values alone is not enough of a control: over
16,384 prices the rounding errors cancel to about 1e-5.
"""
from __future__ import annotations

import numpy as np

# predicate operators a shape may use, evaluated on a column's dictionary
# values and carried to the rows through the forward index
_OPS = {
    "=": lambda v, a: v == a,
    "<=": lambda v, a: v <= a,
    ">=": lambda v, a: v >= a,
    "<": lambda v, a: v < a,
    ">": lambda v, a: v > a,
    "in": lambda v, a: np.isin(v, a),
    "between": lambda v, a: (v >= a[0]) & (v <= a[1]),
}


def _literal(x) -> str:
    return f"'{x}'" if isinstance(x, str) else repr(x)


def render_pql(table: str, shape: dict) -> str:
    """The query text of a shape, in the form upstream's benchmark writes."""
    aggs = ", ".join("count(*)" if fn == "count" else f"{fn}({col})" for fn, col in shape["aggs"])
    pql = f"SELECT {aggs} FROM {table}"
    preds = []
    for col, op, arg in shape.get("filter", []):
        if op == "in":
            preds.append(f"{col} IN ({','.join(_literal(a) for a in arg)})")
        elif op == "between":
            preds.append(f"{col} BETWEEN {_literal(arg[0])} AND {_literal(arg[1])}")
        else:
            preds.append(f"{col} {op} {_literal(arg)}")
    if preds:
        pql += " WHERE " + " AND ".join(preds)
    if shape.get("group_by"):
        pql += f" GROUP BY {', '.join(shape['group_by'])} TOP {shape['top']}"
    return pql


def round_bfloat16(values: np.ndarray) -> np.ndarray:
    """float64 -> nearest-even bfloat16 -> float64, in plain numpy."""
    bits = values.astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


class Reference:
    """Answers of every shape over the segments given to ``add``.

    ``answers[shape]`` is ``{"groups": {key tuple: [agg values]},
    "matched": rows}``; an ungrouped shape has the one key ``()``.
    Sums are float64, counts python ints."""

    def __init__(self, shapes: dict, control: str = "") -> None:
        if control not in ("", "bfloat16"):
            raise ValueError(f"unknown control {control!r}")
        self.shapes = shapes
        self.control = control
        self.rows = 0
        self.sorted_columns: set = set()
        self.cardinalities: dict = {}
        self.answers = {name: {"groups": {}, "matched": 0} for name in shapes}
        self._first = True

    def shape_bytes(self, name: str) -> int:
        return shape_bytes(self.shapes[name], self.answers[name], self.rows,
                           self.cardinalities, self.sorted_columns)

    def add(self, segment) -> None:
        decoded: dict = {}

        def values(col):
            return np.asarray(segment.column(col).dictionary.values)

        def ids(col):
            return segment.column(col).fwd

        def numeric(col):  # the column's row values as float64
            if col not in decoded:
                v = values(col).astype(np.float64)
                if self.control == "bfloat16":
                    v = round_bfloat16(v)
                decoded[col] = v[ids(col)]
            return decoded[col]

        n = len(ids(next(iter(segment.columns))))
        self.rows += n
        is_sorted = {c for c in segment.columns if segment.column(c).metadata.is_sorted}
        self.sorted_columns = is_sorted if self._first else self.sorted_columns & is_sorted
        self._first = False
        for c in segment.columns:
            self.cardinalities[c] = max(self.cardinalities.get(c, 0), len(values(c)))
        for name, shape in self.shapes.items():
            mask = None
            for col, op, arg in shape.get("filter", []):
                m = _OPS[op](values(col), arg)[ids(col)]
                mask = m if mask is None else mask & m
            group_cols = shape.get("group_by", [])
            code = np.zeros(n, dtype=np.int64)
            size = 1
            for col in group_cols:
                card = len(values(col))
                code = code * card + ids(col)
                size *= card
            if mask is not None:
                code = code[mask]
            counts = np.bincount(code, minlength=size)
            per_agg = []
            for fn, col in shape["aggs"]:
                if fn == "count":
                    per_agg.append(counts)
                elif fn == "sum":
                    w = numeric(col)
                    per_agg.append(np.bincount(code, weights=w if mask is None else w[mask], minlength=size))
                else:
                    raise ValueError(f"shape {name}: the reference has no aggregate {fn!r}")
            ans = self.answers[name]
            ans["matched"] += int(counts.sum())
            for c in np.nonzero(counts)[0]:
                key, rest = [], int(c)
                for col in reversed(group_cols):
                    vals = values(col)
                    key.append(str(vals[rest % len(vals)]))
                    rest //= len(vals)
                acc = ans["groups"].setdefault(tuple(reversed(key)), [0] * len(per_agg))
                for i, (fn, _) in enumerate(shape["aggs"]):
                    if fn == "count":
                        acc[i] += int(per_agg[i][c])
                    elif self.control == "bfloat16":
                        part = round_bfloat16(per_agg[i][c : c + 1])[0]
                        acc[i] = float(round_bfloat16(np.array([acc[i] + part]))[0])
                    else:
                        acc[i] += float(per_agg[i][c])


def reply_groups(reply: dict, shape: dict) -> list:
    """Per aggregate, ``{key tuple: value}`` as the reply gives it."""
    out = []
    for res in reply["aggregationResults"]:
        if shape.get("group_by"):
            out.append({tuple(g["group"]): float(g["value"]) for g in res["groupByResult"]})
        else:
            out.append({(): float(res["value"])})
    return out


def compare(reply: dict, shape: dict, answer: dict, rows: int) -> dict:
    """Every number compared for one reply.  ``sum_gap`` is the widest
    relative gap of a float sum (limit: the configuration's
    ``sum_rtol``); the others are counts of faults and have the limit 0:
    ``count_errors`` (a count, ``numDocsScanned`` or ``totalDocs`` off by
    any amount), ``key_errors`` (a group missing, unknown, or returned in
    place of a better one), ``reply_errors`` (exception, partial, a
    segment unserved, a server silent, or ``segmentsHost`` above 0: the
    engine heals a device failure by answering from the host, which is a
    right answer from the wrong place).  Which tier answers is the
    program's choice and is not held."""
    out = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
    if (
        reply.get("exceptions")
        or reply.get("partialResponse")
        or reply.get("numSegmentsUnserved", 0)
        or reply.get("numServersResponded") != reply.get("numServersQueried")
        or (reply.get("cost") or {}).get("segmentsHost", 0)
    ):
        out["reply_errors"] += 1
        return out
    if reply.get("numDocsScanned") != answer["matched"] or reply.get("totalDocs") != rows:
        out["count_errors"] += 1
    groups = answer["groups"]
    top = shape.get("top") if shape.get("group_by") else None
    got = reply_groups(reply, shape)
    if len(got) != len(shape["aggs"]):
        out["reply_errors"] += 1
        return out
    for i, (fn, _) in enumerate(shape["aggs"]):
        want = {k: v[i] for k, v in groups.items()}
        expect_n = len(want) if top is None else min(top, len(want))
        if len(got[i]) != expect_n or any(k not in want for k in got[i]):
            out["key_errors"] += 1
            continue
        for key, value in got[i].items():
            if fn == "count":
                out["count_errors"] += int(int(value) != want[key])
            else:
                out["sum_gap"] = max(out["sum_gap"], abs(value - want[key]) / max(1.0, abs(want[key])))
        if top is not None and len(want) > expect_n:
            # TOP n: the worst group returned may not lie under the best
            # one left out by more than float32 could mistake them
            worst = min(want[k] for k in got[i])
            left_out = max(v for k, v in want.items() if k not in got[i])
            gap = (left_out - worst) / max(1.0, abs(left_out))
            if fn == "count":
                out["key_errors"] += int(gap > 0)
            else:
                out["sum_gap"] = max(out["sum_gap"], gap)
    return out


def control_gaps(reference: Reference, control: Reference) -> dict:
    """Per shape, the ``sum_gap`` the control would show as a reply."""
    gaps = {}
    for name, shape in reference.shapes.items():
        gap = 0.0
        for key, want in reference.answers[name]["groups"].items():
            got = control.answers[name]["groups"][key]
            for i, (fn, _) in enumerate(shape["aggs"]):
                if fn == "sum":
                    gap = max(gap, abs(got[i] - want[i]) / max(1.0, abs(want[i])))
        gaps[name] = gap
    return gaps


def id_bytes(cardinality: int) -> int:
    """Bytes of a dictionary id at the narrowest of 1, 2 and 4."""
    return 1 if cardinality <= 1 << 8 else 2 if cardinality <= 1 << 16 else 4


def shape_bytes(shape: dict, answer: dict, rows: int, cardinalities: dict, sorted_columns: set) -> int:
    """The least a shape has to read: one dictionary id per touched
    column per row.  A filter on sorted columns alone is a binary search,
    so only the matched rows are read and the filter columns not at all.
    A lower bound by construction: a share of the roofline worked out
    from it cannot pass 100% unless time is missing from the trace."""
    filter_cols = {col for col, _, _ in shape.get("filter", [])}
    touched = set(shape.get("group_by", [])) | {col for fn, col in shape["aggs"] if fn != "count"}
    need = rows
    if filter_cols and filter_cols <= sorted_columns:
        need = answer["matched"]
    else:
        touched |= filter_cols
    return need * sum(id_bytes(cardinalities[c]) for c in touched)
