"""The plain reference of ``tpch_lineitem_spec_1chip``: TPC-H Q1 and Q6 as
the specification writes them, with arithmetic inside an aggregate.

The interface is ``reference.py``'s, which ``run.py`` calls: a shape is
data (``traffic/<name>.json``), ``render_pql`` turns it into the PQL the
client sends, ``Reference`` answers it with numpy over the segments'
dictionaries and forward indexes, one segment at a time, in float64 and
python ints; ``compare`` holds a reply to that answer and returns every
number compared; ``shape_bytes`` says how many bytes the shape has to
read at the least.  Nothing here imports the program or ``reference.py``:
the segment is read through ``segment.column(name).dictionary.values``,
``.fwd`` and ``.metadata.is_sorted`` only.

What this module adds: a shape's aggregate is ``["sum" | "avg" |
"count", <column, "*", or {"expr": "<text>"}>]``.  The expression is
parsed by the few lines below (names, numbers, ``+ - *``, unary minus,
parentheses; ``*`` binds tighter, operators of one rank from the left)
and evaluated on the float64 row values; ``avg`` is its sum over the
matched count.

Tolerance.  The configuration states it: counts, keys and
``numDocsScanned`` exact, a float sum or average within ``sum_rtol`` of
the float64 reference, relative to the reference's value (the
denominator held at 1 at the least, as ``reference.py`` holds it: a
reply carries five decimals, which is 1e-4 of ``avg(l_discount)``).  The
program multiplies and adds in float32 from float32 row values; the
control is this class with ``control="bfloat16"``, the next precision
down: column values, every product and sum inside an expression, and a
segment's stored sums are rounded to bfloat16, and the segments' sums
are merged in bfloat16.  ``PERF.md`` gives both readings.
"""
from __future__ import annotations

import re

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
_TOKEN = re.compile(r"\s*(?:(\d+\.?\d*(?:[eE][-+]?\d+)?|\.\d+)|([A-Za-z_][A-Za-z0-9_]*)|([-+*()]))")


def parse_expr(text: str) -> tuple:
    """``"a*(1-b)"`` -> ``("*", ("col", "a"), ("-", ("lit", 1.0), ("col", "b")))``."""
    tokens, pos = [], 0
    while pos < len(text.rstrip()):
        m = _TOKEN.match(text, pos)
        if m is None:
            raise ValueError(f"expression {text!r}: cannot read {text[pos:]!r}")
        number, name, op = m.groups()
        tokens.append(("lit", float(number)) if number else ("col", name) if name else op)
        pos = m.end()
    tokens.append(None)
    at = [0]

    def take():
        at[0] += 1
        return tokens[at[0] - 1]

    def primary():
        t = take()
        if t == "(":
            inner = terms()
            if take() != ")":
                raise ValueError(f"expression {text!r}: expected ')'")
            return inner
        if t == "-":
            return ("neg", primary())
        if not isinstance(t, tuple):
            raise ValueError(f"expression {text!r}: unexpected {t!r}")
        return t

    def factors():
        left = primary()
        while tokens[at[0]] == "*":
            take()
            left = ("*", left, primary())
        return left

    def terms():
        left = factors()
        while tokens[at[0]] in ("+", "-"):
            left = (take(), left, factors())
        return left

    tree = terms()
    if tokens[at[0]] is not None:
        raise ValueError(f"expression {text!r}: unexpected {tokens[at[0]]!r}")
    return tree


def expr_columns(tree: tuple) -> set:
    if tree[0] == "col":
        return {tree[1]}
    return set().union(*(expr_columns(c) for c in tree[1:] if isinstance(c, tuple)))


def eval_expr(tree: tuple, column, rounded=lambda x: x):
    """The tree over row values: ``column(name)`` gives a leaf's float64
    values, ``rounded`` is applied to every intermediate (the control's
    precision; the reference's is float64 and rounds nothing)."""
    op = tree[0]
    if op == "col":
        return column(tree[1])
    if op == "lit":
        return rounded(np.float64(tree[1]))
    if op == "neg":
        return -eval_expr(tree[1], column, rounded)
    a, b = eval_expr(tree[1], column, rounded), eval_expr(tree[2], column, rounded)
    return rounded(a + b if op == "+" else a - b if op == "-" else a * b)


def argument(arg) -> tuple:
    """An aggregate's second entry as a tree; None for ``*``."""
    if arg == "*":
        return None
    return parse_expr(arg["expr"]) if isinstance(arg, dict) else ("col", arg)


def _literal(x) -> str:
    return f"'{x}'" if isinstance(x, str) else repr(x)


def render_pql(table: str, shape: dict) -> str:
    """The query text of a shape, as the specification writes it."""

    def call(fn, arg):
        if fn == "count":
            return "count(*)"
        return f"{fn}({arg['expr'] if isinstance(arg, dict) else arg})"

    pql = f"SELECT {', '.join(call(fn, arg) for fn, arg in shape['aggs'])} FROM {table}"
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


def round_bfloat16(values) -> np.ndarray:
    """float64 -> nearest-even bfloat16 -> float64, in plain numpy."""
    bits = np.asarray(values, dtype=np.float64).astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


class Reference:
    """Answers of every shape over the segments given to ``add``.

    ``answers[shape]`` is ``{"groups": {key tuple: [a value an
    aggregate]}, "counts": {key tuple: matched rows}, "matched": rows,
    "sorted_matched": rows that pass the filter's sorted columns}``; an
    ungrouped shape has the one key ``()``.  A ``sum`` and an ``avg``
    are kept as sums (float64; an ``avg`` is divided by the group's
    count where it is compared), a ``count`` as a python int."""

    def __init__(self, shapes: dict, control: str = "") -> None:
        if control not in ("", "bfloat16"):
            raise ValueError(f"unknown control {control!r}")
        self.shapes = shapes
        self.control = control
        self.rows = 0
        self.sorted_columns: set = set()
        self.cardinalities: dict = {}
        self.answers = {name: {"groups": {}, "counts": {}, "matched": 0, "sorted_matched": 0} for name in shapes}
        self._first = True

    def shape_bytes(self, name: str) -> int:
        return shape_bytes(self.shapes[name], self.answers[name], self.rows,
                           self.cardinalities, self.sorted_columns)

    def add(self, segment) -> None:
        decoded: dict = {}
        rounded = round_bfloat16 if self.control == "bfloat16" else (lambda x: x)

        def values(col):
            return np.asarray(segment.column(col).dictionary.values)

        def ids(col):
            return segment.column(col).fwd

        def numeric(col):  # the column's row values as float64 (the control's: rounded)
            if col not in decoded:
                decoded[col] = rounded(values(col).astype(np.float64))[ids(col)]
            return decoded[col]

        n = len(ids(next(iter(segment.columns))))
        self.rows += n
        is_sorted = {c for c in segment.columns if segment.column(c).metadata.is_sorted}
        self.sorted_columns = is_sorted if self._first else self.sorted_columns & is_sorted
        self._first = False
        for c in segment.columns:
            self.cardinalities[c] = max(self.cardinalities.get(c, 0), len(values(c)))
        for name, shape in self.shapes.items():
            mask = by_sorted = None
            for col, op, arg in shape.get("filter", []):
                m = _OPS[op](values(col), arg)[ids(col)]
                mask = m if mask is None else mask & m
                if col in is_sorted:
                    by_sorted = m if by_sorted is None else by_sorted & m
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
            per_agg, sums = [], {}  # one pass an argument: Q1's sum and avg of a column read the same sums
            for fn, arg in shape["aggs"]:
                if fn == "count":
                    per_agg.append(counts)
                elif fn in ("sum", "avg"):
                    if repr(arg) not in sums:
                        w = eval_expr(argument(arg), numeric, rounded)
                        sums[repr(arg)] = np.bincount(code, weights=w if mask is None else w[mask], minlength=size)
                    per_agg.append(sums[repr(arg)])
                else:
                    raise ValueError(f"shape {name}: the reference has no aggregate {fn!r}")
            ans = self.answers[name]
            ans["matched"] += int(counts.sum())
            ans["sorted_matched"] += n if by_sorted is None else int(by_sorted.sum())
            for c in np.nonzero(counts)[0]:
                key, rest = [], int(c)
                for col in reversed(group_cols):
                    vals = values(col)
                    key.append(str(vals[rest % len(vals)]))
                    rest //= len(vals)
                key = tuple(reversed(key))
                ans["counts"][key] = ans["counts"].get(key, 0) + int(counts[c])
                acc = ans["groups"].setdefault(key, [0] * len(per_agg))
                for i, (fn, _) in enumerate(shape["aggs"]):
                    if fn == "count":
                        acc[i] += int(per_agg[i][c])
                    elif self.control == "bfloat16":
                        part = round_bfloat16(per_agg[i][c : c + 1])[0]
                        acc[i] = float(round_bfloat16(np.array([acc[i] + part]))[0])
                    else:
                        acc[i] += float(per_agg[i][c])


def wanted(shape: dict, answer: dict) -> list:
    """Per aggregate, ``{key tuple: value}`` as a reply should give it:
    an ``avg`` is its sum over the group's matched count."""
    out = []
    for i, (fn, _) in enumerate(shape["aggs"]):
        if fn == "avg":
            out.append({k: v[i] / answer["counts"][k] for k, v in answer["groups"].items()})
        else:
            out.append({k: v[i] for k, v in answer["groups"].items()})
    return out


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
    """Every number compared for one reply, under the four names
    ``run.py judge`` reads.  ``sum_gap`` is the widest relative gap of a
    float sum or average (limit: the configuration's ``sum_rtol``); the
    others are counts of faults and have the limit 0: ``count_errors``
    (a count, ``numDocsScanned`` or ``totalDocs`` off by any amount),
    ``key_errors`` (a group missing, unknown, or returned in place of a
    better one), ``reply_errors`` (exception, partial, a segment
    unserved, a server silent, a result column missing, or
    ``segmentsHost`` above 0: a healed answer from the host is a right
    answer from the wrong place).  Which device tier answers is the
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
    top = shape.get("top") if shape.get("group_by") else None
    got = reply_groups(reply, shape)
    if len(got) != len(shape["aggs"]):
        out["reply_errors"] += 1
        return out
    for (fn, _), have, want in zip(shape["aggs"], got, wanted(shape, answer)):
        expect_n = len(want) if top is None else min(top, len(want))
        if len(have) != expect_n or any(k not in want for k in have):
            out["key_errors"] += 1
            continue
        for key, value in have.items():
            if fn == "count":
                out["count_errors"] += int(int(value) != want[key])
            else:
                out["sum_gap"] = max(out["sum_gap"], abs(value - want[key]) / max(1.0, abs(want[key])))
        if top is not None and len(want) > expect_n:
            # TOP n: the worst group returned may not lie under the best
            # one left out by more than float32 could mistake them
            worst = min(want[k] for k in have)
            left_out = max(v for k, v in want.items() if k not in have)
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
        want = wanted(shape, reference.answers[name])
        have = wanted(shape, control.answers[name])
        for (fn, _), w, h in zip(shape["aggs"], want, have):
            if fn != "count":
                gap = max([gap] + [abs(h[k] - v) / max(1.0, abs(v)) for k, v in w.items()])
        gaps[name] = gap
    return gaps


def id_bytes(cardinality: int) -> int:
    """Bytes of a dictionary id at the narrowest of 1, 2 and 4."""
    return 1 if cardinality <= 1 << 8 else 2 if cardinality <= 1 << 16 else 4


def shape_bytes(shape: dict, answer: dict, rows: int, cardinalities: dict, sorted_columns: set) -> int:
    """The least a shape has to read: one dictionary id a row for every
    distinct column its keys, its filter and all its expressions touch,
    each column once however many aggregates read it.  A shape's filter
    is a conjunction, and its leaves on sorted columns are a binary
    search: only the rows that pass them are read, and those columns
    not at all.  A lower bound by construction: a share of the roofline
    worked out from it cannot pass 100% unless time is missing from the
    trace."""
    touched = set(shape.get("group_by", []))
    for fn, arg in shape["aggs"]:
        if fn != "count":
            touched |= expr_columns(argument(arg))
    touched |= {col for col, _, _ in shape.get("filter", []) if col not in sorted_columns}
    return answer["sorted_matched"] * sum(id_bytes(cardinalities[c]) for c in touched)
