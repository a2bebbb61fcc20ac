"""The plain reference of ``clickbench_hits_search_1chip``: ClickBench's
``queries.sql`` lines 25 to 27, ``SELECT SearchPhrase FROM hits WHERE
SearchPhrase <> '' ORDER BY EventTime | SearchPhrase | EventTime,
SearchPhrase LIMIT 10``: a selection under an ORDER BY, the first
reference here that answers one and the first whose ``compare`` must
take every right answer where ties leave more than one.

The interface is the other references', which ``run.py`` calls
(``render_pql``, ``Reference.add/answers/rows/shape_bytes``, ``compare``,
``control_gaps``).  numpy alone; nothing here imports the program.

A shape states ``select`` (the columns returned), ``filter`` (``[column,
op, value]`` with ``=``, ``<>``, ``<``, ``<=``, ``>``, ``>=``, joined by
AND), ``order_by`` (``[column, "asc" | "desc"]``) and ``limit``.

A segment is answered by itself and **by value**: a column's own values
(its dictionary's through its forward index) are ranked by this module's
own sort of them, never by their place in the program's dictionary, and
the program's table dictionary and global ids are never looked at.  Of a
segment's matching rows it keeps those that can still be among the
table's first ``limit``: every row whose key is at or under the
segment's ``limit``-th (``np.partition``), so every row tied at the cut,
as values (integers as int64, strings as numpy strings); the segments'
rows are folded by value when ``answers`` is first read.

``answers[shape]`` is ``{"keys": [...], "values": [...], "matched": n,
"limit": k}``: the sort key and the selected values, a tuple a row, of
every matching row of the table whose key is at or under the ``k``-th, in
the key's order.  ``compare`` holds a reply to the deployment's
``selection`` guarantee, a right answer of the query as SQL reads it:
min(k, matched) rows; read in the order returned, first every row of the
least key (as a multiset: rows tied on the key may come in any order, and
a reply shows the selected columns alone), then every row of the next,
and so on, and the rows left over drawn from the table's rows at the
cut's key, none oftener than the table holds it (``key_errors``: a row
from above the cut, a row out of order, a row missing or repeated all
break it).  ``numDocsScanned`` (the matching rows) and ``totalDocs`` are
exact (``count_errors``); an exception, a partial reply, a segment
answered by the host are ``reply_errors``.  No shape holds a float:
``sum_gap`` is 0.0.

The control is no lower precision: ``control="drop_segment<k>"`` (``run.py
--control drop_segment0``) answers as if the matching rows of the k-th
segment added were absent, and ``control_gaps`` holds that answer, as a
reply, to the reference's: 1.0 for a shape whose rows it changes (the
reply fails by ``key_errors``), else 0.0; ``numDocsScanned`` is off in
every shape (``count_errors``).
"""
from __future__ import annotations

from collections import Counter

import numpy as np

ID_BYTES = 4  # a staged id of a dictionary of 2^16 to 2^31 values
DROP = "drop_segment"
_OPS = {
    "=": np.equal, "<>": np.not_equal, "<": np.less, "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal,
}


def _literal(value) -> str:
    return "'" + value.replace("'", "''") + "'" if isinstance(value, str) else repr(value)


def render_pql(table: str, shape: dict) -> str:
    """The query text of a shape, as ClickBench writes it."""
    pql = f"SELECT {', '.join(shape['select'])} FROM {table}"
    if shape.get("filter"):
        pql += " WHERE " + " AND ".join(f"{col} {op} {_literal(arg)}" for col, op, arg in shape["filter"])
    if shape.get("order_by"):
        pql += " ORDER BY " + ", ".join(col if way == "asc" else f"{col} DESC" for col, way in shape["order_by"])
    return pql + f" LIMIT {shape['limit']}"


def _values(column) -> np.ndarray:
    """A dictionary's values as one numpy array: int64, or numpy strings."""
    values = column.dictionary.values
    if isinstance(values, np.ndarray) and values.dtype.kind in "iu":
        return values.astype(np.int64)
    return np.asarray(values, dtype=np.str_ if len(values) else "U1")


def _dense_ranks(columns: list, descending: list) -> list:
    """Each column's values as their rank among the column's distinct
    values, by value, reversed where the shape orders it descending."""
    out = []
    for values, desc in zip(columns, descending):
        distinct, rank = np.unique(values, return_inverse=True)
        rank = rank.astype(np.int64).reshape(-1)
        out.append(distinct.size - 1 - rank if desc else rank)
    return out


def _at_or_under_cut(ranks: list, k: int) -> np.ndarray:
    """The places of the rows whose key (the columns of ``ranks``, first
    the most significant) is at or under the ``k``-th least, in the key's
    order; every row where there are ``k`` or fewer."""
    order = np.lexsort(tuple(reversed(ranks))) if ranks else np.arange(0)
    if order.size <= k:
        return order
    tied = np.ones(order.size, dtype=bool)
    for r in ranks:
        tied &= r[order] == r[order[k - 1]]
    return order[: int(np.nonzero(tied)[0][-1]) + 1]


class Reference:
    """Answers of every shape over the segments given to ``add``."""

    def __init__(self, shapes: dict, control: str = "") -> None:
        if control and not (control.startswith(DROP) and control[len(DROP):].isdigit()):
            raise ValueError(f"unknown control {control!r}: no shape holds a float to round; drop_segment<k> is the control")
        for name, shape in shapes.items():
            if not shape.get("select") or "limit" not in shape or any(op not in _OPS for _, op, _ in shape.get("filter", [])):
                raise ValueError(f"shape {name}: this reference answers a selection of named columns under a LIMIT")
        self.shapes = shapes
        self.dropped = int(control[len(DROP):]) if control else -1
        self.rows = 0
        self._segments = 0
        self._parts: dict = {name: [] for name in shapes}  # shape -> (key columns, selected columns) a segment
        self._matched: dict = {name: 0 for name in shapes}
        self._answers: dict = {}

    def shape_bytes(self, name: str) -> int:
        """The least the shape has to read: an id of each column of its
        filter and its ORDER BY, a row."""
        shape = self.shapes[name]
        columns = {col for col, _, _ in shape.get("filter", [])} | {col for col, _ in shape.get("order_by", [])}
        return self.rows * ID_BYTES * len(columns)

    def add(self, segment) -> None:
        """A segment answered by itself; ``answers`` folds the segments'
        rows when it is first read (after ``run.py``'s timing has stopped)."""
        cache: dict = {}

        def column(name: str) -> tuple:
            """values, their rank by value, the rows' ids"""
            if name not in cache:
                col = segment.column(name)
                values = _values(col)
                order = np.argsort(values, kind="stable")
                rank = np.empty(order.size, dtype=np.int64)
                rank[order] = np.arange(order.size, dtype=np.int64)
                cache[name] = (values, rank, np.asarray(col.fwd))
            return cache[name]

        n = segment.num_docs
        absent = self._segments == self.dropped
        for name, shape in self.shapes.items():
            mask = np.ones(n, dtype=bool)
            for col, op, arg in shape.get("filter", []):
                values, _, fwd = column(col)
                mask &= _OPS[op](values, arg)[fwd]
            rows = np.nonzero(mask)[0]
            if absent:
                continue
            self._matched[name] += rows.size
            k = shape["limit"]
            order_by = shape.get("order_by", [])
            key = np.zeros(rows.size, dtype=np.int64)
            for col, way in order_by:  # a key of a segment's own ranks: far under 2^63
                values, rank, fwd = column(col)
                r = rank[fwd[rows]]
                key = key * values.size + (values.size - 1 - r if way == "desc" else r)
            if not order_by:
                rows = rows[:k]  # no order stated: any k matching rows are right; the first are kept
            elif rows.size > k:
                rows = rows[key <= np.partition(key, k - 1)[k - 1]]
            by_value = lambda col: column(col)[0][column(col)[2][rows]]  # the kept rows' values of a column
            self._parts[name].append(([by_value(col) for col, _ in order_by], [by_value(col) for col in shape["select"]]))
        self._answers = {}
        self._segments += 1
        self.rows += n

    @property
    def answers(self) -> dict:
        if not self._answers:
            for name, parts in self._parts.items():
                shape = self.shapes[name]
                order_by = shape.get("order_by", [])
                keys = [np.concatenate([p[0][i] for p in parts]) for i in range(len(order_by))] if parts else []
                values = [np.concatenate([p[1][i] for p in parts]) for i in range(len(shape["select"]))] if parts else []
                if order_by:
                    keep = _at_or_under_cut(_dense_ranks(keys, [way == "desc" for _, way in order_by]), shape["limit"])
                else:
                    keep = np.arange(values[0].size if values else 0)
                self._answers[name] = {
                    "keys": list(zip(*[c[keep].tolist() for c in keys])) if keys else [()] * keep.size,
                    "values": list(zip(*[c[keep].tolist() for c in values])) if values else [],
                    "matched": self._matched[name],
                    "limit": shape["limit"],
                }
        return self._answers


def _rows_right(got: list, answer: dict, ordered: bool) -> bool:
    """Whether ``got`` (a tuple of the selected values a row, in the
    order returned) is a right answer: the module's text says how."""
    n = min(answer["limit"], answer["matched"])
    if len(got) != n:
        return False
    if not ordered:  # no ORDER BY: any n matching rows; the reference keeps too few of them to say more
        return True
    keys, values = answer["keys"], answer["values"]
    at = 0
    while at < n:
        end = at
        while end < len(keys) and keys[end] == keys[at]:
            end += 1
        if end == at:  # fewer rows kept than the reply must hold: no answer of this reference's table
            return False
        have, asked = Counter(values[at:end]), Counter(got[at : min(end, n)])
        if end <= n and have != asked or any(asked[v] > have[v] for v in asked):
            return False
        at = end
    return True


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
    selection = reply.get("selectionResults")
    if not isinstance(selection, dict) or list(selection.get("columns") or []) != list(shape["select"]):
        out["reply_errors"] += 1
        return out
    try:
        got = [tuple(str(v) for v in row) for row in selection.get("results") or []]
    except TypeError:  # a row that is no list
        out["key_errors"] += 1
        return out
    want = dict(answer, values=[tuple(str(v) for v in row) for row in answer["values"]])
    if any(len(row) != len(shape["select"]) for row in got) or not _rows_right(got, want, bool(shape.get("order_by"))):
        out["key_errors"] += 1
    return out


def canonical_reply(answer: dict, shape: dict, rows: int) -> dict:
    """The reference's own first ``limit`` rows as a broker's reply: what
    ``control_gaps`` and the tests hold to another answer."""
    n = min(answer["limit"], answer["matched"])
    return {
        "selectionResults": {"columns": list(shape["select"]), "results": [list(row) for row in answer["values"][:n]]},
        "exceptions": [], "numDocsScanned": answer["matched"], "totalDocs": rows,
        "numServersQueried": 1, "numServersResponded": 1, "partialResponse": False, "cost": {},
    }


def control_gaps(reference: Reference, control: Reference) -> dict:
    """Per shape, 1.0 where the control's rows, as a reply, are no right
    answer of the reference's table (``key_errors``), else 0.0."""
    gaps = {}
    for name, shape in reference.shapes.items():
        got = compare(canonical_reply(control.answers[name], shape, reference.rows), shape, reference.answers[name],
                      reference.rows)
        gaps[name] = float(got["key_errors"] > 0)
    return gaps
