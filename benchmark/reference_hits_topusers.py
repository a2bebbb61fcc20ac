"""The plain reference of ``clickbench_hits_topusers_1chip``: ClickBench's
``queries.sql`` line 16, ``SELECT UserID, COUNT(*) FROM hits GROUP BY
UserID ORDER BY COUNT(*) DESC LIMIT 10``, as PQL's ``SELECT COUNT(*) FROM
hits GROUP BY UserID TOP 10``: the ten users with most rows of 17.6M.

The interface is the other references', which ``run.py`` calls
(``render_pql``, ``Reference.add/answers/rows/shape_bytes``, ``compare``,
``control_gaps``); the query text is ``reference_tpch_spec``'s, loaded from
the file beside this one.  numpy alone; nothing here imports the program.

A segment is answered by itself: ``np.unique(values, return_counts=True)``
over the key column's OWN values (its dictionary's values through its
forward index); the segments' pairs are folded into one pair ``keys`` (the
values seen, ascending) and ``counts`` (int64) **by value**: the program's
table dictionary, its remaps and its global ids are never looked at.

``answers[shape]`` is ``{"keys", "counts", "matched"}``.  ``compare`` holds
a reply of ``TOP n`` to it exactly: the users returned are ``n`` distinct
live users, each with its own count as an integer (``count_errors``; a
value that is no integer counts too), in descending order, and no user
left out has more rows than one returned (``key_errors``: a wrong user
under a right count is an unknown key or a count that is not that
user's); and to what the server took from ALL its groups, since a reply
shows ten of 17.6M: ``numGroupsLive`` to the count of distinct users,
exact (``count_errors``), and ``groupStateSumSq`` to the sum of squares of
every user's count (an integer far under 2^53, so float64 holds it exact)
under ``sum_rtol`` (``sum_gap``): a count dropped, or a user's rows split
in two, under a right top ten fails there.  With more than one answering
server a user may be live on several, so the live count is held between
the reference's and that times the servers and the digest is not held.

The shape has no float to round, so the control is no lower precision:
``control="drop_rank<r>"`` (``run.py --control drop_rank11``) answers with
the FIRST segment's rows of one user left out, the user of rank ``r`` by
rows in the whole table (``drop_rank11`` is the first user a reply of TOP
10 does not show; ``drop_rank100`` the last of the top hundred), and
``control_gaps`` says what that state's digest reads against the
reference's: it has to come out over ``sum_rtol`` (``PERF.md`` section 2).
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


render_pql = _beside("reference_tpch_spec").render_pql
ID_BYTES = 4  # one id of a 17.6M-value dictionary a row: the least the query reads
DROP = "drop_rank"


class Reference:
    """Answers of every shape over the segments given to ``add``."""

    def __init__(self, shapes: dict, control: str = "") -> None:
        if control and not (control.startswith(DROP) and control[len(DROP):].isdigit() and int(control[len(DROP):]) > 0):
            raise ValueError(f"unknown control {control!r}: the shape has no float to round; drop_rank<r> is its control")
        for name, shape in shapes.items():
            if len(shape.get("group_by", [])) != 1 or shape.get("filter") or [fn for fn, _ in shape["aggs"]] != ["count"]:
                raise ValueError(f"shape {name}: this reference answers count(*) by one column, unfiltered")
        self.shapes = shapes
        self.control = control
        self.rows = 0
        self._parts: dict = {name: [] for name in shapes}  # shape -> a (keys, counts) pair a segment
        self._matched: dict = {name: 0 for name in shapes}
        self._answers: dict = {}

    def shape_bytes(self, name: str) -> int:
        """The least the shape has to read: the key's id, a row."""
        return self.rows * ID_BYTES

    def add(self, segment) -> None:
        """A segment answered by itself; ``answers`` folds the segments'
        pairs together when it is first read (``run.py`` reads it after
        its timing has stopped, so the fold is in no metric)."""
        n = None
        for name, shape in self.shapes.items():
            column = segment.column(shape["group_by"][0])
            values = np.asarray(column.dictionary.values)[column.fwd]  # the column's own values, a row each
            n = values.size
            keys, counts = np.unique(values, return_counts=True)
            self._parts[name].append((keys.astype(np.int64), counts.astype(np.int64)))
            self._matched[name] += n
        self._answers = {}
        self.rows += n or 0

    @property
    def answers(self) -> dict:
        if not self._answers:
            for name, parts in self._parts.items():
                keys = np.concatenate([k for k, _ in parts]) if parts else np.empty(0, dtype=np.int64)
                counts = np.concatenate([c for _, c in parts]) if parts else np.empty(0, dtype=np.int64)
                merged, where = np.unique(keys, return_inverse=True)
                total = np.bincount(where, weights=counts, minlength=merged.size).astype(np.int64)  # far under 2^53: exact
                self._answers[name] = {"keys": merged, "counts": total, "matched": self._matched[name]}
                self._answers[name]["digest"] = state_digest(self._answers[name])  # once, not a reply
        return self._answers

    def dropped(self, name: str) -> dict:
        """The control's answer: this reference's, less the first
        segment's rows of the user of rank r (1 the heaviest; ties by
        the smaller id).  A user the first segment does not hold loses
        nothing, and the control reads no gap: choose another rank."""
        ans = self.answers[name]
        rank = int(self.control[len(DROP):])
        order = np.lexsort((ans["keys"], -ans["counts"]))
        user = ans["keys"][order[min(rank, order.size) - 1]]
        counts = ans["counts"].copy()
        first_keys, first_counts = self._parts[name][0]
        at = np.searchsorted(first_keys, user)
        if at < first_keys.size and first_keys[at] == user:
            counts[np.searchsorted(ans["keys"], user)] -= first_counts[at]
        return {"keys": ans["keys"], "counts": counts, "matched": ans["matched"]}


def state_digest(answer: dict) -> dict:
    """What a server's cost vector says of all its groups, from the
    reference's: the live users and the sum of their squared counts."""
    live = answer["counts"][answer["counts"] > 0]
    return {"numGroupsLive": int(live.size), "groupStateSumSq": int(np.sum(live * live))}


def _state_gaps(out: dict, cost: dict, servers: int, answer: dict) -> None:
    want = answer.get("digest") or state_digest(answer)
    have = cost.get("numGroupsLive", 0)
    if servers != 1:
        out["count_errors"] += int(not want["numGroupsLive"] <= have <= servers * want["numGroupsLive"])
        return
    out["count_errors"] += int(have != want["numGroupsLive"])
    gap = abs(float(cost.get("groupStateSumSq", 0.0)) - want["groupStateSumSq"]) / max(1.0, float(want["groupStateSumSq"]))
    out["sum_gap"] = max(out["sum_gap"], gap)


def _reply_gaps(out: dict, keys: np.ndarray, values: np.ndarray, answer: dict, top: int) -> None:
    """A reply's groups, ``keys`` (int64) and ``values`` (float64) in the
    order returned, against the answer."""
    live = answer["counts"] > 0
    at = np.minimum(np.searchsorted(answer["keys"], keys), max(answer["keys"].size - 1, 0))
    known = answer["keys"][at] == keys if answer["keys"].size else np.zeros(keys.size, dtype=bool)
    if keys.size != min(top, int(live.sum())) or not known.all() or not live[at].all() or np.unique(at).size != at.size:
        out["key_errors"] += 1
        return
    if at.size == 0:
        return
    want = answer["counts"][at]
    whole = values == np.rint(values)
    out["count_errors"] += int(np.count_nonzero(~whole) + np.count_nonzero(np.rint(values).astype(np.int64) != want))
    out["key_errors"] += int(np.any(np.diff(values) > 0))  # ORDER BY COUNT(*) DESC
    # a user left out beats one returned: more users of the table lie over the least count returned than the reply holds
    out["key_errors"] += int(np.count_nonzero(answer["counts"] > want.min()) > np.count_nonzero(want > want.min()))


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
    _state_gaps(out, cost, reply.get("numServersQueried", 1), answer)
    try:
        groups = results[0].get("groupByResult") or []
        keys = np.asarray([int(g["group"][0]) for g in groups], dtype=np.int64)
        values = np.asarray([float(g["value"]) for g in groups], dtype=np.float64)
    except (KeyError, TypeError, ValueError):  # a result without its value, a key that is no integer
        out["key_errors"] += 1
        return out
    _reply_gaps(out, keys, values, answer, shape["top"])
    return out


def control_gaps(reference: Reference, control: Reference) -> dict:
    """Per shape, the ``sum_gap`` the control would show as a reply: its
    own state's digest held to the reference's (its own TOP n is right
    wherever the dropped user is not among the n)."""
    gaps = {}
    for name in reference.shapes:
        out = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
        _state_gaps(out, state_digest(control.dropped(name)), 1, reference.answers[name])
        gaps[name] = out["sum_gap"]
    return gaps
