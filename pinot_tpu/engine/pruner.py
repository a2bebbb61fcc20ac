"""Segment pruning before planning.

Reference: pinot-core ``query/pruner/`` —
``DataSchemaSegmentPruner`` (drop segments missing referenced columns),
``ValidSegmentPruner`` (drop empty segments), ``TimeSegmentPruner``
(drop segments whose [startTime, endTime] cannot match the query's
time-column predicate).  Those three drop a segment from the query
(``prune_segments``).

The fourth verdict is this system's: the value pruner (later upstream
versions grew a column-value pruner; v0.016 has none).  A segment is
*value-dead* when its filter tree cannot match a row of it, decided from
the segment's own sorted dictionaries, exactly (``value_dead``).  A
value-dead segment is NOT dropped from the query: it stays among the
segments that are the table's identity (the staged table, the table
context, ``totalDocs``) and is left out of the work
(``scanned_segments``: the positions every tier is handed).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from pinot_tpu.common.request import BrokerRequest, FilterOperator, FilterQueryTree
from pinot_tpu.segment.immutable import ImmutableSegment


def _time_bounds(
    tree: Optional[FilterQueryTree], time_column: str
) -> Optional[Tuple[float, float]]:
    """Conservative [lo, hi] the time column must intersect, from
    top-level AND / single-leaf predicates only."""
    if tree is None:
        return None
    leaves: List[FilterQueryTree] = []
    if tree.is_leaf:
        leaves = [tree]
    elif tree.operator == FilterOperator.AND:
        leaves = [c for c in tree.children if c.is_leaf]
    lo, hi = float("-inf"), float("inf")
    found = False
    for leaf in leaves:
        if leaf.column != time_column:
            continue
        try:
            if leaf.operator == FilterOperator.EQUALITY:
                v = float(leaf.values[0])
                lo, hi = max(lo, v), min(hi, v)
                found = True
            elif leaf.operator == FilterOperator.RANGE and leaf.range_spec:
                r = leaf.range_spec
                if r.lower not in (None, "*"):
                    lo = max(lo, float(r.lower))
                if r.upper not in (None, "*"):
                    hi = min(hi, float(r.upper))
                found = True
            elif leaf.operator == FilterOperator.IN:
                vs = [float(v) for v in leaf.values]
                lo, hi = max(lo, min(vs)), min(hi, max(vs))
                found = True
        except ValueError:
            continue
    return (lo, hi) if found else None


def _leaf_dead(seg: ImmutableSegment, leaf: FilterQueryTree) -> Optional[str]:
    """Why no value of the segment's dictionary passes ``leaf``, or None
    where one does or the leaf is not judged.  The answer is the plan's
    own (``plan.leaf_points`` finds no id, ``plan.leaf_interval`` is
    empty), reached without it for a literal outside the column's
    [min, max]: two comparisons, and the dictionary is searched only for
    a literal inside them."""
    if leaf.operator not in (FilterOperator.EQUALITY, FilterOperator.IN, FilterOperator.RANGE):
        return None  # NOT, NOT IN, REGEX: alive
    col = seg.columns.get(leaf.column)
    if col is None or not col.metadata.single_value:
        return None  # a multi-value column: alive
    d, meta = col.dictionary, col.metadata
    if d.cardinality == 0:
        return None
    lo = d.min_value if meta.min_value is None else meta.min_value
    hi = d.max_value if meta.max_value is None else meta.max_value
    stored = d.stored_type
    try:
        if leaf.operator == FilterOperator.RANGE:
            from pinot_tpu.engine.plan import leaf_interval

            r = leaf.range_spec
            if r is None:
                return None
            lower = None if r.lower in (None, "*") else stored.convert(r.lower)
            upper = None if r.upper in (None, "*") else stored.convert(r.upper)
            outside = (upper is not None and (upper < lo or (upper == lo and not r.include_upper))) or (
                lower is not None and (lower > hi or (lower == hi and not r.include_lower)))
            if not outside:
                first, last = leaf_interval(leaf, d)
                if last > first:
                    return None
            what = ("(" if not r.include_lower else "[") + f"{r.lower},{r.upper}" + (")" if not r.include_upper else "]")
            test = f"{leaf.column} in {what}"
        else:
            for v in leaf.values:
                v = stored.convert(v)
                if lo <= v <= hi and d.index_of(v) >= 0:
                    return None
            test = f"{leaf.column} {'=' if leaf.operator == FilterOperator.EQUALITY else 'IN'} {list(leaf.values)}"
    except (TypeError, ValueError):
        return None  # a literal the column's type does not take: the plan says so
    return f"no value of {leaf.column} in [{lo},{hi}] passes {test}"


def value_dead(seg: ImmutableSegment, tree: Optional[FilterQueryTree]) -> Optional[str]:
    """Why ``tree`` can match no row of ``seg``, or None where it may: a
    leaf as ``_leaf_dead`` judges it, AND dead when any child is, OR when
    every child is, anything else alive.  Exact, from the segment's own
    dictionaries: a dead segment holds no matching row, so leaving it out
    changes no answer.  Every segment a query sees qualifies, a consuming
    one too: its view is a snapshot at a watermark, an ``ImmutableSegment``
    with a sorted dictionary of (at least) the snapshot's values and a
    staging token of its own."""
    if tree is None:
        return None
    if tree.is_leaf:
        return _leaf_dead(seg, tree)
    if tree.operator == FilterOperator.AND:
        for child in tree.children:
            why = value_dead(seg, child)
            if why is not None:
                return why
        return None
    if tree.operator == FilterOperator.OR:
        whys = [value_dead(seg, child) for child in tree.children]
        return "; ".join(whys) if whys and all(w is not None for w in whys) else None
    return None


def _prune_reason(
    seg: ImmutableSegment, request: BrokerRequest, needed: Sequence[str]
) -> Optional[str]:
    """Why upstream's three pruners drop this segment from the query, or
    None to keep it: the ONE verdict prune_segments and the EXPLAIN
    decision records share.  The value verdict is ``value_reason``, over
    what these three keep."""
    if seg.num_docs == 0:  # ValidSegmentPruner
        return "empty segment (ValidSegmentPruner)"
    missing = [c for c in needed if not seg.has_column(c)]
    if missing:  # DataSchemaSegmentPruner
        return f"missing columns {missing} (DataSchemaSegmentPruner)"
    meta = seg.metadata
    if meta.time_column and meta.start_time is not None and meta.end_time is not None:
        bounds = _time_bounds(request.filter, meta.time_column)
        if bounds is not None:
            lo, hi = bounds
            if meta.end_time < lo or meta.start_time > hi:
                return (
                    f"time range [{meta.start_time},{meta.end_time}] outside "
                    f"predicate [{lo},{hi}] (TimeSegmentPruner)"
                )
    return None


def value_reason(seg: ImmutableSegment, request: BrokerRequest) -> Optional[str]:
    """``value_dead``'s answer as a decision record's reason: the leaf,
    the column and the segment's [min, max]."""
    why = value_dead(seg, request.filter)
    return None if why is None else f"{why} (ValueSegmentPruner)"


def prune_explain(
    segments: Sequence[ImmutableSegment], request: BrokerRequest
) -> List[Tuple[ImmutableSegment, Optional[str]]]:
    """Per-segment pruning decisions WITH reasons (EXPLAIN's
    ``decisions.pruned`` records), upstream's three verdicts and the
    value verdict over what they keep: the same verdicts as
    ``prune_segments`` and ``scanned_segments``, so the explained set can
    never drift from the executed one."""
    needed = request.referenced_columns()
    return [(seg, _prune_reason(seg, request, needed) or value_reason(seg, request)) for seg in segments]


def prune_segments(
    segments: Sequence[ImmutableSegment], request: BrokerRequest
) -> List[ImmutableSegment]:
    """The segments upstream's three pruners keep: the query's segments,
    value-dead ones among them."""
    needed = request.referenced_columns()
    return [
        seg for seg in segments if _prune_reason(seg, request, needed) is None
    ]


def scanned_segments(live: Sequence[ImmutableSegment], request: BrokerRequest) -> Tuple[int, ...]:
    """The positions in ``live`` (``prune_segments``' answer) of the
    segments the filter can match: what every tier iterates, decides and
    launches over.  A function of the query's literals and the segments'
    tokens alone, so the executor keeps it with the prepared query."""
    return tuple(i for i, seg in enumerate(live) if value_dead(seg, request.filter) is None)
