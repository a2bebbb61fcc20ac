"""Reduce: merged IntermediateResults -> BrokerResponse.

The ``BrokerReduceService.reduceOnDataTable`` analog
(``core/query/reduce/BrokerReduceService.java:62``): merge per-server
partials, finalize aggregation values, sort + trim group-by results
(ascending iff the function name starts with "min",
``AggregationGroupByOperatorService.java:146``), window + render
selection rows, and sum execution stats.
"""
from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Tuple

from pinot_tpu.common.request import BrokerRequest, group_sort_ascending
from pinot_tpu.common.response import (
    AggregationResult,
    BrokerResponse,
    GroupByResult,
    QueryException,
    SelectionResults,
)
from pinot_tpu.engine.results import IntermediateResult


class _SortKey:
    __slots__ = ("v", "desc")

    def __init__(self, v: Any, desc: bool) -> None:
        self.v = v
        self.desc = desc

    def __lt__(self, other: "_SortKey") -> bool:
        if self.desc:
            return other.v < self.v
        return self.v < other.v

    def __eq__(self, other: object) -> bool:
        return isinstance(other, _SortKey) and self.v == other.v


def merge_results(parts: Sequence[IntermediateResult]) -> Optional[IntermediateResult]:
    parts = [p for p in parts if p is not None]
    if not parts:
        return None
    merged = parts[0]
    for p in parts[1:]:
        merged.merge(p)
    return merged


def reduce_to_response(
    request: BrokerRequest,
    parts: Sequence[IntermediateResult],
    exceptions: Optional[List[QueryException]] = None,
) -> BrokerResponse:
    merged = merge_results(parts)
    resp = BrokerResponse(exceptions=list(exceptions or []))
    if merged is None:
        return resp

    resp.num_docs_scanned = merged.num_docs_scanned
    resp.total_docs = merged.total_docs
    resp.num_segments_queried = merged.num_segments_queried
    resp.num_entries_scanned_in_filter = merged.num_entries_scanned_in_filter
    resp.num_entries_scanned_post_filter = merged.num_entries_scanned_post_filter
    # broker totals == sum of server totals (additive merge invariant)
    resp.cost = dict(merged.cost)
    resp.trace_info = merged.trace

    if request.is_group_by:
        resp.aggregation_results = _reduce_group_by(request, merged)
    elif request.is_aggregation:
        resp.aggregation_results = [
            AggregationResult(function=a.display_name, value=p.finalize())
            for a, p in zip(request.aggregations, merged.aggregations or [])
        ]
    else:
        resp.selection_results = _reduce_selection(request, merged)
    return resp


def _reduce_group_by(request: BrokerRequest, merged: IntermediateResult):
    groups = merged.groups or {}
    out: List[AggregationResult] = []
    gb = request.group_by

    # SQL semantics: HAVING filters GROUPS, so a group failing the
    # predicate disappears from EVERY aggregation's result list, not
    # just the one the predicate mentions.  (optimize_request rejects a
    # predicate naming an unselected aggregation up front.)
    passing = None
    having_idx = -1
    having_vals = {}
    if request.having is not None:
        h = request.having
        for i, agg in enumerate(request.aggregations):
            if h.function == agg.function and (h.column == agg.column or h.column == "*"):
                having_idx = i
                hkeys = list(groups)
                having_vals = dict(
                    zip(hkeys, _batch_finalize([groups[k][i] for k in hkeys]))
                )
                passing = {
                    key
                    for key, v in having_vals.items()
                    if _having_ok(v, h.operator, h.value)
                }
                break

    keys = [k for k in groups if passing is None or k in passing]
    for i, agg in enumerate(request.aggregations):
        if i == having_idx:
            vals = [having_vals[k] for k in keys]
        else:
            vals = _batch_finalize([groups[k][i] for k in keys])
        pairs = list(zip(keys, vals))
        asc = group_sort_ascending(agg.function)
        pairs.sort(key=lambda kv: (kv[1], kv[0]) if asc else (-_num(kv[1]), kv[0]))
        trimmed = pairs[: gb.top_n]
        out.append(
            AggregationResult(
                function=agg.display_name,
                group_by_columns=list(gb.columns),
                group_by_result=[GroupByResult(group=list(k), value=v) for k, v in trimmed],
            )
        )
    return out


def _batch_finalize(partials: List[Any]) -> List[Any]:
    """Per-group finalize, vectorized where the partial type allows:
    a wide HLL group-by pays ~25us of estimator per group when called
    one-by-one; ONE stacked estimate over [G, 256] registers does the
    same math in a single numpy pass (engine/hll.py batch support)."""
    from pinot_tpu.engine import hll as hll_mod
    from pinot_tpu.engine.results import HllPartial

    if len(partials) > 8 and all(type(p) is HllPartial for p in partials):
        import numpy as np

        ests = hll_mod.estimate_from_registers(
            np.stack([p.registers for p in partials])
        )
        return [int(e) for e in np.asarray(ests).ravel()]
    return [p.finalize() for p in partials]


def _num(v: Any) -> float:
    try:
        return float(v)
    except (TypeError, ValueError):
        return -math.inf


def _having_ok(value: Any, op: str, target: float) -> bool:
    v = _num(value)
    if op == "=":
        return v == target
    if op in ("<>", "!="):
        return v != target
    if op == "<":
        return v < target
    if op == ">":
        return v > target
    if op == "<=":
        return v <= target
    if op == ">=":
        return v >= target
    return True


def ordered_selection_rows(sel, rows: List[Tuple[list, list]]) -> List[Tuple[list, list]]:
    """Candidate ``(sort values, row)`` entries in the ORDER BY's order, by
    value (a stable sort: rows tied on the key keep the order they came
    in); as they came where the selection states no order."""
    if not sel.sorts:
        return list(rows)
    descs = [not s.ascending for s in sel.sorts]

    def key(entry: Tuple[list, list]):
        return [_SortKey(v, d) for v, d in zip(entry[0], descs)]

    return sorted(rows, key=key)


def _reduce_selection(request: BrokerRequest, merged: IntermediateResult) -> SelectionResults:
    sel = request.selection
    rows = ordered_selection_rows(sel, merged.selection_rows or [])
    window = rows[sel.offset : sel.offset + sel.size]
    columns = getattr(merged, "selection_columns", None) or _selection_columns(request, window)
    return SelectionResults(columns=columns, rows=[r for _, r in window])


def _selection_columns(request: BrokerRequest, window) -> List[str]:
    cols = request.selection.columns
    if cols and cols != ["*"]:
        return list(cols)
    # '*' with no schema knowledge at reduce: executor attaches names
    return [f"col{i}" for i in range(len(window[0][1]))] if window else []
