"""Selective-query fast path: host postings instead of a device scan.

The reference picks its filter operator per predicate by selectivity:
``BitmapBasedFilterOperator.java:34`` walks the inverted index in
O(matches); ``ScanBasedFilterOperator.java:38`` scans.  This module is
that dispatch re-cut for TPU economics: the device scan path streams
billions of rows a second but costs a dispatch and a result fetch; for a
predicate matching a few thousand rows, resolving row ids from
host-resident CSR postings (``segment/invindex.py``) and aggregating
those rows with numpy fancy-indexing finishes in well under a
millisecond of host time and never touches the device.

Shape: one *driving* leaf (EQ/IN/RANGE/REGEX, non-negated) resolves
row ids from postings; every other predicate of a root-level AND
evaluates as a *residual* on just those rows (recursive subset masks,
mirroring ``host_fallback._segment_mask`` semantics).  Estimated and
actual match counts above the selectivity threshold bail back to the
device scan — exactly the reference's operator-choice contract.
"""
from __future__ import annotations

import os
import weakref
from typing import List, Optional, Sequence

import numpy as np

from pinot_tpu.common.request import BrokerRequest, FilterOperator, FilterQueryTree
from pinot_tpu.engine.context import TableContext
from pinot_tpu.engine.plan import cached_match_table
from pinot_tpu.engine.results import IntermediateResult
from pinot_tpu.segment.immutable import ImmutableSegment
from pinot_tpu.segment.invindex import inverted_index

_DRIVING_OPS = (
    FilterOperator.EQUALITY,
    FilterOperator.IN,
    FilterOperator.RANGE,
    FilterOperator.REGEX,
)


def _max_matches(total_docs: int) -> int:
    env = os.environ.get("PINOT_TPU_INDEX_MAX_MATCHES")
    if env:
        return int(env)
    # crossover heuristic: numpy fancy-index aggregation costs ~10 ns/row
    # host-side; the device scan costs ~0.35 ns/row (2.8 B rows/s) plus a
    # fixed dispatch+RTT floor.  The fraction bound (1/64 of the table)
    # keeps the host path an order of magnitude under the scan at any
    # size AND keeps unselective predicates on the device even for small
    # tables — this is a needle-query path, not a general fallback.
    # Constants live in engine/tiercost.py (PINOT_TPU_TIER_COST_*).
    from pinot_tpu.engine.tiercost import postings_max_matches

    return postings_max_matches(total_docs)


def _mv_subset_hits(col, table: np.ndarray, rows: np.ndarray) -> np.ndarray:
    offs = np.asarray(col.mv_offsets)
    starts = offs[rows]
    counts = offs[rows + 1] - starts
    total = int(counts.sum())
    if total == 0:
        return np.zeros(rows.size, dtype=bool)
    reps = np.repeat(np.arange(rows.size), counts)
    base = np.repeat(starts, counts)
    cum = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(total) - np.repeat(cum, counts)
    hits = table[np.asarray(col.mv_values)[base + pos]]
    any_hit = np.zeros(rows.size, dtype=bool)
    np.logical_or.at(any_hit, reps, hits)
    return any_hit


def _subset_mask(
    seg: ImmutableSegment, tree: FilterQueryTree, rows: np.ndarray
) -> np.ndarray:
    """Evaluate a filter tree over a row-id subset — bool[rows.size].
    Semantics mirror host_fallback._segment_mask exactly."""
    if tree.is_leaf:
        col = seg.column(tree.column)
        d = col.dictionary
        table = cached_match_table(
            tree, d, d.cardinality if d.cardinality else 1,
            cache_key=(seg.segment_name, seg.metadata.crc, tree.column),
        )
        negative = tree.operator in (FilterOperator.NOT, FilterOperator.NOT_IN)
        if col.is_single_value:
            m = table[np.asarray(col.fwd)[rows]]
            return ~m if negative else m
        any_hit = _mv_subset_hits(col, table, rows)
        return ~any_hit if negative else any_hit
    masks = [_subset_mask(seg, c, rows) for c in tree.children]
    out = masks[0]
    for m in masks[1:]:
        out = (out & m) if tree.operator == FilterOperator.AND else (out | m)
    return out


def _decompose(tree: FilterQueryTree):
    """-> (driving candidates, all conjuncts) or None.  The filter must
    be a single leaf or a root-level AND of subtrees; the driving leaf
    is any direct-child positive leaf, the rest evaluate as residuals."""
    if tree.is_leaf:
        return ([tree], [tree]) if tree.operator in _DRIVING_OPS else None
    if tree.operator != FilterOperator.AND:
        return None
    cands = [
        c for c in tree.children if c.is_leaf and c.operator in _DRIVING_OPS
    ]
    return (cands, list(tree.children)) if cands else None


def index_path_decision(
    request: BrokerRequest,
    live: List[ImmutableSegment],
    ctx: TableContext,
    total_docs: int,
    scanned: Optional[Sequence[int]] = None,
):
    """The operator-choice verdict, separated from execution so the
    EXPLAIN plane can report it without serving the query.

    Returns ``(decision, state)``: ``decision`` is a JSON-safe record
    (``taken`` plus the reason/estimates that justify it); ``state`` is
    the resolved ``(best leaf, indexes, residuals, est)`` execution
    handoff, present only when ``taken`` is True.

    ``scanned``: the positions in ``live`` of the segments the filter can
    match (``pruner.scanned_segments``; None: all).  The estimates, the
    crossover and the postings are those segments' alone: the scan this
    tier is weighed against reads no other, and ``indexes`` has an entry
    a scanned segment, in their order."""
    if scanned is not None:
        live = [live[i] for i in scanned]
    if os.environ.get("PINOT_TPU_INVINDEX") == "0":
        return {"taken": False, "reason": "postings path disabled (PINOT_TPU_INVINDEX=0)"}, None
    tree = request.filter
    if tree is None:
        return {"taken": False, "reason": "no filter: nothing selective to drive postings"}, None
    dec = _decompose(tree)
    if dec is None:
        return {
            "taken": False,
            "reason": "filter shape not postings-drivable (needs a root-level "
            "AND / single positive leaf)",
        }, None
    cands, conjuncts = dec
    live_docs = sum(s.num_docs for s in live)
    limit = _max_matches(live_docs)

    # cheap pre-estimate (uniform assumption: matched dict fraction *
    # rows) picks ONE candidate before any postings build; tables are
    # kept for the confirm/resolve stages (REGEX tables cost O(card)
    # regex evaluations — never compute them twice)
    best = None
    best_frac = None
    best_tables = None
    for leaf in cands:
        frac = 0.0
        ok = True
        tables = []
        for seg in live:
            col = seg.columns.get(leaf.column)
            if col is None or col.dictionary.cardinality <= 0:
                ok = False
                break
            d = col.dictionary
            t = cached_match_table(
                leaf, d, d.cardinality,
                cache_key=(seg.segment_name, seg.metadata.crc, leaf.column),
            )
            tables.append(t)
            frac = max(frac, float(t.sum()) / d.cardinality)
        if ok and (best_frac is None or frac < best_frac):
            best, best_frac, best_tables = leaf, frac, tables
    if best is None or best_frac * live_docs > limit:
        return {
            "taken": False,
            "reason": "estimated matches above the postings/scan crossover",
            "column": None if best is None else best.column,
            "estMatches": None
            if best is None
            else int(best_frac * live_docs),
            "maxMatches": int(limit),
        }, None

    # real postings counts confirm (skew can defeat the uniform guess)
    indexes = []
    est = 0
    for seg, t in zip(live, best_tables):
        idx = inverted_index(seg, best.column)
        if idx is None:
            return {
                "taken": False,
                "reason": f"no inverted index for driving column {best.column!r}",
                "column": best.column,
            }, None
        est += idx.count_for_table(t)
        indexes.append((idx, t))
    if est > limit:
        return {
            "taken": False,
            "reason": "postings count above the postings/scan crossover "
            "(skew defeated the uniform estimate)",
            "column": best.column,
            "estMatches": int(est),
            "maxMatches": int(limit),
        }, None

    residuals = [c for c in conjuncts if c is not best]
    decision = {
        "taken": True,
        "reason": "selective driving leaf answers from host postings in O(matches)",
        "column": best.column,
        "estMatches": int(est),
        "maxMatches": int(limit),
        "residuals": len(residuals),
    }
    return decision, (best, indexes, residuals, est)


def try_index_path(
    request: BrokerRequest,
    live: List[ImmutableSegment],
    ctx: TableContext,
    total_docs: int,
    sel_columns: Optional[List[str]],
) -> Optional[IntermediateResult]:
    """O(matches) host path, or None to take the device scan."""
    _decision, state = index_path_decision(request, live, ctx, total_docs)
    if state is None:
        return None
    return run_index_path(state, request, live, ctx, total_docs, sel_columns)


def hold_state(state):
    """A taken decision's hand-off in the form the executor keeps for a
    repeated query.  The postings stay their segments' own
    (``inverted_index`` caches them on the segment, ``release_postings``
    drops them at unload, a consuming segment's snapshot takes its own
    with it), so they are held weakly: a kept query must not keep an
    unloaded segment's index alive."""
    best, indexes, residuals, est = state
    return best, [(weakref.ref(idx), t) for idx, t in indexes], residuals, est


def held_state(kept):
    """``hold_state`` undone, or None where a segment's postings have
    been released since."""
    best, refs, residuals, est = kept
    indexes = [(ref(), t) for ref, t in refs]
    if any(idx is None for idx, _ in indexes):
        return None
    return best, indexes, residuals, est


def run_index_path(
    state,
    request: BrokerRequest,
    live: List[ImmutableSegment],
    ctx: TableContext,
    total_docs: int,
    sel_columns: Optional[List[str]],
    scanned: Optional[Sequence[int]] = None,
) -> IntermediateResult:
    """Answer from the postings a taken ``index_path_decision`` handed
    off in ``state`` (which the executor keeps for a repeated query),
    over the ``scanned`` segments the decision was made for."""
    best, indexes, residuals, est = state
    slot = {si: j for j, si in enumerate(range(len(live)) if scanned is None else scanned)}

    def matched_rows(si: int, seg: ImmutableSegment) -> np.ndarray:
        idx, t = indexes[slot[si]]
        rows = idx.resolve_table(t)
        if rows.size and residuals:
            keep = np.ones(rows.size, dtype=bool)
            for r in residuals:
                keep &= _subset_mask(seg, r, rows)
            rows = rows[keep]
        return rows

    from pinot_tpu.engine.host_fallback import execute_host

    res = execute_host(
        live, ctx, request, total_docs, sel_columns, matched_rows=matched_rows, scanned=scanned
    )
    # filter work was O(postings), not O(n): report candidate rows like
    # the zone-map path does (num_entries_scanned contract)
    res.num_entries_scanned_in_filter = est * max(1, len(residuals) + 1)
    # cost re-attribution: this is the postings tier, and its bytes are
    # O(matches) — the wrapper's full-column upper bound does not apply
    res.cost.pop("segmentsHost", None)
    res.cost["segmentsPostings"] = res.num_segments_queried
    res.cost["bytesScanned"] = est * max(1, len(residuals) + 1) * 8
    if request.is_group_by:
        res.add_cost(**group_state_digest(request, res.groups))
    return res


def group_state_digest(request: BrokerRequest, groups) -> dict:
    """What a device group-by's finalize puts on the cost vector of its
    whole fetched state (``executor._finalize``: ``numGroupsLive``,
    ``numGroupsKept``, ``groupStateSumSq``), of a postings answer's
    groups, so that a reply is held to the same three whichever tier
    made it.  The tier answers O(matches) rows and trims nothing: every
    group found is live and kept.  The digest is the sum of squares, in
    float64, of every group's value of each aggregate whose device state
    is dense floats (count, sum, min, max, avg, minmaxrange)."""
    from pinot_tpu.engine.plan import _agg_kind

    dense = [i for i, a in enumerate(request.aggregations) if _agg_kind(a.base_function) in ("scalar", "pair")]
    sum_sq = sum(float(partials[i].finalize()) ** 2 for partials in groups.values() for i in dense)
    return {"numGroupsLive": len(groups), "numGroupsKept": len(groups), "groupStateSumSq": sum_sq}
