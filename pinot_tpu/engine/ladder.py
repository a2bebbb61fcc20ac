"""The serving ladder, written once: which tier answers a query over
the segments a server holds, and what the device tier derives on the
host before it launches.

``TIERS`` is the order.  ``executor._execute_tiers`` loops over it and
runs the first tier that accepts; ``explain.build_explain_node`` loops
over it and reports that tier; ``explain.build_prewarm_spec`` loops over
it and compiles only where it ends on the device.  Pruning and the
star-tree routing stand ahead of it, written once too (``routed``).

Below the list, the device tier's second level, one function a
derivation, in the order asked: ``scope``, ``roles``, ``plan``,
``launch_segments``, ``inputs``, ``batch``, ``program``.  Every rung is
handed ``scanned`` beside ``live``: the positions of the segments the
filter can match (``pruner.scanned_segments``).  ``live`` is the table's
identity (the staged table, the table context, ``totalDocs``); the work,
and what a rung decides by, is the scanned segments'.  Those that read a staged table take
it as an argument and read its metadata alone (shape bucketing, the
segments' cards, which role arrays are present), so EXPLAIN's phantom
table (``explain._phantom_staged``) yields what the staged one would:
the same ``StaticPlan``, digest, poison key, block ids and program.  The
executor keeps each under its name in the prepared-query memo
(``executor._Prepared``); a phantom's are kept nowhere.

The safety code is not here: the audit plane's blocks, the bit-sliced
tier's fall-through, the device tier's heal loop, the quarantine and the
pinning of the staged table are the executor's.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from pinot_tpu.common.request import BrokerRequest
from pinot_tpu.common.schema import DataType
from pinot_tpu.engine import config
from pinot_tpu.engine.context import TableContext
from pinot_tpu.engine.device import StagedTable
from pinot_tpu.engine.plan import StaticPlan
from pinot_tpu.segment.immutable import ImmutableSegment


class Tier(NamedTuple):
    """One rung.  ``name`` is the tier's one name: the audit plane's
    quarantine key, what ``_finish_tier`` stamps on the reply, the cost
    vector's ``segments<Name>`` count and EXPLAIN's per-segment record.
    ``decide(request, live, ctx, total_docs, mesh, scanned)`` gives (a JSON-safe
    record of the verdict, the hand-off to the tier's execution or None
    where it declines); ``prepared`` is the name the executor keeps the
    hand-off under; ``phase`` is what the reply's first stretch is
    relabelled to when the tier answers; ``on_mesh``: whether it may
    answer a query whose lane shards the segment axis over a mesh."""

    name: str
    decide: Callable[..., Tuple[Optional[Dict[str, Any]], Any]]
    prepared: Optional[str] = None
    phase: Optional[str] = None
    on_mesh: bool = True


def _postings(request, live, ctx, total_docs, mesh, scanned):
    # selective predicates answer from host postings in O(matches)
    # (engine/invindex_path.py — BitmapBasedFilterOperator analog);
    # unselective ones fall through
    from pinot_tpu.engine.invindex_path import index_path_decision

    return index_path_decision(request, live, ctx, total_docs, scanned)


def _bitsliced(request, live, ctx, total_docs, mesh, scanned):
    # mid-selectivity scalar aggregations the postings tier just
    # declined evaluate as O(bit-width) bulk-bitwise passes over
    # bit-sliced planes (engine/bitsliced.py) — single-device only;
    # mesh placements keep the sharded scan path
    from pinot_tpu.engine.bitsliced import bitsliced_decision

    return bitsliced_decision(request, live, ctx, total_docs, scanned)


def _forced_host(request, live, ctx, total_docs, mesh, scanned):
    # queries the planner can only send to the host (group space or
    # guaranteed pair overflow) skip device staging entirely.  A
    # group-by the device declines says why, by name: the key space, or
    # what the runs lowering above MAX_GROUP_CAPACITY keys does not take
    # (plan.group_runs_host_reason)
    from pinot_tpu.engine.plan import group_by_host_reason, plan_forced_host

    sharded = mesh is not None
    if not plan_forced_host(request, ctx, mesh=sharded):
        return None, None
    why = group_by_host_reason(request, ctx, mesh=sharded) if request.is_group_by else None
    return {"groupByHostReason": why}, (why,)


def _device(request, live, ctx, total_docs, mesh, scanned):
    return None, True  # the last rung takes what is left; its second level is below


TIERS: Tuple[Tier, ...] = (
    Tier("postings", _postings, prepared="postings", phase="indexPath"),
    Tier("bitsliced", _bitsliced, prepared="bitsliced", phase="bitslicedPath", on_mesh=False),
    Tier("host", _forced_host, prepared="forcedHost", phase="hostPath"),
    Tier("device", _device),
)


def routed(segments: Sequence[ImmutableSegment], request: BrokerRequest):
    """What stands ahead of the list, for ``executor.execute``, EXPLAIN
    and the prewarm worker alike: (the segments upstream's three pruners
    keep, those of them the star-tree answers from its cube, the rest:
    the engine's, which the list is asked about).  The value verdict is
    not here: it drops no segment from the query, it is a function of
    the literals the prepared memo keeps (``pruner.scanned_segments``
    over the engine's segments), and every rung is handed its answer."""
    from pinot_tpu.engine.pruner import prune_segments
    from pinot_tpu.startree.operator import is_fit_for_star_tree

    live = prune_segments(segments, request)
    star = [s for s in live if is_fit_for_star_tree(request, s)]
    return live, star, [s for s in live if s not in star] if star else live


def first_accepting(request, live, ctx, total_docs, mesh, scanned):
    """(tier, record, hand-off) of the first rung that accepts, for a
    reader that runs nothing: EXPLAIN and the prewarm worker.  The
    executor walks ``TIERS`` itself, through its memo and past the rungs
    the audit plane has blocked."""
    for tier in TIERS:
        if mesh is not None and not tier.on_mesh:
            continue
        record, state = tier.decide(request, live, ctx, total_docs, mesh, scanned)
        if state is not None:
            return tier, record, state
    raise AssertionError("the device rung declines nothing")


# ---------------------------------------------------------------------------
# The device tier's derivations
# ---------------------------------------------------------------------------


def selection_columns(request: BrokerRequest, seg: ImmutableSegment) -> List[str]:
    cols = request.selection.columns
    if not cols or cols == ["*"]:
        return list(seg.columns.keys())
    return list(cols)


def scope(request: BrokerRequest, live: Sequence[ImmutableSegment], mesh):
    """(total docs, the columns to stage, the selection's columns, the
    segment axis' padding for ``mesh``)."""
    needed = set(request.referenced_columns())
    sel_columns: Optional[List[str]] = None
    if request.is_selection:
        sel_columns = selection_columns(request, live[0])
        needed.update(sel_columns)
    pad_to = 0
    if mesh is not None:
        n = int(mesh.devices.size)
        pad_to = -(-len(live) // n) * n
    # columns used ONLY by doc-range predicates on sorted columns
    # never reach the device (the kernel compares row ids against
    # host-computed doc bounds) — skip staging them entirely
    needed -= _docrange_only_columns(request, live, sel_columns)
    return sum(s.num_docs for s in live), tuple(sorted(needed)), sel_columns, pad_to


def _docrange_only_columns(
    request: BrokerRequest, live: Sequence[ImmutableSegment], sel_columns: Optional[List[str]]
) -> set:
    """Filter columns whose every use qualifies for the docrange
    fast path (plan.py StaticLeaf) and which appear nowhere else in
    the query."""
    qualifying = _docrange_qualifying_cols(request, live)
    used_elsewhere = {c for a in request.aggregations for c in a.columns}
    if request.is_group_by:
        used_elsewhere.update(request.group_by.columns)
    if request.is_selection:
        used_elsewhere.update(sel_columns or [])
        used_elsewhere.update(s.column for s in request.selection.sorts)
    return qualifying - used_elsewhere


def _docrange_qualifying_cols(request: BrokerRequest, live: Sequence[ImmutableSegment]) -> set:
    """Filter columns whose EVERY leaf use classifies docrange
    (sorted in every segment, SV, RANGE or single-value EQ).  MUST
    mirror build_static_plan's classification: a column dropped or
    base-skipped on a wrong prediction would leave the kernel
    without its arrays."""
    if request.filter is None:
        return set()
    from pinot_tpu.common.request import FilterOperator

    qualifies: Dict[str, bool] = {}
    for node in request.filter.walk():
        if not node.is_leaf:
            continue
        col = node.column
        ok = False
        if live and live[0].has_column(col):
            meta0 = live[0].column(col).metadata
            shape_ok = node.operator == FilterOperator.RANGE or (
                node.operator == FilterOperator.EQUALITY
                and len(node.values) == 1
            )
            ok = (
                meta0.single_value
                and shape_ok
                and all(s.column(col).metadata.is_sorted for s in live)
            )
        qualifies[col] = qualifies.get(col, True) and ok
    return {c for c, ok in qualifies.items() if ok}


def roles(request: BrokerRequest, live: Sequence[ImmutableSegment], ctx: TableContext):
    """(raw, gfwd, hll role columns, the skip-base set): what staging
    builds beside, or instead of, a column's base arrays."""
    raw_cols, gfwd_cols, hll_cols = _role_columns(request, live, ctx)
    return raw_cols, gfwd_cols, hll_cols, _skip_base_columns(request, live, raw_cols, gfwd_cols, hll_cols)


def _role_columns(request: BrokerRequest, live: Sequence[ImmutableSegment], ctx: Optional[TableContext]):
    """Columns to stage with role-specific arrays: aggregation
    inputs get raw value arrays, group-by/sort keys get global-id
    forward arrays (both avoid slow big-table gathers on device)."""
    seg = live[0]

    def big_card(c: str) -> bool:
        # raw_card_min() is 0 on accelerators (TPU gathers serialize
        # — see engine/config.py measurement); on CPU the narrow
        # fwd + dict-gather feed stands below the threshold.  The
        # staged dtype is sized by the table-wide max cardinality,
        # so the decision must be too.
        card = max(s.column(c).metadata.cardinality for s in live)
        return card > config.raw_card_min()

    def sv(c: str) -> bool:
        return c in seg.columns and seg.column(c).metadata.single_value

    from pinot_tpu.engine.plan import _agg_kind

    # only scalar/pair agg kernels read .raw (presence/hist/hll work
    # in dictId space)
    def numeric_any(c: str) -> bool:
        if c == "*" or c not in seg.columns:
            return False
        return seg.column(c).metadata.data_type.stored_type != DataType.STRING

    # (every leaf of a compound expression is one, whatever its
    # cardinality: the kernel multiplies row values, not dictionaries)
    raw_cols = {
        c
        for a in request.aggregations
        if _agg_kind(a.base_function) in ("scalar", "pair")
        for c in a.columns
        if numeric_any(c) and (a.expr is not None or big_card(c))
    }
    gfwd_cols = set()
    if request.is_group_by:
        gfwd_cols.update(c for c in request.group_by.columns if sv(c))
    if request.is_selection:
        gfwd_cols.update(s.column for s in request.selection.sorts if sv(s.column))
    # presence/hist aggs (distinctcount, percentile) read global
    # value ids per row: stage them host-side (gfwd) so the kernel
    # streams instead of gathering a remap table on device (slow at
    # any cardinality on TPU, ROADMAP S5).  Both kinds
    # stay on device at any cardinality (dense holders within the
    # budget, the sort-pairs path beyond it).
    gfwd_cols.update(
        a.column
        for a in request.aggregations
        if _agg_kind(a.base_function) in ("presence", "hist") and sv(a.column)
    )
    # HLL aggs: modest-cardinality SV columns lower to a presence
    # contraction over gfwd streams (plan.hll_lowers_to_presence —
    # registers depend only on the distinct value set); the rest
    # stream host-computed (register, rank) pairs
    from pinot_tpu.engine.plan import hll_lowers_to_presence

    hll_cols = set()
    for a in request.aggregations:
        if _agg_kind(a.base_function) == "hll" and sv(a.column):
            if hll_lowers_to_presence(request, ctx, a.column):
                gfwd_cols.add(a.column)
            else:
                hll_cols.add(a.column)
    return tuple(sorted(raw_cols)), tuple(sorted(gfwd_cols)), tuple(sorted(hll_cols))


def _skip_base_columns(request: BrokerRequest, live: Sequence[ImmutableSegment], raw_cols, gfwd_cols, hll_cols) -> set:
    """Columns the kernel reads ONLY through a role stream skip
    their base fwd/dict arrays: at 1B rows the dictId stream is the
    difference between fitting in HBM and not.  Filter leaves and
    selection outputs read base arrays, so those columns keep them.
    Staging and the prewarm worker's avals read the one answer: a
    prewarmed executable must match a serving launch bit for bit."""
    if request.is_selection:
        return set()
    # filter leaves need base arrays on device — EXCEPT leaves
    # whose every use classifies docrange (the kernel compares
    # row ids against host-computed bounds, reading no column)
    filter_cols = (
        {n.column for n in request.filter.walk() if n.is_leaf}
        if request.filter is not None
        else set()
    ) - _docrange_qualifying_cols(request, live)
    from pinot_tpu.engine.plan import _agg_kind

    # scalar/pair agg inputs OUTSIDE raw_cols (small dictionaries)
    # read dict[fwd] on device — their base arrays must stay
    # (an expression streams every leaf or gathers every leaf,
    # plan.StaticAgg.use_raw)
    gather_agg_cols = {
        c
        for a in request.aggregations
        if _agg_kind(a.base_function) in ("scalar", "pair")
        and not set(a.columns) <= set(raw_cols)
        for c in a.columns
    }
    return (
        set(raw_cols) | set(gfwd_cols) | set(hll_cols)
    ) - filter_cols - gather_agg_cols


def plan(request: BrokerRequest, ctx: TableContext, staged: StagedTable, scratch: Dict[Any, Any]):
    """(the ``StaticPlan``, its ``plan_digest`` or None off the device,
    the poison key).  The digest is computed ONCE here and shared with
    the lane's injector hook, the failover's quarantine and EXPLAIN's
    ``device.planDigest``.  ``scratch``: the plan->inputs table cache
    (regex), handed on to ``inputs``."""
    from pinot_tpu.engine.dispatch import plan_digest
    from pinot_tpu.engine.plan import build_static_plan

    static = build_static_plan(request, ctx, staged, scratch=scratch)
    pdigest = plan_digest(static) if static.on_device else None
    return static, pdigest, (pdigest, staged.segment_names)


def launch_segments(scanned: Sequence[int], staged: StagedTable, mesh) -> Optional[Dict[str, np.ndarray]]:
    """The segments the device program runs over, where they are not the
    whole staged table (None: the whole launch): a window of L neighbours
    among the staged segments, from ``first`` on, and ``slots`` int32[L],
    a slot's position among the staged segments, -1 for a slot whose
    segment the filter cannot match (or that stands before the scanned
    ones, where the window ends with the table).  It rides with the
    query's inputs as ``q["segments"]`` (``plan.build_query_inputs``) and
    ``kernel.launch_view`` reads it.

    ONE rule, read from the input: L is the scanned segments' span (first
    to last, the dead between them included) padded to the next power of
    two, so a plan compiles at most log2(S) launch sizes besides the
    whole one, and L = S is the whole launch, today's program.  The
    program takes that window of the columns that are already resident,
    one slice: nothing is staged, so a query over a few segments costs no
    second copy of its columns.  A segment is a range of time and a
    filter on a column derived from the time leaves neighbours; a filter
    whose segments lie apart by half the table or more launches whole,
    and its dead segments' rows are rejected as they were before there
    was a verdict.

    A sharded placement (``mesh``) launches whole: each chip holds its
    own shard of the segment axis, a window across shards is a collective
    (an all-gather of the columns a scan exists to leave where they
    lie), and no deployment has a dead segment there yet.  The filter
    rejects the dead segments' rows there too."""
    if mesh is not None:
        return None
    count = 1
    while count < scanned[-1] - scanned[0] + 1:
        count *= 2
    if count >= staged.num_segments:
        return None
    first = min(scanned[0], staged.num_segments - count)
    window = np.arange(first, first + count, dtype=np.int32)
    return {"slots": np.where(np.isin(window, scanned), window, -1).astype(np.int32), "first": np.asarray(first, dtype=np.int32)}


def launched_segments(live: Sequence[ImmutableSegment], q_np: Dict[str, Any]) -> List[Optional[ImmutableSegment]]:
    """The segments a launch's program runs over, in the order of its
    inputs' and outputs' leading axis: ``live`` for the whole launch,
    else a slot's segment, None for an empty slot."""
    launch = q_np.get("segments")
    if launch is None:
        return list(live)
    return [live[i] if i >= 0 else None for i in launch["slots"]]


def inputs(
    request: BrokerRequest,
    static: StaticPlan,
    ctx: TableContext,
    live: Sequence[ImmutableSegment],
    staged: StagedTable,
    scratch: Dict[Any, Any],
    scanned: Sequence[int],
    mesh,
):
    """(the query's input tables ``q_np``, the zone tier's block ids or
    None for the full scan, the rows those blocks hold), all three over
    the launch's segments (``launch_segments``): a table has a row a
    segment of the launch, and the zone tier's gate weighs the candidate
    blocks against the launch's rows, not the table's."""
    from pinot_tpu.engine.kernel import chunk_rows_limit
    from pinot_tpu.engine.plan import build_query_inputs

    q_np = build_query_inputs(request, static, ctx, staged, scratch=scratch, launch=launch_segments(scanned, staged, mesh))
    count = launch_count(staged, q_np)
    block_ids, scanned_rows = _block_skip_ids(static, q_np, launched_segments(live, q_np), staged.n_pad, count)
    limit = chunk_rows_limit()
    if block_ids is not None and limit and count * staged.n_pad > limit:
        # the block kernel has no segment-chunked variant: beyond the
        # per-dispatch row budget its single dispatch would exhaust
        # HBM at compile time — fall through to the chunked full
        # kernel instead (correctness over the block-skip win)
        block_ids = None
    return q_np, block_ids, scanned_rows


def _block_skip_ids(static: StaticPlan, q_np: Dict[str, Any], live: Sequence[ImmutableSegment], n_pad: int, num_segments: int):
    """Zone-map block pruning decision (engine/zonemap.py) over the
    launch's segments ``live`` (``launched_segments``), ``num_segments`` slots of ``n_pad`` rows:
    returns (block_ids [num_segments, nb_pad] or None, candidate_rows or
    None).

    Engages when the candidate blocks, padded to a power of two,
    are at most half the table.  The gate dates from the gathered
    view, whose copy made Q5 (47 blocks of 128) and TPC-H Q6 (22 of
    128) dearer than the full scan they skip (chip runs, PR 28 and
    PR 34); an 'inplace' plan (kernel.zone_blocks) no longer pays
    that, so half is now known to be low for it and waits for a cell
    with selective traffic to be moved (ROADMAP S6).  On a mesh, the
    ids array shards over the segment axis like every other
    per-segment input (nb_pad is a global bucket)."""
    if os.environ.get("PINOT_TPU_ZONEMAP") == "0":
        return None, None
    from pinot_tpu.engine import zonemap

    cand = zonemap.candidate_blocks(static, q_np, live, n_pad)
    if cand is None:
        return None, None
    block = zonemap.zone_block_rows()
    nb_total = num_segments * (n_pad // block)
    nb_max = int(cand.sum(axis=1).max()) if cand.size else 0
    if static.selection is not None:
        # the gathered view exposes only nb_pad*block rows per
        # segment; top_k(k) requires k <= operand length, so grow
        # the candidate window to cover the selection k (falls back
        # to full scan below when that defeats the pruning win)
        nb_max = max(nb_max, -(-static.selection.k // block))
    nb_pad = 1
    while nb_pad < nb_max:
        nb_pad *= 2
    if nb_pad * num_segments > nb_total // 2:
        return None, None
    ids = zonemap.block_ids_input(cand, nb_pad)
    if ids.shape[0] < num_segments:  # mesh-padding segments, a launch's empty slots
        pad = np.full(
            (num_segments - ids.shape[0], nb_pad), -1, dtype=np.int32
        )
        ids = np.concatenate([ids, pad], axis=0)
    return ids, int(cand.sum()) * block


def batch(static: StaticPlan, staged: StagedTable, q_np: Dict[str, Any], block_ids, mesh) -> Optional[Tuple[tuple, int]]:
    """What of a ``BatchSpec`` is a function of the query and the
    staged table's shape: (the inputs' structural signature,
    ``max_members``; 0: no bound), or None where the launch stacks with
    no other query's.  Cross-query micro-batching takes the plain packed
    single-device program only: no mesh collectives, no per-query
    block-id gathers, no chunked dispatch sequence (one member already
    fills the per-dispatch row budget), and no 'runs' group-by, which
    sorts the table's rows in its merge: a member more is a sort more,
    nothing shared.  ``max_members`` keeps batch x rows under that budget
    so batching can never blow the compile-time working set the chunked
    path exists to bound.  A launch over some of the table's segments
    (``launch_segments``) stacks with no other either: each member would
    take its own copy of its segments' columns, where the batch exists to
    read the resident ones once."""
    from pinot_tpu.engine.kernel import chunk_rows_limit, groupby_lowering
    from pinot_tpu.engine.packing import batch_input_signature

    if mesh is not None or block_ids is not None or groupby_lowering(static) == "runs" or "segments" in q_np:
        return None
    limit = chunk_rows_limit()
    rows = max(1, staged.num_segments * staged.n_pad)
    if limit:
        # the launch pads member count UP to a power of two, so the
        # cap must be the largest power of two whose padded batch
        # still fits the row budget — a plain floor-divide cap of 5
        # would pad to 8 and overshoot the budget by ~1.5x
        cap = limit // rows
        max_members = 1
        while max_members * 2 <= cap:
            max_members *= 2
    else:
        max_members = 0
    if max_members == 1:
        return None  # one batch member already fills the budget
    return batch_input_signature(q_np), max_members


def program(static: StaticPlan, staged: StagedTable, q_np: Dict[str, Any], block_ids, mesh):
    """The plan's device program, as ``kernel.plan_program`` chooses it
    for the launch's segment count (``q_np``'s, where it carries the
    launch's segments): looked up on every query where its builders keep
    it, so a program forgotten there is built again by the next launch."""
    from pinot_tpu.engine.kernel import plan_program
    from pinot_tpu.engine.zonemap import zone_block_rows

    block = zone_block_rows() if block_ids is not None else None
    return plan_program(static, launch_count(staged, q_np), staged.n_pad, block, mesh)


def launch_count(staged: StagedTable, q_np: Dict[str, Any]) -> int:
    """L: the segments a launch's program runs over, the staged table's S
    for the whole launch."""
    launch = q_np.get("segments")
    return staged.num_segments if launch is None else int(launch["slots"].shape[0])


def launch_digest(pdigest: Optional[str], staged: StagedTable, q_np: Dict[str, Any]) -> Optional[str]:
    """What names the launch's compiled program: the plan's digest for
    the whole launch, and the digest with L for a launch over L of the
    table's segments, which is a compile of its own.  The lane's compile
    timeline (``via=first|warm``, ``compile.cold``, the cost analysis),
    EXPLAIN's ``compile.state``, the prewarm worker's skip and the
    utilization plane's join read it; the poison quarantine keeps the
    plan's, a plan that fails failing at every size."""
    count = launch_count(staged, q_np)
    return pdigest if pdigest is None or count == staged.num_segments else f"{pdigest}.L{count}"
