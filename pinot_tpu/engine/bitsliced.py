"""Bit-sliced (BSI) filter/aggregate tier — the fourth filter tier.

The bulk-bitwise PIM formulation applied to the device engine: columns
are staged as packed int32 bit-planes (device.py ``bsi``/``bsiv`` role
arrays, built with the packing.py encoder at staging time), and an
eligible scalar aggregation evaluates its whole filter as O(bit-width)
wide AND/OR/popcount passes over n/32-word planes — with COUNT/SUM/
MIN/MAX fused INTO the bitwise pass (kernel.py bitsliced kernels), so
mid-selectivity aggregations never materialize row indices at all.

Position in the tier ladder (engine/executor.py):

  postings (invindex_path)  — needle queries, O(matches) on host
  bit-sliced (this module)  — mid-selectivity scalar aggs, O(W * n/32)
  zone-map (zonemap.py)     — clustered predicates, O(candidate blocks)
  full scan (kernel.py)     — everything else, O(n)

The decision mirrors ``index_path_decision``'s contract: a JSON-safe
verdict EXPLAIN can report without serving the query, plus an opaque
execution state when taken.  Crossover constants live in
engine/tiercost.py (``PINOT_TPU_TIER_COST_*``); ``PINOT_TPU_BITSLICED``
is the tier switch: "0" disables, "force" skips the cost model (tests
pin the tier this way), unset/auto applies it.

Fused SUM is offered only where it is bit-exact against the scan
tier: exactly-integral dictionaries (packing.integral_dictionary_values)
with offset width <= 32, summed host-side in exact integer arithmetic
as  sum = vmin_s * count_s + sum_b 2^b * popcount(value_plane_b & bitmap).
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pinot_tpu.common.request import BrokerRequest, FilterOperator, FilterQueryTree
from pinot_tpu.engine import config
from pinot_tpu.common.schema import DataType
from pinot_tpu.engine.context import TableContext
from pinot_tpu.engine.results import (
    AvgPartial,
    CountPartial,
    IntermediateResult,
    MaxPartial,
    MinPartial,
    SumPartial,
    make_partial,
)
from pinot_tpu.segment.immutable import ImmutableSegment

_MAX_POINTS = 16  # same IN-list bound the StaticPlan leaf lowering uses
_SCALAR_AGGS = ("count", "sum", "min", "max", "avg")


def _k_pad(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length()) if n > 1 else 1


def _leaf_kind(op: FilterOperator) -> Optional[str]:
    if op == FilterOperator.RANGE:
        return "interval"
    if op in (FilterOperator.EQUALITY, FilterOperator.IN):
        return "points"
    if op in (FilterOperator.NOT, FilterOperator.NOT_IN):
        return "points_none"
    return None  # REGEX needs the match-table path


def _encode_tree(
    node: FilterQueryTree,
    live: List[ImmutableSegment],
    leaves: List[Tuple[FilterQueryTree, str, str, int, int]],
):
    """-> nested ("leaf", i) / ("and"|"or", ...) encoding, or a string
    reason why the subtree is not bit-sliceable."""
    from pinot_tpu.engine.device import bsi_filter_width

    if node.is_leaf:
        kind = _leaf_kind(node.operator)
        if kind is None:
            return f"operator {node.operator.name} not bit-sliceable"
        col = node.column
        if not all(s.has_column(col) for s in live):
            return f"column {col!r} missing from a segment"
        cols = [s.column(col) for s in live]
        if not cols[0].metadata.single_value:
            return f"column {col!r} is multi-value"
        if any(c.dictionary.cardinality <= 0 for c in cols):
            return f"column {col!r} has no dictionary"
        if kind != "interval" and len(node.values) > _MAX_POINTS:
            return f"point set over {_MAX_POINTS} values"
        width = bsi_filter_width(cols)
        k_pad = _k_pad(len(node.values)) if kind != "interval" else 0
        leaves.append((node, kind, col, width, k_pad))
        return ("leaf", len(leaves) - 1)
    if node.operator not in (FilterOperator.AND, FilterOperator.OR):
        return f"operator {node.operator.name} not bit-sliceable"
    children = []
    for c in node.children:
        enc = _encode_tree(c, live, leaves)
        if isinstance(enc, str):
            return enc
        children.append(enc)
    op = "and" if node.operator == FilterOperator.AND else "or"
    return (op, *children)


def bitsliced_decision(
    request: BrokerRequest,
    live: List[ImmutableSegment],
    ctx: TableContext,
    total_docs: int,
    scanned: Optional[Sequence[int]] = None,
):
    """The bit-sliced tier verdict, separated from execution so EXPLAIN
    can report it without serving the query (index_path_decision's
    contract).  Returns ``(decision, state)``: a JSON-safe record plus
    the execution handoff (kernel spec, leaf nodes, fused-agg
    descriptors) present only when taken.

    ``scanned``: the positions in ``live`` of the segments the filter can
    match (None: all).  This tier's pass stays whole, over the planes of
    every live segment as they are staged (a plane is a 32nd of a row's
    width a bit, its outputs are a segment's own and a dead segment's
    count is zero), so its cost is the table's; the scan it is weighed
    against reads the scanned segments alone."""
    from pinot_tpu.engine import tiercost
    from pinot_tpu.engine.device import bsi_filter_width, bsiv_value_spec

    mode = os.environ.get("PINOT_TPU_BITSLICED", "")
    if mode == "0":
        return {
            "taken": False,
            "reason": "bit-sliced tier disabled (PINOT_TPU_BITSLICED=0)",
        }, None
    if not live:
        return {"taken": False, "reason": "no live segments"}, None
    if (
        not request.is_aggregation
        or request.is_group_by
        or request.is_selection
        or request.join is not None
        or not request.aggregations
    ):
        return {
            "taken": False,
            "reason": "tier serves single-table scalar aggregations only",
        }, None
    for a in request.aggregations:
        if a.base_function not in _SCALAR_AGGS or a.is_mv:
            return {
                "taken": False,
                "reason": f"aggregation {a.function} not popcount-fusable",
            }, None
    if request.filter is None:
        return {
            "taken": False,
            "reason": "no filter: the plain scan already streams every row once",
        }, None

    leaves: List[Tuple[FilterQueryTree, str, str, int, int]] = []
    tree = _encode_tree(request.filter, live, leaves)
    if isinstance(tree, str):
        return {"taken": False, "reason": tree}, None

    # fused-aggregate eligibility: SUM/AVG need exactly-integral value
    # planes (bit-exactness vs the scan tier), MIN/MAX descend dictId
    # planes (dictionaries are sorted, so extreme dictId = extreme value)
    sums: Dict[str, int] = {}
    extremes: Dict[Tuple[str, bool], int] = {}
    agg_descs = []
    for a in request.aggregations:
        base = a.base_function
        if base == "count":
            agg_descs.append(("count", None))
            continue
        col = a.column
        if a.expr is not None:
            return {
                "taken": False,
                "reason": f"{base}({col}): an expression inside an aggregate "
                "multiplies row values, which sums of bit planes cannot",
            }, None
        if not all(s.has_column(col) for s in live):
            return {"taken": False, "reason": f"agg column {col!r} missing"}, None
        cols = [s.column(col) for s in live]
        if (
            not cols[0].metadata.single_value
            or cols[0].metadata.data_type.stored_type == DataType.STRING
        ):
            return {
                "taken": False,
                "reason": f"agg column {col!r} not a numeric SV column",
            }, None
        if base in ("sum", "avg"):
            spec_v = bsiv_value_spec(cols)
            if spec_v is None:
                return {
                    "taken": False,
                    "reason": f"sum({col}) not fusable: dictionary values "
                    "not exactly integral (bit-exactness contract)",
                }, None
            sums[col] = spec_v[0]
        else:
            extremes[(col, base == "max")] = bsi_filter_width(cols)
        agg_descs.append((base, col))

    filter_planes = sum(w for (_, _, _, w, _) in leaves)
    planes_total = (
        filter_planes + sum(sums.values()) + sum(extremes.values())
    )
    plane_counts = {col: w for (_, _, col, w, _) in leaves}
    decision: Dict[str, Any] = {
        "column": next(iter(plane_counts), None),
        "planes": int(planes_total),
        "planeCounts": plane_counts,
        "fusedAggs": [
            base if col is None else f"{base}({col})" for base, col in agg_descs
        ],
    }
    cap = tiercost.bsi_max_planes()
    if planes_total > cap and mode != "force":
        decision.update(
            taken=False,
            reason=f"{planes_total} planes over the bit-sliced budget ({cap})",
        )
        return decision, None

    if mode != "force":
        # clustered interval predicates belong to the zone-map/doc-range
        # tier: block pruning reads O(candidate blocks), which no
        # bitwise full-width pass can beat
        if os.environ.get("PINOT_TPU_ZONEMAP") != "0":
            for node, kind, col, _, _ in leaves:
                sortedish = kind == "interval" or (
                    kind == "points" and len(node.values) == 1
                )
                if sortedish and all(
                    s.column(col).metadata.is_sorted for s in live
                ):
                    decision.update(
                        taken=False,
                        reason=f"sorted column {col!r} defers to zone-map/"
                        "doc-range block pruning",
                    )
                    return decision, None
        bsi_ns = tiercost.bitsliced_cost_ns(total_docs, planes_total)
        scan_ns = tiercost.scan_cost_ns(
            total_docs if scanned is None else sum(live[i].num_docs for i in scanned))
        decision["estCostNs"] = int(bsi_ns)
        decision["scanCostNs"] = int(scan_ns)
        if bsi_ns >= scan_ns:
            decision.update(
                taken=False,
                reason="cost model favors the full scan "
                f"({planes_total} planes)",
            )
            return decision, None

    decision.update(
        taken=True,
        reason="mid-selectivity scalar aggregation fuses into the "
        f"bitwise pass over {planes_total} planes",
    )
    spec = (
        tuple((kind, col, w, k) for (_, kind, col, w, k) in leaves),
        tree,
        tuple(sorted(sums.items())),
        tuple(sorted((c, w, m) for (c, m), w in extremes.items())),
    )
    return decision, (spec, leaves, agg_descs, planes_total, filter_planes)


def _query_inputs(
    spec, leaves, live: List[ImmutableSegment], S: int
) -> Dict[str, np.ndarray]:
    """Per-segment dictId thresholds/point sets for every leaf —
    dictionaries are per-segment, so each segment lowers its own
    literals (plan.py leaf_interval / leaf_points).  Padded dummy
    segments get empty intervals / all-pad points."""
    from pinot_tpu.engine.plan import leaf_interval, leaf_points

    q: Dict[str, np.ndarray] = {}
    for i, (node, kind, col, _, k_pad) in enumerate(leaves):
        if kind == "interval":
            b = np.zeros((S, 2), dtype=np.int32)
            for s, seg in enumerate(live):
                b[s] = leaf_interval(node, seg.column(col).dictionary)
            q[f"bounds:{i}"] = b
        else:
            p = np.full((S, k_pad), -1, dtype=np.int32)
            for s, seg in enumerate(live):
                p[s] = leaf_points(node, seg.column(col).dictionary, k_pad)
            q[f"pts:{i}"] = p
    return q


def _finalize(
    request: BrokerRequest,
    agg_descs,
    staged,
    live: List[ImmutableSegment],
    outs: Dict[str, np.ndarray],
):
    """Host-side merge of the per-segment kernel outputs into agg
    partials — exact integer arithmetic end to end (python ints), so
    fused SUM is bit-exact against the scan tier's float64 result for
    the integral values the eligibility gate admits."""
    counts = np.asarray(outs["count"], dtype=np.int64)
    matched = int(counts.sum())
    partials = []
    for base, col in agg_descs:
        if base == "count":
            partials.append(CountPartial(float(matched)))
            continue
        if base in ("sum", "avg"):
            sc = staged.columns[col]
            psum = np.asarray(outs[f"psum:{col}"])  # int32 [S, Wv]
            total = 0
            for b in range(sc.bsiv_width):
                total += (1 << b) * int(psum[:, b].sum())
            for s in range(len(live)):
                total += int(sc.bsiv_min[s]) * int(counts[s])
            if base == "sum":
                partials.append(SumPartial(float(total)))
            else:
                partials.append(AvgPartial(float(total), float(matched)))
            continue
        # min/max: per-segment extreme dictId -> host dictionary lookup
        # (empty segments report garbage ids and are masked on count);
        # round-trip through the device value dtype so the answer is
        # bit-identical to the scan tier's staged-dict_vals extreme
        ids = np.asarray(outs[f"ext:{'mx' if base == 'max' else 'mn'}:{col}"])
        fdt = config.np_float_dtype()
        vals = [
            float(fdt(seg.column(col).dictionary.get(int(ids[s]))))
            for s, seg in enumerate(live)
            if counts[s] > 0
        ]
        if not vals:
            partials.append(make_partial(base))
        elif base == "min":
            partials.append(MinPartial(min(vals)))
        else:
            partials.append(MaxPartial(max(vals)))
    return partials, matched


def run_bitsliced_path(
    executor,
    state,
    request: BrokerRequest,
    live: List[ImmutableSegment],
    ctx: TableContext,
    total_docs: int,
    deadline: Optional[float] = None,
    lane=None,
    lane_index: int = 0,
    scanned: Optional[Sequence[int]] = None,
) -> Optional[IntermediateResult]:
    """Serve the scalar aggregation a taken ``bitsliced_decision`` handed
    off in ``state`` (which the executor keeps for a repeated query), or
    None to fall through to the zone-map/scan device section.  Rides
    the same lane dispatch plumbing as the scan kernels (coalescing,
    micro-timers, static cost analysis -> achievedBytesPerSec), with
    the kernel spec standing in for the StaticPlan in every cache key —
    both are process-stable hashables.  The pass is whole
    (``bitsliced_decision``); ``scanned`` is what it counts as queried,
    by the verdict the other tiers count by."""
    spec, leaves, agg_descs, planes_total, filter_planes = state
    leaf_spec, _tree, sums, extremes = spec

    from pinot_tpu.engine.device import get_staged
    from pinot_tpu.engine.dispatch import plan_digest
    from pinot_tpu.engine.kernel import make_packed_bitsliced_kernel

    bsi_cols = sorted(
        {col for (_, col, _, _) in leaf_spec} | {c for (c, _, _) in extremes}
    )
    bsiv_cols = sorted({c for (c, _) in sums})
    all_cols = sorted(set(bsi_cols) | set(bsiv_cols))
    # plane arrays ARE this tier's column layout: the base fwd/dict
    # streams stay host-side (skip_base) unless another query's staging
    # of the same segments backfills them.  The staging-token cache key
    # makes realtime LLC-offset advances invalidate the planes with
    # everything else.
    staged = get_staged(
        live,
        all_cols,
        ctx=ctx,
        skip_base_columns=all_cols,
        bsi_columns=bsi_cols,
        bsiv_columns=bsiv_cols,
        pin=True,  # tier demotion must not race this launch
    )
    from pinot_tpu.engine.residency import RESIDENCY

    try:
        return _dispatch_bitsliced(
            executor, request, live, total_docs, deadline, lane,
            lane_index, staged, spec, leaves, agg_descs, planes_total,
            filter_planes, bsi_cols, bsiv_cols,
            len(live) if scanned is None else len(scanned),
        )
    finally:
        RESIDENCY.unpin(staged.token)


def _dispatch_bitsliced(
    executor,
    request: BrokerRequest,
    live: List[ImmutableSegment],
    total_docs: int,
    deadline: Optional[float],
    lane,
    lane_index: int,
    staged,
    spec,
    leaves,
    agg_descs,
    planes_total: int,
    filter_planes: int,
    bsi_cols,
    bsiv_cols,
    queried: int,
) -> Optional[IntermediateResult]:
    from pinot_tpu.engine.dispatch import plan_digest
    from pinot_tpu.engine.kernel import make_packed_bitsliced_kernel

    for col in bsi_cols:
        if staged.columns[col].bsi is None:
            return None  # staging declined (shape changed underneath)
    for col in bsiv_cols:
        if staged.columns[col].bsiv is None:
            return None

    segs: Dict[str, Any] = {"nd": staged.num_docs_arr}
    dev_bytes = 0
    for col in bsi_cols:
        segs[f"p:{col}"] = staged.columns[col].bsi
        dev_bytes += int(staged.columns[col].bsi.nbytes)
    for col in bsiv_cols:
        segs[f"v:{col}"] = staged.columns[col].bsiv
        dev_bytes += int(staged.columns[col].bsiv.nbytes)

    q_np = _query_inputs(spec, leaves, live, staged.num_segments)
    digest = executor._inputs_digest(q_np)
    pdigest = plan_digest(("bsi", spec))
    cost: Dict[str, float] = {}
    kernel = make_packed_bitsliced_kernel(spec)
    # lane micro-batching (PR 13 tier): the per-leaf bounds/points
    # arrays are plain stackable int32s, so same-spec BSI queries with
    # different literals ride ONE vmapped launch reading the resident
    # planes once — the same amortization the scan kernels get
    batch_spec = None
    exec_info: Dict[str, Any] = {}
    analysis_args = None
    if lane is not None:
        batch_spec = _bsi_batch_spec(executor, spec, staged, q_np, segs)
    if batch_spec is not None:
        # defer the solo upload into the launch closure (executor
        # _device_section idiom): a member that rides a batched launch
        # never uses its own device copy
        args = lambda: (
            segs,
            executor._to_device_inputs(
                q_np, plan=spec, digest=digest, cost=cost
            ),
        )
        analysis_args = (segs, q_np)
    else:
        args = (
            segs,
            executor._to_device_inputs(
                q_np, plan=spec, digest=digest, cost=cost
            ),
        )
    outs = executor._run_kernel(
        kernel, args, spec, staged, digest, None, deadline, pdigest,
        cost=cost, lane=lane, batch_spec=batch_spec, exec_info=exec_info,
        analysis_args=analysis_args,
    )

    partials, matched = _finalize(request, agg_descs, staged, live, outs)
    res = IntermediateResult(
        num_docs_scanned=matched,
        total_docs=total_docs,
        num_segments_queried=queried,
        # the bitwise pass reads words, not rows: planes * n/32 words
        # of 32-bit filter work per leaf plane (the O(W * n/32) claim)
        num_entries_scanned_in_filter=(filter_planes * total_docs) // 32,
        num_entries_scanned_post_filter=matched * max(1, len(agg_descs)),
    )
    res.aggregations = partials
    res.add_cost(
        bytesScanned=dev_bytes,
        deviceBytes=dev_bytes,
        segmentsBitsliced=queried,
        **cost,
    )
    res._device_digest = pdigest
    res._lane_index = lane_index
    res._batch_size = int(exec_info.get("batchSize", 1) or 1)
    m = executor.metrics
    m.meter("filter.bitsliced.planes").mark(planes_total)
    m.meter("filter.bitsliced.fusedAggs").mark(len(agg_descs))
    m.meter("filter.bitsliced.bytes").mark(dev_bytes)
    return res


def _bsi_batch_spec(executor, spec, staged, q_np, segs):
    """BatchSpec for same-spec bit-sliced dispatches (the BSI analog of
    executor._batch_spec): key is (("bsi", spec), staging token, input
    signature) — literal-bucketed spec identity x resident-plane
    identity x structural input identity.  The row budget counts padded
    docs, matching the scan tier's cap, so a batched plane launch can
    never blow the compile-time working set."""
    from pinot_tpu.engine.dispatch import BatchSpec
    from pinot_tpu.engine.kernel import chunk_rows_limit
    from pinot_tpu.engine.packing import batch_input_signature

    limit = chunk_rows_limit()
    rows = max(1, staged.num_segments * staged.n_pad)
    if limit:
        cap = limit // rows
        max_members = 1
        while max_members * 2 <= cap:
            max_members *= 2
    else:
        max_members = 0
    if max_members == 1:
        return None  # one member already fills the budget
    key = (("bsi", spec), staged.token, batch_input_signature(q_np))

    def launch_batched(inputs_list):
        from pinot_tpu.engine.device import to_device_inputs
        from pinot_tpu.engine.kernel import make_packed_batched_bitsliced_kernel
        from pinot_tpu.engine.packing import stack_query_inputs

        bkernel = make_packed_batched_bitsliced_kernel(spec)
        # pad member count to a power of two (repeat member 0, whose
        # extra outputs are never sliced) — compile count stays bounded
        # at log2 distinct batch shapes per spec
        b = len(inputs_list)
        b_pad = 1
        while b_pad < b:
            b_pad *= 2
        if b_pad > b:
            inputs_list = list(inputs_list) + [inputs_list[0]] * (b_pad - b)
        stacked = stack_query_inputs(inputs_list)
        qb = to_device_inputs(stacked)
        return bkernel.fetch, bkernel.dispatch(segs, qb)

    return BatchSpec(key, q_np, launch_batched, max_members=max_members)
