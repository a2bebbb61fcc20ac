"""EXPLAIN: the serving-tier decision records, computed WITHOUT serving.

``build_explain_node`` applies what ``executor.execute`` applies ahead
of the ladder, with the reasons (``prune_explain``'s verdicts, the
star-tree routing), then reads the executor's ladder
(``engine/ladder.py``): the first tier of ``ladder.TIERS`` that accepts
and, where that is the device, the second level's derivations (the
``StaticPlan``, the poison quarantine, the zone-map/full-scan split,
the batch shape).  It walks no order of its own, and returns a JSON-safe
per-server plan node instead of results.

The device tier's derivations normally read a staged table; EXPLAIN
must never stage (a cold EXPLAIN of a 1B-row table must not trigger a
multi-GB H2D transfer) and never launch kernels.  ``_phantom_staged``
therefore builds a metadata-only ``StagedTable`` twin: the same
n_pad/card_pad bucketing, per-segment cards, and role-array PRESENCE
(zero-length sentinels) that real staging would produce — the
derivations read only those, so the phantom yields the IDENTICAL
``StaticPlan`` (hence the identical plan digest and poison key) the
executor would compile, with zero device bytes moved.  A phantom's
derivations are kept nowhere: the executor's prepared-query memo is
neither filled nor counted.

The safety contract (tier-1 guarded): plain EXPLAIN performs zero lane
submissions and marks zero cost meters.
"""
from __future__ import annotations

import math
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from pinot_tpu.common.request import BrokerRequest
from pinot_tpu.engine import config, ladder
from pinot_tpu.engine.context import get_table_context
from pinot_tpu.engine.device import LEDGER, StagedColumn, StagedTable
from pinot_tpu.engine.dispatch import plan_digest
from pinot_tpu.engine.plandigest import plan_shape_digest, plan_shape_summary
from pinot_tpu.engine.pruner import prune_explain, scanned_segments
from pinot_tpu.segment.immutable import ImmutableSegment

# serving-tier name (as it appears in per-segment records) -> cost-
# vector count key, derived from the ONE mapping in engine/results.py
# so EXPLAIN ANALYZE's estimated-vs-actual comparison lines up
# key-for-key with the cost vector ("fullScan" -> "segmentsFullScan")
from pinot_tpu.engine.results import SEGMENT_TIER_NAMES

TIER_COST_KEYS = {name: key for key, name in SEGMENT_TIER_NAMES.items()}


def _json_safe(v: Any) -> Any:
    """numpy scalars/arrays -> plain Python, recursively (the plan node
    rides the tagged wire codec, which knows no numpy)."""
    if isinstance(v, dict):
        return {str(k): _json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_json_safe(x) for x in v]
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.ndarray):
        return [_json_safe(x) for x in v.tolist()]
    if isinstance(v, (np.bool_,)):
        return bool(v)
    return v


_SENTINEL = np.zeros(0, dtype=np.int8)


def _phantom_staged(
    segments: Sequence[ImmutableSegment],
    column_names: Sequence[str],
    raw_cols: Sequence[str],
    gfwd_cols: Sequence[str],
    hll_cols: Sequence[str],
    pad_segments_to: int = 0,
) -> StagedTable:
    """Metadata-only StagedTable twin (module docstring): identical
    shape bucketing + role presence, zero device arrays.  MUST mirror
    ``device.stage_segments``'s metadata computation exactly — the
    resulting StaticPlan (and therefore its digest and poison key) has
    to match what a real execution would build."""
    S = max(len(segments), pad_segments_to)
    n_pad = config.pad_docs(max(seg.num_docs for seg in segments))
    st = StagedTable(
        segment_names=tuple(s.segment_name for s in segments),
        num_segments=S,
        n_pad=n_pad,
        num_docs=tuple(s.num_docs for s in segments) + (0,) * (S - len(segments)),
        num_docs_arr=np.asarray(
            [s.num_docs for s in segments] + [0] * (S - len(segments)),
            dtype=np.int32,
        ),
    )
    for name in sorted(set(column_names)):
        cols = [seg.column(name) for seg in segments]
        meta0 = cols[0].metadata
        cards = tuple(c.dictionary.cardinality for c in cols)
        card_pad = config.pad_card(max(cards))
        sc = StagedColumn(
            name=name,
            stored_type=meta0.data_type.stored_type,
            single_value=meta0.single_value,
            card_pad=card_pad,
            mv_pad=0,
            cards=cards,
        )
        if meta0.single_value:
            # role-array PRESENCE must match stage_segments' conditions:
            # the planner reads only `is not None`
            if name in raw_cols and sc.is_numeric:
                sc.raw = _SENTINEL
            if name in gfwd_cols:
                sc.gfwd = _SENTINEL
            if name in hll_cols:
                sc.hll_rho = _SENTINEL
                sc.hll_bucket = _SENTINEL
        else:
            mv_pad = max(1, max(c.metadata.max_num_multi_values for c in cols))
            sc.mv_pad = config.pad_card(mv_pad)
            if name in raw_cols and sc.is_numeric:
                sc.mv_raw = _SENTINEL
        st.columns[name] = sc
    return st


def _estimate_scan_bytes(
    segments: Sequence[ImmutableSegment], columns: Sequence[str], fraction: float
) -> int:
    """Static byte estimate for a device scan: per-column forward-index
    bytes at the staged integer width, scaled by the zone-map candidate
    fraction (1.0 for a full scan) — the same shape the actual cost
    vector reports."""
    total = 0
    for seg in segments:
        for name in columns:
            col = seg.columns.get(name)
            if col is None:
                continue
            meta = col.metadata
            itemsize = np.dtype(
                config.index_dtype(config.pad_card(max(meta.cardinality, 1)))
            ).itemsize
            rows = seg.num_docs
            if meta.single_value:
                total += rows * itemsize
            else:
                total += rows * max(1, meta.max_num_multi_values) * itemsize
    return int(total * min(max(fraction, 0.0), 1.0))


def _staged_snapshot(table: str, segment_names: Sequence[str]) -> Dict[str, Any]:
    """What of this query's segments is ALREADY resident in HBM, read
    off the PR 6 staging ledger (never stages anything new).  Entries
    must match on BOTH table and segment names: segment names are only
    unique within a table, so name intersection alone would attribute
    another table's staged bytes to this query."""
    from pinot_tpu.engine.plandigest import _raw_table

    wanted = set(segment_names)
    raw = _raw_table(table)
    bytes_total = 0
    columns: set = set()
    entries = 0
    for e in LEDGER.snapshot()["entries"]:
        etable = e.get("table") or ""
        # ledger tables come from segment metadata (physical names);
        # an empty one (metadata without table_name) can only match on
        # segments
        if etable and _raw_table(etable) != raw:
            continue
        if not wanted.intersection(e.get("segments") or ()):
            continue
        entries += 1
        bytes_total += int(e.get("bytes") or 0)
        columns.update((e.get("columns") or {}).keys())
    # per-segment residency tier (engine/residency.py): which of this
    # query's segments sit hot (HBM), warm (host snapshot), cold (disk
    # spool) — anything the manager has never seen is "unstaged".
    # Matching mirrors the ledger rules above: physical table names,
    # empty falls back to segment-name membership.
    from pinot_tpu.engine.residency import RESIDENCY

    tiers = RESIDENCY.segment_tiers(raw, segment_names, raw_match=True)
    residency = {s: tiers.get(s, "unstaged") for s in segment_names}
    return {
        "hbmBytes": bytes_total,
        "stagedTables": entries,
        "columns": sorted(columns),
        "residency": residency,
    }


def _route(executor, request: BrokerRequest):
    """(lane selection or None, the mesh the query would run on, its
    lane): the chip-group routing the serving path applies, so that the
    phantom pads the segment axis for the mesh of the lane this shape
    would execute on."""
    selection = executor.lane_selection(request) if getattr(executor, "lanes", None) is not None else None
    if selection is not None:
        return selection, selection.group.mesh, selection.lane
    return None, executor.mesh, getattr(executor, "lane", None)


def _compile_state(lane, pdigest: str) -> Dict[str, Any]:
    """The ``device.compile`` record of a plan digest on ``lane``."""
    compile_entry = lane.compile_info(pdigest) if lane is not None else None
    if compile_entry is None:
        # never launched here: no analysis exists yet.  The plan ledger
        # can still prove the on-disk cache holds the binary — the first
        # launch would restore, not compile
        from pinot_tpu.engine import compilecache

        state = "persistent" if compilecache.known_plan(pdigest) else "cold"
        return {"state": state, "costAnalysis": "unavailable"}
    # launched here -> warm; a prewarmed/persistent entry that has NOT
    # served yet reports how its executable arrived (the r16 warm-start
    # states)
    state = "warm" if compile_entry.get("launches", 0) > 0 else compile_entry.get("via", "warm")
    compile_info = {"state": state, **compile_entry}
    # static cost-analysis tri-state (utilization plane): a dict once
    # the async analysis landed, explicit "unavailable" when the backend
    # reported nothing, "pending" while it is still running
    if "costAnalysis" not in compile_entry:
        compile_info["costAnalysis"] = "pending"
    elif compile_entry["costAnalysis"] is None:
        compile_info["costAnalysis"] = "unavailable"
    return compile_info


def _device_record(executor, selection, lane, pdigest: str, group_size: int, sharded_plan=None, quarantined=False):
    """The node's ``device`` record: the lane-registered plan's digest
    and compile state, and the mesh decision: which chip-group lane
    executes this shape, the mesh it shards over, and the XLA collectives
    the cross-chip merge lowers to (``sharded_plan`` None, the
    single-chip program: shardAxis/collective None — the per-segment
    combine is fused in-program)."""
    from pinot_tpu.engine.mesh import SEGMENT_AXIS, collective_names

    lanes_obj = getattr(executor, "lanes", None)
    n_lanes = lanes_obj.size if lanes_obj is not None else 1
    return {
        "planDigest": pdigest,
        "compile": _compile_state(lane, pdigest),
        "quarantined": quarantined,
        "mesh": {
            "shape": f"{n_lanes}x{group_size}",
            "lanes": n_lanes,
            "laneIndex": selection.index if selection is not None else 0,
            "shardAxis": SEGMENT_AXIS if sharded_plan is not None else None,
            "collective": collective_names(sharded_plan) if sharded_plan is not None else None,
        },
    }


def _phantom_plan(request: BrokerRequest, normal, ctx, needed, pad_to: int):
    """The device tier's second level against a phantom table: (the
    roles, the phantom, the scratch ``ladder.inputs`` takes on, and
    ``ladder.plan``'s plan, digest and poison key)."""
    roles = ladder.roles(request, normal, ctx)
    phantom = _phantom_staged(
        normal, list(needed) + list(request.referenced_columns()), *roles[:3], pad_segments_to=pad_to
    )
    scratch: Dict[Any, Any] = {}
    return (roles, phantom, scratch) + ladder.plan(request, ctx, phantom, scratch)


def build_explain_node(
    executor,
    segments: Sequence[ImmutableSegment],
    request: BrokerRequest,
    table: str,
    server_name: str,
    plan_stats=None,
    result_cache=None,
) -> Dict[str, Any]:
    """One server's EXPLAIN plan node (module docstring).  ``executor``
    supplies the lane routing AND the live poison-quarantine state;
    ``plan_stats`` (utils/planstats.py) supplies historical estimates;
    ``result_cache`` (engine/rescache.py) answers the device node's
    cacheHit probe without marking hit/miss meters."""
    total_docs = sum(s.num_docs for s in segments)
    records: List[Dict[str, Any]] = []
    tier_counts: Dict[str, int] = {}

    def record(segs, tier: str, reason: str, **extra) -> None:
        for seg in segs:
            tier_counts[TIER_COST_KEYS[tier]] = tier_counts.get(TIER_COST_KEYS[tier], 0) + 1
            records.append(
                dict({"segment": seg.segment_name, "tier": tier, "reason": reason}, **extra)
            )

    # what stands ahead of the ladder, as the executor has it.  Upstream's
    # three verdicts drop a segment from the query; the value verdict's
    # (the leaf, the column and the segment's [min, max]) leaves it among
    # the table's segments and out of every tier's work, and it counts
    # with the pruned; the star-tree routing stands ahead of the value
    # verdict
    _live, star, normal = ladder.routed(segments, request)
    scanned = scanned_segments(normal, request)
    for seg, reason in prune_explain(segments, request):
        if reason is not None and seg not in star:
            record([seg], "pruned", reason)
    record(
        star,
        "starTree",
        "conjunctive-EQ dims + aggregations covered by the "
        "segment's star-tree cube",
    )

    device_info: Optional[Dict[str, Any]] = None
    est_bytes = 0
    if scanned:
        selection, exec_mesh, lane = _route(executor, request)
        ladder_docs, needed, _sel_columns, pad_to = ladder.scope(request, normal, exec_mesh)
        ctx = get_table_context(normal)
        tier, decision, state = ladder.first_accepting(request, normal, ctx, ladder_docs, exec_mesh, scanned)
        # ``normal`` is the table (what is staged, the table context);
        # the records below are of the segments the tier works over
        staged_segs, normal = normal, [normal[i] for i in scanned]
        full_scan_bytes = lambda: _estimate_scan_bytes(normal, needed, 1.0)
        if tier.name == "postings":
            est_bytes = int(decision.get("estMatches", 0)) * (
                decision.get("residuals", 0) + 1
            ) * 8
            record(normal, tier.name, decision["reason"], drivingColumn=decision.get("column"))
        elif tier.name == "bitsliced":
            spec, _leaves, _aggs, planes_total, _fp = state
            est_bytes = (ladder_docs * planes_total) // 8
            record(
                normal,
                tier.name,
                decision["reason"],
                planes=decision.get("planes"),
                planeCounts=decision.get("planeCounts"),
                fusedAggs=decision.get("fusedAggs"),
            )
            # the bit-sliced kernel is a lane-registered device plan
            # like any scan: its digest must match what the real
            # execution hands the lane (run_bitsliced_path), so the
            # compile timeline and poison lookups stay digest-exact
            device_info = _device_record(executor, selection, lane, plan_digest(("bsi", spec)), 1)
        elif tier.name == "host":
            est_bytes = full_scan_bytes()
            why = decision["groupByHostReason"]
            record(
                normal,
                tier.name,
                "planner forces host before staging ("
                + (f"group-by: {why}" if why is not None else "guaranteed sort-pair overflow")
                + ")",
                **({"groupByHostReason": why} if why is not None else {}),
            )
        else:
            _roles, phantom, scratch, plan, pdigest, poison_key = _phantom_plan(request, staged_segs, ctx, needed, pad_to)
            poison = executor.poisoned_entry(poison_key) if plan.on_device else None
            if not plan.on_device:
                est_bytes = full_scan_bytes()
                record(
                    normal,
                    "host",
                    "StaticPlan is device-ineligible (group capacity, "
                    "MV expansion, or pair-overflow guard)",
                )
            else:
                group_size = (
                    selection.group.size
                    if selection is not None
                    else (int(exec_mesh.devices.size) if exec_mesh is not None else 1)
                )
                device_info = _device_record(
                    executor, selection, lane, pdigest, group_size,
                    sharded_plan=plan if exec_mesh is not None else None,
                    quarantined=poison is not None,
                )
                if plan.group_by is not None:
                    device_info["groupBy"] = _group_by_record(request, ctx, plan)
                if plan.selection is not None:
                    device_info["selection"] = _selection_record(plan)
            if poison is not None:
                # HONESTY: the device plan is quarantined, so this
                # query will ACTUALLY serve from the host path — the
                # explain must say so, not report the device tier
                est_bytes = full_scan_bytes()
                record(
                    normal,
                    "host",
                    "device plan quarantined (poisoned): "
                    f"{poison['reason']} — serving via host "
                    f"fallback for {poison['ttlRemainingS']}s more",
                )
            elif plan.on_device:
                q_np, block_ids, scanned_rows = ladder.inputs(
                    request, plan, ctx, staged_segs, phantom, scratch, scanned, exec_mesh)
                # the segments the program runs over, of those staged:
                # L of S, and the scanned ones by name
                count = ladder.launch_count(phantom, q_np)
                device_info["launch"] = {
                    "segments": count,
                    "ofStaged": phantom.num_segments,
                    "scanned": [s.segment_name for s in normal],
                }
                if count != phantom.num_segments:
                    # a launch size is a compile of its own, kept in the
                    # lane's timeline under the plan's digest with L
                    device_info["compile"] = _compile_state(lane, ladder.launch_digest(pdigest, phantom, q_np))
                launched_docs = sum(s.num_docs for s in ladder.launched_segments(staged_segs, q_np) if s is not None)
                if block_ids is not None and scanned_rows is not None:
                    frac = (
                        min(1.0, scanned_rows / launched_docs)
                        if launched_docs
                        else 1.0
                    )
                    est_bytes = _estimate_scan_bytes(normal, needed, frac)
                    record(
                        normal,
                        "zonemap",
                        "zone-map block pruning engages: candidate "
                        f"fraction {frac:.4f} of the launch's rows",
                        candidateFraction=round(frac, 4),
                    )
                else:
                    est_bytes = full_scan_bytes()
                    record(
                        normal,
                        "fullScan",
                        "no selective tier applies: full vmapped "
                        "device scan",
                    )
                # batching decision record (lane micro-batching
                # tier): whether this shape's dispatches would
                # stack with same-plan peers (ladder.batch, the
                # executor's own eligibility), the window/cap that
                # governs formation, and whether the result cache
                # holds this exact query's answer RIGHT NOW.
                cap = lane.batch_max if lane is not None and getattr(lane, "batch_max", 0) > 1 else 0
                batch_shape = ladder.batch(plan, phantom, q_np, block_ids, exec_mesh)
                if batch_shape is not None and batch_shape[1]:
                    cap = min(cap, batch_shape[1])  # the row budget's bound on members
                device_info["batching"] = {
                    "batched": batch_shape is not None and cap > 1,
                    "batchMax": cap,
                    "windowMs": (
                        round(lane.batch_window_s * 1000, 3)
                        if lane is not None
                        else 0.0
                    ),
                    "cacheHit": (
                        result_cache.contains(request, segments, table)
                        if result_cache is not None
                        else False
                    ),
                }

    digest = plan_shape_digest(request)
    estimated: Dict[str, Any] = {
        "source": "static",
        "bytesScanned": int(est_bytes),
    }
    estimated.update({k: v for k, v in tier_counts.items()})
    if plan_stats is not None:
        hist = plan_stats.estimate(digest)
        if hist is not None:
            estimated = dict(hist)
            estimated["source"] = "history"

    node: Dict[str, Any] = {
        "server": server_name,
        "table": table,
        "planDigest": digest,
        "summary": plan_shape_summary(request),
        "numSegments": len(segments),
        "totalDocs": int(total_docs),
        "tierCounts": tier_counts,
        "segments": records,
        "staged": _staged_snapshot(table, [s.segment_name for s in segments]),
        "estimatedCost": estimated,
        "generatedAtMs": round(time.time() * 1000, 3),
    }
    expressions = [a for a in request.aggregations if a.expr is not None]
    if expressions:
        # arithmetic inside an aggregate, as the engine reads it: the
        # canonical text (also the result column's name) and its leaves
        node["expressions"] = [
            {"aggregate": a.function, "expression": a.column, "columns": list(a.columns)}
            for a in expressions
        ]
    if device_info is not None:
        node["device"] = device_info
    return _json_safe(node)


def _group_by_record(request: BrokerRequest, ctx, plan) -> Dict[str, Any]:
    """A device group-by as the planner sized and lowered it: the planned
    key space (``plan.group_capacity``: the product of the keys' table
    cardinalities, what the group state holds a cell for, or what sends
    the plan to the 'runs' lowering), the space the filter's own leaves on
    the keys leave where it is smaller, and the answers of
    ``kernel.groupby_lowering`` and ``groupby_operands``, which the
    launch's tags and marks repeat."""
    from pinot_tpu.engine.kernel import groupby_lowering, groupby_operands

    out = {
        "keySpaceCells": int(plan.group_by.capacity),
        "lowering": groupby_lowering(plan),
        "operands": groupby_operands(plan),
    }
    left = _filtered_key_space(request, ctx)
    if left != plan.group_by.capacity:
        out["filteredKeySpaceCells"] = left
    return out


def _selection_record(plan) -> Dict[str, Any]:
    """A device selection as the planner sized and lowered it: the
    candidates a segment (k = offset + size), the sort columns' table
    cardinalities (the key's radices: a STRING column orders by the table
    dictionary's ordinals), whether their product packs into one key, and
    the answer of ``kernel.selection_lowering``, which the launch's tag
    and mark repeat."""
    from pinot_tpu.engine.kernel import selection_lowering

    sel = plan.selection
    return {
        "lowering": selection_lowering(plan),
        "k": int(sel.k),
        "sortColumns": list(sel.sort_columns),
        "sortCardinalities": [int(g) for g in sel.sort_gcards],
        "keySpace": math.prod(int(g) for g in sel.sort_gcards),
        "packed": bool(sel.packed),
    }


def _filtered_key_space(request: BrokerRequest, ctx) -> int:
    """The product, over the group columns, of the column's table values
    that pass the filter's EQ, IN and RANGE leaves on that column (the
    leaves of a root-level AND, or a filter of one leaf: what
    ``invindex_path._decompose`` takes as driving candidates).  Printed,
    never planned by: ``c_city IN (a, b) ... GROUP BY c_city`` reads 2 of
    250, and a predicate on a column that determines a key (``c_nation =
    x`` leaves ten cities) is not followed, since the table keeps no such
    dependency."""
    from pinot_tpu.engine.invindex_path import _decompose
    from pinot_tpu.engine.plan import match_table

    leaves = (_decompose(request.filter) or ((), ()))[0] if request.filter is not None else ()
    cells = 1
    for c in request.group_by.columns:
        gdict = ctx.column(c).global_dict
        passing = np.ones(max(gdict.cardinality, 1), dtype=bool)
        for leaf in leaves:
            if leaf.column == c:
                passing &= match_table(leaf, gdict, passing.size)
        cells *= max(int(passing.sum()), 1)
    return cells


# ---------------------------------------------------------------------------
# Prewarm compile specs (r16 warm-start plane): the phantom machinery
# above, driven one step further — instead of *reporting* the StaticPlan
# a query would compile, hand back an AOT-lowerable (kernel, avals) pair
# so the prewarm worker (server/prewarm.py) can pay the XLA compile off
# the serving path.  Still zero real staging: segment arrays enter the
# lowering as ShapeDtypeStructs that mirror ``device.stage_segments``'s
# shapes/dtypes exactly (including the skip-base elisions), so the
# compiled executable — and the persistent-cache entry it writes — is
# the one the first serving launch of this shape will ask for.
# ---------------------------------------------------------------------------


def _phantom_segment_avals(
    phantom: StagedTable, needed, ctx, skip_base
) -> Dict[str, Any]:
    """ShapeDtypeStruct twin of ``device.segment_arrays(staged, needed)``
    for a phantom staged table: same keys, same shapes, same dtypes as
    real staging would upload — no device bytes."""
    import jax

    S, n_pad = phantom.num_segments, phantom.n_pad
    fdt = np.dtype(config.np_float_dtype())
    avals: Dict[str, Any] = {}
    has_rows = False
    for name in needed:
        col = phantom.columns.get(name)
        if col is None:
            continue
        idt = np.dtype(config.index_dtype(col.card_pad))
        sb = name in skip_base and col.single_value
        if col.single_value:
            if not sb:
                avals[f"{name}.fwd"] = jax.ShapeDtypeStruct((S, n_pad), idt)
                has_rows = True
        else:
            avals[f"{name}.mv"] = jax.ShapeDtypeStruct((S, n_pad, col.mv_pad), idt)
            avals[f"{name}.mvc"] = jax.ShapeDtypeStruct(
                (S, n_pad), np.dtype(config.count_dtype(col.mv_pad))
            )
            has_rows = True
        if col.is_numeric and not sb:
            avals[f"{name}.dict"] = jax.ShapeDtypeStruct((S, col.card_pad), fdt)
        if col.raw is not None:
            avals[f"{name}.raw"] = jax.ShapeDtypeStruct((S, n_pad), fdt)
            has_rows = True
        if col.gfwd is not None:
            gdt = np.dtype(
                config.index_dtype(
                    config.pad_card(ctx.column(name).global_cardinality)
                )
            )
            avals[f"{name}.gfwd"] = jax.ShapeDtypeStruct((S, n_pad), gdt)
            has_rows = True
        if col.hll_bucket is not None:
            avals[f"{name}.hllb"] = jax.ShapeDtypeStruct((S, n_pad), np.dtype(np.uint8))
            avals[f"{name}.hllr"] = jax.ShapeDtypeStruct((S, n_pad), np.dtype(np.uint8))
            has_rows = True
        if col.mv_raw is not None:
            avals[f"{name}.mvraw"] = jax.ShapeDtypeStruct((S, n_pad, col.mv_pad), fdt)
            has_rows = True
    if has_rows:
        avals["num_docs"] = jax.ShapeDtypeStruct((S,), np.dtype(np.int32))
    else:
        avals["valid"] = jax.ShapeDtypeStruct((S, n_pad), np.dtype(np.bool_))
    return avals


def build_prewarm_spec(
    executor,
    segments: Sequence[ImmutableSegment],
    request: BrokerRequest,
) -> Optional[Dict[str, Any]]:
    """AOT prewarm spec for one query shape, or None when the shape has
    nothing lowerable to prewarm.

    Reads the executor's ladder (as ``build_explain_node`` does) and
    returns ``{"planDigest", "lane", "compile"}`` where ``compile()``
    pays the XLA compile of the program the first serving launch would
    otherwise pay cold.  None is a *skip*, not a failure:

    - host/postings/star-tree-only shapes compile no device kernel, and
      the bit-sliced tier compiles its own (tiny) kernel per spec;
    - mesh-sharded shapes need device-placed lowering (not supported —
      sharded servers fall back to persistent-cache classification);
    - chunked dispatch sequences are many programs, not one lowering
      (``kernel.plan_program`` hands back no ``.lower``);
    - shapes already in the lane's compile timeline are warm already.
    """
    # the engine's segments, and among them those the filter can match:
    # the program compiled is the one the ladder derives for the launch
    # over them, at its segment count
    _live, _star, normal = ladder.routed(segments, request)
    scanned = scanned_segments(normal, request)
    _selection, exec_mesh, lane = _route(executor, request)
    if not scanned or exec_mesh is not None or lane is None:
        return None
    total_docs, needed, _sel_columns, pad_to = ladder.scope(request, normal, exec_mesh)
    ctx = get_table_context(normal)
    if ladder.first_accepting(request, normal, ctx, total_docs, exec_mesh, scanned)[0].name != "device":
        return None
    roles, phantom, scratch, plan, pdigest, _poison_key = _phantom_plan(request, normal, ctx, needed, pad_to)
    if not plan.on_device:
        return None
    q_np, block_ids, _rows = ladder.inputs(request, plan, ctx, normal, phantom, scratch, scanned, exec_mesh)
    # the plan's digest with the launch's L: what the lane's timeline
    # keeps a compile under, so a text whose literals give a new launch
    # size is prewarmed though its plan has launched at another
    ldigest = ladder.launch_digest(pdigest, phantom, q_np)
    if lane.compile_info(ldigest) is not None:
        return None  # already cold/warm/prewarmed here: nothing to pay
    # the builders keep a handle a plan: this is the SAME callable the
    # serving launch will call, so an in-process AOT compile also seeds
    # the persistent cache entry serving reads
    kernel = ladder.program(plan, phantom, q_np, block_ids, exec_mesh)
    if not hasattr(kernel, "lower"):
        return None
    lower_args = (_phantom_segment_avals(phantom, needed, ctx, roles[3]), q_np)
    if block_ids is not None:
        import jax

        lower_args += (jax.ShapeDtypeStruct(block_ids.shape, block_ids.dtype),)

    def compile_now() -> None:
        kernel.lower(*lower_args).compile()

    return {"planDigest": ldigest, "lane": lane, "compile": compile_now}
