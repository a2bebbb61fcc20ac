"""Query planning: BrokerRequest -> (StaticPlan, QueryInputs).

The reference's plan maker (``InstancePlanMakerImplV2.java:40``) builds a
virtual-call operator tree per segment.  Here planning splits a query
into:

- **StaticPlan** — a hashable description of the kernel's *structure*:
  filter tree shape, leaf modes, aggregation list, group-by strides and
  capacity, selection spec.  It is the jit-cache key: two queries with
  the same StaticPlan and array shapes share one compiled XLA program.

- **QueryInputs** — per-segment *data* for that structure, all computed
  host-side in O(cardinality) per column: predicate match tables in
  dictId space (the PredicateEvaluator analog — an EQ/IN/RANGE/REGEX
  predicate becomes a ``bool[card]`` table; the device then does ONE
  gather per leaf, which is the vectorized inverted index), global-id
  remap tables for group-by/distinct/percentile, HLL (bucket, rho)
  tables per dictionary entry.

Filter leaf modes:
  SV      — mask = table[fwd]
  MV_ANY  — mask = any(table[mv] & mv_valid)         (positive predicates)
  MV_NONE — mask = ~any(member[mv] & mv_valid)       (NOT / NOT_IN)
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field, replace
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pinot_tpu.common.request import (
    AggregationInfo,
    BrokerRequest,
    FilterOperator,
    FilterQueryTree,
    RangeSpec,
)
from pinot_tpu.common.schema import DataType
from pinot_tpu.engine import config
from pinot_tpu.engine import hll as hll_mod
from pinot_tpu.engine.context import TableContext
from pinot_tpu.engine.device import StagedTable
from pinot_tpu.segment.dictionary import Dictionary


# ---------------------------------------------------------------------------
# Static plan
# ---------------------------------------------------------------------------

SV, MV_ANY, MV_NONE = "sv", "mv_any", "mv_none"


@dataclass(frozen=True)
class StaticLeaf:
    column: str
    mode: str  # SV | MV_ANY | MV_NONE
    # Gathers through big tables are slow on TPU, but dictIds are
    # order-preserving, so most predicates become vector compares:
    #   docrange    — (iota >= lo_doc) & (iota < hi_doc): a RANGE/EQ on
    #                 a column sorted in every segment is a contiguous
    #                 doc interval found host-side by binary search; the
    #                 kernel never reads the column at all (the
    #                 SortedInvertedIndexBasedFilterOperator analog)
    #   interval    — (fwd >= lo) & (fwd < hi), bounds from q["bounds"]
    #   points      — any(fwd == pts[k]) for small IN/EQ sets
    #   points_none — complement of points (NOT / NOT_IN)
    #   table       — bool[card] gather (regex, large IN lists)
    eval_kind: str = "table"
    k_pad: int = 0  # static points-array length (pow2-padded)


@dataclass(frozen=True)
class StaticAgg:
    func: str  # full function name e.g. "sum", "summv"
    base: str  # base function e.g. "sum"
    # "*" for count(*), a column, or a compound expression's canonical
    # text (common/request.py expr_text): the text, constants and all,
    # is of the plan's shape, so that two queries that differ in one
    # operator or one constant share no program and no digest
    column: str
    is_mv: bool
    # device state kind: scalar | pair | presence | hist | hll
    kind: str
    # static size of the value-state axis (presence/hist), 0 otherwise
    gcard_pad: int = 0
    # read values from the staged raw array (streaming) instead of
    # gathering dict_vals[fwd] — big-dictionary gathers are slow on TPU
    use_raw: bool = False
    # exact distinct via device sort-dedup of (group, valueId) pairs
    # instead of the dense [capacity, gcard_pad] presence holder — the
    # high-cardinality path that keeps distinctcount on-chip where the
    # reference switches to map-based storage
    # (DefaultGroupKeyGenerator.java:60-63)
    sort_pairs: bool = False
    # distinctcounthll lowered to a presence contraction: HLL registers
    # depend only on the DISTINCT value set, so for dictionary columns
    # with modest global cardinality the device computes per-(group,
    # globalDictId) occupancy (K = cap * gcard_pad) and finalize maps
    # present ids -> registers via the global dict's (bucket, rho)
    # tables — bit-identical registers at a fraction of the FLOPs of
    # the direct (group, bucket, rho) contraction (K = cap * 16384)
    hll_from_presence: bool = False
    # the tree of a compound expression under sum or avg, evaluated per
    # row from its leaf columns (kernel._row_values); None for one column.
    # Not in the repr, which ``column`` already tells apart: a plan
    # without an expression keeps the digest and the program name it had
    expr: Optional[tuple] = field(default=None, repr=False)

    @property
    def argument(self) -> tuple:
        return self.expr if self.expr is not None else ("col", self.column)


@dataclass(frozen=True)
class StaticGroupBy:
    columns: Tuple[str, ...]
    col_is_mv: Tuple[bool, ...]
    gcards: Tuple[int, ...]  # global cardinalities (strides derive from these)
    capacity: int  # dense holder size = prod(gcards), device path only
    top_n: int
    # per column: read staged global-id fwd (gfwd) instead of gathering
    # remap[fwd] on device (remap gathers are slow for big dictionaries)
    use_gfwd: Tuple[bool, ...] = ()


@dataclass(frozen=True)
class StaticSelection:
    columns: Tuple[str, ...]
    sort_columns: Tuple[str, ...]
    sort_ascending: Tuple[bool, ...]
    sort_gcards: Tuple[int, ...]  # global cards = composite-key radices
    k: int  # per-segment candidates = offset + size
    # True -> sort key packs into one integer (radix product fits key dtype,
    # lax.top_k path); False -> multi-operand lexicographic lax.sort path.
    packed: bool = True
    use_gfwd: Tuple[bool, ...] = ()  # per sort column, as StaticGroupBy


@dataclass(frozen=True)
class StaticPlan:
    # filter tree encoded as nested tuples: ("leaf", i) | ("and"|"or", (...))
    filter_tree: Optional[tuple]
    leaves: Tuple[StaticLeaf, ...]
    aggs: Tuple[StaticAgg, ...]
    group_by: Optional[StaticGroupBy]
    selection: Optional[StaticSelection]
    on_device: bool  # False -> host (numpy) fallback path


def group_capacity(request, ctx) -> int:
    """Dense group-key space: product of the group columns' global
    cardinalities — the ONE definition build_static_plan and the
    pre-staging host check share."""
    cap = 1
    for c in request.group_by.columns:
        cap *= max(ctx.column(c).global_cardinality, 1)
    return cap


def group_runs_host_reason(request, capacity: int, mv_key: bool = False, mesh: bool = False) -> Optional[str]:
    """Why a group-by over ``capacity`` keys is answered by the host, by
    name, or None where the device answers it: through a dense holder up
    to ``MAX_GROUP_CAPACITY`` keys, through the runs lowering above
    (``kernel.groupby_lowering`` 'runs': the rows' global ids sorted, a
    run a group, no state of ``capacity`` cells anywhere), which takes
    ``count``, ``sum`` and ``avg`` under a TOP n, single-value keys, one
    chip.  Decided from the request alone, before anything is staged;
    ``plan_forced_host``, ``build_static_plan``, EXPLAIN, the
    ``groupby.forcedHost.<name>`` meter and the benchmark's generator
    (``benchmark/hits_topusers_table.py``, by this name) all ask here."""
    if capacity > config.max_key_space():
        return "keySpace"  # no key dtype holds the mixed-radix key (2^30 without x64)
    if capacity <= config.MAX_GROUP_CAPACITY:
        return None
    for a in request.aggregations:
        if a.base_function not in ("count", "sum", "avg"):
            return f"aggregate:{a.base_function}"  # min, max, minmaxrange, distinctcount*, percentile*: no run form yet
    if mv_key:
        return "multiValueKey"  # a row of several keys is several rows of the sort
    if request.group_by.top_n <= 0:
        return "noTopN"  # nothing to trim by: every group would come back
    if mesh:
        return "mesh"  # PINOT_TPU_MESH_SHAPE: the sort is one chip's; the sharded path keeps the host's answer
    from pinot_tpu.engine.kernel import _SORTED_COLS_MAX

    summed = {a.column for a in request.aggregations if a.base_function in ("sum", "avg")}
    entries = {a.column for a in request.aggregations if a.is_mv and a.base_function in ("count", "avg")}
    if len(summed) + len(entries) > _SORTED_COLS_MAX:
        return "measures"  # the sort carries at most that many float columns beside the key
    return None


def group_by_host_reason(request, ctx, mesh: bool = False) -> Optional[str]:
    """``group_runs_host_reason`` of a request over ``ctx``'s segments."""
    mv_key = any(not ctx.segments[0].column(c).metadata.single_value for c in request.group_by.columns)
    return group_runs_host_reason(request, group_capacity(request, ctx), mv_key, mesh)


def value_state_sort_pairs(kind: str, gcard_pad: int, cap: Optional[int]) -> bool:
    """Whether a value-state agg (presence/hist/hll) leaves the dense
    holder for the pair-sort path: per-agg state too big, or (grouped)
    the [capacity, state] product too big.  Shared by build_static_plan
    and plan_forced_host so the two can never drift."""
    if kind in ("presence", "hist") and gcard_pad > config.MAX_VALUE_STATE:
        return True
    if cap is not None:
        state = gcard_pad if kind != "hll" else config.HLL_M
        return cap * state > config.MAX_VALUE_STATE * 4
    return False


def plan_forced_host(request, ctx, mesh: bool = False) -> bool:
    """Host-path decisions decidable BEFORE staging — a strict subset of
    the ``on_device = False`` conditions ``build_static_plan`` applies
    (via the same shared predicates above).  The executor consults this
    first so a query that can only run on the host never pays device
    staging (at north-star scale that's a 1GB+ transfer for nothing).
    ``mesh``: the query would run sharded over a mesh."""
    try:
        cap = group_capacity(request, ctx) if request.is_group_by else None
        if cap is not None and group_by_host_reason(request, ctx, mesh) is not None:
            return True
        if request.filter is None:
            for a in request.aggregations:
                if a.column == "*":
                    continue
                if _agg_kind(a.base_function) not in ("presence", "hist"):
                    continue
                gcard = ctx.column(a.column).global_cardinality
                if gcard <= config.DISTINCT_PAIR_CAP:
                    continue
                # with no filter every dictionary entry lands in >= 1
                # (group, valueId) pair, so a sort-pairs agg at this
                # cardinality is guaranteed to overflow the device
                # buffer (the same condition build_static_plan applies)
                if value_state_sort_pairs(
                    _agg_kind(a.base_function), config.pad_value_card(gcard), cap
                ):
                    return True
    except KeyError:
        return False  # unknown column: let the normal path raise properly
    return False


def hll_lowers_to_presence(request, ctx, column: str) -> bool:
    """Whether an SV distinctcounthll lowers to a presence contraction
    (see StaticAgg.hll_from_presence).  Shared by the planner and the
    executor's staging-role decision (gfwd stream vs per-row HLL
    streams) — the two MUST agree or the kernel reads missing arrays.

    Presence wins when the per-group value state (gcard_pad) is smaller
    than the direct register state (HLL_M * 64 rho lanes); the dense
    holder must also fit the same cap the presence guard applies."""
    import os

    if os.environ.get("PINOT_TPU_HLL_PRESENCE", "1") == "0":
        return False  # A/B kill switch: force the per-row register streams
    # a segment's own dictionary bounds the table's from below: a column
    # of millions of ids is decided without the union of its dictionaries
    # (ctx.column builds the global dictionary and a remap a segment:
    # seconds, and hundreds of MB, at 17.6M users over twelve segments)
    if max(seg.column(column).metadata.cardinality for seg in ctx.segments) > config.HLL_M * 64:
        return False
    gcard_pad = config.pad_value_card(ctx.column(column).global_cardinality)
    if gcard_pad > config.HLL_M * 64:
        return False
    cap = 1
    if request.is_group_by:
        for c in request.group_by.columns:
            cap *= max(ctx.column(c).global_cardinality, 1)
    return cap * gcard_pad <= config.MAX_VALUE_STATE * 4


def _agg_kind(base: str) -> str:
    if base in ("count", "sum", "min", "max"):
        return "scalar"
    if base in ("avg", "minmaxrange"):
        return "pair"
    if base == "distinctcount":
        return "presence"
    if base in ("distinctcounthll", "fasthll"):
        return "hll"
    if base.startswith("percentile"):
        return "hist"
    raise ValueError(f"unknown aggregation {base!r}")


_MAX_POINTS = 16  # IN lists up to this size evaluate as compares
_MAX_RUNS = 64  # match tables with <= this many dictId runs evaluate as interval unions


# regex tables are the one plan-time cost that SCANS a dictionary (re
# over every value); identical regex leaves across queries hit this
# LRU instead, keyed by segment identity so reloads can't alias
_regex_tables: "OrderedDict[tuple, np.ndarray]" = OrderedDict()


def cached_match_table(
    leaf_node, d: Dictionary, card_pad: int, cache_key: Optional[tuple]
) -> np.ndarray:
    """``match_table`` with the regex LRU in front — regex is the only
    operator whose table costs a full dictionary scan.  Raw (pre-
    complement) tables key under a distinct tag so they can never alias
    ``_effective_table`` entries."""
    if cache_key is None or leaf_node.operator != FilterOperator.REGEX:
        return match_table(leaf_node, d, card_pad)
    key = ("raw", cache_key, card_pad, tuple(leaf_node.values))
    cached = _regex_tables.get(key)
    if cached is not None:
        _regex_tables.move_to_end(key)
        return cached
    t = match_table(leaf_node, d, card_pad)
    _regex_tables[key] = t
    if len(_regex_tables) > 256:
        _regex_tables.popitem(last=False)
    return t


def _effective_table(
    leaf_node,
    mode: str,
    d: Dictionary,
    card_pad: int,
    true_card: int,
    cache_key: Optional[tuple] = None,
) -> np.ndarray:
    """The table the kernel would read for this leaf: SV NOT/NOT_IN
    bakes the complement (kernel negates MV_NONE after the
    any-reduce).  Shared by plan-time run counting and input build so
    they can never disagree."""
    key = None
    if cache_key is not None and leaf_node.operator == FilterOperator.REGEX:
        key = (cache_key, mode, card_pad, true_card, tuple(leaf_node.values))
        cached = _regex_tables.get(key)
        if cached is not None:
            _regex_tables.move_to_end(key)
            return cached
    t = match_table(leaf_node, d, card_pad)
    if mode == SV and leaf_node.operator in (FilterOperator.NOT, FilterOperator.NOT_IN):
        flipped = np.zeros(card_pad, dtype=bool)
        flipped[:true_card] = ~t[:true_card]
        t = flipped
    if key is not None:
        _regex_tables[key] = t
        if len(_regex_tables) > 256:
            _regex_tables.popitem(last=False)
    return t


def _table_runs(t: np.ndarray):
    """Maximal True runs of a bool table -> [(lo, hi)) dictId ranges."""
    if not t.any():
        return []
    d = np.diff(t.astype(np.int8))
    starts = list(np.nonzero(d == 1)[0] + 1)
    ends = list(np.nonzero(d == -1)[0] + 1)
    if t[0]:
        starts.insert(0, 0)
    if t[-1]:
        ends.append(t.size)
    return list(zip(starts, ends))


def _pad_pow2(k: int) -> int:
    p = 1
    while p < k:
        p *= 2
    return p


def _leaf_eval_kind(node: FilterQueryTree) -> Tuple[str, int]:
    op = node.operator
    if op == FilterOperator.RANGE:
        return "interval", 0
    if op in (FilterOperator.EQUALITY, FilterOperator.IN):
        k = len(node.values)
        if 0 < k <= _MAX_POINTS:
            return "points", _pad_pow2(k)
    if op in (FilterOperator.NOT, FilterOperator.NOT_IN):
        k = len(node.values)
        if 0 < k <= _MAX_POINTS:
            return "points_none", _pad_pow2(k)
    return "table", 0


def build_static_plan(
    request: BrokerRequest,
    ctx: TableContext,
    staged: StagedTable,
    scratch: Optional[Dict[Any, Any]] = None,
) -> StaticPlan:
    """``scratch`` (optional dict the executor threads into
    build_query_inputs) caches plan-time effective match tables so a
    regex never scans a dictionary twice per query."""
    # ---- filter -----------------------------------------------------
    leaves: List[StaticLeaf] = []

    def encode(node: FilterQueryTree) -> tuple:
        if node.is_leaf:
            # mode from segment metadata, not the staged column: a
            # docrange-only column may be dropped from staging entirely
            if ctx.segments[0].column(node.column).metadata.single_value:
                mode = SV
            elif node.operator in (FilterOperator.NOT, FilterOperator.NOT_IN):
                mode = MV_NONE
            else:
                mode = MV_ANY
            eval_kind, k_pad = _leaf_eval_kind(node)
            if eval_kind == "table":
                # gathers through big match tables serialize on TPU; a
                # table that is a FEW contiguous dictId runs (regex on
                # ordered values, big IN lists over ranges) evaluates as
                # a vectorized interval union instead.  Values-based
                # operators bound their run count by the value count
                # (complements add one run) without building tables;
                # only regex pays a plan-time table scan.
                if node.operator != FilterOperator.REGEX:
                    max_runs = len(node.values) + 1
                else:
                    max_runs = 0
                    for si, seg in enumerate(ctx.segments):
                        scol = seg.column(node.column)
                        stg = staged.column(node.column)
                        t = _effective_table(
                            node, mode, scol.dictionary, stg.card_pad, stg.cards[si],
                            cache_key=(seg.segment_name, seg.metadata.crc, node.column),
                        )
                        if scratch is not None:
                            scratch[(id(node), si)] = t
                        max_runs = max(max_runs, len(_table_runs(t)))
                if max_runs <= _MAX_RUNS:
                    eval_kind, k_pad = "runs", _pad_pow2(max(max_runs, 1))
            if (
                mode == SV
                and (
                    eval_kind == "interval"
                    or (eval_kind == "points" and len(node.values) == 1
                        and node.operator == FilterOperator.EQUALITY)
                )
                and all(
                    seg.column(node.column).metadata.is_sorted
                    for seg in ctx.segments
                )
            ):
                # sorted in every segment: the predicate is one doc
                # interval per segment — no column read in the kernel
                eval_kind, k_pad = "docrange", 0
            leaves.append(
                StaticLeaf(
                    column=node.column, mode=mode, eval_kind=eval_kind, k_pad=k_pad
                )
            )
            return ("leaf", len(leaves) - 1)
        op = "and" if node.operator == FilterOperator.AND else "or"
        return (op, tuple(encode(c) for c in node.children))

    tree = encode(request.filter) if request.filter is not None else None

    on_device = True

    # ---- aggregations ----------------------------------------------
    aggs: List[StaticAgg] = []
    for a in request.aggregations:
        base = a.base_function
        kind = _agg_kind(base)
        gcard_pad = 0
        sort_pairs = False
        hll_from_presence = False
        if (
            kind == "hll"
            and a.column != "*"
            and staged.column(a.column).single_value
            and hll_lowers_to_presence(request, ctx, a.column)
        ):
            kind = "presence"
            hll_from_presence = True
        if kind in ("presence", "hist"):
            gcol = ctx.column(a.column)
            gcard_pad = config.pad_value_card(gcol.global_cardinality)
            if value_state_sort_pairs(kind, gcard_pad, None):
                # dense state would not fit: sort the (group, valueId)
                # pairs on device instead — dedup covers distinctcount,
                # run-length counts cover exact percentile histograms
                sort_pairs = True
        is_mv = a.is_mv
        if any(not staged.column(c).single_value for c in a.columns):
            is_mv = True
        # every leaf of the argument streams its staged raw values, or
        # every leaf gathers dict[fwd] (ladder._role_columns)
        use_raw = (
            a.column != "*"
            and not is_mv
            and all(staged.column(c).raw is not None for c in a.columns)
        )
        aggs.append(
            StaticAgg(
                func=a.function,
                base=base,
                column=a.column,
                is_mv=is_mv,
                kind=kind,
                gcard_pad=gcard_pad,
                use_raw=use_raw,
                sort_pairs=sort_pairs,
                hll_from_presence=hll_from_presence,
                expr=a.expr,
            )
        )

    # ---- group-by ---------------------------------------------------
    group_by: Optional[StaticGroupBy] = None
    if request.is_group_by:
        cols = tuple(request.group_by.columns)
        col_is_mv = tuple(not staged.column(c).single_value for c in cols)
        gcards = tuple(ctx.column(c).global_cardinality for c in cols)
        cap = group_capacity(request, ctx)
        if group_by_host_reason(request, ctx, mesh=staged.sharding is not None) is not None:
            on_device = False
        # value-state aggs need [capacity, gcard] holders — cap the
        # product; presence escapes to the sort-dedup path instead of
        # leaving the device
        for ai, a in enumerate(aggs):
            if a.sort_pairs:
                continue
            if a.kind in ("presence", "hist", "hll"):
                if value_state_sort_pairs(a.kind, a.gcard_pad, cap):
                    # every value-state kind sorts instead of leaving
                    # the device: presence dedups, hist counts runs,
                    # hll packs (bucket, rho) into the pair gid
                    aggs[ai] = replace(a, sort_pairs=True)
        for a in aggs:
            # the finalize paths for hll_from_presence handle only the
            # dense holder (hll_lowers_to_presence admits exactly the
            # shapes the presence guards keep dense)
            assert not (a.hll_from_presence and a.sort_pairs), a
        group_by = StaticGroupBy(
            columns=cols,
            col_is_mv=col_is_mv,
            gcards=gcards,
            capacity=int(cap),
            top_n=request.group_by.top_n,
            use_gfwd=tuple(
                not mv and staged.column(c).gfwd is not None
                for c, mv in zip(cols, col_is_mv)
            ),
        )
        # MV group-by expansion blowup guard
        expansion = 1
        for c, mv in zip(cols, col_is_mv):
            if mv:
                expansion *= staged.column(c).mv_pad
        if expansion > 64:
            on_device = False

    # Guaranteed sort-pairs overflow: the global dictionary holds only
    # values PRESENT in the data, so with no filter every dict entry
    # lands in >= 1 (group, valueId) pair — more unique pairs than the
    # device compaction buffer can return.  Skip the doomed device sort
    # (staging + compile + a 134M-row sort at north-star scale) and go
    # straight to the host path the overflow would reach anyway.
    if request.filter is None:
        for a in aggs:
            if (
                a.sort_pairs
                and a.kind in ("presence", "hist")
                and ctx.column(a.column).global_cardinality
                > config.DISTINCT_PAIR_CAP
            ):
                on_device = False

    # ---- selection --------------------------------------------------
    selection: Optional[StaticSelection] = None
    if request.is_selection:
        sel = request.selection
        cols = tuple(sel.columns) if sel.columns and sel.columns != ["*"] else ("*",)
        sort_cols = tuple(s.column for s in sel.sorts)
        sort_asc = tuple(s.ascending for s in sel.sorts)
        k = min(sel.offset + sel.size, staged.n_pad)
        # Composite sort key packs into one integer only when the radix
        # product fits the key dtype; wider key spaces stay on device via
        # multi-operand lexicographic lax.sort (no host fallback needed).
        sort_gcards = tuple(max(ctx.column(c).global_cardinality, 1) for c in sort_cols)
        space = 1
        for g in sort_gcards:
            space *= g
        selection = StaticSelection(
            columns=cols,
            sort_columns=sort_cols,
            sort_ascending=sort_asc,
            sort_gcards=sort_gcards,
            k=int(k),
            packed=space <= config.max_key_space(),
            use_gfwd=tuple(
                staged.column(c).single_value and staged.column(c).gfwd is not None
                for c in sort_cols
            ),
        )

    return StaticPlan(
        filter_tree=tree,
        leaves=tuple(leaves),
        aggs=tuple(aggs),
        group_by=group_by,
        selection=selection,
        on_device=on_device,
    )


# ---------------------------------------------------------------------------
# Match tables (host-side predicate evaluation in dictId space)
# ---------------------------------------------------------------------------


def _coerce(literal: str, stored: DataType) -> Any:
    return stored.convert(literal)


def _doc_bound(fwd: np.ndarray, dict_id: int) -> int:
    """First doc index with fwd >= dict_id on a sorted column.

    The scalar is cast to the forward index's (narrow) dtype before the
    binary search — a plain Python int makes numpy promote-and-copy the
    whole array (250us on a 250k-row uint16 column vs ~1us)."""
    if dict_id <= 0:
        return 0
    if np.issubdtype(fwd.dtype, np.integer) and dict_id > int(np.iinfo(fwd.dtype).max):
        return int(fwd.size)
    return int(np.searchsorted(fwd, np.asarray(dict_id, dtype=fwd.dtype), "left"))


def leaf_interval(node: FilterQueryTree, dictionary: Dictionary) -> Tuple[int, int]:
    """Half-open [lo, hi) dictId interval satisfying a RANGE leaf —
    dictIds are order-preserving, so range predicates are interval
    compares in dictId space (no table, no gather)."""
    stored = dictionary.stored_type
    card = dictionary.cardinality
    r = node.range_spec or RangeSpec()
    lo = 0
    hi = card
    if r.lower is not None and r.lower != "*":
        v = _coerce(r.lower, stored)
        i = dictionary.insertion_index(v)
        if r.include_lower:
            lo = i
        else:
            lo = i + 1 if (i < card and dictionary._eq(dictionary.values[i], v)) else i
    if r.upper is not None and r.upper != "*":
        v = _coerce(r.upper, stored)
        i = dictionary.insertion_index(v)
        if r.include_upper:
            hi = i + 1 if (i < card and dictionary._eq(dictionary.values[i], v)) else i
        else:
            hi = i
    return lo, max(lo, hi)


def leaf_points(node: FilterQueryTree, dictionary: Dictionary, k_pad: int) -> np.ndarray:
    """dictIds of a small EQ/IN/NOT_IN value set, padded with -1 (which
    never matches a forward index)."""
    stored = dictionary.stored_type
    pts = np.full(k_pad, -1, dtype=np.int32)
    j = 0
    for v in node.values:
        i = dictionary.index_of(_coerce(v, stored))
        if i >= 0:
            pts[j] = i
            j += 1
    return pts


def match_table(node: FilterQueryTree, dictionary: Dictionary, card_pad: int) -> np.ndarray:
    """bool[card_pad] — True at dictIds whose value satisfies the leaf.

    For MV_NONE leaves the table is *membership* of the excluded set
    (the kernel negates after the any-reduction).
    """
    stored = dictionary.stored_type
    card = dictionary.cardinality
    table = np.zeros(card_pad, dtype=bool)
    op = node.operator
    if op in (FilterOperator.EQUALITY, FilterOperator.IN):
        for v in node.values:
            i = dictionary.index_of(_coerce(v, stored))
            if i >= 0:
                table[i] = True
    elif op in (FilterOperator.NOT, FilterOperator.NOT_IN):
        # SV: complement table; MV: membership table (kernel handles NONE)
        member = np.zeros(card_pad, dtype=bool)
        for v in node.values:
            i = dictionary.index_of(_coerce(v, stored))
            if i >= 0:
                member[i] = True
        table = member  # caller flips for SV below
    elif op == FilterOperator.RANGE:
        lo, hi = leaf_interval(node, dictionary)
        if hi > lo:
            table[lo:hi] = True
    elif op == FilterOperator.REGEX:
        pattern = re.compile(node.values[0])
        for i in range(card):
            if pattern.search(str(dictionary.get(i))) is not None:
                table[i] = True
    else:
        raise ValueError(f"unsupported leaf operator {op}")
    return table


# ---------------------------------------------------------------------------
# Query inputs (per-segment arrays, stacked [S, ...])
# ---------------------------------------------------------------------------


def build_query_inputs(
    request: BrokerRequest,
    plan: StaticPlan,
    ctx: TableContext,
    staged: StagedTable,
    scratch: Optional[Dict[Any, Any]] = None,
    launch: Optional[Dict[str, np.ndarray]] = None,
) -> Dict[str, Any]:
    """``launch``: the launch's segments where it is not the whole staged
    table (``ladder.launch_segments``; its ``slots`` int32[L] are positions
    in ``ctx.segments``, -1 for a slot without a segment).  Every
    per-segment table then has L rows, row j made for segment
    ``slots[j]`` (zeros in an empty slot, which has no valid row), and
    ``launch`` itself rides along as ``inputs["segments"]``: the program
    takes those segments of the resident columns (``kernel.launch_view``),
    and being an input it is under the inputs digest, so two launches of
    one plan over different segments neither coalesce nor share uploaded
    inputs."""
    S = staged.num_segments if launch is None else int(launch["slots"].shape[0])
    # (row of the tables, position in ctx.segments) of every segment made for
    rows = list(enumerate(range(len(ctx.segments)))) if launch is None else [
        (j, int(i)) for j, i in enumerate(launch["slots"]) if i >= 0]
    inputs: Dict[str, Any] = {}
    if launch is not None:
        inputs["segments"] = launch

    # filter leaf match tables
    if plan.filter_tree is not None:
        # walk request filter leaves in the same order encode() visited them
        flat_leaves: List[FilterQueryTree] = []

        def collect(node: FilterQueryTree) -> None:
            if node.is_leaf:
                flat_leaves.append(node)
            else:
                for c in node.children:
                    collect(c)

        collect(request.filter)
        tables = []
        bounds = []
        points = []
        run_arrays = []
        for leaf_node, leaf_static in zip(flat_leaves, plan.leaves):
            kind = leaf_static.eval_kind
            # dummies keep the pytree structure identical per plan
            table_e = np.zeros((S, 1), dtype=bool)
            bound_e = np.zeros((S, 2), dtype=np.int32)
            point_e = np.zeros((S, max(leaf_static.k_pad, 1)), dtype=np.int32)
            runs_e = np.zeros(
                (S, max(leaf_static.k_pad, 1) if kind == "runs" else 1, 2),
                dtype=np.int32,
            )
            for j, i in rows:
                seg = ctx.segments[i]
                scol = seg.column(leaf_static.column)
                d = scol.dictionary
                if kind == "runs":
                    t = None if scratch is None else scratch.get((id(leaf_node), i))
                    if t is None:
                        stg = staged.column(leaf_static.column)
                        t = _effective_table(
                            leaf_node, leaf_static.mode, d, stg.card_pad, stg.cards[i],
                            cache_key=(seg.segment_name, seg.metadata.crc, leaf_static.column),
                        )
                    for ri, (lo, hi) in enumerate(_table_runs(t)):
                        runs_e[j, ri] = (lo, hi)
                elif kind == "interval":
                    bound_e[j] = leaf_interval(leaf_node, d)
                elif kind == "docrange":
                    if leaf_node.operator == FilterOperator.EQUALITY:
                        did = d.index_of(d.stored_type.convert(leaf_node.values[0]))
                        lo, hi = (did, did + 1) if did >= 0 else (0, 0)
                    else:
                        lo, hi = leaf_interval(leaf_node, d)
                    bound_e[j] = (
                        _doc_bound(scol.fwd, lo),
                        _doc_bound(scol.fwd, hi),
                    )
                elif kind in ("points", "points_none"):
                    point_e[j] = leaf_points(leaf_node, d, leaf_static.k_pad)
                else:
                    col = staged.column(leaf_static.column)
                    if table_e.shape[1] == 1:
                        table_e = np.zeros((S, col.card_pad), dtype=bool)
                    t = None if scratch is None else scratch.get((id(leaf_node), i))
                    if t is None:
                        t = _effective_table(
                            leaf_node, leaf_static.mode, d, col.card_pad, col.cards[i],
                            cache_key=(seg.segment_name, seg.metadata.crc, leaf_static.column),
                        )
                    table_e[j] = t
            tables.append(table_e)
            bounds.append(bound_e)
            points.append(point_e)
            run_arrays.append(runs_e)
        inputs["match"] = tables
        inputs["bounds"] = bounds
        inputs["pts"] = points
        inputs["runs"] = run_arrays

    # per-agg auxiliary tables
    agg_aux: List[Dict[str, np.ndarray]] = []
    for a in plan.aggs:
        aux: Dict[str, np.ndarray] = {}
        if a.kind in ("presence", "hist"):
            # SV presence/hist read the staged .gfwd stream (kernel
            # _value_gids); shipping the full remap table then would
            # be dead H2D weight — dummy it, as group_remap does
            if not a.is_mv and staged.column(a.column).gfwd is not None:
                aux["remap"] = np.zeros((S, 1), dtype=np.int32)
            else:
                aux["remap"] = _stacked_remap(ctx, staged, a.column, S, rows)
        elif a.kind == "hll":
            if not a.is_mv and staged.column(a.column).hll_bucket is not None:
                # staged per-row streams: the tables would be dead H2D
                aux["bucket"] = np.zeros((S, 1), dtype=np.int32)
                aux["rho"] = np.zeros((S, 1), dtype=np.int32)
            else:
                bucket, rho = _hll_tables(ctx, staged, a.column, S, rows)
                aux["bucket"] = bucket
                aux["rho"] = rho
        agg_aux.append(aux)
    inputs["agg_aux"] = agg_aux

    # group-by remaps (dummy entry when the staged gfwd array is used)
    if plan.group_by is not None and plan.on_device:
        inputs["group_remap"] = [
            np.zeros((S, 1), dtype=np.int32)
            if use_g
            else _stacked_remap(ctx, staged, c, S, rows)
            for c, use_g in zip(plan.group_by.columns, plan.group_by.use_gfwd)
        ]

    # selection sort remaps
    if plan.selection is not None and plan.selection.sort_columns:
        inputs["sel_remap"] = [
            np.zeros((S, 1), dtype=np.int32)
            if use_g
            else _stacked_remap(ctx, staged, c, S, rows)
            for c, use_g in zip(
                plan.selection.sort_columns, plan.selection.use_gfwd
            )
        ]

    return inputs


def _stacked_remap(ctx: TableContext, staged: StagedTable, column: str, S: int, rows) -> np.ndarray:
    """``S``, ``rows``: the launch's, as ``build_query_inputs`` has them."""
    col = staged.column(column)
    gcol = ctx.column(column)
    out = np.zeros((S, col.card_pad), dtype=np.int32)
    for j, i in rows:
        remap = gcol.remaps[i]
        out[j, : remap.size] = remap
    return out


def _hll_tables(ctx: TableContext, staged: StagedTable, column: str, S: int, rows):
    """Per-dictId (bucket, rho) tables: the HLL hash work happens once
    per dictionary entry on host; the device only scatter-maxes."""
    col = staged.column(column)
    bucket = np.zeros((S, col.card_pad), dtype=np.int32)
    rho = np.zeros((S, col.card_pad), dtype=np.int32)
    for j, i in rows:
        d = ctx.segments[i].column(column).dictionary
        bt, rt = hll_mod.dictionary_tables(d)
        bucket[j, : bt.size] = bt
        rho[j, : rt.size] = rt
    return bucket, rho
