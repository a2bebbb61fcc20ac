"""Host fallback path for queries whose dense device state would not fit
(group-by key spaces beyond ``MAX_GROUP_CAPACITY`` where the runs lowering
does not take the query: ``plan.group_runs_host_reason``; huge value-state
aggregations, composite sort keys beyond the key dtype).

The reference's analog is the hash-map group-by storage types
(``DefaultGroupKeyGenerator.java:60-63`` LONG_MAP_BASED/ARRAY_MAP_BASED)
that kick in when the dense ARRAY_BASED key space overflows — and in the
reference that map path is its *fast* path for big key spaces.  Here the
filter always evaluates vectorized (numpy match-table gathers over the
forward index), and group-by aggregation over huge key spaces runs a
vectorized numpy hash pipeline: mixed-radix global-id keys per matched
row -> ``np.unique`` factorization -> ``bincount``/``reduceat``
segmented reductions -> trim to topN*5 candidates before any Python
objects are built.  Only queries outside that shape (MV group columns,
value-state aggregations, radix overflow) drop to the row-wise
accumulators shared with the scan oracle.

Every path works through a segment in row blocks of
``config.HOST_BLOCK_ROWS`` and carries its float64 partial states
(sums, counts, minima, maxima, group keys, distinct pairs) from block
to block: memory is bounded by a block and the states, and no numpy
call holds the interpreter lock for longer than a block takes.
``execute_host_steps`` is the pass as a generator, one ``yield`` a
block; ``execute_host`` runs it to its end.  The shadow auditor
(``utils/audit.py``) drives the steps on its own thread, so that a
134M-row re-derivation runs beside serving instead of in front of it.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

import numpy as np

from pinot_tpu.common.request import (
    BrokerRequest,
    FilterOperator,
    FilterQueryTree,
    expr_eval,
    group_sort_ascending,
)
from pinot_tpu.common.values import render_value
from pinot_tpu.engine import config
from pinot_tpu.engine.context import TableContext
from pinot_tpu.engine.plan import match_table
from pinot_tpu.engine.results import (
    AggPartial,
    AvgPartial,
    CountPartial,
    DistinctPartial,
    HllPartial,
    IntermediateResult,
    MaxPartial,
    MinMaxRangePartial,
    MinPartial,
    SumPartial,
    make_partial,
    trim_group_candidates,
)
from pinot_tpu.segment.immutable import ImmutableSegment
from pinot_tpu.tools.scan_engine import _Accumulator


def _segment_mask(
    seg: ImmutableSegment,
    tree: Optional[FilterQueryTree],
    lo: int = 0,
    hi: Optional[int] = None,
) -> np.ndarray:
    """The filter over rows ``[lo, hi)`` of ``seg`` (all of it by default)."""
    hi = seg.num_docs if hi is None else hi
    n = hi - lo
    if tree is None:
        return np.ones(n, dtype=bool)
    if tree.is_leaf:
        col = seg.column(tree.column)
        d = col.dictionary
        table = match_table(tree, d, d.cardinality if d.cardinality else 1)
        negative = tree.operator in (FilterOperator.NOT, FilterOperator.NOT_IN)
        if col.is_single_value:
            if negative:
                table = ~table
            return table[col.fwd[lo:hi]]
        offsets = col.mv_offsets[lo : hi + 1]
        hits = table[col.mv_values[offsets[0] : offsets[-1]]]
        any_hit = np.zeros(n, dtype=bool)
        np.logical_or.at(any_hit, np.repeat(np.arange(n), np.diff(offsets)), hits)
        return ~any_hit if negative else any_hit
    masks = [_segment_mask(seg, c, lo, hi) for c in tree.children]
    out = masks[0]
    for m in masks[1:]:
        out = (out & m) if tree.operator == FilterOperator.AND else (out | m)
    return out


class _Block(NamedTuple):
    """The matched rows of one step: rows ``[lo, hi)`` of segment ``si``
    under ``sel`` (None: every row of the range; a bool mask over the
    range), or, from a postings-backed resolver, the row ids ``sel``."""

    si: int
    seg: ImmutableSegment
    lo: int
    hi: int
    sel: Optional[np.ndarray]
    n: int  # matched rows

    def take(self, column: np.ndarray) -> np.ndarray:
        """``column``'s entries at the matched rows."""
        if self.sel is None:
            return column[self.lo : self.hi]
        if self.sel.dtype == np.bool_:
            return column[self.lo : self.hi][self.sel]
        return column[self.sel]

    def rows(self) -> np.ndarray:
        """The matched rows' ids in the segment."""
        if self.sel is None:
            return np.arange(self.lo, self.hi)
        if self.sel.dtype == np.bool_:
            return np.flatnonzero(self.sel) + self.lo
        return self.sel


def _matched_blocks(
    segments: List[ImmutableSegment], request: BrokerRequest, matched_rows=None, scanned=None
) -> Iterator[_Block]:
    """Every segment's matched rows, ``config.HOST_BLOCK_ROWS`` rows a
    block: ``ceil(rows / block)`` blocks a segment.  By default the
    filter is a vectorized mask over the block's rows (O(n) host scan);
    ``matched_rows(si, seg)`` substitutes a row-id resolver (the
    inverted-index path's O(matches) postings, engine/invindex_path.py),
    whose ids are cut into blocks of the same size.  ``scanned``: the
    positions of the segments to answer from (None: all): ``si`` stays a
    segment's position in ``segments``, which is its position in the
    table context's remaps."""
    step = config.HOST_BLOCK_ROWS
    for si in range(len(segments)) if scanned is None else scanned:
        seg = segments[si]
        if matched_rows is not None:
            rows = matched_rows(si, seg)
            for lo in range(0, rows.size, step):
                part = rows[lo : lo + step]
                yield _Block(si, seg, 0, seg.num_docs, part, int(part.size))
            continue
        for lo in range(0, seg.num_docs, step):
            hi = min(lo + step, seg.num_docs)
            mask, n = None, hi - lo
            if request.filter is not None:
                mask = _segment_mask(seg, request.filter, lo, hi)
                n = int(np.count_nonzero(mask))
                if n == hi - lo:
                    mask = None  # every row of the range: slices, no copies
            yield _Block(si, seg, lo, hi, mask, n)


_VECTOR_AGGS = {"count", "sum", "min", "max", "avg", "minmaxrange"}
# distinct aggs vectorize in the GROUP-BY path via (group, gid) pair
# dedup (np.unique); they only touch global dict ids, so strings are
# fine.  Without this, a beyond-capacity group-by with distinctcount
# fell to the per-row Python loop, minutes at 134M rows.
_DISTINCT_AGGS = {"distinctcount", "distinctcounthll", "fasthll"}


def _vectorizable_groupby(request: BrokerRequest, segments, ctx: TableContext) -> bool:
    """True when the fast numpy hash path applies: SV group columns,
    scalar/pair aggregations over SV numeric columns, and a mixed-radix
    key that fits int64, packed with a distinct aggregation's value id
    too."""
    seg = segments[0]
    for c in request.group_by.columns:
        if c not in seg.columns or not seg.column(c).is_single_value:
            return False
    space = 1
    for c in request.group_by.columns:
        space *= max(ctx.column(c).global_cardinality, 1)
        if space >= (1 << 62):
            return False
    if not _vectorizable_aggs(request, segments, allow_distinct=True):
        return False
    return all(
        space * max(ctx.column(a.column).global_cardinality, 1) < (1 << 62)
        for a in request.aggregations
        if a.base_function in _DISTINCT_AGGS
    )


def _vectorizable_aggs(
    request: BrokerRequest, segments, allow_distinct: bool = False
) -> bool:
    """True when every aggregation fits the numpy fast paths:
    scalar/pair functions over SV numeric columns (shared check of the
    group-by and aggregation-only vectorized paths); with
    ``allow_distinct``, SV distinct/HLL aggs of any stored type too."""
    seg = segments[0]
    for a in request.aggregations:
        base = a.base_function
        is_distinct = base in _DISTINCT_AGGS
        if base not in _VECTOR_AGGS and not (allow_distinct and is_distinct):
            return False
        if a.column == "*":
            if is_distinct:
                return False  # distinctcount(*) has no gid column: per-row path
            continue
        for c in a.columns:  # an expression's leaves, or the one column
            if c not in seg.columns:
                return False
            col = seg.column(c)
            if not col.is_single_value:
                return False
            if not is_distinct and col.dictionary.stored_type.name == "STRING":
                return False
    return True


def _decoded(cache: dict, c: str, blk: _Block) -> np.ndarray:
    """Column ``c``'s float64 values at the block's matched rows.  The
    dictionary is converted once a (segment, column), into ``cache``."""
    col = blk.seg.column(c)
    values = cache.get((blk.si, c))
    if values is None:
        values = cache[blk.si, c] = np.asarray(col.dictionary.values, dtype=np.float64)
    return values[blk.take(col.fwd)]


def _arguments(request: BrokerRequest) -> Dict[str, tuple]:
    """Each aggregate's argument as an expression tree, by the name the
    states are kept under (``AggregationInfo.column``: a column, or an
    expression's canonical text)."""
    return {a.column: a.argument for a in request.aggregations if a.column != "*"}


def _argument_values(cache: dict, argument: tuple, blk: _Block) -> np.ndarray:
    """An aggregate's argument at the block's matched rows, in float64:
    a column's decoded values, or the expression over its leaves' (the
    same tree the kernel evaluates in float32, kernel._row_values)."""
    return expr_eval(argument, lambda c: _decoded(cache, c, blk))


def _aggregation_vectorized(
    request: BrokerRequest,
    res: IntermediateResult,
    blocks: Iterator[_Block],
) -> Iterator[None]:
    """Scalar/pair aggregations over matched rows via numpy
    fancy-indexing, a block a step; float64 sums, minima and maxima
    carried from block to block.  O(matches) when the resolver is
    postings-backed (engine/invindex_path.py), O(n) under the default
    mask."""
    needed = {
        a.column
        for a in request.aggregations
        if a.base_function != "count" and a.column != "*"
    }
    ranged = {
        a.column
        for a in request.aggregations
        if a.base_function in ("min", "max", "minmaxrange")
    }
    decoders: dict = {}
    arguments = _arguments(request)
    col_sum = {c: 0.0 for c in needed}
    col_min = {c: float("inf") for c in ranged}
    col_max = {c: float("-inf") for c in ranged}
    total = 0
    for blk in blocks:
        res.num_docs_scanned += blk.n
        total += blk.n
        if blk.n:
            for c in needed:
                vals = _argument_values(decoders, arguments[c], blk)
                col_sum[c] += float(vals.sum())
                if c in ranged:
                    col_min[c] = min(col_min[c], float(vals.min()))
                    col_max[c] = max(col_max[c], float(vals.max()))
        yield
    if total == 0:
        res.aggregations = [make_partial(a.base_function) for a in request.aggregations]
        return
    out: List[AggPartial] = []
    for a in request.aggregations:
        b = a.base_function
        if b == "count":
            out.append(CountPartial(float(total)))
        elif b == "sum":
            out.append(SumPartial(col_sum[a.column]))
        elif b == "avg":
            out.append(AvgPartial(col_sum[a.column], float(total)))
        elif b == "min":
            out.append(MinPartial(col_min[a.column]))
        elif b == "max":
            out.append(MaxPartial(col_max[a.column]))
        else:
            out.append(MinMaxRangePartial(col_min[a.column], col_max[a.column]))
    res.aggregations = out


# a key space up to this many groups keeps its states in arrays over the
# whole space (a block is one bincount a column); a larger one keeps them
# by sorted key and merges blocks in
_DENSE_GROUP_SPACE = 1 << 16


class _DenseGroups:
    """Per-group counts, float64 sums, minima and maxima over blocks of
    (key, values) rows, in arrays over the whole key space."""

    def __init__(self, space: int, sum_cols, range_cols) -> None:
        self.counts = np.zeros(space, dtype=np.int64)
        self.sums = {c: np.zeros(space) for c in sum_cols}
        self.mins = {c: np.full(space, np.inf) for c in range_cols}
        self.maxs = {c: np.full(space, -np.inf) for c in range_cols}

    def add(self, keys: np.ndarray, vals: Dict[str, np.ndarray]) -> None:
        space = self.counts.size
        self.counts += np.bincount(keys, minlength=space)
        for c, acc in self.sums.items():
            acc += np.bincount(keys, weights=vals[c], minlength=space)
        for c in self.mins:
            np.minimum.at(self.mins[c], keys, vals[c])
            np.maximum.at(self.maxs[c], keys, vals[c])

    def finish(self):
        """(sorted live keys, counts, sums, minima, maxima), each [k]."""
        uniq = np.flatnonzero(self.counts)
        pick = lambda arrays: {c: a[uniq] for c, a in arrays.items()}
        return uniq, self.counts[uniq], pick(self.sums), pick(self.mins), pick(self.maxs)


class _SparseGroups:
    """The same states by sorted unique key, for a key space too large
    for arrays (the LONG_MAP_BASED analog).  A block is reduced to its
    own groups and waits; the waiting parts are merged into the running
    state once they hold as many keys as it does, so that a pass sorts
    O(n log n) keys in all and holds the states and one block."""

    def __init__(self, sum_cols=(), range_cols=()) -> None:
        self._sum_cols, self._range_cols = tuple(sum_cols), tuple(range_cols)
        none, no_value = np.zeros(0, dtype=np.int64), np.zeros(0)
        self._state = (
            none,
            none,
            {c: no_value for c in self._sum_cols},
            {c: no_value for c in self._range_cols},
            {c: no_value for c in self._range_cols},
        )
        self._parts: List[tuple] = []
        self._waiting = 0

    def _combine(self, keys, counts, sums, mins, maxs) -> tuple:
        """Rows or parts with repeated keys -> one entry a key.  For a
        block's rows ``counts`` is None (one each) and ``sums``,
        ``mins`` and ``maxs`` are all the rows' values."""
        uniq, inv = np.unique(keys, return_inverse=True)
        k = uniq.size
        cnt = np.bincount(inv, weights=counts, minlength=k).astype(np.int64)
        out_sums = {c: np.bincount(inv, weights=sums[c], minlength=k) for c in self._sum_cols}
        out_mins, out_maxs = {}, {}
        for c in self._range_cols:
            out_mins[c] = np.full(k, np.inf)
            out_maxs[c] = np.full(k, -np.inf)
            np.minimum.at(out_mins[c], inv, mins[c])
            np.maximum.at(out_maxs[c], inv, maxs[c])
        return uniq, cnt, out_sums, out_mins, out_maxs

    def add(self, keys: np.ndarray, vals: Dict[str, np.ndarray]) -> None:
        part = self._combine(keys, None, vals, vals, vals)
        self._parts.append(part)
        self._waiting += part[0].size
        if self._waiting >= self._state[0].size:
            self._merge()

    def _merge(self) -> None:
        parts = [self._state] + self._parts
        cat = lambda i, c=None: np.concatenate([p[i] if c is None else p[i][c] for p in parts])
        self._state = self._combine(
            cat(0),
            cat(1),
            {c: cat(2, c) for c in self._sum_cols},
            {c: cat(3, c) for c in self._range_cols},
            {c: cat(4, c) for c in self._range_cols},
        )
        self._parts, self._waiting = [], 0

    def finish(self):
        if self._parts:
            self._merge()
        return self._state


def _groupby_vectorized(
    ctx: TableContext,
    request: BrokerRequest,
    res: IntermediateResult,
    blocks: Iterator[_Block],
) -> Iterator[None]:
    """Vectorized LONG_MAP_BASED analog, a block a step: one int64 key
    per matched row; counts and sums via bincount, min/max via
    ``ufunc.at``, carried per group from block to block (``_DenseGroups``
    or ``_SparseGroups``); groups trimmed to topN*5 before materializing
    Python keys (MCombineGroupByOperator.java:216 trim semantics)."""
    gb = request.group_by
    gcards = [max(ctx.column(c).global_cardinality, 1) for c in gb.columns]
    space = 1
    for g in gcards:
        space *= g
    # columns whose decoded values the states actually need (count reads
    # none); gathered once per (block, column) even when several
    # aggregations share a column
    val_columns = {
        a.column
        for a in request.aggregations
        if a.base_function != "count"
        and a.column != "*"
        and a.base_function not in _DISTINCT_AGGS
    }
    range_columns = {
        a.column
        for a in request.aggregations
        if a.base_function in ("min", "max", "minmaxrange")
    }
    sum_columns = {
        a.column for a in request.aggregations if a.base_function in ("sum", "avg")
    }
    gid_columns = {
        a.column
        for a in request.aggregations
        if a.base_function in _DISTINCT_AGGS
    }
    groups = (
        _DenseGroups(space, sum_columns, range_columns)
        if space <= _DENSE_GROUP_SPACE
        else _SparseGroups(sum_columns, range_columns)
    )
    # distinct/HLL: the set of (group key, gid) pairs per column, packed
    # into one int64 (``_vectorizable_groupby`` holds the product under 2^62)
    gid_cards = {c: max(ctx.column(c).global_cardinality, 1) for c in gid_columns}
    pairs = {c: _SparseGroups() for c in gid_columns}
    decoders: dict = {}
    arguments = _arguments(request)
    # a (segment, group column)'s dictionary ids -> the column's digit of
    # the mixed-radix key, times the radix of the columns after it, in
    # int64: a block's keys are one gather a column, summed in place
    radix = [1] * len(gcards)
    for j in range(len(gcards) - 2, -1, -1):
        radix[j] = radix[j + 1] * gcards[j + 1]
    digits: Dict[Tuple[int, int], np.ndarray] = {}

    def digit(si: int, j: int) -> np.ndarray:
        if (si, j) not in digits:
            digits[si, j] = ctx.column(gb.columns[j]).remaps[si].astype(np.int64) * radix[j]
        return digits[si, j]

    for blk in blocks:
        res.num_docs_scanned += blk.n
        if blk.n:
            keys = digit(blk.si, 0)[blk.take(blk.seg.column(gb.columns[0]).fwd)]
            for j in range(1, len(gb.columns)):
                keys += digit(blk.si, j)[blk.take(blk.seg.column(gb.columns[j]).fwd)]
            groups.add(keys, {c: _argument_values(decoders, arguments[c], blk) for c in val_columns})
            for c in gid_columns:
                gids = ctx.column(c).remaps[blk.si][blk.take(blk.seg.column(c).fwd)]
                pairs[c].add(keys * gid_cards[c] + gids.astype(np.int64), {})
        yield

    uniq, int_counts, sums, mins, maxs = groups.finish()
    k = uniq.size
    if k == 0:
        return
    counts = int_counts.astype(np.float64)

    # sorted packed pairs: each group's distinct gids are one contiguous slice
    distinct_cache: Dict[str, tuple] = {}

    def distinct_pairs(c: str):
        if c not in distinct_cache:
            packed = pairs[c].finish()[0]
            pg = np.searchsorted(uniq, packed // gid_cards[c])
            pgid = packed % gid_cards[c]
            dcounts = np.bincount(pg, minlength=k).astype(np.float64)
            bounds = np.searchsorted(pg, np.arange(k + 1))
            distinct_cache[c] = (pgid, bounds, dcounts)
        return distinct_cache[c]

    states: List[tuple] = []  # (kind, arrays...)
    order_vals: List[np.ndarray] = []
    for a in request.aggregations:
        base = a.base_function
        if base == "count":
            states.append(("count", counts))
            order_vals.append(counts)
            continue
        if base in _DISTINCT_AGGS:
            pgid, bounds, dcounts = distinct_pairs(a.column)
            if base == "distinctcount":
                states.append(("distinct", a.column, pgid, bounds))
                order_vals.append(dcounts)
            else:
                # distinctcounthll: ORDER/TRIM by the exact per-group
                # distinct count (monotone proxy for the estimate —
                # dense registers for all k >= 2^20 groups would cost
                # k*256 bytes + a per-group Python estimator before the
                # trim); registers are built per KEPT group in partial()
                states.append(("hll", a.column, pgid, bounds))
                order_vals.append(dcounts)
            continue
        if base == "sum":
            states.append(("sum", sums[a.column]))
            order_vals.append(sums[a.column])
        elif base == "avg":
            states.append(("avg", sums[a.column], counts))
            order_vals.append(sums[a.column] / np.maximum(counts, 1))
        elif base in ("min", "max", "minmaxrange"):
            mn, mx = mins[a.column], maxs[a.column]
            if base == "min":
                states.append(("min", mn))
                order_vals.append(mn)
            elif base == "max":
                states.append(("max", mx))
                order_vals.append(mx)
            else:
                states.append(("minmaxrange", mn, mx))
                order_vals.append(mx - mn)

    # trim to topN*5 + boundary ties per agg (union), as the device path
    keep = trim_group_candidates(
        order_vals,
        [group_sort_ascending(a.function) for a in request.aggregations],
        gb.top_n,
        k,
    )

    # decompose kept keys -> per-column global ids -> rendered tuples
    gids = []
    rem = uniq[keep].copy()
    for gcard in reversed(gcards):
        gids.append(rem % gcard)
        rem = rem // gcard
    gids.reverse()
    gdicts = [ctx.column(c).global_dict for c in gb.columns]

    def partial(state, i: int):
        kind = state[0]
        if kind == "count":
            return CountPartial(float(state[1][i]))
        if kind == "sum":
            return SumPartial(float(state[1][i]))
        if kind == "min":
            return MinPartial(float(state[1][i]))
        if kind == "max":
            return MaxPartial(float(state[1][i]))
        if kind == "avg":
            return AvgPartial(float(state[1][i]), float(state[2][i]))
        if kind == "distinct":
            _, c, pgid, bounds = state
            gdict = ctx.column(c).global_dict
            ids = pgid[bounds[i] : bounds[i + 1]]
            # pair-dedup'd gids are already unique; one vectorized gather
            # replaces the per-value Python set build (north-star groups
            # carry millions of distinct values each)
            return DistinctPartial(gdict.value_array()[ids])
        if kind == "hll":
            from pinot_tpu.engine import hll as hll_mod

            _, c, pgid, bounds = state
            bt, rt = hll_mod.dictionary_tables(ctx.column(c).global_dict)
            ids = pgid[bounds[i] : bounds[i + 1]]
            regs = np.zeros(hll_mod.M, dtype=np.uint8)
            np.maximum.at(regs, bt[ids], rt[ids])
            return HllPartial(regs)
        return MinMaxRangePartial(float(state[1][i]), float(state[2][i]))

    for row, i in enumerate(keep):
        ktup = tuple(
            render_value(gdicts[j].stored_type, gdicts[j].get(int(gids[j][row])))
            for j in range(len(gb.columns))
        )
        res.groups[ktup] = [partial(st, int(i)) for st in states]


def _referenced_column_bytes(
    segments: List[ImmutableSegment], request: BrokerRequest
) -> int:
    """Column-data bytes the host path reads, upper bound: the full
    forward index (SV) / MV value stream of every referenced column —
    the default mask resolver scans every row for the filter, and value
    columns gather through the same arrays.  Postings-backed callers
    (engine/invindex_path.py) overwrite this with their O(matches)
    figure."""
    total = 0
    cols = request.referenced_columns()
    for seg in segments:
        for name in cols:
            col = seg.columns.get(name)
            if col is None:
                continue
            fwd = getattr(col, "fwd", None)
            if fwd is not None:
                total += np.asarray(fwd).nbytes
            mv = getattr(col, "mv_values", None)
            if mv is not None:
                total += np.asarray(mv).nbytes
    return total


def execute_host(
    segments: List[ImmutableSegment],
    ctx: TableContext,
    request: BrokerRequest,
    total_docs: int,
    sel_columns: Optional[List[str]],
    matched_rows=None,
    scanned=None,
) -> IntermediateResult:
    """The host's answer: ``execute_host_steps`` run to its end."""
    return run_steps(
        execute_host_steps(segments, ctx, request, total_docs, sel_columns, matched_rows, scanned)
    )


def run_steps(steps):
    """Run a generator of steps to its end; its return value."""
    while True:
        try:
            next(steps)
        except StopIteration as done:
            return done.value


def execute_host_steps(
    segments: List[ImmutableSegment],
    ctx: TableContext,
    request: BrokerRequest,
    total_docs: int,
    sel_columns: Optional[List[str]],
    matched_rows=None,
    scanned=None,
):
    """The pass as a generator: one ``yield`` after each block of
    ``config.HOST_BLOCK_ROWS`` rows, the result as its return value.
    Cost-accounted: every host-served query reports hostMs (wall time,
    the caller's pauses between steps included), bytesScanned, and the
    host serving tier on its result's cost vector (engine/results.py
    COST_KEYS).  ``segments`` with ``ctx`` are the table; ``scanned``
    (None: all) the positions of those the pass reads and counts, the
    ones the query's filter can match (``pruner.scanned_segments``)."""
    import time as _time

    t0 = _time.perf_counter()
    res = yield from _execute_host_impl(
        segments, ctx, request, total_docs, sel_columns, matched_rows, scanned
    )
    read = segments if scanned is None else [segments[i] for i in scanned]
    res.add_cost(
        hostMs=round((_time.perf_counter() - t0) * 1000, 3),
        bytesScanned=_referenced_column_bytes(read, request),
        segmentsHost=len(read),
    )
    return res


def _execute_host_impl(
    segments: List[ImmutableSegment],
    ctx: TableContext,
    request: BrokerRequest,
    total_docs: int,
    sel_columns: Optional[List[str]],
    matched_rows=None,
    scanned=None,
):
    res = IntermediateResult(
        total_docs=total_docs,
        num_segments_queried=len(segments) if scanned is None else len(scanned),
    )
    blocks = _matched_blocks(segments, request, matched_rows, scanned)
    if request.is_group_by:
        res.groups = {}
        if _vectorizable_groupby(request, segments, ctx):
            yield from _groupby_vectorized(ctx, request, res, blocks)
            return res
    elif request.is_aggregation:
        if _vectorizable_aggs(request, segments):
            yield from _aggregation_vectorized(request, res, blocks)
            return res
        # row-wise accumulators (NOT mergeable partials — those have no
        # .add); _to_partial adapts them below, same as the group-by path
        res.aggregations = [_Accumulator(a) for a in request.aggregations]
    else:
        res.selection_rows = []
        res.selection_columns = sel_columns

    taken = [0] * len(segments)  # unsorted selection: rows taken a segment
    for blk in blocks:
        seg = blk.seg
        res.num_docs_scanned += blk.n
        matched = blk.rows() if blk.n else ()

        if request.is_group_by:
            gb = request.group_by
            for doc in matched:
                row = seg.row(int(doc))
                for key in _group_keys(seg, row, gb.columns):
                    accs = res.groups.get(key)
                    if accs is None:
                        accs = [_Accumulator(a) for a in request.aggregations]
                        res.groups[key] = accs
                    for acc in accs:
                        acc.add(row)
        elif request.is_aggregation:
            for doc in matched:
                row = seg.row(int(doc))
                for acc, _a in zip(res.aggregations, request.aggregations):
                    acc.add(row)
        else:
            sel = request.selection
            k = sel.offset + sel.size
            # unsorted: the first k matched rows of each segment, as before blocks
            take = matched if sel.sorts else matched[: max(k - taken[blk.si], 0)]
            taken[blk.si] += len(take)
            for doc in take:
                row = seg.row(int(doc))
                sort_vals = []
                for s in sel.sorts:
                    v = row[s.column]
                    if isinstance(v, list):
                        v = v[0] if v else None
                    sort_vals.append(v)
                res.selection_rows.append((sort_vals, [row[c] for c in sel_columns]))
        yield

    # adapt oracle accumulators -> mergeable partials
    if request.is_group_by:
        res.groups = {
            key: [_to_partial(acc) for acc in accs] for key, accs in res.groups.items()
        }
    elif request.is_aggregation:
        res.aggregations = [_to_partial(acc) for acc in res.aggregations]
    return res


def _group_keys(seg: ImmutableSegment, row, columns) -> List[Tuple[str, ...]]:
    keys: List[Tuple[str, ...]] = [()]
    for col in columns:
        st = seg.column(col).dictionary.stored_type
        v = row[col]
        vals = v if isinstance(v, list) else [v]
        keys = [k + (render_value(st, x),) for k in keys for x in vals]
    return keys


def _to_partial(acc):
    """Convert a scan-oracle accumulator (or an already-built partial)
    into a mergeable AggPartial."""
    from pinot_tpu.engine.results import (
        AggPartial,
        AvgPartial,
        CountPartial,
        DistinctPartial,
        HistogramPartial,
        HllPartial,
        MaxPartial,
        MinMaxRangePartial,
        MinPartial,
        SumPartial,
    )
    from pinot_tpu.engine import hll as hll_mod

    if isinstance(acc, AggPartial):
        return acc
    base = acc.base
    if base == "count":
        return CountPartial(acc.count)
    if base == "sum":
        return SumPartial(acc.sum)
    if base == "min":
        return MinPartial(acc.min)
    if base == "max":
        return MaxPartial(acc.max)
    if base == "avg":
        return AvgPartial(acc.sum, acc.count)
    if base == "minmaxrange":
        return MinMaxRangePartial(acc.min, acc.max)
    if base == "distinctcount":
        return DistinctPartial(set(acc.distinct))
    if base in ("distinctcounthll", "fasthll"):
        return HllPartial(hll_mod.registers_from_values(acc.distinct))
    if base.startswith("percentile"):
        p = int(base[len("percentileest"):]) if base.startswith("percentileest") else int(base[len("percentile"):])
        counts: Dict[float, int] = {}
        for v in acc.values:
            counts[v] = counts.get(v, 0) + 1
        return HistogramPartial(counts, percentile=p)
    raise ValueError(base)
