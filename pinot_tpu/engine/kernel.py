"""Kernel builder: StaticPlan -> jit-compiled query kernel.

The reference executes a virtual-call operator tree per segment in
10k-doc blocks (``AggregationGroupByOperator.java:74-96``,
``MProjectionOperator.java``).  Here the whole per-segment pipeline —
filter mask -> projection gather -> aggregate / group-by scatter —
is ONE traced XLA program over the full (padded) column arrays:

  mask      = boolean combine of match-table gathers       (filter ops)
  values    = dict_vals[fwd]                                (projection)
  scalars   = masked reductions                             (aggregation)
  group-by  = scatter-add/min/max into dense [capacity]
              holders keyed by global-id mixed-radix keys   (group-by)

The kernel is written for ONE segment and lifted with ``jax.vmap`` over
the stacked segment axis — the TPU replacement for MCombineOperator's
thread pools; cross-segment merge is an elementwise reduction over that
axis (and a `psum` across chips in ``pinot_tpu.parallel``).

Everything is static-shaped: padding rows are masked by ``valid``,
invalid scatter entries are routed to index=capacity and dropped
(XLA scatter mode 'drop').
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu.common.request import expr_eval
from pinot_tpu.engine import config
from pinot_tpu.engine.plan import MV_ANY, MV_NONE, SV, StaticAgg, StaticPlan

BIG = jnp.inf

# Group-by scatter-adds lower poorly on TPU (serialized scatter); for
# small key spaces a chunked one-hot matmul rides the MXU instead:
#   acc[K] += w[chunk] @ onehot(keys[chunk], K)
# Enabled on non-CPU backends (or forced via env for tests).
import os as _os

MATMUL_GROUP_CAP = int(_os.environ.get("PINOT_TPU_MATMUL_GROUP_CAP", str(512)))
# 2^18-row chunks: PR 30's sweep of the row loop on a v5e (k6, ms a
# call): 2^15 7.07, 2^16 6.25, 2^17 5.73, 2^18 5.47, 2^19 5.34 (PERF.md)
_MATMUL_CHUNK = int(_os.environ.get("PINOT_TPU_MATMUL_CHUNK", str(1 << 18)))
# dense presence/hist holders ride the FACTORED contraction
# (_value_state_counts) with a combined (group, valueId) key while
# capacity * gcard_pad stays under this, and so does an ungrouped
# distinctcounthll (hll_lowering 'matmul': 16,384 (register, rank)
# cells).  That one is judged (chip runs, PR 41, ClickBench hits,
# 12 segments x 8,388,608 rows, distinctcounthll(UserID) with no filter):
# a reply 33.7 to 34.3 ms, the program's largest operation 20.1 ms, 0.2
# ns a row (PR 26 read 0.76 at K = 2^14 for the Pallas form of the same
# contraction); the scatter it replaces costs 13.4 ns a row at any K
# (PR 37).  The presence and hist holders have no cell yet: ROADMAP D4
_MATMUL_VALUE_CAP = int(_os.environ.get("PINOT_TPU_MATMUL_VALUE_CAP", str(1 << 18)))
# grouped HLL: the contraction's work grows with capacity x 16,384
# cells, so this admits 16 groups and no cell has so few (the sweep that
# put the crossover with the sort near capacity 16 is from before the
# chip round, record gone: ROADMAP D4)
_MATMUL_HLL_CAP = int(_os.environ.get("PINOT_TPU_MATMUL_HLL_CAP", str(1 << 18)))
# grouped HLL beyond the matmul gate lowers to one packed int32 key a
# row, sorted a segment (in parts of _HLL_SORT_PART rows or fewer), and
# the sum of each (group, register) run's last rank on the matrix unit
# (_hll_sorted_registers; bit-identical to scatter-max) while
# (capacity * HLL_M * 64) fits int32; beyond that the flat scatter runs.
# Judged at 9,040 groups (chip runs, PR 42, PR 44 and PR 45, ClickBench
# hits by RegionID, 12 segments of 2^23 rows, 2.31M cells): 225 ms of
# device time a query while a segment was sorted as one row, of it the
# twelve sorts 168 (lax.sort of the keys ALONE, unstable, over
# [12, 2^23]: 1.67 ns a row in the cell, 1.70 alone in a program), the
# twelve windowed calls 20.7 (0.2 ns a row, one or two windows a block of
# 8,192 rows), the keys' pass into cells and ranks 7, the occupancy's
# contraction beside it 14.4; since PR 45 the sort is of [24, 2^22] and
# 110 (1.09 ns a row), the twenty-four calls 19.7, the query 172: the
# sorted constants' comment below has the sweep.  PR 42 read 329 for the sorts, 3.27 ns a row, and
# took it for the price of the keys: it was the price of TWO operands.
# lax.sort's default is stable, and a stable sort on this chip is the
# unstable one with an operand of row numbers carried as the last key
# (`sort(%keys, %iota)`, 537 MB of temporaries), which orders nothing
# where the keys are the only operand.  ns a row by the rows one sort
# spans (PR 44's probe, seed 4400001001, lax.sort alone over the 100.7M
# packed keys as [rows / span, span]), keys alone and unstable: 2^19
# 0.71, 2^20 0.83, 2^21 0.97, 2^22 1.12, 2^23 1.70, all 2^26.6 flat
# 1.64; with the row numbers: 2^19 1.40, 2^23 3.30, flat 3.25.  The
# keys' content costs nothing (every row one key 1.69; plain ids of 17.6M
# users 1.70 and 1.64).  What looks like a step between 2^23 and 2^22 is
# the sublanes: the chip sorts eight rows at a time, so [12, 2^23] pays
# for sixteen rows and [24, 2^22] for its 24 (PR 45: the same parts as
# [12, 2, 2^22] cost 146.7 ms, 4 tiles of 8 rows, for 110.0 as
# [24, 2^22], 3 tiles); a tile of eight rows falls 13 to 14% a halving of
# the row, which is log^2 of the rows.  Until PR 42 the reduce sorted
# every segment's keys at once and a searchsorted of one bound a cell
# read the largest key: 1,217 ms of a 1,590 ms query (27 steps of a
# gather the chip serialises, 19.5 ns an element a step; PR 41), and
# packing the keys 44, now under 4.3.  The scatter it stands in for would
# cost 13.4 ns a row, 1,350 ms (PR 37's reading; not run here).  Above
# 12,256 groups the cells go in ranges, every part running every range
# (compiled for a v5e at 65,536, not run on one); above 65,536: no cell
_HLL_SORT_CAP = int(_os.environ.get("PINOT_TPU_HLL_SORT_CAP", str(1 << 16)))


def _use_matmul_groupby() -> bool:
    import os

    force = os.environ.get("PINOT_TPU_GROUPBY_MATMUL")
    if force is not None:
        return force == "1"
    return jax.default_backend() != "cpu"


def hll_lowering(plan: StaticPlan) -> Optional[str]:
    """Which lowering the plan's HLL aggregates take (kind 'hll': a
    distinctcounthll whose registers are built from the per-row (register,
    rank) streams, grouped or not), from what the plan states — consulted
    by the kernel builder (_agg_state, _group_state), by zone_blocks and
    by the launch's ``hll=`` tag and ``hll.lowering.*`` mark.  Whatever
    the answer, a segment's state is dense registers [capacity, HLL_M]
    that fold by ``max`` ('pairs' apart).

    'matmul':  the (group, register, rank) occupancy contraction on the
               matrix unit (_value_state_counts) and an argmax by iota:
               an ungrouped aggregate on the chip (16,384 cells, under
               _MATMUL_VALUE_CAP) and a group-by of up to
               _MATMUL_HLL_CAP / 16,384 groups (16).
    'sort':    a group-by beyond that, up to _HLL_SORT_CAP groups: one
               packed int32 key a row, sorted a segment where they are
               built, in hll_sort_parts parts; a register is the sum of
               its (group, register) run's last rank, added on the
               matrix unit by the windowed contraction over rows in key
               order, and the largest of its parts' sums
               (_hll_sorted_registers).
    'scatter': the serialised scatter-max: a group-by over more groups
               than the packed key holds, and every aggregate on the CPU
               backend, which has no matrix unit and would run the sorted
               form's Pallas call in the interpreter (unless
               PINOT_TPU_GROUPBY_MATMUL=1, the tests' switch, forces the
               chip's lowerings, as for groupby_lowering).
    'pairs':   a group space whose dense registers would pass the value
               state's budget (plan.value_state_sort_pairs): (slot,
               register x 64 + rank) pairs through the sort-dedup reduce.
    None for a plan without such an aggregate (also where the planner
    lowered distinctcounthll to a presence contraction: kind 'presence').
    The aggregates of one plan share its group space, so one answer."""
    aggs = [a for a in getattr(plan, "aggs", ()) if getattr(a, "kind", None) == "hll"]  # a JoinPlan's are not StaticAggs
    if not aggs:
        return None
    if aggs[0].sort_pairs:
        return "pairs"
    if not _use_matmul_groupby():
        return "scatter"
    cells = config.HLL_M * 64  # rho < 64 always (64-bit hash)
    if plan.group_by is None:
        return "matmul" if cells <= _MATMUL_VALUE_CAP else "scatter"
    capacity = plan.group_by.capacity
    if capacity * cells <= _MATMUL_HLL_CAP:
        return "matmul"
    return "sort" if capacity <= _HLL_SORT_CAP else "scatter"


def hll_sort_parts(rows: int) -> int:
    """In how many parts of equal length the 'sort' lowering sorts a
    segment of ``rows`` packed keys (_hll_sorted_registers): as few as
    leave a part at most ``_HLL_SORT_PART`` rows.  Consulted by the
    kernel builder and by the launch's ``hll.sort.parts`` mark, which
    must agree."""
    return max(1, -(-rows // _HLL_SORT_PART))


# Dense group-by capacities above MATMUL_GROUP_CAP ride the two-level
# (radix-128) contraction: up to this bound over the rows as they stand,
# beyond it over the rows sorted by group id (groupby_operands 'sorted').
# Contraction work grows with K (2 * cols * K flops a row); the sort's
# and the scatter's do not.  Q3's shape (occupancy + one float32 sum) on
# a v5e, ns a row: the scatter, _segment_add_radix (chip run, PR 26,
# 33.5M shuffled rows; at K=2,000 over the cell's 134M sorted rows 17.58
# against 0.14) and _segment_add_sorted (chip run, PR 38, seed
# 3800003201, 4 segments of 2^23 shuffled rows; radix re-read there:
# 1.49 at 2^15, 2.90 at 2^16):
#   K = 2^11: 13.62   0.15            K = 2^17:     -    5.72   3.75
#   K = 2^14: 13.56   0.76            K = 2^18: 13.56  11.33   3.75
#   K = 2^15:     -   1.49   3.75     K = 220,000:  -       -  3.75
#   K = 2^16: 13.55   2.87   3.75
# The sorted form costs the same at every K: 3.32 of its 3.75 are the
# sort (lax.sort of an int32 key carrying one float32, unstable: two
# operands; a second sum carried, three operands, costs 1.9 more, 5.66
# against the contraction's 5.09 at 2^16).  A sort is priced by its
# operands as much as by its rows: the key alone over the same [*, 2^23]
# costs 1.70 (PR 44's probe; 1.61 flat in PR 43's cell), and a stable
# sort adds an operand of row numbers to whatever it is given (the key
# alone, stable: 3.30).
# So on segments of 2^23 rows the crossover lies near K = 85,000 at one
# sum and 73,000 at two, and 2^16 stays the largest measured K the
# contraction over the rows as they stand wins.  The sort's cost a row falls with
# the rows a segment sorts (log^2 n stages): over 16 x 524,288 rows, as
# the zone tier's gathered view hands TPC-H Q15 its candidate blocks
# (K = 220,000, a product under the sum, 5.64M of 8.39M rows valid;
# chip runs, PR 38, seeds 3800001001 and 3800003201), the sorted form
# takes 11.6 to 11.75 ms, 1.40 ns a row (the sort 8.9, key and payload,
# unstable: 1.06 a row, where the key alone reads 0.71 over rows of 2^19
# and a third operand, the payload's or a stable sort's, 1.7 to 2.0: PR
# 38, PR 40, PR 44; the windowed contraction 2.4 to 2.9; 11.70 with
# every row on one key, 11.03 with 0.7% of the rows valid), the
# contraction at K = 220,000 without the sort 82.3 (9.81), the parent's
# two scatters 113.8 (13.57; in the cell 56.9 and 55.6 ms a query, PR 37)
# and a sort followed by segment_sum(indices_are_sorted=True) 144.5: the
# scatter stays serial whatever it is told.
RADIX_GROUP_CAP = 1 << 16
_RADIX = 128
# VMEM the generated one-hots of one grid step may take (the block of
# rows shrinks as K grows), and the limit Mosaic is given above its
# 16 MiB default for the accumulator's two buffers beside them; a v5e
# core has 128 MiB
_RADIX_STEP_BYTES = 12 << 20
_RADIX_VMEM_LIMIT = 64 << 20
_RADIX_BLOCK_MAX = 8192  # rows a step: 18.8, 21.3, 27.6 ms at 8192, 4096, 2048 (Q3, 134M rows)
# rows in key order (_segment_add_sorted): rows a step and the sublanes
# (x 128 keys) of the window its hi one-hot spans (chip run, PR 38, Q15's
# shape, ms: 11.63 at 8192 x 64; 11.70 at 4096 x 64; 13.52 at 16384 x 64;
# 13.57 at 8192 x 128; 13.73 at 16384 x 128); the VMEM one call's
# accumulator may take, and the weight columns a call at most (a step's
# one-hot is [(1 + 3 cols) x window, rows a step] bfloat16 beside it).
# The accumulator's bound was 20 MB until PR 42, whose one-part call
# (_hll_sorted_registers) the chip's compiler refuses from 15.9 MB of
# accumulator on ("ran out of memory in memory space vmem", 16 MiB of
# scoped allocation for the call fused with the update of its vmapped
# output; 15.0 compiles: a v5e described, not attached); the sums'
# calls of ten parts compile at 20.4.  One bound for both, under that
# with room; no cell's or test's sums change their calls by it (a
# capacity of 307,000 to 520,000 with two or more sums would)
_SORTED_BLOCK = 8192
_SORTED_WINDOW = 64
# rows one lax.sort of a grouped distinct count's packed keys spans at
# the most (hll_sort_parts; whole blocks of _SORTED_BLOCK): a longer
# segment is sorted in equal parts, whose registers fold by max.  Chip
# run, PR 45, seed 4400001001, _hll_sorted_registers whole under vmap
# over [12, 2^23] keys at 9,040 groups, ms (no row filtered; three in
# ten), and of it the sort and the windowed calls: one part of 2^23
# 195.6 (192.6): 167.9, 12.5; parts of 2^22 142.6 (139.4): 110.0, 16.5;
# 2^21 141.0 (134.9): 95.2, 29.0; 2^20 139.1 (137.5): 81.7, 40.7; the
# passes about them 15 to 17 at every length.  What a shorter part gives
# the sort its calls take back (a block of sorted rows spans 35, 71, 141
# sublanes of cells under a window of 64, for 18 as one part), so the
# three lie within 3.5 ms and the longest has the fewest call sites (a
# part its own: 2, 4, 8; times the ranges above 12,256 groups) and the
# least beside the keys
_HLL_SORT_PART = 1 << 22
_SORTED_ACC_BYTES = 12 << 20
_SORTED_COLS_MAX = 3


def groupby_lowering(plan: StaticPlan) -> Optional[str]:
    """Which lowering a dense group-by's occupancy and sum-shaped
    aggregates (count, sum, avg) take, from what the plan states —
    consulted by the kernel builder and by the launch's
    ``groupby.lowering.*`` meter and ``groupby=`` tag, which must agree.

    'onehot':  K <= MATMUL_GROUP_CAP, one chunked [cols, chunk] @
               [chunk, K] contraction (_segment_add_matmul_multi).
    'radix':   every larger K: the two-level contraction with
               float32-faithful weights, over the rows as they stand up
               to RADIX_GROUP_CAP (_segment_add_radix) and over the rows
               in key order above it (_segment_add_sorted; what
               groupby_operands says).
    'scatter': the CPU backend, unless PINOT_TPU_GROUPBY_MATMUL=1 (the
               tests' switch) forces the chip's lowerings.
    'runs':    more keys than a dense holder takes (over
               config.MAX_GROUP_CAPACITY, on every backend): no state of
               K cells at all.  The table's rows are sorted by group id
               once, a run of equal ids is a group, its count is its
               length and its sums a scan over it.  The sorted ids are
               read once, in blocks (_run_lengths): the position where
               the open run began is carried from block to block, and a
               block hands on how many runs end in it, their squared
               lengths' sum and the largest of them, so that the cut and
               the candidates' places are searched over blocks and read
               rows in the few blocks a summary points at.  The program
               hands back the per-server trim's candidates, the count of
               runs and the digest of their values (_reduce_group_runs).
               The planner lets through only what it takes
               (plan.group_runs_host_reason).
    None for a plan without a group-by.  min, max, minmaxrange,
    presence, hist and HLL aggregates keep _group_state on every
    dense lowering."""
    if getattr(plan, "group_by", None) is None:
        return None
    if plan.group_by.capacity > config.MAX_GROUP_CAPACITY:
        return "runs"
    if not _use_matmul_groupby():
        return "scatter"
    return "onehot" if plan.group_by.capacity <= MATMUL_GROUP_CAP else "radix"


def _sum_shaped(agg: StaticAgg) -> bool:
    """count, sum and avg over plain values: the aggregates whose group
    state is a sum of per-row weight columns (_group_add_weights), so
    that the states of two blocks of rows add."""
    return agg.base == "count" or (agg.base in ("sum", "avg") and agg.kind in ("scalar", "pair"))


def _contraction_slots(plan: StaticPlan) -> Tuple[Dict[int, List[int]], int]:
    """Which rows of a dense group-by's float states [m, K] each
    sum-shaped aggregate reads, and m.  Row 0 is the occupancy (the
    validity column), which is the plain count's weights exactly.

    A row belongs to what it sums, not to the aggregate that asks: the
    sum of one argument (a column, or an expression's canonical text) is
    one row for every ``sum`` and ``avg`` over it, a single-value
    ``avg``'s count is row 0, and a multi-value column's entry count is
    one row for its ``countmv`` and its ``avgmv``.  Rows are numbered in
    the order their first reader stands in the plan, an aggregate's sum
    before its count (_contraction_operands builds them in that order)."""
    slots: Dict[int, List[int]] = {}
    row_of: Dict[tuple, int] = {}

    def row(*what) -> int:
        return row_of.setdefault(what, 1 + len(row_of))

    for i, agg in enumerate(plan.aggs):
        if not _sum_shaped(agg):
            continue
        source = (agg.column, agg.is_mv, agg.use_raw)
        if agg.base == "count":
            slots[i] = [row("entries", *source) if agg.is_mv else 0]
            continue
        slots[i] = [row("sum", *source)]
        if agg.base == "avg":
            slots[i].append(row("entries", *source) if agg.is_mv else 0)
    return slots, 1 + len(row_of)


def groupby_cells(plan: StaticPlan) -> Optional[Tuple[int, int]]:
    """(K x m cells of a dense group-by's float states, rows that sharing
    saved: what one row an aggregate and two an ``avg`` would take, less
    m), the launch's ``cells=`` tag and ``groupby.slots.shared`` marks.
    None for a plan without a group-by."""
    if getattr(plan, "group_by", None) is None:
        return None
    m = _contraction_slots(plan)[1]
    unshared = 1 + sum(
        0 if agg.base == "count" and not agg.is_mv else 2 if agg.base == "avg" else 1
        for agg in plan.aggs
        if _sum_shaped(agg)
    )
    dense = groupby_lowering(plan) != "runs"  # the runs lowering holds no cell
    return plan.group_by.capacity * m * dense, unshared - m


# The row loop answers a group-by of up to this many (group, column)
# cells with one masked float32 reduction a cell on the vector unit,
# whose work grows with the cells where the matrix unit's does not.
# Table kernel over 16 segments of 8,388,608 rows on a v5e, ms a call
# (chip run, PR 30; the staged one-hot contraction beside it):
#   K=6 x 4 columns (k6)   5.5  15.0      K=16 x 4   10.5  17.4
#   K=7 x 2 columns (q6)   3.6  13.0      K=32 x 2    8.7  14.8
#   K=8 x 4                5.6  15.1      K=32 x 4   18.4  18.2
#   K=16 x 2               6.1  15.1      K=64 x 2   15.4  17.7
# 64 cells is the largest measured count that wins clearly (1.7x) at
# either width; at 128 the two meet.
_LOOP_CELLS = 64


def groupby_operands(plan: StaticPlan) -> Optional[str]:
    """How a dense group-by's operands (filter mask, group key, weight
    columns) reach its lowering, from what the plan states — consulted
    by the kernel builder and by the launch's ``operands=`` tag and
    ``groupby.operands.loop|sorted`` meters, which must agree.

    'loop':   the 'onehot' lowering of a plan whose every output adds
              over blocks of rows (count, sum, avg; single-value keys;
              no selection part) and whose K x m cells number at most
              _LOOP_CELLS: the row loop is the outermost thing in the
              segment's program, each step filters, keys and reduces
              one block of the staged columns (_make_loop_groupby_kernel),
              and no segment-sized intermediate reaches HBM.
    'sorted': the 'radix' lowering above RADIX_GROUP_CAP: the operands
              are built over the whole segment and put in key order
              (one sort by group id carrying the weight columns), so
              that a block of rows contracts over a window of keys and
              not over all K (_segment_add_sorted).
    'staged': every other group-by: the operands are built over the
              whole segment and handed to the lowering as they stand.
    None for a plan without a group-by."""
    lowering = groupby_lowering(plan)
    if lowering is None:
        return None
    if lowering == "runs":
        return "staged"  # the lowering sorts the table's rows itself, after the segments' operands are built
    if lowering == "radix" and plan.group_by.capacity > RADIX_GROUP_CAP:
        return "sorted"
    additive = (
        plan.selection is None
        and not any(plan.group_by.col_is_mv)
        and all(_sum_shaped(agg) for agg in plan.aggs)
    )
    cells = plan.group_by.capacity * _contraction_slots(plan)[1]
    return "loop" if lowering == "onehot" and additive and cells <= _LOOP_CELLS else "staged"


def _exact_parts(idx, w_refs, capacity: int):
    """The rows [1, block] that a step of the two-level contraction
    scales its hi one-hot by: the validity, then three bfloat16-exact
    parts a float32 weight (8 + 8 + 8 significand bits, each the top 16
    bits of what is left, taken by mask: a rounding convert is a round
    trip XLA may elide), which sum to the weight exactly."""
    from jax.experimental.pallas import tpu as pltpu

    def top(x):
        bits = pltpu.bitcast(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
        return pltpu.bitcast(bits, jnp.float32)

    parts = [(idx < capacity).astype(jnp.float32)]
    for w_ref in w_refs:
        w1 = top(w_ref[...])
        r = w_ref[...] - w1
        w2 = top(r)
        parts.extend([w1, w2, r - w2])
    return parts


def _states_of_parts(acc):
    """[1 + 3 m, K] sums of _exact_parts' rows -> the states' rows: the
    occupancy, then a weight's three parts added smallest first."""
    return [acc[0]] + [(acc[j + 2] + acc[j + 1]) + acc[j] for j in range(1, acc.shape[0], 3)]


def _whole_blocks(flat_idx, weights, blk: int, capacity: int):
    """int32 buckets and float32 weight columns padded to whole blocks
    of ``blk`` rows with rows that count nowhere (the sentinel bucket
    ``capacity``, zero weights)."""
    flat_idx = flat_idx.astype(jnp.int32)
    weights = [w.astype(jnp.float32) for w in weights]
    pad = (-flat_idx.shape[0]) % blk
    if pad:
        flat_idx = jnp.concatenate([flat_idx, jnp.full(pad, capacity, jnp.int32)])
        weights = [jnp.concatenate([w, jnp.zeros(pad, w.dtype)]) for w in weights]
    return flat_idx, weights


def _segment_add_radix(flat_idx, weights, capacity: int):
    """Occupancy counts and the sums of the float ``weights`` columns
    over ``capacity`` buckets, with ONE two-level one-hot contraction on
    the matrix unit (Pallas).  Invalid rows carry ``flat_idx ==
    capacity`` (and zero weights).

    The flat index splits into radix-128 digits (hi, lo).  Per block of
    rows, ``acc[c*K1 + h, l] += sum_rows (hi==h) * w_c * (lo==l)``:
    ``A^T [cols*K1, block] . B^T [128, block]`` contracted over the
    block, A the thin hi one-hot scaled by the weights and B the lo
    one-hot.  Both are GENERATED in VMEM from the block's indices and
    weights and never reach HBM, whose traffic is the index and weight
    streams alone (the XLA form of the same contraction, a dot_general
    in a scan, measured 182 ms for this one's 18.8 on Q3's shape).
    The accumulator stays in VMEM across the sequential grid.

    Precision is float32's.  Column 0 is the validity (0/1); a float
    weight rides as three bfloat16 columns that sum to it exactly
    (8 + 8 + 8 significand bits, each the top 16 bits of what is left,
    taken by mask: a rounding convert is a round trip XLA may elide).
    The one-hots are exact in bfloat16, so every product is exact and
    only the order of the float32 additions differs from the
    scatter's.  Counts are exact: a segment has fewer than 2^24 rows.

    Returns float states [1 + len(weights), capacity]: row 0 the
    occupancy counts, then one row a weight column, as the scatter
    gives them.  Runs in the Pallas interpreter on the CPU backend,
    which only the tests' switch reaches."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K1 = -(-capacity // _RADIX)  # the sentinel lands in the padded tail or past it
    K1p = -(-K1 // 16) * 16  # a bfloat16 tile is 16 sublanes
    n_parts = 1 + 3 * len(weights)
    rows = n_parts * K1p
    # a row of the block costs A^T's column, the hi digit's iota, mask
    # and select beside it, and B^T's column with its iota
    blk = _RADIX_STEP_BYTES // (rows * 2 + K1p * 12 + _RADIX * 6)
    blk = max(256, min(_RADIX_BLOCK_MAX, 1 << (blk.bit_length() - 1)))
    flat_idx, weights = _whole_blocks(flat_idx, weights, blk, capacity)

    def kernel(idx_ref, *refs):
        w_refs, acc_ref = refs[:-1], refs[-1]

        @pl.when(pl.program_id(0) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        idx = idx_ref[...]  # [1, blk]: rows along the lanes
        hi = jax.lax.broadcasted_iota(jnp.int32, (K1p, blk), 0) == (idx >> 7)
        lo = jax.lax.broadcasted_iota(jnp.int32, (_RADIX, blk), 0) == (idx & (_RADIX - 1))
        parts = _exact_parts(idx, w_refs, capacity)
        a_t = jnp.concatenate(
            [jnp.where(hi, p, 0.0).astype(jnp.bfloat16) for p in parts], axis=0
        )
        acc_ref[...] += jax.lax.dot_general(
            a_t, lo.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    acc = pl.pallas_call(
        kernel,
        grid=(flat_idx.shape[0] // blk,),
        in_specs=[pl.BlockSpec((1, blk), lambda i: (0, i))] * (1 + len(weights)),
        out_specs=pl.BlockSpec((rows, _RADIX), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _RADIX), jnp.float32),
        # the accumulator idiom (init at step 0, then +=) needs the grid
        # in order: the compiled TPU grid, or the interpreter
        interpret=jax.default_backend() == "cpu",
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_RADIX_VMEM_LIMIT),
    )(flat_idx.reshape(1, -1), *[w.reshape(1, -1) for w in weights])
    acc = acc.reshape(n_parts, K1p * _RADIX)[:, :capacity]
    return jnp.stack(_states_of_parts(acc)).astype(config.float_dtype())


def _sorted_sublanes(capacity: int) -> int:
    """Sublanes (x 128 buckets) of the windowed contraction's accumulator
    over ``capacity`` buckets: the last window may start at the last
    bucket's sublane, and a bfloat16 tile is 16 sublanes."""
    return -(-(-(-capacity // _RADIX) + _SORTED_WINDOW) // 16) * 16


def _sorted_window_sums(idx, cols, capacity: int, n_parts: int, parts):
    """The two-level contraction over rows ALREADY in bucket order, its
    hi one-hot cut to a window of ``_SORTED_WINDOW`` sublanes (x 128
    buckets): float32 sums [n_parts, capacity] of the ``n_parts`` rows
    [1, block] that ``parts(idx, col_refs)`` makes of a block's buckets
    and float32 columns inside the kernel, each exact in bfloat16.  The
    caller says what a row's parts are (_segment_add_sorted: the
    validity and three a float32 weight; _hll_sorted_registers: the rank
    as it stands); the grid, the windows and the accumulator are one.

    ``idx``: int32, ascending, whole blocks of ``_SORTED_BLOCK``; rows
    that count nowhere carry ``capacity`` or more (and parts of zero) and
    stand last.  A grid step's block has its first and last bucket known
    before the call (scalar prefetch), so the step builds ``hi`` over the
    window at the first bucket's sublane (aligned down to 8), adds the
    product into the accumulator at that dynamic sublane offset, and
    moves the window on while the block's last bucket lies past it.  A
    window either ends a block or moves ``_SORTED_WINDOW`` x 128 buckets
    on, so the call takes at most ``rows / block + capacity / (window
    buckets)`` products whatever the buckets are: the work no longer
    grows with K times the rows.  A block of rows that count nowhere
    does nothing.  The whole accumulator stays in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    W, blk = _SORTED_WINDOW, _SORTED_BLOCK
    blocks = idx.reshape(-1, blk)
    firsts = blocks[:, 0]
    lasts = jnp.max(jnp.where(blocks < capacity, blocks, -1), axis=1)  # -1: no row of the block counts
    K1p = _sorted_sublanes(capacity)

    def kernel(first_ref, last_ref, idx_ref, *refs):
        w_refs, acc_ref = refs[:-1], refs[-1]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        idx = idx_ref[...]  # [1, blk]: rows along the lanes
        lo = (jax.lax.broadcasted_iota(jnp.int32, (_RADIX, blk), 0) == (idx & (_RADIX - 1))).astype(jnp.bfloat16)
        rows = parts(idx, w_refs)
        hi_digit = idx >> 7
        base0 = (first_ref[i] >> 10) << 3
        windows = jnp.where(last_ref[i] < 0, 0, ((last_ref[i] >> 7) - base0) // W + 1)

        def window(j, carry):
            base = pl.multiple_of(base0 + j * W, 8)
            hi = jax.lax.broadcasted_iota(jnp.int32, (W, blk), 0) == (hi_digit - base)
            a_t = jnp.concatenate([jnp.where(hi, p, 0.0).astype(jnp.bfloat16) for p in rows], axis=0)
            prod = jax.lax.dot_general(a_t, lo, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            for c in range(n_parts):
                acc_ref[c, pl.ds(base, W), :] += prod[c * W:(c + 1) * W]
            return carry

        jax.lax.fori_loop(0, windows, window, 0)

    acc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(blocks.shape[0],),
            in_specs=[pl.BlockSpec((1, blk), lambda i, *_: (0, i))] * (1 + len(cols)),
            out_specs=pl.BlockSpec((n_parts, K1p, _RADIX), lambda i, *_: (0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_parts, K1p, _RADIX), jnp.float32),
        interpret=jax.default_backend() == "cpu",
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_RADIX_VMEM_LIMIT),
    )(firsts, lasts, idx.reshape(1, -1), *[w.reshape(1, -1) for w in cols])
    return acc.reshape(n_parts, K1p * _RADIX)[:, :capacity]


def _segment_add_sorted(flat_idx, weights, capacity: int):
    """_segment_add_radix's states for a ``capacity`` whose contraction
    would cost more than putting the rows in key order first
    (groupby_operands 'sorted'): ONE sort of the rows by bucket carrying
    the weight columns, then the windowed contraction over the rows in
    that order (_sorted_window_sums).  Filtered rows carry ``flat_idx ==
    capacity`` and zero weights and sort to the end.

    Weights, counts and precision are _segment_add_radix's: validity and
    three exact bfloat16 parts a weight, float32 sums.  Past
    ``_SORTED_ACC_BYTES`` of accumulator the weight columns go in groups,
    a call a group over the same sorted rows."""
    flat_idx, weights = _whole_blocks(flat_idx, weights, _SORTED_BLOCK, capacity)
    flat_idx, *weights = jax.lax.sort((flat_idx, *weights), num_keys=1, is_stable=False)

    def call(ws):
        parts = lambda idx, w_refs: _exact_parts(idx, w_refs, capacity)
        return _states_of_parts(_sorted_window_sums(flat_idx, ws, capacity, 1 + 3 * len(ws), parts))

    acc_bytes = _sorted_sublanes(capacity) * _RADIX * 4  # one part's
    per = max(1, min(_SORTED_COLS_MAX, (_SORTED_ACC_BYTES // acc_bytes - 1) // 3))  # weight columns a call
    states = call(weights[:per])
    for at in range(per, len(weights), per):
        states.extend(call(weights[at:at + per])[1:])
    return jnp.stack(states).astype(config.float_dtype())


def _hll_sorted_registers(packed, capacity: int):
    """One segment's dense HLL registers [capacity, HLL_M] uint8 from its
    packed int32 keys, ``((group * HLL_M + register) << 6) | rank`` a
    row and ``_PAIR_SENTINEL`` where the row is filtered out: the 'sort'
    lowering (hll_lowering), bit for bit the scatter-max's.

    The keys are sorted where they were built, unstably: they are the
    sort's one operand, so rows that compare equal are equal in every bit
    and a stable sort's row-number operand would order nothing (on the
    chip it doubles the sort: PR 44).  A segment of more rows than
    ``_HLL_SORT_PART`` is cut into ``hll_sort_parts`` parts of equal
    length (whole blocks each, the padding sentinels, so no part is
    padding alone) and the ONE sort runs along a part's rows
    (_sort_in_parts): shorter rows sort cheaper, and two parts of twelve
    segments fill the chip's sublanes where one leaves a quarter empty
    (PR 45).  A register is the largest rank any row of its cell carries,
    so each part gives registers of its own by what follows
    (_hll_run_end_sums) and the parts fold by ``max`` as the segments'
    do: no merge, bit for bit the scatter-max's in any cut.  A part's
    float32 sums are cast to uint8 before the fold.  A segment of one
    part is the program it was before there were parts."""
    parts = hll_sort_parts(packed.shape[0])
    keys = _whole_blocks(packed, [], parts * _SORTED_BLOCK, _PAIR_SENTINEL)[0]
    if parts == 1:
        return _hll_run_end_sums(jax.lax.sort(keys, is_stable=False), capacity)
    return functools.reduce(jnp.maximum, [_hll_run_end_sums(part, capacity) for part in _sort_in_parts(parts)(keys)])


@functools.lru_cache(maxsize=None)
def _sort_in_parts(parts: int):
    """keys [rows] -> ``parts`` arrays [rows / parts], each a slice of the
    keys in ascending order, by ONE unstable ``lax.sort`` of one operand.

    Under the segments' ``vmap`` the operand is [parts x segments, rows a
    part], a row a (part, segment), and not [segments, parts, rows a
    part]: the chip sorts eight rows at a time, one to a sublane, so
    twelve segments cost sixteen rows' time in every part, and 24 rows cost
    24 (PR 45: over 12 x 2^23 keys in parts of 2^22, 146.7 ms as
    [12, 2, 2^22] and 110.0 as [24, 2^22]).  ``vmap`` alone cannot fold its
    own axis into the rows, so the batched form is written out here
    (``custom_vmap``); what it returns is what ``vmap`` of the plain form
    would.  The parts are slices put side by side, never a reshape: a
    reshape of [segments, rows] to [segments, parts, rows a part] moves
    the parts onto the sublanes and costs the chip's compiler a minute."""
    @jax.custom_batching.custom_vmap
    def sort_in_parts(keys):
        return tuple(jax.lax.sort(jnp.stack(jnp.split(keys, parts)), is_stable=False))

    @sort_in_parts.def_vmap
    def of_segments(segments, in_batched, keys):  # keys [segments, rows]
        rows = jax.lax.sort(jnp.concatenate(jnp.split(keys, parts, axis=1)), is_stable=False)  # [parts x segments, rows a part]
        return tuple(jnp.split(rows, parts)), (True,) * parts

    return sort_in_parts


def _hll_run_end_sums(keys, capacity: int):
    """Registers [capacity, HLL_M] uint8 of packed keys in ascending
    order, whole blocks of ``_SORTED_BLOCK`` (_hll_sorted_registers: a
    segment's, or one part's of it).

    A row is the last of its (group, register) run where ``key >> 6``
    differs from the next row's; there its weight is the rank
    ``key & 63``, which is the run's largest (the rank rides the key's
    low bits), and everywhere else 0.  So exactly one row a live cell
    carries a weight, and a register is the SUM of its cell's weights:
    the windowed contraction over the rows in cell order
    (_sorted_window_sums), one part a row, since a rank is at most 63 and
    exact in bfloat16.  The sentinel's cell lies past the last one and
    counts nowhere.

    The accumulator is 4 B a cell in VMEM.  Cells past ``_SORTED_ACC_BYTES``
    of it (over 12,256 groups at 256 registers) go in ranges, a call a
    range over the same sorted rows: the rows under a range are clamped
    to its first cell with a weight of 0 and those over it to its
    sentinel, which keeps them in order."""
    cells = capacity * config.HLL_M
    cell = keys >> 6
    ends = jnp.concatenate([cell[1:] != cell[:-1], jnp.ones(1, bool)])
    # cells a call: the sublanes _SORTED_ACC_BYTES holds, less the last window's, in whole tiles
    per = ((_SORTED_ACC_BYTES // (_RADIX * 4)) // 16 * 16 - _SORTED_WINDOW) * _RADIX
    rank_as_it_stands = lambda idx, w_refs: [w_refs[0][...]]
    regs = []
    for at in range(0, cells, per):
        size = min(per, cells - at)
        inside = ends & (cell >= at) & (cell < at + size)
        rank = jnp.where(inside, keys & 63, 0).astype(jnp.float32)
        regs.append(_sorted_window_sums(jnp.clip(cell - at, 0, size), [rank], size, 1, rank_as_it_stands)[0])
    return jnp.concatenate(regs).astype(jnp.uint8).reshape(capacity, config.HLL_M)


def _segment_add_matmul_multi(flat_idx, W, capacity: int):
    """Sum m weight columns into capacity buckets with ONE chunked
    one-hot contraction: [m, chunk] @ [chunk, K] per scan step.

    The one-hot block is built once per chunk for EVERY aggregation —
    per-agg scans would rebuild (and re-stream) it once per agg, which
    dominated the Q1 kernel's HBM traffic.  Out-of-range indices
    (== capacity) one-hot to a zero row and drop."""
    fdt = config.float_dtype()
    m, n = W.shape
    chunk = min(_MATMUL_CHUNK, n)
    pad = (-n) % chunk
    if pad:
        flat_idx = jnp.concatenate([flat_idx, jnp.full(pad, capacity, flat_idx.dtype)])
        W = jnp.concatenate([W, jnp.zeros((m, pad), W.dtype)], axis=1)
    nb = flat_idx.shape[0] // chunk

    def body(acc, b):
        start = b * chunk
        i_c = jax.lax.dynamic_slice_in_dim(flat_idx, start, chunk)
        w_c = jax.lax.dynamic_slice_in_dim(W, start, chunk, axis=1).astype(fdt)
        onehot = jax.nn.one_hot(i_c, capacity, dtype=fdt)  # [chunk, K]
        return acc + w_c @ onehot, None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((m, capacity), dtype=fdt), jnp.arange(nb)
    )
    return acc


# block size for the factored contraction: smaller blocks keep the
# per-block [K1, 128] partials cheap to tree-sum (from a sweep before
# the chip round, record gone; not judged on the chip: ROADMAP D4)
_FACTORED_CHUNK = int(_os.environ.get("PINOT_TPU_FACTORED_CHUNK", str(1 << 15)))


def _value_state_counts(flat_idx, K: int):
    """Occupancy counts over a combined value-state key space of size K
    with a FACTORED one-hot contraction: split the key into (hi, lo)
    radix-128 digits and contract two THIN one-hots as a real
    [K1, block] @ [block, 128] matmul per block — full MXU tiles instead
    of the M=1 degenerate matmul of the scan contraction.  Judged on the
    chip for an ungrouped distinctcounthll (K = 16,384; PR 41: 0.2 ns a
    row over 100.7M rows, the comment at _MATMUL_VALUE_CAP); the
    presence and hist holders that ride it have no cell: ROADMAP D4.

    Weights must be binary and FOLDED into the index: invalid entries
    carry ``flat_idx == K`` and one-hot to a dropped row.  bf16 one-hots
    are exact (values 0/1) and the f32 accumulate is exact for counts
    below 2^24 per cell per segment.  Returns float counts [K].
    """
    fdt = config.float_dtype()
    onehot_dt = jnp.bfloat16 if jax.default_backend() != "cpu" else fdt
    n = flat_idx.shape[0]
    chunk = min(_FACTORED_CHUNK, max(128, n))
    pad = (-n) % chunk
    if pad:
        flat_idx = jnp.concatenate(
            [flat_idx, jnp.full(pad, K, flat_idx.dtype)]
        )
    nb = flat_idx.shape[0] // chunk
    K1 = -(-K // 128)  # sentinel K lands in the padded tail, sliced off
    blocks = flat_idx.reshape(nb, chunk)
    hi = jax.nn.one_hot(blocks // 128, K1, dtype=onehot_dt)
    lo = jax.nn.one_hot(blocks % 128, 128, dtype=onehot_dt)
    out = jax.lax.dot_general(
        hi, lo, (((1,), (1,)), ((0,), (0,))), preferred_element_type=fdt
    )
    return jnp.sum(out, axis=0).reshape(-1)[:K]


def _row_shaped(key: str) -> bool:
    return key.endswith((".fwd", ".raw", ".gfwd", ".mv", ".hllb", ".hllr", ".mvraw"))


def _valid_mask(seg: Dict[str, Any]) -> jnp.ndarray:
    """Doc-validity mask: ``iota < num_docs`` (free register compare)
    rather than a stored bool column (an HBM byte per row).  Falls back
    to a materialized ``valid`` array when no row-shaped column exists
    to take the row count from."""
    if "num_docs" in seg:
        for k, v in seg.items():
            if _row_shaped(k):
                n = v.shape[0]
                return jax.lax.iota(jnp.int32, n) < seg["num_docs"]
    return seg["valid"]


def _mv_valid(seg: Dict[str, Any], column: str) -> jnp.ndarray:
    """MV entry-validity mask from per-doc counts: iota < mvc."""
    mv = seg[f"{column}.mv"]
    counts = seg[f"{column}.mvc"]
    iota = jax.lax.broadcasted_iota(jnp.int32, mv.shape, mv.ndim - 1)
    return iota < counts[..., None]


def _doc_ids(seg: Dict[str, Any]) -> jnp.ndarray:
    """Row ids for doc-range predicates: the original doc ids when rows
    were block-gathered (zone-map path), else a plain iota."""
    if "rowid" in seg:
        return seg["rowid"]
    for k, v in seg.items():
        if _row_shaped(k):
            return jax.lax.iota(jnp.int32, v.shape[0])
    return jax.lax.iota(jnp.int32, seg["valid"].shape[0])


def _leaf_mask(plan: StaticPlan, i: int, seg: Dict[str, Any], q: Dict[str, Any]) -> jnp.ndarray:
    leaf = plan.leaves[i]
    kind = leaf.eval_kind
    if kind == "docrange":
        # sorted column: contiguous doc interval, no column read
        lo, hi = q["bounds"][i][0], q["bounds"][i][1]
        ids = _doc_ids(seg)
        return (ids >= lo) & (ids < hi)

    def ids_match(ids):
        """Per-dictId predicate truth, by the leaf's static eval kind.
        interval/points are pure vector compares (dictIds are
        order-preserving); table is the bool[card] gather fallback."""
        if kind == "interval":
            lo, hi = q["bounds"][i][0], q["bounds"][i][1]
            return (ids >= lo) & (ids < hi)
        if kind in ("points", "points_none"):
            pts = q["pts"][i]  # [k_pad], -1 padded
            hit = jnp.any(ids[..., None] == pts, axis=-1)
            return ~hit if (kind == "points_none" and leaf.mode == SV) else hit
        if kind == "runs":
            # interval union: [k_pad, 2] dictId ranges (SV complements
            # baked in, like the table kind); empty runs match nothing
            rr = q["runs"][i]
            return jnp.any(
                (ids[..., None] >= rr[:, 0]) & (ids[..., None] < rr[:, 1]), axis=-1
            )
        return q["match"][i][ids]

    if leaf.mode == SV:
        return ids_match(seg[f"{leaf.column}.fwd"])  # [n]
    mv = seg[f"{leaf.column}.mv"]  # [n, mv]
    mvv = _mv_valid(seg, leaf.column)
    hit = jnp.any(ids_match(mv) & mvv, axis=-1)
    if leaf.mode == MV_ANY:
        return hit
    return ~hit  # MV_NONE


def _eval_tree(plan: StaticPlan, node: tuple, seg, q) -> jnp.ndarray:
    kind = node[0]
    if kind == "leaf":
        return _leaf_mask(plan, node[1], seg, q)
    masks = [_eval_tree(plan, c, seg, q) for c in node[1]]
    out = masks[0]
    for m in masks[1:]:
        out = (out & m) if kind == "and" else (out | m)
    return out


def _row_values(agg: StaticAgg, seg, mask):
    """Per-row (or per-entry) numeric values + entry mask for an agg column."""
    fdt = config.float_dtype()
    if agg.is_mv:
        mvv = _mv_valid(seg, agg.column) & mask[:, None]
        mvr = seg.get(f"{agg.column}.mvraw")
        if mvr is not None:
            return mvr, mvv  # staged decoded values, no gather
        mv = seg[f"{agg.column}.mv"]
        vals = seg[f"{agg.column}.dict"][mv]
        return vals, mvv

    def column(name: str):
        if agg.use_raw:
            return seg[f"{name}.raw"]  # streamed, no gather
        return seg[f"{name}.dict"][seg[f"{name}.fwd"]]

    # the argument is an expression whose simplest case is one column:
    # products and sums of the leaves' values in the float dtype, fused
    # into the reduction that reads them (a python constant is weakly
    # typed and takes the arrays' precision)
    return expr_eval(agg.argument, column), mask


def _agg_state(agg: StaticAgg, i: int, seg, q, mask, hll: Optional[str] = None) -> Any:
    """Per-segment partial state for one aggregation (no group-by)."""
    fdt = config.float_dtype()
    base = agg.base
    if base == "count":
        if agg.is_mv:
            mvv = _mv_valid(seg, agg.column) & mask[:, None]
            return jnp.sum(mvv, dtype=fdt)
        return jnp.sum(mask, dtype=fdt)

    if agg.kind == "scalar" or agg.kind == "pair":
        vals, m = _row_values(agg, seg, mask)
        if base == "sum":
            return jnp.sum(jnp.where(m, vals, 0), dtype=fdt)
        if base == "min":
            return jnp.min(jnp.where(m, vals, BIG))
        if base == "max":
            return jnp.max(jnp.where(m, vals, -BIG))
        if base == "avg":
            return (
                jnp.sum(jnp.where(m, vals, 0), dtype=fdt),
                jnp.sum(m, dtype=fdt),
            )
        if base == "minmaxrange":
            return (
                jnp.min(jnp.where(m, vals, BIG)),
                jnp.max(jnp.where(m, vals, -BIG)),
            )

    aux = q["agg_aux"][i]
    if agg.kind in ("presence", "hist"):
        # one (entry mask, global valueId) extraction serves all three
        # storage strategies below
        remap = aux["remap"]
        if agg.is_mv:
            mv = seg[f"{agg.column}.mv"]
            m = (_mv_valid(seg, agg.column) & mask[:, None]).reshape(-1)
            gids = remap[mv].reshape(-1)
        else:
            m = mask
            gids = _value_gids(agg, seg, remap)
        if agg.sort_pairs:
            # emit (0, valueId) pairs; the sort reduce dedups (presence)
            # and carries run starts for occurrence counts (hist)
            sent = _PAIR_SENTINEL
            return (
                jnp.where(m, 0, sent).astype(jnp.int32),
                jnp.where(m, gids.astype(jnp.int32), sent),
            )
        K = agg.gcard_pad
        if _use_matmul_groupby() and K <= _MATMUL_VALUE_CAP:
            combined = jnp.where(m, gids.astype(jnp.int32), K).astype(jnp.int32)
            flat = _value_state_counts(combined, K)
            if agg.kind == "presence":
                return (flat > 0).astype(jnp.int32)
            return flat
        if agg.kind == "presence":
            presence = jnp.zeros(K, dtype=jnp.int32)
            return presence.at[gids].max(m.astype(jnp.int32), mode="drop")
        hist = jnp.zeros(K, dtype=fdt)
        return hist.at[gids].add(m.astype(fdt), mode="drop")

    if agg.kind == "hll":
        bucket, rho = aux["bucket"], aux["rho"]
        if agg.is_mv:
            mv = seg[f"{agg.column}.mv"]
            m = (_mv_valid(seg, agg.column) & mask[:, None]).reshape(-1)
            b_rows = bucket[mv].reshape(-1)
            r_rows = rho[mv].reshape(-1)
        else:
            m = mask
            b_rows, r_rows = _hll_rows(agg, seg, bucket, rho)
        K = config.HLL_M * 64  # rho < 64 always (64-bit hash)
        if hll == "matmul":
            # register max via a (bucket, rho) occupancy contraction on
            # the MXU + argmax-by-iota — replaces the serialized
            # scatter-max
            combined = jnp.where(
                m, b_rows.astype(jnp.int32) * 64 + r_rows.astype(jnp.int32), K
            ).astype(jnp.int32)
            counts = _value_state_counts(combined, K).reshape(config.HLL_M, 64)
            rho_iota = jax.lax.broadcasted_iota(jnp.int32, (config.HLL_M, 64), 1)
            return jnp.max(jnp.where(counts > 0, rho_iota, 0), axis=1)
        regs = jnp.zeros(config.HLL_M, dtype=jnp.uint8)
        return regs.at[b_rows.astype(jnp.int32)].max(
            jnp.where(m, r_rows, 0).astype(jnp.uint8), mode="drop"
        )

    raise AssertionError(agg)


def _group_keys(plan: StaticPlan, seg, q, mask):
    """Mixed-radix global group keys.

    Returns (keys [n, E], kvalid [n, E]) where E is the static MV
    expansion factor (1 if all group columns are single-value).
    """
    gb = plan.group_by
    kdt = config.key_dtype()
    n = mask.shape[0]
    keys = jnp.zeros((n, 1), dtype=kdt)
    kvalid = mask[:, None]
    for col, is_mv, gcard, remap, use_g in zip(
        gb.columns, gb.col_is_mv, gb.gcards, q["group_remap"], gb.use_gfwd
    ):
        if not is_mv:
            if use_g:
                g = seg[f"{col}.gfwd"].astype(kdt)  # [n], staged global ids
            else:
                g = remap[seg[f"{col}.fwd"]].astype(kdt)  # [n]
            keys = keys * gcard + g[:, None]
        else:
            mv = seg[f"{col}.mv"]
            mvv = _mv_valid(seg, col)
            g = remap[mv].astype(kdt)  # [n, mv]
            E = keys.shape[1]
            keys = (keys[:, :, None] * gcard + g[:, None, :]).reshape(n, -1)
            kvalid = (kvalid[:, :, None] & mvv[:, None, :]).reshape(n, -1)
    return keys, kvalid


def _group_add_weights(agg: StaticAgg, seg, mask, kvalid):
    """Flattened per-entry weight columns for the sum-shaped group aggs
    (count / sum / avg) — the batchable operands of the fused one-hot
    contraction.  None for aggs needing other combining ops (min/max/
    presence/hist/hll), which keep their own scatter paths."""
    if not _sum_shaped(agg):
        return None
    fdt = config.float_dtype()
    shape = kvalid.shape

    def per_entry(row_scalar):
        return jnp.broadcast_to(row_scalar[:, None], shape).reshape(-1)

    if agg.base == "count":
        if agg.is_mv:
            mvv = _mv_valid(seg, agg.column)
            return (per_entry(jnp.sum(mvv, axis=-1).astype(fdt)),)
        return (jnp.ones(shape, dtype=fdt).reshape(-1),)
    vals, m = _row_values(agg, seg, mask)
    if agg.is_mv:
        row_sum = jnp.sum(jnp.where(m, vals, 0), axis=-1)
        row_cnt = jnp.sum(m, axis=-1).astype(fdt)
    else:
        row_sum = vals
        row_cnt = jnp.ones_like(vals, dtype=fdt)
    if agg.base == "sum":
        return (per_entry(row_sum),)
    return (per_entry(row_sum), per_entry(row_cnt))


def _group_state(agg: StaticAgg, i: int, seg, q, mask, keys, kvalid, capacity, hll: Optional[str] = None) -> Any:
    fdt = config.float_dtype()
    base = agg.base
    idx = jnp.where(kvalid, keys, capacity)  # invalid -> dropped
    flat_idx = idx.reshape(-1)
    fvalid = kvalid.reshape(-1)

    def per_entry(row_scalar):
        """Broadcast a per-row scalar across the expansion axis, flattened."""
        return jnp.broadcast_to(row_scalar[:, None], idx.shape).reshape(-1)

    def group_add(weights):
        # count/sum/avg reach here only on the scatter branch — on the
        # matmul branch the fused multi-column contraction handles them
        # (make_single_segment_kernel)
        w = jnp.where(fvalid, weights, 0)
        return jnp.zeros(capacity, dtype=fdt).at[flat_idx].add(w, mode="drop")

    if base == "count":
        if agg.is_mv:
            mvv = _mv_valid(seg, agg.column)
            row_counts = jnp.sum(mvv, axis=-1).astype(fdt)
            w = per_entry(row_counts)
        else:
            w = jnp.ones_like(flat_idx, dtype=fdt)
        return group_add(w)

    if agg.kind in ("scalar", "pair"):
        vals, m = _row_values(agg, seg, mask)
        if agg.is_mv:
            row_sum = jnp.sum(jnp.where(m, vals, 0), axis=-1)
            row_cnt = jnp.sum(m, axis=-1).astype(fdt)
            row_min = jnp.min(jnp.where(m, vals, BIG), axis=-1)
            row_max = jnp.max(jnp.where(m, vals, -BIG), axis=-1)
        else:
            row_sum = vals
            row_cnt = jnp.ones_like(vals, dtype=fdt)
            row_min = vals
            row_max = vals

        def scatter_add(row_vals):
            return group_add(per_entry(row_vals))

        def scatter_min(row_vals):
            return jnp.full(capacity, BIG, dtype=fdt).at[flat_idx].min(
                jnp.where(fvalid, per_entry(row_vals), BIG), mode="drop"
            )

        def scatter_max(row_vals):
            return jnp.full(capacity, -BIG, dtype=fdt).at[flat_idx].max(
                jnp.where(fvalid, per_entry(row_vals), -BIG), mode="drop"
            )

        if base == "sum":
            return scatter_add(row_sum)
        if base == "min":
            return scatter_min(row_min)
        if base == "max":
            return scatter_max(row_max)
        if base == "avg":
            return (scatter_add(row_sum), scatter_add(row_cnt))
        if base == "minmaxrange":
            return (scatter_min(row_min), scatter_max(row_max))

    aux = q["agg_aux"][i]
    if agg.kind in ("presence", "hist"):
        remap = aux["remap"]
        if agg.is_mv:
            mv = seg[f"{agg.column}.mv"]
            mvv = _mv_valid(seg, agg.column)
            gids = remap[mv]  # [n, mv]
            E = idx.shape[1]
            pair_k = jnp.broadcast_to(idx[:, :, None], idx.shape + gids.shape[-1:]).reshape(-1)
            pair_g = jnp.broadcast_to(gids[:, None, :], (gids.shape[0], E, gids.shape[-1])).reshape(-1)
            pair_v = (kvalid[:, :, None] & mvv[:, None, :]).reshape(-1)
        else:
            gids = _value_gids(agg, seg, remap)  # [n] global value ids
            pair_k = flat_idx
            pair_g = per_entry(gids)
            pair_v = fvalid
        if agg.sort_pairs:
            # high-cardinality exact distinct: emit (group slot, valueId)
            # pairs; the cross-segment reduce sort-dedups them
            # (apply_reduce "distinct_pairs") — no [capacity, gcard_pad]
            # state ever materializes
            sent = _PAIR_SENTINEL
            return (
                jnp.where(pair_v, pair_k.astype(jnp.int32), sent),
                jnp.where(pair_v, pair_g.astype(jnp.int32), sent),
            )
        K = capacity * agg.gcard_pad
        if _use_matmul_groupby() and K <= _MATMUL_VALUE_CAP:
            # combined (group, valueId) key through the one-hot MXU
            # contraction: ~0.7ns/row at K=2^16 vs the serialized 2-D
            # scatter's ~12.5ns/element
            combined = jnp.where(
                pair_v, pair_k.astype(jnp.int32) * agg.gcard_pad + pair_g, K
            ).astype(jnp.int32)
            flat = _value_state_counts(combined, K)
            grid = flat.reshape(capacity, agg.gcard_pad)
            if agg.kind == "presence":
                return (grid > 0).astype(jnp.int32)
            return grid
        if agg.kind == "presence":
            holder = jnp.zeros((capacity, agg.gcard_pad), dtype=jnp.int32)
            return holder.at[pair_k, pair_g].max(pair_v.astype(jnp.int32), mode="drop")
        holder = jnp.zeros((capacity, agg.gcard_pad), dtype=fdt)
        return holder.at[pair_k, pair_g].add(pair_v.astype(fdt), mode="drop")

    if agg.kind == "hll":
        bucket, rho = aux["bucket"], aux["rho"]
        if agg.is_mv:
            mv = seg[f"{agg.column}.mv"]
            mvv = _mv_valid(seg, agg.column)
            b = bucket[mv]
            r = rho[mv]
            E = idx.shape[1]
            pair_k = jnp.broadcast_to(idx[:, :, None], idx.shape + b.shape[-1:]).reshape(-1)
            pair_b = jnp.broadcast_to(b[:, None, :], (b.shape[0], E, b.shape[-1])).reshape(-1)
            pair_r = jnp.broadcast_to(r[:, None, :], (r.shape[0], E, r.shape[-1])).reshape(-1)
            pair_v = (kvalid[:, :, None] & mvv[:, None, :]).reshape(-1)
        else:
            b_rows, r_rows = _hll_rows(agg, seg, bucket, rho)
            pair_k = flat_idx
            pair_b = per_entry(b_rows)
            pair_r = per_entry(r_rows)
            pair_v = fvalid
        if agg.sort_pairs:
            # big group spaces: (slot, bucket*64+rho) pairs through the
            # generic sort-dedup reduce; finalize max-reduces rho per
            # (slot, bucket) into registers
            sent = _PAIR_SENTINEL
            gid = pair_b.astype(jnp.int32) * 64 + pair_r.astype(jnp.int32)
            return (
                jnp.where(pair_v, pair_k.astype(jnp.int32), sent),
                jnp.where(pair_v, gid, sent),
            )
        K = capacity * config.HLL_M * 64
        if hll == "matmul":
            # small group spaces: (group, bucket, rho) occupancy on the
            # MXU + argmax-by-iota, like the scalar HLL path
            combined = jnp.where(
                pair_v,
                (
                    pair_k.astype(jnp.int32) * config.HLL_M
                    + pair_b.astype(jnp.int32)
                )
                * 64
                + pair_r.astype(jnp.int32),
                K,
            ).astype(jnp.int32)
            counts = _value_state_counts(combined, K).reshape(
                capacity, config.HLL_M, 64
            )
            rho_iota = jax.lax.broadcasted_iota(
                jnp.int32, (capacity, config.HLL_M, 64), 2
            )
            return jnp.max(jnp.where(counts > 0, rho_iota, 0), axis=2)
        if hll == "sort":
            # mid/large group spaces: pack (group, bucket, rho) into ONE
            # int32 per entry (4 B/row — the leanest HBM footprint of
            # the three paths), sort them here and add each (group,
            # bucket) run's last rho on the matrix unit (bit-identical
            # to scatter-max)
            packed = jnp.where(
                pair_v,
                ((pair_k * config.HLL_M + pair_b.astype(jnp.int32)) << 6)
                | pair_r.astype(jnp.int32),
                _PAIR_SENTINEL,
            )
            return _hll_sorted_registers(packed, capacity)
        # huge capacities (> _HLL_SORT_CAP: packed key overflows int32):
        # one FLAT scatter index instead of (k, b) pairs — a single fused
        # index plus uint8 values keeps per-row temporaries at 5 B/row
        flat = jnp.where(
            pair_v,
            pair_k * config.HLL_M + pair_b.astype(jnp.int32),
            capacity * config.HLL_M,
        )
        holder = jnp.zeros(capacity * config.HLL_M, dtype=jnp.uint8)
        regs = holder.at[flat].max(pair_r.astype(jnp.uint8), mode="drop")
        return regs.reshape(capacity, config.HLL_M)

    raise AssertionError(agg)


def _count_states_to_int(plan: StaticPlan, out: Dict[str, Any]) -> None:
    """Cast one segment's row-count states to ``config.row_count_dtype``
    so every later merge (segment axis, dispatch chunks, mesh psum) is an
    integer sum.  The states arrive as floats holding exact integers:
    one segment has fewer than 2^24 rows."""
    cdt = config.row_count_dtype()
    prefix = "gb_" if plan.group_by is not None else "agg_"
    for i, agg in enumerate(plan.aggs):
        key = f"{prefix}{i}"
        if agg.base == "count":
            out[key] = out[key].astype(cdt)
        elif agg.base == "avg":
            out[key] = (out[key][0], out[key][1].astype(cdt))
        elif agg.kind == "hist" and not agg.sort_pairs:
            out[key] = out[key].astype(cdt)


def _filter_mask(plan: StaticPlan, seg, q) -> jnp.ndarray:
    valid = _valid_mask(seg)
    if plan.filter_tree is None:
        return valid
    return _eval_tree(plan, plan.filter_tree, seg, q) & valid


def _contraction_operands(plan: StaticPlan, seg, mask, keys, kvalid, zeroed: bool):
    """(flat_idx, cols) of the rows of ``seg``: the bucket of every
    (row, key) entry, ``capacity`` where it is filtered out, and the m
    weight columns of _contraction_slots, the validity first.
    ``zeroed``: the weights of a filtered entry are zero, as a matrix
    product needs them; a masked reduction selects on the index and
    takes them as they are.  ONE pass covers occupancy AND every
    sum-shaped agg: one index (one one-hot per chunk) for all of them,
    instead of a scan per agg — the per-agg version re-streamed the
    one-hot blocks and dominated the kernel's HBM traffic."""
    flat_idx = jnp.where(kvalid, keys, plan.group_by.capacity).reshape(-1)
    fvalid = kvalid.reshape(-1)
    cols = [fvalid.astype(config.float_dtype())]
    for i, rows in _contraction_slots(plan)[0].items():
        if max(rows) < len(cols):
            continue  # every row it reads is built: the occupancy, or another aggregate's
        w = _group_add_weights(plan.aggs[i], seg, mask, kvalid)
        for r, vec in zip(rows, w):
            if r == len(cols):
                cols.append(jnp.where(fvalid, vec, 0) if zeroed else vec)
    return flat_idx, cols


def _contraction_outputs(plan: StaticPlan, states, out: Dict[str, Any]) -> None:
    """gb_presence and the sum-shaped aggregates' gb_<i> from a dense
    group-by's float states [m, K]."""
    out["gb_presence"] = (states[0] > 0).astype(jnp.int32)
    for i, slots in _contraction_slots(plan)[0].items():
        rows = [states[j] for j in slots]
        out[f"gb_{i}"] = rows[0] if len(rows) == 1 else tuple(rows)


def _block_view(seg: Dict[str, Any], start, size: int) -> Dict[str, Any]:
    """Rows [start, start + size) of one segment's arrays as a segment
    of their own, the way _gather_blocks hands over a gathered view:
    row-shaped arrays sliced, ``valid`` and the original doc ids
    (``rowid``) sliced where the segment carries them (a gathered view)
    and derived where it does not, so that _valid_mask and docrange
    leaves read the block as they read a segment."""
    view: Dict[str, Any] = {}
    for k, v in seg.items():
        if k in ("valid", "rowid") or _row_key(k):
            view[k] = jax.lax.dynamic_slice_in_dim(v, start, size)
        elif k != "num_docs":
            view[k] = v
    if "rowid" not in view:
        view["rowid"] = start + jax.lax.iota(jnp.int32, size)
    if "valid" not in view:
        view["valid"] = view["rowid"] < seg["num_docs"]
    return view


def _make_loop_groupby_kernel(plan: StaticPlan) -> Callable:
    """The single-segment kernel of a group-by whose operands are built
    in the row loop (groupby_operands 'loop').  One scan over blocks of
    _MATMUL_CHUNK rows; a step slices the staged columns, evaluates the
    filter, the keys and the weight columns of its block, and adds the
    block's states [m, K] to the carried ones.  What the program reads
    from HBM is the staged columns, once; mask, index and weights of a
    block never leave the chip's vector memory.

    A block's states are K masked reductions a column: the one-hot
    contraction, evaluated on the vector unit without the one-hot.
    Values and sums are float32 (the matrix unit at default precision
    rounds the values to bfloat16), and the elementwise work stays on
    [segments, rows] tiles: fed to the matrix unit, the same block's
    operands are laid out [segments, m, rows], m of 8 sublanes used,
    and a K=6 query costs 12.5 ms for this form's 5.5 (chip run, PR 30)."""
    cap = plan.group_by.capacity
    m = _contraction_slots(plan)[1]

    def kernel(seg: Dict[str, Any], q: Dict[str, Any]) -> Dict[str, Any]:
        fdt = config.float_dtype()
        n = next(v.shape[0] for k, v in seg.items() if k == "valid" or _row_shaped(k))
        chunk = min(_MATMUL_CHUNK, n)

        def add_block(states, start, size):
            view = _block_view(seg, start, size)
            mask = _filter_mask(plan, view, q)
            keys, kvalid = _group_keys(plan, view, q, mask)
            flat_idx, cols = _contraction_operands(plan, view, mask, keys, kvalid, zeroed=False)
            rows = [[] for _ in range(m)]
            for g in range(cap):
                hit = flat_idx == g  # never a filtered row: its index is K
                rows[0].append(jnp.sum(hit, dtype=fdt))
                for row, vec in zip(rows[1:], cols[1:]):
                    row.append(jnp.sum(jnp.where(hit, vec, 0), dtype=fdt))
            return states + jnp.stack([jnp.stack(row) for row in rows])

        states, _ = jax.lax.scan(
            lambda states, b: (add_block(states, b * chunk, chunk), None),
            jnp.zeros((m, cap), dtype=fdt),
            jnp.arange(n // chunk, dtype=jnp.int32),
        )
        if n % chunk:  # a gathered view's row count: the tail is a block of its own
            states = add_block(states, n - n % chunk, n % chunk)
        # single-value keys: every matched doc is one entry of the occupancy
        # row, whose float counts are exact (a segment has fewer than 2^24 rows)
        out: Dict[str, Any] = {"num_docs": jnp.sum(states[0]).astype(config.row_count_dtype())}
        _contraction_outputs(plan, states, out)
        _count_states_to_int(plan, out)
        return out

    return kernel


def _make_runs_groupby_kernel(plan: StaticPlan) -> Callable:
    """The single-segment part of the 'runs' lowering (groupby_lowering):
    the segment's filter, keys and weight columns, as the dense lowerings
    build them (_contraction_operands: a filtered row carries the key
    ``capacity`` and weights of zero), handed on row by row as
    ``gb_rows``.  Nothing is summed here: the table's rows meet in
    _reduce_group_runs."""

    def kernel(seg: Dict[str, Any], q: Dict[str, Any]) -> Dict[str, Any]:
        mask = _filter_mask(plan, seg, q)
        keys, kvalid = _group_keys(plan, seg, q, mask)
        flat_idx, cols = _contraction_operands(plan, seg, mask, keys, kvalid, zeroed=True)
        return {"num_docs": jnp.sum(mask, dtype=config.row_count_dtype()), "gb_rows": (flat_idx, *cols[1:])}

    return kernel


def make_single_segment_kernel(plan: StaticPlan) -> Callable:
    if groupby_lowering(plan) == "runs":
        return _make_runs_groupby_kernel(plan)
    if groupby_operands(plan) == "loop":
        return _make_loop_groupby_kernel(plan)
    hll = hll_lowering(plan)

    def kernel(seg: Dict[str, Any], q: Dict[str, Any]) -> Dict[str, Any]:
        mask = _filter_mask(plan, seg, q)
        out: Dict[str, Any] = {
            "num_docs": jnp.sum(mask, dtype=config.row_count_dtype())
        }

        if plan.group_by is not None:
            keys, kvalid = _group_keys(plan, seg, q, mask)
            cap = plan.group_by.capacity
            lowering = groupby_lowering(plan)
            if lowering != "scatter":
                flat_idx, cols = _contraction_operands(plan, seg, mask, keys, kvalid, zeroed=True)
                if lowering == "onehot":
                    states = _segment_add_matmul_multi(flat_idx, jnp.stack(cols), cap)
                elif groupby_operands(plan) == "sorted":
                    states = _segment_add_sorted(flat_idx, cols[1:], cap)
                else:
                    states = _segment_add_radix(flat_idx, cols[1:], cap)
                _contraction_outputs(plan, states, out)
                for i, agg in enumerate(plan.aggs):
                    if f"gb_{i}" not in out:
                        out[f"gb_{i}"] = _group_state(
                            agg, i, seg, q, mask, keys, kvalid, cap, hll
                        )
            else:
                flat_idx = jnp.where(kvalid, keys, cap).reshape(-1)
                out["gb_presence"] = (
                    jnp.zeros(cap, dtype=jnp.int32)
                    .at[flat_idx]
                    .max(kvalid.reshape(-1).astype(jnp.int32), mode="drop")
                )
                for i, agg in enumerate(plan.aggs):
                    out[f"gb_{i}"] = _group_state(
                        agg, i, seg, q, mask, keys, kvalid, cap, hll
                    )
        else:
            for i, agg in enumerate(plan.aggs):
                out[f"agg_{i}"] = _agg_state(agg, i, seg, q, mask, hll)
        _count_states_to_int(plan, out)

        if plan.selection is not None:
            out.update(_selection_outputs(plan, seg, q, mask))
        return out

    return kernel


def _sort_ordinals(sel, seg, q, dtype):
    """Per sort column: global ordinal of each doc's value, ascending
    order (descending columns flipped). MV columns order by first value
    (oracle semantics)."""
    for col, asc, gcard, remap, use_g in zip(
        sel.sort_columns,
        sel.sort_ascending,
        sel.sort_gcards,
        q["sel_remap"],
        sel.use_gfwd,
    ):
        if use_g:
            g = seg[f"{col}.gfwd"].astype(dtype)
        else:
            scol = seg.get(f"{col}.fwd")
            if scol is None:
                scol = seg[f"{col}.mv"][:, 0]
            g = remap[scol].astype(dtype)
        if not asc:
            g = (gcard - 1) - g
        yield g, gcard


def selection_lowering(plan) -> Optional[str]:
    """The form a selection's k candidates a segment are found in, from
    what the plan states (None for a plan without a selection): consulted
    by _selection_outputs and by the launch's ``selection=`` tag and
    ``selection.lowering.*`` mark, which must agree.

    'first': no sort column: the first k matching rows, in doc order.
    'topk':  the sort columns' global ordinals pack into one key
             (StaticSelection.packed: the product of their table
             cardinalities at most config.max_key_space()): one
             ``lax.top_k`` a segment over all its rows.
    'sort':  a wider key: a stable multi-operand ``lax.sort`` of every
             row, one int32 operand a sort column."""
    sel = getattr(plan, "selection", None)
    if sel is None:
        return None
    if not sel.sort_columns:
        return "first"
    return "topk" if sel.packed else "sort"


def _selection_outputs(plan: StaticPlan, seg, q, mask) -> Dict[str, Any]:
    sel = plan.selection
    n = mask.shape[0]
    kdt = config.key_dtype()
    lowering = selection_lowering(plan)
    if lowering == "first":
        # first-k matching docIds, in doc order
        score = jnp.where(mask, jnp.arange(n, dtype=kdt), n)
    elif lowering == "sort":
        # Wide key space: radix product overflows the key dtype, so sort
        # lexicographically with one int32 operand per sort column instead
        # of packing (XLA sorts multi-operand natively; reference handles
        # this with its heap comparator, SelectionOperatorService.java:66).
        keys = [jnp.logical_not(mask).astype(jnp.int32)]  # matches first
        keys.extend(g for g, _ in _sort_ordinals(sel, seg, q, jnp.int32))
        keys.append(jnp.arange(n, dtype=jnp.int32))  # doc-order tie-break
        sorted_ops = jax.lax.sort(tuple(keys), num_keys=len(keys), is_stable=True)
        idx = sorted_ops[-1][: sel.k]
        return {"sel_docids": idx, "sel_valid": mask[idx]}
    else:
        key = jnp.zeros(n, dtype=kdt)
        for g, gcard in _sort_ordinals(sel, seg, q, kdt):
            key = key * gcard + g
        score = jnp.where(mask, key, jnp.iinfo(kdt).max)
    neg = -score
    _, idx = jax.lax.top_k(neg, sel.k)  # k smallest scores
    sel_valid = mask[idx]
    return {"sel_docids": idx.astype(jnp.int32), "sel_valid": sel_valid}


# ---------------------------------------------------------------------------
# Cross-segment merge spec + compiled table kernel
# ---------------------------------------------------------------------------


def output_reducers(plan: StaticPlan) -> Dict[str, str]:
    """Reduce op over the segment axis per output key.

    'none' outputs stay per-segment (selection candidates).
    These same ops become `psum`/`pmax`-style collectives across chips.
    """
    red: Dict[str, str] = {"num_docs": "sum"}
    if groupby_lowering(plan) == "runs":
        red["gb_rows"] = "runs"  # no state a segment: reduce_outputs hands every segment's rows to _reduce_group_runs
        return red
    if plan.group_by is not None:
        red["gb_presence"] = "max"
        for i, agg in enumerate(plan.aggs):
            red[f"gb_{i}"] = _state_reduce(agg)
    else:
        for i, agg in enumerate(plan.aggs):
            red[f"agg_{i}"] = _state_reduce(agg)
    if plan.selection is not None:
        red["sel_docids"] = "none"
        red["sel_valid"] = "none"
    return red


def _state_reduce(agg: StaticAgg) -> str:
    base = agg.base
    if base in ("count", "sum"):
        return "sum"
    if base == "min":
        return "min"
    if base == "max":
        return "max"
    if base == "avg":
        return "sum_pair"
    if base == "minmaxrange":
        return "minmax_pair"
    if agg.kind == "presence":
        return "distinct_pairs" if agg.sort_pairs else "max"
    if agg.kind == "hist":
        return "distinct_pairs" if agg.sort_pairs else "sum"
    if agg.kind == "hll":
        return "distinct_pairs" if agg.sort_pairs else "max"  # dense registers, whatever built them
    raise AssertionError(agg)


# int32 sentinel marking invalid (masked) pairs; sorts past every real
# (slot, gid) pair since slots < MAX_GROUP_CAPACITY and gids < 2^31-1
_PAIR_SENTINEL = np.iinfo(np.int32).max


def _hll_rows(agg: StaticAgg, seg, bucket, rho):
    """Per-row (register index, rank) for an SV HLL agg: prefer the
    host-staged uint8 streams over on-device table gathers.  Returned
    in their NATIVE dtype (uint8 streams) — consumers cast only where
    the op needs it, because a blanket int32 cast materializes 4 B/row
    temporaries that dominate HBM at 1B rows."""
    hb = seg.get(f"{agg.column}.hllb")
    if hb is not None:
        return hb, seg[f"{agg.column}.hllr"]
    fwd = seg[f"{agg.column}.fwd"]
    return bucket[fwd], rho[fwd]


def _value_gids(agg: StaticAgg, seg, remap):
    """Per-row GLOBAL value ids for an SV presence/hist agg: prefer
    the host-staged global-id stream (``.gfwd``, ladder._role_columns)
    over an on-device remap-table gather — device gathers serialize on
    TPU at any cardinality (2026-07 chip measurement, ROADMAP S5)."""
    gf = seg.get(f"{agg.column}.gfwd")
    if gf is not None:
        return gf
    return remap[seg[f"{agg.column}.fwd"]]


def _reduce_distinct_pairs(value):
    """Global sort-dedup of (group slot, valueId) pairs across all
    segments — the exact distinct/histogram merge without per-pair
    state.

    1. lexicographic sort of the flattened pairs (two int32 keys — no
       int64 needed, so it runs with x64 disabled on TPU),
    2. run-boundary mask = the unique pairs; sentinels excluded,
    3. stable compaction sort (unique-first, position carried as
       payload) into a DISTINCT_PAIR_CAP buffer.

    Returns (slots[CAP], gids[CAP], starts[CAP], n_unique, total_valid):
    ``starts`` are each run's first position in the sorted order, so
    per-pair OCCURRENCE counts fall out as diff(starts) on host —
    distinctcount ignores them, exact percentile histograms need them.
    Host falls back when n_unique overflows the buffer.
    """
    s = value[0].reshape(-1)
    g = value[1].reshape(-1)
    s, g = jax.lax.sort((s, g), num_keys=2, is_stable=True)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (s[1:] != s[:-1]) | (g[1:] != g[:-1])]
    )
    uniq = first & (s != _PAIR_SENTINEL)
    n_unique = jnp.sum(uniq).astype(jnp.int32)
    total_valid = jnp.sum(s != _PAIR_SENTINEL).astype(jnp.int32)
    rank = jnp.where(uniq, 0, 1).astype(jnp.int32)
    pos = jax.lax.iota(jnp.int32, s.shape[0])
    _, s2, g2, p2 = jax.lax.sort((rank, s, g, pos), num_keys=1, is_stable=True)
    k = min(config.DISTINCT_PAIR_CAP, int(s2.shape[0]))
    return (s2[:k], g2[:k], p2[:k], n_unique, total_valid)


def counts_from_starts(starts, n, total):
    """Recover per-pair occurrence counts from a compacted 5-tuple's
    run starts ON DEVICE (the host does this with np.diff): entry i's
    count = starts[i+1] - starts[i], last valid entry = total - start."""
    k = starts.shape[0]
    iota = jax.lax.iota(jnp.int32, k)
    nxt = jnp.concatenate([starts[1:], starts[-1:]])
    nxt = jnp.where(iota == n - 1, total, nxt)
    return jnp.where(iota < n, nxt - starts, 0)


def merge_pair_buffers(slots, gids, counts):
    """Merge gathered per-chip compacted (slot, gid, count) buffers into
    one 5-tuple with the same contract as _reduce_distinct_pairs.

    The exclusive cumsum of counts in merged-sorted order plays the
    'starts' role: diff of consecutive unique entries' excl-cumsum is
    exactly the summed count of the run (each (slot, gid) appears at
    most once per chip)."""
    s = slots.reshape(-1).astype(jnp.int32)
    g = gids.reshape(-1).astype(jnp.int32)
    c = counts.reshape(-1).astype(jnp.int32)
    s, g, c = jax.lax.sort((s, g, c), num_keys=2, is_stable=True)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (s[1:] != s[:-1]) | (g[1:] != g[:-1])]
    )
    uniq = first & (s != _PAIR_SENTINEL)
    n_unique = jnp.sum(uniq).astype(jnp.int32)
    total_valid = jnp.sum(jnp.where(s != _PAIR_SENTINEL, c, 0)).astype(jnp.int32)
    excl = jnp.cumsum(c) - c
    rank = jnp.where(uniq, 0, 1).astype(jnp.int32)
    _, s2, g2, e2 = jax.lax.sort((rank, s, g, excl), num_keys=1, is_stable=True)
    k = min(config.DISTINCT_PAIR_CAP, int(s2.shape[0]))
    return (s2[:k], g2[:k], e2[:k], n_unique, total_valid)


def apply_reduce(op: str, value: Any):
    if op == "sum":
        return jnp.sum(value, axis=0)
    if op == "min":
        return jnp.min(value, axis=0)
    if op == "max":
        return jnp.max(value, axis=0)
    if op == "sum_pair":
        return (jnp.sum(value[0], axis=0), jnp.sum(value[1], axis=0))
    if op == "minmax_pair":
        return (jnp.min(value[0], axis=0), jnp.max(value[1], axis=0))
    if op == "distinct_pairs":
        return _reduce_distinct_pairs(value)
    if op == "none":
        return value
    raise ValueError(op)


def reduce_outputs(plan: StaticPlan, outs: Dict[str, Any]) -> Dict[str, Any]:
    """The stacked segments' outputs [S, ...] merged over the segment
    axis, each by its reducer (output_reducers); the rows of a 'runs'
    group-by (``gb_rows``) by _reduce_group_runs, which needs the plan."""
    reducers = output_reducers(plan)
    merged = {k: apply_reduce(reducers[k], v) for k, v in outs.items() if reducers[k] != "runs"}
    if "gb_rows" in outs:
        merged.update(_reduce_group_runs(plan, outs["gb_rows"]))
    return merged


# rows a step of the 'runs' lowering's pass over the sorted ids
# (_run_lengths: whole tiles of [8, 128], a step's ids as [rows / 128,
# 128]), which is also the block whose largest length bounds the cut
# (_kth_largest).  Chip run, PR 49, seed 49001, 12 x 2^23 ids in order
# (4.3M runs, the longest 674,568), ms a pass: 16.67 at 8,192 rows a
# step, 9.22 at 16,384, 5.48 at 32,768, 3.96 at 65,536, 3.71 at 131,072
# (a grid step costs 1.3 us before it reads a row; the ids are read and
# the lengths written in 0.49 each), and the cut reads 100 such blocks
# whole (2.6 ms at 131,072): 65,536 is the least of the two together.
# The same pass as jnp steps (log2(block) shifted maxima a block) read
# 33.5 at blocks of 1,024 and 42.1 at 8,192, and a step that walks a
# longer block in chunks of 8,192 with its carry in scalars 14.1
_RUNS_BLOCK = 65536
# rows of a block the candidates' places are counted and searched by
# (_first_set: a place reads one such block, 10,100 places a query): one
# tile of [8, 128].  In the cell (PR 49, call 1, ms a query): the two
# flags' counts 0.54, the search of 10,000 places over 98,304 blocks
# 0.92, the gather of their blocks 0.26
_RUNS_PLACE_BLOCK = 1024
# partial sums the digest of a 'runs' group-by comes back in (the host
# adds them in float64)
_RUNS_DIGEST_PARTS = 1024


def _shifted(x, step: int, fill):
    """x[i - step] at i, ``fill`` in the first ``step`` places."""
    return jnp.concatenate([jnp.full((step,), fill, x.dtype), x[:-step]])


def _run_lengths(ids, capacity: int, with_dist: bool):
    """ONE pass over ids in ascending order, whole blocks of
    ``_RUNS_BLOCK`` (Pallas, a sequential grid, a block a step): a run of
    equal ids is a group, and a run of ids under ``capacity`` is live.

    Carried from block to block (SMEM): the position where the run that
    is open at the block's end began.  A step is also handed the tile of
    [8, 128] ids before its block and the one after it, for the id before
    its first row and the id after its last (ascending: a tile's largest
    and smallest), so no step waits for another's rows.  In a block, a
    row's run began at the largest start position at or before it: 7
    shifted maxima along the lanes, log2(sublanes) over the rows' last
    lanes, the carry under both.

    Returned, a row: ``order`` int32 [n], a run's length at its last
    live row and the dtype's minimum everywhere else; with ``with_dist``
    also a row's distance from its run's first (what _run_sums reads),
    else None.  A block: how many live runs end in it, the sum of their
    squared lengths (config.float_dtype()) and the largest of them (the
    minimum where none ends), each set down in a tile of [8, 128] that
    stays in VMEM for the 1,024 steps it has places for."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blk, tile = _RUNS_BLOCK, 8 * 128
    R = blk // 128
    fdt = config.float_dtype()
    low = int(jnp.iinfo(jnp.int32).min)
    nb = ids.shape[0] // blk

    def kernel(ids_ref, before_ref, after_ref, *refs):
        order_ref = refs[0]
        dist_ref = refs[1] if with_dist else None
        ends_ref, squares_ref, longest_ref, open_ref = refs[-4:]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _first_block():
            open_ref[0] = 0

        @pl.when(i % tile == 0)
        def _new_tile():
            for ref in (ends_ref, squares_ref, longest_ref):
                ref[...] = jnp.zeros_like(ref)

        x = ids_ref[...]  # [R, 128]: row r, lane l is the block's row 128 r + l
        row = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 1)
        pos = i * blk + row * 128 + lane
        before = jnp.where(i == 0, -1, jnp.max(before_ref[...]))
        after = jnp.where(i == nb - 1, capacity, jnp.min(after_ref[...]))

        def neighbour(step, edge_lane, edge_row, outside):
            by_lane = pltpu.roll(x, step % 128, axis=1)
            at_edge = jnp.where(row == edge_row, outside, pltpu.roll(by_lane, step % R, axis=0))
            return jnp.where(lane == edge_lane, at_edge, by_lane)

        start = x != neighbour(1, 0, 0, before)
        end = (x != neighbour(-1, 127, R - 1, after)) & (x < capacity)
        # where a row's run began: the largest start position at or before it
        first = jnp.where(start, pos, open_ref[0])
        step = 1
        while step < 128:
            first = jnp.maximum(first, jnp.where(lane >= step, pltpu.roll(first, step, axis=1), low))
            step *= 2
        above = pltpu.roll(first, 1, axis=1)[:, :1]  # [R, 1]: a row's last lane, its largest
        rows = row[:, :1]
        step = 1
        while step < R:
            above = jnp.maximum(above, jnp.where(rows >= step, pltpu.roll(above, step, axis=0), low))
            step *= 2
        first = jnp.maximum(first, jnp.where(rows >= 1, pltpu.roll(above, 1, axis=0), low))
        open_ref[0] = jnp.max(first)

        dist = pos - first
        order = jnp.where(end, dist + 1, low)
        order_ref[...] = order
        if with_dist:
            dist_ref[...] = dist
        mine = (row[:8] * 128 + lane[:8]) == i % tile  # the block's place in its tile of summaries
        ends_ref[...] = jnp.where(mine, jnp.sum(end.astype(jnp.int32)), ends_ref[...])
        squares_ref[...] = jnp.where(mine, jnp.sum(jnp.where(end, jnp.square((dist + 1).astype(fdt)), 0)), squares_ref[...])
        longest_ref[...] = jnp.where(mine, jnp.max(order), longest_ref[...])

    by_row = pl.BlockSpec((R, 128), lambda i: (i, 0))
    tile_before = pl.BlockSpec((8, 128), lambda i: (jnp.maximum(i * (R // 8) - 1, 0), 0))
    tile_after = pl.BlockSpec((8, 128), lambda i: (jnp.minimum((i + 1) * (R // 8), nb * (R // 8) - 1), 0))
    by_block = pl.BlockSpec((8, 128), lambda i: (i // tile, 0))
    rows_out = jax.ShapeDtypeStruct((nb * R, 128), jnp.int32)
    tiles = -(-nb // tile) * 8
    ids = ids.reshape(-1, 128)
    outs = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[by_row, tile_before, tile_after],
        out_specs=[by_row] * (1 + with_dist) + [by_block] * 3,
        out_shape=[rows_out] * (1 + with_dist) + [jax.ShapeDtypeStruct((tiles, 128), dt) for dt in (jnp.int32, fdt, jnp.int32)],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        # the carry needs the grid in order: the compiled TPU grid, or the interpreter
        interpret=jax.default_backend() == "cpu",
    )(ids, ids, ids)
    order, dist = outs[0].reshape(-1), (outs[1].reshape(-1) if with_dist else None)
    return (order, dist, *(out.reshape(-1)[:nb] for out in outs[-3:]))


def _run_sums(values, dist, longest):
    """At a run's last row, the sum of the run's ``values`` (rows in run
    order; ``dist`` a row's distance from its run's first): log2(longest
    run) shifted adds, each row adding the partial sum that ends a power
    of two before it where that row is of its run, so a run's values add
    up pairwise, in the values' own precision, whatever the other runs
    hold.  A step no run is long enough for is skipped."""
    step = 1
    while step < values.shape[0]:
        values = jax.lax.cond(
            longest >= step,
            lambda s, k=step: s + jnp.where(dist >= k, _shifted(s, k, 0), 0),
            lambda s: s,
            values,
        )
        step *= 2
    return values


def _order_keys(values):
    """Integers that order as ``values`` do: an integer is its own; a
    float's bits, the magnitude's flipped where the sign is set (a NaN
    stands past +inf, where np.sort leaves it)."""
    if jnp.issubdtype(values.dtype, jnp.integer):
        return values
    idt = jnp.int32 if values.dtype == jnp.float32 else jnp.int64
    bits = jax.lax.bitcast_convert_type(values + 0, idt)  # -0.0 + 0 is +0.0
    return jnp.where(bits < 0, bits ^ jnp.iinfo(idt).max, bits)


def _largest_with(enough, lo, hi):
    """The largest integer in [lo, hi] that ``enough`` holds for, by
    bisection; ``enough`` holds for ``lo`` and for everything under a
    value it holds for."""
    def halve(bounds):
        lo, hi = bounds
        mid = (lo >> 1) + (hi >> 1) + (((lo & 1) + (hi & 1) + 1) >> 1)  # ceil((lo + hi) / 2), no overflow
        ok = enough(mid)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    return jax.lax.while_loop(lambda b: b[0] < b[1], halve, (lo, hi))[0]


def _first_set(keys, flag, size: int):
    """(positions of the first ``size`` rows of integer ``keys`` [blocks,
    ... a block's rows] that ``flag`` holds for, ascending in the flat
    order, the rows' number where they run out; how many rows it holds
    for).  The blocks' counts, a cumulative count over the BLOCKS, one
    search a place over that, and a place's row from the one block it
    lands in (a cumulative count along the block's lanes by shifted adds:
    ``cumsum`` along 1,024 lanes is a window the chip walks, 2.3 ms for
    10,000 blocks): no pass along the rows but the count."""
    nb, blk = keys.shape[0], int(np.prod(keys.shape[1:]))
    counts = jnp.sum(flag(keys).reshape(nb, -1), axis=1, dtype=jnp.int32)
    upto = jnp.cumsum(counts)
    wanted = jnp.arange(1, size + 1, dtype=jnp.int32)
    block = jnp.minimum(jnp.searchsorted(upto, wanted, side="left", method="compare_all"), nb - 1).astype(jnp.int32)
    inside = wanted - (upto[block] - counts[block])  # which of its block's set rows a place is
    lanes = np.gcd(blk, 128)
    upto_row = flag(keys[block]).reshape(size, -1, lanes).astype(jnp.int32)
    step = 1
    while step < lanes:
        upto_row = upto_row + jnp.pad(upto_row[..., :-step], ((0, 0), (0, 0), (step, 0)))
        step *= 2
    rows_before = jnp.cumsum(upto_row[..., -1], axis=1) - upto_row[..., -1]
    within = jnp.sum(upto_row + rows_before[..., None] < inside[:, None, None], axis=(1, 2), dtype=jnp.int32)
    return jnp.where(wanted <= upto[-1], block * blk + within, nb * blk), upto[-1]


def _kth_largest(keys, largest, k: int):
    """The ``k``-th largest of integer ``keys`` [blocks, ... a block's
    rows] (the dtype's minimum where they are fewer), ``largest`` each block's
    largest.  The ``k``-th largest of the blocks' largest is at most the
    answer, and fewer than ``k`` blocks hold anything over it: the
    bisection counts over those blocks' rows alone, from that floor to
    the largest key, and no pass runs along the rows."""
    nb = keys.shape[0]
    low = jnp.array(jnp.iinfo(keys.dtype).min, keys.dtype)
    top = jnp.max(largest)
    floor = low
    if nb >= k:
        floor = _largest_with(lambda v: jnp.sum(largest >= v, dtype=jnp.int32) >= k, low, top)
    over, found = _first_set(largest[None, :], lambda v: v > floor, min(k, nb))
    found = (jnp.arange(over.shape[0]) < found).reshape(-1, *[1] * (keys.ndim - 1))
    rows = jnp.where(found, keys[jnp.minimum(over, nb - 1)], low)
    return _largest_with(lambda v: jnp.sum(rows >= v, dtype=jnp.int32) >= k, floor, top)


def _reduce_group_runs(plan: StaticPlan, rows):
    """The 'runs' lowering's merge (groupby_lowering): every segment's
    rows ``(key [S, n], weight columns [S, n] ...)`` to what the server's
    finalize needs of a group-by, with no array of ``capacity`` cells.

    ONE sort of the table's keys carrying the weight columns (a filtered
    row's key is ``capacity`` and sorts last; so do the rows that pad the
    table to whole blocks of ``_RUNS_BLOCK``): a run of equal keys is a
    group, whichever segments its rows came from, so the sort is the
    merge across segments as well.  The sorted ids are then read ONCE
    (_run_lengths): a run's count is its length, taken from row positions
    in int32 (exact at any size: no count crosses the segment axis as a
    float) and set down at the run's last row; a block hands on how many
    live runs end in it (their sum is the live count), the sum of their
    squared lengths (the digest of a count) and the largest of them.  A
    plan that carries columns also takes a row's distance from its run's
    first, and its sums are _run_sums over the carried columns.  Every
    aggregate's value stands at its run's last row.

    Out of those, per aggregate in its own (descending) order, the
    per-server trim's candidates as results.trim_group_candidates keeps
    them: with more than ``max(5 x TOP, 100)`` live groups, the groups
    strictly beyond the value at that cut and the groups tied with it in
    ascending key, ``max(MAX_TRIM_TIES, what is left of the trim)`` of
    them; else every live group.  The cut is _kth_largest over the blocks
    the blocks' largest values leave; the positions come from _first_set,
    by block.  Returned: ``gb_runs_keys`` (the candidates'
    keys an aggregate, -1 where a place is empty; an aggregate's list may
    repeat another's), ``gb_runs_state`` (the m state rows of
    _contraction_slots at those places, the occupancy's as a row count),
    ``gb_runs_live`` (live groups, exact) and ``gb_runs_sumsq`` (each
    aggregate's sum of squared values over every live group, in at most
    _RUNS_DIGEST_PARTS partial sums: the cost vector's
    ``groupStateSumSq``).  A few hundred kilobytes at the most, whatever
    ``capacity`` is."""
    from pinot_tpu.engine.results import MAX_TRIM_TIES

    gb = plan.group_by
    fdt = config.float_dtype()
    # the segments' rows as they were made, then laid flat: without the
    # barrier the compiler lays flat every operand of the filter's select
    # (the ids, an iota, the segments' row counts), a copy of the table each
    if rows[0].shape[-1] % 1024 == 0:  # a segment's rows as tiles of [8, 128]: the flat order, and a copy the chip makes at twice the rate
        rows = [r.reshape(*r.shape[:-1], -1, 8, 128) for r in rows]
    rows = jax.lax.optimization_barrier(tuple(rows))
    ids = rows[0].reshape(-1)
    cols = [c.reshape(-1) for c in rows[1:]]
    pad = (-ids.shape[0]) % _RUNS_BLOCK
    if pad:  # rows that count nowhere, as a filtered row does
        ids = jnp.concatenate([ids, jnp.full(pad, gb.capacity, ids.dtype)])
        cols = [jnp.concatenate([c, jnp.zeros(pad, c.dtype)]) for c in cols]
    n = ids.shape[0]
    trim = max(gb.top_n * 5, 100)
    most_ties = max(MAX_TRIM_TIES, trim)

    with jax.named_scope("groupby_runs_sort"):
        ids, *cols = jax.lax.sort((ids, *cols), num_keys=1, is_stable=False)

    with jax.named_scope("groupby_runs_pass"):
        length, dist, ends, squares, longest = _run_lengths(ids, gb.capacity, bool(cols))
        live = jnp.sum(ends)
        state = [length]  # the occupancy: a run's length at its last row
        if cols:
            most = jnp.maximum(jnp.max(longest), 1) - 1
            state.extend(_run_sums(c, dist, most) for c in cols)
        end = length > 0

    def value_of(agg: StaticAgg, slots):
        if agg.base == "avg":
            return state[slots[0]] / jnp.maximum(state[slots[1]], 1).astype(fdt)
        return state[slots[0]]

    def in_parts(sums):  # a block's or a row's, to partial sums of whole blocks
        return jnp.sum(sums.reshape(np.gcd(n // _RUNS_BLOCK, _RUNS_DIGEST_PARTS), -1), axis=1)

    keys_out, at_out, sumsq = [], [], []
    chosen: Dict[tuple, tuple] = {}  # aggregates of one order share one selection
    for i, slots in _contraction_slots(plan)[0].items():
        agg = plan.aggs[i]
        which = (agg.base == "avg", tuple(slots))
        if which not in chosen:
            if which == (False, (0,)):  # a count: the pass made its order, its blocks' largest and its digest's parts
                order, largest = length, longest
                with jax.named_scope("groupby_runs_digest"):
                    digest = in_parts(squares)
            else:
                value = value_of(agg, slots)
                with jax.named_scope("groupby_runs_digest"):
                    digest = in_parts(jnp.where(end, jnp.square(value.astype(fdt)), 0))
                with jax.named_scope("groupby_runs_select"):
                    order = _order_keys(value)
                    order = jnp.where(end, order, jnp.iinfo(order.dtype).min)
                    largest = jnp.max(order.reshape(-1, _RUNS_BLOCK), axis=1)
            with jax.named_scope("groupby_runs_select"):
                # blocks as whole tiles of [8, 128]: views of the flat rows, where [blocks, rows a block] is a copy
                cut = _kth_largest(order.reshape(-1, _RUNS_BLOCK // 128, 128), largest, trim)
                few = live <= trim
                low = jnp.iinfo(order.dtype).min
                by_block = order.reshape(-1, _RUNS_PLACE_BLOCK // 128, 128)
                beyond_at, beyond_n = _first_set(by_block, lambda v: (v > low) & (few | (v > cut)), min(trim, n))
                tied_at, tied_n = _first_set(by_block, lambda v: (v > low) & ~few & (v == cut), min(most_ties, n))
                tied_n = jnp.minimum(tied_n, jnp.maximum(MAX_TRIM_TIES, trim - beyond_n))
                at = jnp.concatenate([beyond_at, tied_at])
                kept = jnp.concatenate([
                    jnp.arange(beyond_at.shape[0], dtype=jnp.int32) < beyond_n,
                    jnp.arange(tied_at.shape[0], dtype=jnp.int32) < tied_n,
                ])
            chosen[which] = (jnp.minimum(at, n - 1), kept, digest)
        at, kept, digest = chosen[which]
        keys_out.append(jnp.where(kept, ids[at], -1))
        at_out.append(at)
        sumsq.append(digest)
    at = jnp.concatenate(at_out)
    return {
        "gb_runs_keys": jnp.concatenate(keys_out),
        "gb_runs_state": tuple(
            row[at].astype(config.row_count_dtype()) if r == 0 else row[at] for r, row in enumerate(state)
        ),
        "gb_runs_live": live,
        "gb_runs_sumsq": jnp.stack(sumsq),
    }


def _row_key(key: str) -> bool:
    return key.endswith((".fwd", ".raw", ".gfwd", ".mv", ".mvc", ".hllb", ".hllr", ".mvraw"))


def _gather_blocks(seg: Dict[str, Any], ids: jnp.ndarray, block: int):
    """Gather candidate row blocks out of one segment's staged arrays.

    ids: int32 [nb_pad], -1 = padding.  Row-shaped arrays [n_pad, ...]
    come back as [nb_pad*block, ...]; a ``valid`` mask and the original
    doc ids (``rowid``) are derived so the single-segment kernel runs
    unchanged on the gathered view.
    """
    safe = jnp.maximum(ids, 0)
    out: Dict[str, Any] = {}
    for k, v in seg.items():
        if k == "num_docs" or k == "valid" or not _row_key(k):
            if k not in ("num_docs", "valid"):
                out[k] = v
            continue
        nb_tot = v.shape[0] // block
        vb = v.reshape((nb_tot, block) + v.shape[1:])
        out[k] = vb[safe].reshape((ids.shape[0] * block,) + v.shape[1:])
    offs = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], block), 1)
    rowid = (safe[:, None] * block + offs).reshape(-1)
    live = jnp.broadcast_to((ids >= 0)[:, None], (ids.shape[0], block)).reshape(-1)
    if "num_docs" in seg:
        valid = live & (rowid < seg["num_docs"])
    else:
        vb = seg["valid"].reshape(-1, block)
        valid = live & vb[safe].reshape(-1)
    out["valid"] = valid
    out["rowid"] = rowid  # original doc ids (docrange leaves, selection)
    return out, rowid


def make_single_segment_block_kernel(plan: StaticPlan, block: int) -> Callable:
    """Single-segment kernel over a gathered copy of the candidate row
    blocks: the zone tier's form for a plan whose outputs need the view
    whole (zone_blocks 'gathered')."""
    single = make_single_segment_kernel(plan)

    def kernel(seg: Dict[str, Any], q: Dict[str, Any], ids: jnp.ndarray):
        gseg, rowid = _gather_blocks(seg, ids, block)
        out = single(gseg, q)
        if "sel_docids" in out:
            out["sel_docids"] = rowid[out["sel_docids"]]
        return out

    return kernel


def kernel_name(tier: str, plan, digest_of=None) -> str:
    """The name a device program is jitted under:
    ``pinot_<tier>_<agg|gb<K>|sel>_<first 8 of the plan digest>``, so
    that a capture's ``XLA Modules`` (``jit_<name>(<fingerprint>)``),
    the launch and wait spans' ``program=`` tag and EXPLAIN's
    ``device.planDigest`` agree.  ``tier``: scan, zone (block-skipping),
    bsi, join, their batched twins (``scanb``, ``bsib``) and the mesh
    programs (``mesh``, ``meshzone``).  ``K`` is the dense group
    capacity.  The digest is ``engine/dispatch.plan_digest`` of the
    literal-erased plan (of ``digest_of`` where the plan object is not
    what the lane digests): stable across processes, seeds and code
    changes that leave the plan's fields alone, where XLA's fingerprint
    moves with any change to the lowered program."""
    from pinot_tpu.engine.dispatch import plan_digest

    group_by = getattr(plan, "group_by", None)
    if group_by is not None:
        shape = f"gb{group_by.capacity}"
    elif getattr(plan, "n_groups", 0):  # a JoinPlan's group space
        shape = f"gb{plan.n_groups}"
    elif getattr(plan, "selection", None) is not None:
        shape = "sel"
    else:
        shape = "agg"
    return f"pinot_{tier}_{shape}_{plan_digest(plan if digest_of is None else digest_of)[:8]}"


def named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``, for ``jax.jit`` to name its module after."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def launch_view(segs: Dict[str, Any], q: Dict[str, Any]):
    """(the staged arrays the launch works on, the query's per-segment
    inputs): what a table program does first with its arguments.

    A launch over the whole staged table carries no ``q["segments"]``
    and gets both back as they came: the program traced is the one
    traced before there was a choice, by the same name and fingerprint.
    A launch over L of the table's S segments carries what
    ``ladder.launch_segments`` made (the key's presence is the inputs'
    structure, so jit keeps the two apart) and every other input of ``q``
    with L rows.  The view is the L rows from ``first`` on of the
    resident ``[S, ...]`` arrays, one ``dynamic_slice``: a transient of
    this program and never a staged table.  ``slots`` int32[L] holds a
    slot's segment, -1 for a slot with no valid row.
    On the chip (PR 48, SSB's q4_2 and q4_3 at 4 of 16 segments of 2^23
    rows): the slice 14.2 and 112.3 ms of device time a query, a take at
    the slots 21.0 and 119.0, four single-segment slices concatenated
    22.4 and 129.9, the whole launch 52.0 and 505.2."""
    launch = q.get("segments")
    if launch is None:
        return segs, q
    q = {k: v for k, v in q.items() if k != "segments"}
    slots = launch["slots"]
    view = {k: jax.lax.dynamic_slice_in_dim(v, launch["first"], slots.shape[0], axis=0) for k, v in segs.items()}
    if "num_docs" in view:
        view["num_docs"] = jnp.where(slots >= 0, view["num_docs"], 0)
    else:
        view["valid"] = view["valid"] & (slots >= 0)[:, None]
    return view, q


@functools.lru_cache(maxsize=256)
def make_table_kernel(plan: StaticPlan) -> Callable:
    """vmap the single-segment kernel over the stacked segment axis and
    merge; jitted once per (plan, shape signature).

    The lru_cache is what makes jit's own executable cache effective:
    returning a fresh jit wrapper per query would retrace and recompile
    the same plan on every call.
    """
    single = make_single_segment_kernel(plan)

    def table_fn(segs: Dict[str, Any], q: Dict[str, Any]) -> Dict[str, Any]:
        outs = jax.vmap(single)(*launch_view(segs, q))
        return reduce_outputs(plan, outs)

    return jax.jit(named(table_fn, kernel_name("scan", plan)))


# Per-row kernel temporaries scale with S * n_pad: beyond ~2^28 rows the
# int32 intermediates alone reach several GB and a 1B-row table blows
# the 16 GB HBM at compile time.  Chunking the segment axis bounds the
# working set; chunk outputs (already segment-reduced) combine with the
# same elementwise ops the in-kernel reduce uses.  Env-overridable
# (PINOT_TPU_CHUNK_ROWS = max rows per dispatch; 0 disables).
_ELEMENTWISE_REDUCERS = ("sum", "min", "max", "sum_pair", "minmax_pair")


def chunk_rows_limit() -> int:
    import os

    try:
        return int(os.environ.get("PINOT_TPU_CHUNK_ROWS", str(1 << 28)))
    except ValueError:
        return 1 << 28


def plan_chunkable(plan: StaticPlan) -> bool:
    """Chunk-combinable: every output reduces elementwise.  The
    distinct_pairs sort-dedup buffers and per-segment selection outputs
    need their full segment axis in one program."""
    return all(op in _ELEMENTWISE_REDUCERS for op in output_reducers(plan).values())


def combine_reduced(op: str, a, b):
    if op == "sum":
        return a + b
    if op == "max":
        return jnp.maximum(a, b)
    if op == "min":
        return jnp.minimum(a, b)
    if op == "sum_pair":
        return (a[0] + b[0], a[1] + b[1])
    if op == "minmax_pair":
        return (jnp.minimum(a[0], b[0]), jnp.maximum(a[1], b[1]))
    raise ValueError(op)


# The fold of an in-place step reads and writes every segment's carried
# outputs, so they have to stay small beside the block of rows the step
# reads (65,536 rows of 10 B in TPC-H Q6).  Measured on the chip at
# [2, 2000] float states (the closed cell's Q5) and at two scalars (Q6);
# nothing above that was, so a dense holder of more elements than this
# keeps the gathered view, whose one pass builds it once.
_INPLACE_STATE_CELLS = 1 << 18


def _state_cells(plan: StaticPlan) -> int:
    """Elements of one segment's outputs, from what the plan states."""

    def width(agg: StaticAgg) -> int:
        if agg.kind in ("presence", "hist"):
            return agg.gcard_pad
        if agg.kind == "hll":
            return config.HLL_M
        return 2 if agg.base in ("avg", "minmaxrange") else 1

    groups = plan.group_by.capacity if plan.group_by is not None else 1
    return groups * (1 + sum(width(agg) for agg in plan.aggs))


def zone_blocks(plan: StaticPlan) -> str:
    """How the zone tier's block program reads a launch's candidate
    blocks, from what the plan states: consulted by the kernel builder
    (make_stacked_block_kernel) and by the launch's ``blocks=`` tag and
    ``zone.blocks.*`` mark, which must agree.

    'inplace':  every output combines elementwise over blocks of rows
                (_ELEMENTWISE_REDUCERS: no selection part, no
                distinct_pairs), no HLL aggregate takes the 'sort'
                lowering and a segment's outputs are at most
                _INPLACE_STATE_CELLS elements: a loop over the candidate
                ids slices the staged columns where they lie and folds
                each block's outputs into the carried ones.
    'gathered': every other plan: its outputs need the view whole, so
                the candidate blocks are copied out first
                (_gather_blocks).  A sorted HLL's registers would fold,
                but a sort and a Pallas call a block of rows is not what
                the loop was measured for, and no cell asks (ROADMAP
                R6a)."""
    elementwise = all(op in _ELEMENTWISE_REDUCERS for op in output_reducers(plan).values())
    inplace = elementwise and hll_lowering(plan) != "sort" and _state_cells(plan) <= _INPLACE_STATE_CELLS
    return "inplace" if inplace else "gathered"


def _reducer_identity(op: str, like):
    """What combine_reduced(op, ., x) leaves x at, shaped like ``like``
    (a ShapeDtypeStruct, or the pair of them a ``*_pair`` reducer has)."""

    def fill(kind: str, s):
        if kind == "sum":
            return jnp.zeros(s.shape, s.dtype)
        if jnp.issubdtype(s.dtype, jnp.floating):
            return jnp.full(s.shape, BIG if kind == "min" else -BIG, s.dtype)
        info = jnp.iinfo(s.dtype)
        return jnp.full(s.shape, info.max if kind == "min" else info.min, s.dtype)

    if op == "sum_pair":
        return (fill("sum", like[0]), fill("sum", like[1]))
    if op == "minmax_pair":
        return (fill("min", like[0]), fill("max", like[1]))
    return fill(op, like)


def _make_inplace_block_kernel(plan: StaticPlan, block: int) -> Callable:
    """The zone tier's block program for an 'inplace' plan, over the
    stacked segment axis: segs, q, ids [S, nb_pad] -> every segment's
    outputs [S, ...], as ``vmap`` of the single-segment kernel gives
    them.

    One loop over the UNION of the launch's candidate block ids.  A step
    takes rows [id * block, (id + 1) * block) of every segment at once
    (_block_view at a start the segments share), runs the plan's
    single-segment kernel on that view with a segment's rows valid only
    where the id is among its own candidates, and folds the block's
    outputs into the carried ones with the plan's reducers.  What the
    program reads from HBM is those blocks of the staged columns, once,
    where they lie.

    Why the union and not each segment's own list: a staged column is
    [segments, rows] and the chip tiles it (8, 128), a 1-byte column
    four segments to a 32-bit word, so one segment's block is not
    contiguous in HBM.  A start that differs by segment is a gather,
    which the compiler lowers to a slice-and-update copy a segment a
    column a step, and a loop over (segment, block) pairs works one
    sublane in eight (chip run, PR 35: PERF.md section 6).  A shared
    start is the row loop's slice.  Segments of one table filtered on a
    clustered column keep much the same blocks; where they do not, the
    loop runs to the union's length and masks, never further than a
    scan of every block.

    The ids arrive packed to the front, -1 behind them
    (zonemap.block_ids_input); a launch without a candidate runs one
    step with nothing valid, which gives the kernel's own empty
    outputs."""
    single = make_single_segment_kernel(plan)
    reducers = output_reducers(plan)

    def kernel(segs: Dict[str, Any], q: Dict[str, Any], ids: jnp.ndarray) -> Dict[str, Any]:
        n = next(v.shape[1] for k, v in segs.items() if k == "valid" or _row_key(k))
        # member[s, b]: block b is among segment s's candidates (a padding -1 is no block's id)
        member = jnp.any(ids[:, :, None] == jnp.arange(n // block, dtype=ids.dtype), axis=1)
        wanted = jnp.any(member, axis=0)
        order = jnp.argsort(~wanted, stable=True).astype(jnp.int32)  # the union's ids first, ascending

        def step_outputs(j):
            b = order[j]

            def of_segment(seg, q_seg, live):
                view = _block_view(seg, b * block, block)
                view["valid"] = view["valid"] & live
                return single(view, q_seg)

            return jax.vmap(of_segment)(segs, q, member[:, b])

        carried = {
            k: _reducer_identity(reducers[k], like)
            for k, like in jax.eval_shape(step_outputs, jnp.int32(0)).items()
        }
        return jax.lax.fori_loop(
            0,
            jnp.maximum(jnp.sum(wanted, dtype=jnp.int32), 1),
            lambda j, acc: {k: combine_reduced(reducers[k], acc[k], v) for k, v in step_outputs(j).items()},
            carried,
        )

    return kernel


def make_stacked_block_kernel(plan: StaticPlan, block: int) -> Callable:
    """The zone tier's block program over the stacked segment axis,
    before the merge: segs, q, block ids int32 [S, nb_pad] (-1 padded,
    packed to the front) -> every segment's outputs [S, ...].  The form
    is zone_blocks(plan)'s."""
    if zone_blocks(plan) == "inplace":
        return _make_inplace_block_kernel(plan, block)
    return jax.vmap(make_single_segment_block_kernel(plan, block))


@functools.lru_cache(maxsize=256)
def make_block_table_kernel(plan: StaticPlan, block: int) -> Callable:
    """Jitted block-skipping variant of make_table_kernel; extra input:
    block ids int32 [S, nb_pad] (-1 padded)."""
    stacked = make_stacked_block_kernel(plan, block)

    def table_fn(segs, q, ids):
        outs = stacked(*launch_view(segs, q), ids)
        return reduce_outputs(plan, outs)

    return jax.jit(named(table_fn, kernel_name("zone", plan)))


def _pick_chunk(num_segments: int, n_pad: int, limit: int, granularity: int = 1) -> int:
    """Segments per dispatch under the row budget, in multiples of
    ``granularity`` (the mesh device count on sharded paths).  Prefers
    a divisor of num_segments (every dispatch then shares one compiled
    shape) but never shrinks below half the budget chasing one — a
    remainder-shaped trailing chunk costing one extra compile is
    cheaper than collapsing to tiny dispatches on prime counts."""
    chunk = max(1, limit // max(n_pad, 1)) if limit else num_segments
    chunk = max(granularity, (chunk // granularity) * granularity)
    divisor = chunk
    while divisor > max(granularity, chunk // 2) and (
        num_segments % divisor or divisor % granularity
    ):
        divisor -= granularity
    if (
        divisor >= max(granularity, chunk // 2)
        and num_segments % divisor == 0
        and divisor % granularity == 0
    ):
        chunk = divisor
    return chunk


@functools.lru_cache(maxsize=64)
def _chunked_program(plan: StaticPlan, mesh, num_segments: int, chunk: int) -> Callable:
    """The table program (``mesh``: the sharded one), dispatched over
    the segment axis ``chunk`` segments at a time, the chunks' reduced
    outputs combined as the program's own merge combines them."""
    from pinot_tpu.engine.packing import make_packed_kernel

    if mesh is None:
        table = make_table_kernel(plan)
    else:
        from pinot_tpu.parallel.multichip import make_sharded_table_kernel

        table = make_sharded_table_kernel(plan, mesh)
    reducers = output_reducers(plan)
    # the combined outputs still fetch via ONE packed D2H transfer —
    # per-leaf fetches pay a transfer each (engine/packing.py).  The
    # chunks run as ``table``'s program; the pack is named after it.
    pack = make_packed_kernel(lambda o: o, table.__name__ + "_pack")

    def sliced(tree, s, e):
        return jax.tree_util.tree_map(lambda x: x[s:e], tree)

    def dispatch(segs: Dict[str, Any], q: Dict[str, Any]):
        outs = None
        # a launch over a window of the table's segments (launch_view)
        # chunks its slots with its inputs, and each chunk's program
        # takes its own part of the window from the whole resident arrays
        launch = q.get("segments")
        rest = q if launch is None else {k: v for k, v in q.items() if k != "segments"}
        for s in range(0, num_segments, chunk):
            e = min(s + chunk, num_segments)
            if launch is None:
                o = table(sliced(segs, s, e), sliced(q, s, e))
            else:
                part = {"slots": launch["slots"][s:e], "first": launch["first"] + s}
                o = table(segs, dict(sliced(rest, s, e), segments=part))
            outs = (
                o
                if outs is None
                else {k: combine_reduced(reducers[k], outs[k], o[k]) for k in o}
            )
        return pack.dispatch(outs)

    def run(segs: Dict[str, Any], q: Dict[str, Any]) -> Dict[str, Any]:
        return pack.fetch(dispatch(segs, q))

    # device-lane pipeline halves (engine/dispatch.py): launch the chunk
    # sequence without blocking, fetch later from the FINALIZE worker
    run.dispatch = dispatch
    run.fetch = pack.fetch
    run.__name__ = table.__name__
    return run


@functools.lru_cache(maxsize=256)
def _packed_sharded_kernel(plan: StaticPlan, mesh, block: Optional[int]) -> Callable:
    """The mesh's program (``block``: its zone form) behind the
    single-transfer fetch.  ``Mesh`` hashes and compares by its devices
    and axis names, which is a placement's identity: two chip groups
    never share a program, two lanes over one group do."""
    from pinot_tpu.engine.packing import make_packed_kernel
    from pinot_tpu.parallel.multichip import make_sharded_table_kernel

    return make_packed_kernel(
        make_sharded_table_kernel(plan, mesh, block), kernel_name("mesh" if block is None else "meshzone", plan)
    )


@functools.lru_cache(maxsize=256)
def make_packed_table_kernel(plan: StaticPlan) -> Callable:
    """make_table_kernel + single-transfer output fetch: returns HOST
    numpy outputs via one packed D2H transfer (engine/packing.py) —
    the serving path's kernel (per-leaf fetches pay one transfer
    each)."""
    from pinot_tpu.engine.packing import make_packed_kernel

    return make_packed_kernel(make_table_kernel(plan), kernel_name("scan", plan))


@functools.lru_cache(maxsize=256)
def make_packed_block_table_kernel(plan: StaticPlan, block: int) -> Callable:
    from pinot_tpu.engine.packing import make_packed_kernel

    return make_packed_kernel(make_block_table_kernel(plan, block), kernel_name("zone", plan))


def plan_program(plan: StaticPlan, num_segments: int, n_pad: int, block: Optional[int] = None, mesh=None) -> Callable:
    """The device program that answers ``plan`` over a staged table of
    ``num_segments`` x ``n_pad`` rows: the one place that chooses among
    the builders of this module, for the executor's launch, EXPLAIN and
    the prewarm worker alike.  ``block``: the zone tier's block rows
    where the launch carries candidate block ids, else None; ``mesh``:
    the chip group the segment axis is sharded over, else None.

    What comes back has ``.dispatch`` and ``.fetch`` (engine/packing.py)
    and, where it is one program, ``.lower``; a table over the
    per-dispatch row budget (``chunk_rows_limit``, per device on a mesh)
    whose outputs combine elementwise is a sequence of launches of the
    table program and has none.  Every handle is kept where its builder
    keeps it (the ``lru_cache``s here): asked twice, the same callable,
    so jit's executables are found again and a program the prewarm
    worker compiled is the one the first launch calls.

    ``kernel_name`` tiers: ``scan`` and ``zone`` on one device, ``mesh``
    and ``meshzone`` sharded.  The zone program has no chunked form:
    ``ladder.inputs`` hands a table over the budget no block ids."""
    if block is not None:
        return make_packed_block_table_kernel(plan, block) if mesh is None else _packed_sharded_kernel(plan, mesh, block)
    limit = chunk_rows_limit()
    n_dev = 1 if mesh is None else int(mesh.devices.size)
    # beyond the budget the kernel's per-row temporaries exceed HBM at
    # compile time: run segment-axis chunks (in multiples of the mesh's
    # devices) and combine the reduced outputs
    if limit and num_segments * n_pad > limit * n_dev and plan_chunkable(plan):
        chunk = _pick_chunk(num_segments, n_pad, limit * n_dev, granularity=n_dev)
        if num_segments > chunk:
            return _chunked_program(plan, mesh, num_segments, chunk)
    return make_packed_table_kernel(plan) if mesh is None else _packed_sharded_kernel(plan, mesh, None)


@functools.lru_cache(maxsize=128)
def make_packed_batched_table_kernel(plan: StaticPlan) -> Callable:
    """Cross-query batched variant of the packed table kernel (the
    lane micro-batching tier, engine/dispatch.py): ONE launch evaluates
    B same-plan queries over the SAME staged segment arrays, with each
    query's literals/inputs stacked along a new leading batch axis.

    This is the PIMDAL amortization move for serving: the memory-bound
    column scan is read ONCE per launch while B operator instances
    consume it, so same-shape queries that differ only in literals
    (``a>5`` vs ``a>999`` — one StaticPlan, different query inputs)
    stop paying B full passes over the resident columns.

    vmap is applied OUTSIDE the per-table function with
    ``in_axes=(None, 0)``: segment arrays broadcast (never copied per
    batch member), query-input leaves carry the batch axis, and every
    output leaf gains a leading ``[B]`` axis the executor slices per
    member at FINALIZE.  Per-member reductions happen along the same
    axes as the unbatched kernel, so member b's outputs are the same
    computation the unbatched launch would have produced — the
    byte-identity differential in tests/test_batching.py holds the two
    together.  Outputs fetch via the standard single packed D2H
    transfer, counted once per batched launch."""
    single = make_single_segment_kernel(plan)

    def table_fn(segs: Dict[str, Any], q: Dict[str, Any]) -> Dict[str, Any]:
        outs = jax.vmap(single)(segs, q)
        return reduce_outputs(plan, outs)

    from pinot_tpu.engine.packing import make_packed_kernel

    return make_packed_kernel(jax.vmap(table_fn, in_axes=(None, 0)), kernel_name("scanb", plan))


# ---------------------------------------------------------------------------
# Bit-sliced (BSI) filter/aggregate tier (engine/bitsliced.py): the
# bulk-bitwise formulation.  A predicate over a W-plane bit-sliced
# column evaluates in O(W) wide bitwise passes over n/32 packed uint32
# words; COUNT/SUM/MIN/MAX fuse into the SAME pass via popcounts and a
# bit-serial candidate descent, so a qualifying mid-selectivity
# aggregation never materializes row indices at all.
#
# The kernel spec is a plain hashable tuple (no StaticPlan — the tier
# has its own, much smaller, plan space):
#   (leaves, tree, sums, extremes)
#   leaves   = ((kind, col, width, k_pad), ...)   kind in
#              {"interval", "points", "points_none"}
#   tree     = ("leaf", i) | ("and"|"or", child, ...)
#   sums     = ((col, value_width), ...)          value-offset planes
#   extremes = ((col, width, is_max), ...)        dictId planes
# Inputs: segs = {"nd": int32 [S],
#                 "p:<col>": uint32 [S, W, nw], "v:<col>": uint32 [S, Wv, nw]}
#         q    = {"bounds:<i>": int32 [S, 2], "pts:<i>": int32 [S, k_pad]}
# Outputs (per segment — host finalize owns the cross-segment merge so
# it can apply per-segment vmin offsets and dictionary lookups):
#   "count": int32 [S]; "psum:<col>": int32 [S, Wv]; "ext:<col>": int32 [S]
# ---------------------------------------------------------------------------

_U32_FULL = np.uint32(0xFFFFFFFF)


def _bsi_valid_words(num_docs, n_words: int):
    """uint32 [n_words] validity mask from the segment's doc count:
    word j keeps bits for rows j*32 .. j*32+31 below num_docs."""
    j = jax.lax.iota(jnp.int32, n_words)
    bits = jnp.clip(num_docs - j * 32, 0, 32)
    base = (
        jnp.uint32(1) << jnp.clip(bits, 0, 31).astype(jnp.uint32)
    ) - jnp.uint32(1)
    return jnp.where(bits >= 32, jnp.uint32(_U32_FULL), base)


def _bsi_ge(planes, t, width: int):
    """Bitmap of rows whose value >= t (runtime int32 scalar) — the
    bit-serial MSB->LSB descent: ``gt`` accumulates rows already proven
    greater, ``eq`` tracks rows still matching t's prefix."""
    gt = jnp.zeros_like(planes[0])
    eq = jnp.full_like(planes[0], _U32_FULL)
    for b in range(width - 1, -1, -1):
        tb = ((t >> b) & 1).astype(jnp.uint32)
        tmask = jnp.uint32(0) - tb  # 0x0 or 0xFFFFFFFF
        gt = gt | (eq & planes[b] & ~tmask)
        eq = eq & ~(planes[b] ^ tmask)
    ge = gt | eq
    if width < 31:
        # t at/above 2^W would otherwise truncate to GE(t mod 2^W)
        ge = jnp.where(t >= (1 << width), jnp.zeros_like(ge), ge)
    return ge


def _bsi_points(planes, pts, width: int):
    """Bitmap of rows whose value is in ``pts`` (int32 [k], -1 padded) —
    per-point XNOR descent, OR-reduced over the point axis."""
    eq = jnp.full((pts.shape[0], planes.shape[1]), _U32_FULL, dtype=jnp.uint32)
    for b in range(width):
        pb = ((pts >> b) & 1).astype(jnp.uint32)[:, None]
        eq = eq & ~(planes[b][None, :] ^ (jnp.uint32(0) - pb))
    # -1 padding under the arithmetic shift above is all-ones and would
    # alias dictId 2^W - 1: mask padded (and any out-of-width) points
    ok = pts >= 0
    if width < 31:
        ok = ok & (pts < (1 << width))
    eq = jnp.where(ok[:, None], eq, jnp.zeros_like(eq))
    return jax.lax.reduce(eq, np.uint32(0), jax.lax.bitwise_or, (0,))


def _bsi_extreme(planes, bitmap, width: int, is_max: bool):
    """Bit-serial candidate descent: the extreme dictId among bitmap
    rows (garbage when the bitmap is empty — callers mask on count)."""
    cand = bitmap
    out = jnp.int32(0)
    for b in range(width - 1, -1, -1):
        t = (cand & planes[b]) if is_max else (cand & ~planes[b])
        any_t = jnp.any(t != 0)
        cand = jnp.where(any_t, t, cand)
        taken = any_t if is_max else ~any_t
        out = out | (taken.astype(jnp.int32) << b)
    return out


def _bsi_eval_tree(node, bms):
    if node[0] == "leaf":
        return bms[node[1]]
    acc = _bsi_eval_tree(node[1], bms)
    for child in node[2:]:
        m = _bsi_eval_tree(child, bms)
        acc = (acc & m) if node[0] == "and" else (acc | m)
    return acc


def make_single_segment_bitsliced_kernel(spec) -> Callable:
    leaves, tree, sums, extremes = spec

    def single(seg: Dict[str, Any], q: Dict[str, Any]) -> Dict[str, Any]:
        bms = []
        n_words = None
        for i, (kind, col, width, k_pad) in enumerate(leaves):
            planes = seg[f"p:{col}"]
            n_words = planes.shape[-1]
            if kind == "interval":
                lo, hi = q[f"bounds:{i}"][0], q[f"bounds:{i}"][1]
                bm = _bsi_ge(planes, lo, width) & ~_bsi_ge(planes, hi, width)
            else:
                bm = _bsi_points(planes, q[f"pts:{i}"], width)
                if kind == "points_none":
                    bm = ~bm  # complement; padding cleared by vw below
            bms.append(bm)
        vw = _bsi_valid_words(seg["nd"], n_words)
        bitmap = _bsi_eval_tree(tree, bms) & vw
        pop = jax.lax.population_count
        outs: Dict[str, Any] = {
            "count": jnp.sum(pop(bitmap)).astype(jnp.int32)
        }
        for col, vwidth in sums:
            outs[f"psum:{col}"] = (
                jnp.sum(pop(seg[f"v:{col}"] & bitmap[None, :]), axis=1)
                .astype(jnp.int32)
            )
        for col, width, is_max in extremes:
            outs[f"ext:{'mx' if is_max else 'mn'}:{col}"] = _bsi_extreme(
                seg[f"p:{col}"], bitmap, width, is_max
            )
        return outs

    return single


@functools.lru_cache(maxsize=256)
def make_packed_bitsliced_kernel(spec) -> Callable:
    """vmapped + jitted + packed-fetch bit-sliced tier kernel — same
    caching/dispatch idiom as make_packed_table_kernel (the lru_cache
    is what makes jit's executable cache effective)."""
    single = make_single_segment_bitsliced_kernel(spec)

    def table_fn(segs: Dict[str, Any], q: Dict[str, Any]) -> Dict[str, Any]:
        return jax.vmap(single)(segs, q)

    from pinot_tpu.engine.packing import make_packed_kernel

    name = kernel_name("bsi", None, ("bsi", spec))
    return make_packed_kernel(jax.jit(named(table_fn, name)), name)


@functools.lru_cache(maxsize=128)
def make_packed_batched_bitsliced_kernel(spec) -> Callable:
    """Cross-query batched bit-sliced kernel — the BSI tier joining the
    lane micro-batching plane (make_packed_batched_table_kernel's exact
    shape, applied to the plane kernels): ONE launch evaluates B
    same-spec queries over the SAME resident bit-planes, each member's
    per-leaf ``bounds:<i>``/``pts:<i>`` arrays stacked along a new
    leading batch axis.

    The plane arrays broadcast (``in_axes=(None, 0)`` — never copied
    per member), so B distinct range/IN literals over one bit-sliced
    column cost one O(W) bitwise pass instead of B.  Every output leaf
    gains a leading [B] axis the lane slices per member; member b's
    outputs are the computation the solo launch would have produced
    (tests/test_bitsliced.py holds the two together byte-identically)."""
    single = make_single_segment_bitsliced_kernel(spec)

    def table_fn(segs: Dict[str, Any], q: Dict[str, Any]) -> Dict[str, Any]:
        return jax.vmap(single)(segs, q)

    from pinot_tpu.engine.packing import make_packed_kernel

    name = kernel_name("bsib", None, ("bsi", spec))
    return make_packed_kernel(jax.jit(named(jax.vmap(table_fn, in_axes=(None, 0)), name)), name)


# ---------------------------------------------------------------------------
# Device hash join (engine/join.py JoinPlan -> one jitted program)
# ---------------------------------------------------------------------------


def _join_hash(k, cap: int):
    """Knuth multiplicative hash of int32 key ids, masked to the pow2
    open-addressing capacity."""
    h = (k.astype(jnp.uint32) * jnp.uint32(2654435761)) >> jnp.uint32(8)
    return (h & jnp.uint32(cap - 1)).astype(jnp.int32)


@functools.lru_cache(maxsize=128)
def make_join_kernel(jplan) -> Callable:
    """Build+probe hash-join program for one ``engine/join.py``
    JoinPlan: int32 open-addressing over padded lanes.

    BUILD: unique build keys insert in parallel-claim rounds — each
    unplaced lane proposes slot ``(hash + r) & (cap-1)``; lanes whose
    proposed slot is empty scatter-min their lane index to claim it,
    winners write (key, lane) into the table, everyone else advances
    ``r``.  Keys are unique (the host packing pre-aggregated per key)
    and the table is <= half full, so every lane lands within ``cap``
    rounds; ``join_ok`` reports the invariant so the executor can heal
    to the host join instead of serving a wrong answer if it ever
    breaks.

    PROBE: every probe lane walks its probe sequence until key match
    (join hit: the build lane index) or empty slot (no match), all
    lanes in lockstep under one while_loop.

    AGGREGATE: matched lanes gather the build side's per-key
    pre-reductions (cnt/sum/min/max) and combine with their own value
    columns — a probe row matching a duplicated build key contributes
    ``cnt`` joined rows, so SUM weights by cnt and COUNT sums cnt,
    which is exactly the inner-join multiplicity.  Group mode scatters
    into dense ``[n_groups]`` holders keyed by the mixed-radix
    (probe-group, build-group) id."""
    cap = jplan.cap

    def kern(inputs: Dict[str, Any]) -> Dict[str, Any]:
        bk = inputs["bk"]
        bc = inputs["bc"]
        U = bk.shape[0]

        # -- build phase: parallel-claim insertion --------------------
        bh = _join_hash(bk, cap)
        lane_ids = jnp.arange(U, dtype=jnp.int32)
        table_key = jnp.full((cap,), -1, dtype=jnp.int32)
        table_row = jnp.zeros((cap,), dtype=jnp.int32)
        placed = bk < 0  # padded lanes never insert

        def build_cond(state):
            _tk, _tr, placed_, r = state
            return jnp.logical_and(jnp.any(~placed_), r < 2 * cap)

        def build_body(state):
            tk, tr, placed_, r = state
            slot = (bh + r) & (cap - 1)
            attempt = jnp.logical_and(~placed_, tk[slot] == -1)
            # claim: lowest lane index wins each contested empty slot
            claim_slot = jnp.where(attempt, slot, cap)
            claims = jnp.full((cap,), U, dtype=jnp.int32)
            claims = claims.at[claim_slot].min(lane_ids, mode="drop")
            won = jnp.logical_and(attempt, claims[slot] == lane_ids)
            win_slot = jnp.where(won, slot, cap)
            tk = tk.at[win_slot].set(bk, mode="drop")
            tr = tr.at[win_slot].set(lane_ids, mode="drop")
            return tk, tr, jnp.logical_or(placed_, won), r + 1

        table_key, table_row, placed, _r = jax.lax.while_loop(
            build_cond, build_body, (table_key, table_row, placed, jnp.int32(0))
        )
        join_ok = jnp.all(placed)

        # -- probe phase: lockstep linear probing ---------------------
        pk = inputs["pk"]
        N = pk.shape[0]
        ph = _join_hash(pk, cap)
        midx0 = jnp.full((N,), -1, dtype=jnp.int32)
        done0 = pk < 0  # padded lanes: no match

        def probe_cond(state):
            done, _m, off = state
            return jnp.logical_and(jnp.any(~done), off <= cap)

        def probe_body(state):
            done, midx, off = state
            slot = (ph + off) & (cap - 1)
            at = table_key[slot]
            found = jnp.logical_and(~done, at == pk)
            empty = jnp.logical_and(~done, at == -1)
            midx = jnp.where(found, table_row[slot], midx)
            return jnp.logical_or(done, jnp.logical_or(found, empty)), midx, off + 1

        _done, midx, _off = jax.lax.while_loop(
            probe_cond, probe_body, (done0, midx0, jnp.int32(0))
        )

        matched = midx >= 0
        safe = jnp.maximum(midx, 0)
        fdt = config.float_dtype()
        cnt = jnp.where(matched, bc[safe], 0).astype(jnp.int32)
        cntf = cnt.astype(fdt)
        outs: Dict[str, Any] = {
            "num_docs": jnp.sum(cnt.astype(jnp.int64))
            if jax.config.jax_enable_x64
            else jnp.sum(cnt),
            "join_ok": join_ok,
        }

        pv = inputs["pv"]
        bs = inputs["bs"]
        bmn = inputs["bmn"]
        bmx = inputs["bmx"]
        inf = jnp.asarray(jnp.inf, dtype=fdt)

        def probe_vals(idx):
            return pv[idx]

        if jplan.n_groups:
            G = jplan.n_groups
            gid = inputs["pg"] * jnp.int32(jplan.bg_space) + inputs["bg"][safe]
            gslot = jnp.where(matched, gid, G)  # drop unmatched lanes
            gcnt = jnp.zeros((G,), jnp.int32).at[gslot].add(cnt, mode="drop")
            outs["gb_cnt"] = gcnt
            for i, (kind, side, idx) in enumerate(jplan.aggs):
                if kind == "count":
                    outs[f"gb_{i}"] = gcnt
                    continue
                if side == "p":
                    v = probe_vals(idx)
                    vsum = v * cntf
                    vmin = v
                    vmax = v
                else:
                    vsum = bs[idx][safe]
                    vmin = bmn[idx][safe]
                    vmax = bmx[idx][safe]

                def _sum():
                    return jnp.zeros((G,), fdt).at[gslot].add(
                        jnp.where(matched, vsum, 0.0), mode="drop"
                    )

                def _min():
                    return jnp.full((G,), inf).at[gslot].min(
                        jnp.where(matched, vmin, inf), mode="drop"
                    )

                def _max():
                    return jnp.full((G,), -inf).at[gslot].max(
                        jnp.where(matched, vmax, -inf), mode="drop"
                    )

                if kind == "sum":
                    outs[f"gb_{i}"] = _sum()
                elif kind == "avg":
                    outs[f"gb_{i}"] = (_sum(), gcnt)
                elif kind == "min":
                    outs[f"gb_{i}"] = _min()
                elif kind == "max":
                    outs[f"gb_{i}"] = _max()
                else:  # minmaxrange
                    outs[f"gb_{i}"] = (_min(), _max())
            return outs

        total_cnt = jnp.sum(cnt)
        for i, (kind, side, idx) in enumerate(jplan.aggs):
            if kind == "count":
                outs[f"agg_{i}"] = total_cnt
                continue
            if side == "p":
                v = probe_vals(idx)
                ssum = jnp.sum(jnp.where(matched, v * cntf, 0.0))
                smin = jnp.min(jnp.where(jnp.logical_and(matched, cnt > 0), v, inf))
                smax = jnp.max(
                    jnp.where(jnp.logical_and(matched, cnt > 0), v, -inf)
                )
            else:
                ssum = jnp.sum(jnp.where(matched, bs[idx][safe], 0.0))
                smin = jnp.min(jnp.where(matched, bmn[idx][safe], inf))
                smax = jnp.max(jnp.where(matched, bmx[idx][safe], -inf))
            if kind == "sum":
                outs[f"agg_{i}"] = ssum
            elif kind == "avg":
                outs[f"agg_{i}"] = (ssum, total_cnt)
            elif kind == "min":
                outs[f"agg_{i}"] = smin
            elif kind == "max":
                outs[f"agg_{i}"] = smax
            else:
                outs[f"agg_{i}"] = (smin, smax)
        return outs

    from pinot_tpu.engine.packing import make_packed_kernel

    return make_packed_kernel(kern, kernel_name("join", jplan))
