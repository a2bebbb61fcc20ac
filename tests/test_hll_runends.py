"""The 'sort' lowering of a grouped ``distinctcounthll`` (PR 42): a
segment's packed keys are sorted where they are built and a register is
the SUM of its (group, register) run's last rank, added on the matrix
unit by the windowed contraction that ``_segment_add_sorted`` shares
(``kernel._hll_sorted_registers``, ``kernel._sorted_window_sums``).  Held
here on the CPU, where the Pallas call runs in the interpreter (only the
tests' switch reaches it): the registers are the scatter-max's bit for
bit, a segment alone and two folded by ``max``; the shared inner function
still gives ``_segment_add_sorted`` the radix contraction's states.
``tests/test_tpu_compile.py`` compiles the same calls for a described
v5e at the cell's size; the cell's answers against the reference are in
``tests/test_hits_users.py``."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pinot_tpu.engine import config
from pinot_tpu.engine import kernel as kernel_mod
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.pql import optimize_request, parse_pql
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.tools.datagen import make_test_schema, random_rows

M = config.HLL_M
SENTINEL = kernel_mod._PAIR_SENTINEL
BLOCK = kernel_mod._SORTED_BLOCK


def scatter_max(packed: np.ndarray, capacity: int) -> np.ndarray:
    """What the 'scatter' lowering builds of the same rows, in numpy."""
    regs = np.zeros(capacity * M, np.uint8)
    live = packed != SENTINEL
    np.maximum.at(regs, packed[live] >> 6, (packed[live] & 63).astype(np.uint8))
    return regs.reshape(capacity, M)


def keys(rng, capacity: int, rows: int, filtered: float = 0.3) -> np.ndarray:
    group, register, rank = rng.integers(0, capacity, rows), rng.integers(0, M, rows), rng.integers(0, 64, rows)
    packed = (((group * M + register) << 6) | rank).astype(np.int32)
    packed[rng.random(rows) < filtered] = SENTINEL
    return packed


def every_row_filtered(rng):
    return 40, np.full(3 * BLOCK, SENTINEL, np.int32)


def every_row_in_one_cell(rng):
    return 40, (((23 * M + 200) << 6) | rng.integers(1, 50, 2 * BLOCK + 7)).astype(np.int32)


def the_largest_rank_repeated_in_its_run(rng):
    packed = keys(rng, 17, 5_000, filtered=0.0) & ~np.int32(63)
    return 17, packed | np.where(rng.random(5_000) < 0.5, 37, rng.integers(0, 37, 5_000)).astype(np.int32)


def no_whole_number_of_blocks(rng):
    return 300, keys(rng, 300, 2 * BLOCK + 4_097)


def the_last_cell_and_the_first(rng):
    packed = np.array([63, (9_040 * M - 1) << 6 | 1, SENTINEL, 5, (9_040 * M - 1) << 6 | 62], np.int32)
    return 9_040, packed


CASES = {
    "capacity_17": lambda rng: (17, keys(rng, 17, 20_000)),
    "capacity_9040_the_cells": lambda rng: (9_040, keys(rng, 9_040, 30_000)),
    "every_row_filtered": every_row_filtered,
    "every_row_in_one_cell": every_row_in_one_cell,
    "the_largest_rank_repeated_in_its_run": the_largest_rank_repeated_in_its_run,
    "no_whole_number_of_blocks": no_whole_number_of_blocks,
    "the_last_cell_and_the_first": the_last_cell_and_the_first,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_register_is_the_sum_of_its_runs_last_rank(case):
    capacity, packed = CASES[case](np.random.default_rng(42))
    got = np.asarray(jax.jit(lambda p: kernel_mod._hll_sorted_registers(p, capacity))(packed))
    assert got.dtype == np.uint8 and got.shape == (capacity, M)
    assert np.array_equal(got, scatter_max(packed, capacity))


def test_the_sort_has_one_operand_and_is_unstable():
    """The keys are the sort's only operand, so a stable sort orders
    nothing an unstable one does not, and on the chip it carries a second
    operand of row numbers through every stage (PR 44: 3.3 ns a row
    against 1.6).  Held on the lowered text, which is the same for every
    backend."""
    text = jax.jit(lambda p: kernel_mod._hll_sorted_registers(p, 9_040)).lower(jax.ShapeDtypeStruct((3 * BLOCK + 5,), jnp.int32)).as_text()
    sorts = re.findall(r'stablehlo\.sort"?\(([^)]*)\)', text)
    assert len(sorts) == 1 and "," not in sorts[0], sorts
    assert text.count("is_stable = false") == 1 and "is_stable = true" not in text


def every_key_64_times_over(rng, capacity):
    return np.repeat(keys(rng, capacity, 400, filtered=0.1), 64)  # runs of rows equal in every bit, the sentinel's too


def two_cells_only(rng, capacity):
    cells = np.array([0, capacity * M - 1])  # the first and the last
    return ((cells[rng.integers(0, 2, BLOCK + 300)] << 6) | rng.integers(0, 4, BLOCK + 300)).astype(np.int32)


def all_rows_one_key_but_one(rng, capacity):
    packed = np.full(2 * BLOCK, (int(rng.integers(0, capacity * M)) << 6) | 21, np.int32)
    packed[rng.integers(0, packed.size)] = (int(rng.integers(0, capacity * M)) << 6) | 44
    return packed


def a_ragged_count_with_sentinels_between(rng, capacity):
    packed = np.repeat(keys(rng, capacity, 900, filtered=0.0), 10)[:BLOCK + 777]
    packed[rng.integers(0, packed.size, packed.size // 3)] = SENTINEL
    return packed


DUPLICATES = {f.__name__: f for f in (every_key_64_times_over, two_cells_only, all_rows_one_key_but_one, a_ragged_count_with_sentinels_between)}


@pytest.mark.parametrize("capacity", [17, 9_040])
@pytest.mark.parametrize("seed", [11, 4_400_001_001, 4_400_001_002, 2**31 + 7])
@pytest.mark.parametrize("case", sorted(DUPLICATES))
def test_rows_equal_in_every_bit_need_no_order_among_themselves(case, seed, capacity):
    """What an unstable sort may do differently from a stable one is
    permute equal keys: the registers are the scatter-max's whatever it
    does with them."""
    packed = DUPLICATES[case](np.random.default_rng(seed), capacity)
    got = np.asarray(jax.jit(lambda p: kernel_mod._hll_sorted_registers(p, capacity))(packed))
    assert np.array_equal(got, scatter_max(packed, capacity))
    assert got.any()


# sublanes (x 128 cells) of accumulator a call may take: 48 after the last window's 64, 24 groups of 256 registers
RANGE_GROUPS = 24


@pytest.mark.parametrize("capacity,rows", [
    (RANGE_GROUPS, "spread"),  # the most one call holds
    (RANGE_GROUPS + 1, "spread"),  # one past it: two ranges, the second of one group
    (5 * RANGE_GROUPS + 3, "spread"),  # six ranges
    (3 * RANGE_GROUPS, "last_range_only"),  # the ranges before it see rows over them alone
    (3 * RANGE_GROUPS, "filtered"),
])
def test_cells_past_one_calls_accumulator_go_in_ranges(monkeypatch, capacity, rows):
    monkeypatch.setattr(kernel_mod, "_SORTED_ACC_BYTES", (kernel_mod._SORTED_WINDOW + 48) * 128 * 4)
    rng = np.random.default_rng(capacity)
    packed = keys(rng, capacity, BLOCK + 1_000)
    if rows == "last_range_only":
        packed = np.where(packed >> 6 >= 2 * RANGE_GROUPS * M, packed, SENTINEL).astype(np.int32)
        assert (packed != SENTINEL).sum() > 1_000
    elif rows == "filtered":
        packed[:] = SENTINEL
    calls, inner = [], kernel_mod._sorted_window_sums
    monkeypatch.setattr(kernel_mod, "_sorted_window_sums", lambda idx, cols, cells, *a: calls.append(cells) or inner(idx, cols, cells, *a))
    got = np.asarray(jax.jit(lambda p: kernel_mod._hll_sorted_registers(p, capacity))(packed))
    assert sum(calls) == capacity * M and len(calls) == -(-capacity // RANGE_GROUPS) and max(calls) == min(capacity, RANGE_GROUPS) * M
    assert np.array_equal(got, scatter_max(packed, capacity))


# ---------------------------------------------------------------------------
# a segment of more rows than one part holds (PR 45): the parts fold by max
# ---------------------------------------------------------------------------
PART = BLOCK  # the part length patched small: a segment of 2 * BLOCK + 1 rows is sorted in three parts
CELL = (23 * M + 200) << 6  # one (group, register) cell of capacity 40, rank 0


def _quiet(rng, rows, capacity=40):
    """Keys whose ranks stay under 32, so that a planted rank above it is its cell's largest."""
    packed = keys(rng, capacity, rows)
    return np.where(packed == SENTINEL, packed, packed & ~np.int32(32))


def _planted(rng, rows, ranks_by_part):
    packed = _quiet(rng, rows)
    packed[packed >> 6 == CELL >> 6] = SENTINEL  # the cell's rows are the planted ones alone
    length = BLOCK * -(-rows // (BLOCK * -(-rows // PART)))  # rows a part, as the kernel cuts them
    for part, rank in ranks_by_part.items():
        packed[part * length + int(rng.integers(0, min(length, rows - part * length)))] = CELL | rank
    return 40, packed


def a_part_wholly_filtered(rng):
    capacity, packed = 40, keys(rng, 40, 3 * PART)
    packed[PART:2 * PART] = SENTINEL  # the middle part sorts sentinels alone and gives registers of zero
    return capacity, packed


PARTED = {
    "the_largest_rank_in_the_first_part": (3, lambda rng: _planted(rng, 3 * PART, {0: 61, 1: 40, 2: 33})),
    "the_largest_rank_in_the_last_part": (3, lambda rng: _planted(rng, 3 * PART, {0: 33, 1: 40, 2: 61})),
    "a_cell_in_every_part_with_equal_ranks": (3, lambda rng: _planted(rng, 3 * PART, {0: 47, 1: 47, 2: 47})),
    "a_ragged_count": (3, lambda rng: (300, keys(rng, 300, 2 * PART + 4_097))),
    "one_row_past_a_part": (2, lambda rng: (40, keys(rng, 40, PART + 1))),
    "a_part_wholly_filtered": (3, a_part_wholly_filtered),
    "every_row_filtered": (3, lambda rng: (40, np.full(3 * PART, SENTINEL, np.int32))),
    "five_parts": (5, lambda rng: (17, keys(rng, 17, 4 * PART + 1))),
    "five_parts_the_last_of_one_row": (5, lambda rng: _planted(rng, 4 * PART + 1, {4: 61, 0: 9})),
}


def _sorts(text):
    """(operands, dimension, operand type) of every sort in a lowered text."""
    return re.findall(r'stablehlo\.sort"?\(([^)]*)\) <\{dimension = (\d+) : i64, is_stable = false\}>.*?\}\) : \((tensor<[^>]*>)\)', text, re.S)


def _parted_registers(monkeypatch, packed, capacity):
    """The registers with the part length patched small, what the one
    sort was handed and the rows of each part summed."""
    monkeypatch.setattr(kernel_mod, "_HLL_SORT_PART", PART)
    summed, inner = [], kernel_mod._hll_run_end_sums
    monkeypatch.setattr(kernel_mod, "_hll_run_end_sums", lambda part, cap: summed.append(part.shape) or inner(part, cap))
    run = jax.jit(lambda p: kernel_mod._hll_sorted_registers(p, capacity))
    sorts = _sorts(run.lower(jax.ShapeDtypeStruct(packed.shape, jnp.int32)).as_text())
    traced = list(summed)  # one trace's worth: the call below may or may not trace again
    return np.asarray(run(packed)), sorts, traced


@pytest.mark.parametrize("case", sorted(PARTED))
def test_a_segment_of_several_parts_gives_the_scatters_registers(monkeypatch, case):
    """Each part sorted alone gives registers of its own and the parts
    fold by ``max``, as the segments do: the scatter-max's bit for bit in
    any cut, wherever a cell's largest rank lies."""
    parts, make = PARTED[case]
    capacity, packed = make(np.random.default_rng(45))
    got, sorts, summed = _parted_registers(monkeypatch, packed, capacity)
    assert kernel_mod.hll_sort_parts(packed.size) == parts
    # ONE sort of one operand, along a part's rows; the parts equal in length, whole blocks, and none of padding alone
    length = summed[0][0]
    assert summed == [(length,)] * parts and length % BLOCK == 0 and length <= PART
    assert (parts - 1) * length < packed.size <= parts * length
    assert len(sorts) == 1 and "," not in sorts[0][0] and sorts[0][1:] == ("1", f"tensor<{parts}x{length}xi32>"), sorts
    assert got.dtype == np.uint8 and got.shape == (capacity, M)
    assert np.array_equal(got, scatter_max(packed, capacity))
    if case.startswith(("the_largest", "a_cell", "five_parts_the")):
        assert got.reshape(-1)[CELL >> 6] == (47 if case.startswith("a_cell") else 61)  # the planted rank, from whichever part


@pytest.mark.parametrize("capacity,parts", [(RANGE_GROUPS + 1, 3), (3 * RANGE_GROUPS, 2)])
def test_cells_in_ranges_and_rows_in_parts(monkeypatch, capacity, parts):
    """Over one call's accumulator AND over one part's rows: every part
    runs every range's call over its own sorted rows."""
    monkeypatch.setattr(kernel_mod, "_SORTED_ACC_BYTES", (kernel_mod._SORTED_WINDOW + 48) * 128 * 4)
    packed = keys(np.random.default_rng(capacity), capacity, (parts - 1) * PART + 1_000)
    calls, inner = [], kernel_mod._sorted_window_sums
    monkeypatch.setattr(kernel_mod, "_sorted_window_sums", lambda idx, cols, cells, *a: calls.append(cells) or inner(idx, cols, cells, *a))
    got, sorts, summed = _parted_registers(monkeypatch, packed, capacity)
    assert len(summed) == parts and len(sorts) == 1
    assert len(calls) == parts * -(-capacity // RANGE_GROUPS) and sum(calls) == parts * capacity * M
    assert np.array_equal(got, scatter_max(packed, capacity))


@pytest.mark.parametrize("segments,rows,queries", [(3, 2 * PART + 1, 0), (12, PART + 5, 0), (5, 4 * PART + 77, 0), (3, 2 * PART + 9, 2)])
def test_under_the_segments_vmap_a_row_of_the_sort_is_a_part_of_a_segment(monkeypatch, segments, rows, queries):
    """``vmap`` of the plain form would sort [segments, parts, rows a
    part]; the batched form written out (``_sort_in_parts``) sorts
    [parts x segments, rows a part], which fills the chip's sublanes, and
    hands back what ``vmap`` would: every segment's registers are the
    scatter-max's, also with the queries' ``vmap`` around the segments'."""
    monkeypatch.setattr(kernel_mod, "_HLL_SORT_PART", PART)
    rng = np.random.default_rng(segments * rows)
    packed = np.stack([keys(rng, 40, rows) for _ in range(segments * max(queries, 1))]).reshape((queries,) * bool(queries) + (segments, rows))
    run = jax.vmap(lambda p: kernel_mod._hll_sorted_registers(p, 40))
    run = jax.jit(jax.vmap(run) if queries else run)
    parts = kernel_mod.hll_sort_parts(rows)
    length = BLOCK * -(-rows // (parts * BLOCK))
    leading = f"{queries}x" * bool(queries)
    assert [(dim, shape) for _, dim, shape in _sorts(run.lower(jax.ShapeDtypeStruct(packed.shape, jnp.int32)).as_text())] == \
        [(str(1 + bool(queries)), f"tensor<{leading}{parts * segments}x{length}xi32>")]
    assert np.array_equal(np.asarray(run(packed)).reshape(-1, 40, M), np.stack([scatter_max(p, 40) for p in packed.reshape(-1, rows)]))


@pytest.mark.parametrize("rows,parts", [(1, 1), (1 << 22, 1), ((1 << 22) + 1, 2), (1 << 23, 2), ((1 << 23) + 1, 3), (5 << 22, 5)])
def test_as_few_parts_as_leave_a_part_the_length_the_sweep_chose(rows, parts):
    assert kernel_mod._HLL_SORT_PART == 1 << 22 and kernel_mod._HLL_SORT_PART % BLOCK == 0
    assert kernel_mod.hll_sort_parts(rows) == parts


def test_a_segment_of_one_part_is_the_program_it_was(monkeypatch):
    """Up to ``_HLL_SORT_PART`` rows nothing is cut: the lowered text is
    the one a part length no segment reaches gives, the sort's operand the
    segment's padded keys as one row, ``is_stable = false`` once."""
    lowered = lambda: jax.jit(lambda p: kernel_mod._hll_sorted_registers(p, 9_040)).lower(jax.ShapeDtypeStruct((3 * BLOCK + 5,), jnp.int32)).as_text()
    text = lowered()
    monkeypatch.setattr(kernel_mod, "_HLL_SORT_PART", 1 << 40)
    assert text == lowered()
    monkeypatch.setattr(kernel_mod, "_HLL_SORT_PART", 4 * BLOCK)  # the segment's padded rows, to the row
    assert text == lowered()
    assert [(dim, shape) for _, dim, shape in _sorts(text)] == [("0", f"tensor<{4 * BLOCK}xi32>")]
    assert text.count("is_stable = false") == 1 and "is_stable = true" not in text
    monkeypatch.setattr(kernel_mod, "_HLL_SORT_PART", 2 * BLOCK)
    assert text != lowered()


# ---------------------------------------------------------------------------
# through the kernel builder: a segment's state and the fold
# ---------------------------------------------------------------------------
QUERIES = {
    "two_segments_folded_by_max": "SELECT distinctcounthll(dimLong) FROM testTable GROUP BY dimStr TOP 10",
    "a_multi_value_group_key": "SELECT distinctcounthll(dimLong), count(*) FROM testTable GROUP BY dimStrMV TOP 10",
    "a_multi_value_argument_and_two_keys": "SELECT distinctcounthll(dimIntMV) FROM testTable GROUP BY dimStr, dimInt TOP 10",
    "under_a_filter": "SELECT fasthll(dimLong) FROM testTable WHERE metInt > 4000 GROUP BY dimInt TOP 10",
}


@pytest.fixture(scope="module")
def launches():
    """(plan, segment arrays, query inputs) of each of QUERIES as the
    executor hands them to the table kernel, over two segments."""
    schema = make_test_schema(with_mv=True)
    rows = random_rows(schema, 2_400, seed=4242, cardinality=40)
    segs = [build_segment(schema, rows[:1_100], "testTable", "re0"), build_segment(schema, rows[1_100:], "testTable", "re1")]
    got = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PINOT_TPU_HLL_PRESENCE", "0")  # the register streams: 40 values a column would ride a presence holder
        run_kernel = QueryExecutor._run_kernel

        def spy(self, kernel, args, plan, *rest, **kw):
            got[name] = (plan, args[0], args[1])
            return run_kernel(self, kernel, args, plan, *rest, **kw)

        mp.setattr(QueryExecutor, "_run_kernel", spy)
        for name, pql in QUERIES.items():
            QueryExecutor().execute(segs, optimize_request(parse_pql(pql)))
    return got


@pytest.mark.parametrize("query", sorted(QUERIES))
def test_the_sorted_form_gives_the_scatters_registers_a_segment_and_folded(monkeypatch, launches, query):
    plan, segs, q = launches[query]
    at = next(i for i, a in enumerate(plan.aggs) if a.kind == "hll" and not a.sort_pairs)
    assert kernel_mod.hll_lowering(plan) == "scatter"  # the CPU's own answer, without the switch
    states = {}
    for answer in ("scatter", "sort"):
        monkeypatch.setattr(kernel_mod, "hll_lowering", lambda plan, answer=answer: answer)
        assert kernel_mod.output_reducers(plan)[f"gb_{at}"] == "max"
        small = kernel_mod._state_cells(plan) <= kernel_mod._INPLACE_STATE_CELLS  # two of the four: 40 groups
        assert kernel_mod.zone_blocks(plan) == ("inplace" if small and answer == "scatter" else "gathered")
        assert kernel_mod.plan_chunkable(plan)
        states[answer] = np.asarray(jax.jit(jax.vmap(kernel_mod.make_single_segment_kernel(plan)))(segs, q)[f"gb_{at}"])
    assert states["sort"].dtype == np.uint8 and states["sort"].shape == (2, plan.group_by.capacity, M)
    assert states["sort"].any(axis=(1, 2)).all()  # both segments have rows
    assert np.array_equal(states["sort"], states["scatter"])
    folded = kernel_mod.apply_reduce("max", jnp.asarray(states["sort"]))
    assert np.array_equal(np.asarray(folded), states["scatter"].max(axis=0))
    assert np.array_equal(np.asarray(kernel_mod.combine_reduced("max", states["sort"][0], states["sort"][1])), np.asarray(folded))


def test_the_zone_tier_hands_a_sorted_hll_its_gathered_view(monkeypatch):
    """A filter that prunes to a few blocks rides the zone tier: under
    the scatter the blocks are read in place, under the sorted form they
    are copied out first (``zone_blocks`` asks ``hll_lowering``), and the
    replies are the same text."""
    import json

    from pinot_tpu.engine.reduce import reduce_to_response
    from pinot_tpu.tools.datagen import synthetic_lineitem_keys_segment

    for name, value in (("PINOT_TPU_ZONE_BLOCK", "512"), ("PINOT_TPU_INVINDEX", "0"), ("PINOT_TPU_HLL_PRESENCE", "0")):
        monkeypatch.setenv(name, value)
    segs = [synthetic_lineitem_keys_segment(32_768, seed=70 + i, name=f"zone{i}") for i in range(2)]
    request = optimize_request(parse_pql(
        "SELECT distinctcounthll(l_suppkey), count(*) FROM lineitem WHERE l_shipdate >= '1996-01-01' AND "
        "l_shipdate < '1996-04-01' GROUP BY l_shipmode, l_returnflag TOP 30"))
    launched, run_kernel = [], QueryExecutor._run_kernel

    def spy(self, kernel, args, plan, staged, digest, block_ids, *rest, **kw):
        launched.append((kernel_mod.hll_lowering(plan), kernel_mod.zone_blocks(plan), block_ids is not None))
        return run_kernel(self, kernel, args, plan, staged, digest, block_ids, *rest, **kw)

    monkeypatch.setattr(QueryExecutor, "_run_kernel", spy)
    cached = (kernel_mod.make_table_kernel, kernel_mod.make_packed_table_kernel, kernel_mod.make_block_table_kernel,
              kernel_mod.make_packed_block_table_kernel)
    replies = []
    try:
        for forced in ("0", "1"):
            monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", forced)
            for c in cached:
                c.cache_clear()
            reply = reduce_to_response(request, [QueryExecutor().execute(segs, request)])
            assert not reply.exceptions, reply.exceptions
            replies.append(json.dumps(reply.to_json()["aggregationResults"], sort_keys=True))
    finally:
        for c in cached:
            c.cache_clear()
    assert launched == [("scatter", "inplace", True), ("sort", "gathered", True)]
    assert replies[0] == replies[1] and '"value": "0"' not in replies[0]


PARTED_QUERIES = {k: v for k, v in QUERIES.items() if k != "under_a_filter"}  # its filter leaves a view of under one part
PARTED_QUERIES["through_the_zone_tier"] = "SELECT distinctcounthll(dimLong) FROM testTable WHERE metInt < {cut} GROUP BY dimStr TOP 10"


@pytest.fixture(scope="module")
def parted_segments():
    """Two segments of 18,000 rows clustered on metInt, and the value under
    which 47.5% of the rows lie: a filter on it keeps 67 of a segment's 141
    blocks of 128 rows, a gathered view of 128 blocks, two parts of ``PART``."""
    schema = make_test_schema(with_mv=True)
    rows = sorted(random_rows(schema, 2 * 18_000, seed=45, cardinality=40), key=lambda r: r["metInt"])
    return [build_segment(schema, rows[i::2], "testTable", f"parted{i}") for i in range(2)], rows[int(0.475 * len(rows))]["metInt"]


@pytest.mark.parametrize("query", sorted(PARTED_QUERIES))
def test_the_launch_marks_the_parts_the_kernel_cuts(monkeypatch, parted_segments, query):
    """``hll.sort.parts`` is ``hll_sort_parts`` of the keys a segment hands
    the lowering, which the launch works out from the staged table (a key
    a row of the view, times the multi-value widths) and the kernel reads
    off its operand: the two must agree, and the reply is the scatter's."""
    import json

    from pinot_tpu.engine.reduce import reduce_to_response

    for name, value in (("PINOT_TPU_HLL_PRESENCE", "0"), ("PINOT_TPU_INVINDEX", "0"), ("PINOT_TPU_ZONE_BLOCK", "128")):
        monkeypatch.setenv(name, value)
    monkeypatch.setattr(kernel_mod, "_HLL_SORT_PART", PART)
    segs, cut = parted_segments
    request = optimize_request(parse_pql(PARTED_QUERIES[query].format(cut=cut)))
    handed, inner = [], kernel_mod._hll_sorted_registers
    monkeypatch.setattr(kernel_mod, "_hll_sorted_registers", lambda packed, cap: handed.append(packed.shape[0]) or inner(packed, cap))
    cached = (kernel_mod.make_table_kernel, kernel_mod.make_packed_table_kernel, kernel_mod.make_block_table_kernel,
              kernel_mod.make_packed_block_table_kernel)
    replies, marked = [], []
    try:
        for forced in ("0", "1"):
            monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", forced)
            for c in cached:
                c.cache_clear()
            executor = QueryExecutor()
            reply = reduce_to_response(request, [executor.execute(segs, request)])
            assert not reply.exceptions, reply.exceptions
            replies.append(json.dumps(reply.to_json()["aggregationResults"], sort_keys=True))
            marked.append((executor.metrics.meter("hll.lowering.sort").count, executor.metrics.meter("hll.sort.parts").count))
    finally:
        for c in cached:
            c.cache_clear()
    (keys_a_segment,) = set(handed)  # one aggregate, traced once a program
    parts = kernel_mod.hll_sort_parts(keys_a_segment)
    assert parts > 1 and marked == [(0, 0), (1, parts)]
    assert replies[0] == replies[1] and '"value": "0"' not in replies[0]
    if query == "through_the_zone_tier":
        assert keys_a_segment == 128 * 128  # the gathered view's rows (67 blocks padded to 128), not the segment's 18,000


def test_no_reducer_names_the_lowering():
    """The op tag ``hll_sort:<capacity>`` and the reduce behind it are gone:
    the state is dense registers, and what folds them is ``max``."""
    import inspect

    from pinot_tpu.engine import mesh
    from pinot_tpu.parallel import multichip

    assert not hasattr(kernel_mod, "_reduce_hll_sort")
    # (the one searchsorted of the kernels is the runs group-by's, PR 43: a few thousand places, not a bound a cell)
    for part in (mesh, multichip, kernel_mod._hll_sorted_registers, kernel_mod._group_state, kernel_mod._agg_state,
                 kernel_mod.apply_reduce, kernel_mod._sorted_window_sums):
        assert "searchsorted(" not in inspect.getsource(part), part.__name__
    for module in (kernel_mod, mesh, multichip):
        assert "hll_sort:" not in inspect.getsource(module), module.__name__
    with pytest.raises(ValueError):
        kernel_mod.apply_reduce("hll_sort:40", jnp.zeros((2, 40 * M), jnp.int32))


# ---------------------------------------------------------------------------
# the inner function the two share
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("capacity,rows,sums,valid", [
    (2_200, 16 * 1_024, 1, 0.67),  # TPC-H Q15's shape, a hundredth of its keys: a product under one sum, two rows in three valid
    (2_200, BLOCK + 5, 4, 1.0),  # weight columns in two groups, a ragged row count
    (70_000, 3 * BLOCK, 1, 0.01),  # keys that are blocks apart
])
def test_the_shared_windows_give_the_sorted_sums_the_radix_contractions_states(capacity, rows, sums, valid):
    rng = np.random.default_rng(rows)
    idx = np.where(rng.random(rows) < valid, rng.integers(0, capacity, rows), capacity).astype(np.int32)
    weights = [np.where(idx < capacity, rng.uniform(900.0, 105_000.0, rows), 0.0).astype(np.float32) for _ in range(sums)]
    with jax.enable_x64(False):  # the chip's precision: the states are float32 on both sides
        got = np.asarray(jax.jit(lambda i, *w: kernel_mod._segment_add_sorted(i, list(w), capacity))(idx, *weights))
        want = np.asarray(jax.jit(lambda i, *w: kernel_mod._segment_add_radix(i, list(w), capacity))(idx, *weights))
    assert got.shape == (1 + sums, capacity) and got.dtype == np.float32
    assert np.array_equal(got[0], np.bincount(idx, minlength=capacity + 1)[:capacity])  # the occupancy, exact
    exact = np.stack([np.bincount(idx, weights=w.astype(np.float64), minlength=capacity + 1)[:capacity] for w in weights])
    assert np.allclose(got[1:], exact, rtol=2e-6, atol=0.0) and np.allclose(got[1:], want[1:], rtol=2e-6, atol=0.0)
    assert np.array_equal(got[0], want[0])
