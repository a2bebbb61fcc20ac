"""Compile the group-by contraction kernels for a v5e that is described,
not attached: the TPU's compiler is installed here, and what it refuses
(a slice off the tiling, more VMEM than a kernel may use) it would
refuse on the chip, and what its program keeps in HBM beside its
arguments it would keep there.  Nothing runs, so nothing here is a time
or a result; ``tests/test_engine.py::test_radix_groupby_forced`` and
``test_onehot_groupby_operands_built_in_the_loop`` hold the answers.  One file, one worker: the topology is described inside a
fixture, never at import."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (groups, segments, rows a segment, float columns): the closed cell's Q3
# and Q4, the gate's bound with two sums and with ten, a ragged row count;
# above the bound the rows in key order: Q15's gathered view, the largest
# capacity a device group-by has (its accumulator in VMEM), weight columns in groups
SHAPES = {
    "q3_k2000": (2000, 16, 1 << 23, 1),
    "q4_k2000": (2000, 16, 1 << 23, 2),
    "k513": (513, 4, 1 << 23, 1),
    "bound_two_sums": (1 << 16, 4, 1 << 23, 2),
    "bound_ten_sums": (1 << 16, 2, 1 << 22, 10),
    "ragged_rows": (2000, 4, 3 * (1 << 20) + 5, 1),
    "sorted_q15_k220000": (220_000, 16, 1 << 19, 1),
    "sorted_k1048576_two_sums": (1 << 20, 2, 1 << 19, 2),
    "sorted_k65537_four_sums_ragged": (65_537, 2, (1 << 19) + 5, 4),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_radix_contraction_compiles_for_v5e(one_chip, monkeypatch, shape):
    from pinot_tpu.engine import kernel as kernel_mod

    K, S, n, m = SHAPES[shape]
    assert (K > kernel_mod.RADIX_GROUP_CAP) == shape.startswith("sorted")
    segment_add = kernel_mod._segment_add_sorted if shape.startswith("sorted") else kernel_mod._segment_add_radix
    # the kernel asks the backend whether to run in the Pallas
    # interpreter; this compile is for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def table(idx, *ws):
        single = lambda i, *xs: segment_add(i, [jnp.where(i < K, x, 0) for x in xs], K)
        return jnp.sum(jax.vmap(single)(idx, *ws), axis=0)

    idx = jax.ShapeDtypeStruct((S, n), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((S, n), jnp.float32, sharding=one_chip)
    with jax.enable_x64(False):  # the chip's process runs float32 and int32; the suite's runs x64
        compiled = jax.jit(table).lower(idx, *([w] * m)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the one-hots stay in VMEM: what the program keeps in HBM beside its
    # arguments is the masked weight columns (in key order: the sort's
    # operands, in and out) and no [rows, K1 + 128] operand
    copies = 2 if shape.startswith("sorted") else 1
    assert compiled.memory_analysis().temp_size_in_bytes <= copies * (m + 1) * S * n * 4 + (64 << 20)


# (groups, segments, packed keys a segment): ClickBench's regions as hits_distinct_users_closed holds them
# (a segment in two parts, each one call: 9.3 MB of accumulator), the lowering's cap (67 MB of cells: six ranges a part),
# a ragged row count of one part
HLL_SHAPES = {
    "hits_regions_9040": (9_040, 12, 1 << 23),
    "cap_65536_in_ranges": (1 << 16, 2, 1 << 23),
    "k17_ragged_rows": (17, 2, (1 << 20) + 5),
}


@pytest.mark.parametrize("shape", sorted(HLL_SHAPES))
def test_hll_run_ends_compile_for_v5e(one_chip, monkeypatch, shape):
    """The 'sort' lowering of a grouped distinctcounthll: ONE sort of a
    segment's keys in parts of ``_HLL_SORT_PART`` rows or fewer, and the
    windowed contraction over each (group, register) run's last rank a
    part, the parts and the segments folded by maximum."""
    from pinot_tpu.engine import config, kernel as kernel_mod

    K, S, n = HLL_SHAPES[shape]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    table = lambda packed: jnp.max(jax.vmap(lambda p: kernel_mod._hll_sorted_registers(p, K))(packed), axis=0)
    with jax.enable_x64(False):
        compiled = jax.jit(table).lower(jax.ShapeDtypeStruct((S, n), jnp.int32, sharding=one_chip)).compile()
    ranges = -(-K * config.HLL_M * 4 // kernel_mod._SORTED_ACC_BYTES)
    parts = kernel_mod.hll_sort_parts(n)
    assert (ranges > 1) == shape.endswith("in_ranges") and (parts > 1) == (n > kernel_mod._HLL_SORT_PART) == (shape != "k17_ragged_rows")
    # the keys are the sort's one operand, sorted in place along a part's rows: a stable sort would carry
    # `sort(%keys, %iota)`, a row of row numbers.  A row of the operand is a part of a segment ([parts x segments, rows a
    # part]: the batched form _sort_in_parts writes out), so 24 rows fill three tiles of eight sublanes where
    # [12, 2, 2^22] would pad each part's twelve to sixteen (PR 45: 110.0 ms for 146.7 on the chip)
    text = compiled.as_text()
    sorts = re.findall(r"(\S+) sort\(([^)]*)\), dimensions=\{(\d)\}", text)
    assert len(sorts) == 1 and "," not in sorts[0][1] and "iota" not in sorts[0][1], sorts
    padded = -(-n // (parts * kernel_mod._SORTED_BLOCK)) * kernel_mod._SORTED_BLOCK
    assert sorts[0][0].startswith(f"s32[{parts * S},{padded}]{{1,0:") and sorts[0][2] == "1", sorts
    assert "is_stable=true" not in text
    # sorted keys, a part's copy of its rows, cells and ranks (a range its own) and the accumulators before the fold.  PR
    # 44's bound was 1.5 rows of int32 and two a range beside the accumulators; the parts' slices of the sorted rows are
    # copies, so 2: in rows beside the accumulators the three compiled programs hold 3.95 at 9,040 groups (1,812.1 MB in
    # all; as one part 3.45 and 1,610.7: the peak is not the sort's), 12.07 at 65,536 in ranges (1,078.4 MB; as one part
    # 13.01 and 1,141.5: a range's cells and ranks span a part, not a segment), 0 at 17 (one part: unchanged)
    rows = S * parts * padded * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= (2 + 2 * ranges) * rows + 2 * S * K * config.HLL_M * 4 + (64 << 20)
    assert text.count("tpu_custom_call") >= ranges * parts and "while" in text


# the open cell's two group-bys (benchmark/traffic/suite_open.json: k6, q6) and TPC-H Q1 as the
# specification writes it (benchmark/traffic/tpch_q1q6_closed.json: 36 cells, two products a row)
SUITE_GROUPBYS = {
    "q1_spec": "SELECT sum(l_quantity), sum(l_extendedprice), sum(l_extendedprice*(1-l_discount)), "
               "sum(l_extendedprice*(1-l_discount)*(1+l_tax)), avg(l_quantity), avg(l_extendedprice), avg(l_discount), "
               "count(*) FROM lineitem WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag, l_linestatus TOP 10",
    "k6": "SELECT sum(l_quantity), sum(l_extendedprice), sum(l_discount), count(*) FROM lineitem "
          "WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag, l_linestatus TOP 10",
    "q6": "SELECT sum(l_extendedprice) FROM lineitem WHERE l_shipmode IN ('RAIL','FOB') AND "
          "l_receiptdate BETWEEN '1997-01-01' AND '1997-12-31' GROUP BY l_shipmode TOP 10",
}


@pytest.fixture(scope="module")
def suite_launches():
    """(plan, segment arrays, query inputs) of each launch of
    SUITE_GROUPBYS as the executor makes it on the chip: float32, value
    columns staged raw, the contractions on; over a tiny lineitem table."""
    from pinot_tpu.engine import kernel as kernel_mod
    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.pql import optimize_request, parse_pql
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    launches = {}
    with pytest.MonkeyPatch.context() as mp, jax.enable_x64(False):
        mp.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
        mp.setenv("PINOT_TPU_RAW_CARD_MIN", "0")
        run_kernel = QueryExecutor._run_kernel

        def spy(self, kernel, args, plan, *rest, **kw):
            launches[name] = (plan, args[0], args[1])
            return run_kernel(self, kernel, args, plan, *rest, **kw)

        mp.setattr(QueryExecutor, "_run_kernel", spy)
        segs = [synthetic_lineitem_segment(4096, seed=70 + i, name=f"compile{i}") for i in range(2)]
        try:
            for name, pql in SUITE_GROUPBYS.items():
                QueryExecutor().execute(segs, optimize_request(parse_pql(pql)))
        finally:
            kernel_mod.make_table_kernel.cache_clear()
            kernel_mod.make_packed_table_kernel.cache_clear()
    return launches


@pytest.mark.parametrize("shape", sorted(SUITE_GROUPBYS))
def test_loop_groupby_keeps_its_operands_out_of_hbm_on_v5e(one_chip, suite_launches, monkeypatch, shape):
    """The single-segment kernel of the open cell's group-bys, vmapped
    over 16 segments of 8,388,608 rows: with the operands built in the
    row loop the program keeps next to nothing in HBM beside its
    arguments.  The staged form kept the [4, S, n] stack and the index
    (2.0 GiB for k6, 1.1 for q6)."""
    from pinot_tpu.engine import kernel as kernel_mod

    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    plan, segs, q = suite_launches[shape]
    assert kernel_mod.groupby_operands(plan) == "loop"
    S, n = 16, 1 << 23

    def at_scale(key, v):
        rows = (n,) + v.shape[2:] if kernel_mod._row_key(key) else v.shape[1:]
        return jax.ShapeDtypeStruct((S,) + rows, v.dtype, sharding=one_chip)

    segs = {key: at_scale(key, v) for key, v in segs.items()}
    q = jax.tree_util.tree_map(lambda v: at_scale("", v), q)
    assert sum(v.shape == (S, n) for v in segs.values()) >= 3
    table = jax.jit(jax.vmap(kernel_mod.make_single_segment_kernel(plan)))
    with jax.enable_x64(False):
        compiled = table.lower(segs, q).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


# the two closed cells' queries that ride the zone tier (benchmark/traffic/groupby_closed.json: q5;
# tpch_q1q6_closed.json: q6), and the candidate blocks they keep of a segment's 128, padded
ZONE_SHAPES = {
    "q5": ("SELECT sum(l_extendedprice) FROM lineitem WHERE l_shipdate BETWEEN '1995-01-01' AND '1996-12-31' "
           "GROUP BY l_shipdate TOP 10", 64),
    "q6": ("SELECT sum(l_extendedprice*l_discount) FROM lineitem WHERE l_shipdate >= '1994-01-01' AND "
           "l_shipdate < '1995-01-01' AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24", 32),
}


# TPC-H Q15 (benchmark/traffic/tpch_q15_closed.json): a quarter of the clustered date is 6 or 7 of a
# segment's 128 blocks, padded to 8; 220,000 suppliers are over RADIX_GROUP_CAP and _INPLACE_STATE_CELLS
Q15 = ("SELECT sum(l_extendedprice*(1-l_discount)) FROM lineitem WHERE l_shipdate >= '1996-01-01' AND "
       "l_shipdate < '1996-04-01' GROUP BY l_suppkey TOP 1", 8)


@pytest.fixture(scope="module")
def zone_launches():
    """(plan, segment arrays, query inputs) of each launch of ZONE_SHAPES
    and of Q15 through the zone tier as the executor makes it on the
    chip, over a tiny lineitem table (with its supplier key: the nine
    older columns are the plain table's) with a block to match."""
    from pinot_tpu.engine import kernel as kernel_mod
    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.pql import optimize_request, parse_pql
    from pinot_tpu.tools.datagen import synthetic_lineitem_keys_segment

    launches = {}
    with pytest.MonkeyPatch.context() as mp, jax.enable_x64(False):
        mp.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
        mp.setenv("PINOT_TPU_RAW_CARD_MIN", "0")
        mp.setenv("PINOT_TPU_INVINDEX", "0")
        mp.setenv("PINOT_TPU_ZONE_BLOCK", "512")
        run_kernel = QueryExecutor._run_kernel

        def spy(self, kernel, args, plan, staged, digest, block_ids, *rest, **kw):
            assert block_ids is not None, name
            launches[name] = (plan, args[0], args[1])
            return run_kernel(self, kernel, args, plan, staged, digest, block_ids, *rest, **kw)

        mp.setattr(QueryExecutor, "_run_kernel", spy)
        segs = [synthetic_lineitem_keys_segment(32768, seed=70 + i, name=f"zone{i}") for i in range(2)]
        try:
            for name, (pql, _) in dict(ZONE_SHAPES, q15=Q15).items():
                QueryExecutor().execute(segs, optimize_request(parse_pql(pql)))
        finally:
            for cached in (kernel_mod.make_table_kernel, kernel_mod.make_block_table_kernel,
                           kernel_mod.make_packed_block_table_kernel):
                cached.cache_clear()
    return launches


def compile_zone_program(one_chip, launch, nb_pad: int):
    """A zone launch's block program compiled for the described chip at
    16 segments of 8,388,608 rows in blocks of 65,536, ``nb_pad``
    candidate blocks a segment."""
    from pinot_tpu.engine import kernel as kernel_mod

    plan, segs, q = launch
    S, n, block = 16, 1 << 23, 1 << 16

    def at_scale(key, v):
        rows = (n,) + v.shape[2:] if kernel_mod._row_key(key) else v.shape[1:]
        return jax.ShapeDtypeStruct((S,) + rows, v.dtype, sharding=one_chip)

    segs = {key: at_scale(key, v) for key, v in segs.items()}
    q = jax.tree_util.tree_map(lambda v: at_scale("", v), q)
    ids = jax.ShapeDtypeStruct((S, nb_pad), jnp.int32, sharding=one_chip)
    table = kernel_mod.make_block_table_kernel(plan, block)
    try:
        with jax.enable_x64(False):
            return table.lower(segs, q, ids).compile()
    finally:
        kernel_mod.make_block_table_kernel.cache_clear()


@pytest.mark.parametrize("shape", sorted(ZONE_SHAPES))
def test_zone_program_reads_its_blocks_in_place_on_v5e(one_chip, zone_launches, monkeypatch, shape):
    """The zone tier's block program of Q5 and of TPC-H Q6 over 16
    segments of 8,388,608 rows in blocks of 65,536: looping over the
    block ids in place, the program keeps next to nothing in HBM beside
    its arguments.  The gathered form kept the copies of the candidate
    blocks there (1.0 GiB for Q5, 1.2 for Q6)."""
    from pinot_tpu.engine import kernel as kernel_mod

    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the radix contraction compiled, not interpreted
    assert kernel_mod.zone_blocks(zone_launches[shape][0]) == "inplace"
    compiled = compile_zone_program(one_chip, zone_launches[shape], ZONE_SHAPES[shape][1])
    assert ("tpu_custom_call" in compiled.as_text()) == (shape == "q5")
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_q15_sorts_220000_groups_over_the_gathered_view_on_v5e(one_chip, zone_launches, monkeypatch):
    """TPC-H Q15's zone program, 8 candidate blocks a segment, 220,000
    suppliers: over the gathered copy of the candidate blocks the rows
    are sorted by supplier with the product they carry, and a block of
    them contracts over a window of keys (PR 38).  No count, sum or avg
    reaches a scatter.  What the program keeps in HBM beside its 1.5 GiB
    of arguments is stated here: under the 1.10 GiB of the scatter's
    program (the copies of three columns, ``valid`` and ``rowid`` over
    8.4M rows, the sort's operands, [16, 4, 1792, 128] accumulators)."""
    from pinot_tpu.engine import kernel as kernel_mod

    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the contraction compiled, not interpreted
    plan, segs, _ = zone_launches["q15"]
    assert plan.group_by.capacity == 220_000 > kernel_mod.RADIX_GROUP_CAP
    assert kernel_mod.groupby_lowering(plan) == "radix" and kernel_mod.zone_blocks(plan) == "gathered"
    assert kernel_mod.groupby_operands(plan) == "sorted"
    assert kernel_mod._state_cells(plan) == 2 * 220_000 > kernel_mod._INPLACE_STATE_CELLS
    assert plan.group_by.use_gfwd == (True,) and segs["l_suppkey.gfwd"].dtype == jnp.int32  # ids of 4 bytes
    compiled = compile_zone_program(one_chip, zone_launches["q15"], Q15[1])
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "scatter(" not in text and " sort(" in text
    # staged: global ids of 4 bytes and two float32 measures a row; l_shipdate is searched, never staged
    assert 0 <= memory.argument_size_in_bytes - 3 * 16 * (1 << 23) * 4 < 1 << 20
    assert memory.output_size_in_bytes <= 2 * 220_000 * 4 + 4096
    # the scatter's program of PR 37 kept 1,184,590,848 bytes there
    assert memory.temp_size_in_bytes < 1_184_590_848, f"temporaries {memory.temp_size_in_bytes / (1 << 30):.3f} GiB"


# ClickBench line 16 (benchmark/traffic/hits_topusers_closed.json): COUNT(*) by 17.6M UserID, TOP 10, the
# 'runs' lowering (PR 43); beside it the same with a sum and an average, which the sort carries
RUNS_SHAPES = {
    "line_16": "SELECT COUNT(*) FROM hits GROUP BY UserID TOP 10",
    "a_sum_and_an_average": "SELECT COUNT(*), sum(AdvEngineID), avg(ResolutionWidth) FROM hits GROUP BY UserID TOP 10",
}
HITS_USERS_KEYS = 17_630_976


@pytest.fixture(scope="module")
def runs_launches():
    """(plan, segment arrays, query inputs) of each launch of RUNS_SHAPES
    as the executor makes it on the chip (float32, int32), over a tiny
    hits table whose users are over a patched MAX_GROUP_CAPACITY."""
    from pinot_tpu.engine import config, kernel as kernel_mod
    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.pql import optimize_request, parse_pql
    from pinot_tpu.tools.datagen import synthetic_hits_users_segment

    launches = {}
    with pytest.MonkeyPatch.context() as mp, jax.enable_x64(False):
        mp.setenv("PINOT_TPU_RAW_CARD_MIN", "0")
        mp.setattr(config, "MAX_GROUP_CAPACITY", 1 << 10)
        run_kernel = QueryExecutor._run_kernel

        def spy(self, kernel, args, plan, *rest, **kw):
            launches[name] = (plan, args[0], args[1])
            return run_kernel(self, kernel, args, plan, *rest, **kw)

        mp.setattr(QueryExecutor, "_run_kernel", spy)
        segs = [synthetic_hits_users_segment(4096, seed=430 + i, name=f"runs{i}") for i in range(2)]
        try:
            for name, pql in RUNS_SHAPES.items():
                QueryExecutor().execute(segs, optimize_request(parse_pql(pql)))
        finally:
            kernel_mod.make_table_kernel.cache_clear()
            kernel_mod.make_packed_table_kernel.cache_clear()
    return launches


@pytest.mark.parametrize("shape", sorted(RUNS_SHAPES))
def test_runs_groupby_compiles_for_v5e_at_the_cells_size(one_chip, runs_launches, monkeypatch, shape):
    """The whole table program of ClickBench's line 16 at the cell's own
    size, 12 segments of 8,388,608 rows and 17.6M keys (and, smaller, of
    the same with two carried columns): one sort of the
    table's ids, the blocked pass over them (Pallas, compiled and not
    interpreted), the cut, the candidates.  What comes back
    is kilobytes, and what the program keeps in HBM beside the staged
    columns is rows, never keys: a few vectors of 100.7M elements, and
    the candidates' blocks."""
    import dataclasses
    import math

    from pinot_tpu.engine import config, kernel as kernel_mod
    from pinot_tpu.engine.results import MAX_TRIM_TIES

    monkeypatch.setattr(config, "MAX_GROUP_CAPACITY", 1 << 20)  # the program's own bound: 17.6M keys are over it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the pass compiled for the chip, not interpreted
    plan, segs, q = runs_launches[shape]
    plan = dataclasses.replace(plan, group_by=dataclasses.replace(
        plan.group_by, gcards=(HITS_USERS_KEYS,), capacity=HITS_USERS_KEYS))
    assert kernel_mod.groupby_lowering(plan) == "runs"
    # the cell's query at the cell's size; the carried columns' scans over few rows (a step of the scan a doubling of the rows: the compile grows with them)
    S, n = (12, 1 << 23) if shape == "line_16" else (2, 1 << 16)

    def at_scale(key, v):
        rows = (n,) + v.shape[2:] if kernel_mod._row_key(key) else v.shape[1:]
        dtype = jnp.int32 if key.endswith(".gfwd") else v.dtype  # ids of 17.6M values take four bytes
        return jax.ShapeDtypeStruct((S,) + rows, dtype, sharding=one_chip)

    segs = {key: at_scale(key, v) for key, v in segs.items()}
    q = jax.tree_util.tree_map(lambda v: at_scale("", v), q)
    assert segs["UserID.gfwd"].shape == (S, n)
    try:
        with jax.enable_x64(False):
            compiled = kernel_mod.make_table_kernel(plan).lower(segs, q).compile()
    finally:
        kernel_mod.make_table_kernel.cache_clear()
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes < 1 << 20  # candidates, a count and a digest: never 17.6M of anything
    columns = 1 + 2 * (shape != "line_16")  # the key, and the two measures the sort carries
    # (3 + 3 x columns) vectors of S x n x 4 B: the rows laid flat, the sort's result, the pass's lengths
    # (and distances), a carried column's scan; and the candidates' blocks, twice (PR 48's program kept 8 + 6 x columns)
    places = 100 + MAX_TRIM_TIES
    assert memory.temp_size_in_bytes <= (3 + 3 * columns) * S * n * 4 + 2 * places * kernel_mod._RUNS_PLACE_BLOCK * 4
    text = compiled.as_text()
    assert text.count(" sort(") == 1  # one sort of the table's rows: it is the merge across segments too
    assert text.count("tpu_custom_call") == 1  # the pass over the sorted ids
    if shape == "line_16":
        # nothing cumulative and no search runs along the table's rows: a window at most over the candidates' blocks,
        # and no loop that carries a vector of the rows (PR 48's program had six: three copies of the segments' rows
        # into one vector, the cut's counting passes and the two searches)
        windows = [math.prod([int(d) for d in re.search(r"= \w+\[([\d,]+)\]", line).group(1).split(",")])
                   for line in text.splitlines() if " reduce-window(" in line]
        assert max(windows) <= places * kernel_mod._RUNS_PLACE_BLOCK < S * n // 8
        assert not [line for line in text.splitlines() if " while(" in line and f"[{S * n}]" in line]


# a launch over a window of the staged table's segments (PR 48): the neighbours of a date range, and a dead one among them
LAUNCH_SHAPES = {
    "window_4_of_16": "SELECT SUM(v), COUNT(*) FROM dated WHERE wk BETWEEN 120 AND 159 GROUP BY g100, g50 TOP 5000",
    "window_3_of_16_a_dead_one_between": "SELECT SUM(v), COUNT(*) FROM dated WHERE (wk BETWEEN 80 AND 99 OR wk = 115) GROUP BY g100, g50 TOP 5000",
}


@pytest.fixture(scope="module")
def part_launches():
    """(plan, segment arrays, query inputs) of each launch of
    LAUNCH_SHAPES as the executor makes it on the chip (float32, int32),
    over sixteen tiny segments of ten weeks each."""
    import numpy as np

    from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema
    from pinot_tpu.engine import kernel as kernel_mod
    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.pql import optimize_request, parse_pql
    from pinot_tpu.segment.columnar import build_segment_from_columns

    schema = Schema("dated", dimensions=[FieldSpec("wk", DataType.INT), FieldSpec("g100", DataType.INT), FieldSpec("g50", DataType.INT)],
                    metrics=[FieldSpec("v", DataType.INT, FieldType.METRIC)])
    rng = np.random.default_rng(48)
    n = 1024
    segs = [build_segment_from_columns(schema, {
        "wk": (10 * i + rng.integers(0, 10, size=n)).astype(np.int32), "g100": rng.integers(0, 100, size=n).astype(np.int32),
        "g50": rng.integers(0, 50, size=n).astype(np.int32), "v": rng.integers(1, 1000, size=n).astype(np.int32),
    }, n, "dated", f"part{i:02d}") for i in range(16)]
    launches = {}
    with pytest.MonkeyPatch.context() as mp, jax.enable_x64(False):
        mp.setenv("PINOT_TPU_RAW_CARD_MIN", "0")
        mp.setenv("PINOT_TPU_INVINDEX", "0")
        run_kernel = QueryExecutor._run_kernel

        def spy(self, kernel, args, plan, *rest, **kw):
            launches[name] = (plan, args[0], args[1])
            return run_kernel(self, kernel, args, plan, *rest, **kw)

        mp.setattr(QueryExecutor, "_run_kernel", spy)
        try:
            for name, pql in LAUNCH_SHAPES.items():
                QueryExecutor().execute(segs, optimize_request(parse_pql(pql)))
        finally:
            kernel_mod.make_table_kernel.cache_clear()
            kernel_mod.make_packed_table_kernel.cache_clear()
    return launches


@pytest.mark.parametrize("shape", sorted(LAUNCH_SHAPES))
def test_a_launch_over_four_of_sixteen_segments_keeps_four_segments_of_temporaries_on_v5e(one_chip, part_launches, monkeypatch, shape):
    """The table program of a 5,000-cell group-by (the radix contraction,
    as SSB's q4_2 takes) over four of sixteen resident segments of
    8,388,608 rows: the arguments are the whole staged columns, and what
    the program keeps in HBM beside them is four segments' worth, the
    view (one ``dynamic-slice`` a column, a dead segment inside the
    window an empty slot) and the kernel's own, never sixteen."""
    from pinot_tpu.engine import kernel as kernel_mod

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan, segs, q = part_launches[shape]
    assert kernel_mod.groupby_lowering(plan) == "radix"
    assert q["segments"]["slots"].shape == (4,) and int((q["segments"]["slots"] < 0).sum()) == (shape != "window_4_of_16")
    S, L, n = 16, 4, 1 << 23

    def at_scale(key, v):
        rows = (n,) + v.shape[2:] if kernel_mod._row_key(key) else v.shape[1:]
        return jax.ShapeDtypeStruct((S,) + rows, v.dtype, sharding=one_chip)

    segs = {key: at_scale(key, v) for key, v in segs.items()}
    q = jax.tree_util.tree_map(lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip), q)
    try:
        with jax.enable_x64(False):
            compiled = kernel_mod.make_table_kernel(plan).lower(segs, q).compile()
    finally:
        kernel_mod.make_table_kernel.cache_clear()
    memory = compiled.memory_analysis()
    staged = sum(v.dtype.itemsize * S * n for key, v in segs.items() if kernel_mod._row_key(key))
    assert memory.argument_size_in_bytes >= staged  # the whole resident columns go in
    assert memory.temp_size_in_bytes < staged  # and under a quarter of their rows is worked on
    assert "dynamic-slice" in compiled.as_text() and "gather" not in compiled.as_text()  # one slice a column


# ClickBench lines 25 and 26 (benchmark/traffic/hits_search_selection_closed.json): a selection under an ORDER BY
# over a time and over a STRING column of 6M values (PR 50).  Line 27's key, the pair, does not pack into 2^30 and takes
# the stable sort of four operands, whose program compiles for over a minute at ANY size (69 s here at [2, 2^20], 79 s
# at the cell's [12, 2^23], where it keeps 2.28 GB beside its arguments): too long for tier-1, so it is not compiled here
SEARCH = "SELECT SearchPhrase FROM hits WHERE SearchPhrase <> '' ORDER BY {} LIMIT 10"
SELECTION_SHAPES = {"by_time": "EventTime", "by_phrase": "SearchPhrase"}
HITS_SECONDS, HITS_PHRASE_KEYS = 3_110_400, 6_019_104


@pytest.fixture(scope="module")
def selection_launches():
    """(plan, segment arrays, query inputs) of each launch of
    SELECTION_SHAPES as the executor makes it on the chip (float32,
    int32), over a tiny table of the cell's generator."""
    from pinot_tpu.engine import kernel as kernel_mod
    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.pql import optimize_request, parse_pql
    from pinot_tpu.tools.datagen import synthetic_hits_search_segment

    launches = {}
    with pytest.MonkeyPatch.context() as mp, jax.enable_x64(False):
        mp.setenv("PINOT_TPU_RAW_CARD_MIN", "0")
        run_kernel = QueryExecutor._run_kernel

        def spy(self, kernel, args, plan, *rest, **kw):
            launches[name] = (plan, args[0], args[1])
            return run_kernel(self, kernel, args, plan, *rest, **kw)

        mp.setattr(QueryExecutor, "_run_kernel", spy)
        segs = [synthetic_hits_search_segment(4096, seed=500 + i, name=f"search{i}", users=5_000, phrases=9_000) for i in range(2)]
        try:
            for name, order in SELECTION_SHAPES.items():
                QueryExecutor().execute(segs, optimize_request(parse_pql(SEARCH.format(order))))
        finally:
            kernel_mod.make_table_kernel.cache_clear()
            kernel_mod.make_packed_table_kernel.cache_clear()
    return launches


@pytest.mark.parametrize("shape", sorted(SELECTION_SHAPES))
def test_selection_compiles_for_v5e_at_the_cells_size(one_chip, selection_launches, monkeypatch, shape):
    """The table programs of ClickBench's lines 25 and 26 at the cell's
    own size and key spaces (12 segments of 8,388,608 rows; 3.1M seconds,
    6M phrases): one ``lax.top_k`` a segment over a packed key.  What
    comes back is ten row numbers a segment, and what the program keeps
    in HBM beside the staged ids is a few vectors of its rows."""
    import dataclasses

    from pinot_tpu.engine import kernel as kernel_mod

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan, segs, q = selection_launches[shape]
    gcards = ({"EventTime": HITS_SECONDS, "SearchPhrase": HITS_PHRASE_KEYS}[plan.selection.sort_columns[0]],)
    plan = dataclasses.replace(plan, selection=dataclasses.replace(plan.selection, sort_gcards=gcards))
    assert kernel_mod.selection_lowering(plan) == "topk" and plan.selection.k == 10 and plan.selection.use_gfwd == (True,)
    S, n = 12, 1 << 23

    def at_scale(key, v):
        rows = (n,) + v.shape[2:] if kernel_mod._row_key(key) else v.shape[1:]
        dtype = jnp.int32 if key.endswith((".gfwd", ".fwd")) else v.dtype  # ids of 259,200 to 6M values take four bytes
        return jax.ShapeDtypeStruct((S,) + rows, dtype, sharding=one_chip)

    segs = {key: at_scale(key, v) for key, v in segs.items()}
    q = jax.tree_util.tree_map(lambda v: at_scale("", v), q)
    try:
        with jax.enable_x64(False):
            compiled = kernel_mod.make_table_kernel(plan).lower(segs, q).compile()
    finally:
        kernel_mod.make_table_kernel.cache_clear()
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes < 1 << 16  # ten row numbers and their validity a segment, and a count
    # the key and the row numbers, in and out, the segment axis padded to its tile of 16
    assert memory.temp_size_in_bytes <= 4 * 16 * n * 4 + (64 << 20), f"temporaries {memory.temp_size_in_bytes / (1 << 30):.3f} GiB"
