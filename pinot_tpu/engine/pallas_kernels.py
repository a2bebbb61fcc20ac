"""Pallas TPU kernels — fused hot-path experiments.

The default engine path is plain XLA (gathers + masked reductions +
one-hot matmul group-by), which XLA fuses well.  This module provides a
hand-fused Pallas version of the hottest query shape — filtered
multi-SUM group-by (TPC-H Q1) — keeping each row block's entire
pipeline (filter -> mask -> dictionary lookup -> one-hot matmul
accumulate) inside VMEM, one HBM read per forward-index element.

TPU lowering notes (validated on a real v5e chip):

* Mosaic has no arbitrary VMEM int-indexing; ``table[idx]`` does not
  lower.  Two TPU-native substitutes are used instead:
  - **interval filters** (the common case after the planner's
    dictId-space rewrite, e.g. ``l_shipdate <= '1998-09-02'``) become
    pure vector compares ``lo <= fwd < hi`` — no table at all;
  - **table lookups** (match tables, value dictionaries) become
    chunked lane shuffles: the table is cut into 128-lane chunks, each
    chunk is broadcast across sublanes and gathered with
    ``jnp.take_along_axis(chunk, idx - c*128, axis=1)``, which lowers
    to ``tpu.dynamic_gather``; out-of-chunk lanes are masked.  Cost is
    O(card/128) vector ops per block, so tables are capped at
    ``MAX_TABLE_CARD``; higher-cardinality value columns must be fed as
    raw float rows (``value_dicts[i] is None``).
* Group accumulation stays a one-hot matmul into a persistent VMEM
  scratch across grid steps (the MXU path, mirroring
  ``kernel._segment_add_matmul``).

Status: compiles on a TPU v5e under jax 0.9.0 and agrees with the XLA
kernel there (``tests/test_tpu_platform.py``); the unit tests run it in
interpret mode by passing ``interpret=True``.  Nothing in the executor
calls it: XLA's own fusion of the same pipeline is the serving path, and
``tools/microbench.py pallas_ab`` is the A/B that would change that.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu.engine import config

import os as _os

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# sublanes per grid step; the sublane walk is unrolled at trace time, so
# larger blocks trade Mosaic compile time for fewer grid steps
BLOCK_ROWS = int(_os.environ.get("PINOT_TPU_PALLAS_ROWS", "8"))
BLOCK_COLS = 128  # lanes
BLOCK = BLOCK_ROWS * BLOCK_COLS
LANE = 128
MAX_TABLE_CARD = 4096  # beyond this a lookup is 32+ chunked shuffles — feed raw


def _pad_rows(n: int) -> int:
    return -(-n // BLOCK) * BLOCK


def _pad_lane(c: int) -> int:
    return max(LANE, -(-c // LANE) * LANE)


def _table_gather(tab_row: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``tab_row[idx]`` via chunked lane shuffles.

    tab_row: [card_pad] (card_pad % 128 == 0), idx: [R, 128] int32.
    Lowers to ``tpu.dynamic_gather`` per 128-wide chunk.
    """
    card_pad = tab_row.shape[0]
    out = jnp.zeros(idx.shape, tab_row.dtype)
    for c in range(card_pad // LANE):
        chunk = jnp.broadcast_to(tab_row[c * LANE : (c + 1) * LANE][None, :], idx.shape)
        local = idx - c * LANE
        in_chunk = (local >= 0) & (local < LANE)
        g = jnp.take_along_axis(chunk, jnp.clip(local, 0, LANE - 1), axis=1)
        out = jnp.where(in_chunk, g, out)
    return out


def fused_filtered_groupby_sums(
    filter_fwd: jnp.ndarray,  # int [n]
    match: Optional[jnp.ndarray],  # bool [card_f] (table mode) or None
    valid: jnp.ndarray,  # bool  [n]
    group_keys: jnp.ndarray,  # int32 [n] precombined mixed-radix keys
    value_fwds: Sequence[Optional[jnp.ndarray]],  # int [n] or None (raw mode)
    value_dicts: Sequence[Optional[jnp.ndarray]],  # float [card_v] or None
    capacity: int,
    interpret: bool = False,
    filter_bounds: Optional[Tuple[int, int]] = None,  # interval mode [lo, hi)
    value_raws: Optional[Sequence[Optional[jnp.ndarray]]] = None,  # float [n]
):
    """Returns (num_docs, count[K], [sums[K] per value column]).

    One fused pass: mask = filter(filter_fwd) & valid; per value column
    v = dict[v_fwd] (or raw rows); scatter via one-hot matmul into K
    buckets.  Filter is either a match table (``match``) or a dictId
    interval (``filter_bounds``); exactly one must be given.
    """
    if (match is None) == (filter_bounds is None):
        raise ValueError("exactly one of match / filter_bounds required")
    if match is not None and match.shape[0] > MAX_TABLE_CARD:
        raise ValueError(
            f"match table card {match.shape[0]} > {MAX_TABLE_CARD}: the chunked "
            "lane-shuffle unrolls O(card/128) ops per block — rewrite the "
            "predicate as an interval or split it before the pallas path"
        )
    fdt = jnp.float32 if not config.x64_enabled() else jnp.float64
    n = filter_fwd.shape[0]
    n_pad = _pad_rows(n)
    k_pad = _pad_lane(capacity)
    nv = len(value_dicts)
    value_raws = list(value_raws) if value_raws is not None else [None] * nv
    for i in range(nv):
        if (value_dicts[i] is None) == (value_raws[i] is None):
            raise ValueError(f"value column {i}: exactly one of dict/raw required")
        if value_dicts[i] is not None and value_dicts[i].shape[0] > MAX_TABLE_CARD:
            raise ValueError(
                f"value dict card {value_dicts[i].shape[0]} > {MAX_TABLE_CARD}; "
                "stage this column raw for the pallas path"
            )

    def pad1(x, fill=0):
        return jnp.pad(x, (0, n_pad - n), constant_values=fill)

    # filter fwd only read in table mode or interval mode — always staged
    f2 = pad1(filter_fwd.astype(jnp.int32)).reshape(-1, BLOCK_COLS)
    valid2 = pad1(valid, False).reshape(-1, BLOCK_COLS)
    keys2 = pad1(group_keys.astype(jnp.int32)).reshape(-1, BLOCK_COLS)

    row_inputs: List[jnp.ndarray] = []  # per-value row-shaped inputs
    table_inputs: List[jnp.ndarray] = []  # per-value dict tables [1, card_pad]
    val_is_raw: List[bool] = []
    for i in range(nv):
        if value_dicts[i] is None:
            row_inputs.append(pad1(value_raws[i].astype(fdt)).reshape(-1, BLOCK_COLS))
            val_is_raw.append(True)
        else:
            row_inputs.append(
                pad1(value_fwds[i].astype(jnp.int32)).reshape(-1, BLOCK_COLS)
            )
            d = value_dicts[i].astype(fdt)
            dp = _pad_lane(d.shape[0])
            table_inputs.append(jnp.pad(d, (0, dp - d.shape[0]))[None, :])
            val_is_raw.append(False)

    table_mode = match is not None
    if table_mode:
        m = match.astype(fdt)
        mp = _pad_lane(m.shape[0])
        match_in = [jnp.pad(m, (0, mp - m.shape[0]))[None, :]]
        bounds_in = []
    else:
        match_in = []
        lo, hi = filter_bounds
        bounds_in = [jnp.asarray([[int(lo), int(hi)]], dtype=jnp.int32)]

    num_blocks = n_pad // BLOCK
    grid = (num_blocks,)
    n_tables = len(table_inputs)

    def kernel(*refs):
        i = 0
        f_ref = refs[i]; i += 1
        valid_ref = refs[i]; i += 1
        keys_ref = refs[i]; i += 1
        v_refs = refs[i : i + nv]; i += nv
        if table_mode:
            match_ref = refs[i]; i += 1
        else:
            bounds_ref = refs[i]; i += 1
        d_refs = refs[i : i + n_tables]; i += n_tables
        out_docs = refs[i]; i += 1
        out_count = refs[i]; i += 1
        out_sums = refs[i]; i += 1
        acc = refs[i]  # VMEM scratch [nv + 2, k_pad]

        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            acc[:, :] = jnp.zeros((nv + 2, k_pad), dtype=fdt)

        fidx = f_ref[:, :]  # [R, 128] int32
        if table_mode:
            hit = _table_gather(match_ref[0, :], fidx) > 0
        else:
            lo = bounds_ref[0, 0]
            hi = bounds_ref[0, 1]
            hit = (fidx >= lo) & (fidx < hi)
        mask = hit & valid_ref[:, :]
        maskf = mask.astype(fdt)

        lane0 = jax.lax.broadcasted_iota(jnp.int32, (k_pad,), 0) == 0
        acc[0, :] = acc[0, :] + jnp.where(lane0, jnp.sum(maskf), jnp.zeros((), fdt))

        # Mosaic rejects the [R*128, 1] shape cast a full-block one-hot
        # needs, so: transpose each [R, 128] operand once to [128, R]
        # (tpu.transpose) and walk the R sublanes, building the one-hot
        # [128, k_pad] once per sublane and contracting ALL value
        # columns against it in a single [128, nv+1] x [128, k_pad]
        # MXU matmul.
        ti = 0
        cols = [maskf]  # count column
        for vi in range(nv):
            if val_is_raw[vi]:
                vals = v_refs[vi][:, :]
            else:
                vals = _table_gather(d_refs[ti][0, :], v_refs[vi][:, :])
                ti += 1
            cols.append(vals * maskf)
        keys_t = jax.lax.transpose(keys_ref[:, :], (1, 0))  # [128, R]
        cols_t = [jax.lax.transpose(c, (1, 0)) for c in cols]
        iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, k_pad), 1)
        delta = jnp.zeros((nv + 1, k_pad), fdt)
        for s in range(BLOCK_ROWS):
            onehot = (keys_t[:, s : s + 1] == iota_k).astype(fdt)  # [128, k_pad]
            a = jnp.concatenate([c[:, s : s + 1] for c in cols_t], axis=1)
            delta = delta + jax.lax.dot_general(
                a,
                onehot,
                (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=fdt,
            )
        acc[1:, :] = acc[1:, :] + delta

        @pl.when(step == num_blocks - 1)
        def _emit():
            out_docs[0, 0] = acc[0, 0]
            out_count[0, :] = acc[1, :]
            if nv:
                out_sums[:, :] = acc[2:, :]
            else:  # count-only group-by: the padded slot must be written
                out_sums[:, :] = jnp.zeros((1, k_pad), dtype=fdt)

    row_spec = pl.BlockSpec(
        (BLOCK_ROWS, BLOCK_COLS), lambda b: (b, 0), memory_space=pltpu.VMEM
    )
    table_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    in_specs = (
        [row_spec, row_spec, row_spec]
        + [row_spec] * nv
        + ([table_spec] if table_mode else [smem_spec])
        + [table_spec] * n_tables
    )
    inputs = (
        [f2, valid2, keys2]
        + row_inputs
        + match_in
        + bounds_in
        + table_inputs
    )

    out_docs, out_count, out_sums = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1), lambda b: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, k_pad), lambda b: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((max(nv, 1), k_pad), lambda b: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), fdt),
            jax.ShapeDtypeStruct((1, k_pad), fdt),
            jax.ShapeDtypeStruct((max(nv, 1), k_pad), fdt),
        ],
        scratch_shapes=[pltpu.VMEM((nv + 2, k_pad), fdt)],
        interpret=interpret,
    )(*inputs)

    return (
        out_docs[0, 0],
        out_count[0, :capacity],
        [out_sums[i, :capacity] for i in range(nv)],
    )
