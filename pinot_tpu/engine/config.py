"""Engine-wide dtype and sizing policy.

On CPU test runs x64 is enabled and aggregation runs in float64,
reproducing the reference's Java ``double`` semantics exactly; on TPU the
default is float32/bfloat16-friendly shapes (sums use pairwise tree
reduction inside XLA, which keeps error small at 100M+ rows).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Padding buckets: shapes are padded up so the jit cache stays small
# (the reference's analog is its fixed 10k/5k block sizes,
# DocIdSetPlanNode.java:33).
DOC_PAD_MULTIPLE = 1024
MIN_CARD_PAD = 8

# Group-by dense-holder cap (reference caps ARRAY_BASED key space at 1M,
# DefaultGroupKeyGenerator.java): beyond this no dense holder is built.
# count, sum and avg under a TOP n then take the runs lowering on the
# device (kernel.groupby_lowering 'runs': the rows sorted by key, no
# holder at all); what it does not take runs the host hash path, by name
# (plan.group_runs_host_reason).
MAX_GROUP_CAPACITY = 1 << 20

# distinctcount / percentile dense state cap (global dictionary size).
MAX_VALUE_STATE = 1 << 22

# sort-dedup distinct path (StaticAgg.sort_pairs): device output buffer
# for compacted unique (group, valueId) pairs.  Overflow (more unique
# pairs than this) falls back to the host path at runtime — at that
# cardinality the exact-distinct result itself is bigger than any
# sensible response payload.
DISTINCT_PAIR_CAP = 1 << 22

# Rows the host path (engine/host_fallback.py) takes in one step: the
# filter mask, the dictionary gathers and the float64 partial states of
# one block.  That bounds what one numpy call holds the interpreter lock
# for beside serving (the shadow auditor's passes), and keeps a block's
# temporaries (8 bytes a row each) in the cache and in memory the
# allocator already holds.  On a v5e host the widest shape served (a
# K=6 group-by with four aggregates, 134M rows) takes 5 to 6 ms a step
# here; a pass of it cost 3.1 s of the processor at 2^18, 3.4 s at
# 2^19, 4.3 to 8.0 s at 2^20 by the machine, 20 s with a segment whole
# (PERF.md section 6, PR 29).
HOST_BLOCK_ROWS = 1 << 18

HLL_LOG2M = 8  # HllConstants.java DEFAULT_LOG2M
HLL_M = 1 << HLL_LOG2M


def x64_enabled() -> bool:
    return bool(jax.config.jax_enable_x64)


def float_dtype():
    return jnp.float64 if x64_enabled() else jnp.float32


def np_float_dtype():
    return np.float64 if x64_enabled() else np.float32


def key_dtype():
    return jnp.int64 if x64_enabled() else jnp.int32


def max_key_space() -> int:
    return 2**62 if x64_enabled() else 2**30


def row_count_dtype():
    """Integer dtype in which row counts cross the segment axis and the
    mesh.  A float32 sum stops being exact at 2^24: 16 segments of 8.4M
    rows answered ``count(*)`` one short on the chip.  Within ONE segment
    a float32 count is exact (fewer than 2^24 rows), so kernels may
    count in float there (the one-hot matmul does) and cast before the
    merge.  int32 bounds a server's answer at 2^31 - 1 matched rows."""
    return jnp.int64 if x64_enabled() else jnp.int32


def pad_docs(n: int) -> int:
    """Round doc count up to the padding bucket (pow2 beyond one block)."""
    if n <= DOC_PAD_MULTIPLE:
        m = 8
        while m < n:
            m *= 2
        return m
    blocks = -(-n // DOC_PAD_MULTIPLE)
    # round block count to next power of two to bound jit-cache size
    b = 1
    while b < blocks:
        b *= 2
    return b * DOC_PAD_MULTIPLE


def pad_card(c: int) -> int:
    m = MIN_CARD_PAD
    while m < c:
        m *= 2
    return m


def pad_value_card(c: int) -> int:
    """Value-state holder padding: QUARTER-pow2 buckets (2048, 2560,
    3072, 3584, 4096, 5120, ...).  The dense presence/hist/HLL
    contraction cost is LINEAR in the padded cardinality, so pow2's
    up-to-2x overshoot is real MXU work (l_shipdate's 2526 values
    padded to 4096, a 1.6x tax on the HLL group-by); quarter steps
    cap the overshoot at 25% while keeping the jit cache bucketed."""
    base = MIN_CARD_PAD
    while base * 2 <= c:
        base *= 2
    if base >= c:
        return base
    step = max(base // 4, MIN_CARD_PAD)
    return base + -(-(c - base) // step) * step


# ---------------------------------------------------------------------------
# HBM staging widths.  The query kernels are memory-bound (SURVEY §6:
# rows/s ~ HBM bytes/row), so forward indexes stage at the narrowest
# integer dtype that holds the dictId range — the analog of the
# reference's bit-packed fwd index (FixedBitSingleValueReader.java:25),
# except the "unpack" is a free in-register upcast on TPU.
# ---------------------------------------------------------------------------

# Agg-input feed policy: columns with cardinality above raw_card_min()
# stage a dictionary-decoded float raw array for aggregation reads; at
# or below it, the kernel gathers dict_vals[fwd].
#
# XLA lowers the per-row dict gather to a serialized loop on the TPU
# (from a measurement before the chip round, record gone; what the raw
# feeds cost in staged bytes is ROADMAP S9).  So on accelerators
# the threshold defaults to 0: ALWAYS stage raw feeds — the 4x HBM
# bytes/row are far cheaper than any gather.  On CPU (tests) vector
# gathers are cheap and narrow staging halves memory, so the old
# threshold stands.  Env-overridable for A/B (PINOT_TPU_RAW_CARD_MIN).
import os as _os

_raw_card_min: int | None = None


def raw_card_min() -> int:
    """Lazy so importing config never initializes a jax backend (tests
    must force the CPU mesh before first backend init)."""
    global _raw_card_min
    env = _os.environ.get("PINOT_TPU_RAW_CARD_MIN")
    if env is not None:
        return int(env)
    if _raw_card_min is None:
        import jax

        _raw_card_min = (1 << 15) if jax.default_backend() == "cpu" else 0
    return _raw_card_min


_qinput_budget: int | None = None


def qinput_cache_budget_bytes() -> int:
    """HBM byte budget for the device-resident query-input cache
    (executor._to_device_inputs).  Sized so serving many distinct query
    shapes over high-cardinality tables cannot pin unbounded HBM: the
    v5e chip has 16 GB; segments + workspace dominate, so the input
    cache defaults to 1 GiB.  Env-overridable
    (PINOT_TPU_QINPUT_CACHE_BYTES); 0 disables caching entirely.
    Parsed once — this sits on the query hot path, and a junk env value
    must degrade to the default, not fail every query at serve time."""
    global _qinput_budget
    if _qinput_budget is None:
        try:
            _qinput_budget = int(_os.environ.get("PINOT_TPU_QINPUT_CACHE_BYTES", 1 << 30))
        except ValueError:
            _qinput_budget = 1 << 30
    return _qinput_budget


def index_dtype(max_exclusive: int):
    """np dtype for dictId arrays indexing tables of max_exclusive rows.

    Unsigned, and sized so the table length itself is representable
    (jax index normalization materializes the axis size as a constant
    of the index dtype)."""
    if max_exclusive <= 255:
        return np.uint8
    if max_exclusive <= 65535:
        return np.uint16
    return np.int32


# count arrays (values <= bound) share the same width ladder
count_dtype = index_dtype
