"""Single-transfer kernel-output fetch.

A Q1-shaped query returns ~10 output leaves, and fetching them one
``np.asarray`` at a time pays one device-to-host transfer (dispatch,
sync and copy) each, serially, on the query's critical path.

Fix: bitcast every output leaf to bytes ON DEVICE, concatenate into one
``uint8`` buffer inside the same jitted program, fetch it with a single
transfer, and slice/view it back into numpy arrays on host.  The
reference lands on the same design point for its server->broker hop:
all result sections ride in one contiguous binary DataTable payload
(``common/utils/DataTable.java:304-325``), not an object per column.

The layout (shapes/dtypes/offsets) is derived host-side with
``jax.eval_shape`` — a trace, not an execution — and cached per input
shape signature, mirroring jit's own executable cache.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp


def _to_bytes(x: jnp.ndarray) -> jnp.ndarray:
    """Flatten one leaf to a 1-D uint8 view (device-side)."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    b = jax.lax.bitcast_convert_type(x, jnp.uint8)
    return b.reshape(-1)


def _np_dtype(dt) -> np.dtype:
    return np.dtype(np.bool_) if dt == jnp.bool_ else np.dtype(dt)


def _layout_for(out_shapes) -> Tuple[Any, list]:
    leaves, treedef = jax.tree_util.tree_flatten(out_shapes)
    layout = []
    off = 0
    for s in leaves:
        dt = _np_dtype(s.dtype)
        nbytes = int(np.prod(s.shape, dtype=np.int64)) * dt.itemsize
        pad = (-nbytes) % 8  # 8-byte aligned parts: safe host .view()
        layout.append((tuple(s.shape), dt, off, nbytes))
        off += nbytes + pad
    return treedef, layout


def make_packed_kernel(fn: Callable, name: str) -> Callable:
    """Wrap a kernel-like callable (pytree of device arrays out) so a
    call returns the same pytree as HOST numpy arrays via one packed
    device-to-host transfer.  ``name`` (engine/kernel.py
    ``kernel_name``) is what the program is jitted under, and the
    returned callable's ``__name__``: a capture shows
    ``jit_<name>(<fingerprint>)``, not one ``jit_packed`` for all.

    The returned callable also exposes the two pipeline halves as
    attributes: ``.dispatch(*args) -> handle`` launches the packed
    program and returns WITHOUT reading it back (jax dispatch is
    asynchronous — the device lane uses this to keep the device queue
    fed), and ``.fetch(handle)`` performs the single blocking D2H
    transfer + unpack (the FINALIZE stage, safe to call from any
    thread and from several waiters of one coalesced dispatch)."""

    def packed(*args):
        leaves = jax.tree_util.tree_leaves(fn(*args))
        parts = []
        for x in leaves:
            b = _to_bytes(jnp.asarray(x))
            pad = (-b.size) % 8
            if pad:
                b = jnp.pad(b, (0, pad))
            parts.append(b)
        if not parts:
            return jnp.zeros((0,), jnp.uint8)
        return jnp.concatenate(parts)

    packed.__name__ = packed.__qualname__ = name
    packed = jax.jit(packed)
    layout_cache: Dict[Tuple, Tuple] = {}

    def dispatch(*args):
        """Launch the packed program; returns an opaque (layout, device
        buffer) handle without blocking on execution."""
        key = tuple(
            (tuple(l.shape), str(l.dtype))
            for l in jax.tree_util.tree_leaves(args)
            if hasattr(l, "shape")
        )
        lay = layout_cache.get(key)
        if lay is None:
            lay = _layout_for(jax.eval_shape(fn, *args))
            if len(layout_cache) > 64:
                layout_cache.clear()
            layout_cache[key] = lay
        return lay, packed(*args)

    def fetch(handle, count_transfer: bool = True):
        """ONE device->host transfer + unpack; blocks until the
        dispatched program completes."""
        (treedef, layout), buf_dev = handle
        buf = np.asarray(buf_dev)
        # D2H accounting for the utilization plane: this is THE packed
        # result transfer, so counting here captures every pipelined
        # and serial device query's fetch bytes.  Coalesced waiters
        # pass count_transfer=False — they unpack the SAME cached host
        # copy, and N records for one physical copy would inflate
        # d2hBytes with the coalescing rate.
        from pinot_tpu.engine.device import TRANSFERS

        if count_transfer:
            TRANSFERS.record_d2h(buf.nbytes)
        outs = []
        for shape, dt, off, nbytes in layout:
            if nbytes == 0:
                outs.append(np.zeros(shape, dt))
                continue
            part = buf[off : off + nbytes]
            if dt == np.bool_:
                outs.append(part.copy().reshape(shape).astype(np.bool_))
            else:
                outs.append(part.copy().view(dt).reshape(shape))
        return jax.tree_util.tree_unflatten(treedef, outs)

    def call(*args):
        return fetch(dispatch(*args))

    call.__name__ = name
    call.dispatch = dispatch
    call.fetch = fetch
    # AOT lowering handle for the static cost analysis (the jitted
    # packed program is what actually runs, so its analysis is the
    # honest one — packing copies included)
    call.lower = packed.lower
    return call


# ---------------------------------------------------------------------------
# Bit-sliced index (BSI) encoding — the fourth filter/aggregate tier's
# segment-pack-time layout (engine/bitsliced.py, engine/kernel.py).
# A width-W non-negative integer column becomes W bit-planes of packed
# uint32 words: row r lands in word r // 32 at bit r % 32 (LSB-first
# within a word, plane b holds bit b of every row).  Predicates then
# evaluate as O(W) wide AND/OR/popcount passes over n/32 words instead
# of O(n) per-row compares — the bulk-bitwise PIM formulation.
# ---------------------------------------------------------------------------


def bit_width(max_value: int) -> int:
    """Planes needed for values in [0, max_value] — at least 1 so a
    constant column still round-trips through the encoder."""
    return max(1, int(max_value).bit_length())


def bitslice_encode(
    values: np.ndarray, width: int, n_words: int
) -> np.ndarray:
    """uint32 [width, n_words] bit-planes of a non-negative int array.

    Rows beyond ``values.size`` (up to ``n_words * 32``) encode as 0 —
    the kernels mask padding through the validity words, mirroring how
    the forward-index staging zero-pads (device.py _stack_fwd)."""
    v = np.ascontiguousarray(values, dtype=np.int64)
    if v.size and (int(v.min()) < 0 or bit_width(int(v.max())) > width):
        raise ValueError(
            f"values out of range for {width}-plane bit-slice encoding"
        )
    planes = np.zeros((width, n_words), dtype=np.uint32)
    n = min(v.size, n_words * 32)
    for b in range(width):
        bits = np.zeros(n_words * 32, dtype=np.uint8)
        bits[:n] = (v[:n] >> b) & 1
        planes[b] = np.packbits(bits, bitorder="little").view(np.uint32)
    return planes


def bitslice_decode(planes: np.ndarray, num_rows: int) -> np.ndarray:
    """Inverse of bitslice_encode: int64 [num_rows] values."""
    width, n_words = planes.shape
    out = np.zeros(num_rows, dtype=np.int64)
    for b in range(width):
        bits = np.unpackbits(
            np.ascontiguousarray(planes[b]).view(np.uint8), bitorder="little"
        )[:num_rows]
        out |= bits.astype(np.int64) << b
    return out


def integral_dictionary_values(values) -> "np.ndarray | None":
    """Dictionary values as exact non-negative-offsettable int64, or
    None when the dictionary is not exactly integral (fused SUM must be
    bit-exact against the scan tier's float accumulation, which it is
    for integral values below 2**53 — engine/bitsliced.py)."""
    vals = np.asarray(values)
    if not np.issubdtype(vals.dtype, np.number) or vals.size == 0:
        return None
    v = np.asarray(vals, dtype=np.float64)
    if not np.all(np.isfinite(v)):
        return None
    if np.any(np.abs(v) >= 2.0**53) or not np.all(v == np.floor(v)):
        return None
    return v.astype(np.int64)


# ---------------------------------------------------------------------------
# Cross-query batching helpers (engine/dispatch.py micro-batching tier):
# stack B queries' host input pytrees along a new leading axis before the
# one vmapped launch, and slice one member's outputs back out of the
# fetched batch.
# ---------------------------------------------------------------------------


def stack_query_inputs(inputs_list):
    """Stack B structurally-identical numpy query-input pytrees into one
    pytree whose ndarray leaves lead with the batch axis.  Callers
    guarantee structural identity (same StaticPlan => same treedef and
    leaf shapes — the batch key enforces it); non-array leaves must be
    equal across members and pass through unstacked."""
    leaves0, treedef = jax.tree_util.tree_flatten(inputs_list[0])
    stacked = []
    columns = [jax.tree_util.tree_flatten(t)[0] for t in inputs_list]
    for i, leaf in enumerate(leaves0):
        if isinstance(leaf, np.ndarray):
            stacked.append(np.stack([col[i] for col in columns]))
        else:
            stacked.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, stacked)


def batch_input_signature(inputs) -> tuple:
    """Hashable (shape, dtype) signature of a query-input pytree — the
    belt-and-braces component of the lane batch key: two dispatches
    stack only when their leaves agree exactly."""
    return tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        if isinstance(leaf, np.ndarray)
        else ("scalar", repr(leaf))
        for leaf in jax.tree_util.tree_leaves(inputs)
    )


def slice_batched_outputs(outs, index: int):
    """Member ``index``'s output pytree from a batched launch's fetched
    host outputs (every array leaf leads with the batch axis)."""
    return jax.tree_util.tree_map(lambda x: x[index], outs)


# ---------------------------------------------------------------------------
# Static XLA cost analysis (the utilization plane's "paper roofline"
# numerator): flops + bytes-accessed estimates per compiled plan.
# ---------------------------------------------------------------------------


def _normalize_cost_analysis(ca) -> "dict | None":
    """XLA cost-analysis dict -> {"flops", "bytesAccessed"} floats, or
    None when the backend reported nothing usable."""
    if not isinstance(ca, dict):
        return None
    out = {}
    flops = ca.get("flops")
    if isinstance(flops, (int, float)) and flops >= 0:
        out["flops"] = float(flops)
    nbytes = ca.get("bytes accessed")
    if isinstance(nbytes, (int, float)) and nbytes >= 0:
        out["bytesAccessed"] = float(nbytes)
    return out or None


def kernel_cost_analysis(kernel, args) -> "dict | None":
    """Static per-plan cost analysis for a kernel callable — the packed
    wrapper above (``.lower`` re-exported) or a plain ``jax.jit``
    object.  Tries the cheap path first (``lowered.cost_analysis()`` —
    a trace plus HLO-level analysis, no XLA optimization pass), and
    falls back to ``lowered.compile().cost_analysis()`` plus
    ``memory_analysis`` only when ``PINOT_TPU_COST_ANALYSIS=compile``
    (a SECOND full compile of the plan, so never implicit).  Returns ``{"flops", "bytesAccessed"[,
    "peakMemoryBytes"], "source"}`` or None — every backend gap
    degrades to None, never an exception (the graceful-fallback
    contract the tests hold)."""
    import os

    mode = os.environ.get("PINOT_TPU_COST_ANALYSIS", "lowered")
    if mode == "0" or mode == "off":
        return None
    lower = getattr(kernel, "lower", None)
    if lower is None:
        return None
    try:
        lowered = lower(*args)
    except Exception:
        return None
    out = None
    try:
        out = _normalize_cost_analysis(lowered.cost_analysis())
    except Exception:
        out = None
    if out is not None:
        out["source"] = "lowered"
    if mode == "compile":
        try:
            compiled = lowered.compile()
            full = _normalize_cost_analysis(compiled.cost_analysis())
            if full is not None:
                out = dict(full)
                out["source"] = "compiled"
            try:
                mem = compiled.memory_analysis()
                peak = sum(
                    int(getattr(mem, attr, 0) or 0)
                    for attr in (
                        "argument_size_in_bytes",
                        "output_size_in_bytes",
                        "temp_size_in_bytes",
                    )
                )
                if out is not None and peak > 0:
                    out["peakMemoryBytes"] = peak
            except Exception:
                pass
        except Exception:
            pass
    return out
