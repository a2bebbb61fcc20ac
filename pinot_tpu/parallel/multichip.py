"""Multi-chip query execution: shard the segment axis over a device mesh.

The reference scales a query two ways (SURVEY §2.5): segments fan out
across server threads (``MCombineOperator.java:55-64``) and across
servers via broker scatter-gather + reduce
(``BrokerReduceService.java:62``).  On TPU both collapse into ONE SPMD
program: the stacked segment axis is sharded over a 1-D
``jax.sharding.Mesh``; each chip vmaps the single-segment kernel over
its local segments; cross-chip merge is an XLA collective over ICI
(``psum`` for sums/histograms/group-by holders, ``pmin``/``pmax`` for
min/max/HLL registers/presence bitmaps).  Aggregation outputs come back
replicated; selection candidates stay sharded (gathered host-side).

Cross-host/DCN scale-out keeps the broker/server scatter-gather path
(see ``pinot_tpu.broker``) — the mesh covers the chips a single server
process owns (its "slice").
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from pinot_tpu.engine.kernel import (
    apply_reduce,
    make_single_segment_kernel,
    output_reducers,
)
from pinot_tpu.engine.plan import StaticPlan

SEGMENT_AXIS = "segments"


def default_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.asarray(devs), (SEGMENT_AXIS,))


def _collective(op: str, value: Any, axis):
    # ``axis`` may be one name or a tuple of mesh axis names: on a 2-D
    # (hosts, chips) mesh the same psum reduces over ICI within a host
    # and DCN across hosts (multihost.py layering)
    if op == "sum":
        return jax.lax.psum(value, axis)
    if op == "min":
        return jax.lax.pmin(value, axis)
    if op == "max":
        return jax.lax.pmax(value, axis)
    if op == "sum_pair":
        return (jax.lax.psum(value[0], axis), jax.lax.psum(value[1], axis))
    if op == "minmax_pair":
        return (jax.lax.pmin(value[0], axis), jax.lax.pmax(value[1], axis))
    if op == "distinct_pairs":
        # sort-dedup distinct/histogram merge across chips: each chip's
        # compacted buffer converts run starts -> counts, all chips
        # gather everyone's buffers (CAP-bounded, rides ICI/DCN), and a
        # replicated re-merge sums counts of pairs seen on several chips
        from pinot_tpu.engine.kernel import (
            _PAIR_SENTINEL,
            counts_from_starts,
            merge_pair_buffers,
        )

        slots, gids, starts, n, total = value
        k_buf = slots.shape[0]
        counts = counts_from_starts(starts, n, total)
        iota = jax.lax.iota(jnp.int32, k_buf)
        valid = iota < n
        s_ = jnp.where(valid, slots, _PAIR_SENTINEL)
        g_ = jnp.where(valid, gids, _PAIR_SENTINEL)
        # a chip whose local uniques overflowed its buffer already lost
        # pairs; so can int32 cumsum positions past ~2^30 total
        # occurrences — both force the merged n_unique past the buffer
        # so the executor's overflow check drops to the exact host path
        over_local = (n > k_buf).astype(jnp.int32)
        names = axis if isinstance(axis, tuple) else (axis,)
        stacked = jnp.stack([s_, g_, counts])  # ONE gather per axis
        for ax in names:
            stacked = jnp.concatenate(jax.lax.all_gather(stacked, ax), axis=1)
        grand_total = jax.lax.psum(total.astype(jnp.float32), axis)
        overflow = jax.lax.psum(over_local, axis) + (
            grand_total >= 2.0**30
        ).astype(jnp.int32)
        s2, g2, e2, n_u, tv = merge_pair_buffers(
            stacked[0], stacked[1], stacked[2]
        )
        n_u = jnp.where(overflow > 0, jnp.int32(s2.shape[0] + 1), n_u)
        return (s2, g2, e2, n_u, tv)
    if op == "none":
        return value
    raise ValueError(op)


def _out_specs(reducers: Dict[str, str], shard_spec) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, op in reducers.items():
        spec = shard_spec if op == "none" else P()
        if op in ("sum_pair", "minmax_pair"):
            out[k] = (spec, spec)
        elif op == "distinct_pairs":
            out[k] = (spec,) * 5
        else:
            out[k] = spec
    return out


def _make_sharded(plan: StaticPlan, mesh: Mesh, stacked: Callable, n_extra: int) -> Callable:
    """Shared SPMD wiring for the full-scan and block-skipping kernels:
    ``stacked`` gives every local segment's outputs [S_local, ...] on
    each chip, merged with collectives over every mesh axis.
    ``n_extra`` extra positional operands (e.g. the block id array)
    shard over the segment axis like everything else."""
    reducers = output_reducers(plan)
    axes = tuple(mesh.axis_names)  # 1-D (segments) or 2-D (hosts, segments)

    def local_fn(segs: Dict[str, Any], q: Dict[str, Any], *extra) -> Dict[str, Any]:
        outs = stacked(segs, q, *extra)  # this chip's segments
        merged: Dict[str, Any] = {}
        for k, v in outs.items():
            op = reducers[k]
            if op == "none":
                merged[k] = v  # stays sharded over the segment axis
            else:
                merged[k] = _collective(op, apply_reduce(op, v), axes)
        return merged

    shard_spec = P(axes)  # segment axis sharded over every mesh axis

    def sharded(segs, q, *extra):
        in_specs = (
            jax.tree_util.tree_map(lambda _: shard_spec, segs),
            jax.tree_util.tree_map(lambda _: shard_spec, q),
        ) + (shard_spec,) * n_extra
        fn = shard_map(
            local_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=_out_specs(reducers, shard_spec),
            check_vma=False,
        )
        return fn(segs, q, *extra)

    from pinot_tpu.engine.kernel import kernel_name, named

    return jax.jit(named(sharded, kernel_name("meshzone" if n_extra else "mesh", plan)))


def make_sharded_table_kernel(plan: StaticPlan, mesh: Mesh, block: Optional[int] = None) -> Callable:
    """Compile the query kernel as an SPMD program over the mesh.

    Takes the same (seg_arrays, query_inputs) pytrees as the
    single-chip table kernel; every leaf's leading axis must equal the
    (padded) segment count and divide evenly by the mesh size.  Works
    over a 1-D ``segments`` mesh (one server's slice, ICI collectives)
    or a 2-D ``(hosts, segments)`` mesh (``multihost.py``): the segment
    axis shards over all mesh axes and the merge collectives name all
    of them, so XLA lowers the reduction hierarchically — ICI inside a
    host, DCN across hosts.

    With ``block`` (the zone tier's block rows) it is the block-skipping
    program: the block id array [S, nb_pad] is a third operand and
    shards over the segment axis with everything else, so selective
    queries stay O(candidate blocks) per chip (an 'inplace' plan's loop
    runs over the union of its shard's ids).
    """
    if block is None:
        return _make_sharded(plan, mesh, jax.vmap(make_single_segment_kernel(plan)), 0)
    from pinot_tpu.engine.kernel import make_stacked_block_kernel

    return _make_sharded(plan, mesh, make_stacked_block_kernel(plan, block), 1)
