"""Zone maps: per-block dictId min/max for host-side block pruning.

The reference answers selective queries in O(matches) via inverted
indexes (``BitmapInvertedIndexReader.java:28``,
``SortedInvertedIndexBasedFilterOperator.java``); a full-scan engine
pays O(n) regardless of selectivity.  The TPU-native substitute is a
**zone map**: per 64k-row block, per SV column, the min/max dictId.
Because dictionaries are sorted, dictId order == value order, so every
predicate the planner already rewrote into dictId space (intervals,
point lists, match tables) can be tested per block on the host:

  interval [lo,hi)   -> candidate iff  zmax >= lo and zmin < hi
  points   {p...}    -> candidate iff  some p in [zmin, zmax]
                        (sorted points: two searchsorted calls)
  match table        -> candidate iff  any(match[zmin : zmax+1])
                        (prefix-sum lookup)

AND/OR trees combine candidacy bitwise; MV leaves are conservatively
all-candidate.  The device program reads only candidate blocks
(``kernel.make_block_table_kernel``: a loop over the launch's block ids
that slices the staged columns where they lie, or for a selection a
gathered copy of them; ``kernel.zone_blocks``), so work scales with
selectivity — a point query on a clustered column touches one block per
segment instead of the whole table.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from pinot_tpu.engine import config
from pinot_tpu.engine.plan import MV_ANY, MV_NONE, SV, StaticPlan
from pinot_tpu.segment.immutable import ImmutableSegment


def zone_block_rows() -> int:
    import os

    v = os.environ.get("PINOT_TPU_ZONE_BLOCK")
    return int(v) if v else 65536


def column_zones(
    seg: ImmutableSegment, column: str, block: int
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(zmin, zmax) dictId per block for an SV column; cached on the
    segment (segments are immutable). None for MV columns."""
    col = seg.column(column)
    if not col.metadata.single_value:
        return None
    cache = getattr(seg, "_zone_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(seg, "_zone_cache", cache)
    key = (column, block)
    z = cache.get(key)
    if z is None:
        # persisted zones may use a different (write-time) block size;
        # a coarser request that is a multiple of it can be derived by
        # grouped min/max instead of rescanning the column
        for (cname, pblock), (pmin, pmax) in cache.items():
            if cname != column or pblock >= block or block % pblock:
                continue
            g = block // pblock
            nb = -(-pmin.size // g)
            pad = nb * g - pmin.size
            if pad:
                pmin = np.concatenate([pmin, np.full(pad, pmin[-1])])
                pmax = np.concatenate([pmax, np.full(pad, pmax[-1])])
            z = (pmin.reshape(nb, g).min(axis=1), pmax.reshape(nb, g).max(axis=1))
            cache[key] = z
            return z
    if z is None:
        if col.fwd is None:
            # no persisted zones to derive from and nothing to scan:
            # degrade to all-candidate (matches the MV handling) rather
            # than crash the query-time pruning path
            return None
        fwd = np.asarray(col.fwd)
        n = fwd.size
        nb = -(-n // block) if n else 0
        pad = nb * block - n
        if pad:
            # pad with the last real value so padding never widens a zone
            fill = fwd[-1] if n else 0
            fwd = np.concatenate([fwd, np.full(pad, fill, fwd.dtype)])
        f2 = fwd.reshape(nb, block) if nb else fwd.reshape(0, block)
        z = (f2.min(axis=1).astype(np.int64), f2.max(axis=1).astype(np.int64))
        cache[key] = z
    return z


def _leaf_candidates(
    leaf, i: int, q_np: Dict, seg: ImmutableSegment, si: int, nb: int, block: int
) -> Optional[np.ndarray]:
    """bool[nb] conservative candidacy for one filter leaf on one
    segment; None = cannot evaluate (treat as all-candidate)."""
    if leaf.mode != SV:
        return None  # MV predicates: conservative
    kind = leaf.eval_kind
    if kind == "docrange":
        # doc-interval predicate: candidacy is exact block overlap —
        # no zones needed (and the column may not even be staged)
        nb_real = -(-seg.num_docs // block)
        out = np.zeros(nb, dtype=bool)
        lo_doc, hi_doc = q_np["bounds"][i][si]
        blk = np.arange(nb_real, dtype=np.int64)
        out[:nb_real] = (blk * block < hi_doc) & ((blk + 1) * block > lo_doc)
        return out
    z = column_zones(seg, leaf.column, block)
    if z is None:
        return None
    zmin, zmax = z
    nb_real = zmin.shape[0]
    out = np.zeros(nb, dtype=bool)  # blocks past the data are dead
    if kind == "interval":
        lo, hi = q_np["bounds"][i][si]
        out[:nb_real] = (zmax >= lo) & (zmin < hi)
        return out
    if kind == "points":
        pts = q_np["pts"][i][si]
        pts = np.sort(pts[pts >= 0])
        if pts.size == 0:
            return out
        out[:nb_real] = np.searchsorted(pts, zmin, "left") < np.searchsorted(
            pts, zmax, "right"
        )
        return out
    if kind == "points_none":
        # NOT IN: a block is excluded only if every row hits the point
        # set — provable from zones only for single-value blocks
        pts = q_np["pts"][i][si]
        pts = set(int(p) for p in pts if p >= 0)
        single = zmin == zmax
        excluded = single & np.isin(zmin, list(pts) or [-1])
        out[:nb_real] = ~excluded
        return out
    if kind == "runs":
        # interval union: candidate when ANY run overlaps the zone
        rr = q_np["runs"][i][si]  # [k, 2], empty runs lo == hi == 0
        hit = np.zeros(nb_real, dtype=bool)
        for lo, hi in rr:
            if hi > lo:
                hit |= (zmax >= lo) & (zmin < hi)
        out[:nb_real] = hit
        return out
    # match table: any matching dictId within [zmin, zmax]
    table = q_np["match"][i][si]
    csum = np.concatenate([[0], np.cumsum(table.astype(np.int64))])
    hi = np.minimum(zmax + 1, csum.size - 1)
    lo = np.minimum(zmin, csum.size - 1)
    out[:nb_real] = (csum[hi] - csum[lo]) > 0
    return out


def _tree_candidates(
    plan: StaticPlan, node, q_np, seg, si: int, nb: int, block: int
) -> np.ndarray:
    kind = node[0]
    if kind == "leaf":
        leaf = plan.leaves[node[1]]
        c = _leaf_candidates(leaf, node[1], q_np, seg, si, nb, block)
        if c is None:
            c = np.ones(nb, dtype=bool)
        return c
    parts = [_tree_candidates(plan, ch, q_np, seg, si, nb, block) for ch in node[1]]
    out = parts[0]
    for p in parts[1:]:
        out = (out & p) if kind == "and" else (out | p)
    return out


def candidate_blocks(
    plan: StaticPlan,
    q_np: Dict,
    live: Sequence[ImmutableSegment],
    n_pad: int,
    block: Optional[int] = None,
) -> Optional[np.ndarray]:
    """bool [len(live), n_pad//block] candidate map, or None when block
    pruning does not apply (no filter, or segments smaller than one
    block).  ``live`` is in the order of ``q_np``'s rows; a None among
    them is a launch's empty slot (``ladder.launched_segments``), which
    has no candidate."""
    if plan.filter_tree is None:
        return None
    block = block or zone_block_rows()
    if n_pad < 2 * block or n_pad % block:
        return None
    nb = n_pad // block
    out = np.zeros((len(live), nb), dtype=bool)
    for si, seg in enumerate(live):
        if seg is None:
            continue
        cand = _tree_candidates(plan, plan.filter_tree, q_np, seg, si, nb, block)
        # blocks fully past the segment's rows stay dead
        nb_live = -(-seg.num_docs // block)
        cand[nb_live:] = False
        out[si] = cand
    return out


def block_ids_input(cand: np.ndarray, nb_pad: int) -> np.ndarray:
    """Pack the candidate map into a padded int32 id array [S, nb_pad]
    (-1 = no block)."""
    S, _ = cand.shape
    ids = np.full((S, nb_pad), -1, dtype=np.int32)
    for s in range(S):
        sel = np.nonzero(cand[s])[0]
        ids[s, : sel.size] = sel
    return ids
