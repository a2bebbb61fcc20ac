"""Device staging: immutable segments -> HBM-resident stacked arrays.

The analog of the reference's mmap staging (``PinotDataBuffer.java:45``)
plus the load path (``Loaders.java:40``): column data becomes jax device
arrays, ready for the jit'd query kernels.

Layout (S = number of segments stacked on the leading axis — the
parallelism axis that replaces MCombineOperator's thread pools and is
sharded over the chip mesh in ``pinot_tpu.parallel``):

  fwd        int8/16/32 [S, n_pad]          SV dictId forward index
  mv         int8/16/32 [S, n_pad, mv_pad]  MV dictIds (padded)
  mv_counts  int8/16    [S, n_pad]          per-doc MV entry count
  dict_vals  float      [S, card_pad]       numeric dictionary values
  num_docs_arr int32    [S]                 true doc count per segment

Integer widths are minimal for the column's cardinality
(``config.index_dtype``) — the kernels are HBM-bandwidth-bound, so a
card-3 column should cost 1 byte/row, not 4.  Validity masks are never
stored: the kernel derives doc validity from ``iota < num_docs`` and MV
entry validity from ``iota < mv_counts``, trading a free register
compare for an HBM byte per row (or per MV slot).

All shapes are bucketed (pow2 padding, ``config.pad_docs/pad_card``;
value-state holder axes use quarter-pow2 ``config.pad_value_card``) so
the jit cache stays bounded; padding docs carry dictId 0.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu.common.schema import DataType
from pinot_tpu.engine import config
from pinot_tpu.segment.immutable import ImmutableSegment


@dataclass
class StagedColumn:
    name: str
    stored_type: DataType
    single_value: bool
    card_pad: int
    mv_pad: int
    cards: Tuple[int, ...]  # per-segment true cardinality
    fwd: Optional[jnp.ndarray] = None
    mv: Optional[jnp.ndarray] = None
    mv_counts: Optional[jnp.ndarray] = None
    dict_vals: Optional[jnp.ndarray] = None
    # optional role-specific arrays (big-dictionary gathers are slow on
    # TPU, so these trade HBM for streaming access):
    raw: Optional[jnp.ndarray] = None  # float [S, n_pad] dictionary-decoded values
    gfwd: Optional[jnp.ndarray] = None  # int32 [S, n_pad] global-dictId fwd
    hll_bucket: Optional[jnp.ndarray] = None  # uint8 [S, n_pad] HLL register index
    hll_rho: Optional[jnp.ndarray] = None  # uint8 [S, n_pad] HLL rank
    mv_raw: Optional[jnp.ndarray] = None  # float [S, n_pad, mv_pad] decoded MV values
    # bit-sliced tier planes (engine/bitsliced.py): dictId bit-planes
    # for bitwise filter/min/max evaluation, and value-offset planes
    # (value - per-segment vmin) for popcount-fused SUM
    bsi: Optional[jnp.ndarray] = None  # uint32 [S, W, n_pad//32] dictId planes
    bsiv: Optional[jnp.ndarray] = None  # uint32 [S, Wv, n_pad//32] value-offset planes
    bsi_width: int = 0
    bsiv_width: int = 0
    bsiv_min: Optional[Tuple[int, ...]] = None  # per-segment integer vmin

    @property
    def is_numeric(self) -> bool:
        return self.stored_type != DataType.STRING


import itertools

_stage_tokens = itertools.count()


@dataclass
class StagedTable:
    """A set of segments staged into device memory, stacked on axis 0."""

    segment_names: Tuple[str, ...]
    num_segments: int
    n_pad: int
    num_docs: Tuple[int, ...]
    num_docs_arr: jnp.ndarray  # int32 [S]
    columns: Dict[str, StagedColumn] = field(default_factory=dict)
    _valid: Optional[jnp.ndarray] = None
    # process-unique staging identity: the device lane's coalesce key
    # needs "same staged table" without pinning the object (an id()
    # would recycle after GC and could alias a RE-staged table into an
    # in-flight dispatch — silent stale results).  Sharded placements
    # (mesh execution) keep the same invariant: each (segment set,
    # placement) staging mints its OWN token, so a table re-staged onto
    # a different chip group can never alias an in-flight dispatch.
    token: int = field(default_factory=lambda: next(_stage_tokens))
    # placement of the leading segment axis (engine/mesh.py chip
    # groups): a jax Sharding splitting axis 0 across the group's
    # chips, or None for default single-device placement.  Role-array
    # augmentation and the on-demand valid mask must land on the SAME
    # placement, so it rides the staged table.
    sharding: Any = field(default=None, repr=False, compare=False)

    def column(self, name: str) -> StagedColumn:
        return self.columns[name]

    @property
    def total_docs(self) -> int:
        return int(sum(self.num_docs))

    @property
    def valid(self) -> jnp.ndarray:
        """bool [S, n_pad] doc-validity mask, materialized on demand —
        kernels derive validity from num_docs instead of reading this."""
        if self._valid is None:
            v = np.zeros((self.num_segments, self.n_pad), dtype=bool)
            for i, n in enumerate(self.num_docs):
                v[i, :n] = True
            # same placement as the staged columns: a default-device
            # mask fed to a chip-group program would force a reshard
            self._valid = (
                jax.device_put(v, self.sharding)
                if self.sharding is not None
                else jnp.asarray(v)
            )
        return self._valid


def _csr_scatter(values, offsets, out_row, *extra):
    """Fill one segment's padded [n_pad, mv_pad] matrix row block from
    CSR (values, offsets) — the ONE place the scatter-index math lives.
    ``extra`` pairs of (values2, out_row2) scatter through the same
    indices (mv ids + mv_raw share one offsets array)."""
    counts = np.diff(offsets)
    n = counts.size
    row_idx = np.repeat(np.arange(n), counts)
    col_idx = (
        np.concatenate([np.arange(k) for k in counts]) if n else np.zeros(0, int)
    )
    out_row[row_idx, col_idx] = values
    for v2, o2 in zip(extra[::2], extra[1::2]):
        o2[row_idx, col_idx] = v2
    return counts


def stage_segments(
    segments: Sequence[ImmutableSegment],
    column_names: Sequence[str],
    device=None,
    pad_segments_to: int = 0,
    raw_columns: Sequence[str] = (),
    gfwd_columns: Sequence[str] = (),
    hll_columns: Sequence[str] = (),
    ctx=None,
    skip_base_columns: Sequence[str] = (),
    sharding=None,
    bsi_columns: Sequence[str] = (),
    bsiv_columns: Sequence[str] = (),
    hll_timer=None,
) -> StagedTable:
    """Stack + pad + transfer the given columns of the segments.

    ``pad_segments_to`` rounds the segment axis up with all-invalid
    dummy segments so it divides the mesh's device count (multi-chip
    ``shard_map`` needs an evenly shardable leading axis).

    ``sharding`` (mesh execution, engine/mesh.py): a jax Sharding
    splitting the leading segment axis across a chip group — the
    GlobalDeviceArray-style staging where each chip's HBM holds only
    its shard of every column.  None keeps default placement (the
    single-chip path).

    ``raw_columns`` (numeric SV) additionally stage dictionary-decoded
    value arrays; ``gfwd_columns`` (SV, requires ``ctx``) stage
    global-dictId forward arrays; ``hll_columns`` (SV) stage per-row
    HLL (register, rank) uint8 streams. All are host-side numpy
    gathers done once at staging so query kernels stream instead of
    gathering.

    ``skip_base_columns``: SV columns whose base ``fwd``/``dict_vals``
    arrays are NOT uploaded — for columns the kernel reads only through
    a role stream (agg input / group key / HLL), the dictId stream is
    dead HBM weight; at 1B rows it decides whether the table fits on
    one chip at all.  The caller must guarantee no filter leaf,
    selection output, or dict-gather path touches these columns.
    """
    S = max(len(segments), pad_segments_to)
    n_pad = config.pad_docs(max(seg.num_docs for seg in segments))

    if sharding is not None:
        put = lambda x: jax.device_put(x, sharding)  # noqa: E731
    elif device is not None:
        put = lambda x: jax.device_put(x, device)  # noqa: E731
    else:
        put = jnp.asarray

    staged = StagedTable(
        segment_names=tuple(s.segment_name for s in segments),
        num_segments=S,
        n_pad=n_pad,
        num_docs=tuple(s.num_docs for s in segments) + (0,) * (S - len(segments)),
        num_docs_arr=put(
            np.asarray(
                [s.num_docs for s in segments] + [0] * (S - len(segments)),
                dtype=np.int32,
            )
        ),
        sharding=sharding,
    )

    fdt = config.np_float_dtype()
    for name in column_names:
        cols = [seg.column(name) for seg in segments]
        meta0 = cols[0].metadata
        cards = tuple(c.dictionary.cardinality for c in cols)
        card_pad = config.pad_card(max(cards))
        idt = config.index_dtype(card_pad)
        sc = StagedColumn(
            name=name,
            stored_type=meta0.data_type.stored_type,
            single_value=meta0.single_value,
            card_pad=card_pad,
            mv_pad=0,
            cards=cards,
        )
        skip_base = name in skip_base_columns and meta0.single_value
        if meta0.single_value:
            if not skip_base:
                # the stacked copy is built only when it uploads — at
                # 1B rows the transient alone is multiple GB of host RAM
                sc.fwd = put(_stack_fwd(cols, S, n_pad, idt))
            if name in raw_columns and sc.is_numeric:
                raw = np.zeros((S, n_pad), dtype=fdt)
                for i, c in enumerate(cols):
                    vals = np.asarray(c.dictionary.values, dtype=fdt)
                    raw[i, : c.fwd.size] = vals[c.fwd]
                sc.raw = put(raw)
            if name in gfwd_columns and ctx is not None:
                gdt = config.index_dtype(
                    config.pad_card(ctx.column(name).global_cardinality)
                )
                gf = np.zeros((S, n_pad), dtype=gdt)
                remaps = ctx.column(name).remaps
                for i, c in enumerate(cols):
                    gf[i, : c.fwd.size] = remaps[i][c.fwd]
                sc.gfwd = put(gf)
            if name in hll_columns:
                hb, hr = _hll_streams(cols, S, n_pad, hll_timer)
                sc.hll_rho = put(hr)  # rho first (see _augment_staged)
                sc.hll_bucket = put(hb)
            if name in bsi_columns:
                sc.bsi_width = bsi_filter_width(cols)
                sc.bsi = put(_bsi_planes(cols, S, n_pad, sc.bsi_width))
            if name in bsiv_columns and sc.is_numeric:
                spec = bsiv_value_spec(cols)
                if spec is not None:
                    sc.bsiv_width, sc.bsiv_min = spec
                    sc.bsiv = put(
                        _bsiv_planes(cols, S, n_pad, sc.bsiv_width, sc.bsiv_min)
                    )
        else:
            mv_pad = max(1, max(c.metadata.max_num_multi_values for c in cols))
            mv_pad = config.pad_card(mv_pad)  # pow2 bucket
            mv = np.zeros((S, n_pad, mv_pad), dtype=idt)
            mvc = np.zeros((S, n_pad), dtype=config.count_dtype(mv_pad))
            want_raw = name in raw_columns and sc.is_numeric
            mvr = np.zeros((S, n_pad, mv_pad), dtype=fdt) if want_raw else None
            for i, c in enumerate(cols):
                if mvr is not None:
                    vals = np.asarray(c.dictionary.values, dtype=fdt)
                    counts = _csr_scatter(
                        c.mv_values, c.mv_offsets, mv[i], vals[c.mv_values], mvr[i]
                    )
                else:
                    counts = _csr_scatter(c.mv_values, c.mv_offsets, mv[i])
                mvc[i, : counts.size] = counts
            sc.mv_pad = mv_pad
            sc.mv = put(mv)
            sc.mv_counts = put(mvc)
            if mvr is not None:
                sc.mv_raw = put(mvr)
        if sc.is_numeric and not skip_base:
            sc.dict_vals = put(_stack_dict_vals(cols, S, card_pad, fdt))
        staged.columns[name] = sc
    return staged


def _stack_fwd(cols, S: int, n_pad: int, idt) -> np.ndarray:
    """Stacked (S, n_pad) dictId forward array — the ONE layout shared
    by staging and the later-query backfill (_augment_staged)."""
    fwd = np.zeros((S, n_pad), dtype=idt)
    for i, c in enumerate(cols):
        fwd[i, : c.fwd.size] = c.fwd
    return fwd


def _stack_dict_vals(cols, S: int, card_pad: int, fdt) -> np.ndarray:
    dv = np.zeros((S, card_pad), dtype=fdt)
    for i, c in enumerate(cols):
        dv[i, : c.dictionary.cardinality] = np.asarray(c.dictionary.values, dtype=fdt)
    return dv


# ---------------------------------------------------------------------------
# Bit-sliced tier staging (engine/bitsliced.py): plane layouts are
# built host-side at staging time with the packing.py encoder, stacked
# [S, W, n_pad//32], and attached as role arrays so realtime
# staging-token advances invalidate them exactly like every other role.
# ---------------------------------------------------------------------------


def bsi_filter_width(cols) -> int:
    """Uniform dictId plane count across segments: enough planes for
    the widest per-segment dictionary."""
    from pinot_tpu.engine.packing import bit_width

    return max(bit_width(max(c.dictionary.cardinality - 1, 0)) for c in cols)


def bsiv_value_spec(cols) -> "Optional[Tuple[int, Tuple[int, ...]]]":
    """(plane count, per-segment integer vmin) for value-offset planes,
    or None when any segment's dictionary is not exactly integral —
    fused SUM is only offered where it is bit-exact vs the scan tier."""
    from pinot_tpu.engine.packing import bit_width, integral_dictionary_values

    vmins = []
    width = 1
    for c in cols:
        iv = integral_dictionary_values(c.dictionary.values)
        if iv is None:
            return None
        vmin, vmax = int(iv.min()), int(iv.max())
        vmins.append(vmin)
        width = max(width, bit_width(vmax - vmin))
    if width > 32:
        return None
    return width, tuple(vmins)


def _bsi_planes(cols, S: int, n_pad: int, width: int) -> np.ndarray:
    from pinot_tpu.engine.packing import bitslice_encode

    # round UP: segments smaller than one 32-row word still need a word
    nw = max(1, (n_pad + 31) // 32)
    planes = np.zeros((S, width, nw), dtype=np.uint32)
    for i, c in enumerate(cols):
        planes[i] = bitslice_encode(np.asarray(c.fwd), width, nw)
    return planes


def _bsiv_planes(
    cols, S: int, n_pad: int, width: int, vmins: Tuple[int, ...]
) -> np.ndarray:
    from pinot_tpu.engine.packing import bitslice_encode, integral_dictionary_values

    nw = max(1, (n_pad + 31) // 32)
    planes = np.zeros((S, width, nw), dtype=np.uint32)
    for i, c in enumerate(cols):
        iv = integral_dictionary_values(c.dictionary.values)
        planes[i] = bitslice_encode(iv[c.fwd] - vmins[i], width, nw)
    return planes


# ---------------------------------------------------------------------------
# HBM staging ledger: byte-accurate accounting of what the staging
# cache currently pins in device memory, per staged table / column /
# role — the capacity signal multichip staging and broker admission
# control consume.  One ledger per process (the staging cache is
# process-global too: in-process multi-server harnesses share one
# device, so their instances report the same process-wide figure).
# ---------------------------------------------------------------------------

# StagedColumn array attributes -> ledger role names
_ROLE_ATTRS = (
    ("fwd", "fwd"),
    ("mv", "mv"),
    ("mv_counts", "mvCounts"),
    ("dict_vals", "dict"),
    ("raw", "raw"),
    ("gfwd", "gfwd"),
    ("hll_bucket", "hll"),
    ("hll_rho", "hll"),
    ("mv_raw", "mvRaw"),
    ("bsi", "bsi"),
    ("bsiv", "bsi"),
)


def _device_label(dev) -> str:
    return f"{getattr(dev, 'platform', 'dev')}:{getattr(dev, 'id', '?')}"


def _add_device_bytes(arr, by_device: Dict[str, int]) -> None:
    """Attribute one staged array's bytes to the device(s) actually
    holding them.  Sharded placements (mesh execution) split across the
    chip group via ``addressable_shards`` — each shard's OWN nbytes, so
    a replicated array honestly counts once per holding device; plain
    single-device arrays land on their one device; host-side arrays
    (never the real staging path) attribute to "host"."""
    shards = None
    try:
        shards = getattr(arr, "addressable_shards", None)
    except Exception:
        shards = None
    if shards:
        try:
            # accumulate into a scratch map first: a mid-iteration
            # failure (buffer deleted concurrently) must not leave
            # partial per-shard bytes behind AND re-attribute the whole
            # array below — that would break "byDevice sums to total"
            local: Dict[str, int] = {}
            for sh in shards:
                key = _device_label(getattr(sh, "device", None))
                local[key] = local.get(key, 0) + int(sh.data.nbytes)
            for key, n in local.items():
                by_device[key] = by_device.get(key, 0) + n
            return
        except Exception:
            pass  # fall through to whole-array attribution
    by_device["host"] = by_device.get("host", 0) + int(getattr(arr, "nbytes", 0))


def _measure_staged(
    staged: StagedTable,
) -> Tuple[int, Dict[str, int], Dict[str, int], Dict[str, int]]:
    """(total bytes, per-column bytes, per-role bytes, per-device
    bytes) of a staged table's device arrays — read straight off the
    jax arrays' nbytes, so the ledger total matches the staged bytes
    exactly; the per-device map sums to the total for (non-replicated)
    sharded placements."""
    total = int(getattr(staged.num_docs_arr, "nbytes", 0))
    by_role: Dict[str, int] = {"meta": total}
    by_device: Dict[str, int] = {}
    _add_device_bytes(staged.num_docs_arr, by_device)
    if staged._valid is not None:
        n = int(staged._valid.nbytes)
        total += n
        by_role["meta"] = by_role.get("meta", 0) + n
        _add_device_bytes(staged._valid, by_device)
    by_column: Dict[str, int] = {}
    for name, sc in staged.columns.items():
        col_bytes = 0
        for attr, role in _ROLE_ATTRS:
            arr = getattr(sc, attr)
            if arr is None:
                continue
            n = int(arr.nbytes)
            col_bytes += n
            by_role[role] = by_role.get(role, 0) + n
            _add_device_bytes(arr, by_device)
        by_column[name] = col_bytes
        total += col_bytes
    return total, by_column, by_role, by_device


class StagingLedger:
    """Ledger of HBM-resident staged tables: byte totals, per-table /
    per-column-role breakdowns, a high-watermark, and eviction
    visibility.  Entries key on the StagedTable's process-unique
    ``token`` and are re-measured on role augmentation, so the totals
    stay byte-accurate as arrays attach."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: Dict[int, Dict] = {}  # token -> entry
        self.high_watermark = 0
        self.evictions = 0
        self.evicted_bytes = 0

    def update(self, staged: StagedTable, table: str) -> int:
        total, by_column, by_role, by_device = _measure_staged(staged)
        with self._lock:
            self._entries[staged.token] = {
                "table": table,
                "segments": list(staged.segment_names),
                "bytes": total,
                "columns": by_column,
                "roles": by_role,
                "devices": by_device,
            }
            now = sum(e["bytes"] for e in self._entries.values())
            if now > self.high_watermark:
                self.high_watermark = now
        return total

    def drop(self, staged: StagedTable) -> None:
        with self._lock:
            entry = self._entries.pop(staged.token, None)
            if entry is not None:
                self.evictions += 1
                self.evicted_bytes += entry["bytes"]

    def total_bytes(self) -> int:
        with self._lock:
            return sum(e["bytes"] for e in self._entries.values())

    def table_count(self) -> int:
        with self._lock:
            return len(self._entries)

    def snapshot(self) -> Dict:
        """JSON-safe view served on server status() / /debug/metrics
        and aggregated cluster-wide by the controller /debug/capacity."""
        with self._lock:
            by_table: Dict[str, int] = {}
            by_role: Dict[str, int] = {}
            by_device: Dict[str, int] = {}
            entries = []
            for e in self._entries.values():
                by_table[e["table"]] = by_table.get(e["table"], 0) + e["bytes"]
                for role, n in e["roles"].items():
                    by_role[role] = by_role.get(role, 0) + n
                for dev, n in e.get("devices", {}).items():
                    by_device[dev] = by_device.get(dev, 0) + n
                entries.append(
                    {
                        "table": e["table"],
                        "segments": list(e["segments"]),
                        "bytes": e["bytes"],
                        "columns": dict(e["columns"]),
                        "devices": dict(e.get("devices", {})),
                    }
                )
            return {
                "stagedBytes": sum(e["bytes"] for e in self._entries.values()),
                "highWatermarkBytes": self.high_watermark,
                "stagedTables": len(self._entries),
                "evictions": self.evictions,
                "evictedBytes": self.evicted_bytes,
                "byTable": by_table,
                "byRole": by_role,
                "byDevice": by_device,
                "entries": entries,
            }


LEDGER = StagingLedger()


class TransferStats:
    """Cumulative host<->device transfer accounting — the measured-
    bandwidth half of the utilization plane (the staging ledger above
    tracks what is RESIDENT; this tracks what MOVED).  H2D marks come
    from the staging paths (``get_staged`` cache misses / role
    augmentation) and the batched query-input upload
    (``to_device_inputs``); D2H marks come from the packed result fetch
    (``engine/packing.py``) and the executor's raw-output fallback.
    Per-process, like the staging cache it instruments."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.h2d_bytes = 0
        self.h2d_transfers = 0
        self.d2h_bytes = 0
        self.d2h_transfers = 0
        # process identity in every snapshot: servers sharing a process
        # (in-process clusters, the chaos harness) all report THIS one
        # counter, and fleet rollups dedupe on the token instead of
        # multiply-counting the same bytes per server
        self.process_token = f"{os.getpid():x}-{id(self):x}"

    def record_h2d(self, nbytes: int) -> None:
        if nbytes <= 0:
            return
        with self._lock:
            self.h2d_bytes += int(nbytes)
            self.h2d_transfers += 1

    def record_d2h(self, nbytes: int) -> None:
        if nbytes <= 0:
            return
        with self._lock:
            self.d2h_bytes += int(nbytes)
            self.d2h_transfers += 1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "h2dBytes": self.h2d_bytes,
                "h2dTransfers": self.h2d_transfers,
                "d2hBytes": self.d2h_bytes,
                "d2hTransfers": self.d2h_transfers,
                "processToken": self.process_token,
            }


TRANSFERS = TransferStats()


def _table_of(segments: Sequence[ImmutableSegment]) -> str:
    meta = getattr(segments[0], "metadata", None) if segments else None
    return getattr(meta, "table_name", "") or ""


# ---------------------------------------------------------------------------
# Staging cache: segments are immutable, so staging is reusable per
# (segment set, column set) — the HBM-residency analog of the reference
# keeping segments mmap'd between queries.
# ---------------------------------------------------------------------------

_stage_cache: Dict[Tuple, StagedTable] = {}
# per-key locks serialize staging (cache miss) and role-array
# augmentation so two concurrent queries over the same segments don't
# both materialize + transfer multi-GB column sets (ADVICE r1:
# redundant work + transient 2x HBM, not a race); distinct tables
# stage concurrently and cache hits never wait on a cold stage
_locks_guard = threading.Lock()
_key_locks: Dict[Tuple, "threading.Lock"] = {}
# cache-membership guard: insert/evict/clear AND the paired ledger
# bookkeeping happen atomically under this lock (per-key locks don't
# order distinct keys, so a size-cap clear racing another key's insert
# could otherwise iterate a mutating dict or strand a ledger entry for
# a table the cache no longer holds)
_cache_guard = threading.Lock()


def _lock_for(key: Tuple) -> "threading.Lock":
    with _locks_guard:
        lock = _key_locks.get(key)
        if lock is None:
            if len(_key_locks) > 256:
                _key_locks.clear()
            lock = _key_locks.setdefault(key, threading.Lock())
        return lock


def placement_key(sharding) -> Optional[Tuple]:
    """Hashable identity of a staging placement: None for default
    single-device placement, else the sharding's device set + spec.
    Part of the staging-cache key, so the same segments staged onto two
    chip groups are two entries — one group's arrays can never alias
    another group's dispatch (the sharded extension of the PR 3
    staging-token invariant)."""
    if sharding is None:
        return None
    try:
        ids = tuple(sorted(getattr(d, "id", -1) for d in sharding.device_set))
    except Exception:
        ids = (repr(sharding),)
    return (type(sharding).__name__, ids, str(getattr(sharding, "spec", "")))


def get_staged(
    segments: Sequence[ImmutableSegment],
    column_names: Sequence[str],
    pad_segments_to: int = 0,
    raw_columns: Sequence[str] = (),
    gfwd_columns: Sequence[str] = (),
    hll_columns: Sequence[str] = (),
    ctx=None,
    skip_base_columns: Sequence[str] = (),
    sharding=None,
    bsi_columns: Sequence[str] = (),
    bsiv_columns: Sequence[str] = (),
    pin: bool = False,
    hll_timer=None,
) -> StagedTable:
    """Cached staging. The cache key covers only the base arrays; role
    arrays (raw/gfwd/hll streams) are attached to the cached
    StagedTable on demand, so queries differing only in roles share one
    HBM copy of the base columns.  A column staged stream-only
    (skip_base_columns) gets its base arrays backfilled if a later
    query needs them (e.g. a filter arrives on a former agg-only
    column).  ``sharding`` places the segment axis across a chip group
    (mesh execution) and is part of the cache identity.

    Residency (engine/residency.py): a miss first checks the warm/cold
    tiers — a demoted table promotes back via pure device_put of its
    packed snapshot, zero re-encode — and every insert is registered
    with the residency manager, which enforces the HBM byte/entry caps
    by demoting the coldest unpinned tables instead of the old
    clear-everything size cap.  ``pin=True`` refcounts the staged
    table's token so tier demotion can never race this query's launch;
    the caller MUST ``RESIDENCY.unpin(st.token)`` when done.
    ``hll_timer``: the caller's ``phase.hllDerive``, which times what
    deriving a column's per-row HLL streams costs here (_hll_streams)."""
    from pinot_tpu.engine.residency import RESIDENCY
    # identity component: (name, claimed crc, instance token).  The
    # token (segment/immutable.py) is what makes a re-loaded copy of the
    # same segment a guaranteed MISS — name+crc alone would alias a
    # clean re-fetch onto arrays staged from a quarantined corrupt load,
    # even mid-flight (no eviction race can resurrect the old entry:
    # new instances simply never produce the old key).
    key = (
        tuple(
            (s.segment_name, s.metadata.crc, s.staging_token) for s in segments
        ),
        tuple(sorted(column_names)),
        pad_segments_to,
        placement_key(sharding),
    )
    with _lock_for(key):
        st = _stage_cache.get(key)
        if st is None:
            # warm/cold promotion first: a demoted table's packed
            # snapshot restores with pure device_puts — one read, zero
            # re-encode (sharded placements are drop-only, never
            # snapshotted, so they always re-stage from source)
            snap = RESIDENCY.take_resident(key) if sharding is None else None
            promoted = snap is not None
            if promoted:
                from pinot_tpu.engine.residency import restore_staged

                st = restore_staged(snap)
                # backfill any role/base arrays this query needs that
                # the resident copy was demoted without
                _augment_staged(
                    st,
                    segments,
                    raw_columns,
                    gfwd_columns,
                    hll_columns,
                    ctx,
                    base_columns=[
                        c
                        for c in column_names
                        if c not in set(skip_base_columns)
                    ],
                    bsi_columns=bsi_columns,
                    bsiv_columns=bsiv_columns,
                    hll_timer=hll_timer,
                )
            else:
                st = stage_segments(
                    segments,
                    sorted(column_names),
                    pad_segments_to=pad_segments_to,
                    raw_columns=raw_columns,
                    gfwd_columns=gfwd_columns,
                    hll_columns=hll_columns,
                    ctx=ctx,
                    skip_base_columns=skip_base_columns,
                    sharding=sharding,
                    bsi_columns=bsi_columns,
                    bsiv_columns=bsiv_columns,
                    hll_timer=hll_timer,
                )
            table = _table_of(segments)
            with _cache_guard:
                _stage_cache[key] = st
                staged_bytes = LEDGER.update(st, table)
                RESIDENCY.note_hot(
                    key,
                    st,
                    table,
                    staged_bytes,
                    demotable=sharding is None,
                    promoted=promoted,
                )
            # a cold stage IS one H2D transfer burst of the measured
            # array bytes (the utilization plane's upload accounting);
            # a promotion's device_puts are the same physical transfer
            TRANSFERS.record_h2d(staged_bytes)
            if promoted:
                # async promotion ahead of dispatch: lift the table's
                # remaining cold entries to warm in the background
                RESIDENCY.prefetch_siblings(key, table)
            # cap enforcement AFTER insert (outside _cache_guard): the
            # coldest unpinned residents demote to warm/cold instead of
            # the old clear-everything size cap
            RESIDENCY.enforce(exclude_tokens=(st.token,))
        else:
            attached = _augment_staged(
                st,
                segments,
                raw_columns,
                gfwd_columns,
                hll_columns,
                ctx,
                base_columns=[
                    c for c in column_names if c not in set(skip_base_columns)
                ],
                bsi_columns=bsi_columns,
                bsiv_columns=bsiv_columns,
                hll_timer=hll_timer,
            )
            RESIDENCY.touch(key)
            if attached:
                # re-measure (augmentation attached arrays) ONLY while
                # still cache-resident: a concurrent demotion already
                # counted this table out, and updating after that would
                # strand a ledger entry nothing will ever drop.  A
                # plain hit (attached == 0 — the overwhelmingly common
                # case) walks no arrays at all on this path.
                with _cache_guard:
                    if _stage_cache.get(key) is st:
                        nb = LEDGER.update(st, _table_of(segments))
                        RESIDENCY.set_bytes(key, nb)
                # augmentation's newly-attached role arrays ARE the H2D
                # delta (zero on a plain cache hit — no phantom transfers)
                TRANSFERS.record_h2d(attached)
        if pin:
            # refcount BEFORE releasing the key lock: demotion checks
            # pins under the manager lock, and an unpinned window here
            # could demote the table between staging and launch
            RESIDENCY.pin(st.token)
    return st


def _augment_staged(
    st: StagedTable,
    segments: Sequence[ImmutableSegment],
    raw_columns: Sequence[str],
    gfwd_columns: Sequence[str],
    hll_columns: Sequence[str],
    ctx,
    base_columns: Sequence[str] = (),
    bsi_columns: Sequence[str] = (),
    bsiv_columns: Sequence[str] = (),
    hll_timer=None,
) -> int:
    """Attach missing role arrays to an already-staged table.  Returns
    the bytes newly uploaded (0 on a plain hit) so the caller can record
    the exact H2D delta without re-walking every staged array."""
    attached = 0
    fdt = config.np_float_dtype()
    S, n_pad = st.num_segments, st.n_pad
    # augmentation lands on the SAME placement the base staging used:
    # a default-device role array attached to a chip-group table would
    # force a reshard on every launch
    put = (
        (lambda x: jax.device_put(x, st.sharding))
        if st.sharding is not None
        else jnp.asarray
    )
    for name in base_columns:
        # backfill base arrays a stream-only staging skipped
        sc = st.columns.get(name)
        if sc is None or not sc.single_value or sc.fwd is not None:
            continue
        cols = [seg.column(name) for seg in segments]
        sc.fwd = put(
            _stack_fwd(cols, S, n_pad, config.index_dtype(sc.card_pad))
        )
        attached += int(sc.fwd.nbytes)
        if sc.is_numeric and sc.dict_vals is None:
            sc.dict_vals = put(
                _stack_dict_vals(cols, S, sc.card_pad, fdt)
            )
            attached += int(sc.dict_vals.nbytes)
    for name in raw_columns:
        sc = st.columns.get(name)
        if sc is None or sc.raw is not None or not sc.is_numeric or not sc.single_value:
            continue
        raw = np.zeros((S, n_pad), dtype=fdt)
        for i, seg in enumerate(segments):
            c = seg.column(name)
            vals = np.asarray(c.dictionary.values, dtype=fdt)
            raw[i, : c.fwd.size] = vals[c.fwd]
        sc.raw = put(raw)
        attached += int(sc.raw.nbytes)
    for name in gfwd_columns:
        sc = st.columns.get(name)
        if sc is None or sc.gfwd is not None or not sc.single_value or ctx is None:
            continue
        gdt = config.index_dtype(config.pad_card(ctx.column(name).global_cardinality))
        gf = np.zeros((S, n_pad), dtype=gdt)
        remaps = ctx.column(name).remaps
        for i, seg in enumerate(segments):
            c = seg.column(name)
            gf[i, : c.fwd.size] = remaps[i][c.fwd]
        sc.gfwd = put(gf)
        attached += int(sc.gfwd.nbytes)
    for name in raw_columns:
        sc = st.columns.get(name)
        if (
            sc is None
            or sc.mv_raw is not None
            or sc.single_value
            or not sc.is_numeric
            or sc.mv is None
        ):
            continue
        mvr = np.zeros((S, n_pad, sc.mv_pad), dtype=fdt)
        for i, seg in enumerate(segments):
            c = seg.column(name)
            vals = np.asarray(c.dictionary.values, dtype=fdt)
            _csr_scatter(vals[c.mv_values], c.mv_offsets, mvr[i])
        sc.mv_raw = put(mvr)
        attached += int(sc.mv_raw.nbytes)
    for name in hll_columns:
        sc = st.columns.get(name)
        if sc is None or sc.hll_bucket is not None or not sc.single_value:
            continue
        hb, hr = _hll_streams([seg.column(name) for seg in segments], S, n_pad, hll_timer)
        # rho FIRST: readers holding this cached table guard on
        # hll_bucket, so both must be visible once bucket is
        sc.hll_rho = put(hr)
        sc.hll_bucket = put(hb)
        attached += int(sc.hll_rho.nbytes) + int(sc.hll_bucket.nbytes)
    for name in bsi_columns:
        sc = st.columns.get(name)
        if sc is None or sc.bsi is not None or not sc.single_value:
            continue
        cols = [seg.column(name) for seg in segments]
        sc.bsi_width = bsi_filter_width(cols)
        sc.bsi = put(_bsi_planes(cols, S, n_pad, sc.bsi_width))
        attached += int(sc.bsi.nbytes)
    for name in bsiv_columns:
        sc = st.columns.get(name)
        if (
            sc is None
            or sc.bsiv is not None
            or not sc.single_value
            or not sc.is_numeric
        ):
            continue
        cols = [seg.column(name) for seg in segments]
        spec = bsiv_value_spec(cols)
        if spec is None:
            continue
        width, vmins = spec
        planes = put(_bsiv_planes(cols, S, n_pad, width, vmins))
        # width/vmin metadata FIRST: readers holding this cached table
        # guard on bsiv, so the scalars must be visible once it is
        sc.bsiv_width, sc.bsiv_min = width, vmins
        sc.bsiv = planes
        attached += int(sc.bsiv.nbytes)
    return attached


def _hll_streams(cols, S: int, n_pad: int, timer=None):
    """Per-row HLL (register index, rank) uint8 streams, computed
    host-side per dictionary entry then fanned out through the forward
    index — the kernel reads the streams instead of gathering
    per-dictId tables on device.  ``timer`` (``phase.hllDerive``) and
    the ``pinot:hllDerive`` annotation time the hashing of the
    dictionaries and the fan-out; no span: ``staging`` stays the leaf."""
    from pinot_tpu.engine.hll import dictionary_tables
    from pinot_tpu.utils.trace import boundary

    with boundary("hllDerive", None, timer):
        hb = np.zeros((S, n_pad), dtype=np.uint8)
        hr = np.zeros((S, n_pad), dtype=np.uint8)
        for i, c in enumerate(cols):
            bt, rt = dictionary_tables(c.dictionary)
            # one gather through the forward index for the two tables
            both = (bt.astype(np.uint16) << 8 | rt)[c.fwd]
            hb[i, : c.fwd.size] = both >> 8
            hr[i, : c.fwd.size] = both & 0xFF
    return hb, hr


def clear_staging_cache() -> None:
    """Drop all staged tables AND their residency entries (every tier):
    callers clear to force genuine re-staging — a retained warm copy
    would silently turn the next stage into a promotion."""
    from pinot_tpu.engine.residency import RESIDENCY

    with _cache_guard:
        for st in list(_stage_cache.values()):
            LEDGER.drop(st)
        _stage_cache.clear()
    RESIDENCY.reset()


def evict_staged_segment(segment_name: str) -> int:
    """Drop every cached staged table containing ``segment_name`` — the
    quarantine path's HBM hygiene.  Correctness does not depend on this
    (the per-instance staging token already guarantees a re-loaded
    segment misses the cache); eviction just releases the quarantined
    copy's device arrays instead of waiting for the size-cap clear.
    Returns the number of cache entries dropped."""
    from pinot_tpu.engine.residency import RESIDENCY

    with _cache_guard:
        victims = []
        for key in list(_stage_cache):
            if any(e[0] == segment_name for e in key[0]):
                victims.append(key)
        for key in victims:
            st = _stage_cache.pop(key, None)
            if st is not None:
                LEDGER.drop(st)
    # residency hygiene runs on the SAME contract: the quarantined
    # copy's warm/cold snapshots must not survive either (a re-loaded
    # segment mints new tokens, so they could never be promoted — but
    # they would pin host RAM/disk for nothing)
    RESIDENCY.drop_segment(segment_name)
    return len(victims)


def to_device_inputs(tree, sharding=None):
    """Convert a numpy pytree (query inputs) to device arrays — the one
    converter production and benchmarks share.  All ndarray leaves ride
    ONE batched ``jax.device_put``: per-leaf puts each pay a host->
    device dispatch; the batched form coalesces the transfer.  ``sharding`` places every leaf across
    a chip group (mesh execution — query inputs lead with the segment
    axis, like the staged columns they join)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    idx = [i for i, leaf in enumerate(leaves) if isinstance(leaf, np.ndarray)]
    if idx:
        TRANSFERS.record_h2d(sum(leaves[i].nbytes for i in idx))
        batch = [leaves[i] for i in idx]
        if sharding is not None:
            put = jax.device_put(batch, [sharding] * len(batch))
        else:
            put = jax.device_put(batch)
        for i, v in zip(idx, put):
            leaves[i] = v
    return jax.tree_util.tree_unflatten(treedef, leaves)


def segment_arrays(staged: StagedTable, needed) -> Dict[str, jnp.ndarray]:
    """Assemble the kernel's ``seg`` pytree for the given columns.

    Row validity ships as the per-segment ``num_docs`` scalar (the
    kernel compares against an iota); the materialized ``valid`` mask is
    only sent when no row-shaped column array exists to take the row
    count from (e.g. ``SELECT COUNT(*)`` with no filter).
    """
    arrays: Dict[str, jnp.ndarray] = {}
    has_rows = False
    for name in needed:
        col = staged.columns.get(name)
        if col is None:
            continue
        if col.fwd is not None:
            arrays[f"{name}.fwd"] = col.fwd
            has_rows = True
        if col.mv is not None:
            arrays[f"{name}.mv"] = col.mv
            arrays[f"{name}.mvc"] = col.mv_counts
            has_rows = True
        if col.dict_vals is not None:
            arrays[f"{name}.dict"] = col.dict_vals
        if col.raw is not None:
            arrays[f"{name}.raw"] = col.raw
            has_rows = True
        if col.gfwd is not None:
            arrays[f"{name}.gfwd"] = col.gfwd
            has_rows = True
        if col.hll_bucket is not None:
            arrays[f"{name}.hllb"] = col.hll_bucket
            arrays[f"{name}.hllr"] = col.hll_rho
            has_rows = True
        if col.mv_raw is not None:
            arrays[f"{name}.mvraw"] = col.mv_raw
            has_rows = True
    if has_rows:
        arrays["num_docs"] = staged.num_docs_arr
    else:
        arrays["valid"] = staged.valid
    return arrays
