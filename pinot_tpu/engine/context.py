"""Table dictionary context: global (query-level) dictionaries + per-segment
remaps.

Dictionaries are per-segment in the reference, and cross-segment group-by
merge happens by *materialized value* in Java HashMaps
(``MCombineGroupByOperator.java:152``).  That doesn't vectorize.  The
TPU-native design instead builds a **table-level global dictionary** per
column (the sorted union of the segments' dictionaries) plus one small
``remap: int32[segment_card]`` array per (segment, column) translating
local dictIds to global ids.  Group keys, distinct-count presence vectors
and percentile histograms are then indexed in the *global* id space —
identical across segments — so cross-segment (and cross-chip) merge is a
plain elementwise reduction (``psum``-able over ICI), with group-key
materialization a single host-side lookup at reduce time.

Contexts are cached per (table, segment-set fingerprint): segments are
immutable, so remaps never change for a sealed segment.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from pinot_tpu.common.schema import DataType
from pinot_tpu.segment.dictionary import Dictionary
from pinot_tpu.segment.immutable import ImmutableSegment


@dataclass
class GlobalColumn:
    """Global dictionary + per-segment remap arrays for one column."""

    name: str
    stored_type: DataType
    global_dict: Dictionary
    # remaps[i][local_dict_id] -> global_dict_id  (int32, len = segment card)
    remaps: List[np.ndarray]

    @property
    def global_cardinality(self) -> int:
        return self.global_dict.cardinality


class TableContext:
    """Global dictionaries for one set of segments (one query's scope)."""

    def __init__(self, segments: Sequence[ImmutableSegment]):
        self.segments = list(segments)
        self._columns: Dict[str, GlobalColumn] = {}
        # the serving role's ``phase.globalDictBuild`` timer (the executor
        # hands it to get_table_context), None for a context nobody times
        self.build_timer = None

    def column(self, name: str) -> GlobalColumn:
        gc = self._columns.get(name)
        if gc is None:
            # a column's union and remaps are built once a segment set,
            # by whichever query first asks for its global ids: timer and
            # ``pinot:globalDictBuild`` annotation, no span (it falls in
            # the executor's ``staging`` stretch, which stays the leaf)
            from pinot_tpu.utils.trace import boundary

            with boundary("globalDictBuild", None, self.build_timer):
                gc = self._build(name)
            self._columns[name] = gc
        return gc

    def _build(self, name: str) -> GlobalColumn:
        dicts = [seg.column(name).dictionary for seg in self.segments]
        stored = dicts[0].stored_type
        if stored == DataType.STRING:
            # every dictionary is sorted, so a stable sort of them laid
            # end to end merges their runs (numpy's timsort: a few
            # comparisons a value, where a hash and a look-up an entry
            # cost seven times as much at 12 dictionaries of 783,000
            # phrases); a value's global id is the count of run heads up
            # to it, scattered back to where it came from
            values = np.concatenate([d.value_array() for d in dicts])
            order = np.argsort(values, kind="stable")
            merged = values[order]
            head = np.ones(merged.size, dtype=bool)
            head[1:] = merged[1:] != merged[:-1]
            gids = np.empty(merged.size, dtype=np.int32)
            gids[order] = np.cumsum(head, dtype=np.int32) - 1
            gdict = Dictionary(stored, merged[head].tolist())
            remaps = np.split(gids, np.cumsum([len(d) for d in dicts])[:-1])
        else:
            union = np.unique(np.concatenate([np.asarray(d.values) for d in dicts]))
            gdict = Dictionary(stored, union)
            remaps = [
                np.searchsorted(union, np.asarray(d.values)).astype(np.int32) for d in dicts
            ]
        return GlobalColumn(name=name, stored_type=stored, global_dict=gdict, remaps=remaps)


_context_cache: Dict[Tuple[str, ...], TableContext] = {}


def get_table_context(segments: Sequence[ImmutableSegment], build_timer=None) -> TableContext:
    # (name, crc, instance token): the token makes a re-loaded segment
    # (quarantine re-fetch) miss — a context built from a corrupt load's
    # dictionaries must never serve the clean copy (see engine/device.py)
    key = tuple((s.segment_name, s.metadata.crc, s.staging_token) for s in segments)
    ctx = _context_cache.get(key)
    if ctx is None:
        ctx = TableContext(segments)
        if len(_context_cache) > 64:
            _context_cache.clear()
        _context_cache[key] = ctx
    if build_timer is not None and ctx.build_timer is None:
        ctx.build_timer = build_timer
    return ctx
