"""Per-instance query executor: segments + BrokerRequest -> IntermediateResult.

The ``ServerQueryExecutorV1Impl.processQuery`` analog
(``core/query/executor/ServerQueryExecutorV1Impl.java:88``):
prune -> stage -> plan -> run compiled kernel -> finalize partials.

Unlike the reference's per-segment operator trees + combine thread pool,
ALL segments execute in one vmapped XLA program with the cross-segment
merge fused in (see ``kernel.py``); this host class only prepares inputs
and converts device outputs to mergeable ``IntermediateResult`` partials.
"""
from __future__ import annotations

import logging
import math
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu.common.request import BrokerRequest, group_sort_ascending
from pinot_tpu.common.schema import DataType
from pinot_tpu.common.values import render_value
from pinot_tpu.engine import config, ladder
from pinot_tpu.engine.context import TableContext, get_table_context
from pinot_tpu.engine.device import get_staged
from pinot_tpu.engine.plan import StaticPlan
from pinot_tpu.engine.pruner import prune_segments, scanned_segments
from pinot_tpu.engine.results import (
    AggPartial,
    AvgPartial,
    CountPartial,
    DistinctPartial,
    HistogramPartial,
    HllPartial,
    IntermediateResult,
    MaxPartial,
    MinMaxRangePartial,
    MinPartial,
    SumPartial,
)
from pinot_tpu.segment.immutable import ImmutableSegment
from pinot_tpu.utils.trace import boundary, current_trace, measured, phases

logger = logging.getLogger(__name__)

# bounds of the prepared-query memo, which is host memory: its entries,
# and the bytes of the tables they hold (query inputs, block ids, the
# postings hand-off's match tables).  The least recently asked goes first.
_PREPARED_ENTRIES = 256
_PREPARED_BYTES = 64 << 20


def _settings_fence() -> Tuple[Tuple[str, str], ...]:
    """Every ``PINOT_TPU_*`` setting of the moment, part of a prepared
    query's key: the ladder reads a dozen of them on every query
    (``INVINDEX``, ``BITSLICED``, ``ZONEMAP``, ``ZONE_BLOCK``,
    ``CHUNK_ROWS``, the tier cost model's) and a verdict derived under
    one value must not answer under another."""
    return tuple(sorted((k, os.environ[k]) for k in os.environ if k.startswith("PINOT_TPU_")))


def _table_bytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree) if isinstance(leaf, np.ndarray))


def _check_expression_columns(request: BrokerRequest, seg: ImmutableSegment) -> None:
    """An aggregate's compound expression reads single-value numeric
    columns: anything else is refused by name, here where the schema is
    known (the parser refuses what the text alone shows)."""
    from pinot_tpu.pql.parser import PqlParseError

    for a in request.aggregations:
        if a.expr is None:
            continue
        for c in a.columns:
            if c not in seg.columns:
                continue  # the pruner drops a segment that lacks a column the query names
            meta = seg.column(c).metadata
            if not meta.single_value:
                raise PqlParseError(
                    f"an expression over a multi-value column is not supported "
                    f"({c!r} in {a.function}({a.column}))"
                )
            if meta.data_type.stored_type == DataType.STRING:
                raise PqlParseError(
                    f"an expression over a column that is not numeric is not supported "
                    f"({c!r} in {a.function}({a.column}))"
                )


class _Derived:
    """Derivations kept by name: ``once(name, derive)`` derives at the
    first ask and answers every later one with that value.  Two queries
    of one key that race derive the same value twice, and either stands:
    every derivation kept here is a function of the key alone."""

    __slots__ = ("values",)

    def __init__(self) -> None:
        self.values: Dict[str, Any] = {}

    def once(self, name: str, derive: Callable[[], Any]) -> Any:
        try:
            return self.values[name]
        except KeyError:
            value = self.values[name] = derive()
            return value


class _Prepared(_Derived):
    """What the ladder derives on the host from (the query's text, the
    identity of the segments served, the placement, the settings) alone,
    kept by the executor under that key so that a repeated query derives
    none of it again.  By name, in the order ``_execute_tiers`` asks:

    ``scope``     total docs, the columns to stage, the selection's
                  columns, the segment padding
    ``scanned``   the positions of the segments the filter can match
                  (``pruner.scanned_segments``): what every tier works
                  over
    ``postings``  ``index_path_decision``'s hand-off, its postings held
                  weakly (``invindex_path.hold_state``; (): declined)
    ``bitsliced`` ``bitsliced_decision``'s hand-off (None: declined)
    ``forcedHost`` ``plan_forced_host``
    ``roles``     raw / gfwd / hll role columns and the skip-base set

    and in ``device``, derived against the staged table whose token it
    names (a table staged anew after a demotion is another table):

    ``plan``      the ``StaticPlan``, its ``plan_digest``, the poison key
    ``inputs``    ``q_np``, its digest, the block ids and the rows they scan
    ``batch``     the batch spec's signature and member cap

    It holds no device array and no ``StagedTable``, no segment, table
    context or postings: an entry must keep alive neither a demoted
    table's HBM nor an unloaded segment's host memory."""

    __slots__ = ("device", "nbytes")

    def __init__(self) -> None:
        super().__init__()
        self.device: Optional[_PreparedDevice] = None
        self.nbytes = 0  # as the memo last counted it


class _PreparedDevice(_Derived):
    __slots__ = ("token",)

    def __init__(self, token: int) -> None:
        super().__init__()
        self.token = token


class _Use:
    """One query's use of the memo: the entry, its key (None: not kept)
    and what the query found, ``hit``, ``miss`` or ``stale``."""

    __slots__ = ("prepared", "key", "outcome")

    def __init__(self, prepared: _Prepared, key, outcome: str) -> None:
        self.prepared, self.key, self.outcome = prepared, key, outcome


class _PairsState:
    """Host-side index over a compacted (group slot, valueId) pair
    buffer from the sort reduce (kernel.py ``_reduce_distinct_pairs``):
    per-slot distinct counts for trim ordering, per-slot gid slices for
    DistinctPartial building, and per-pair OCCURRENCE counts (run
    lengths off the carried start positions) for exact percentile
    histograms."""

    def __init__(self, state, capacity: int) -> None:
        slots, gids, starts, n, total_valid = state
        n = int(n)
        # the device reduce's stable unique-first compaction leaves the
        # first n entries already sorted by (slot, gid) — no host re-sort
        self._slots_sorted = np.asarray(slots)[:n].astype(np.int64)
        self._gids_sorted = np.asarray(gids)[:n]
        self._pair_counts = np.diff(
            np.append(np.asarray(starts)[:n].astype(np.int64), int(total_valid))
        )
        self._bounds = np.searchsorted(
            self._slots_sorted, np.arange(capacity + 1, dtype=np.int64)
        )
        self.counts = np.diff(self._bounds).astype(np.float64)

    def gids_for(self, key: int) -> np.ndarray:
        a, b = self._bounds[key], self._bounds[key + 1]
        return self._gids_sorted[a:b]

    def gid_counts_for(self, key: int):
        """(gids ascending, occurrence counts) for one group slot."""
        a, b = self._bounds[key], self._bounds[key + 1]
        return self._gids_sorted[a:b], self._pair_counts[a:b]

    def gids_rows_for(self, keys: np.ndarray):
        """Batched slice gather: (gids, rows) where ``rows[i]`` is the
        position in ``keys`` whose slot owns ``gids[i]`` — the input
        shape ``_regs_from_gids`` batch-decodes."""
        if not keys.size:
            empty = np.zeros(0, dtype=np.int64)
            return empty, empty
        lo, hi = self._bounds[keys], self._bounds[keys + 1]
        counts = hi - lo
        total = int(counts.sum())
        # vectorized ragged gather: per-element position minus its own
        # slice's cumulative start, plus the slice's lo
        offs = np.concatenate(([0], np.cumsum(counts)[:-1])).astype(np.int64)
        take = np.arange(total) - np.repeat(offs, counts) + np.repeat(lo, counts)
        return self._gids_sorted[take], np.repeat(np.arange(keys.size), counts)

    def percentiles_for(self, keys: np.ndarray, p: int, vals: np.ndarray) -> np.ndarray:
        """Vectorized exact percentile per requested group slot from the
        sparse (gid, count) runs — mirrors the dense-histogram math."""
        csum = np.concatenate([[0], np.cumsum(self._pair_counts)])
        lo, hi = self._bounds[keys], self._bounds[keys + 1]
        n = csum[hi] - csum[lo]
        idx = np.minimum((n * p / 100.0).astype(np.int64), np.maximum(n - 1, 0))
        # global cumulative position of each group's idx-th element
        pos = np.searchsorted(csum[1:], csum[lo] + idx, side="right")
        pos = np.minimum(pos, self._gids_sorted.size - 1) if self._gids_sorted.size else pos
        gid = self._gids_sorted[pos] if self._gids_sorted.size else np.zeros_like(pos)
        out = np.where(n > 0, vals[np.minimum(gid, vals.size - 1)], -np.inf)
        return out


def _regs_from_gids(
    gids: np.ndarray, rows: np.ndarray | None = None, n_rows: int = 0
) -> np.ndarray:
    """Decode packed (bucket*64 + rho) pair gids into HLL registers
    (max rho per bucket) — the one place the gid packing is interpreted
    on host.  Without ``rows``: one uint8[HLL_M] register array.  With
    ``rows`` (same shape as ``gids``) and ``n_rows``: a batched
    uint8[n_rows, HLL_M] decode, one register array per row."""
    from pinot_tpu.utils.npgroup import scatter_max_2d

    g = gids.astype(np.int64)
    rho = (g & 63).astype(np.uint8)
    if rows is None:
        return scatter_max_2d(np.zeros(g.size, np.int64), 1, g >> 6, rho, config.HLL_M)[0]
    return scatter_max_2d(rows, n_rows, g >> 6, rho, config.HLL_M)


def _regs_from_value_gids(
    ctx, column: str, gids: np.ndarray, rows: np.ndarray | None = None, n_rows: int = 0
) -> np.ndarray:
    """HLL registers from GLOBAL dictionary value ids (the
    hll_from_presence finalize: registers depend only on the distinct
    value set).  Batched like ``_regs_from_gids`` when ``rows`` given."""
    from pinot_tpu.engine import hll as hll_mod
    from pinot_tpu.utils.npgroup import scatter_max_2d

    bt, rt = hll_mod.dictionary_tables(ctx.column(column).global_dict)
    g = np.asarray(gids, dtype=np.int64)
    ok = g < bt.size  # padded/overflow slots carry no value
    g = g[ok]
    if rows is None:
        return scatter_max_2d(np.zeros(g.size, np.int64), 1, bt[g], rt[g], config.HLL_M)[0]
    return scatter_max_2d(np.asarray(rows)[ok], n_rows, bt[g], rt[g], config.HLL_M)


def _hist_partial(gdict, gids, cnts, p: int) -> "HistogramPartial":
    counts = {
        float(gdict.get(int(g))): int(c)
        for g, c in zip(gids, cnts)
        if g < gdict.cardinality
    }
    return HistogramPartial(counts, percentile=p)


class QueryExecutor:
    """Executes queries over a set of immutable segments on this host's
    device(s).

    With ``mesh`` set, the stacked segment axis is sharded over the
    device mesh and cross-chip merge rides ICI collectives
    (``pinot_tpu.parallel.multichip``); without it, the vmapped
    single-device kernel runs.
    """

    def __init__(self, mesh=None, metrics=None, lane=None, lanes=None) -> None:
        self.mesh = mesh
        # mesh execution plane (engine/mesh.py + dispatch.LaneGroup):
        # with a lane group set, every query is routed to a chip-group
        # lane by its literal-erased plan-shape digest — staging,
        # kernel compilation, and the launch all happen against THAT
        # group's mesh.  ``mesh``/``lane`` stay as the single-lane
        # (pre-mesh) configuration for standalone executors.
        self.lanes = lanes
        if lanes is not None and lane is None:
            lane = lanes.primary
        if metrics is None:
            # the registry is the single source of truth for phase
            # timers AND the self-healing counters (heal.*), so a
            # standalone executor gets a private one instead of
            # branching on None at every mark
            from pinot_tpu.utils.metrics import ServerMetrics

            metrics = ServerMetrics("executor")
        self.metrics = metrics  # MetricsRegistry: per-phase timers + heal.*
        # pre-register the self-healing series so /metrics exposes them
        # at zero from process start (a scrape gap is not "no failures")
        for name in self._HEAL_COUNTERS:
            metrics.meter(f"heal.{name}")
        # three-stage serving pipeline (engine/dispatch.py): with a
        # DeviceLane set, kernel launches leave this worker thread and
        # coalesce with identical in-flight dispatches; without one,
        # launch + fetch run inline (the serial path, byte-identical
        # results — the differential suite holds the two together)
        self.lane = lane
        self._mesh_shardings: Dict[Any, Any] = {}  # mesh id -> (NamedSharding, placement key)
        self._qinput_cache: "OrderedDict[Any, Any]" = OrderedDict()
        self._qinput_cache_bytes = 0
        # the QueryScheduler runs queries on a worker pool; byte
        # accounting must not drift under concurrent misses/evictions
        self._qinput_cache_lock = threading.Lock()
        # the prepared-query memo (_Prepared): key -> entry, least
        # recently asked first; hit + miss + stale = queries that
        # reached the ladder
        self._prepared: "OrderedDict[Any, _Prepared]" = OrderedDict()
        self._prepared_bytes = 0
        self._prepared_lock = threading.Lock()
        for outcome in ("hit", "miss", "stale"):
            metrics.meter(f"plan.prepared.{outcome}")
        # one mark a query by the rung that answered it (_finish_tier)
        self._tier_answered = {t.name: metrics.meter(f"tier.answered.{t.name}") for t in ladder.TIERS}
        metrics.gauge("plan.prepared.entries").set_fn(lambda: len(self._prepared))
        for place in ("device", "host"):  # both from the start: a share needs the one that stays 0
            metrics.meter(f"agg.expr.{place}")
        # self-healing state: device failures fail over to the host
        # path, and a (plan digest, segment set) that keeps failing on
        # device is quarantined so repeat offenders skip the device
        # entirely (engine/dispatch.py classification contract).
        # Counters live in the metrics registry (heal.*) — ONE source
        # of truth for status(), /metrics, and /debug/metrics.
        self._heal_lock = threading.Lock()
        # poison key -> (reason, expiry): quarantine entries carry a TTL
        # (PINOT_TPU_POISON_TTL_S, default 300s) so a plan poisoned by a
        # transient burst is eventually re-admitted to the device — the
        # worst case of a wrong verdict is one more failover cycle, the
        # worst case of a permanent verdict is serving a healthy plan
        # from the slow host path forever
        self._poisoned: Dict[Any, Tuple[str, float]] = {}
        self._poison_ttl_s = float(os.environ.get("PINOT_TPU_POISON_TTL_S", "300"))
        # audit-plane quarantine flag: True once any ("audit", digest,
        # tier) key entered the poison map, so the serving path only
        # pays a plan-digest derivation when a quarantine could apply
        self._has_audit_poison = False

    # -- self-healing bookkeeping --------------------------------------
    _HEAL_COUNTERS = (
        "deviceFailures",
        "deviceRetries",
        "hostFailovers",
        "poisonSkips",
        # allocation-failure heals: RESOURCE_EXHAUSTED launches that
        # recovered by demoting the coldest residents and retrying
        # (engine/residency.py) — never poisoned, host only as last
        # resort
        "resourceExhausted",
    )

    def _heal_mark(self, name: str, **tags) -> None:
        self.metrics.meter(f"heal.{name}").mark()
        tr = current_trace()
        if tr is not None and tr.enabled:
            tr.event(name, **tags)

    def healing_stats(self) -> Dict[str, int]:
        now = time.monotonic()
        stats = {
            name: self.metrics.meter(f"heal.{name}").count
            for name in self._HEAL_COUNTERS
        }
        with self._heal_lock:
            stats["poisonedPlans"] = sum(
                1 for _, exp in self._poisoned.values() if now < exp
            )
        return stats

    def _is_poisoned(self, key: Any) -> bool:
        with self._heal_lock:
            entry = self._poisoned.get(key)
            if entry is None:
                return False
            if time.monotonic() >= entry[1]:
                self._poisoned.pop(key, None)  # TTL expired: re-admit
                return False
            return True

    def poisoned_entry(self, key: Any) -> Optional[Dict[str, Any]]:
        """Live quarantine record for a (plan digest, segment set) key,
        or None — the EXPLAIN plane's honesty hook: a poisoned plan's
        EXPLAIN must report the host tier it will ACTUALLY serve from,
        not the device tier it would have picked."""
        now = time.monotonic()
        with self._heal_lock:
            entry = self._poisoned.get(key)
            if entry is None or now >= entry[1]:
                return None
            return {"reason": entry[0], "ttlRemainingS": round(entry[1] - now, 3)}

    def _poison(self, key: Any, reason: str) -> None:
        expiry = time.monotonic() + self._poison_ttl_s
        with self._heal_lock:
            self._poisoned[key] = (reason, expiry)
            if len(self._poisoned) > 1024:  # runaway-workload backstop
                self._poisoned.clear()
                self._poisoned[key] = (reason, expiry)

    def clear_poisoned(self) -> None:
        """Ops/test hook: re-admit quarantined plans to the device (a
        rolled-out runtime fix makes old poison verdicts stale)."""
        with self._heal_lock:
            self._poisoned.clear()
        self._has_audit_poison = False

    # -- audit-plane quarantine (utils/audit.py) -----------------------
    def audit_quarantine(self, digest: str, tier: str, reason: str) -> None:
        """Shadow-audit verdict: ``tier`` produced a WRONG answer for
        plan shape ``digest``.  Rides the same TTL'd poison map as the
        device-failure quarantine — the serving path skips the
        quarantined tier for that shape (postings/bitsliced fall
        through to the next tier, device fails over to host) until the
        TTL re-admits it."""
        self._poison(("audit", str(digest), str(tier)), f"audit: {reason}")
        self._has_audit_poison = True
        self._heal_mark("auditQuarantines", tier=tier)

    def audit_quarantined_snapshot(self) -> List[Dict[str, Any]]:
        """Live audit-quarantine entries for ``/debug/audit``."""
        now = time.monotonic()
        out: List[Dict[str, Any]] = []
        with self._heal_lock:
            for key, (reason, exp) in self._poisoned.items():
                if (
                    isinstance(key, tuple)
                    and len(key) == 3
                    and key[0] == "audit"
                    and now < exp
                ):
                    out.append(
                        {
                            "planDigest": key[1],
                            "tier": key[2],
                            "reason": reason,
                            "ttlRemainingS": round(exp - now, 3),
                        }
                    )
        return out

    def _audit_blocked(self, digest: Optional[str], tier: str) -> bool:
        if digest is None:
            return False
        if self._is_poisoned(("audit", digest, tier)):
            self._heal_mark("auditTierSkips", tier=tier)
            return True
        return False

    def _fault_injector(self):
        lane = self.lane
        inj = getattr(lane, "fault_injector", None) if lane is not None else None
        if inj is None and self.lanes is not None:
            for lane in self.lanes.lanes:
                inj = getattr(lane, "fault_injector", None)
                if inj is not None:
                    break
        return inj

    def _finish_tier(
        self, result: IntermediateResult, request: BrokerRequest, tier: str
    ) -> IntermediateResult:
        """Every ``_execute_engine`` exit point: stamp which serving
        tier produced the answer (the audit plane's quarantine key),
        mark it (``tier.answered.<tier>``, one mark a query that the
        ladder answered; a failover's answer marks ``host``) and consult
        the armed wrong-answer injection, if any (chaos tests only —
        production lanes have no fault injector)."""
        result._served_tier = tier
        self._tier_answered[tier].mark()
        inj = self._fault_injector()
        if inj is not None and getattr(inj, "corruption_armed", False):
            from pinot_tpu.engine.plandigest import plan_shape_digest

            delta = inj.check_corrupt(plan_shape_digest(request), tier)
            if delta is not None:
                from pinot_tpu.common.faults import apply_result_corruption

                apply_result_corruption(result, delta)
        return result

    def execute_host_oracle(
        self, segments: Sequence[ImmutableSegment], request: BrokerRequest
    ) -> IntermediateResult:
        """The shadow-audit oracle: ``host_oracle_steps`` run to its end."""
        from pinot_tpu.engine.host_fallback import run_steps

        return run_steps(self.host_oracle_steps(segments, request))

    def host_oracle_steps(
        self, segments: Sequence[ImmutableSegment], request: BrokerRequest
    ):
        """The oracle's pass as a generator: re-execute ``request`` over
        the exact views a production reply served, on the always-correct
        host path — no device lane, no result cache, no tier ladder —
        one ``yield`` after each block of rows
        (``host_fallback.execute_host_steps``), the result as the
        generator's return value.  Pruning is correctness-preserving,
        so the payload (modulo accounting) must match whatever tier
        served production."""
        from pinot_tpu.engine.host_fallback import execute_host_steps

        segments = list(segments)
        total_docs = sum(s.num_docs for s in segments)
        # upstream's three verdicts alone: the oracle is the independent
        # derivation, so it scans every schema-valid segment, the ones the
        # value pruner leaves out of production's work among them, and
        # this way audits the pruner too
        live = prune_segments(segments, request)
        if not live:
            res = self._empty_result(request, total_docs)
        else:
            sel_columns = (
                ladder.selection_columns(request, live[0])
                if request.is_selection
                else None
            )
            ctx = get_table_context(live)
            res = yield from execute_host_steps(
                live, ctx, request, total_docs, sel_columns
            )
        res._served_tier = "host"
        return res

    # -- mesh / lane-group routing -------------------------------------
    def lane_selection(self, request: BrokerRequest, shape_digest: Optional[str] = None):
        """Shape-hashed chip-group routing (dispatch.LaneGroup.select),
        or None without a lane group.  Shared by the serving path and
        EXPLAIN so the phantom plan stages/pads exactly like the lane
        that would execute it."""
        if self.lanes is None:
            return None
        if shape_digest is None:
            from pinot_tpu.engine.plandigest import plan_shape_digest

            shape_digest = plan_shape_digest(request)
        return self.lanes.select(shape_digest)

    def _mesh_placement(self, mesh):
        """(NamedSharding splitting the segment axis over ``mesh``, its
        ``placement_key``), None and None without a mesh: one cached
        instance per mesh — it is part of staging-cache keys."""
        if mesh is None:
            return None, None
        key = id(mesh)
        placed = self._mesh_shardings.get(key)
        if placed is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from pinot_tpu.engine.device import placement_key

            # axis 0 shards over EVERY mesh axis — the same spec the
            # sharded kernels' in_specs use (multichip._make_sharded),
            # so staged arrays arrive already laid out for shard_map
            sh = NamedSharding(mesh, P(tuple(mesh.axis_names)))
            placed = self._mesh_shardings[key] = (sh, placement_key(sh))
        return placed

    def _phase(self, name: str, **tags) -> boundary:
        """One executor boundary, unstarted (utils/trace.py): the
        ServerQueryPhase-style timer ``phase.<name>`` (SURVEY §5:
        pruning / planBuild / planExec phases), the span on the
        request's tree when it is traced, and ``pinot:<name>`` on the
        profiler's host plane."""
        return boundary(name, current_trace(), self.metrics.timer(f"phase.{name}"), **tags)

    def execute(
        self,
        segments: Sequence[ImmutableSegment],
        request: BrokerRequest,
        deadline: Optional[float] = None,
    ) -> IntermediateResult:
        """``deadline`` (monotonic seconds) is the broker-propagated
        budget; threaded into the device lane so a query whose budget
        drained while queued there is shed, not executed."""
        # star-tree routing: eligible segments answer from their
        # pre-aggregated cube (startree/operator.py); the rest take the
        # normal device path, partials merge below
        from pinot_tpu.startree.operator import execute_star_tree

        with self._phase("prune"):  # segment pruning and star-tree routing
            total_docs = sum(s.num_docs for s in segments)
            # upstream's three verdicts drop a segment from the query; the
            # value verdict leaves one out of the work (_execute_tiers)
            live, star, normal = ladder.routed(segments, request)
            pruned = len(segments) - len(live)
            self.metrics.meter("prune.segments.offered").mark(len(segments))
        if not live:
            res = self._empty_result(request, total_docs)
            res.add_cost(segmentsPruned=pruned)
            return res

        if star:
            parts = [execute_star_tree(s, request) for s in star]
            if normal:
                parts.append(self._execute_engine(normal, request, deadline))
            merged = parts[0]
            for p in parts[1:]:
                merged.merge(p)
            merged.total_docs = total_docs
            merged.add_cost(segmentsPruned=pruned)
            merged._served_tier = (
                "starTree"
                if not normal
                else getattr(parts[-1], "_served_tier", "starTree")
            )
            return merged

        result = self._execute_engine(live, request, deadline)
        result.total_docs = total_docs
        result.add_cost(segmentsPruned=pruned)
        return result

    def _execute_engine(
        self,
        live: List[ImmutableSegment],
        request: BrokerRequest,
        deadline: Optional[float] = None,
    ) -> IntermediateResult:
        # the first stretch is ``staging`` (tier choice + get_staged)
        # unless a host tier answers and relabels it
        ph = phases(self._phase)
        ph.enter("staging")
        use = None
        try:
            from pinot_tpu.engine.plandigest import plan_shape_digest

            # one digest a query: the lane's choice, the audit plane's
            # quarantine checks and the memo's key all read it
            shape = plan_shape_digest(request)
            # chip-group routing (mesh execution): the lane group picks
            # the lane/mesh this shape executes on; without one, the
            # legacy single-mesh (or no-mesh) configuration applies
            sel = self.lane_selection(request, shape)
            mesh = sel.group.mesh if sel is not None else self.mesh
            use = self._prepared_for(request, live, shape, sel, mesh)
            result = self._execute_tiers(live, request, deadline, ph, shape, sel, mesh, use)
        finally:
            ph.stop()
            if use is not None:
                self.metrics.meter(f"plan.prepared.{use.outcome}").mark()
        if use.outcome == "hit":
            result.add_cost(preparedHit=1)
        n_expr = sum(1 for a in request.aggregations if a.expr is not None)
        if n_expr:
            # one mark a query whose plan holds an expression, by where it
            # was answered: a device program, or a host tier (the postings
            # tier, the forced host path, the failover)
            on_device = getattr(result, "_served_tier", "") in ("device", "bitsliced")
            self.metrics.meter("agg.expr.device" if on_device else "agg.expr.host").mark()
            result.add_cost(exprAggs=n_expr)
        return result

    # -- the prepared-query memo ---------------------------------------
    def _prepared_for(self, request: BrokerRequest, live: List[ImmutableSegment], shape: str, sel, mesh) -> _Use:
        """This query's entry of the memo, found or begun.  The key is
        ``rescache.ResultCache.key_for``'s identity (shape digest, which
        holds the raw table; literal digest; each segment's name and
        staging token) with the segments in the order served, because
        staging, the query inputs and the postings hand-off are laid
        out in it; then the placement, the lane group and the settings.
        A re-loaded segment, a consuming segment whose offset advanced,
        another literal, another chip group or a flipped setting is
        another key.  A segment without a token is not kept."""
        from pinot_tpu.engine.plandigest import plan_literal_digest

        try:
            fence = tuple((s.segment_name, int(s.staging_token)) for s in live)
        except (AttributeError, TypeError):
            return _Use(_Prepared(), None, "miss")
        key = (
            shape,
            plan_literal_digest(request),
            fence,
            self._mesh_placement(mesh)[1],
            sel.index if sel is not None else 0,
            _settings_fence(),
        )
        with self._prepared_lock:
            found = self._prepared.get(key)
            if found is not None:
                self._prepared.move_to_end(key)
                return _Use(found, key, "hit")
            begun = self._prepared[key] = _Prepared()
            self._prepared_make_room()
        return _Use(begun, key, "miss")

    def _prepared_make_room(self) -> None:
        """Under the memo's lock: the least recently asked go until
        entries and bytes fit their bounds."""
        while self._prepared and (
            len(self._prepared) > _PREPARED_ENTRIES or self._prepared_bytes > _PREPARED_BYTES
        ):
            _, old = self._prepared.popitem(last=False)
            self._prepared_bytes -= old.nbytes

    def _prepared_count(self, use: _Use) -> None:
        """Count the entry's tables anew, after a derivation that holds
        some.  An entry over a quarter of the bound alone is not kept
        (one query must not turn the whole memo over)."""
        prep = use.prepared
        postings = prep.values.get("postings")
        nbytes = _table_bytes([t for _ref, t in postings[1]]) if postings else 0
        if prep.device is not None:
            nbytes += _table_bytes(prep.device.values.get("inputs"))
        with self._prepared_lock:
            if self._prepared.get(use.key) is prep:  # else turned out meanwhile, or never kept
                self._prepared_bytes += nbytes - prep.nbytes
                prep.nbytes = nbytes
                if nbytes > _PREPARED_BYTES // 4:
                    del self._prepared[use.key]
                    self._prepared_bytes -= nbytes
                self._prepared_make_room()

    def _execute_tiers(
        self,
        live: List[ImmutableSegment],
        request: BrokerRequest,
        deadline: Optional[float],
        ph: phases,
        shape: str,
        sel,
        mesh,
        use: _Use,
    ) -> IntermediateResult:
        def scope():
            _check_expression_columns(request, live[0])
            return ladder.scope(request, live, mesh)

        use.prepared.once("scope", scope)

        def scanned_of():
            # a function of the literals and the segments' tokens, the
            # memo's key: a repeated text pays this look-up.  Derived, it
            # is a stretch of ``prune`` between two of ``staging``: the
            # phases follow one another, so no leaf is timed twice
            ph.enter("prune")
            try:
                return scanned_segments(live, request)
            finally:
                ph.enter("staging")

        # ``live`` stays the table's identity (the memo's fence, the
        # staged table's key, the table context, totalDocs); the work is
        # over the segments the filter can match, and every tier is
        # handed them.  The rest count as pruned, as the time pruner's do
        scanned = use.prepared.once("scanned", scanned_of)
        dead = len(live) - len(scanned)
        if dead:
            self.metrics.meter("prune.segments.value").mark(dead)
        if not scanned:
            res = self._empty_result(request, use.prepared.values["scope"][0])
            res.add_cost(segmentsPruned=dead)
            return res
        # looked up on every query (its own cache, by the segments'
        # tokens): an entry that held it would hold the segments
        ctx = get_table_context(live, build_timer=self.metrics.timer("phase.globalDictBuild"))

        # audit-plane quarantine (utils/audit.py): a tier caught
        # serving wrong answers for this shape is skipped — looked up
        # only while some audit quarantine is live
        audit_digest = shape if self._has_audit_poison else None

        # the ladder (engine/ladder.py): the first tier that accepts
        # answers; a blocked or declining one falls through to the next
        for tier in ladder.TIERS:
            if mesh is not None and not tier.on_mesh:
                continue
            if self._audit_blocked(audit_digest, tier.name):
                if tier.name != "device":
                    continue
                # wrong-answer quarantine: unlike a device FAILURE (which
                # retries), a tier caught lying never gets another attempt
                # inside the TTL — straight to the host oracle path
                res = self._host_failover("auditQuarantine", live, request, ctx, ph, use)
            else:
                res = self._SERVE[tier.name](self, tier, live, request, deadline, ctx, ph, sel, mesh, use)
            if res is not None:
                if dead:
                    res.add_cost(segmentsPruned=dead)
                return res
        raise AssertionError("the device tier answers or raises")

    def _host_failover(self, reason: str, live, request, ctx, ph: phases, use: _Use) -> IntermediateResult:
        from pinot_tpu.engine.host_fallback import execute_host

        total_docs, _needed, sel_columns, _pad_to = use.prepared.values["scope"]
        self._heal_mark("hostFailovers", reason=reason)
        ph.enter("hostFailover")
        res = execute_host(live, ctx, request, total_docs, sel_columns, scanned=use.prepared.values["scanned"])
        return self._finish_tier(res, request, "host")

    def _serve_postings(self, tier, live, request, deadline, ctx, ph, sel, mesh, use) -> Optional[IntermediateResult]:
        from pinot_tpu.engine import invindex_path

        prep = use.prepared
        total_docs, _needed, sel_columns, _pad_to = prep.values["scope"]
        scanned = prep.values["scanned"]
        kept = prep.values.get(tier.prepared)  # None: not decided yet; (): declined
        state = invindex_path.held_state(kept) if kept else None
        if state is None and kept != ():  # not decided, or the postings it took were released since
            state = tier.decide(request, live, ctx, total_docs, mesh, scanned)[1]
            prep.values[tier.prepared] = invindex_path.hold_state(state) if state is not None else ()
            self._prepared_count(use)
        if state is None:
            return None
        ires = invindex_path.run_index_path(state, request, live, ctx, total_docs, sel_columns, scanned)
        ph.relabel(tier.phase)
        return self._finish_tier(ires, request, tier.name)

    def _serve_bitsliced(self, tier, live, request, deadline, ctx, ph, sel, mesh, use) -> Optional[IntermediateResult]:
        # A device fault here falls through to the scan tier's healing
        # loop instead of failing the query on an optimization tier.
        from pinot_tpu.engine.bitsliced import run_bitsliced_path

        total_docs = use.prepared.values["scope"][0]
        scanned = use.prepared.values["scanned"]
        try:
            state = use.prepared.once(
                tier.prepared, lambda: tier.decide(request, live, ctx, total_docs, mesh, scanned)[1]
            )
            bres = None
            if state is not None:
                bres = run_bitsliced_path(
                    self, state, request, live, ctx, total_docs, deadline,
                    lane=sel.lane if sel is not None else None,
                    lane_index=sel.index if sel is not None else 0,
                    scanned=scanned,
                )
        except Exception as e:
            from pinot_tpu.engine.dispatch import (
                LaneClosedError,
                QueryAbandonedError,
            )

            if isinstance(
                e, (QueryAbandonedError, LaneClosedError, TimeoutError)
            ):
                raise
            # answered anyway, by the scan below — say so, or the
            # only trace of a tier that never works is a meter
            logger.warning(
                "bit-sliced tier failed; falling through to the scan",
                exc_info=True,
            )
            self._heal_mark("bitslicedFallbacks", error=str(e)[:200])
            bres = None
        if bres is None:
            return None
        ph.relabel(tier.phase)
        return self._finish_tier(bres, request, tier.name)

    def _serve_host(self, tier, live, request, deadline, ctx, ph, sel, mesh, use) -> Optional[IntermediateResult]:
        total_docs, _needed, sel_columns, _pad_to = use.prepared.values["scope"]
        scanned = use.prepared.values["scanned"]
        forced = use.prepared.once(tier.prepared, lambda: tier.decide(request, live, ctx, total_docs, mesh, scanned)[1])
        if forced is None:
            return None
        from pinot_tpu.engine.host_fallback import execute_host

        # a group-by the device declines says why, by name (the
        # reason EXPLAIN gives the segment's host record)
        (reason,) = forced
        if reason is not None:
            self.metrics.meter(f"groupby.forcedHost.{reason.split(':')[0]}").mark()
        res = execute_host(live, ctx, request, total_docs, sel_columns, scanned=scanned)
        ph.relabel(tier.phase)
        return self._finish_tier(res, request, tier.name)

    def _serve_device(self, tier, live, request, deadline, ctx, ph, sel, mesh, use) -> IntermediateResult:
        # -- device section under the self-healing contract -----------
        # The WHOLE device path (staging, H2D uploads, kernel dispatch,
        # D2H fetch, finalize) is covered: classify the failure
        # (engine/dispatch.py), retry ONCE on device for transients,
        # then quarantine the (plan digest, segment set) and serve the
        # same request via the always-correct host path.  Deadline and
        # shutdown control flow propagates untouched.
        from pinot_tpu.engine.dispatch import (
            DeviceExecutionError,
            LaneClosedError,
            QueryAbandonedError,
            classify_device_error,
        )

        poison_ref: Dict[str, Any] = {}  # device section records the key
        last: Optional[DeviceExecutionError] = None
        # attempt budget: one plain device retry for transients (PR 3),
        # plus one extra round reserved for RESOURCE_EXHAUSTED — an OOM
        # retried into the same full HBM would fail identically, so
        # each OOM round first demotes the coldest unpinned residents
        # (engine/residency.py) to make room.  Host failover stays the
        # LAST resort.
        for attempt in (0, 1, 2):
            if attempt:
                if last is None or not last.retryable:
                    break  # poison/stall: deterministic, a device retry
                    # would fail (or wedge the fresh lane) identically
                if getattr(last, "resource_exhausted", False):
                    from pinot_tpu.engine.residency import RESIDENCY

                    exclude = tuple(
                        t for t in (poison_ref.get("token"),) if t is not None
                    )
                    freed = RESIDENCY.demote_for_pressure(
                        exclude_tokens=exclude
                    )
                    self._heal_mark("resourceExhausted", freedBytes=freed)
                elif attempt > 1:
                    break  # plain transients get exactly ONE device retry
                self._heal_mark("deviceRetries")
                ph.enter("staging")
            try:
                res = self._device_section(
                    live, request, deadline, ctx, use, ph, poison_ref, sel=sel, mesh=mesh,
                )
                # the section answers from the host where the plan is not
                # on the device, is quarantined, or overflowed its pairs
                return self._finish_tier(res, request, "host" if poison_ref.get("host") else tier.name)
            except (QueryAbandonedError, LaneClosedError, TimeoutError):
                raise
            except Exception as e:
                if poison_ref.pop("host", False):
                    # the section had already LEFT the device path (plan
                    # not on device / poison skip / pair overflow) — a
                    # host execution error is not a device failure and
                    # re-running the host path could only fail again
                    raise
                last = classify_device_error(e)
                # the query is about to be answered anyway (device retry,
                # then the host): without this line a chip that does no
                # work shows only in the heal.* meters
                logger.warning(
                    "device path failed (%s, attempt %d): %s",
                    "retryable" if last.retryable else "poison",
                    attempt,
                    last,
                    exc_info=e,
                )
                self._heal_mark(
                    "deviceFailures", retryable=last.retryable, error=str(last)[:200]
                )
        # device exhausted: quarantine (when the section got far enough
        # to know its plan) and transparently fail over.  Coalesced
        # waiters each land here and each finalize from the host.
        if poison_ref.get("key") is not None and not getattr(
            last, "resource_exhausted", False
        ):
            # OOM never poisons: the plan is healthy, the device was
            # full — quarantining it would strand a good plan on the
            # slow host path after pressure subsides
            self._poison(poison_ref["key"], str(last))
        return self._host_failover(str(last)[:200], live, request, ctx, ph, use)

    _SERVE = {
        "postings": _serve_postings,
        "bitsliced": _serve_bitsliced,
        "host": _serve_host,
        "device": _serve_device,
    }

    def _device_section(
        self,
        live: List[ImmutableSegment],
        request: BrokerRequest,
        deadline: Optional[float],
        ctx: TableContext,
        use: _Use,
        ph: phases,
        poison_ref: Dict[str, Any],
        sel=None,
        mesh=None,
    ) -> IntermediateResult:
        prep = use.prepared
        _total_docs, needed, _sel_columns, pad_to = prep.values["scope"]
        lane = sel.lane if sel is not None else self.lane
        sharding, placement = self._mesh_placement(mesh)

        raw_cols, gfwd_cols, hll_cols, skip_base = prep.once("roles", lambda: ladder.roles(request, live, ctx))
        # what a kept entry cannot spare a query, because it is the
        # table's state and not a function of the key: the staged table
        # of the moment, pinned.  pin=True: its token is refcounted for
        # this query's whole device section, so tier demotion under
        # memory pressure (engine/residency.py) can never race the launch
        staged = get_staged(
            live,
            needed,
            pad_segments_to=pad_to,
            raw_columns=raw_cols,
            gfwd_columns=gfwd_cols,
            hll_columns=hll_cols,
            ctx=ctx,
            skip_base_columns=skip_base,
            sharding=sharding,
            pin=True,
            hll_timer=self.metrics.timer("phase.hllDerive") if hll_cols else None,
        )
        # the OOM heal's demotion pass must not evict the very table
        # this query is about to retry against
        poison_ref["token"] = staged.token
        from pinot_tpu.engine.residency import RESIDENCY

        try:
            return self._device_section_staged(
                live, request, deadline, ctx, use, ph, poison_ref, sel, mesh, lane,
                sharding, placement, staged,
            )
        finally:
            RESIDENCY.unpin(staged.token)

    def _device_section_staged(
        self,
        live: List[ImmutableSegment],
        request: BrokerRequest,
        deadline: Optional[float],
        ctx: TableContext,
        use: _Use,
        ph: phases,
        poison_ref: Dict[str, Any],
        sel,
        mesh,
        lane,
        sharding,
        placement,
        staged,
    ) -> IntermediateResult:
        ph.enter("planBuild")  # staging ends here
        prep = use.prepared
        total_docs, needed, sel_columns, _pad_to = prep.values["scope"]
        scanned = prep.values["scanned"]
        dev = prep.device
        if dev is None or dev.token != staged.token:
            # derived against another staged table (demoted since, and
            # staged anew): everything below is derived again
            if dev is not None and use.outcome == "hit":
                use.outcome = "stale"
            dev = prep.device = _PreparedDevice(staged.token)
        scratch: Dict[Any, Any] = {}  # plan->inputs table cache (regex)

        plan, pdigest, poison_key = dev.once("plan", lambda: ladder.plan(request, ctx, staged, scratch))

        if not plan.on_device:
            from pinot_tpu.engine.host_fallback import execute_host

            poison_ref["host"] = True  # host path from here: not a device fault
            ph.stop()
            return execute_host(live, ctx, request, total_docs, sel_columns, scanned=scanned)

        # poison quarantine: this (plan digest, segment set) keeps
        # failing on device — skip the device entirely and serve from
        # the always-correct host path (PIMDAL-style contract: the host
        # path stays a correct fallback for the accelerator path)
        poison_ref["key"] = poison_key
        if self._is_poisoned(poison_key):
            from pinot_tpu.engine.host_fallback import execute_host

            self._heal_mark("poisonSkips")
            ph.enter("hostFailover")
            poison_ref["host"] = True  # host path from here: not a device fault
            return execute_host(live, ctx, request, total_docs, sel_columns, scanned=scanned)

        from pinot_tpu.engine.device import segment_arrays

        def inputs():
            # made over the launch's segments (ladder.launch_segments):
            # their positions are one of the inputs, so the digest (the
            # lane's coalesce key, the uploaded inputs' key) tells two
            # launches of one plan over different segments apart
            q_np, block_ids, scanned_rows = ladder.inputs(request, plan, ctx, live, staged, scratch, scanned, mesh)
            return q_np, self._inputs_digest(q_np), block_ids, scanned_rows

        derived = "inputs" not in dev.values
        q_np, digest, block_ids, scanned_rows = dev.once("inputs", inputs)
        if derived:
            self._prepared_count(use)
        cost: Dict[str, float] = {}  # per-query cost vector accumulator
        # the staged table's arrays of the moment: never kept (_Prepared)
        seg_arrays = segment_arrays(staged, needed)
        # the kernel's lookup, the batch spec and (where the launch does
        # not carry it) the upload of the query's inputs
        ph.enter("kernelPrep")
        # kernel outputs fetch via ONE packed D2H transfer
        # (engine/packing.py): per-leaf fetches pay a transfer each
        batch_spec = None
        analysis_args = None

        def upload_inputs():
            return self._to_device_inputs(
                q_np, plan=plan, digest=digest, cost=cost, sharding=sharding,
                placement=placement,
            )

        kernel = ladder.program(plan, staged, q_np, block_ids, mesh)
        # the segments the program runs over, in the order of its outputs'
        # leading axis (None: a launch's empty slot), and L of the staged S
        launched = ladder.launched_segments(live, q_np)
        launch_count = ladder.launch_count(staged, q_np)
        # the plan's digest with L: a launch size is a compile of its own,
        # and the lane's compile timeline is kept by what compiles
        ldigest = ladder.launch_digest(pdigest, staged, q_np)
        if block_ids is not None:
            # block ids shard over the segment axis with everything else
            ids_dev = (
                jax.device_put(np.asarray(block_ids), sharding)
                if sharding is not None
                else jnp.asarray(block_ids)
            )
            args = (seg_arrays, upload_inputs(), ids_dev)
        else:
            if lane is not None:
                # cross-query micro-batching, where ladder.batch finds
                # the launch eligible
                batch_shape = dev.once("batch", lambda: ladder.batch(plan, staged, q_np, block_ids, mesh))
                if batch_shape is not None:
                    batch_spec = self._batch_spec(plan, staged, q_np, seg_arrays, batch_shape)
            if batch_spec is not None:
                # defer the solo upload into the launch closure: a
                # dispatch that rides a batched launch never uses its
                # own device copy (the batch uploads ONE stacked
                # pytree), so an eager per-member put would be dead H2D
                # weight exactly on the shapes that batch most
                args = lambda: (seg_arrays, upload_inputs())
                # cost analysis traces shapes only: the host numpy
                # pytree stands in so the helper thread never uploads
                analysis_args = (seg_arrays, q_np)
            else:
                args = (seg_arrays, upload_inputs())
        exec_info: Dict[str, Any] = {}
        ph.stop()  # laneWait/planExec are timed inside _run_kernel
        outs = self._run_kernel(
            kernel, args, plan, staged, digest, block_ids, deadline, ldigest,
            cost=cost, lane=lane, batch_spec=batch_spec, exec_info=exec_info,
            analysis_args=analysis_args,
            segments=f"{launch_count}/{staged.num_segments}",
        )
        ph.enter("finalize")

        # sort-dedup distinct overflow: more unique pairs than the
        # device buffer holds — only the host path can finish exactly
        for i, agg in enumerate(plan.aggs):
            if agg.sort_pairs:
                state = (
                    outs[f"gb_{i}"] if plan.group_by is not None else outs[f"agg_{i}"]
                )
                if int(state[3]) > state[0].shape[0]:
                    from pinot_tpu.engine.host_fallback import execute_host

                    # pair overflow: host finishes exactly — leaving the
                    # device path, so host errors are not device faults
                    poison_ref["host"] = True
                    ph.stop()
                    return execute_host(live, ctx, request, total_docs, sel_columns, scanned=scanned)

        # the rows the program ran over: the staged table's for the whole
        # launch, which is every query of a table without a dead segment
        launched_docs = (staged.total_docs if launch_count == staged.num_segments
                         else sum(s.num_docs for s in launched if s is not None))
        result = self._finalize(request, plan, ctx, launched, launched_docs, outs, total_docs, sel_columns)
        # a value-dead segment of a whole launch was read and its rows
        # rejected; it is counted with the pruned all the same, so that
        # the tiers' counts partition numSegmentsQueried by the verdict
        result.num_segments_queried = len(scanned)
        if plan.group_by is not None:
            ph.current.tag(groups=int(result.cost.get("numGroupsLive", 0)))  # add_cost keeps no zero
        if scanned_rows is not None:
            # zone maps skipped non-candidate blocks: filter scan cost
            # is O(candidate rows), the point of the skipping path
            result.num_entries_scanned_in_filter = len(plan.leaves) * scanned_rows
        # device-path cost vector: staged bytes the kernel read (the
        # block path reads only the candidate fraction), the serving
        # tier, and the dispatch-side hits recorded into ``cost``
        dev_bytes = sum(getattr(a, "nbytes", 0) for a in seg_arrays.values())
        dev_bytes = dev_bytes * launch_count // staged.num_segments
        if block_ids is not None and scanned_rows is not None and launched_docs:
            dev_bytes = int(
                dev_bytes * min(1.0, scanned_rows / launched_docs)
            )
        result.add_cost(bytesScanned=dev_bytes, deviceBytes=dev_bytes, **cost)
        if block_ids is not None:
            result.add_cost(segmentsZonemap=len(scanned))
        else:
            result.add_cost(segmentsFullScan=len(scanned))
        # device-plan identity for the utilization plane: lets the
        # plan-stats recorder join this shape's measured wall time with
        # the lane's static cost analysis (roofline numerator); the
        # lane index attributes it to the chip group that executed
        result._device_digest = ldigest
        result._lane_index = sel.index if sel is not None else 0
        # batching actuals for EXPLAIN ANALYZE's device node: how many
        # same-shape queries this member's launch actually carried
        result._batch_size = int(exec_info.get("batchSize", 1) or 1)
        ph.stop()
        return result

    def _batch_spec(self, plan: StaticPlan, staged, q_np, seg_arrays, batch_shape):
        """BatchSpec for the lane micro-batching tier (PIMDAL-style
        cross-query amortization — engine/dispatch.py module
        docstring): same-StaticPlan dispatches over the same staged
        table stack their query inputs along a leading batch axis and
        execute as ONE vmapped launch reading the resident columns
        once.

        The key is (StaticPlan, staging token, input signature):
        literal-bucketed program identity (``a>5`` and ``a>999`` build
        the SAME StaticPlan — only their match tables/bounds differ) x
        resident-table identity x structural input identity
        (``ladder.batch``).  Built on every query: its launch closes
        over the staged table's arrays of the moment."""
        from pinot_tpu.engine.dispatch import BatchSpec

        signature, max_members = batch_shape
        key = (plan, staged.token, signature)

        def launch_batched(inputs_list):
            from pinot_tpu.engine.device import to_device_inputs
            from pinot_tpu.engine.kernel import make_packed_batched_table_kernel
            from pinot_tpu.engine.packing import stack_query_inputs

            bkernel = make_packed_batched_table_kernel(plan)
            # pad the member count to a power of two (repeat member 0 —
            # harmless extra lanes whose outputs are never sliced) so
            # compile count per plan is bounded at log2(BATCH_MAX)
            # distinct batch shapes instead of one per observed size
            b = len(inputs_list)
            b_pad = 1
            while b_pad < b:
                b_pad *= 2
            if b_pad > b:
                inputs_list = list(inputs_list) + [inputs_list[0]] * (b_pad - b)
            stacked = stack_query_inputs(inputs_list)
            # ONE stacked H2D upload for the whole batch (recorded by
            # to_device_inputs); the per-member device-resident input
            # cache is bypassed — literals differ per member by design
            qb = to_device_inputs(stacked)
            return bkernel.fetch, bkernel.dispatch(seg_arrays, qb)

        return BatchSpec(key, q_np, launch_batched, max_members=max_members)

    @staticmethod
    def _hll_keys_a_segment(plan: StaticPlan, staged, block_ids) -> int:
        """The packed keys a segment hands a grouped distinctcounthll's
        'sort' lowering (``kernel._group_state``): one a row of the view
        the kernel runs over (the staged rows, or the zone tier's gathered
        blocks), times the width of every multi-value group key, times
        the widest multi-value argument's."""
        from pinot_tpu.engine.zonemap import zone_block_rows

        keys = staged.n_pad if block_ids is None else block_ids.shape[-1] * zone_block_rows()
        for column, is_mv in zip(plan.group_by.columns, plan.group_by.col_is_mv):
            keys *= staged.column(column).mv_pad if is_mv else 1
        return keys * max(staged.column(a.column).mv_pad if a.is_mv else 1 for a in plan.aggs if a.kind == "hll")

    def _run_kernel(
        self, kernel, args, plan, staged, digest, block_ids, deadline,
        pdigest=None, cost: Optional[Dict[str, float]] = None, lane=None,
        batch_spec=None, exec_info: Optional[Dict[str, Any]] = None,
        analysis_args=None, segments: str = "",
    ) -> Dict[str, Any]:
        """DISPATCH + output fetch.  Serial mode (no lane): launch and
        fetch inline, the pre-pipeline behavior.  Pipelined: the launch
        runs on the (shape-selected) device lane — coalesced with
        identical in-flight dispatches, or micro-batched with same-plan
        peers when ``batch_spec`` is set — and this worker blocks only
        when FINALIZE first reads the outputs (the packed D2H
        transfer).  ``args`` may be a zero-arg callable (batch-eligible
        dispatches defer their solo H2D upload into the launch itself);
        ``analysis_args`` is the host-shaped stand-in the cost-analysis
        helper lowers with in that case.  ``segments``: ``<L>/<S>``, the
        launch span's ``segments=`` tag (``ladder.launch_segments``)."""
        if lane is None:
            lane = self.lane
        cost_args = args if not callable(args) else analysis_args

        def launch():
            a = args() if callable(args) else args
            disp = getattr(kernel, "dispatch", None)
            if disp is not None:
                return kernel.fetch, disp(*a)
            return None, kernel(*a)  # raw jit: device arrays out

        # the jitted program's name (engine/kernel.py kernel_name): the
        # ``program=`` tag of the launch and wait spans, as the device
        # planes of a capture name it
        program = getattr(kernel, "__name__", "")
        # a group-by program's lowering and where its operands are built,
        # from the functions the kernel builder asks: the launch's
        # ``groupby=`` and ``operands=`` tags, its ``groupby.lowering.*``
        # mark and, built in the row loop or put in key order, its
        # ``groupby.operands.loop|sorted`` mark ("" for any other
        # program); and how a zone-tier program
        # reads its candidate blocks: the ``blocks=`` tag and the
        # ``zone.blocks.*`` mark
        from pinot_tpu.engine.kernel import groupby_cells, groupby_lowering, groupby_operands, hll_lowering, hll_sort_parts, selection_lowering, zone_blocks

        groupby = groupby_lowering(plan) or ""
        operands = groupby_operands(plan) or ""
        blocks = zone_blocks(plan) if block_ids is not None else ""
        # which lowering its HLL aggregates take, grouped or not: the
        # ``hll=`` tag and the ``hll.lowering.*`` mark ("" without one)
        hll = hll_lowering(plan) or ""
        # under 'sort', in how many parts a segment's packed keys are
        # sorted: the ``hll.sort.parts`` mark (0 under any other lowering)
        hll_parts = hll_sort_parts(self._hll_keys_a_segment(plan, staged, block_ids)) if hll == "sort" else 0
        # the form a selection's candidates are found in: the
        # ``selection=`` tag and the ``selection.lowering.*`` mark
        selection = selection_lowering(plan) or ""
        # its K x m cells and the rows sharing saved (the ``cells=`` tag,
        # ``groupby.slots.shared``), and how many aggregates take a
        # compound expression (the ``expr=`` tag)
        cells = groupby_cells(plan) or (0, 0)
        n_expr = sum(1 for a in getattr(plan, "aggs", ()) if getattr(a, "expr", None) is not None)
        coalesced = False
        ticket = None
        # planExec excludes lane queueing (timed as laneWait): it covers
        # launch (serial mode) + the wait for the device + the D2H
        # fetch, so the per-stage timers on status() sum to wall time
        # instead of double-counting the wait inside planExec
        executing = self._phase("planExec")
        try:
            if lane is None:
                executing.start()
                if groupby:
                    self.metrics.meter(f"groupby.lowering.{groupby}").mark()
                if operands in ("loop", "sorted"):
                    self.metrics.meter(f"groupby.operands.{operands}").mark()
                if cells[1]:
                    self.metrics.meter("groupby.slots.shared").mark(cells[1])
                if blocks:
                    self.metrics.meter(f"zone.blocks.{blocks}").mark()
                if hll:
                    self.metrics.meter(f"hll.lowering.{hll}").mark()
                if hll_parts:
                    self.metrics.meter("hll.sort.parts").mark(hll_parts)
                if selection:
                    self.metrics.meter(f"selection.lowering.{selection}").mark()
                fetch, handle = launch()
            else:
                # coalesce key: identical (plan, staged-table token, inputs
                # digest, block-id set) => identical device outputs.  The
                # token is process-unique (device.py), so a table re-staged
                # after GC can never alias an in-flight dispatch.
                bkey = (
                    None
                    if block_ids is None
                    else (block_ids.shape, block_ids.tobytes())
                )
                from pinot_tpu.engine.packing import kernel_cost_analysis

                # queue + coalesce wait + the launch call on the lane
                # thread: laneQueue, laneDispatch and laneDeliver are
                # recorded there (engine/dispatch.py), laneWake here; the
                # coalesced tag marks a query that rode an identical
                # in-flight dispatch
                with self._phase("laneWait") as waiting:
                    ticket = lane.submit(
                        (plan, staged.token, digest, bkey),
                        launch,
                        deadline,
                        plan_digest=pdigest,
                        # static roofline numerator: flops/bytes per launch of
                        # this compiled plan, resolved ONCE per digest on the
                        # lane's async analysis thread (graceful None fallback)
                        cost_provider=lambda: kernel_cost_analysis(kernel, cost_args),
                        batch=batch_spec,
                        trace=current_trace(),
                        parent=waiting.span_id,
                        program=program,
                        groupby=groupby,
                        operands=operands,
                        expr=n_expr,
                        cells=cells,
                        blocks=blocks,
                        hll=hll,
                        hll_parts=hll_parts,
                        segments=segments,
                        selection=selection,
                    )
                    fetch, handle = ticket.result(deadline)
                    # the lane thread delivered -> this worker runs again
                    measured("laneWake", (time.perf_counter() - ticket.delivered_at) * 1000.0,
                             current_trace(), self.metrics.timer("phase.laneWake"))
                    coalesced = ticket.coalesced
                    waiting.tag(coalesced=coalesced)
                if cost is not None and coalesced:
                    cost["coalesceHits"] = cost.get("coalesceHits", 0) + 1
                bsize = int(getattr(ticket, "batch_size", 1) or 1)
                if exec_info is not None:
                    exec_info["batchSize"] = bsize
                if cost is not None and bsize > 1:
                    # this query rode a cross-query batched launch (its
                    # literals stacked with bsize-1 same-plan peers)
                    cost["batchHits"] = cost.get("batchHits", 0) + 1
                executing.start()
            with self._phase("deviceWait", program=program):
                # the packed handle is (layout, device buffer); the raw
                # jit's is the output pytree itself.  The D2H copy is
                # queued behind the program first, as ``np.asarray`` on a
                # pending buffer queues it: waiting alone would put a
                # host round trip between the kernel and the copy
                # (0.35 ms a query on the v5e, PERF.md PR 25).
                buffers = jax.tree_util.tree_leaves(handle[1] if fetch is not None else handle)
                for buf in buffers:
                    buf.copy_to_host_async()
                jax.block_until_ready(buffers)
            with self._phase("d2hUnpack"):
                if ticket is not None:
                    lane.output_ready(ticket)  # occupancy: the device is done with this launch
                # exactly ONE waiter per dispatch is non-coalesced, so the
                # physical D2H copy is counted once no matter how many
                # queries rode the dispatch (coalesced waiters read the
                # cached host copy)
                outs = fetch(handle, count_transfer=not coalesced) if fetch is not None else handle
                outs = {
                    k: np.asarray(v)
                    if not isinstance(v, tuple)
                    else tuple(np.asarray(x) for x in v)
                    for k, v in outs.items()
                }
                if fetch is None:
                    # raw-jit path (mesh/chunked kernels): the np.asarray
                    # calls above were the D2H transfers — the packed path
                    # counts its own single buffer inside packing.fetch
                    from pinot_tpu.engine.device import TRANSFERS

                    if not coalesced:
                        TRANSFERS.record_d2h(
                            sum(
                                x.nbytes
                                for v in outs.values()
                                for x in (v if isinstance(v, tuple) else (v,))
                            )
                        )
        finally:
            executing.stop()
        if cost is not None:
            # the cost vector's deviceMs is this same window: device
            # execution + the packed D2H fetch, not lane queueing
            cost["deviceMs"] = cost.get("deviceMs", 0.0) + round(executing.ms, 3)
        return outs

    def _inputs_digest(self, inputs: Dict[str, Any]) -> str:
        """Content digest of the numpy query-inputs pytree — one
        computation shared by the device-resident input cache and the
        lane's coalesce key."""
        import hashlib

        h = hashlib.blake2b(digest_size=16)
        leaves, _ = jax.tree_util.tree_flatten(inputs)
        for leaf in leaves:
            if isinstance(leaf, np.ndarray):
                part = str((leaf.shape, str(leaf.dtype))).encode() + leaf.tobytes()
            else:
                part = repr(leaf).encode()
            # length-prefix each leaf so adjacent contributions can't
            # re-split into the same byte stream ((1, 23) vs (12, 3))
            h.update(len(part).to_bytes(8, "little"))
            h.update(part)
        return h.hexdigest()

    def _to_device_inputs(
        self,
        inputs: Dict[str, Any],
        plan=None,
        digest: Optional[str] = None,
        cost: Optional[Dict[str, float]] = None,
        sharding=None,
        placement=None,
    ) -> Dict[str, Any]:
        """Device-resident query-inputs cache: a repeated query (same
        plan, same literal tables) reuses the arrays already in HBM
        instead of re-uploading (each upload is a host->device
        transfer on the query's critical path).  Keyed by (plan, content digest,
        placement), so realtime watermark changes, different literals,
        or a different chip group miss safely.  ``placement`` is
        ``placement_key(sharding)`` where the caller already has it."""
        from pinot_tpu.engine.device import placement_key, to_device_inputs

        if plan is None:
            return to_device_inputs(inputs, sharding=sharding)
        if digest is None:
            digest = self._inputs_digest(inputs)
        if placement is None:
            placement = placement_key(sharding)
        key = (plan, digest, placement)
        with self._qinput_cache_lock:
            cached = self._qinput_cache.get(key)
            if cached is not None:
                self._qinput_cache.move_to_end(key)
                if cost is not None:
                    cost["qinputCacheHits"] = cost.get("qinputCacheHits", 0) + 1
                return cached[0]
        dev = to_device_inputs(inputs, sharding=sharding)
        # Evict by HBM bytes, not entry count: one entry can hold
        # per-segment match tables of S x card_pad, so 128 entries of a
        # high-cardinality workload would pin multiple GB (ADVICE r3).
        nbytes = sum(
            getattr(leaf, "nbytes", 0)
            for leaf in jax.tree_util.tree_flatten(dev)[0]
        )
        from pinot_tpu.engine.config import qinput_cache_budget_bytes

        budget = qinput_cache_budget_bytes()
        if nbytes == 0 or nbytes > budget // 4:
            # zero-byte entries would never be evicted by byte pressure;
            # oversized ones would churn the whole cache for one query
            return dev
        with self._qinput_cache_lock:
            if key not in self._qinput_cache:
                self._qinput_cache[key] = (dev, nbytes)
                self._qinput_cache_bytes += nbytes
            # bytes bound HBM; the entry cap bounds per-entry host/device
            # allocator overhead that logical nbytes doesn't see
            while self._qinput_cache and (
                self._qinput_cache_bytes > budget or len(self._qinput_cache) > 128
            ):
                _, (_, old_bytes) = self._qinput_cache.popitem(last=False)
                self._qinput_cache_bytes -= old_bytes
        return dev

    def _empty_result(self, request: BrokerRequest, total_docs: int) -> IntermediateResult:
        res = IntermediateResult(total_docs=total_docs)
        if request.is_aggregation and not request.is_group_by:
            from pinot_tpu.engine.results import make_partial

            res.aggregations = [make_partial(a.base_function) for a in request.aggregations]
        elif request.is_group_by:
            res.groups = {}
        else:
            res.selection_rows = []
        return res

    # ------------------------------------------------------------------
    def _finalize(
        self,
        request: BrokerRequest,
        plan: StaticPlan,
        ctx: TableContext,
        live: List[ImmutableSegment],
        launched_docs: int,
        outs: Dict[str, Any],
        total_docs: int,
        sel_columns: Optional[List[str]],
    ) -> IntermediateResult:
        """``live``: the segments the program ran over, in the order of
        its outputs' leading axis (``ladder.launched_segments``), and
        ``launched_docs`` their rows."""
        matched = int(outs["num_docs"])
        res = IntermediateResult(
            num_docs_scanned=matched,
            total_docs=total_docs,
            num_segments_queried=len(live),
            num_entries_scanned_in_filter=len(plan.leaves) * launched_docs,
            num_entries_scanned_post_filter=matched * max(1, len(plan.aggs)),
        )

        if plan.group_by is not None:
            # what of the group state came back from the device, whatever
            # lowering made it: a dense holder's K cells an aggregate, or
            # the runs lowering's candidates
            self.metrics.meter("groupby.stateFetchBytes").mark(sum(
                x.nbytes
                for k, v in outs.items() if k.startswith("gb_")
                for x in (v if isinstance(v, tuple) else (v,))
            ))
            res.groups, live_groups, digest = self._finalize_groups(request, plan, ctx, outs)
            # groups with a row in the fetched state, and those left after
            # the per-server trim (an empty answer marks neither); the
            # digest is of every live group, so a reply of TOP n can be
            # held to the whole state and not to the n it returns
            self.metrics.meter("groupby.groups.live").mark(live_groups)
            self.metrics.meter("groupby.groups.kept").mark(len(res.groups))
            # the cells the plan sized the group space at (the product of
            # the keys' table cardinalities), beside what was found live
            self.metrics.meter("groupby.keySpaceCells").mark(plan.group_by.capacity)
            res.add_cost(numGroupsLive=live_groups, numGroupsKept=len(res.groups), **digest)
        elif plan.aggs:
            res.aggregations = [
                self._scalar_partial(agg, outs[f"agg_{i}"], ctx)
                for i, agg in enumerate(plan.aggs)
            ]
        if plan.selection is not None:
            # the candidates' rows gathered and decoded: a timer and a
            # ``pinot:selectionRows`` annotation inside ``finalize`` (no
            # span of its own), and the valid candidates the device handed
            # the host, a query
            with boundary("selectionRows", None, self.metrics.timer("phase.selectionRows")):
                res.selection_rows = self._finalize_selection(
                    request, plan, live, outs, sel_columns
                )
            self.metrics.meter("selection.candidates").mark(len(res.selection_rows))
            res.selection_columns = sel_columns
        return res

    def _scalar_partial(self, agg, state, ctx: TableContext) -> AggPartial:
        base = agg.base
        if base == "count":
            return CountPartial(float(state))
        if base == "sum":
            return SumPartial(float(state))
        if base == "min":
            return MinPartial(float(state))
        if base == "max":
            return MaxPartial(float(state))
        if base == "avg":
            return AvgPartial(float(state[0]), float(state[1]))
        if base == "minmaxrange":
            return MinMaxRangePartial(float(state[0]), float(state[1]))
        if agg.kind == "presence":
            gdict = ctx.column(agg.column).global_dict
            if agg.sort_pairs:
                ids = np.asarray(state[1])[: int(state[3])]
            else:
                ids = np.nonzero(np.asarray(state))[0]
            if agg.hll_from_presence:
                return HllPartial(_regs_from_value_gids(ctx, agg.column, ids))
            ids = np.asarray(ids, dtype=np.int64)
            ids = ids[ids < gdict.cardinality]
            return DistinctPartial(gdict.value_array()[ids])
        if agg.kind == "hist":
            gdict = ctx.column(agg.column).global_dict
            p = int(base[len("percentileest"):]) if base.startswith("percentileest") else int(base[len("percentile"):])
            if agg.sort_pairs:
                ps = _PairsState(state, 1)
                return _hist_partial(gdict, *ps.gid_counts_for(0), p)
            h = np.asarray(state)
            ids = np.nonzero(h)[0]
            counts = {
                float(gdict.get(int(i))): int(h[i]) for i in ids if i < gdict.cardinality
            }
            return HistogramPartial(counts, percentile=p)
        if agg.kind == "hll":
            return HllPartial(np.asarray(state).astype(np.uint8))
        raise AssertionError(agg)

    # ------------------------------------------------------------------
    def _kept_group_keys(self, plan: StaticPlan, ctx: TableContext, outs) -> Tuple[int, Dict[str, float], np.ndarray]:
        """(groups with a row, the digest of their values, the dense keys
        the per-server trim keeps, ascending) of a group-by's fetched
        state.  The digest: ``groupStateSumSq``, the sum of squares of
        every live group's value of each aggregate whose state is dense
        floats, and for the aggregates whose state is HLL registers
        ``groupStateHllSum``, the sum of every live group's estimate (an
        integer, exact in float64), and ``groupStateHllSumSq``."""
        gb = plan.group_by
        if "gb_runs_keys" in outs:
            return self._kept_run_keys(plan, outs)
        keys = np.nonzero(np.asarray(outs["gb_presence"]))[0]
        live_groups = int(keys.size)
        digest = {"groupStateSumSq": 0.0}
        if live_groups == 0:
            return 0, digest, keys

        # sort-dedup distinct states arrive as compacted (slot, gid)
        # pair buffers; index them once per agg for the per-group reads
        for i, agg in enumerate(plan.aggs):
            if agg.sort_pairs and not isinstance(outs[f"gb_{i}"], _PairsState):
                outs[f"gb_{i}"] = _PairsState(outs[f"gb_{i}"], gb.capacity)

        # every live group's order value of each aggregate whose state is
        # dense floats (count, sum, min, max, avg, minmaxrange: a gather)
        # or dense HLL registers (one estimate a group, in one numpy
        # pass), and of the others only where the trim needs them
        trims = live_groups > max(gb.top_n * 5, 100)
        order = {}
        for i, agg in enumerate(plan.aggs):
            dense = agg.kind in ("scalar", "pair")
            registers = agg.kind == "hll" and not agg.sort_pairs
            if trims or dense or registers:
                order[i] = self._group_order_values(agg, outs[f"gb_{i}"], keys, ctx)
            if dense:  # one pass in float64, no BLAS: its threads cost a wake-up a query
                digest["groupStateSumSq"] += float(np.square(order[i], dtype=np.float64).sum())
            elif registers:
                digest["groupStateHllSum"] = digest.get("groupStateHllSum", 0.0) + float(order[i].sum())
                digest["groupStateHllSumSq"] = digest.get("groupStateHllSumSq", 0.0) + float(np.square(order[i]).sum())

        # Trim candidate groups per aggregation (reference trims to
        # topN*5 per server, MCombineGroupByOperator.java:216): a
        # selection around each aggregation's cut, one pass over the
        # live groups and no sort of them; the union over aggregations
        # (incl. capped boundary ties) is kept so merges stay consistent.
        from pinot_tpu.engine.results import trim_group_candidates

        if trims:
            keep = trim_group_candidates(
                [order[i] for i in range(len(plan.aggs))],
                [group_sort_ascending(agg.func) for agg in plan.aggs],
                gb.top_n,
                live_groups,
            )
            keys = keys[keep]
        return live_groups, digest, keys

    def _kept_run_keys(self, plan: StaticPlan, outs) -> Tuple[int, Dict[str, float], np.ndarray]:
        """_kept_group_keys of a 'runs' group-by (kernel.groupby_lowering),
        whose program counted the live groups, took the digest and
        trimmed: the candidates an aggregate are put together here, each
        key once and ascending, and the aggregates' states set down as a
        dense lowering's would stand at those keys, a place a key
        (``gb_<i>`` by place, which _finalize_groups reads by place)."""
        from pinot_tpu.engine.kernel import _contraction_slots

        listed = np.asarray(outs["gb_runs_keys"])
        filled = np.nonzero(listed >= 0)[0]
        keys, first = np.unique(listed[filled], return_index=True)
        state = [np.asarray(row)[filled[first]] for row in outs["gb_runs_state"]]
        for i, slots in _contraction_slots(plan)[0].items():
            rows = [state[j] for j in slots]
            outs[f"gb_{i}"] = rows[0] if len(rows) == 1 else tuple(rows)
        digest = {"groupStateSumSq": float(np.sum(outs["gb_runs_sumsq"], dtype=np.float64))}
        return int(outs["gb_runs_live"]), digest, keys.astype(np.int64)

    def _finalize_groups(
        self, request: BrokerRequest, plan: StaticPlan, ctx: TableContext, outs
    ) -> Tuple[Dict[Tuple[str, ...], List[AggPartial]], int, Dict[str, float]]:
        """The kept groups' partials by rendered key, how many groups the
        fetched state held before the trim, and their values' digest."""
        gb = plan.group_by
        # from the fetched state to the kept keys: timer and annotation
        # only, inside ``finalize`` (which stays the span and the leaf)
        with boundary("groupTrim", None, self.metrics.timer("phase.groupTrim")):
            live_groups, digest, keys = self._kept_group_keys(plan, ctx, outs)
        if keys.size == 0:
            return {}, live_groups, digest

        # decompose mixed-radix keys -> per-column global ids
        gids = []
        rem = keys.copy()
        for gcard in reversed(gb.gcards):
            gids.append(rem % gcard)
            rem = rem // gcard
        gids.reverse()

        gdicts = [ctx.column(c).global_dict for c in gb.columns]
        key_tuples: List[Tuple[str, ...]] = []
        for row in range(keys.size):
            key_tuples.append(
                tuple(
                    render_value(gdicts[j].stored_type, gdicts[j].get(int(gids[j][row])))
                    for j in range(len(gb.columns))
                )
            )

        by_place = "gb_runs_keys" in outs  # the states hold the kept keys alone (_kept_run_keys)
        groups: Dict[Tuple[str, ...], List[AggPartial]] = {}
        for row, ktup in enumerate(key_tuples):
            k = row if by_place else int(keys[row])
            partials: List[AggPartial] = []
            for i, agg in enumerate(plan.aggs):
                partials.append(self._group_partial(agg, outs[f"gb_{i}"], k, ctx))
            groups[ktup] = partials
        return groups, live_groups, digest

    def _group_order_values(self, agg, state, keys: np.ndarray, ctx: TableContext) -> np.ndarray:
        """Exact finalized per-group values, used for trim ordering."""
        base = agg.base
        if base in ("count", "sum", "min", "max"):
            return np.asarray(state)[keys]
        if base == "avg":
            s = np.asarray(state[0])[keys]
            c = np.asarray(state[1])[keys]
            with np.errstate(divide="ignore", invalid="ignore"):
                return np.where(c > 0, s / np.maximum(c, 1), -np.inf)
        if base == "minmaxrange":
            return np.asarray(state[1])[keys] - np.asarray(state[0])[keys]
        if agg.kind == "presence":
            if agg.hll_from_presence:
                # never sort_pairs: hll_lowers_to_presence admits only
                # shapes whose dense holder fits (plan.py asserts this)
                from pinot_tpu.engine import hll as hll_mod

                occ = np.asarray(state)[keys]  # [K, gcard_pad]
                r, c = np.nonzero(occ)
                regs = _regs_from_value_gids(ctx, agg.column, c, r, keys.size)
                return np.asarray(
                    hll_mod.estimate_from_registers(regs), dtype=np.float64
                )
            if agg.sort_pairs:
                return state.counts[keys]
            return np.asarray(state)[keys].sum(axis=1).astype(float)
        if agg.kind == "hist":
            # exact percentile from histogram rows, vectorized:
            # sorted[int(n * p/100)] per group (PercentileUtil.java:50)
            p = int(base[len("percentileest"):]) if base.startswith("percentileest") else int(base[len("percentile"):])
            gdict = ctx.column(agg.column).global_dict
            vals = np.asarray(gdict.values, dtype=np.float64)
            if agg.sort_pairs:
                return state.percentiles_for(keys, p, vals)
            h = np.asarray(state)[keys]  # [K, gcard_pad]
            cs = np.cumsum(h, axis=1)
            n = cs[:, -1]
            idx = np.minimum((n * p / 100.0).astype(np.int64), np.maximum(n - 1, 0))
            pos = (cs <= idx[:, None]).sum(axis=1)
            pos = np.minimum(pos, vals.size - 1)
            return np.where(n > 0, vals[pos], -np.inf)
        if agg.kind == "hll":
            from pinot_tpu.engine import hll as hll_mod

            # registers to estimates: timer and annotation only, inside
            # ``finalize`` and its ``groupTrim`` (no span of its own)
            with boundary("hllEstimate", None, self.metrics.timer("phase.hllEstimate")):
                if agg.sort_pairs:
                    # vectorized over ALL requested keys: one batched decode
                    # over the concatenated per-slot gid slices
                    gids, rows = state.gids_rows_for(keys)
                    regs = _regs_from_gids(gids, rows, keys.size)
                else:
                    regs = np.asarray(state)[keys]
                return np.asarray(hll_mod.estimate_from_registers(regs), dtype=np.float64).reshape(-1)
        raise AssertionError(agg)

    def _group_partial(self, agg, state, key: int, ctx: TableContext) -> AggPartial:
        base = agg.base
        if base == "count":
            return CountPartial(float(np.asarray(state)[key]))
        if base == "sum":
            return SumPartial(float(np.asarray(state)[key]))
        if base == "min":
            return MinPartial(float(np.asarray(state)[key]))
        if base == "max":
            return MaxPartial(float(np.asarray(state)[key]))
        if base == "avg":
            return AvgPartial(float(np.asarray(state[0])[key]), float(np.asarray(state[1])[key]))
        if base == "minmaxrange":
            return MinMaxRangePartial(float(np.asarray(state[0])[key]), float(np.asarray(state[1])[key]))
        if agg.kind == "presence":
            gdict = ctx.column(agg.column).global_dict
            if agg.sort_pairs:
                ids = state.gids_for(key)
            else:
                row = np.asarray(state)[key]
                ids = np.nonzero(row)[0]
            if agg.hll_from_presence:
                return HllPartial(_regs_from_value_gids(ctx, agg.column, ids))
            ids = np.asarray(ids, dtype=np.int64)
            ids = ids[ids < gdict.cardinality]
            return DistinctPartial(gdict.value_array()[ids])
        if agg.kind == "hist":
            gdict = ctx.column(agg.column).global_dict
            p = int(base[len("percentileest"):]) if base.startswith("percentileest") else int(base[len("percentile"):])
            if agg.sort_pairs:
                return _hist_partial(gdict, *state.gid_counts_for(key), p)
            row = np.asarray(state)[key]
            ids = np.nonzero(row)[0]
            counts = {float(gdict.get(int(i))): int(row[i]) for i in ids if i < gdict.cardinality}
            return HistogramPartial(counts, percentile=p)
        if agg.kind == "hll":
            if agg.sort_pairs:
                return HllPartial(_regs_from_gids(state.gids_for(key)))
            return HllPartial(np.asarray(state)[key].astype(np.uint8))
        raise AssertionError(agg)

    # ------------------------------------------------------------------
    # distributed joins (engine/join.py): device hash-join under the
    # SAME self-healing contract as scans — classify, retry once on
    # transients, quarantine the join-plan digest, heal to the exact
    # host join.  A poisoned join plan heals exactly like a poisoned
    # scan plan (shared poison map, shared heal.* counters).
    # ------------------------------------------------------------------
    def execute_join(
        self,
        request: BrokerRequest,
        build,
        probe,
        deadline: Optional[float] = None,
    ) -> IntermediateResult:
        # the first stretch (packing the sides into a plan) is
        # ``planBuild`` unless the host join answers and relabels it
        ph = phases(self._phase)
        ph.enter("planBuild")
        try:
            return self._execute_join(request, build, probe, deadline, ph)
        finally:
            ph.stop()

    def _execute_join(
        self, request: BrokerRequest, build, probe, deadline: Optional[float], ph: phases
    ) -> IntermediateResult:
        from pinot_tpu.engine import join as join_mod

        side_bytes = build.nbytes() + probe.nbytes()
        try:
            planned = join_mod.build_join_plan(request, build, probe)
        except join_mod.JoinValidationError:
            raise  # typed client error, not a healable fault
        except Exception as e:
            # host-side packing is part of the device section's promise:
            # a packing bug degrades to the exact host join, it never
            # takes the query down
            self._heal_mark("hostFailovers", reason=f"joinPack: {e}"[:200])
            planned = None
        if planned is None:
            res = join_mod.host_join(request, build, probe)
            res.add_cost(buildRows=build.n, probeRows=probe.n)
            ph.relabel("hostPath")
            return res
        plan, inputs, meta = planned
        jdigest = join_mod.join_plan_digest(plan)

        from pinot_tpu.engine.dispatch import (
            DeviceExecutionError,
            LaneClosedError,
            QueryAbandonedError,
            classify_device_error,
        )

        poison_key = (jdigest, "join")
        sel = self.lane_selection(request)
        lane = sel.lane if sel is not None else self.lane
        if self._is_poisoned(poison_key):
            self._heal_mark("poisonSkips")
            res = join_mod.host_join(request, build, probe)
            res.add_cost(buildRows=build.n, probeRows=probe.n)
            ph.relabel("hostFailover")
            return res

        last: Optional[DeviceExecutionError] = None
        for attempt in (0, 1):
            if attempt:
                if last is None or not last.retryable:
                    break
                self._heal_mark("deviceRetries")
            try:
                return self._join_device_section(
                    request, plan, inputs, meta, build, probe, deadline,
                    jdigest, lane, sel, side_bytes, ph,
                )
            except (QueryAbandonedError, LaneClosedError, TimeoutError):
                raise
            except Exception as e:
                last = classify_device_error(e)
                self._heal_mark(
                    "deviceFailures", retryable=last.retryable, error=str(last)[:200]
                )
        self._poison(poison_key, str(last))
        self._heal_mark("hostFailovers", reason=str(last)[:200])
        ph.enter("hostFailover")
        res = join_mod.host_join(request, build, probe)
        res.add_cost(buildRows=build.n, probeRows=probe.n)
        return res

    def _join_device_section(
        self, request, plan, inputs, meta, build, probe, deadline,
        jdigest, lane, sel, side_bytes, ph,
    ) -> IntermediateResult:
        from pinot_tpu.engine import join as join_mod
        from pinot_tpu.engine.kernel import make_join_kernel

        kernel = make_join_kernel(plan)
        digest = self._inputs_digest(inputs)
        cost: Dict[str, float] = {}

        class _JoinToken:
            # stands in for the staged-table token in _run_kernel's
            # coalesce key: join inputs are content-digested, so the
            # constant token can never alias distinct data generations
            token = ("join",)
            num_segments = 0
            n_pad = 0

        dev_bytes = sum(a.nbytes for a in inputs.values())
        # joins are deliberately EXCLUDED from the micro-batching tier
        # (batch_spec=None): stacking distinct join payloads has no
        # shared-column amortization to win, and the byte-identity
        # proof for batched joins hasn't been done (ISSUE 14 guard)
        ph.stop()
        outs = self._run_kernel(
            kernel, (inputs,), plan, _JoinToken(), digest, None, deadline,
            pdigest=jdigest, cost=cost, lane=lane, batch_spec=None,
        )
        if not bool(outs.get("join_ok", True)):
            # the parallel-claim build ran out of rounds (cannot happen
            # with unique keys and a half-full table, but a wrong
            # answer must never ship): heal to the exact host join
            raise RuntimeError("join hash-table build did not converge")
        ph.enter("finalize")
        result = join_mod.finalize_device_join(
            request, plan, meta, build, probe, outs
        )
        result.add_cost(
            buildRows=build.n,
            probeRows=probe.n,
            bytesScanned=side_bytes,
            deviceBytes=dev_bytes,
            **cost,
        )
        result._device_digest = jdigest
        result._lane_index = sel.index if sel is not None else 0
        result._batch_size = 1
        return result

    # ------------------------------------------------------------------
    def _finalize_selection(
        self,
        request: BrokerRequest,
        plan: StaticPlan,
        live: List[ImmutableSegment],
        outs,
        sel_columns: List[str],
    ) -> List[Tuple[list, list]]:
        sel = request.selection
        docids = np.asarray(outs["sel_docids"])  # [S, k]
        valid = np.asarray(outs["sel_valid"])  # [S, k]
        rows: List[Tuple[list, list]] = []
        for si, seg in enumerate(live):
            if seg is None:  # a launch's empty slot
                continue
            for j in range(docids.shape[1]):
                if not valid[si, j]:
                    continue
                doc = int(docids[si, j])
                if doc >= seg.num_docs:
                    continue
                full = seg.row(doc)
                sort_vals = []
                for s in sel.sorts:
                    v = full[s.column]
                    if isinstance(v, list):
                        v = v[0] if v else None
                    sort_vals.append(v)
                rows.append((sort_vals, [full[c] for c in sel_columns]))
        return rows
