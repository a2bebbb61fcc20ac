"""Mergeable aggregation partials + finalization.

These are the *contents* of the server->broker partial results (the
DataTable payload analog).  Each aggregation function has a partial
state that merges associatively — across segments, servers, and chips:

  count/sum         float        merge = +
  min / max         float        merge = min / max
  avg               (sum, count) merge = pairwise +      (AvgPair analog)
  minmaxrange       (min, max)
  distinctcount     value set    merge = union           (IntOpenHashSet analog)
  distinctcounthll  uint8[m] HLL registers, merge = elementwise max
                    (vs the reference's Java-serialized HLL objects,
                     DataTableCustomSerDe.java:49)
  percentile*       value->count histogram, merge = counter add
                    (vs the reference shipping the raw DoubleArrayList —
                     strictly smaller, and exact)

Group-by partials are {group key tuple -> per-function partial} maps,
merged key-wise (MCombineGroupByOperator.java:152 semantics) and trimmed
to top_n at final reduce.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.engine import hll as hll_mod


class AggPartial:
    """Base: merge in place, then finalize to the response value."""

    def merge(self, other: "AggPartial") -> None:
        raise NotImplementedError

    def finalize(self) -> Any:
        raise NotImplementedError


class CountPartial(AggPartial):
    def __init__(self, count: float = 0.0) -> None:
        self.count = float(count)

    def merge(self, other: "CountPartial") -> None:
        self.count += other.count

    def finalize(self) -> Any:
        return int(self.count)


class SumPartial(AggPartial):
    def __init__(self, total: float = 0.0) -> None:
        self.total = float(total)

    def merge(self, other: "SumPartial") -> None:
        self.total += other.total

    def finalize(self) -> float:
        return self.total


class MinPartial(AggPartial):
    def __init__(self, value: float = math.inf) -> None:
        self.value = float(value)

    def merge(self, other: "MinPartial") -> None:
        self.value = min(self.value, other.value)

    def finalize(self) -> float:
        return self.value


class MaxPartial(AggPartial):
    def __init__(self, value: float = -math.inf) -> None:
        self.value = float(value)

    def merge(self, other: "MaxPartial") -> None:
        self.value = max(self.value, other.value)

    def finalize(self) -> float:
        return self.value


class AvgPartial(AggPartial):
    def __init__(self, total: float = 0.0, count: float = 0.0) -> None:
        self.total = float(total)
        self.count = float(count)

    def merge(self, other: "AvgPartial") -> None:
        self.total += other.total
        self.count += other.count

    def finalize(self) -> float:
        return self.total / self.count if self.count else -math.inf


class MinMaxRangePartial(AggPartial):
    def __init__(self, mn: float = math.inf, mx: float = -math.inf) -> None:
        self.mn = float(mn)
        self.mx = float(mx)

    def merge(self, other: "MinMaxRangePartial") -> None:
        self.mn = min(self.mn, other.mn)
        self.mx = max(self.mx, other.mx)

    def finalize(self) -> float:
        return self.mx - self.mn


class DistinctPartial(AggPartial):
    """Exact distinct value set for one group.

    ``values`` is either a Python set (small results, wire
    deserialization) or a UNIQUE numpy array (host/device bulk paths —
    at north-star cardinality a 4M-entry Python set costs tens of
    seconds per group to build, a vectorized gather milliseconds)."""

    def __init__(self, values: Optional[object] = None) -> None:
        self.values = values if values is not None else set()

    def merge(self, other: "DistinctPartial") -> None:
        a, b = self.values, other.values
        if isinstance(a, set) and isinstance(b, set):
            a |= b
            return
        na = np.asarray(sorted(a, key=repr)) if isinstance(a, set) else a
        nb = np.asarray(sorted(b, key=repr)) if isinstance(b, set) else b
        if na.size == 0:
            self.values = nb
        elif nb.size == 0:
            self.values = na
        else:
            self.values = np.union1d(na, nb)

    def iter_sorted(self):
        """Values in a deterministic order (serde contract)."""
        if isinstance(self.values, set):
            return sorted(self.values, key=repr)
        return np.sort(self.values).tolist()

    def finalize(self) -> int:
        return len(self.values) if isinstance(self.values, set) else int(self.values.size)


class HllPartial(AggPartial):
    def __init__(self, registers: Optional[np.ndarray] = None) -> None:
        self.registers = (
            registers.astype(np.uint8)
            if registers is not None
            else np.zeros(hll_mod.M, dtype=np.uint8)
        )

    def merge(self, other: "HllPartial") -> None:
        self.registers = np.maximum(self.registers, other.registers)

    def finalize(self) -> int:
        return int(hll_mod.estimate_from_registers(self.registers))


class HistogramPartial(AggPartial):
    """Exact value histogram for percentiles."""

    def __init__(self, counts: Optional[Dict[float, int]] = None, percentile: int = 50) -> None:
        self.counts: Dict[float, int] = counts or {}
        self.percentile = percentile

    def merge(self, other: "HistogramPartial") -> None:
        for v, c in other.counts.items():
            self.counts[v] = self.counts.get(v, 0) + c

    def finalize(self) -> float:
        """Reference formula sorted[int(n * p/100)]
        (quantile/PercentileUtil.java:50) over the histogram."""
        if not self.counts:
            return -math.inf
        items = sorted(self.counts.items())
        n = sum(c for _, c in items)
        idx = min(int(n * self.percentile / 100.0), n - 1)
        acc = 0
        for v, c in items:
            acc += c
            if acc > idx:
                return v
        return items[-1][0]


def make_partial(base_function: str) -> AggPartial:
    if base_function == "count":
        return CountPartial()
    if base_function == "sum":
        return SumPartial()
    if base_function == "min":
        return MinPartial()
    if base_function == "max":
        return MaxPartial()
    if base_function == "avg":
        return AvgPartial()
    if base_function == "minmaxrange":
        return MinMaxRangePartial()
    if base_function == "distinctcount":
        return DistinctPartial()
    if base_function in ("distinctcounthll", "fasthll"):
        return HllPartial()
    if base_function.startswith("percentileest"):
        return HistogramPartial(percentile=int(base_function[len("percentileest"):]))
    if base_function.startswith("percentile"):
        return HistogramPartial(percentile=int(base_function[len("percentile"):]))
    raise ValueError(f"unknown aggregation {base_function!r}")


GroupKey = Tuple[str, ...]


# Canonical per-query cost-vector keys (the execution-stats extension
# beyond the reference's numDocsScanned/numEntriesScanned* — see
# PARITY.md "Cost accounting").  Every value is additive, so the merge
# is a plain key-wise sum and the broker's totals are exactly the sum
# of the per-server totals (the invariant tests/test_cost.py holds):
#
#   bytesScanned       column bytes the serving path read (device: staged
#                      array bytes handed to the kernel, scaled by the
#                      zone-map candidate fraction; host: forward-index
#                      bytes of referenced columns; postings: O(matches))
#   deviceMs / hostMs  kernel-execution wall ms split by where the
#                      filter/aggregate work actually ran
#   deviceBytes        the DEVICE-TIER share of bytesScanned (staged
#                      array bytes the kernel read) — the utilization
#                      plane's achieved-bandwidth numerator; host/
#                      postings bytes never pollute the roofline
#   coalesceHits       queries served by riding an identical in-flight
#                      device dispatch (engine/dispatch.py)
#   qinputCacheHits    device-resident query-input cache hits
#   preparedHit        queries whose tier verdicts, plan, query inputs
#                      and block ids came from the executor's
#                      prepared-query memo (engine/executor.py _Prepared)
#   exprAggs           aggregates of the query whose argument is a
#                      compound expression (sum(a*(1-b))), on whatever
#                      tier answered (meters agg.expr.device|host)
#   numGroupsLive      groups with a row in a device group-by's
#                      fetched state, before the per-server trim
#   numGroupsKept      groups left after it (max(5 x TOP, 100) an
#                      aggregate, and ties; meters
#                      groupby.groups.live|kept)
#   groupStateSumSq    the sum of squares, in float64, of every live
#                      group's value of each aggregate whose state is
#                      dense floats (count, sum, min, max, avg,
#                      minmaxrange): a digest of the whole fetched
#                      state, where a reply of TOP n shows n groups.
#   groupStateHllSum   the sum of every live group's estimate of each
#                      aggregate whose state is dense HLL registers
#                      (distinctcounthll by group), as the trim computes
#                      them: an integer far under 2^53, so exact
#   groupStateHllSumSq the sum of their squares: with the one above the
#                      digest of a fetched register state
#                      These five are a server's own: the merge adds
#                      them, so with one answering server they are the
#                      query's, and with more a group live on two counts
#                      twice and the squares are of each server's part
#   batchHits          queries that rode a cross-query batched launch
#                      (literals stacked with same-plan peers into one
#                      vmapped kernel — the lane micro-batching tier)
#   rescacheHits       queries answered from the ingest-aware result
#                      cache (engine/rescache.py) — a hit marks ZERO
#                      device/host work by construction
#   segmentsPruned     segments dropped by metadata pruning, and those
#                      whose own dictionaries the filter empties, which
#                      no tier scans (pruner.py)
#   segmentsPostings   segments answered from host postings (invindex)
#   segmentsBitsliced  segments answered by the bit-sliced bulk-bitwise
#                      tier (engine/bitsliced.py — popcount-fused aggs)
#   segmentsZonemap    segments scanned via the zone-map block kernel
#   segmentsFullScan   segments scanned by the full device kernel
#   segmentsHost       segments served by the host path (forced,
#                      failover, or pair overflow)
#   segmentsStarTree   segments answered from their star-tree cube
#   buildRows          join build-side rows extracted / hash-table
#                      inserted (engine/join.py — dim-side work)
#   probeRows          join probe-side rows extracted / probed against
#                      the build hash table (fact-side work)
#   shuffleBytes       serialized join-exchange bytes a server RECEIVED
#                      in a shuffle join (the skew-balance observable:
#                      no server should receive >2x the mean)
#   broadcastBytes     serialized build-side bytes a server received in
#                      a broadcast join (one copy per probe server)
COST_KEYS = (
    "bytesScanned",
    "deviceMs",
    "hostMs",
    "deviceBytes",
    "coalesceHits",
    "qinputCacheHits",
    "preparedHit",
    "exprAggs",
    "numGroupsLive",
    "numGroupsKept",
    "groupStateSumSq",
    "groupStateHllSum",
    "groupStateHllSumSq",
    "batchHits",
    "rescacheHits",
    "buildRows",
    "probeRows",
    "shuffleBytes",
    "broadcastBytes",
    "segmentsPruned",
    "segmentsPostings",
    "segmentsBitsliced",
    "segmentsZonemap",
    "segmentsFullScan",
    "segmentsHost",
    "segmentsStarTree",
)

# Serving-tier subset of COST_KEYS — THE single source the introspection
# plane derives from (server cost.tier.* meters, EXPLAIN tier records,
# trace_dump's tier footer): a tier added here propagates everywhere.
# All but segmentsPruned partition numSegmentsQueried exactly.
SEGMENT_TIER_KEYS = tuple(k for k in COST_KEYS if k.startswith("segments"))

# cost-vector key -> short display tier name ("segmentsFullScan" ->
# "fullScan"), shared by EXPLAIN records and trace_dump's footer so the
# two surfaces can never render the same tier differently
SEGMENT_TIER_NAMES = {
    k: k[len("segments"):][0].lower() + k[len("segments"):][1:]
    for k in SEGMENT_TIER_KEYS
}


class IntermediateResult:
    """One executor's (server's) partial answer for a query — the unit
    that flows broker-ward and merges with peers
    (BrokerReduceService.reduceOnDataTable analog)."""

    def __init__(
        self,
        aggregations: Optional[List[AggPartial]] = None,
        groups: Optional[Dict[GroupKey, List[AggPartial]]] = None,
        selection_rows: Optional[List[Tuple[list, list]]] = None,  # (sort_key_values, row)
        num_docs_scanned: int = 0,
        total_docs: int = 0,
        num_segments_queried: int = 0,
        num_entries_scanned_in_filter: int = 0,
        num_entries_scanned_post_filter: int = 0,
        trace: Optional[Dict[str, Any]] = None,
        selection_columns: Optional[List[str]] = None,
        exceptions: Optional[List[Tuple[int, str]]] = None,
        unserved_segments: Optional[List[str]] = None,
        cost: Optional[Dict[str, float]] = None,
        plan_info: Optional[List[Dict[str, Any]]] = None,
    ) -> None:
        self.selection_columns = selection_columns
        self.exceptions: List[Tuple[int, str]] = exceptions or []
        # requested segments this server could not serve (dropped /
        # quarantined pending re-fetch): the broker re-covers them on a
        # replica or folds them into partialResponse/numSegmentsUnserved
        self.unserved_segments: List[str] = unserved_segments or []
        self.aggregations = aggregations
        self.groups = groups
        self.selection_rows = selection_rows
        self.num_docs_scanned = num_docs_scanned
        self.total_docs = total_docs
        self.num_segments_queried = num_segments_queried
        self.num_entries_scanned_in_filter = num_entries_scanned_in_filter
        self.num_entries_scanned_post_filter = num_entries_scanned_post_filter
        self.trace = trace or {}
        # per-query cost vector (COST_KEYS above): sparse — absent keys
        # mean zero, so empty-path results stay cheap to build and ship
        self.cost: Dict[str, float] = dict(cost or {})
        # per-REPLY saturation snapshot of the answering server (NOT
        # additive — never merged): {"pending", "maxPending", "laneDepth"}
        # set by ServerInstance.handle_request; the broker's admission
        # controller reads it to drive the per-server AIMD concurrency
        # window (shed early with 429 instead of feeding a saturated
        # server until 210s appear)
        self.backpressure: Dict[str, float] = {}
        # EXPLAIN / EXPLAIN ANALYZE plan trees: one JSON-safe node per
        # answering server (engine/explain.py), concatenated on merge
        # like traces (never summed) — the broker collects them into
        # BrokerResponse.explain["servers"]
        self.plan_info: List[Dict[str, Any]] = list(plan_info or [])
        # join-extract payload (engine/join.py SideRows wire dict):
        # columnar key/value arrays a join-extract phase returns to the
        # broker exchange.  NOT additive — the broker drains it before
        # the result joins the reduce merge; always None on the normal
        # single-table serving path.
        self.join_payload: Optional[Dict[str, Any]] = None
        # event-time freshness stamp (broker/freshness.py): for replies
        # covering realtime tables, {"minEventMs": <max consumed
        # event-time in ms, min over served partitions>}.  Merged with
        # MIN semantics — the broker's freshnessMs must reflect the
        # STALEST data that contributed to the answer.  None for
        # offline-only replies and for peers predating the audit plane.
        self.freshness: Optional[Dict[str, Any]] = None

    def add_cost(self, **kv: float) -> None:
        """Accumulate cost-vector components (key-wise add)."""
        for k, v in kv.items():
            if v:
                self.cost[k] = self.cost.get(k, 0) + v

    def merge(self, other: "IntermediateResult") -> None:
        self.exceptions.extend(other.exceptions)
        self.unserved_segments.extend(other.unserved_segments)
        self.plan_info.extend(other.plan_info)
        # freshness min-combines: an answer is only as fresh as its
        # stalest contributing realtime partition
        of = getattr(other, "freshness", None)
        if of is not None and of.get("minEventMs") is not None:
            mine = self.freshness
            if mine is None or mine.get("minEventMs") is None:
                self.freshness = dict(of)
            else:
                mine["minEventMs"] = min(mine["minEventMs"], of["minEventMs"])
        # cost vectors are additive by construction: the broker's merged
        # totals equal the sum of the per-server totals EXACTLY
        for k, v in other.cost.items():
            self.cost[k] = self.cost.get(k, 0) + v
        self.num_docs_scanned += other.num_docs_scanned
        self.total_docs += other.total_docs
        self.num_segments_queried += other.num_segments_queried
        self.num_entries_scanned_in_filter += other.num_entries_scanned_in_filter
        self.num_entries_scanned_post_filter += other.num_entries_scanned_post_filter
        # trace values are span LISTS keyed by scope: two partials from
        # the same scope concatenate instead of clobbering each other
        for scope, spans in other.trace.items():
            mine = self.trace.get(scope)
            if isinstance(mine, list) and isinstance(spans, list):
                self.trace[scope] = mine + spans
            else:
                self.trace[scope] = spans
        if other.aggregations is not None:
            if self.aggregations is None:
                self.aggregations = other.aggregations
            else:
                for mine, theirs in zip(self.aggregations, other.aggregations):
                    mine.merge(theirs)
        if other.groups is not None:
            if self.groups is None:
                self.groups = other.groups
            else:
                for key, partials in other.groups.items():
                    existing = self.groups.get(key)
                    if existing is None:
                        self.groups[key] = partials
                    else:
                        for mine, theirs in zip(existing, partials):
                            mine.merge(theirs)
        if other.selection_rows is not None:
            if self.selection_rows is None:
                self.selection_rows = other.selection_rows
            else:
                self.selection_rows.extend(other.selection_rows)
        if self.selection_columns is None:
            self.selection_columns = other.selection_columns


# Cap on boundary-tie groups admitted past the trim: final ordering
# breaks value ties by rendered key (which the trim cannot see), so
# tied-at-the-boundary groups are kept — but at huge key spaces a
# degenerate workload (e.g. COUNT(*) over near-unique keys, every group
# tied at 1) would otherwise re-admit millions of groups and defeat the
# trim entirely.  Beyond the cap a deterministic subset is kept: every
# group strictly beyond the boundary, then the tied groups in ascending
# index until both the cap and the trim are met, in either direction;
# the reference's per-server topN*5 trim makes the same non-guarantee
# for deep ties (MCombineGroupByOperator.java:216).
MAX_TRIM_TIES = 10_000


def trim_group_candidates(
    order_vals_list: List[np.ndarray],
    ascending_list: List[bool],
    top_n: int,
    k: int,
) -> np.ndarray:
    """Candidate group indices to keep after the per-server trim.

    ``order_vals_list`` holds one finalized-value array of shape [k] per
    aggregation; a group survives if it is within topN*5 (min 100) of
    any aggregation's ordering, or tied (capped) with that boundary.
    A selection, not a sort: one ``np.argpartition`` around the cut an
    aggregation, O(k), in ``np.sort``'s order (NaN last).  Returns sorted
    indices into [0, k).
    """
    trim = max(top_n * 5, 100)
    if k <= trim:
        return np.arange(k)
    keep = np.zeros(k, dtype=bool)
    for ov, asc in zip(order_vals_list, ascending_list):
        ov = np.asarray(ov)
        cut = trim - 1 if asc else k - trim
        part = np.argpartition(ov, cut)
        boundary = ov[part[cut]]
        nan_cut = boundary != boundary
        tied = ov != ov if nan_cut else ov == boundary
        # the cut's side holds every group strictly beyond the boundary
        # and whichever tied ones the selection left there: drop those,
        # and take the tied in ascending index
        inside = part[:trim] if asc else part[cut:]
        beyond = inside[~tied[inside]]
        ties = np.nonzero(tied)[0]
        room = trim - beyond.size
        if nan_cut:  # NaN equals nothing, so no tie is added: the NaN a stable sort leaves inside the cut
            ties = ties[:room] if asc else ties[-room:]
        else:
            ties = ties[: max(MAX_TRIM_TIES, room)]
        keep[beyond] = True
        keep[ties] = True
    return np.nonzero(keep)[0]
