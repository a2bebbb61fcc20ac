"""LLC (low-level consumer) realtime coordination.

Mirrors the reference's three-way dance (SURVEY §3.4):

- server: ``LLRealtimeSegmentDataManager.java:68`` — one consumer per
  stream partition appends into a mutable segment until a row/time
  threshold, then reports ``segmentConsumed(offset)`` to the controller.
- controller: ``SegmentCompletionManager.java:45-54`` — an FSM per
  consuming segment (HOLDING -> COMMITTER_DECIDED -> COMMITTER_UPLOADING
  -> COMMITTED) picks the max-offset replica as committer and answers
  each replica HOLD / CATCH_UP / COMMIT / KEEP / DISCARD / NOT_LEADER
  (``SegmentCompletionProtocol.java:63-105``).
- commit: the committer converts mutable -> immutable columnar, uploads;
  the controller persists metadata (exact start/end offsets — the
  checkpoint), flips replicas CONSUMING -> ONLINE (laggards download the
  committed copy), and opens the next CONSUMING segment at the end
  offset.  Restart resumes from the last committed end offset
  (``ValidationManager`` repairs missing consuming segments).

Segment naming: ``{table}__{partition}__{seq}`` (LLCSegmentName analog).
"""
from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from pinot_tpu.common.fencing import StaleEpochError, epoch_int
from pinot_tpu.common.schema import Schema, time_unit_to_millis
from pinot_tpu.common.tableconfig import StreamConfig, TableConfig
from pinot_tpu.controller.resource_manager import (
    CONSUMING,
    ClusterResourceManager,
    ONLINE,
)
from pinot_tpu.realtime.mutable import MutableSegment
from pinot_tpu.realtime.stream import StreamProvider

logger = logging.getLogger(__name__)

MAX_HOLD_TIME_MS = 3000  # SegmentCompletionProtocol.java:50


def _commit_stall_ms() -> float:
    """How long an elected committer may go protocol-silent (no
    segmentConsumed/segmentCommit calls) before the FSM re-elects a
    caught-up replica (the reference's max-segment-commit-time,
    ``controller.realtime.segment.commit.timeoutSeconds``).  Lease
    validity alone cannot catch this: under a ONE-WAY partition the
    victim's heartbeats keep renewing its controller-side lease while
    its self-fenced commit plane is frozen."""
    return float(os.environ.get("PINOT_TPU_COMMIT_STALL_S", "120")) * 1000.0

# FSM states (SegmentCompletionManager.java:48-54)
HOLDING = "HOLDING"
COMMITTER_DECIDED = "COMMITTER_DECIDED"
COMMITTER_UPLOADING = "COMMITTER_UPLOADING"
COMMITTED = "COMMITTED"

# responses (SegmentCompletionProtocol.java:63-105)
RESP_HOLD = "HOLD"
RESP_CATCH_UP = "CATCH_UP"
RESP_DISCARD = "DISCARD"
RESP_KEEP = "KEEP"
RESP_COMMIT = "COMMIT"
RESP_NOT_LEADER = "NOT_LEADER"


def make_segment_name(table: str, partition: int, seq: int) -> str:
    return f"{table}__{partition}__{seq}"


def parse_segment_name(name: str) -> Tuple[str, int, int]:
    table, partition, seq = name.rsplit("__", 2)
    return table, int(partition), int(seq)


class _SegmentFsm:
    def __init__(self, num_replicas: int) -> None:
        self.state = HOLDING
        self.num_replicas = num_replicas
        self.offsets: Dict[str, int] = {}
        self.committer: Optional[str] = None
        self.target_offset: Optional[int] = None
        self.final_offset: Optional[int] = None
        self.first_report_ms: Optional[float] = None
        self.commit_inflight = False  # an upload is being persisted
        # last protocol call from the elected committer (stall detector)
        self.committer_activity_ms: Optional[float] = None


class SegmentCompletionManager:
    """Controller-side commit FSM (SegmentCompletionManager.java:45).

    Partition fencing: every protocol call may carry the caller's
    serving-lease ``epoch`` (the controller incarnation that granted
    it); a mismatch against this controller's epoch raises a typed
    ``StaleEpochError`` — a committer leased by a dead controller
    cannot commit into a live one, and a zombie controller cannot
    accept commits leased by its successor.  ``lease_checker`` (wired
    by the Controller to ``ParticipantGateway.server_lease_valid``)
    lets the FSM re-elect when the chosen committer's lease expires
    mid-protocol (partitioned away mid-upload) instead of holding the
    partition's commit hostage forever; the commit-stall window
    (``PINOT_TPU_COMMIT_STALL_S``) re-elects a committer whose lease
    stays controller-side valid but whose commit plane went silent
    (one-way partition: heartbeats arrive, replies are lost)."""

    def __init__(self, realtime_manager: "RealtimeSegmentManager") -> None:
        self.rm = realtime_manager
        self._fsm: Dict[str, _SegmentFsm] = {}
        self._lock = threading.Lock()
        # (server) -> bool: does this replica still hold a valid
        # serving lease?  None = no lease plane (in-process harness).
        self.lease_checker = None
        self.commit_stall_ms = _commit_stall_ms()
        self.clock = time.time  # injectable for stall/hold tests

    def _get(self, segment: str) -> _SegmentFsm:
        fsm = self._fsm.get(segment)
        if fsm is None:
            replicas = self.rm.resources.get_ideal_state(
                self.rm.physical_table_of(segment)
            ).get(segment, {})
            fsm = _SegmentFsm(max(len(replicas), 1))
            self._fsm[segment] = fsm
        return fsm

    def _mark(self, name: str) -> None:
        metrics = getattr(self.rm, "metrics", None)
        if metrics is not None:
            metrics.meter(name).mark()

    def _check_epoch(self, epoch) -> None:
        """Reject a protocol call fenced off by controller failover.
        Unarmed when either side has no epoch (legacy / in-process)."""
        current = getattr(self.rm, "epoch", None)
        if current is None or epoch is None:
            return
        e = epoch_int(epoch)
        if e == -1:
            return
        if e != int(current):
            self._mark("fence.staleEpochRejections")
            # direction-aware message (fields keep their wire meaning:
            # staleEpoch = caller's, currentEpoch = this controller's):
            # an operator debugging the 409 must be pointed at the side
            # that is actually fenced off
            if e < int(current):
                msg = (
                    f"commit-plane call under stale lease epoch {e}; "
                    f"controller epoch is {current}"
                )
            else:
                msg = (
                    f"commit-plane call under lease epoch {e} from a "
                    f"newer controller incarnation; this controller "
                    f"(epoch {current}) is the fenced-off zombie"
                )
            raise StaleEpochError(msg, stale=e, current=int(current))

    def _committer_leased(self, fsm: _SegmentFsm) -> bool:
        if self.lease_checker is None or fsm.committer is None:
            return True
        try:
            return bool(self.lease_checker(fsm.committer))
        except Exception:  # a broken probe must not wedge the protocol
            return True

    def segment_consumed(
        self, segment: str, server: str, offset: int, epoch=None
    ) -> Tuple[str, Optional[int]]:
        """A replica hit its threshold at ``offset``. Returns
        (response, target_offset)."""
        self._check_epoch(epoch)
        with self._lock:
            fsm = self._get(segment)
            now = self.clock() * 1000

            if fsm.state == COMMITTED:
                if offset == fsm.final_offset:
                    return RESP_KEEP, fsm.final_offset
                return RESP_DISCARD, fsm.final_offset

            fsm.offsets[server] = offset
            if fsm.first_report_ms is None:
                fsm.first_report_ms = now

            if fsm.state == HOLDING:
                all_reported = len(fsm.offsets) >= fsm.num_replicas
                hold_expired = now - fsm.first_report_ms > MAX_HOLD_TIME_MS
                if not (all_reported or hold_expired):
                    return RESP_HOLD, None
                # decide committer: max offset wins (ties -> name order)
                fsm.committer = max(fsm.offsets, key=lambda s: (fsm.offsets[s], s))
                fsm.target_offset = fsm.offsets[fsm.committer]
                fsm.committer_activity_ms = now
                fsm.state = COMMITTER_DECIDED

            if fsm.state in (COMMITTER_DECIDED, COMMITTER_UPLOADING):
                assert fsm.target_offset is not None
                if server == fsm.committer:
                    fsm.committer_activity_ms = now
                if offset < fsm.target_offset:
                    return RESP_CATCH_UP, fsm.target_offset
                stalled = (
                    fsm.committer_activity_ms is not None
                    and now - fsm.committer_activity_ms > self.commit_stall_ms
                )
                if (
                    server != fsm.committer
                    and not fsm.commit_inflight
                    and (stalled or not self._committer_leased(fsm))
                ):
                    # committer failover: the elected committer's
                    # serving lease expired (partitioned away / died
                    # mid-upload), OR it went protocol-silent past the
                    # commit-stall window — under a ONE-WAY partition
                    # its heartbeats keep the controller-side lease
                    # alive while its self-fenced commit plane freezes,
                    # so lease validity alone cannot detect it.  No
                    # upload is being persisted — re-elect this
                    # caught-up replica.  The old committer's late
                    # segmentCommit lands on ``committer != server``
                    # below: NOT_LEADER, no double commit.
                    logger.warning(
                        "committer %s for %s %s; re-electing %s",
                        fsm.committer, segment,
                        "stalled past the commit window" if stalled
                        else "lost its lease",
                        server,
                    )
                    self._mark("fence.committerReElections")
                    fsm.committer = server
                    fsm.committer_activity_ms = now
                    fsm.state = COMMITTER_DECIDED
                if server == fsm.committer and not fsm.commit_inflight:
                    # COMMITTER_UPLOADING here (not inflight) means a
                    # previous commit attempt FAILED (e.g. the
                    # controller had just restarted): re-issue COMMIT so
                    # the committer retries instead of holding forever.
                    # While an upload is actually being persisted the
                    # committer holds — no duplicate commit.
                    fsm.state = COMMITTER_UPLOADING
                    return RESP_COMMIT, fsm.target_offset
                return RESP_HOLD, fsm.target_offset
        return RESP_HOLD, None

    def commit_fence_check(self, segment: str, server: str, epoch=None):
        """Cheap pre-upload fence: raises the typed ``StaleEpochError``
        or returns ``NOT_LEADER`` for a caller with no write authority,
        so the HTTP surface can reject a fenced upload before buffering
        and parsing megabytes of segment body.  Advisory only — the
        authoritative fences re-run under the lock in
        ``segment_commit`` (a lease can expire between the two)."""
        self._check_epoch(epoch)
        with self._lock:
            fsm = self._fsm.get(segment)
            if fsm is not None and fsm.committer == server:
                # upload starting: the body transfer that follows can
                # legitimately outlast the commit-stall window — stamp
                # activity NOW so a slow upload isn't mistaken for a
                # silent (partitioned) committer and re-elected away
                fsm.committer_activity_ms = self.clock() * 1000
        if self.lease_checker is not None:
            try:
                leased = bool(self.lease_checker(server))
            except Exception:
                leased = True
            if not leased:
                self._mark("fence.leaseRejections")
                return RESP_NOT_LEADER
        return None

    def segment_commit(self, segment: str, server: str, committed, epoch=None) -> str:
        """Committer uploads its converted segment (segmentCommit).

        The FSM flips to COMMITTED only AFTER the metadata/ideal-state
        persistence succeeds — a failure (controller freshly restarted,
        replica not re-registered yet) leaves the FSM in
        COMMITTER_UPLOADING so the committer's next segmentConsumed
        retries the commit rather than wedging on KEEP/HOLD.

        Fencing order: stale epoch raises (typed), an expired lease is
        NOT_LEADER (the replica may retry after renewing), and a
        non-committer is NOT_LEADER — so a committer partitioned away
        mid-upload can never land a second copy after re-election.
        """
        self._check_epoch(epoch)
        with self._lock:
            fsm = self._get(segment)
            if server == fsm.committer:
                fsm.committer_activity_ms = self.clock() * 1000
            if self.lease_checker is not None:
                try:
                    leased = bool(self.lease_checker(server))
                except Exception:
                    leased = True
                if not leased:
                    # lease fence FIRST (even over the COMMITTED
                    # short-circuit): an upload arriving without write
                    # authority is always rejected — the replica must
                    # renew its lease and learn the final verdict via
                    # segmentConsumed (KEEP/DISCARD) instead
                    self._mark("fence.leaseRejections")
                    return RESP_NOT_LEADER
            if fsm.state == COMMITTED:
                return RESP_KEEP  # duplicate upload after a lost reply
            if fsm.committer != server or fsm.state != COMMITTER_UPLOADING:
                return RESP_NOT_LEADER
            if fsm.commit_inflight:
                # a previous upload of this segment is still being
                # persisted (slow request + client retry): hold rather
                # than run on_segment_committed twice concurrently
                return RESP_HOLD
            fsm.commit_inflight = True
        try:
            self.rm.on_segment_committed(segment, committed)
        except Exception:
            with self._lock:
                fsm.commit_inflight = False
            raise
        with self._lock:
            fsm.commit_inflight = False
            fsm.state = COMMITTED
            fsm.final_offset = fsm.target_offset
        return RESP_KEEP


class RealtimeSegmentManager:
    """Controller-side realtime coordinator
    (PinotLLCRealtimeSegmentManager analog): creates CONSUMING segments,
    persists commit metadata, opens the next sequence."""

    def __init__(self, resources: ClusterResourceManager, store, metrics=None) -> None:
        self.resources = resources
        self.store = store
        # optional ControllerMetrics: realtime commit-plane series
        # (segmentCommits meter + segmentCommitMs persistence timer)
        self.metrics = metrics
        # optional IngestConsumerPool (realtime/pool.py): when set,
        # every in-process consumer this manager creates is driven by
        # the pool's bounded workers instead of waiting for manual
        # consume_step calls — the partition-parallel ingest plane
        self.ingest_pool = None
        # controller fencing incarnation (set by the Controller): arms
        # the commit-plane epoch fence in SegmentCompletionManager
        self.epoch: Optional[int] = None
        if metrics is not None:
            metrics.meter("segmentCommits")
            metrics.timer("segmentCommitMs")
        self.completion = SegmentCompletionManager(self)
        self._tables: Dict[str, Dict[str, Any]] = {}  # physical -> {schema, stream, config}
        self._consumers: Dict[Tuple[str, str], "RealtimeSegmentDataManager"] = {}
        self._lock = threading.Lock()
        # serializes consuming-segment creation: commit-time creation,
        # the periodic ValidationManager tick, and the server-available
        # repair kick can all race the check-then-create otherwise
        self._create_lock = threading.Lock()

    # -- setup ---------------------------------------------------------
    def setup_table(
        self, config: TableConfig, schema: Schema, stream: StreamProvider
    ) -> str:
        physical = self.resources.add_table(config)
        with self._lock:
            self._tables[physical] = {
                "schema": schema,
                "stream": stream,
                "config": config,
            }
        if self.resources.property_store is not None:
            from pinot_tpu.realtime.stream import describe_stream

            desc = describe_stream(stream)
            if desc is not None:
                self.resources.property_store.put("streams", physical, desc)
        if config.stream is not None and config.stream.consumer_type == "highlevel":
            # HLC: one consumer per SERVER (not per partition) in a
            # broker-coordinated group; segments are server-owned and
            # roll locally (HLRealtimeSegmentDataManager.java:54)
            self.ensure_hlc_consumers(physical)
        else:
            for partition in range(stream.partition_count()):
                self._create_consuming_segment(physical, partition, seq=0, start_offset=0)
        return physical

    def update_schema(self, raw_name: str, schema: Schema) -> List[str]:
        """Schema evolution for realtime tables: swap the stored schema
        so the NEXT segment rollover consumes with the grown schema
        (CONSUMING transitions serialize it as schemaJson).  The
        currently-consuming segment keeps its frozen schema — its rows
        get default columns when it seals, matching the reference's
        apply-at-rollover behavior."""
        updated = []
        with self._lock:
            for physical, tinfo in self._tables.items():
                if tinfo["config"].raw_name == raw_name:
                    tinfo["schema"] = schema
                    updated.append(physical)
        return updated

    def _is_hlc(self, physical: str) -> bool:
        with self._lock:
            tinfo = self._tables.get(physical)
        return bool(
            tinfo
            and tinfo["config"].stream is not None
            and tinfo["config"].stream.consumer_type == "highlevel"
        )

    def ensure_hlc_consumers(self, physical: str) -> None:
        """Every live server gets one CONSUMING segment for an HLC
        table (new servers join the group when they register — the
        server-available repair hook calls this too)."""
        if not self._is_hlc(physical):
            return
        with self.resources._lock:
            live = sorted(
                name
                for name, inst in self.resources.instances.items()
                if inst.role == "server" and inst.alive and not inst.draining
            )
        ideal = self.resources.get_ideal_state(physical)
        # ownership from the pinned replica sets (sealed uploads replace
        # segment metadata, so custom keys are NOT a reliable record);
        # track the highest seq per idx so recreated consumers never
        # collide with a historical sealed segment name
        owners = set()
        max_seq: Dict[int, int] = {}
        idx_last: Dict[int, set] = {}  # replica set of the newest segment per idx
        consuming_idx = set()
        for seg, replicas in ideal.items():
            try:
                _, idx, seq = parse_segment_name(seg)
            except ValueError:
                continue
            if seq > max_seq.get(idx, -1):
                max_seq[idx] = seq
                idx_last[idx] = set(replicas)
            if CONSUMING in replicas.values():
                owners.update(replicas)
                consuming_idx.add(idx)
        next_idx = 0
        for server in live:
            if server in owners:
                continue
            # Mid-roll (sealed upload flipped the entry ONLINE before the
            # roll registered the successor) or crash-after-seal: the
            # server still owns the idx whose newest segment is pinned to
            # it.  Continue that idx at the next sequence — the name
            # matches what the server's own /realtime/hlc/roll would
            # register, so both paths dedupe instead of this tick opening
            # a phantom CONSUMING segment at a fresh idx that no consumer
            # will ever serve.
            resumed = False
            for idx in sorted(max_seq):
                if idx not in consuming_idx and server in idx_last.get(idx, ()):
                    self._create_hlc_segment(
                        physical, server, idx, seq=max_seq[idx] + 1
                    )
                    # mark the idx consumed so a second live server in
                    # the same replica set (replication > 1 after a
                    # rebalance) doesn't no-op on the deduped name and
                    # end the tick with no CONSUMING segment at all —
                    # it falls through to a fresh idx instead
                    max_seq[idx] += 1
                    consuming_idx.add(idx)
                    resumed = True
                    break
            if resumed:
                continue
            while next_idx in max_seq:
                next_idx += 1
            max_seq[next_idx] = -1
            self._create_hlc_segment(
                physical, server, next_idx, seq=max_seq[next_idx] + 1
            )

    def register_hlc_roll(self, physical: str, server: str, idx: int, seq: int) -> str:
        """A server sealed its HLC segment and continues locally on the
        next sequence: record the new CONSUMING segment so routing
        covers it (the server already serves it)."""
        if not self._is_hlc(physical):
            raise ValueError(f"{physical} is not a highlevel-consumer table")
        return self._create_hlc_segment(physical, server, idx, seq)

    def _create_hlc_segment(self, physical: str, server: str, idx: int, seq: int) -> str:
        from pinot_tpu.segment.immutable import SegmentMetadata

        name = make_segment_name(physical, idx, seq)
        with self._create_lock:
            if name in self.resources.get_ideal_state(physical):
                return name
            with self._lock:
                tinfo = self._tables.get(physical)
            from pinot_tpu.realtime.stream import describe_stream

            desc = describe_stream(tinfo["stream"]) if tinfo else None
            meta = SegmentMetadata(
                segment_name=name,
                table_name=physical,
                num_docs=0,
                custom={
                    "partition": idx,
                    "seq": seq,
                    "hlcServer": server,
                    "status": "IN_PROGRESS",
                },
            )
            info: Dict[str, Any] = {
                "partition": idx,
                "startOffset": 0,
                "consumerType": "highlevel",
                "hlcServer": server,
            }
            if desc is not None:
                info["streamDescriptor"] = desc
            if tinfo is not None:
                info["rowsPerSegment"] = (
                    tinfo["config"].stream.rows_per_segment
                    if tinfo["config"].stream
                    else 100_000
                )
                info["schemaJson"] = tinfo["schema"].to_json()
            self.resources.add_segment(
                physical, meta, info, target_state=CONSUMING, servers=[server]
            )
            return name

    def recover_table(self, physical: str, config: TableConfig, schema: Schema) -> bool:
        """Rebuild the in-memory realtime wiring for a table restored
        from the property store: reattach the stream provider and put
        ``consuming_starter`` callbacks back on every CONSUMING
        segment's metadata record so re-registering servers resume
        consumption from the checkpointed offsets (the reference
        resumes from the per-segment ZK offsets on restart, SURVEY §5
        checkpoint/resume)."""
        store = self.resources.property_store
        if store is None:
            return False
        desc = store.get("streams", physical)
        if desc is None:
            return False
        from pinot_tpu.realtime.stream import stream_from_descriptor

        stream = stream_from_descriptor(desc)
        with self._lock:
            self._tables[physical] = {
                "schema": schema,
                "stream": stream,
                "config": config,
            }
        with self.resources._lock:
            for (tbl, seg), info in self.resources.segment_metadata.items():
                if tbl != physical:
                    continue
                replicas = self.resources.ideal_states.get(physical, {}).get(seg, {})
                if CONSUMING in replicas.values():
                    info["consuming_starter"] = self._start_consumer
        return True

    def physical_table_of(self, segment: str) -> str:
        return parse_segment_name(segment)[0]

    def _create_consuming_segment(
        self, physical: str, partition: int, seq: int, start_offset: int
    ) -> str:
        name = make_segment_name(physical, partition, seq)
        with self._create_lock:
            if name in self.resources.get_ideal_state(physical):
                return name  # idempotent: a concurrent path created it
            return self._create_consuming_segment_locked(
                physical, partition, seq, start_offset, name
            )

    def _create_consuming_segment_locked(
        self, physical: str, partition: int, seq: int, start_offset: int, name: str
    ) -> str:
        from pinot_tpu.segment.immutable import SegmentMetadata

        meta = SegmentMetadata(
            segment_name=name,
            table_name=physical,
            num_docs=0,
            custom={
                "partition": partition,
                "seq": seq,
                "startOffset": start_offset,
                "status": "IN_PROGRESS",
            },
        )
        info: Dict[str, Any] = {
            "consuming_starter": self._start_consumer,
            "partition": partition,
            "startOffset": start_offset,
        }
        # serializable consume spec: lets REMOTE participants (separate
        # server processes) start a consumer from the transition message
        # alone, and survives in the property store for recovery
        with self._lock:
            tinfo = self._tables.get(physical)
        if tinfo is not None:
            from pinot_tpu.realtime.stream import describe_stream

            desc = describe_stream(tinfo["stream"])
            if desc is not None:
                info["streamDescriptor"] = desc
            info["rowsPerSegment"] = (
                tinfo["config"].stream.rows_per_segment
                if tinfo["config"].stream
                else 100_000
            )
            info["schemaJson"] = tinfo["schema"].to_json()
        self.resources.add_segment(
            physical,
            meta,
            info,
            target_state=CONSUMING,
        )
        return name

    # -- server-side consumer creation (via ServerStarter CONSUMING) --
    def _start_consumer(self, server_instance, table: str, segment: str, info: Dict[str, Any]) -> bool:
        if info.get("consumerType") == "highlevel":
            # HLC consumers live in networked server processes (the
            # group coordinator is the stream broker); the in-process
            # harness supports LLC tables only
            logger.warning("in-process cluster cannot host HLC consumer %s", segment)
            return False
        with self._lock:
            tinfo = self._tables.get(table)
            if (segment, server_instance.name) in self._consumers:
                return True  # already consuming; don't reset the offset
        if tinfo is None:
            return False
        dm = RealtimeSegmentDataManager(
            server=server_instance,
            manager=self,
            table=table,
            segment_name=segment,
            schema=tinfo["schema"],
            stream=tinfo["stream"],
            partition=int(info["partition"]),
            start_offset=int(info["startOffset"]),
            rows_per_segment=tinfo["config"].stream.rows_per_segment
            if tinfo["config"].stream
            else 100_000,
        )
        with self._lock:
            self._consumers[(segment, server_instance.name)] = dm
        server_instance.add_segment(table, dm.mutable)
        pool = self.ingest_pool
        if pool is not None:
            pool.add(dm, key=(segment, server_instance.name))
        return True

    def consumers_of(self, segment: str) -> List["RealtimeSegmentDataManager"]:
        with self._lock:
            return [dm for (seg, _), dm in self._consumers.items() if seg == segment]

    def release_segment_consumers(self, segment: str, server: Optional[str] = None) -> None:
        """Stop and forget in-process consumers of ``segment`` — all of
        them, or only ``server``'s (the stabilizer retires a consuming
        segment whose holders are all dead/draining, or sheds one
        unavailable replica of a still-consuming segment; a stale map
        entry would make a later CONSUMING transition on the same
        (segment, server) resume the OLD mutable with uncommitted rows
        instead of re-consuming from the committed offset)."""
        with self._lock:
            for key in [
                k
                for k in self._consumers
                if k[0] == segment and (server is None or k[1] == server)
            ]:
                self._consumers[key].stop()
                del self._consumers[key]
                if self.ingest_pool is not None:
                    self.ingest_pool.remove(key)

    # -- commit --------------------------------------------------------
    def on_segment_committed(self, segment: str, committed) -> None:
        t0 = time.perf_counter()
        physical, partition, seq = parse_segment_name(segment)
        path = self.store.save(physical, committed)
        end_offset = committed.metadata.custom.get("endOffset", 0)
        # persist metadata (the ZK offset checkpoint) + flip replicas ONLINE
        with self.resources._lock:
            self.resources.segment_metadata[(physical, segment)] = {
                "metadata": committed.metadata,
                "dir": path,
                "segment": committed,
            }
            replicas = self.resources.ideal_states[physical].get(segment, {})
            for server in replicas:
                replicas[server] = ONLINE
        self.resources.persist_ideal_state(physical)
        self.resources.persist_segment_record(physical, segment)
        for server in list(replicas):
            self.resources._execute_transition(physical, segment, server, ONLINE)
        self.resources._notify_view(physical)
        # retire consumers for this segment
        with self._lock:
            for key in [k for k in self._consumers if k[0] == segment]:
                self._consumers[key].stop()
                del self._consumers[key]
                if self.ingest_pool is not None:
                    self.ingest_pool.remove(key)
        if self.metrics is not None:
            self.metrics.meter("segmentCommits").mark()
            self.metrics.timer("segmentCommitMs").update(
                (time.perf_counter() - t0) * 1000
            )
        # open the next consuming segment at the committed end offset;
        # a transient failure (no replica re-registered yet after a
        # controller restart) must NOT fail the commit itself — the
        # ValidationManager recreates missing CONSUMING segments
        # (ensure_consuming_segments, ValidationManager.java:64)
        try:
            self._create_consuming_segment(physical, partition, seq + 1, int(end_offset))
        except Exception as e:
            logger.warning(
                "could not open next consuming segment for %s partition %d "
                "(validation repair will retry): %s",
                physical,
                partition,
                e,
            )

    # -- validation hook ----------------------------------------------
    def ensure_consuming_segments(self) -> None:
        """Re-create missing CONSUMING segments
        (ValidationManager.java:64 LLC repair)."""
        with self._lock:
            tables = list(self._tables.keys())
        for physical in tables:
            if self._is_hlc(physical):
                # HLC repair: every live server must be consuming
                self.ensure_hlc_consumers(physical)
                continue
            ideal = self.resources.get_ideal_state(physical)
            with self._lock:
                stream = self._tables[physical]["stream"]
            for partition in range(stream.partition_count()):
                has_consuming = False
                max_seq, max_end = -1, 0
                for seg, replicas in ideal.items():
                    try:
                        _, p, seq = parse_segment_name(seg)
                    except ValueError:
                        continue
                    if p != partition:
                        continue
                    if any(st == CONSUMING for st in replicas.values()):
                        has_consuming = True
                    info = self.resources.get_segment_metadata(physical, seg)
                    if info and info.get("metadata") is not None and seq > max_seq:
                        max_seq = seq
                        max_end = int(info["metadata"].custom.get("endOffset", 0))
                if not has_consuming:
                    logger.info(
                        "validation: recreating consuming segment %s p%d seq%d @%d",
                        physical, partition, max_seq + 1, max_end,
                    )
                    self._create_consuming_segment(
                        physical, partition, max_seq + 1, max_end
                    )


class RealtimeSegmentDataManager:
    """Server-side per-partition consumer
    (LLRealtimeSegmentDataManager.java:68)."""

    def __init__(
        self,
        server,
        manager: RealtimeSegmentManager,
        table: str,
        segment_name: str,
        schema: Schema,
        stream: StreamProvider,
        partition: int,
        start_offset: int,
        rows_per_segment: int,
    ) -> None:
        self.server = server
        self.manager = manager
        self.table = table
        self.segment_name = segment_name
        self.stream = stream
        self.partition = partition
        self.offset = start_offset
        self.rows_per_segment = rows_per_segment
        # cooperative-pool idle cadence (realtime/pool.py): how long a
        # paused/empty/HOLDing consumer stays off its pool worker
        self.poll_interval_s = 0.05
        # rows one pool step may consume (columnar topics serve whole
        # 64k blocks — the ingest ladder raises this to block size so
        # throughput runs aren't bounded by trim-and-refetch)
        self.step_rows = 1000
        self.mutable = MutableSegment(schema, segment_name, table)
        self.mutable.start_offset = start_offset
        self._stopped = False
        self._thread: Optional[threading.Thread] = None
        # None = untried; True/False once the stream's columnar support
        # for this partition is known (columnar topics carry whole
        # binary blocks; row-JSON topics raise on fetchc misuse)
        self._columnar: Optional[bool] = None
        # ingest observability: per-partition consumer-lag gauge (latest
        # available stream offset − consumed offset; reads live via
        # set_fn) + rows/s and commit-latency series on the hosting
        # server's registry.  Rolling to the next sequence re-registers
        # the same gauge name, so the series is continuous per
        # (table, partition) across segment commits.
        self._metrics = getattr(server, "metrics", None)
        from pinot_tpu.realtime.stream import LagProbe

        self._lag_probe = LagProbe(stream, partition, lambda: self.offset)
        self._lag_gauge_name = f"ingest.lag.{table}.p{partition}"
        # ingest backpressure: the hosting server's watermark governor
        # (pause above the HBM/mutable high watermark, resume below the
        # low) + a per-consumer paused gauge for per-partition visibility
        self._governor = getattr(server, "ingest_backpressure", None)
        self._paused = False
        self._paused_gauge_name = f"ingest.paused.{table}.p{partition}"
        self._paused_fn = lambda: 1 if self._paused else 0
        # event-time freshness (broker/freshness.py): every indexed
        # batch advances the process-wide (table, partition) watermark
        # to the max of the schema time column; the per-partition lag
        # gauge (now − watermark, ms) re-registers across segment
        # rollover exactly like ingest.lag.* — the series is continuous
        # per (table, partition)
        from pinot_tpu.broker.freshness import WATERMARKS, now_ms

        self._time_col = schema.time_column_name
        self._time_unit_ms = (
            time_unit_to_millis(schema.time_field.time_unit)
            if schema.time_field is not None
            else None
        )
        self._freshness_gauge_name = f"freshness.lag.{table}.p{partition}"

        def _freshness_probe(_t=table, _p=partition):
            w = WATERMARKS.get(_t, _p)
            return round(max(0.0, now_ms() - w), 3) if w is not None else 0
        self._freshness_fn = _freshness_probe
        if self._metrics is not None:
            lag_key = f"{table}.p{partition}"
            self._metrics.gauge(f"ingest.lag.{lag_key}").set_fn(self._lag_probe)
            self._metrics.gauge(f"ingest.paused.{lag_key}").set_fn(self._paused_fn)
            if self._time_col is not None:
                self._metrics.gauge(f"freshness.lag.{lag_key}").set_fn(
                    self._freshness_fn
                )

    def lag(self) -> Optional[int]:
        """Consumer lag in rows: latest available offset on this
        partition minus the consumed offset (0 = fully caught up);
        TTL-cached + failure-degrading (realtime/stream.py LagProbe)."""
        return self._lag_probe()

    def stop(self) -> None:
        self._stopped = True
        # detach the lag gauge: a stopped consumer's frozen offset must
        # not keep reporting (phantom, ever-growing) lag when the
        # partition's successor lands on another server.  The equality
        # guard in clear_fn keeps this safe if a successor on THIS
        # server already re-registered the same series.
        if self._metrics is not None:
            self._metrics.gauge(self._lag_gauge_name).clear_fn(self._lag_probe)
            self._metrics.gauge(self._paused_gauge_name).clear_fn(self._paused_fn)
            self._metrics.gauge(self._freshness_gauge_name).clear_fn(
                self._freshness_fn
            )

    def _mark_rows(self, n: int) -> None:
        if n and self._metrics is not None:
            self._metrics.meter("ingest.rowsConsumed").mark(int(n))

    def _notify_offset_advance(self) -> None:
        """Result-cache watermark hook (engine/rescache.py): the
        consume offset moved, so every cached answer over this table's
        previous watermark is superseded — drop it eagerly.  The
        cache's staging-token key fence already made those entries
        unreachable; this keeps memory and hit-rate honest."""
        cache = getattr(self.server, "result_cache", None)
        if cache is not None and cache.enabled:
            cache.on_offset_advance(self.table, self.partition, self.offset)

    def _advance_watermark(self, time_values) -> None:
        """Event-time watermark advance for one indexed batch
        (broker/freshness.py; monotone — replays can never regress it)."""
        if self._time_unit_ms is None:
            return
        from pinot_tpu.broker.freshness import WATERMARKS, batch_max_event_ms

        event_ms = batch_max_event_ms(time_values, self._time_unit_ms)
        if event_ms is not None:
            WATERMARKS.advance(self.table, self.partition, event_ms)

    # -- consumption ---------------------------------------------------
    def _fetch_and_index(self, limit: int) -> int:
        """One fetch + index against the stream, preferring the
        columnar block path when the provider and partition support it
        (netstream producec topics: np.frombuffer decode + vectorized
        dictionary encode).
        Returns rows consumed and advances the offset."""
        fetch_cols = getattr(self.stream, "fetch_columns", None)
        if self._columnar is not False and fetch_cols is not None:
            try:
                cols, n, next_offset = fetch_cols(self.partition, self.offset)
            except RuntimeError as e:
                # Only a DEFINITIVE broker verdict may latch row mode:
                # the broker's typed "row-mode partition" rejection, or
                # a broker that doesn't know the fetchc op at all.  A
                # transient transport error must re-raise whether the
                # mode is KNOWN-columnar (the broker rejects row fetches
                # there forever) or still UNKNOWN — latching False on a
                # first-fetch hiccup would wedge ingest on a columnar
                # partition until restart (the consume loop retries the
                # raised error next step instead).
                msg = str(e)
                if "row-mode" in msg or "unknown op" in msg:
                    self._columnar = False  # row-mode partition / no fetchc support
                else:
                    raise
            # any other exception (socket, decode) propagates: never
            # evidence of the partition's mode — always retryable
            else:
                self._columnar = True
                if n <= 0:
                    return 0
                if n > limit:
                    # blocks serve whole; respect the segment budget and
                    # resume MID-block next step (the provider trims)
                    cols = {c: a[:limit] for c, a in cols.items()}
                    next_offset = next_offset - (n - limit)
                    n = limit
                try:
                    self.mutable.index_columns(cols)
                except ValueError:
                    # MV schema / NaN payloads: decode to rows once and
                    # take the row path for this batch
                    names = list(cols)
                    self.mutable.index_batch(
                        [
                            {c: cols[c][i].item() for c in names}
                            for i in range(n)
                        ]
                    )
                self.offset = next_offset
                self.mutable.end_offset = next_offset
                self._mark_rows(n)
                if self._time_col is not None:
                    self._advance_watermark(cols.get(self._time_col))
                self._notify_offset_advance()
                return n
        rows, next_offset = self.stream.fetch(self.partition, self.offset, limit)
        self.mutable.index_batch(rows)
        advanced = next_offset != self.offset
        self.offset = next_offset
        self.mutable.end_offset = next_offset
        self._mark_rows(len(rows))
        if rows and self._time_col is not None:
            self._advance_watermark(
                [r.get(self._time_col) for r in rows if self._time_col in r]
            )
        if advanced:
            self._notify_offset_advance()
        return len(rows)

    def consume_step(self, max_rows: int = 1000) -> int:
        """Fetch + index one (bounded) batch; returns rows consumed.
        Returns 0 WITHOUT touching the stream while the server's ingest
        governor holds consumption above a memory watermark — the offset
        freezes, lag grows visibly, nothing is dropped or skipped."""
        if self._stopped:
            return 0
        if self._governor is not None:
            allowed = self._governor.consume_allowed()
            self._paused = not allowed
            if not allowed:
                return 0
            max_rows = self._governor.clamp_batch(max_rows)
        budget = self.rows_per_segment - self.mutable.num_docs
        if budget <= 0:
            return 0
        return self._fetch_and_index(min(max_rows, budget))

    @property
    def threshold_reached(self) -> bool:
        return self.mutable.num_docs >= self.rows_per_segment

    def step(self) -> Optional[float]:
        """One cooperative pool unit (realtime/pool.py): a bounded
        consume batch, plus one completion-protocol round at the row
        threshold.  Returns seconds until this consumer is eligible
        again, or None when finished (committed/discarded/stopped —
        the successor sequence gets its own consumer).  Never blocks:
        a backpressure pause, an empty stream, or a completion HOLD
        all surface as an idle delay so the shared workers stay free
        for the other partitions."""
        if self._stopped:
            return None
        got = self.consume_step(self.step_rows)
        if self.threshold_reached:
            resp = self.try_commit()
            if self._stopped or resp in (RESP_KEEP, RESP_DISCARD):
                # on_segment_committed retires this consumer (stop());
                # KEEP/DISCARD mean the sequence is settled elsewhere
                return None
            # HOLD / CATCH_UP / NOT_LEADER / lease-frozen: retry later
            return self.poll_interval_s
        if self._paused or got == 0:
            return self.poll_interval_s
        return 0.0

    def try_commit(self) -> str:
        """Run the completion protocol once
        (segmentConsumed -> maybe segmentCommit).  A server whose
        serving lease expired has no write authority: the round is
        frozen (HOLD) — offsets keep, nothing is lost — until the
        lease renews."""
        if self._stopped:
            return RESP_DISCARD
        lease = getattr(self.server, "lease", None)
        epoch = None
        if lease is not None:
            if not lease.held():
                if self._metrics is not None:
                    self._metrics.meter("lease.blockedCommits").mark()
                return RESP_HOLD
            if lease.granted:
                epoch = lease.epoch
        completion = self.manager.completion
        resp, target = completion.segment_consumed(
            self.segment_name, self.server.name, self.offset, epoch=epoch
        )
        if resp == RESP_CATCH_UP and target is not None:
            while self.offset < target and not self._stopped:
                if self._fetch_and_index(target - self.offset) == 0:
                    break
            return resp
        if resp == RESP_COMMIT:
            t0 = time.perf_counter()
            committed = self.mutable.to_committed_segment()
            out = completion.segment_commit(
                self.segment_name, self.server.name, committed, epoch=epoch
            )
            # commit latency: mutable->immutable conversion + the
            # controller persistence round (the ingest stall window)
            if self._metrics is not None:
                self._metrics.timer("ingest.commitMs").update(
                    (time.perf_counter() - t0) * 1000
                )
            return out
        return resp
