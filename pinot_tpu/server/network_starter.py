"""Networked server starter: a server process joining a remote controller.

The in-process ``ServerStarter`` receives transitions as direct
callbacks; this variant is the real-deployment analog of
``HelixServerStarter.java:63`` + ``SegmentFetcherAndLoader.java:84``:

- register with the controller over HTTP (PARTICIPANT join),
- heartbeat for liveness (the ZK session),
- poll transition messages, execute them (download segment bytes from
  the controller's store with CRC skip, load into the query engine, or
  drop), ack the resulting state,
- serve broker queries on a length-framed TCP socket.

All state the controller needs rides in the register/ack payloads; the
server keeps a local segment cache under ``data_dir`` so a restart with
matching CRCs skips downloads.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

from pinot_tpu.common.schema import Schema
from pinot_tpu.controller.resource_manager import CONSUMING, DROPPED, OFFLINE, ONLINE
from pinot_tpu.realtime.mutable import MutableSegment
from pinot_tpu.segment.format import (
    SEGMENT_FILE_NAME,
    SegmentIntegrityError,
    SegmentStaleError,
    read_segment,
    verify_segment_crc,
)
from pinot_tpu.server.instance import ServerInstance
from pinot_tpu.transport.tcp import TcpServer

logger = logging.getLogger(__name__)


class ServerAdminHttpServer:
    """Server-side observability HTTP surface (the reference server's
    admin-application analog): ``/health``, Prometheus text at
    ``/metrics``, the full status/metrics JSON at ``/debug/metrics``,
    per-plan stats at ``/debug/plans``, the device-utilization
    snapshot at ``/debug/device``, the mesh topology + per-lane
    dispatch stats at ``/debug/mesh``, and the on-demand profiler bracket
    at ``POST /debug/profile/start|stop`` (``GET /debug/profile`` for
    state).  The query data plane stays on the framed TCP socket; this
    port is scrape/ops-only.  The networked starter advertises it to
    the controller as the instance URL so the dashboard can aggregate
    a cluster-wide metrics snapshot."""

    def __init__(self, server: ServerInstance, host: str = "127.0.0.1", port: int = 0):
        inst = server

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def _send(self, body: bytes, ctype: str, status: int = 200) -> None:
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _send_json(self, payload, status: int = 200) -> None:
                self._send(
                    json.dumps(payload).encode("utf-8"), "application/json", status
                )

            def do_GET(self):
                if self.path == "/health":
                    return self._send(b'{"status": "ok"}', "application/json")
                if self.path == "/metrics":
                    return self._send(
                        inst.metrics_text().encode("utf-8"),
                        "text/plain; version=0.0.4",
                    )
                if self.path == "/debug/metrics":
                    return self._send(
                        json.dumps(inst.status()).encode("utf-8"),
                        "application/json",
                    )
                if self.path == "/debug/device":
                    # utilization snapshot alone (status() minus the
                    # heavyweight sections): the controller rollup and
                    # dashboards poll this cheaply
                    return self._send_json(inst.device_utilization())
                if self.path == "/debug/mesh":
                    # mesh execution plane (engine/mesh.py): topology
                    # snapshot + per-lane dispatch stats — which chip
                    # group serves which lane, rolled up
                    return self._send_json(
                        {
                            "topology": inst.topology.snapshot(),
                            "lanes": None
                            if inst.lanes is None
                            else inst.lanes.stats(),
                        }
                    )
                if self.path == "/debug/profile":
                    return self._send_json(inst.profiler.snapshot())
                if self.path == "/debug/prewarm":
                    # warm-start readiness surface (server/prewarm.py):
                    # warming/ready flag + pass counters
                    return self._send_json(inst.prewarm.state())
                if self.path == "/debug/flightrec":
                    return self._send_json(inst.flightrec.snapshot())
                if self.path == "/debug/residency":
                    # tiered residency plane (engine/residency.py):
                    # per-tier bytes/entries, cap pressure, and the
                    # demotion/promotion cycle counters
                    from pinot_tpu.engine.residency import RESIDENCY

                    return self._send_json(RESIDENCY.snapshot())
                if self.path == "/debug/segments":
                    # per-segment CRC map for the controller's
                    # cross-replica checksum sweep (CrcAuditManager)
                    return self._send_json(inst.segment_crcs())
                if (
                    self.path.startswith("/segments/")
                    and self.path.endswith("/copy")
                ):
                    # reverse replication donor: the DeepStoreScrubber
                    # repairing a lost/corrupt deep-store copy pulls the
                    # verified bytes of this server's replica
                    p = self.path.strip("/").split("/")
                    if len(p) == 4:
                        data = inst.segment_copy_bytes(p[1], p[2])
                        if data is not None:
                            return self._send(data, "application/octet-stream")
                    return self._send(
                        b'{"error": "segment not donatable"}',
                        "application/json",
                        404,
                    )
                if self.path == "/debug/audit":
                    # shadow-audit plane (utils/audit.py): sampler
                    # counters, quarantined (digest, tier) pairs, and
                    # the recent-divergence ring
                    return self._send_json(inst.audit_snapshot())
                from urllib.parse import parse_qs, urlparse

                url = urlparse(self.path)
                if url.path == "/debug/history":
                    # bounded metric time series (utils/timeseries.py):
                    # ?series= comma-separated name prefixes, ?windowS=
                    # trailing window in seconds
                    return self._send_json(
                        inst.history.query_from_qs(url.query)
                    )
                if url.path == "/debug/plans":
                    # per-plan-digest workload stats (utils/planstats.py);
                    # ?by=cost reorders the top-K by total work instead
                    # of frequency
                    qs = parse_qs(url.query)
                    by = (qs.get("by") or ["count"])[0]
                    try:
                        top = int((qs.get("top") or ["50"])[0])
                    except ValueError:
                        top = 50
                    return self._send(
                        json.dumps(
                            inst.plan_stats.snapshot(top=top, by=by)
                        ).encode("utf-8"),
                        "application/json",
                    )
                self._send(b'{"error": "not found"}', "application/json", 404)

            def do_POST(self):
                from pinot_tpu.server.profiler import ProfilerUnavailableError

                n = int(self.headers.get("Content-Length", "0") or 0)
                raw = self.rfile.read(n) if n else b""
                try:
                    body = json.loads(raw) if raw else {}
                except ValueError:
                    return self._send_json({"error": "bad JSON body"}, 400)
                if self.path == "/debug/profile/start":
                    try:
                        return self._send_json(
                            inst.profile_start(body.get("timeoutS"))
                        )
                    except ProfilerUnavailableError as e:
                        # typed 404: THIS backend has no usable profiler
                        # — distinct from an unknown route or bad input
                        return self._send_json(
                            {
                                "error": str(e),
                                "errorType": "ProfilerUnavailableError",
                            },
                            404,
                        )
                    except Exception as e:
                        return self._send_json({"error": str(e)}, 500)
                if self.path == "/debug/profile/stop":
                    return self._send_json(inst.profile_stop())
                self._send_json({"error": "not found"}, 404)

        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> None:
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


class RemoteConsumer:
    """Server-process-side LLC consumer: pulls rows from the stream
    broker by offset, indexes into a mutable segment served to queries
    immediately, and runs the completion protocol against the
    controller over HTTP (the ``LLRealtimeSegmentDataManager.java:68``
    consume loop + ``SegmentCompletionProtocol`` client).

    Since r15 consumers are COOPERATIVE: instead of one dedicated
    thread per consuming segment (which melts down at 100+ tables),
    each consumer exposes ``step()`` — one bounded, never-blocking unit
    of consume/commit work — and the starter's shared
    ``IngestConsumerPool`` (``PINOT_TPU_INGEST_CONSUMERS`` workers)
    drives all of them.  Every wait the old loop slept through
    (backpressure pause, empty stream, completion HOLD, controller
    freeze) now surfaces as the step's return delay, so a frozen
    partition costs zero worker time and N hot partitions genuinely
    consume in parallel."""

    def __init__(
        self,
        starter: "NetworkedServerStarter",
        table: str,
        segment: str,
        msg: Dict[str, Any],
        poll_interval_s: float = 0.2,
    ) -> None:
        from pinot_tpu.common.schema import Schema
        from pinot_tpu.realtime.mutable import MutableSegment
        from pinot_tpu.realtime.stream import stream_from_descriptor

        self.starter = starter
        self.table = table
        self.segment = segment
        self.partition = int(msg.get("partition", 0))
        self.offset = int(msg.get("startOffset", 0))
        self.rows_per_segment = int(msg.get("rowsPerSegment", 100_000))
        self.poll_interval_s = poll_interval_s
        self.stream = stream_from_descriptor(msg["streamDescriptor"])
        schema = Schema.from_json(msg["schemaJson"])
        self.mutable = MutableSegment(schema, segment, table)
        self.mutable.start_offset = self.offset
        self._stop = threading.Event()
        # controller unreachability is a FREEZE, not a failure: offsets
        # hold, the consumer survives, and retries back off with full
        # jitter (utils/retry.py) so a healing controller is not
        # stampeded by every frozen consumer at once.  The backoff's
        # delay parks this consumer in the pool (``_park_s``) instead
        # of blocking a shared worker.
        from pinot_tpu.utils.retry import FullJitterBackoff

        self._ctrl_backoff = FullJitterBackoff(
            initial_s=max(0.1, poll_interval_s), cap_s=5.0
        )
        # seconds the NEXT pool step should wait before re-driving this
        # consumer; set by the protocol paths (HOLD/freeze) per round
        self._park_s = poll_interval_s
        # ingest observability (same series as the in-process consumer,
        # realtime/llc.py): per-partition lag gauge + rows/s meter.
        # The TTL-cached probe (realtime/stream.py LagProbe) keeps the
        # stream-broker RPC off the metrics-scrape path.
        from pinot_tpu.realtime.stream import LagProbe

        self._metrics = getattr(starter.server, "metrics", None)
        self._lag_probe = LagProbe(self.stream, self.partition, lambda: self.offset)
        self._lag_gauge_name = f"ingest.lag.{table}.p{self.partition}"
        # ingest backpressure: the hosting server's watermark governor
        # pauses consumption above the HBM/mutable high watermark; the
        # per-consumer paused gauge makes the held partition visible
        self._governor = getattr(starter.server, "ingest_backpressure", None)
        self._paused = False
        self._paused_gauge_name = f"ingest.paused.{table}.p{self.partition}"
        self._paused_fn = lambda: 1 if self._paused else 0
        # event-time freshness (broker/freshness.py): this consumer
        # advances the per-(table, partition) watermark from the schema
        # time column as it indexes — the same series the in-process
        # consumer (realtime/llc.py) reports, keyed so rollover and
        # pool resizes keep it continuous
        from pinot_tpu.broker.freshness import WATERMARKS, now_ms
        from pinot_tpu.common.schema import time_unit_to_millis

        self._time_col = schema.time_column_name
        self._time_unit_ms = (
            time_unit_to_millis(schema.time_field.time_unit)
            if schema.time_field is not None
            else None
        )
        self._freshness_gauge_name = f"freshness.lag.{table}.p{self.partition}"

        def _freshness_probe(_t=table, _p=self.partition):
            w = WATERMARKS.get(_t, _p)
            return round(max(0.0, now_ms() - w), 3) if w is not None else 0

        self._freshness_fn = _freshness_probe
        if self._metrics is not None:
            lag_key = f"{table}.p{self.partition}"
            self._metrics.gauge(f"ingest.lag.{lag_key}").set_fn(self._lag_probe)
            self._metrics.gauge(f"ingest.paused.{lag_key}").set_fn(self._paused_fn)
            if self._time_col is not None:
                self._metrics.gauge(f"freshness.lag.{lag_key}").set_fn(
                    self._freshness_fn
                )

    def lag(self) -> Optional[int]:
        return self._lag_probe()

    def _detach_lag_gauge(self) -> None:
        """Stop reporting lag once this consumer is done: a frozen
        offset would otherwise read as phantom ever-growing lag when
        the partition's successor lives on another server.  clear_fn's
        equality guard makes this a no-op if a rolled successor on this
        server already owns the series."""
        if self._metrics is not None:
            self._metrics.gauge(self._lag_gauge_name).clear_fn(self._lag_probe)
            self._metrics.gauge(self._paused_gauge_name).clear_fn(self._paused_fn)
            self._metrics.gauge(self._freshness_gauge_name).clear_fn(
                self._freshness_fn
            )

    def start(self) -> None:
        self.starter.server.add_segment(self.table, self.mutable)
        self.starter.ingest_pool.add(self, key=self.segment)

    def stop(self) -> None:
        self._stop.set()
        self._detach_lag_gauge()

    # -- consume loop ---------------------------------------------------
    def _consume_to(self, limit_rows: int) -> int:
        budget = limit_rows - self.mutable.num_docs
        if budget <= 0:
            return 0
        if self._governor is not None:
            # bounded in-flight batches: one governor decision covers at
            # most max_batch_rows of exposure (the r6 path fetched a
            # whole segment budget in ONE call)
            budget = self._governor.clamp_batch(budget)
        rows, next_offset = self.stream.fetch(self.partition, self.offset, budget)
        self.mutable.index_batch(rows)
        if rows and self._time_col is not None and self._time_unit_ms is not None:
            from pinot_tpu.broker.freshness import WATERMARKS, batch_max_event_ms

            event_ms = batch_max_event_ms(
                [r.get(self._time_col) for r in rows if self._time_col in r],
                self._time_unit_ms,
            )
            if event_ms is not None:
                WATERMARKS.advance(self.table, self.partition, event_ms)
        advanced = next_offset != self.offset
        self.offset = next_offset
        self.mutable.end_offset = next_offset
        if rows and self._metrics is not None:
            self._metrics.meter("ingest.rowsConsumed").mark(len(rows))
        if advanced:
            # result-cache watermark hook (engine/rescache.py): cached
            # answers over the previous consume offset are superseded
            cache = getattr(self.starter.server, "result_cache", None)
            if cache is not None and cache.enabled:
                cache.on_offset_advance(self.table, self.partition, self.offset)
        return len(rows)

    def step(self) -> Optional[float]:
        """One cooperative pool unit: a bounded consume batch plus (at
        the row threshold) one completion-protocol round.  Returns the
        seconds until this consumer is eligible again, or None when the
        segment is finished (committed/discarded/stopped) — the
        CONSUMING transition for the next sequence registers a fresh
        consumer under the same per-(table, partition) gauge names."""
        if self._stop.is_set():
            self._detach_lag_gauge()
            return None
        if self._governor is not None:
            allowed = self._governor.consume_allowed()
            self._paused = not allowed
            if not allowed:
                # held above a memory watermark: offset freezes, lag
                # grows on the gauge, nothing is lost — consumption
                # resumes below the low watermark
                return self.poll_interval_s
        try:
            got = self._consume_to(self.rows_per_segment)
        except Exception as e:
            logger.warning("stream fetch failed for %s: %s", self.segment, e)
            return self.poll_interval_s
        if self.mutable.num_docs >= self.rows_per_segment:
            self._park_s = self.poll_interval_s
            if self._completion_round():
                # finished: this consumer's offset is frozen, so its
                # lag series must not keep reporting; a rolled
                # successor re-registers the same name
                self._detach_lag_gauge()
                return None
            return self._park_s
        return 0.0 if got else self.poll_interval_s

    def _freeze(self, why: str, err) -> bool:
        """Controller unreachable (or authority lost) mid-protocol:
        freeze the round — offset untouched, consumer alive — and park
        for a full-jitter backoff before the pool retries it."""
        self._park_s = self._ctrl_backoff.next_delay()
        logger.warning(
            "%s for %s frozen (retry in %.2fs): %s",
            why, self.segment, self._park_s, err,
        )
        return False

    def _completion_round(self) -> bool:
        """One segmentConsumed exchange; True when this consumer is
        done.  Never blocks — idle verdicts (HOLD, freeze, failed
        commit) set ``_park_s`` and return False so the pool re-drives
        this consumer after the delay."""
        lease = self.starter.server.lease
        if not lease.held():
            # write authority expired (partitioned past the lease
            # window): no segmentConsumed/commit until it renews — the
            # live controller may be re-electing a committer right now
            if self._metrics is not None:
                self._metrics.meter("lease.blockedCommits").mark()
            return self._freeze("completion round", "serving lease expired")
        epoch = lease.epoch if lease.granted else None
        try:
            out = self.starter._post(
                "/realtime/consumed",
                {
                    "segment": self.segment,
                    "server": self.starter.name,
                    "offset": self.offset,
                    "epoch": epoch,
                },
            )
        except Exception as e:
            return self._freeze("segmentConsumed", e)
        self._ctrl_backoff.reset()
        resp = out.get("response")
        target = out.get("targetOffset")
        if resp == "COMMIT":
            try:
                return self._commit(epoch)
            except Exception as e:
                # conversion/serialization failure: stay alive and retry
                # via the next segmentConsumed round
                logger.warning("commit of %s failed: %s", self.segment, e)
                self._park_s = self.poll_interval_s
                return False
        if resp == "CATCH_UP" and target is not None:
            while self.offset < int(target) and not self._stop.is_set():
                try:
                    got = self._consume_to(
                        self.rows_per_segment + int(target) - self.offset
                    )
                except Exception as e:
                    # transient stream failure mid-catch-up: keep the
                    # consumer alive, retry on the next round
                    logger.warning("catch-up fetch failed for %s: %s", self.segment, e)
                    self._park_s = self.poll_interval_s
                    return False
                if got == 0:
                    # stream has no more rows toward the target yet:
                    # yield the worker, resume catching up next step
                    self._park_s = self.poll_interval_s
                    return False
            return False
        if resp == "DISCARD":
            # another replica committed a different offset range: drop
            # local rows; the ONLINE transition will download the
            # committed copy
            self.starter.server.remove_segment(self.table, self.segment)
            return True
        if resp == "KEEP":
            # committed elsewhere at exactly our offset; keep serving
            # the local rows until the ONLINE transition replaces them
            return True
        # HOLD (or unknown): retry after the poll cadence
        self._park_s = self.poll_interval_s
        return False

    def _commit(self, epoch=None) -> bool:
        t0 = time.perf_counter()
        committed = self.mutable.to_committed_segment()
        path = f"/realtime/commit/{self.segment}/{self.starter.name}"
        if epoch is not None:
            # the lease epoch fences this upload: a controller failover
            # mid-upload typed-rejects it (409 StaleEpochError) instead
            # of double-committing into the new incarnation
            path += f"?epoch={epoch}"
        try:
            out = self.starter.upload_segment_bytes(path, committed)
        except Exception as e:
            # unreachable mid-upload: freeze-and-retry (the controller
            # may have persisted the copy and lost only the reply — the
            # next segmentConsumed answers KEEP/DISCARD idempotently)
            return self._freeze("segmentCommit", e)
        if out.get("response") != "KEEP":
            # NOT_LEADER / HOLD (commit already being persisted by a
            # prior attempt, or our lease/leadership was fenced away):
            # retry via the next segmentConsumed round
            return False
        if self._metrics is not None:
            self._metrics.timer("ingest.commitMs").update(
                (time.perf_counter() - t0) * 1000
            )
        logger.info("committed %s at offset %d", self.segment, self.offset)
        return True


class HLRemoteConsumer:
    """High-level-consumer ingestion for one server (the
    ``HLRealtimeSegmentDataManager.java:54`` analog): this server is
    one member of the table's consumer group; the stream broker assigns
    it partitions and rebalances on membership change.  Rows index into
    a server-owned mutable segment; at the row threshold the segment
    converts and uploads pinned to this server, group offsets commit,
    and consumption rolls locally to the next sequence (no committer
    election — HLC segments have exactly one owner).  Delivery is
    at-least-once across rebalances, as in the reference."""

    rolls_locally = True  # ONLINE of a sealed HLC segment must not stop us

    def __init__(self, starter: "NetworkedServerStarter", table: str, segment: str, msg: Dict[str, Any]) -> None:
        from pinot_tpu.realtime.llc import parse_segment_name
        from pinot_tpu.realtime.netstream import HLConsumer

        self.starter = starter
        self.table = table
        self.segment = segment
        _, self.idx, self.seq = parse_segment_name(segment)
        self.rows_per_segment = int(msg.get("rowsPerSegment", 100_000))
        self.poll_interval_s = float(msg.get("pollIntervalS", 0.2))
        desc = msg["streamDescriptor"]
        if desc.get("type") == "kafka":
            # consumer groups over the Kafka wire protocol (0.9+ group
            # coordinator APIs, realtime/kafka_group.py)
            from pinot_tpu.realtime.kafka_group import KafkaGroupConsumer as _Consumer
        else:
            _Consumer = HLConsumer
        self.consumer = _Consumer(
            desc["host"], int(desc["port"]), desc["topic"],
            group=table, consumer_id=starter.name,
            session_timeout=float(msg.get("sessionTimeoutS", 10.0)),
        )
        self.consumer.on_revoke = self._on_revoke
        self.schema = Schema.from_json(msg["schemaJson"])
        self.mutable = MutableSegment(self.schema, segment, table)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self.starter.server.add_segment(self.table, self.mutable)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        try:
            self.consumer.close()
        except Exception:
            pass

    def _run(self) -> None:
        try:
            joined = False
            while not self._stop.is_set():
                if not joined:
                    try:
                        self.consumer.join()
                        joined = True
                    except Exception as e:
                        # stream broker not reachable yet: keep trying —
                        # a one-shot join would strand the consumer
                        logger.warning("HLC join failed for %s: %s", self.segment, e)
                        self._stop.wait(self.poll_interval_s)
                        continue
                try:
                    budget = self.rows_per_segment - self.mutable.num_docs
                    rows = self.consumer.poll() if budget > 0 else []
                except Exception as e:
                    logger.warning("HLC poll failed for %s: %s", self.segment, e)
                    self._stop.wait(self.poll_interval_s)
                    continue
                self.mutable.index_batch([row for _, row in rows])
                if self.mutable.num_docs >= self.rows_per_segment:
                    if not self._seal_and_roll():
                        self._stop.wait(self.poll_interval_s)
                elif not rows:
                    self._stop.wait(self.poll_interval_s)
        except Exception:
            logger.exception("HLC consumer for %s died", self.segment)

    def _on_revoke(self) -> None:
        """Rebalance revoked (part of) our assignment: uncommitted rows
        must become durable before a successor resumes, so seal + upload
        + commit now (tiny segments are fine; rebalances are rare).  If
        the upload fails, DISCARD the uncommitted rows instead — they
        stay uncommitted, so the successor re-reads them; keeping them
        in our mutable would double-count."""
        if self.mutable.num_docs == 0:
            try:
                self.consumer.commit()
            except Exception as e:
                # every consumed row is already durable in sealed
                # segments; a failed commit only means a successor
                # re-reads from older committed offsets (at-least-once)
                logger.warning("HLC revoke-time offset commit failed: %s", e)
            return
        try:
            sealed = self._seal_and_roll()
        except Exception:
            # e.g. to_committed_segment() failed: fall through to the
            # discard path — the hook must leave the member in a known
            # state rather than raise into the consumer
            logger.exception("HLC seal during revoke failed for %s", self.segment)
            sealed = False
        if not sealed:
            old = self.segment
            self.mutable = MutableSegment(self.schema, self.segment, self.table)
            self.starter.server.add_segment(self.table, self.mutable)
            # the discarded rows were never persisted NOR committed:
            # roll positions back to committed so whoever owns these
            # partitions next (possibly still us) re-fetches them
            try:
                self.consumer.reset_to_committed()
            except Exception as e:
                logger.warning("HLC position rollback failed: %s", e)
            logger.warning(
                "HLC revoke: upload failed; discarded uncommitted rows of %s", old
            )

    def _seal_and_roll(self) -> bool:
        import urllib.parse

        from pinot_tpu.realtime.llc import make_segment_name

        committed = self.mutable.to_committed_segment()
        try:
            self.starter.upload_segment_bytes(
                f"/segments/{urllib.parse.quote(self.table)}?server={self.starter.name}",
                committed,
            )
        except Exception as e:
            logger.warning("HLC upload of %s failed (will retry): %s", self.segment, e)
            return False
        # segment durable on the controller: checkpoint group offsets,
        # then continue on the next sequence (at-least-once on a crash
        # between upload and commit — the reference's HLC contract)
        try:
            self.consumer.commit()
        except Exception as e:
            logger.warning("HLC offset commit failed for %s: %s", self.segment, e)
        old = self.segment
        self.seq += 1
        self.segment = make_segment_name(self.table, self.idx, self.seq)
        self.mutable = MutableSegment(self.schema, self.segment, self.table)
        # re-key BEFORE notifying the controller so the CONSUMING
        # transition for the new name dedupes against this consumer
        self.starter._consumers.pop(old, None)
        self.starter._consumers[self.segment] = self
        self.starter.server.add_segment(self.table, self.mutable)
        try:
            self.starter._post(
                "/realtime/hlc/roll",
                {"table": self.table, "server": self.starter.name,
                 "idx": self.idx, "seq": self.seq},
            )
        except Exception as e:
            # routing misses the new consuming segment until the
            # validation/repair tick re-registers it; data is safe
            logger.warning("HLC roll notify failed for %s: %s", self.segment, e)
        logger.info("HLC sealed %s (%d rows), rolled to %s", old, committed.num_docs, self.segment)
        return True


class NetworkedServerStarter:
    def __init__(
        self,
        controller_url: str,
        name: str,
        host: str = "127.0.0.1",
        port: int = 0,
        data_dir: Optional[str] = None,
        heartbeat_interval_s: float = 1.0,
        poll_interval_s: float = 0.3,
        admin_port: int = 0,
        fault_injector=None,
    ) -> None:
        self.controller_url = controller_url.rstrip("/")
        self.name = name
        self.server = ServerInstance(name)
        self.tcp = TcpServer(self.server.handle_request, host=host, port=port)
        # ops/scrape surface: /health, /metrics (Prometheus), /debug/metrics
        self.admin = ServerAdminHttpServer(self.server, host=host, port=admin_port)
        self.data_dir = data_dir
        self.heartbeat_interval_s = heartbeat_interval_s
        self.poll_interval_s = poll_interval_s
        # link-level chaos hook: every controller-bound HTTP call routes
        # through the injector as link (name -> "controller"), so a chaos
        # harness can cut/delay/duplicate this server's control plane
        self.fault_injector = fault_injector
        # partition-riding backoffs (full jitter, utils/retry.py): the
        # heartbeat and message loops keep their cadence while healthy
        # and back off jittered while the controller is unreachable, so
        # a healing controller is not hammered by the fleet in lockstep.
        # The HEARTBEAT backoff is capped below the controller's
        # advertised liveness timeout (tightened from the register
        # reply): under an ASYMMETRIC partition our requests still
        # arrive even though replies are lost, and backing off past the
        # timeout would flap this live server dead at the controller.
        from pinot_tpu.utils.retry import FullJitterBackoff

        self._hb_backoff = FullJitterBackoff(
            initial_s=max(0.1, heartbeat_interval_s), cap_s=2.0
        )
        # per-request timeout for heartbeat-loop RPCs, tightened with
        # the backoff cap (_tighten_hb_backoff) so a blackholed request
        # fails well before the liveness window elapses
        self._hb_timeout_s = 10.0
        self._msg_backoff = FullJitterBackoff(
            initial_s=max(0.1, poll_interval_s), cap_s=10.0
        )
        self._local_crcs: Dict[str, int] = {}
        self._consumers: Dict[str, RemoteConsumer] = {}  # segment -> consumer
        # partition-parallel ingest plane (realtime/pool.py): ONE
        # bounded worker pool drives every LLC consumer on this server
        # (PINOT_TPU_INGEST_CONSUMERS workers), so 100+ consuming
        # tables cost a fixed thread budget and N hot partitions
        # consume concurrently
        from pinot_tpu.realtime.pool import IngestConsumerPool

        self.ingest_pool = IngestConsumerPool(
            metrics=self.server.metrics, name=name
        )
        self._stop = threading.Event()
        # cross-signal wake: a heartbeat SUCCEEDING while the message
        # poll is deep in backoff means the controller is reachable
        # again — poll now instead of sleeping out the backoff window
        # (bounds recovery time: pending ONLINE transitions re-ack fast)
        self._msg_wake = threading.Event()
        self._threads: list = []
        # fleet plan prewarming (server/prewarm.py): the worker pulls
        # the controller's merged top-K workload for the tables this
        # server hosts; segment loads (ONLINE transitions) trigger the
        # passes, and the warming flag rides every heartbeat so the
        # controller can gate rebalance trims and tell the brokers
        self.server.prewarm.workload_source = self._fetch_workload

    # -- HTTP helpers --------------------------------------------------
    def _link(self, fn):
        """Run one controller-bound RPC through the link injector."""
        from pinot_tpu.common.faults import call_on_controller_link

        return call_on_controller_link(
            self.fault_injector, self.name, fn, metrics=self.server.metrics
        )

    def upload_segment_bytes(self, path: str, segment) -> Dict[str, Any]:
        """Serialize a committed segment and POST it to the controller
        (shared by the LLC committer and HLC seal paths)."""
        import tempfile

        from pinot_tpu.segment.format import write_segment

        with tempfile.TemporaryDirectory() as td:
            write_segment(segment, td)
            with open(os.path.join(td, SEGMENT_FILE_NAME), "rb") as f:
                data = f.read()

        def send():
            req = urllib.request.Request(
                self.controller_url + path,
                data=data,
                headers={"Content-Type": "application/octet-stream"},
            )
            with urllib.request.urlopen(req, timeout=120) as r:
                return json.loads(r.read())

        return self._link(send)

    def _post(
        self, path: str, payload: Dict[str, Any], timeout_s: float = 10.0
    ) -> Dict[str, Any]:
        def send():
            req = urllib.request.Request(
                self.controller_url + path,
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=timeout_s) as r:
                return json.loads(r.read())

        return self._link(send)

    def _get(self, path: str) -> Dict[str, Any]:
        def send():
            with urllib.request.urlopen(self.controller_url + path, timeout=10) as r:
                return json.loads(r.read())

        return self._link(send)

    def _fetch_workload(self, tables, n) -> list:
        """Prewarm workload feed: the controller's fleet-merged top-K
        plan shapes, narrowed to the given tables."""
        import urllib.parse

        qs = f"?n={int(n)}"
        if tables:
            qs += "&tables=" + urllib.parse.quote(",".join(tables))
        out = self._get("/debug/workload" + qs)
        return out.get("topByCount") or out.get("top") or []

    # -- lifecycle -----------------------------------------------------
    def start(self) -> None:
        # Initialize the jax backend on the MAIN thread before any
        # query can arrive.  A server that cannot reach its device does
        # not start: a lazy first initialization inside a scheduler
        # worker would turn a missing chip into failed queries later.
        import jax

        jax.devices()
        self.tcp.start()
        self.admin.start()
        out = self._post(
            "/instances",
            {
                "name": self.name,
                "role": "server",
                "addr": [self.tcp.address[0], self.tcp.address[1]],
                # admin URL rides the registration so the controller
                # dashboard can aggregate this server's /debug/metrics
                "url": self.admin.url,
            },
        )
        # first serving lease rides the registration reply
        self.server.lease.renew(out.get("lease"))
        self._tighten_hb_backoff(out)
        for fn in (self._heartbeat_loop, self._message_loop):
            t = threading.Thread(target=fn, daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        self._stop.set()
        self._msg_wake.set()  # unblock a message loop deep in backoff
        for consumer in list(self._consumers.values()):
            consumer.stop()
        self.ingest_pool.stop()
        for t in self._threads:
            t.join(timeout=2)
        self.tcp.stop()
        self.admin.stop()

    def _tighten_hb_backoff(self, reply: Dict[str, Any]) -> None:
        """Keep the worst-case heartbeat gap under the controller's
        liveness timeout (see __init__ on asymmetric partitions): the
        backoff cap AND the per-request timeout each take a third of
        the advertised window — a blackholed request blocking for the
        default 10s would exceed the 6s default window on its own."""
        timeout = reply.get("heartbeatTimeoutSeconds")
        if timeout:
            from pinot_tpu.utils.retry import tighten_liveness_budget

            self._hb_timeout_s = tighten_liveness_budget(
                self._hb_backoff, float(timeout), self._hb_timeout_s
            )

    def _heartbeat_loop(self) -> None:
        wait_s = self.heartbeat_interval_s
        unreachable = self.server.metrics.gauge("controller.unreachable")
        while not self._stop.wait(wait_s):
            try:
                out = self._post(
                    f"/instances/{self.name}/heartbeat",
                    # warm-start readiness rides the liveness beat: the
                    # controller folds it into the cluster state (broker
                    # deprioritization) and the rebalancer's trim gate
                    {"warming": bool(self.server.prewarm.warming)},
                    timeout_s=self._hb_timeout_s,
                )
                # drain ack: the controller tells us (on the heartbeat it
                # already makes) that an operator is draining this host;
                # surfaced in status() so ops tooling sees the ack
                self.server.draining = bool(out.get("draining"))
                # serving-lease renewal rides the same reply: write
                # authority extends lease_s from NOW.  A "held" reply
                # (flap hysteresis) carries no lease — correctly so.
                self.server.lease.renew(out.get("lease"))
                if out.get("reregister"):
                    reg = self._post(
                        "/instances",
                        {
                            "name": self.name,
                            "role": "server",
                            "addr": [self.tcp.address[0], self.tcp.address[1]],
                            "url": self.admin.url,
                        },
                        timeout_s=self._hb_timeout_s,
                    )
                    self.server.lease.renew(reg.get("lease"))
                if self._hb_backoff.failures:
                    # controller back after an outage: wake the message
                    # loop out of its backoff so queued transitions
                    # (e.g. pending ONLINE re-acks) land immediately,
                    # and kick frozen consumers out of their backoff
                    # parks (their next protocol round will now land)
                    self._msg_backoff.reset()
                    self._msg_wake.set()
                    self.ingest_pool.kick()
                self._hb_backoff.reset()
                unreachable.set(0)
                wait_s = self.heartbeat_interval_s
            except Exception as e:
                # partitioned from the controller: ride it out — serve
                # from local state, let the lease run down (write
                # authority self-fences), and retry with full jitter so
                # the fleet doesn't stampede the healing controller
                self.server.metrics.meter("controller.heartbeatFailures").mark()
                unreachable.set(1)
                wait_s = self._hb_backoff.next_delay()
                logger.warning(
                    "heartbeat to controller failed (%d consecutive, "
                    "retry in %.2fs): %s", self._hb_backoff.failures, wait_s, e,
                )

    def _message_loop(self) -> None:
        wait_s = self.poll_interval_s
        while True:
            if self._msg_wake.wait(timeout=wait_s):
                self._msg_wake.clear()
                wait_s = self.poll_interval_s
            if self._stop.is_set():
                return
            try:
                msgs = self._get(f"/instances/{self.name}/messages")["messages"]
                self._msg_backoff.reset()
                wait_s = self.poll_interval_s
            except Exception as e:
                wait_s = self._msg_backoff.next_delay()
                logger.warning(
                    "message poll failed (retry in %.2fs): %s", wait_s, e
                )
                continue
            for msg in msgs:
                self._handle(msg)

    # -- transitions ---------------------------------------------------
    def _handle(self, msg: Dict[str, Any]) -> None:
        table, segment, target = msg["table"], msg["segment"], msg["target"]
        if target == CONSUMING and not self.server.lease.held():
            # lease fence on WRITE authority: a server that cannot renew
            # its lease must not take on NEW consuming roles (another
            # replica may already own this partition as far as the live
            # controller is concerned).  Don't ack: the at-least-once
            # board redelivers once the lease renews.
            self.server.metrics.meter("lease.blockedTransitions").mark()
            logger.warning(
                "deferring CONSUMING %s/%s: serving lease expired",
                table, segment,
            )
            return
        try:
            if target == ONLINE:
                # CONSUMING -> ONLINE: retire the consumer before the
                # committed immutable copy replaces the mutable.  An HLC
                # consumer rolls itself to the next sequence (it may
                # still be keyed under the sealed name for an instant) —
                # never stop it here.
                consumer = self._consumers.get(segment)
                if consumer is not None and not getattr(consumer, "rolls_locally", False):
                    self._consumers.pop(segment, None)
                    consumer.stop()
                    self.ingest_pool.remove(segment)
                ok = self._load(
                    table,
                    segment,
                    msg.get("crc"),
                    msg.get("downloadUri"),
                    msg.get("invertedIndexColumns"),
                    msg.get("schemaJson"),
                )
            elif target == CONSUMING:
                ok = self._start_consumer(table, segment, msg)
            elif target in (OFFLINE, DROPPED):
                consumer = self._consumers.pop(segment, None)
                if consumer is not None:
                    consumer.stop()
                    self.ingest_pool.remove(segment)
                self.server.remove_segment(table, segment)
                self._local_crcs.pop(segment, None)
                ok = True
            else:
                logger.error("unsupported transition target %s", target)
                ok = False
        except Exception:
            logger.exception("transition %s/%s -> %s failed", table, segment, target)
            ok = False
        try:
            self._post(
                f"/instances/{self.name}/ack",
                {
                    "msgId": msg.get("msgId"),
                    "table": table,
                    "segment": segment,
                    "state": target,
                    "ok": ok,
                },
            )
        except Exception as e:
            # the un-acked message stays on the board and is redelivered
            logger.warning("ack failed for %s/%s: %s", table, segment, e)

    def _start_consumer(self, table: str, segment: str, msg: Dict[str, Any]) -> bool:
        if segment in self._consumers:
            return True  # redelivered message; don't reset the offset
        if not msg.get("streamDescriptor") or not msg.get("schemaJson"):
            logger.error("CONSUMING message for %s lacks a consume spec", segment)
            return False
        if msg.get("consumerType") == "highlevel":
            # one group member per (server, table): a replayed CONSUMING
            # for an older sequence (e.g. after controller recovery)
            # must not start a second consumer under the same member id
            for c in self._consumers.values():
                if getattr(c, "rolls_locally", False) and c.table == table:
                    return True
            consumer = HLRemoteConsumer(self, table, segment, msg)
        else:
            consumer = RemoteConsumer(self, table, segment, msg)
        self._consumers[segment] = consumer
        consumer.start()
        return True

    def _local_dir(self, table: str, segment: str) -> Optional[str]:
        if self.data_dir is None:
            return None
        return os.path.join(self.data_dir, table, segment)

    def _load(
        self,
        table: str,
        segment: str,
        crc: Optional[int],
        download_uri: Optional[str] = None,
        inv_columns=None,
        schema_json=None,
    ) -> bool:
        if schema_json is not None:
            self.server.set_table_schema(table, Schema.from_json(schema_json))
        tdm = self.server.data_manager.table(table)
        loaded = tdm is not None and segment in tdm.segment_names()
        if loaded and crc is not None and self._local_crcs.get(segment) == crc:
            return True  # CRC match (SegmentFetcherAndLoader.java:84)

        local = self._local_dir(table, segment)
        seg_obj = None
        if local is not None and os.path.exists(os.path.join(local, SEGMENT_FILE_NAME)):
            try:
                cached = read_segment(local)
                if crc is None or cached.metadata.crc == crc:
                    # local cache hit — but only a copy whose BYTES
                    # verify may serve (a bit-rotted cache with an
                    # intact header would otherwise sail through)
                    verify_segment_crc(cached, source=local)
                    seg_obj = cached
            except SegmentIntegrityError:
                # quarantine the corrupt cache copy aside (forensics)
                # and fall through to a verified re-download from the
                # controller's durable copy
                from pinot_tpu.server.starter import quarantine_local_copy

                self.server.record_crc_failure(table, segment)
                quarantine_local_copy(self.server, table, segment, local)
                logger.warning(
                    "corrupt local cache for %s/%s quarantined; re-downloading",
                    table, segment,
                )
            except Exception:
                logger.warning("corrupt local cache for %s/%s; re-downloading", table, segment)
        if seg_obj is None:
            # scheme-dispatched fetch (SegmentFetcherFactory.java):
            # an explicit downloadUri (hdfs://, external http…) wins;
            # default is the controller-served copy over HTTP.  With a
            # known CRC the factory verifies before install and returns
            # the parsed segment (no second decode); with crc=None the
            # download's own dataCrc claim is still self-verified — a
            # corrupt controller copy must never enter serving.
            from pinot_tpu.segment.fetcher import DEFAULT_FACTORY

            uri = download_uri or (
                f"{self.controller_url}/segments/{table}/{segment}/file"
            )
            try:
                if local is not None:
                    os.makedirs(local, exist_ok=True)
                    seg_obj = DEFAULT_FACTORY.fetch(
                        uri, os.path.join(local, SEGMENT_FILE_NAME), expected_crc=crc
                    )
                    if seg_obj is None:
                        seg_obj = read_segment(local)
                        verify_segment_crc(seg_obj, source=uri)
                else:
                    import tempfile

                    with tempfile.TemporaryDirectory() as td:
                        seg_obj = DEFAULT_FACTORY.fetch(
                            uri, os.path.join(td, SEGMENT_FILE_NAME), expected_crc=crc
                        )
                        if seg_obj is None:
                            seg_obj = read_segment(td)
                            verify_segment_crc(seg_obj, source=uri)
            except SegmentStaleError:
                # wrong VERSION at the source (replication lag), not
                # corruption: no counters, retried on the next transition
                logger.warning(
                    "controller copy of %s/%s is a stale version; leaving "
                    "unserved until it catches up", table, segment,
                )
                return False
            except SegmentIntegrityError:
                self.server.record_crc_failure(table, segment)
                # the DOWNLOADED bytes are bad: the store copy is the
                # suspect — report it so the controller's scrubber can
                # repair it from a healthy replica (reverse replication)
                try:
                    self._post(
                        "/deepstore/suspect",
                        {"table": table, "segment": segment, "source": uri},
                    )
                except Exception:
                    logger.warning(
                        "could not report store suspect %s/%s", table, segment
                    )
                logger.exception(
                    "downloaded copy of %s/%s failed integrity verification; "
                    "leaving unserved", table, segment,
                )
                return False
        self.server.add_segment(table, seg_obj)
        from pinot_tpu.segment.invindex import warm_inverted_indexes

        warm_inverted_indexes(seg_obj, inv_columns)
        if crc is not None:
            self._local_crcs[segment] = crc
        return True
