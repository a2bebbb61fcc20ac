"""Realtime ingestion tests: mutable segments, LLC consume/commit FSM,
rollover, offset checkpointing, validation repair, hybrid federation."""
import pytest

from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema, TimeFieldSpec
from pinot_tpu.pql import parse_pql
from pinot_tpu.realtime.llc import (
    RESP_CATCH_UP,
    RESP_COMMIT,
    RESP_HOLD,
    RESP_KEEP,
    make_segment_name,
    parse_segment_name,
)
from pinot_tpu.realtime.mutable import MutableSegment
from pinot_tpu.realtime.stream import FileBasedStreamProvider, MemoryStreamProvider
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.tools.cluster_harness import InProcessCluster
from pinot_tpu.tools.scan_engine import ScanQueryProcessor
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.engine.reduce import reduce_to_response


def rsvp_schema():
    """meetupRsvp-style schema (RealtimeQuickStart analog)."""
    return Schema(
        "meetupRsvp",
        dimensions=[
            FieldSpec("venue_name", DataType.STRING),
            FieldSpec("event_name", DataType.STRING),
        ],
        metrics=[FieldSpec("rsvp_count", DataType.INT, FieldType.METRIC)],
        time_field=TimeFieldSpec("mtime", DataType.LONG, time_unit="MILLISECONDS"),
    )


def make_row(i):
    return {
        "venue_name": f"venue{i % 5}",
        "event_name": f"event{i % 3}",
        "rsvp_count": i % 7,
        "mtime": 1_000_000 + i,
    }


# ---------------------------------------------------------- mutable
def test_mutable_segment_snapshot_queries():
    schema = rsvp_schema()
    seg = MutableSegment(schema, "m0", "rt")
    rows = [make_row(i) for i in range(100)]
    for r in rows:
        seg.index(r)

    snap = seg.snapshot()
    assert snap.num_docs == 100
    # snapshot is cached until the watermark moves
    assert seg.snapshot() is snap
    seg.index(make_row(100))
    snap2 = seg.snapshot()
    assert snap2 is not snap and snap2.num_docs == 101

    # query the snapshot through the engine, compare vs oracle
    oracle = ScanQueryProcessor(schema, rows + [make_row(100)])
    for pql in [
        "SELECT count(*) FROM rt WHERE venue_name = 'venue1'",
        "SELECT sum(rsvp_count) FROM rt GROUP BY event_name",
        "SELECT max(mtime) FROM rt",
    ]:
        req = parse_pql(pql)
        got = reduce_to_response(req, [QueryExecutor().execute([seg.snapshot()], req)])
        want = oracle.execute(parse_pql(pql))
        assert got.to_json()["aggregationResults"] == want.to_json()["aggregationResults"]


def test_segment_name_roundtrip():
    name = make_segment_name("rt_REALTIME", 3, 7)
    assert parse_segment_name(name) == ("rt_REALTIME", 3, 7)


# ---------------------------------------------------------- llc flow
def test_consume_query_commit_rollover(tmp_path):
    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    schema = rsvp_schema()
    stream = MemoryStreamProvider(num_partitions=1)
    physical = cluster.add_realtime_table(schema, stream, rows_per_segment=50)

    for i in range(120):
        stream.produce(make_row(i))

    seg0 = make_segment_name(physical, 0, 0)
    consumers = cluster.controller.realtime_manager.consumers_of(seg0)
    assert len(consumers) == 1
    dm = consumers[0]

    # consume a partial batch: rows visible to queries immediately
    dm.consume_step(max_rows=30)
    resp = cluster.query("SELECT count(*) FROM meetupRsvp")
    assert resp.num_docs_scanned == 30

    # hit the threshold -> commit -> rollover to seq 1
    dm.consume_step(max_rows=1000)
    assert dm.threshold_reached
    assert dm.try_commit() == RESP_KEEP

    ideal = cluster.controller.resources.get_ideal_state(physical)
    assert ideal[seg0] == {"server0": "ONLINE"}
    seg1 = make_segment_name(physical, 0, 1)
    assert ideal[seg1] == {"server0": "CONSUMING"}

    # committed segment checkpointed exact offsets
    info = cluster.controller.resources.get_segment_metadata(physical, seg0)
    assert info["metadata"].custom["startOffset"] == 0
    assert info["metadata"].custom["endOffset"] == 50

    # new consumer picks up from offset 50
    dm1 = cluster.controller.realtime_manager.consumers_of(seg1)[0]
    assert dm1.offset == 50
    dm1.consume_step(max_rows=1000)
    assert dm1.try_commit() == RESP_KEEP  # second segment seals at 100

    seg2 = make_segment_name(physical, 0, 2)
    dm2 = cluster.controller.realtime_manager.consumers_of(seg2)[0]
    dm2.consume_step(max_rows=1000)  # 20 rows, under threshold

    # total rows: 2 sealed segments (100) + consuming (20)
    resp = cluster.query("SELECT count(*) FROM meetupRsvp")
    assert resp.num_docs_scanned == 120

    # aggregate correctness across sealed + consuming
    oracle = ScanQueryProcessor(schema, [make_row(i) for i in range(120)])
    got = cluster.query("SELECT sum(rsvp_count) FROM meetupRsvp GROUP BY venue_name")
    want = oracle.execute(parse_pql("SELECT sum(rsvp_count) FROM meetupRsvp GROUP BY venue_name"))
    assert got.to_json()["aggregationResults"] == want.to_json()["aggregationResults"]


def test_replicated_consumers_catch_up(tmp_path):
    cluster = InProcessCluster(num_servers=2, data_dir=str(tmp_path))
    schema = rsvp_schema()
    stream = MemoryStreamProvider(num_partitions=1)
    physical = cluster.add_realtime_table(
        schema, stream, rows_per_segment=40, replication=2
    )
    for i in range(60):
        stream.produce(make_row(i))

    seg0 = make_segment_name(physical, 0, 0)
    dms = cluster.controller.realtime_manager.consumers_of(seg0)
    assert len(dms) == 2
    fast, slow = dms

    fast.consume_step(max_rows=40)
    slow.consume_step(max_rows=25)  # laggard

    # laggard reports first: HOLD (not all replicas reported)
    assert slow.try_commit() == RESP_HOLD
    # fast replica reports at 40: committer decided = fast -> COMMIT path runs
    assert fast.try_commit() == RESP_KEEP
    # laggard now catches up to the committed offset and keeps/downloads
    resp = slow.try_commit()
    assert resp in ("KEEP", "DISCARD", "CATCH_UP", "HOLD")

    # both replicas now ONLINE on the sealed segment
    view = cluster.controller.resources.get_external_view(physical)
    assert view[seg0] == {"server0": "ONLINE", "server1": "ONLINE"}
    # query still counts each row once (routing picks one replica)
    assert cluster.query("SELECT count(*) FROM meetupRsvp").num_docs_scanned >= 40


def test_validation_recreates_consuming(tmp_path):
    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    schema = rsvp_schema()
    stream = MemoryStreamProvider(num_partitions=1)
    physical = cluster.add_realtime_table(schema, stream, rows_per_segment=10)
    for i in range(10):
        stream.produce(make_row(i))

    seg0 = make_segment_name(physical, 0, 0)
    dm = cluster.controller.realtime_manager.consumers_of(seg0)[0]
    dm.consume_step(max_rows=100)
    assert dm.try_commit() == RESP_KEEP

    # simulate loss of the seq-1 consuming segment (controller crash analog)
    seg1 = make_segment_name(physical, 0, 1)
    cluster.controller.resources.delete_segment(physical, seg1)
    assert seg1 not in cluster.controller.resources.get_ideal_state(physical)

    cluster.controller.validation_manager.run_once()
    ideal = cluster.controller.resources.get_ideal_state(physical)
    # recreated at the next seq after the last COMMITTED one (seq 0) -> seq 1
    assert seg1 in ideal and ideal[seg1]["server0"] == "CONSUMING"
    dm2 = cluster.controller.realtime_manager.consumers_of(seg1)[0]
    assert dm2.offset == 10  # resumes from the committed end offset


def test_file_stream_provider(tmp_path):
    import json

    p = tmp_path / "part0.jsonl"
    p.write_text("\n".join(json.dumps(make_row(i)) for i in range(25)))
    stream = FileBasedStreamProvider([str(p)])
    assert stream.partition_count() == 1
    assert stream.latest_offset(0) == 25
    rows, nxt = stream.fetch(0, 10, 10)
    assert len(rows) == 10 and nxt == 20
    rows, nxt = stream.fetch(0, 20, 10)
    assert len(rows) == 5 and nxt == 25


def test_multi_partition(tmp_path):
    cluster = InProcessCluster(num_servers=2, data_dir=str(tmp_path))
    schema = rsvp_schema()
    stream = MemoryStreamProvider(num_partitions=2)
    physical = cluster.add_realtime_table(schema, stream, rows_per_segment=1000)
    for i in range(30):
        stream.produce(make_row(i), partition=i % 2)

    for p in range(2):
        seg = make_segment_name(physical, p, 0)
        for dm in cluster.controller.realtime_manager.consumers_of(seg):
            dm.consume_step(max_rows=100)
    assert cluster.query("SELECT count(*) FROM meetupRsvp").num_docs_scanned == 30


# ---------------------------------------------------------- hybrid
def test_hybrid_time_boundary(tmp_path):
    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    schema = rsvp_schema()

    # offline side: times 1_000_000..1_000_049
    offline_physical = cluster.add_offline_table(schema, table_name="meetupRsvp")
    offline_rows = [make_row(i) for i in range(50)]
    cluster.upload(offline_physical, build_segment(schema, offline_rows, offline_physical, "off0"))

    # realtime side overlaps: times 1_000_030..1_000_079 (30..79)
    stream = MemoryStreamProvider(num_partitions=1)
    rt_physical = cluster.add_realtime_table(schema, stream, rows_per_segment=1000)
    rt_rows = [make_row(i) for i in range(30, 80)]
    for r in rt_rows:
        stream.produce(r)
    seg0 = make_segment_name(rt_physical, 0, 0)
    cluster.controller.realtime_manager.consumers_of(seg0)[0].consume_step(max_rows=100)

    # federated query: boundary = offline max time (1_000_049);
    # offline answers <= boundary, realtime answers > boundary
    resp = cluster.query("SELECT count(*) FROM meetupRsvp")
    assert resp.num_docs_scanned == 80  # 0..79 counted exactly once
    assert not resp.exceptions

    resp = cluster.query("SELECT max(mtime) FROM meetupRsvp")
    assert resp.aggregation_results[0].value == 1_000_079.0


def test_index_batch_dirty_row_is_atomic():
    """Regression: a dirty value mid-batch (producer garbage a
    DataType.convert rejects) must not misalign columns — encode
    happens before any row array mutates, so the whole batch rejects
    and a corrected retry lands cleanly."""
    import pytest

    schema = rsvp_schema()
    seg = MutableSegment(schema, "atom", "t")
    seg.index_batch([make_row(i) for i in range(10)])
    bad = [make_row(10), {**make_row(11), "rsvp_count": "not-an-int"}]
    with pytest.raises(Exception):
        seg.index_batch(bad)
    assert seg.num_docs == 10
    seg.index_batch([make_row(10), make_row(11)])
    assert seg.num_docs == 12
    snap = seg.snapshot()
    assert snap.num_docs == 12
    # every column aligned: spot-check the last row round-trips
    row = snap.row(11)
    assert row["rsvp_count"] == make_row(11)["rsvp_count"]
    assert row["venue_name"] == make_row(11)["venue_name"]


def test_flaky_consumer_ingests_exactly_once(tmp_path):
    """A stream provider that fails 60% of fetches and returns short
    batches must not lose or duplicate rows: the consume/commit cycle
    retries until every segment seals at exact offsets (the
    FlakyConsumerRealtimeClusterIntegrationTest analog)."""
    from pinot_tpu.realtime.stream import FlakyStreamProvider

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    schema = rsvp_schema()
    inner = MemoryStreamProvider(num_partitions=1)
    stream = FlakyStreamProvider(inner, fail_rate=0.6, seed=42)
    physical = cluster.add_realtime_table(schema, stream, rows_per_segment=50)

    total = 173
    for i in range(total):
        inner.produce(make_row(i))

    # drive consumption with retry-on-failure, as the production
    # network consume loop does (server/network_starter.py _run)
    seq = 0
    attempts = 0
    while attempts < 4000:
        attempts += 1
        seg = make_segment_name(physical, 0, seq)
        dms = cluster.controller.realtime_manager.consumers_of(seg)
        if not dms:
            break
        dm = dms[0]
        try:
            got = dm.consume_step(max_rows=64)
        except RuntimeError:
            continue  # injected failure: retry, offsets unchanged
        if dm.threshold_reached:
            dm.try_commit()
            seq += 1
        elif got == 0:
            break
    assert stream.failures > 5  # the injection actually engaged

    # exactly-once: every row present once, sealed offsets contiguous
    resp = cluster.query("SELECT count(*) FROM meetupRsvp")
    assert resp.num_docs_scanned == total
    got = cluster.query("SELECT sum(rsvp_count) FROM meetupRsvp")
    oracle = ScanQueryProcessor(schema, [make_row(i) for i in range(total)])
    want = oracle.execute(parse_pql("SELECT sum(rsvp_count) FROM meetupRsvp"))
    assert got.to_json()["aggregationResults"] == want.to_json()["aggregationResults"]
    end = 0
    for s in range(seq):
        info = cluster.controller.resources.get_segment_metadata(
            physical, make_segment_name(physical, 0, s)
        )
        assert info["metadata"].custom["startOffset"] == end
        end = info["metadata"].custom["endOffset"]


def test_index_batch_nested_list_sv_value_is_atomic():
    """Regression: equal-length LIST values in an SV numeric column
    build a 2-D array that must be rejected in the ENCODE phase (the
    vectorized fast path), not explode in commit after other columns
    already mutated."""
    import pytest

    from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema

    schema = Schema(
        "t",
        dimensions=[
            FieldSpec("mv", DataType.INT_ARRAY, single_value=False),
            FieldSpec("a", DataType.INT),
        ],
    )
    seg = MutableSegment(schema, "nested", "t")
    with pytest.raises(Exception):
        seg.index_batch([{"mv": [1], "a": [1, 2]}, {"mv": [2], "a": [3, 4]}])
    assert seg.num_docs == 0
    seg.index_batch([{"mv": [9], "a": 7}])
    snap = seg.snapshot()
    assert snap.row(0) == {"mv": [9], "a": 7}


def test_index_batch_nan_dict_cardinality_stable():
    """Regression: NaN ingest must key the dictionary identically
    whether a batch takes the vectorized or the per-value path."""
    from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema

    schema = Schema(
        "t", metrics=[FieldSpec("m", DataType.DOUBLE, FieldType.METRIC)]
    )
    nan = float("nan")
    seg_fast = MutableSegment(schema, "f", "t")
    seg_fast.index_batch([{"m": nan}, {"m": nan}])  # no None: fast path eligible
    seg_slow = MutableSegment(schema, "s", "t")
    seg_slow.index_batch([{"m": nan}, {"m": nan}, {"m": None}])  # fallback loop
    card_fast = len(seg_fast._columns["m"].id_to_value)
    ids_slow = seg_slow._columns["m"].ids[:2].tolist()
    # both paths must key the two NaNs the same way
    assert card_fast == len(set(ids_slow))


# ------------------------------------------- columnar mode detection
def _columnar_dm(stream):
    from pinot_tpu.realtime.llc import RealtimeSegmentDataManager

    return RealtimeSegmentDataManager(
        None, None, "rt_REALTIME", "rt__0__0__t", rsvp_schema(), stream, 0, 0, 1000
    )


def _block(n, start=0):
    import numpy as np

    return {
        "venue_name": np.array([f"venue{i % 5}" for i in range(start, start + n)]),
        "event_name": np.array([f"event{i % 3}" for i in range(start, start + n)]),
        "rsvp_count": np.arange(start, start + n, dtype=np.int64) % 7,
        "mtime": np.arange(1_000_000 + start, 1_000_000 + start + n, dtype=np.int64),
    }


def test_columnar_transient_error_does_not_latch_row_mode():
    """Regression (llc.py _fetch_and_index): a transient transport error
    on the FIRST columnar fetch must re-raise — the mode is still
    unknown.  The old code latched _columnar=False, permanently wedging
    ingest on columnar partitions (whose row fetches the broker rejects
    forever) until a restart."""

    class FailOnceStream:
        def __init__(self):
            self.transport_failures = 1
            self.row_fetches = 0

        def fetch_columns(self, partition, offset):
            if self.transport_failures:
                self.transport_failures -= 1
                raise OSError("connection reset by peer")
            return _block(10), 10, offset + 10

        def fetch(self, partition, offset, max_rows):
            self.row_fetches += 1
            return [], offset

    stream = FailOnceStream()
    dm = _columnar_dm(stream)
    with pytest.raises(OSError):
        dm.consume_step()
    assert dm._columnar is None  # mode still unknown, nothing latched
    assert stream.row_fetches == 0  # never fell through to the row path
    assert dm.consume_step() == 10  # plain retry next step recovers
    assert dm._columnar is True and dm.offset == 10


def test_columnar_transient_runtime_error_unknown_mode_reraises():
    """A non-definitive RuntimeError (bad reply, truncated frame) while
    the mode is unknown re-raises too — only the broker's typed verdict
    may latch."""

    class BadReplyOnceStream:
        def __init__(self):
            self.bad = 1

        def fetch_columns(self, partition, offset):
            if self.bad:
                self.bad -= 1
                raise RuntimeError("stream broker: bad reply")
            return _block(4), 4, offset + 4

        def fetch(self, partition, offset, max_rows):
            raise AssertionError("row path must not engage")

    dm = _columnar_dm(BadReplyOnceStream())
    with pytest.raises(RuntimeError, match="bad reply"):
        dm.consume_step()
    assert dm._columnar is None
    assert dm.consume_step() == 4
    assert dm._columnar is True


def test_columnar_definitive_row_mode_latches():
    """The broker's typed row-mode rejection IS definitive: latch row
    mode and consume via the row path from then on."""

    class RowModeStream:
        def __init__(self):
            self.columnar_attempts = 0

        def fetch_columns(self, partition, offset):
            self.columnar_attempts += 1
            raise RuntimeError("stream broker: row-mode partition")

        def fetch(self, partition, offset, max_rows):
            rows = [make_row(i) for i in range(offset, min(offset + max_rows, 5))]
            return rows, offset + len(rows)

    stream = RowModeStream()
    dm = _columnar_dm(stream)
    assert dm.consume_step() == 5
    assert dm._columnar is False
    dm.consume_step()
    assert stream.columnar_attempts == 1  # latched: no more fetchc probes


def test_columnar_transport_error_on_known_columnar_reraises():
    """Once KNOWN columnar, transport errors keep re-raising (retryable)
    rather than flipping to the row path."""

    class FlakyColumnarStream:
        def __init__(self):
            self.calls = 0

        def fetch_columns(self, partition, offset):
            self.calls += 1
            if self.calls == 2:
                raise OSError("connection hiccup")
            return _block(3, start=offset), 3, offset + 3

        def fetch(self, partition, offset, max_rows):
            raise AssertionError("row path must not engage")

    dm = _columnar_dm(FlakyColumnarStream())
    assert dm.consume_step() == 3
    assert dm._columnar is True
    with pytest.raises(OSError):
        dm.consume_step()
    assert dm._columnar is True  # still columnar
    assert dm.consume_step() == 3  # recovers at the same offset
    assert dm.offset == 6
