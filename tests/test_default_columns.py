"""Schema-evolution default columns.

Reference behavior: when a schema grows, segments built before the new
column get a synthesized default-value column at load time
(pinot-core ``segment/index/loader/defaultcolumn/
BaseDefaultColumnHandler.java:18``), so old rows keep answering —
with default-null semantics — instead of the segment being pruned.
"""
import numpy as np

from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema, TimeFieldSpec
from pinot_tpu.pql import parse_pql
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.default_column import inject_default_columns, make_default_column
from pinot_tpu.server.instance import ServerInstance
from pinot_tpu.tools.cluster_harness import InProcessCluster
from pinot_tpu.tools.datagen import make_test_schema, random_rows


def _grown_schema(base: Schema) -> Schema:
    """base + a new string dimension, MV int dimension, and a metric."""
    return Schema(
        base.schema_name,
        dimensions=list(base.dimensions)
        + [
            FieldSpec("newDim", DataType.STRING, FieldType.DIMENSION),
            FieldSpec(
                "newMV", DataType.INT_ARRAY, FieldType.DIMENSION, single_value=False
            ),
        ],
        metrics=list(base.metrics)
        + [FieldSpec("newMet", DataType.DOUBLE, FieldType.METRIC)],
        time_field=base.time_field,
    )


# ---------------------------------------------------------------- unit
def test_make_default_column_sv_string():
    spec = FieldSpec("d", DataType.STRING, FieldType.DIMENSION)
    col = make_default_column(spec, 7)
    assert col.metadata.cardinality == 1
    assert col.metadata.is_sorted
    assert col.dictionary.get(0) == "null"
    np.testing.assert_array_equal(col.fwd, np.zeros(7, dtype=np.int32))
    assert col.values_for_doc(3) == "null"


def test_make_default_column_metric_and_mv():
    met = make_default_column(FieldSpec("m", DataType.DOUBLE, FieldType.METRIC), 4)
    assert met.values_for_doc(0) == 0.0  # metric default null is additive identity
    mv = make_default_column(
        FieldSpec("mv", DataType.INT_ARRAY, FieldType.DIMENSION, single_value=False), 4
    )
    assert not mv.is_single_value
    assert mv.values_for_doc(2) == [-(2**31)]  # INT dimension null


def test_inject_skips_existing_and_time():
    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, 20, seed=5)
    seg = build_segment(schema, rows, "t", "s0")
    grown = _grown_schema(schema)
    assert inject_default_columns(seg, grown) == 3
    assert seg.has_column("newDim") and seg.has_column("newMet")
    # metadata stays consistent with the live column set (converters
    # and persistence iterate metadata.columns)
    assert "newDim" in seg.metadata.columns and "newMet" in seg.metadata.columns
    # idempotent; never resynthesizes present columns or the time column
    assert inject_default_columns(seg, grown) == 0
    # a schema whose time column is absent from the segment: not injected
    other = Schema(
        "t2",
        dimensions=[FieldSpec("dimStr", DataType.STRING, FieldType.DIMENSION)],
        time_field=TimeFieldSpec("otherTime", DataType.INT, time_unit="DAYS"),
    )
    seg2 = build_segment(schema, rows, "t", "s1")
    inject_default_columns(seg2, other)
    assert not seg2.has_column("otherTime")


# ------------------------------------------------------ server instance
def test_server_retro_patches_loaded_segments():
    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, 50, seed=7)
    old_seg = build_segment(schema, rows, "testTable_OFFLINE", "old")
    server = ServerInstance("s0")
    server.add_segment("testTable_OFFLINE", old_seg)  # loaded pre-evolution

    grown = _grown_schema(schema)
    server.set_table_schema("testTable_OFFLINE", grown)  # evolve: retro-patch
    assert old_seg.has_column("newDim")

    new_rows = [dict(r, newDim="x", newMV=[1, 2], newMet=2.5) for r in rows]
    new_seg = build_segment(grown, new_rows, "testTable_OFFLINE", "new")
    server.add_segment("testTable_OFFLINE", new_seg)  # future loads auto-patch
    assert new_seg.has_column("newDim")


# --------------------------------------------------------- end-to-end
def test_mixed_age_segments_answer_with_defaults(tmp_path):
    cluster = InProcessCluster(num_servers=2, data_dir=str(tmp_path))
    schema = make_test_schema(with_mv=False)
    physical = cluster.add_offline_table(schema)
    rows = random_rows(schema, 120, seed=9)
    cluster.upload(physical, build_segment(schema, rows[:60], physical, "oldSeg"))

    # grow the schema, then upload a segment built against it
    grown = _grown_schema(schema)
    cluster.controller.add_schema(grown)
    new_rows = [dict(r, newDim="fresh", newMV=[3], newMet=1.0) for r in rows[60:]]
    cluster.upload(physical, build_segment(grown, new_rows, physical, "newSeg"))

    # old segment participates: all 120 rows scanned, not 60
    resp = cluster.query("SELECT count(*) FROM testTable GROUP BY newDim TOP 10")
    groups = {
        tuple(g.group): g.value for g in resp.aggregation_results[0].group_by_result
    }
    assert groups == {("fresh",): 60.0, ("null",): 60.0}

    # metric default is 0: sum over all rows == sum over new rows only
    resp2 = cluster.query("SELECT sum(newMet) FROM testTable")
    assert resp2.num_docs_scanned == 120
    assert resp2.aggregation_results[0].value == 60.0

    # filter on the default value selects exactly the old rows
    resp3 = cluster.query("SELECT count(*) FROM testTable WHERE newDim = 'null'")
    assert resp3.aggregation_results[0].value == 60.0


def test_realtime_rollover_picks_up_evolved_schema(tmp_path):
    """Schema evolution on a live realtime table: the next segment
    rollover consumes the new column's real streamed values; sealed
    pre-evolution segments answer with defaults."""
    from pinot_tpu.realtime.llc import RESP_KEEP, make_segment_name
    from pinot_tpu.realtime.stream import MemoryStreamProvider

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    base = Schema(
        "meetupRsvp",
        dimensions=[FieldSpec("venue_name", DataType.STRING)],
        metrics=[FieldSpec("rsvp_count", DataType.INT, FieldType.METRIC)],
        time_field=TimeFieldSpec("mtime", DataType.LONG, time_unit="MILLISECONDS"),
    )
    stream = MemoryStreamProvider(num_partitions=1)
    physical = cluster.add_realtime_table(base, stream, rows_per_segment=50)
    for i in range(50):
        stream.produce({"venue_name": f"v{i % 3}", "rsvp_count": 1, "mtime": 1000 + i})

    seg0 = make_segment_name(physical, 0, 0)
    dm0 = cluster.controller.realtime_manager.consumers_of(seg0)[0]
    dm0.consume_step(max_rows=1000)  # fills segment 0 with old-schema rows

    # evolve while segment 0 is still consuming: the evolution applies
    # to segments created from here on (the reference's semantics — a
    # consuming segment keeps the schema it was created with)
    grown = Schema(
        base.schema_name,
        dimensions=list(base.dimensions),
        metrics=list(base.metrics)
        + [FieldSpec("guests", DataType.INT, FieldType.METRIC)],
        time_field=base.time_field,
    )
    cluster.controller.add_schema(grown)
    assert dm0.try_commit() == RESP_KEEP  # seals; rollover creates seg1 post-evolution

    # rows with the new column stream into the post-evolution segment
    for i in range(50):
        stream.produce(
            {"venue_name": "v9", "rsvp_count": 1, "guests": 2, "mtime": 2000 + i}
        )
    seg1 = make_segment_name(physical, 0, 1)
    dm1 = cluster.controller.realtime_manager.consumers_of(seg1)[0]
    dm1.consume_step(max_rows=1000)
    assert dm1.try_commit() == RESP_KEEP

    # old rows: guests = 0 (metric default); new rows: real value 2
    resp = cluster.query("SELECT sum(guests) FROM meetupRsvp")
    assert resp.num_docs_scanned == 100
    assert resp.aggregation_results[0].value == 100.0  # 50 rows x 2 guests
