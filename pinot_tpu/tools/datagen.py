"""Seeded data generators for tests, quickstarts, and benchmarks.

Covers the role of the reference's ``pinot-tools`` data generator and the
TPC-H harness in ``contrib/pinot-benchmark`` (lineitem-shaped generator
below; real TPC-H data files aren't shipped, so the distribution is
synthetic but shape- and cardinality-faithful for Q0-Q6).
"""
from __future__ import annotations

import random
import string
from typing import Any, Dict, List, Optional, Sequence

from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema, TimeFieldSpec

Row = Dict[str, Any]


def random_rows(
    schema: Schema,
    num_rows: int,
    seed: int = 0,
    cardinality: int = 20,
    mv_max: int = 3,
) -> List[Row]:
    """Random rows for a schema with bounded per-column cardinality."""
    rng = random.Random(seed)
    # Fixed value pools per column so cardinality is bounded.
    pools: Dict[str, List[Any]] = {}
    for spec in schema.all_fields():
        st = spec.stored_type
        if st == DataType.STRING:
            pools[spec.name] = [
                "".join(rng.choices(string.ascii_lowercase, k=rng.randint(3, 8)))
                for _ in range(cardinality)
            ]
        elif st in (DataType.INT, DataType.LONG):
            pools[spec.name] = [rng.randint(0, 10_000) for _ in range(cardinality)]
        else:
            pools[spec.name] = [round(rng.uniform(-100, 100), 3) for _ in range(cardinality)]

    rows: List[Row] = []
    for _ in range(num_rows):
        row: Row = {}
        for spec in schema.all_fields():
            pool = pools[spec.name]
            if spec.single_value:
                row[spec.name] = rng.choice(pool)
            else:
                row[spec.name] = [rng.choice(pool) for _ in range(rng.randint(1, mv_max))]
        rows.append(row)
    return rows


def make_test_schema(with_mv: bool = True) -> Schema:
    """A small mixed-type schema exercising every stored type."""
    dims = [
        FieldSpec("dimStr", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("dimInt", DataType.INT, FieldType.DIMENSION),
        FieldSpec("dimLong", DataType.LONG, FieldType.DIMENSION),
    ]
    if with_mv:
        dims.append(FieldSpec("dimStrMV", DataType.STRING_ARRAY, FieldType.DIMENSION, single_value=False))
        dims.append(FieldSpec("dimIntMV", DataType.INT_ARRAY, FieldType.DIMENSION, single_value=False))
    metrics = [
        FieldSpec("metInt", DataType.INT, FieldType.METRIC),
        FieldSpec("metFloat", DataType.FLOAT, FieldType.METRIC),
        FieldSpec("metDouble", DataType.DOUBLE, FieldType.METRIC),
    ]
    time_field = TimeFieldSpec("daysSinceEpoch", DataType.INT, time_unit="DAYS")
    return Schema("testTable", dimensions=dims, metrics=metrics, time_field=time_field)


# ---------------------------------------------------------------------------
# baseballStats-shaped quickstart data (Quickstart.java:33 /
# sample_data/baseball.schema — synthetic; shape- and type-faithful)
# ---------------------------------------------------------------------------

_TEAMS = ["BOS", "NYA", "CHA", "SFN", "LAN", "SLN", "ATL", "SEA", "OAK", "TEX"]
_LEAGUES = ["AL", "NL"]
_FIRST = ["hank", "babe", "ty", "willie", "ted", "lou", "joe", "mickey", "stan", "cal"]
_LAST = ["aaron", "ruth", "cobb", "mays", "williams", "gehrig", "dimaggio", "mantle", "musial", "ripken"]


def baseball_schema() -> Schema:
    return Schema(
        "baseballStats",
        dimensions=[
            FieldSpec("playerName", DataType.STRING),
            FieldSpec("teamID", DataType.STRING),
            FieldSpec("league", DataType.STRING),
            FieldSpec("yearID", DataType.INT),
        ],
        metrics=[
            FieldSpec("runs", DataType.INT, FieldType.METRIC),
            FieldSpec("hits", DataType.INT, FieldType.METRIC),
            FieldSpec("homeRuns", DataType.INT, FieldType.METRIC),
            FieldSpec("atBats", DataType.INT, FieldType.METRIC),
        ],
    )


def baseball_rows(num_rows: int = 10_000, seed: int = 42) -> List[Row]:
    rng = random.Random(seed)
    players = [f"{f} {l}" for f in _FIRST for l in _LAST]
    rows: List[Row] = []
    for _ in range(num_rows):
        at_bats = rng.randint(50, 650)
        hits = rng.randint(0, at_bats // 2)
        rows.append(
            {
                "playerName": rng.choice(players),
                "teamID": rng.choice(_TEAMS),
                "league": rng.choice(_LEAGUES),
                "yearID": rng.randint(1980, 2015),
                "runs": rng.randint(0, 140),
                "hits": hits,
                "homeRuns": rng.randint(0, 60),
                "atBats": at_bats,
            }
        )
    return rows


# ---------------------------------------------------------------------------
# TPC-H lineitem-shaped generator (contrib/pinot-benchmark workload shape)
# ---------------------------------------------------------------------------

_SHIP_MODES = ["RAIL", "FOB", "MAIL", "SHIP", "TRUCK", "AIR", "REG AIR"]
_RETURN_FLAGS = ["R", "A", "N"]
_LINE_STATUS = ["O", "F"]


def lineitem_schema() -> Schema:
    return Schema(
        "lineitem",
        dimensions=[
            FieldSpec("l_returnflag", DataType.STRING),
            FieldSpec("l_linestatus", DataType.STRING),
            FieldSpec("l_shipmode", DataType.STRING),
            FieldSpec("l_shipdate", DataType.STRING),
            FieldSpec("l_receiptdate", DataType.STRING),
        ],
        metrics=[
            FieldSpec("l_quantity", DataType.DOUBLE, FieldType.METRIC),
            FieldSpec("l_extendedprice", DataType.DOUBLE, FieldType.METRIC),
            FieldSpec("l_discount", DataType.DOUBLE, FieldType.METRIC),
            FieldSpec("l_tax", DataType.DOUBLE, FieldType.METRIC),
        ],
    )


def _rand_date(rng: random.Random, lo_year: int = 1992, hi_year: int = 1998) -> str:
    y = rng.randint(lo_year, hi_year)
    m = rng.randint(1, 12)
    d = rng.randint(1, 28)
    return f"{y:04d}-{m:02d}-{d:02d}"


def _synthetic_columnar_segment(
    schema: Schema,
    table_name: str,
    dict_values: Dict[str, Any],
    num_rows: int,
    seed: int,
    name: str,
    clustered_column: Optional[str] = None,
    time_column: Optional[str] = None,
    rng=None,
    drawn_last: Sequence[str] = (),
):
    """Shared fast-path builder behind every synthetic_*_segment:
    ColumnData built directly from per-column value pools (dictIds drawn
    uniformly) instead of the two-pass row builder, so 10M+ row segments
    construct in seconds.  ``clustered_column`` is sorted after draw
    (arrival-ordered data: zone maps / docrange fast paths have
    something to prune, as a sorted Pinot column does).  Callers whose
    value pools consumed random state pass their ``rng`` so the draw
    sequence (and thus seeded data) stays reproducible.  Rows are drawn
    column by column in the schema's order, the ``drawn_last`` columns
    after the others: a schema that adds a column to another's can keep
    the other's data for a seed."""
    import numpy as np

    from pinot_tpu.common.schema import DataType
    from pinot_tpu.segment.dictionary import Dictionary
    from pinot_tpu.segment.immutable import (
        ColumnData,
        ColumnMetadata,
        ImmutableSegment,
        SegmentMetadata,
    )

    rng = rng if rng is not None else np.random.default_rng(seed)
    columns = {}
    for spec in sorted(schema.all_fields(), key=lambda spec: spec.name in drawn_last):
        vals = dict_values[spec.name]
        if spec.stored_type == DataType.STRING:
            d = Dictionary(DataType.STRING, sorted(set(vals)))
        else:
            d = Dictionary(spec.stored_type, np.unique(np.asarray(vals)))
        card = d.cardinality
        fwd = rng.integers(0, card, size=num_rows, dtype=np.int64).astype(np.int32)
        if spec.name == clustered_column:
            fwd.sort()
        columns[spec.name] = ColumnData(
            metadata=ColumnMetadata(
                name=spec.name,
                data_type=spec.data_type,
                field_type=spec.field_type,
                single_value=True,
                cardinality=card,
                total_docs=num_rows,
                # true sortedness: a clustered column qualifies for the
                # docrange fast path (plan.py), as a sorted Pinot column
                # does for SortedInvertedIndexBasedFilterOperator
                is_sorted=bool(num_rows == 0 or np.all(fwd[1:] >= fwd[:-1])),
                total_number_of_entries=num_rows,
                min_value=d.min_value,
                max_value=d.max_value,
            ),
            dictionary=d,
            fwd=fwd,
        )
    smeta = SegmentMetadata(
        segment_name=name,
        table_name=table_name,
        num_docs=num_rows,
        columns={c.metadata.name: c.metadata for c in columns.values()},
        time_column=time_column,
    )
    seg = ImmutableSegment(metadata=smeta, columns=columns)
    # a cheap cache-identity token, not a data CRC (no custom["dataCrc"]):
    # the same in every process, which hash() of a str is not
    import zlib

    smeta.crc = zlib.crc32(f"{name}:{num_rows}:{seed}".encode())
    return seg


def _lineitem_pools(rng) -> Dict[str, Any]:
    """The value pools of lineitem's nine columns, for both lineitem
    generators: 2,000 dates of 28-day months from 1992-01-01, and a
    16,384-value price dictionary drawn from ``rng`` (the pools' one
    draw, before any row's)."""
    import numpy as np

    dates = sorted(
        f"{y:04d}-{m:02d}-{d:02d}" for y in range(1992, 1999) for m in range(1, 13) for d in range(1, 29)
    )[:2000]
    return {
        "l_returnflag": sorted(_RETURN_FLAGS),
        "l_linestatus": sorted(_LINE_STATUS),
        "l_shipmode": sorted(_SHIP_MODES),
        "l_shipdate": dates,
        "l_receiptdate": dates,
        "l_quantity": np.arange(1.0, 51.0),
        "l_extendedprice": np.round(np.sort(rng.uniform(900.0, 105_000.0, 16384)), 2),
        "l_discount": np.round(np.arange(0.0, 0.11, 0.01), 2),
        "l_tax": np.round(np.arange(0.0, 0.09, 0.01), 2),
    }


def synthetic_lineitem_segment(num_rows: int, seed: int = 7, name: str = "li0"):
    """Fast numpy-path lineitem segment for benchmarks (see
    ``_synthetic_columnar_segment``)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return _synthetic_columnar_segment(
        lineitem_schema(), "lineitem", _lineitem_pools(rng), num_rows, seed, name,
        clustered_column="l_shipdate", rng=rng,
    )


def lineitem_keys_schema() -> Schema:
    """lineitem's nine columns and its supplier key: the table of TPC-H
    Q15 (clause 2.4.15), whose view groups lineitem by ``l_suppkey``."""
    base = lineitem_schema()
    return Schema(
        base.schema_name,
        dimensions=base.dimensions + [FieldSpec("l_suppkey", DataType.INT)],
        metrics=base.metrics,
    )


def synthetic_lineitem_keys_segment(num_rows: int, seed: int = 7, name: str = "li0", suppliers: int = 220_000):
    """``synthetic_lineitem_segment`` with ``l_suppkey`` uniform over
    1..``suppliers`` (as dbgen's is), drawn after the nine older columns
    from the same generator state: those are bit for bit what
    ``synthetic_lineitem_segment`` gives for the seed.  Every segment's
    dictionary holds every supplier.  TPC-H has SF x 10,000 suppliers,
    and the benchmark's table is SF1 raised 22 times: 220,000."""
    import numpy as np

    rng = np.random.default_rng(seed)
    pools = _lineitem_pools(rng)
    pools["l_suppkey"] = np.arange(1, suppliers + 1, dtype=np.int64)
    return _synthetic_columnar_segment(
        lineitem_keys_schema(), "lineitem", pools, num_rows, seed, name,
        clustered_column="l_shipdate", rng=rng, drawn_last=("l_suppkey",),
    )


def lineitem_rows(num_rows: int, seed: int = 7) -> List[Row]:
    rng = random.Random(seed)
    rows: List[Row] = []
    for _ in range(num_rows):
        rows.append(
            {
                "l_returnflag": rng.choice(_RETURN_FLAGS),
                "l_linestatus": rng.choice(_LINE_STATUS),
                "l_shipmode": rng.choice(_SHIP_MODES),
                "l_shipdate": _rand_date(rng),
                "l_receiptdate": _rand_date(rng),
                "l_quantity": float(rng.randint(1, 50)),
                "l_extendedprice": round(rng.uniform(900.0, 105_000.0), 2),
                "l_discount": round(rng.uniform(0.0, 0.1), 2),
                "l_tax": round(rng.uniform(0.0, 0.08), 2),
            }
        )
    return rows


# ---------------------------------------------------------------------------
# Synthetic ad-events (the BASELINE.json north-star config: "Synthetic
# ad-events 1B rows: high-cardinality distinctCountHLL group-by")
# ---------------------------------------------------------------------------

ADEVENTS_TABLE = "adevents"


def adevents_schema() -> Schema:
    return Schema(
        ADEVENTS_TABLE,
        dimensions=[
            FieldSpec("campaign_id", DataType.INT, FieldType.DIMENSION),
            FieldSpec("site_id", DataType.INT, FieldType.DIMENSION),
            FieldSpec("user_id", DataType.LONG, FieldType.DIMENSION),
        ],
        metrics=[FieldSpec("clicks", DataType.INT, FieldType.METRIC)],
        time_field=TimeFieldSpec("event_time", DataType.LONG, time_unit="MILLISECONDS"),
    )


def synthetic_adevents_segment(
    num_rows: int,
    seed: int = 7,
    name: str = "ad0",
    campaign_card: int = 1024,
    site_card: int = 128,
    user_card: int = 1 << 20,
    user_universe: int = 1 << 26,
):
    """Fast numpy-path ad-events segment: the high-cardinality HLL
    workload.  ``user_id`` draws ``user_card`` distinct users per
    segment from a ``user_universe``-wide population, so segments
    overlap partially (the realistic dedup case) and the GLOBAL
    dictionary grows toward the universe size across segments."""
    import numpy as np

    rng = np.random.default_rng(seed)
    users = np.unique(
        rng.integers(0, user_universe, size=int(user_card * 1.05), dtype=np.int64)
    )
    t0 = 1_700_000_000_000 + seed * 3_600_000
    dict_values = {
        "campaign_id": np.arange(campaign_card, dtype=np.int64),
        "site_id": np.arange(site_card, dtype=np.int64),
        "user_id": users,
        "clicks": np.arange(16, dtype=np.int64),
        # clustered: events arrive in time order (zone-map fodder)
        "event_time": t0 + np.arange(4096, dtype=np.int64) * 1000,
    }
    return _synthetic_columnar_segment(
        adevents_schema(), ADEVENTS_TABLE, dict_values, num_rows, seed, name,
        clustered_column="event_time", time_column="event_time", rng=rng,
    )
