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
    made: Optional[Dict[str, Any]] = None,
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
    the other's data for a seed.  ``made`` holds the dictIds of columns
    the caller made itself (a column that follows from another, as a
    city's nation does): those are not drawn, and index the column's
    pool in its dictionary's order."""
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
        if made is not None and spec.name in made:
            fwd = np.asarray(made[spec.name], dtype=np.int32)
        else:
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


# ---------------------------------------------------------------------------
# ClickBench ``hits``, the columns its distinct-user queries read
# (github.com/ClickHouse/ClickBench, queries.sql lines 5, 9 and 10:
# COUNT(DISTINCT UserID) alone and by RegionID).  The data file is not
# shipped, so every distribution is synthetic and skewed as a web log is.
# ---------------------------------------------------------------------------

HITS_TABLE = "hits"
HITS_USERS = 19_250_000  # ids drawn from; a table of 100.7M rows then holds about 17.6M distinct
HITS_USER_EXPONENT = 0.7
HITS_REGIONS = 9_040
HITS_ADV_ENGINES = 18
HITS_ADV_SHARE = 0.0063  # AdvEngineID <> 0 in 630,500 of the source's 99,997,497 rows
# (width, weight in 1,000): a dozen common screens, mean 1,540
HITS_WIDTHS = ((1024, 40), (1280, 110), (1366, 290), (1440, 90), (1536, 80), (1600, 80), (1680, 60),
               (1920, 210), (2048, 10), (2560, 20), (360, 5), (768, 5))
_HITS_DAYS_A_SEGMENT = 3  # a segment is a contiguous run of dates
_HITS_FIRST_DAY = 15887  # 2013-07-01, days since the epoch
_REGION_PRIME = 9_041  # rank -> RegionID: rank * 5 mod 9,041, a bijection on 1..9,040
_ID_SALT = 0x9E3779B97F4A7C15


def hits_users_schema() -> Schema:
    """The five columns ClickBench's three distinct-user queries read,
    and the date a deployment partitions by.  PQL has no SMALLINT:
    ``AdvEngineID`` and ``ResolutionWidth`` are INT."""
    return Schema(
        HITS_TABLE,
        dimensions=[
            FieldSpec("UserID", DataType.LONG),
            FieldSpec("RegionID", DataType.INT),
        ],
        metrics=[
            FieldSpec("AdvEngineID", DataType.INT, FieldType.METRIC),
            FieldSpec("ResolutionWidth", DataType.INT, FieldType.METRIC),
        ],
        time_field=TimeFieldSpec("EventDate", DataType.INT, time_unit="DAYS"),
    )


def zipf_cdf(n: int, exponent: float):
    """Cumulative probabilities of ranks 1..n under p(k) ~ k^-exponent."""
    import numpy as np

    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -exponent)
    return cdf / cdf[-1]


_zipf_tables: Dict[tuple, Any] = {}


def _zipf_ranks_sorted(rng, size: int, n: int, exponent: float):
    """``size`` draws of a rank 0..n-1 by Zipf's law, ascending: sorted
    uniforms searched in the cumulative table (a sorted needle walks the
    table once)."""
    import numpy as np

    key = (n, exponent)
    if key not in _zipf_tables:
        _zipf_tables[key] = zipf_cdf(n, exponent)
    ranks = np.searchsorted(_zipf_tables[key], np.sort(rng.random(size)), side="left").astype(np.int64)
    return np.minimum(ranks, n - 1, out=ranks)


def _mix64(x):
    """splitmix64's finalizer over a uint64 array: a fixed function of an
    id, for the id's value and its home region."""
    import numpy as np

    x = x + np.uint64(_ID_SALT)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def hits_expected_distinct(rows: int, n: int, exponent: float) -> float:
    """How many distinct ranks ``rows`` Zipf draws over 1..n hold, in
    expectation: sum of 1 - (1 - p_k)^rows."""
    import numpy as np

    p = np.diff(zipf_cdf(n, exponent), prepend=0.0)
    return float(np.sum(-np.expm1(rows * np.log1p(-p))))


def _hits_first_day(name: str) -> int:
    """The first of a segment's three days: later by the segment's number,
    the digits that end ``name``."""
    digits = "".join(ch for ch in name if ch.isdigit())
    return _HITS_FIRST_DAY + _HITS_DAYS_A_SEGMENT * (int(digits[-6:]) if digits else 0)


def _hits_user_column(rng, num_rows: int, users: int):
    """``UserID`` of a ``hits`` segment, the first draws of ``rng``: the
    dictionary's values (ascending), the rows' ids, and what the columns
    that follow a user need (the distinct users' hashes by rank, which
    distinct user a row by rank is, and the rows' shuffle).  The ranks
    are drawn in ascending order, so that the distinct ones are the runs'
    heads; their values sorted into the dictionary; the rows shuffled at
    the end (no table by rank, no sort of the rows)."""
    import numpy as np

    user_rank = _zipf_ranks_sorted(rng, num_rows, users, HITS_USER_EXPONENT)
    head = np.ones(num_rows, dtype=bool)
    head[1:] = user_rank[1:] != user_rank[:-1]
    run = np.cumsum(head, dtype=np.int32) - 1  # which distinct user a row is, rows by rank
    hashes = _mix64(user_rank[head].astype(np.uint64))
    values = (hashes >> np.uint64(1)).astype(np.int64)  # opaque, positive, one an id (a collision is a 2^-63 event)
    order = np.argsort(values)
    position = np.empty(order.size, dtype=np.int32)
    position[order] = np.arange(order.size, dtype=np.int32)
    shuffle = rng.permutation(num_rows)
    return values[order], position[run][shuffle], hashes, run, shuffle


def synthetic_hits_users_segment(
    num_rows: int,
    seed: int = 7,
    name: str = "hits0",
    users: int = HITS_USERS,
    regions: int = HITS_REGIONS,
):
    """One segment of ``hits``, skewed as a web log is: ``UserID`` by
    Zipf's law (exponent 0.7) over ``users`` ids, each id's value a fixed
    63-bit function of its rank (as the source's are opaque); ``RegionID``
    by Zipf's law (exponent 1) over ``regions``: a user's home region, a
    fixed function of the id, for nine hits in ten and a fresh draw for
    the tenth; ``AdvEngineID`` 0 in 99.37% of the rows and uniform over
    1..18 otherwise; ``ResolutionWidth`` from a dozen common widths;
    ``EventDate`` three consecutive days a segment, later by the
    segment's number (the digits that end ``name``), sorted.  The rows of
    ``seed`` are the same in every process."""
    import numpy as np

    rng = np.random.default_rng(seed)
    values, user_fwd, hashes, run, shuffle = _hits_user_column(rng, num_rows, users)
    # RegionID: the home region of each distinct user, then a fresh draw
    # for a tenth of the rows.  Rank r has the value r * 5 mod 9,041 (a
    # bijection on 1..9,040, so that size does not follow the key's order)
    home_u = (_mix64(hashes) >> np.uint64(11)).astype(np.float64) / float(1 << 53)
    home = np.minimum(np.searchsorted(zipf_cdf(regions, 1.0), home_u, side="left"), regions - 1).astype(np.int32)
    region_rank = home[run][shuffle]
    away = np.nonzero(rng.random(num_rows) >= 0.9)[0]
    region_rank[away] = _zipf_ranks_sorted(rng, away.size, regions, 1.0)[rng.permutation(away.size)]
    region_values = np.arange(1, regions + 1, dtype=np.int64)
    if regions == HITS_REGIONS:
        region_fwd = (((region_rank.astype(np.int64) + 1) * 5) % _REGION_PRIME - 1).astype(np.int32)
    else:
        region_fwd = region_rank.astype(np.int32)
    adv_fwd = np.zeros(num_rows, dtype=np.int32)
    clicked = np.nonzero(rng.random(num_rows) < HITS_ADV_SHARE)[0]
    adv_fwd[clicked] = rng.integers(1, HITS_ADV_ENGINES + 1, clicked.size)
    by_width = sorted(HITS_WIDTHS)
    width_cdf = np.cumsum([k for _, k in by_width]) / float(sum(k for _, k in by_width))
    width_fwd = np.minimum(np.searchsorted(width_cdf, rng.random(num_rows), side="right"), len(by_width) - 1).astype(np.int32)
    first_day = _hits_first_day(name)
    day_fwd = np.sort(rng.integers(0, _HITS_DAYS_A_SEGMENT, num_rows, dtype=np.int32))

    # every dictionary holds its whole pool (UserID's: the ids drawn)
    pools = {
        "UserID": (values, user_fwd),
        "RegionID": (region_values, region_fwd),
        "AdvEngineID": (np.arange(HITS_ADV_ENGINES + 1, dtype=np.int64), adv_fwd),
        "ResolutionWidth": (np.array([w for w, _ in by_width], dtype=np.int64), width_fwd),
        "EventDate": (first_day + np.arange(_HITS_DAYS_A_SEGMENT, dtype=np.int64), day_fwd),
    }
    return _hits_segment(hits_users_schema(), pools, num_rows, seed, name)


def _hits_segment(schema: Schema, pools: Dict[str, Any], num_rows: int, seed: int, name: str):
    """A ``hits`` segment from ``pools``: a column's dictionary values
    (ascending; a STRING column's as a list of ``str``) and its rows' ids."""
    import zlib

    from pinot_tpu.segment.dictionary import Dictionary
    from pinot_tpu.segment.immutable import ColumnData, ColumnMetadata, ImmutableSegment, SegmentMetadata

    columns = {}
    for spec in schema.all_fields():
        pool, fwd = pools[spec.name]
        d = Dictionary(spec.stored_type, pool)
        columns[spec.name] = ColumnData(
            metadata=ColumnMetadata(
                name=spec.name,
                data_type=spec.data_type,
                field_type=spec.field_type,
                single_value=True,
                cardinality=d.cardinality,
                total_docs=num_rows,
                is_sorted=spec.name == "EventDate",
                total_number_of_entries=num_rows,
                min_value=d.min_value,
                max_value=d.max_value,
            ),
            dictionary=d,
            fwd=fwd,
        )
    smeta = SegmentMetadata(
        segment_name=name,
        table_name=HITS_TABLE,
        num_docs=num_rows,
        columns={c.metadata.name: c.metadata for c in columns.values()},
        time_column="EventDate",
    )
    seg = ImmutableSegment(metadata=smeta, columns=columns)
    smeta.crc = zlib.crc32(f"{name}:{num_rows}:{seed}".encode())
    return seg


# ---------------------------------------------------------------------------
# ClickBench ``hits``, the columns its search-phrase queries read
# (queries.sql lines 13 to 15, 20 and 25 to 27: counts by SearchPhrase,
# a look-up by UserID, and SearchPhrase WHERE SearchPhrase <> '' ORDER BY
# EventTime | SearchPhrase | both LIMIT 10).  The users' table above with
# its UserID and EventDate, a STRING column of millions of distinct
# values, most rows empty, and the second of each hit.
# ---------------------------------------------------------------------------

HITS_PHRASE_EMPTY_SHARE = 0.869  # SearchPhrase = '' in the source's rows, about
HITS_PHRASES = 22_672_621  # phrases drawn from; 100.7M rows (13.19M with a phrase) then hold about 6,019,103 distinct
HITS_PHRASE_EXPONENT = 0.8
HITS_SEARCH_ENGINES = 90
_PHRASE_BYTES = 60  # the longest phrase: six words of nine bytes and five spaces are 59
_PHRASE_WORDS = 4096  # the vocabulary; two words hold a rank of up to 2^24 ...
_PHRASE_RANK_BITS = 25  # ... and a third of 2 values the 25th bit: a phrase spells its rank, so no two ranks share one
_vocabulary: Dict[str, Any] = {}


def hits_search_schema() -> Schema:
    """``hits`` with the columns ClickBench's search-phrase queries read.
    ``EventTime`` is the hit's second since the epoch (the source's
    DateTime), ``EventDate`` its day, the column a deployment partitions
    by.  PQL has no SMALLINT: ``SearchEngineID`` is INT."""
    return Schema(
        HITS_TABLE,
        dimensions=[
            FieldSpec("SearchPhrase", DataType.STRING),
            FieldSpec("EventTime", DataType.LONG),
            FieldSpec("SearchEngineID", DataType.INT),
            FieldSpec("UserID", DataType.LONG),
        ],
        time_field=TimeFieldSpec("EventDate", DataType.INT, time_unit="DAYS"),
    )


def _phrase_vocabulary():
    """``_PHRASE_WORDS`` distinct words of 1 to 9 bytes of UTF-8, half of
    them Latin and half Cyrillic (two bytes a letter, as most of the
    source's phrases are), from a seed of its own: the same in every
    segment and process.  Returns the words' bytes [words, 9], zero
    padded, and their lengths."""
    import numpy as np

    if "words" not in _vocabulary:  # one assignment at the end: run.py's generator threads may all arrive here first
        rng = random.Random(0x5EA2C4)
        latin, cyrillic = string.ascii_lowercase, "".join(chr(c) for c in range(0x430, 0x450))
        words: List[str] = []
        seen = set()
        while len(words) < _PHRASE_WORDS:
            if len(words) % 2:
                word = "".join(rng.choice(cyrillic) for _ in range(rng.randint(1, 4)))
            else:
                word = "".join(rng.choice(latin) for _ in range(rng.randint(1, 9)))
            if word not in seen:
                seen.add(word)
                words.append(word)
        table = np.zeros((_PHRASE_WORDS, 9), dtype=np.uint8)
        for i, word in enumerate(words):
            raw = word.encode("utf-8")
            table[i, : len(raw)] = np.frombuffer(raw, dtype=np.uint8)
        _vocabulary["words"] = (table, np.count_nonzero(table, axis=1).astype(np.int64))
    return _vocabulary["words"]


def hits_phrase_bytes(ranks):
    """The phrases of ``ranks`` (0-based, under 2^25) as rows of UTF-8
    bytes [n, 60], zero padded: 2 to 6 words of the vocabulary with a
    space between, 3 to 59 bytes.  A fixed function of the rank alone.
    The first two words, and which half of the vocabulary the third or
    the missing third comes from, spell the rank after a bijection of its
    25 bits, so that two ranks never share a phrase and a phrase's place
    in the dictionary says nothing of its popularity; the other words
    are the rank's hash."""
    import numpy as np

    table, lengths = _phrase_vocabulary()
    ranks = np.asarray(ranks, dtype=np.uint64)
    mask = np.uint64((1 << _PHRASE_RANK_BITS) - 1)
    spelled = (ranks * np.uint64(0x9E3779B1)) & mask  # an odd multiplier: a bijection on 25 bits
    h = _mix64(ranks)
    top = (spelled >> np.uint64(24)).astype(np.int64)  # the 25th bit
    # 2 to 6 words; a phrase of two words has the 25th bit 0 (its twin with the bit set takes three)
    count = np.where(top == 1, 3 + (h % np.uint64(4)).astype(np.int64), 2 + (h % np.uint64(5)).astype(np.int64))
    half = _PHRASE_WORDS // 2
    words = [
        (spelled & np.uint64(_PHRASE_WORDS - 1)).astype(np.int64),
        ((spelled >> np.uint64(12)) & np.uint64(_PHRASE_WORDS - 1)).astype(np.int64),
        top * half + ((h >> np.uint64(8)) % np.uint64(half)).astype(np.int64),
    ] + [((h >> np.uint64(20 + 12 * j)) % np.uint64(_PHRASE_WORDS)).astype(np.int64) for j in range(3)]
    out = np.zeros((ranks.size, _PHRASE_BYTES), dtype=np.uint8)
    start = np.zeros(ranks.size, dtype=np.int64)
    for j, word in enumerate(words):
        rows = np.nonzero(count > j)[0]
        at, word = start[rows], word[rows]
        if j:
            out[rows, at] = 32
            at = at + 1
        length = lengths[word]
        for c in range(table.shape[1]):
            has = length > c
            out[rows[has], at[has] + c] = table[word[has], c]
        start[rows] = at + length
    return out


def _phrase_strings(rows_of_bytes) -> List[str]:
    """Rows of zero-padded UTF-8 bytes as a list of ``str``: one decode of
    the rows joined by NUL, one split."""
    import numpy as np

    n = rows_of_bytes.shape[0]
    if n == 0:
        return []
    ended = np.concatenate([rows_of_bytes, np.zeros((n, 1), dtype=np.uint8)], axis=1)
    keep = ended != 0
    keep[:, -1] = True
    return ended[keep].tobytes()[:-1].decode("utf-8").split("\x00")


def _present(pool, ids, size: int):
    """A dictionary of the values of ``pool`` (ascending) that ``ids``
    hold, and the rows' ids in it: a count and a running sum, no sort."""
    import numpy as np

    seen = np.bincount(ids, minlength=size) > 0
    place = np.cumsum(seen, dtype=np.int32) - 1
    return pool[seen], place[ids]


def synthetic_hits_search_segment(
    num_rows: int,
    seed: int = 7,
    name: str = "hits0",
    users: int = HITS_USERS,
    phrases: int = HITS_PHRASES,
):
    """One segment of ``hits`` for ClickBench's search-phrase queries.
    ``UserID`` and ``EventDate`` as ``synthetic_hits_users_segment`` makes
    them (for a seed the same users row for row; three consecutive days,
    sorted).  ``SearchPhrase`` is empty in 86.9% of the rows; the others
    draw a phrase by Zipf's law (exponent 0.8) over ``phrases`` ranks,
    each rank's phrase ``hits_phrase_bytes``'s: the same string in every
    segment that holds the rank.  ``EventTime`` is a second of the row's
    ``EventDate``, uniform over its 86,400 and not sorted inside the day.
    ``SearchEngineID`` is 0 where the phrase is empty and drawn by Zipf's
    law (exponent 1) over 1..90 elsewhere.  A dictionary holds the values
    its rows hold."""
    import numpy as np

    if phrases > 1 << _PHRASE_RANK_BITS:
        raise ValueError(f"phrases {phrases}: a phrase spells a rank of {_PHRASE_RANK_BITS} bits")
    rng = np.random.default_rng(seed)
    user_values, user_fwd, _, _, _ = _hits_user_column(rng, num_rows, users)
    first_day = _hits_first_day(name)
    day = np.sort(rng.integers(0, _HITS_DAYS_A_SEGMENT, num_rows, dtype=np.int32))
    second = day.astype(np.int64) * 86_400 + rng.integers(0, 86_400, num_rows, dtype=np.int64)
    seconds = _HITS_DAYS_A_SEGMENT * 86_400
    time_values, time_fwd = _present(first_day * 86_400 + np.arange(seconds, dtype=np.int64), second, seconds)
    day_values, day_fwd = _present(first_day + np.arange(_HITS_DAYS_A_SEGMENT, dtype=np.int64), day, _HITS_DAYS_A_SEGMENT)

    # SearchPhrase: as UserID, the ranks drawn in ascending order, the
    # distinct ones the runs' heads; their phrases sorted as bytes (the
    # order of UTF-8 is the order of the code points, and of ``str``)
    asked = np.nonzero(rng.random(num_rows) >= HITS_PHRASE_EMPTY_SHARE)[0]
    rank = _zipf_ranks_sorted(rng, asked.size, phrases, HITS_PHRASE_EXPONENT)
    head = np.ones(asked.size, dtype=bool)
    head[1:] = rank[1:] != rank[:-1]
    run = np.cumsum(head, dtype=np.int32) - 1
    spelled = hits_phrase_bytes(rank[head])
    order = np.argsort(spelled.view(f"S{_PHRASE_BYTES}").ravel(), kind="stable")
    position = np.empty(order.size, dtype=np.int32)
    position[order] = np.arange(1, order.size + 1, dtype=np.int32)  # 0 is the empty phrase's
    phrase_fwd = np.zeros(num_rows, dtype=np.int32)
    phrase_fwd[asked] = position[run][rng.permutation(asked.size)]
    phrase_values = _phrase_strings(spelled[order])
    if asked.size < num_rows:
        phrase_values.insert(0, "")
    else:
        phrase_fwd -= 1
    engine = np.zeros(num_rows, dtype=np.int64)
    engine[asked] = 1 + _zipf_ranks_sorted(rng, asked.size, HITS_SEARCH_ENGINES, 1.0)[rng.permutation(asked.size)]
    engine_values, engine_fwd = _present(np.arange(HITS_SEARCH_ENGINES + 1, dtype=np.int64), engine, HITS_SEARCH_ENGINES + 1)

    pools = {
        "SearchPhrase": (phrase_values, phrase_fwd),
        "EventTime": (time_values, time_fwd),
        "SearchEngineID": (engine_values, engine_fwd),
        "UserID": (user_values, user_fwd),
        "EventDate": (day_values, day_fwd),
    }
    return _hits_segment(hits_search_schema(), pools, num_rows, seed, name)


# ---------------------------------------------------------------------------
# The Star Schema Benchmark (O'Neil, O'Neil, Chen, Revilak, 2009) as the
# denormalised table ``lineorder_flat`` (ClickHouse's SSB documentation):
# lineorder with the attributes of its customer, supplier, part and date
# folded in at ingestion, which is how a store without a join serves it.
# dbgen's data files are not shipped; the domains are dbgen's, and the
# functional dependencies hold row by row.
# ---------------------------------------------------------------------------

SSB_TABLE = "lineorder_flat"
SSB_FIRST_DAY, SSB_DAYS = "1992-01-01", 2406  # SSB's date dimension: to 1998-08-02
SSB_REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
# five nations a region, in the regions' order (TPC-H's 25)
SSB_NATIONS = (
    "ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE",
    "ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES",
    "CHINA", "INDIA", "INDONESIA", "JAPAN", "VIETNAM",
    "FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM",
    "EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA",
)
# ten cities a nation: the nation's first nine characters (padded) and a digit
SSB_CITIES = tuple(f"{n[:9]:<9}{d}" for n in SSB_NATIONS for d in range(10))
SSB_MFGRS = tuple(f"MFGR#{m}" for m in range(1, 6))
SSB_CATEGORIES = tuple(f"{m}{c}" for m in SSB_MFGRS for c in range(1, 6))  # 'MFGR#14'
SSB_BRANDS = tuple(f"{c}{b}" for c in SSB_CATEGORIES for b in range(1, 41))  # 'MFGR#2221'
_SSB_MONTHS = ("Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep", "Oct", "Nov", "Dec")
_SSB_UNIT_PRICES = 1024  # distinct part prices a segment


def lineorder_flat_schema() -> Schema:
    """Every column that SSB's thirteen queries read, under the flat
    table's names in lower case: 19 of its some 40 (the keys
    ``lo_custkey``, ``lo_suppkey``, ``lo_partkey`` are left out and
    their attributes kept)."""
    dims = [FieldSpec(c, DataType.STRING) for c in (
        "c_city", "c_nation", "c_region", "s_city", "s_nation", "s_region",
        "p_mfgr", "p_category", "p_brand", "d_yearmonth")]
    dims += [FieldSpec(c, DataType.INT) for c in ("lo_orderdate", "d_year", "d_yearmonthnum", "d_weeknuminyear")]
    return Schema(
        SSB_TABLE,
        dimensions=dims,
        metrics=[FieldSpec(c, DataType.INT, FieldType.METRIC) for c in (
            "lo_quantity", "lo_discount", "lo_extendedprice", "lo_revenue", "lo_supplycost")],
    )


def _sorted_position(values: Sequence[str]):
    """Where each of ``values`` stands in their sorted dictionary."""
    import numpy as np

    order = np.argsort(np.asarray(values, dtype=object), kind="stable")
    at = np.empty(len(values), dtype=np.int32)
    at[order] = np.arange(len(values), dtype=np.int32)
    return at


def synthetic_lineorder_flat_segment(num_rows: int, seed: int = 7, name: str = "lof0", segments: int = 16):
    """One segment of ``lineorder_flat``: a contiguous range of order
    dates, the ``k``-th of ``segments`` equal parts of SSB's 2,406 days
    (``k``: the digits that end ``name``, modulo ``segments``), with
    ``lo_orderdate`` sorted inside it.

    A row's customer, supplier and part are drawn uniformly, as dbgen
    draws the foreign keys, and stand here as the finest attribute the
    queries read (the city of 250, the brand of 1,000); the coarser ones
    follow from it, so city -> nation -> region and brand -> category ->
    manufacturer hold row by row, as ``d_year``, ``d_yearmonthnum``,
    ``d_yearmonth`` and ``d_weeknuminyear`` follow from the date.
    ``lo_quantity`` 1..50 and ``lo_discount`` 0..10 uniform; a part's
    price one of 1,024 a segment, uniform over dbgen's 90,000..209,900;
    ``lo_extendedprice`` = quantity x price, ``lo_revenue`` =
    extendedprice x (100 - discount) / 100 (dbgen's integer division),
    ``lo_supplycost`` = 6 x price / 10.  The rows of ``seed`` are the
    same in every process."""
    import datetime

    import numpy as np

    rng = np.random.default_rng(seed)
    digits = "".join(ch for ch in name if ch.isdigit())
    k = (int(digits[-6:]) if digits else 0) % segments
    first, last = k * SSB_DAYS // segments, (k + 1) * SSB_DAYS // segments
    day0 = datetime.date.fromisoformat(SSB_FIRST_DAY)
    days = [day0 + datetime.timedelta(d) for d in range(first, last)]

    made: Dict[str, Any] = {}
    pools: Dict[str, Any] = {}
    day = np.sort(rng.integers(0, len(days), num_rows, dtype=np.int32))
    # the date's attributes: a value a day, the rows' ids through the day
    by_day = {
        "lo_orderdate": [d.year * 10000 + d.month * 100 + d.day for d in days],
        "d_year": [d.year for d in days],
        "d_yearmonthnum": [d.year * 100 + d.month for d in days],
        "d_weeknuminyear": [(d.timetuple().tm_yday - 1) // 7 + 1 for d in days],
        "d_yearmonth": [f"{_SSB_MONTHS[d.month - 1]}{d.year}" for d in days],
    }
    for col, values in by_day.items():
        pools[col] = sorted(set(values))
        at = {v: i for i, v in enumerate(pools[col])}
        made[col] = np.asarray([at[v] for v in values], dtype=np.int32)[day]

    # customer, supplier, part: the finest attribute drawn, the coarser by division
    city_at, nation_at, region_at = (_sorted_position(v) for v in (SSB_CITIES, SSB_NATIONS, SSB_REGIONS))
    for who in ("c", "s"):
        city = rng.integers(0, len(SSB_CITIES), num_rows, dtype=np.int32)
        made[f"{who}_city"], made[f"{who}_nation"], made[f"{who}_region"] = (
            city_at[city], nation_at[city // 10], region_at[city // 50])
        pools[f"{who}_city"], pools[f"{who}_nation"], pools[f"{who}_region"] = SSB_CITIES, SSB_NATIONS, SSB_REGIONS
    brand = rng.integers(0, len(SSB_BRANDS), num_rows, dtype=np.int32)
    made["p_brand"], made["p_category"], made["p_mfgr"] = (
        _sorted_position(SSB_BRANDS)[brand], _sorted_position(SSB_CATEGORIES)[brand // 40],
        _sorted_position(SSB_MFGRS)[brand // 200])
    pools["p_brand"], pools["p_category"], pools["p_mfgr"] = SSB_BRANDS, SSB_CATEGORIES, SSB_MFGRS

    # the measures: small tables over (quantity, price) and (extendedprice, discount)
    prices = np.unique(rng.integers(90_000, 209_901, _SSB_UNIT_PRICES, dtype=np.int64))
    quantity = rng.integers(0, 50, num_rows, dtype=np.int32)  # dictId of 1..50
    discount = rng.integers(0, 11, num_rows, dtype=np.int32)  # dictId of 0..10
    price = rng.integers(0, prices.size, num_rows, dtype=np.int32)
    pools["lo_quantity"], made["lo_quantity"] = np.arange(1, 51, dtype=np.int64), quantity
    pools["lo_discount"], made["lo_discount"] = np.arange(0, 11, dtype=np.int64), discount
    ext_table = np.arange(1, 51, dtype=np.int64)[:, None] * prices[None, :]
    pools["lo_extendedprice"], ext_at = np.unique(ext_table, return_inverse=True)
    made["lo_extendedprice"] = ext_at.reshape(ext_table.shape).astype(np.int32)[quantity, price]
    rev_table = pools["lo_extendedprice"][:, None] * (100 - np.arange(0, 11, dtype=np.int64))[None, :] // 100
    pools["lo_revenue"], rev_at = np.unique(rev_table, return_inverse=True)
    made["lo_revenue"] = rev_at.reshape(rev_table.shape).astype(np.int32)[made["lo_extendedprice"], discount]
    cost = 6 * prices // 10
    pools["lo_supplycost"], cost_at = np.unique(cost, return_inverse=True)
    made["lo_supplycost"] = cost_at.astype(np.int32)[price]

    return _synthetic_columnar_segment(
        lineorder_flat_schema(), SSB_TABLE, pools, num_rows, seed, name, rng=rng, made=made,
    )
