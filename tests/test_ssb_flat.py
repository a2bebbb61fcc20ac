"""The Star Schema Benchmark over the denormalised ``lineorder_flat``
table (PR 47): all thirteen queries, through an in-process cluster and
the broker, against the benchmark's plain reference
(``benchmark/reference_ssb_flat.py``: numpy, nothing of the program) and
against the scan oracle (``tools/scan_engine.py``), none answered by the
host tier; the tier and the lowering of the seven shapes of the cell
``ssb_flat_drilldown_closed`` as ``PERF.md`` states them, read from
EXPLAIN; a reply of the postings tier held to the device's for the same
query; and the generator: the functional dependencies hold row by row
and a seed repeats its segment bit for bit.  The cell itself is
rehearsed with the other eight in ``test_benchmark_rehearsal.py``."""
import importlib.util
import json
import os

import numpy as np
import pytest

from pinot_tpu.pql import parse_pql
from pinot_tpu.tools import datagen
from pinot_tpu.tools.cluster_harness import InProcessCluster
from pinot_tpu.tools.scan_engine import ScanQueryProcessor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEGMENTS, ROWS, SEED = 3, 20_000, 2**31 + 47
TABLE = "lineorder_flat"


def _load(path: str):
    spec = importlib.util.spec_from_file_location("ssb_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref_mod = _load(os.path.join(BENCH, "reference_ssb_flat.py"))
CELL_SHAPES = json.load(open(os.path.join(BENCH, "traffic", "ssb_flat_drilldown_closed.json")))["shapes"]
# flights 1 and 2, which the cell leaves out (ISSUE 47) and the table holds the columns of
OTHER_SHAPES = [
    {"name": "q1_1", "filter": [["d_year", "=", 1993], ["lo_discount", "between", [1, 3]], ["lo_quantity", "<", 25]],
     "aggs": [["sum", {"expr": "lo_extendedprice*lo_discount"}]]},
    {"name": "q1_2", "filter": [["d_yearmonthnum", "=", 199401], ["lo_discount", "between", [4, 6]],
                                ["lo_quantity", "between", [26, 35]]],
     "aggs": [["sum", {"expr": "lo_extendedprice*lo_discount"}]]},
    {"name": "q1_3", "filter": [["d_weeknuminyear", "=", 6], ["d_year", "=", 1994], ["lo_discount", "between", [5, 7]],
                                ["lo_quantity", "between", [26, 35]]],
     "aggs": [["sum", {"expr": "lo_extendedprice*lo_discount"}]]},
    {"name": "q2_1", "filter": [["p_category", "=", "MFGR#12"], ["s_region", "=", "AMERICA"]],
     "group_by": ["d_year", "p_brand"], "top": 280, "aggs": [["sum", "lo_revenue"]]},
    {"name": "q2_2", "filter": [["p_brand", "between", ["MFGR#2221", "MFGR#2228"]], ["s_region", "=", "ASIA"]],
     "group_by": ["d_year", "p_brand"], "top": 56, "aggs": [["sum", "lo_revenue"]]},
    {"name": "q2_3", "filter": [["p_brand", "=", "MFGR#2239"], ["s_region", "=", "EUROPE"]],
     "group_by": ["d_year", "p_brand"], "top": 7, "aggs": [["sum", "lo_revenue"]]},
]
SHAPES = {s["name"]: s for s in OTHER_SHAPES + CELL_SHAPES}
PQL = {name: ref_mod.render_pql(TABLE, shape) for name, shape in SHAPES.items()}
CLEAN = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}


def make_segments(seed: int = SEED):
    return [datagen.synthetic_lineorder_flat_segment(ROWS, seed=seed * 1000 + i, name=f"seg{i}", segments=SEGMENTS)
            for i in range(SEGMENTS)]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(the cluster, the reference over its segments, the scan oracle
    over their rows)."""
    segments = make_segments()
    ref = ref_mod.Reference(SHAPES)
    for seg in segments:
        ref.add(seg)
    oracle = ScanQueryProcessor(datagen.lineorder_flat_schema(), [r for seg in segments for r in seg.rows()])
    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path_factory.mktemp("ssb")))
    try:
        physical = cluster.add_offline_table(datagen.lineorder_flat_schema())
        for seg in segments:
            cluster.upload(physical, seg)
        yield cluster, ref, oracle
    finally:
        cluster.stop()
        for server in cluster.servers:
            server.shutdown()


def groups_of(reply: dict) -> dict:
    (result,) = reply["aggregationResults"]
    if "groupByResult" not in result:
        return {(): float(result["value"])}
    return {tuple(g["group"]): float(g["value"]) for g in result["groupByResult"]}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_every_ssb_query_equals_the_reference_and_the_scan_oracle(served, name):
    cluster, ref, oracle = served
    reply = cluster.query(PQL[name]).to_json()
    assert not reply["exceptions"] and not reply.get("cost", {}).get("segmentsHost", 0), reply
    got = ref_mod.compare(reply, SHAPES[name], ref.answers[name], ref.rows)
    assert got == dict(CLEAN, sum_gap=got["sum_gap"]) and got["sum_gap"] < 1e-5, (name, got)
    want = oracle.execute(parse_pql(PQL[name])).to_json()
    assert reply["numDocsScanned"] == want["numDocsScanned"] and reply["totalDocs"] == SEGMENTS * ROWS
    have, scanned = groups_of(reply), groups_of(want)
    assert set(have) == set(scanned), name
    for key, value in scanned.items():
        assert have[key] == pytest.approx(value, rel=1e-5), (name, key)
    if name not in ("q3_4", "q1_3"):  # a month of two cities each, a week of a year: may be no row at this size
        assert reply["numDocsScanned"] > 0, name


# -- the seven shapes of the cell: tier and lowering, as PERF.md states them ----
# (tier, planned key space, the space the filter's leaves on the keys leave, the chip's lowering, its operands)
AS_PERF_MD_STATES = {
    "q3_1": ("fullScan", 4_375, 3_750, "radix", "staged"),
    "q3_2": ("fullScan", 437_500, 375_000, "radix", "sorted"),
    "q3_3": ("postings", None, None, None, None),
    "q3_4": ("postings", None, None, None, None),
    "q4_1": ("fullScan", 175, None, "onehot", "staged"),
    "q4_2": ("fullScan", 4_375, 1_250, "radix", "staged"),
    "q4_3": ("fullScan", 1_750_000, 500_000, "runs", "staged"),
}


@pytest.mark.parametrize("name", sorted(AS_PERF_MD_STATES))
def test_the_cells_shapes_take_the_tier_and_lowering_perf_md_states(served, monkeypatch, name):
    """EXPLAIN's words, on the table cut to three date ranges: the tier
    record of the live segments, and for a device group-by the planned
    key space, what the filter leaves of it, and the lowering the chip's
    backend takes (asked for here by the tests' switch; on the CPU every
    dense lowering is 'scatter')."""
    cluster, _ref, _oracle = served
    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    (node,) = cluster.query("EXPLAIN PLAN FOR " + PQL[name]).to_json()["explain"]["servers"]
    tier, cells, left, lowering, operands = AS_PERF_MD_STATES[name]
    tiers = {s["tier"] for s in node["segments"]} - {"pruned"}
    assert tiers == {tier}, node["segments"]
    assert "segmentsHost" not in node["tierCounts"]
    if cells is None:
        assert "groupBy" not in node.get("device", {})
        assert {s.get("drivingColumn") for s in node["segments"] if s["tier"] == tier} == {"c_city"}
        return
    record = node["device"]["groupBy"]
    assert (record["keySpaceCells"], record["lowering"], record["operands"]) == (cells, lowering, operands)
    assert record.get("filteredKeySpaceCells") == left


# what the value pruner (PR 48) leaves of the three date ranges: the third holds 1996-05 to 1998-08
SEGMENTS_DEAD = {"q3_1": 0, "q3_2": 0, "q3_3": 0, "q3_4": 2, "q4_1": 0, "q4_2": 2, "q4_3": 2}


@pytest.mark.parametrize("name", sorted(SEGMENTS_DEAD))
def test_a_shape_works_over_the_date_ranges_its_filter_can_match(served, name):
    """A segment is a range of order dates, so ``d_year = 1997 OR d_year =
    1998`` and ``d_yearmonth = 'Dec1997'`` can match in the last range
    alone: the postings tier answers q3_4 from one segment's postings, the
    device q4_2 and q4_3 from one segment of the three that stay staged;
    ``totalDocs`` stays the table's, and the reply stays the reference's."""
    cluster, ref, _oracle = served
    server = cluster.servers[0]
    marks = lambda: {k: server.metrics.snapshot()["meters"].get(f"prune.segments.{k}", {"count": 0})["count"] for k in ("offered", "value")}
    before = marks()
    reply = cluster.query(PQL[name]).to_json()
    dead = SEGMENTS_DEAD[name]
    tier = "segmentsPostings" if AS_PERF_MD_STATES[name][0] == "postings" else "segmentsFullScan"
    assert reply["cost"].get("segmentsPruned", 0) == dead and reply["cost"][tier] == SEGMENTS - dead, reply["cost"]
    assert reply["numSegmentsQueried"] == SEGMENTS - dead and reply["totalDocs"] == SEGMENTS * ROWS
    assert {k: v - before[k] for k, v in marks().items()} == {"offered": SEGMENTS, "value": dead}
    got = ref_mod.compare(reply, SHAPES[name], ref.answers[name], ref.rows)
    assert got == dict(CLEAN, sum_gap=got["sum_gap"]) and got["sum_gap"] < 1e-5, (name, got)


def test_a_postings_reply_is_the_devices_reply(served, monkeypatch):
    """q3_3 through the postings tier, then with the tier switched off in
    this test alone through the device: the same groups, sums, counts,
    live count and digest."""
    cluster, ref, _oracle = served
    by_postings = cluster.query(PQL["q3_3"]).to_json()
    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")
    by_device = cluster.query(PQL["q3_3"]).to_json()
    assert by_postings["cost"].get("segmentsPostings") and not by_device["cost"].get("segmentsPostings")
    assert by_device["cost"].get("deviceMs", 0) > 0 and not by_device["cost"].get("segmentsHost", 0)
    assert groups_of(by_postings).keys() == groups_of(by_device).keys() and groups_of(by_postings)
    for key, value in groups_of(by_device).items():
        assert groups_of(by_postings)[key] == pytest.approx(value, rel=1e-6)
    for number in ("numDocsScanned", "totalDocs"):
        assert by_postings[number] == by_device[number]
    assert by_postings["cost"]["numGroupsLive"] == by_device["cost"]["numGroupsLive"] == len(groups_of(by_device))
    assert by_postings["cost"]["groupStateSumSq"] == pytest.approx(by_device["cost"]["groupStateSumSq"], rel=1e-6)
    for reply in (by_postings, by_device):
        assert ref_mod.compare(reply, SHAPES["q3_3"], ref.answers["q3_3"], ref.rows)["sum_gap"] < 1e-5


def test_the_ladder_marks_the_tier_that_answered(served):
    cluster, _ref, _oracle = served
    meters = cluster.servers[0].metrics.snapshot()["meters"]
    before = {t: meters.get(f"tier.answered.{t}", {"count": 0})["count"] for t in ("postings", "device", "host")}
    cluster.query(PQL["q3_3"])
    cluster.query(PQL["q4_1"])
    meters = cluster.servers[0].metrics.snapshot()["meters"]
    after = {t: meters.get(f"tier.answered.{t}", {"count": 0})["count"] for t in before}
    assert (after["postings"] - before["postings"], after["device"] - before["device"], after["host"]) == (1, 1, 0)
    assert meters["groupby.keySpaceCells"]["count"] >= 175


def test_a_quarantined_plans_answer_marks_the_host(served, monkeypatch):
    """What the chip showed at the cell's size (PR 47, call A): q3_2's
    first compile outran the lane's watchdog, the plan was quarantined,
    and the host answered the shape from inside the device rung: that is
    a ``host`` mark, which ``tier_host_share`` must see."""
    cluster, _ref, _oracle = served
    executor = cluster.servers[0].executor
    count = lambda: cluster.servers[0].metrics.snapshot()["meters"].get("tier.answered.host", {"count": 0})["count"]
    before = count()
    monkeypatch.setattr(executor, "_is_poisoned", lambda key: True)
    reply = cluster.query(PQL["q3_2"]).to_json()
    assert reply["cost"]["segmentsHost"] == 3 and not reply["exceptions"]
    assert count() - before == 1


# -- the generator -------------------------------------------------------------


def column_values(seg, name: str) -> np.ndarray:
    col = seg.column(name)
    values = col.dictionary.values
    return (np.asarray(values, dtype=object) if isinstance(values, list) else np.asarray(values))[col.fwd]


def test_the_functional_dependencies_hold_row_by_row():
    for seg in make_segments():
        for who in ("c", "s"):
            city, nation, region = (column_values(seg, f"{who}_{what}") for what in ("city", "nation", "region"))
            assert all(c[:9].rstrip() == n[:9].rstrip() for c, n in zip(city[:2000], nation[:2000]))
            assert len({(c, n) for c, n in zip(city, nation)}) == len(set(city)) == 250
            assert len({(n, r) for n, r in zip(nation, region)}) == len(set(nation)) == 25 and len(set(region)) == 5
        brand, category, mfgr = (column_values(seg, c) for c in ("p_brand", "p_category", "p_mfgr"))
        assert all(b.startswith(c) and c.startswith(m) for b, c, m in zip(brand[:2000], category[:2000], mfgr[:2000]))
        assert (len(set(brand)), len(set(category)), len(set(mfgr))) == (1000, 25, 5)
        date, year, month, text = (column_values(seg, c) for c in ("lo_orderdate", "d_year", "d_yearmonthnum", "d_yearmonth"))
        assert np.array_equal(year, date // 10000) and np.array_equal(month, date // 100)
        months = ("Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep", "Oct", "Nov", "Dec")
        assert all(t == f"{months[m % 100 - 1]}{m // 100}" for t, m in zip(text[::97], month[::97]))
        assert np.all(date[1:] >= date[:-1]) and seg.column("lo_orderdate").metadata.is_sorted
        ext, disc, rev, qty, cost = (column_values(seg, c) for c in (
            "lo_extendedprice", "lo_discount", "lo_revenue", "lo_quantity", "lo_supplycost"))
        assert np.array_equal(rev, ext * (100 - disc) // 100)
        price = ext // qty
        assert np.array_equal(ext, price * qty) and np.array_equal(cost, 6 * price // 10)
        assert price.min() >= 90_000 and price.max() <= 209_900 and (qty.min(), qty.max()) == (1, 50)
        assert (disc.min(), disc.max()) == (0, 10)
    first, last = make_segments()[0], make_segments()[-1]
    assert column_values(first, "lo_orderdate")[0] // 100 == 199201 and column_values(last, "lo_orderdate")[-1] // 100 == 199808


def test_a_seed_repeats_its_segment_bit_for_bit():
    a, b = (datagen.synthetic_lineorder_flat_segment(5_000, seed=SEED, name="seg14") for _ in range(2))
    other = datagen.synthetic_lineorder_flat_segment(5_000, seed=SEED + 1, name="seg14")
    assert a.compute_crc() == b.compute_crc() != other.compute_crc()
    for name in a.columns:
        assert np.array_equal(a.column(name).fwd, b.column(name).fwd), name
    # the sixteenth range by its name's digits: 1997-10-06 to 1998-03-04, Dec1997 inside it
    assert "Dec1997" in a.column("d_yearmonth").dictionary.values
    assert (a.column("lo_orderdate").dictionary.values[0], a.column("lo_orderdate").dictionary.values[-1]) == (19971006, 19980304)
