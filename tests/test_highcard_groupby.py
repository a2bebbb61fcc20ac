"""A group-by over more keys than the contraction takes (PR 37): TPC-H
Q15's view by ``l_suppkey`` and its maximum as TOP n, through broker and
server against the benchmark's plain reference
(``benchmark/reference_tpch_keys.py``: numpy, float64, nothing of the
program), at 70,000 suppliers (a segment's states fold a block at a time:
the zone tier ``inplace``) and at 300,000 (over ``_INPLACE_STATE_CELLS``:
``gathered``); both above ``RADIX_GROUP_CAP``: on the chip the rows are put
in key order for the contraction (PR 38; forced on here in one case), on
the CPU the scatter adds them up.  What the launch's tags and marks say
is what the kernel builder asks; the generator's nine older columns are the plain
lineitem's."""
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from pinot_tpu.engine import kernel as kernel_mod
from pinot_tpu.engine.mesh import build_topology
from pinot_tpu.tools.cluster_harness import single_server_broker
from pinot_tpu.tools.datagen import (
    lineitem_keys_schema,
    lineitem_schema,
    synthetic_lineitem_keys_segment,
    synthetic_lineitem_segment,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEGMENTS, ROWS = 4, 50_000
ZONE_BLOCK = 512  # 50,000 rows are staged as 65,536: 128 blocks, as 8,388,608 rows are in blocks of 65,536
ZONE_FORM = {70_000: "inplace", 300_000: "gathered"}  # occupancy and one sum: 2 x K cells against 2^18
SUM_RTOL = 1e-6  # float64 on the CPU: the gap is the reply's five decimals of a revenue of 1e5


def _load(path: str):
    spec = importlib.util.spec_from_file_location("keys_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


keys_ref = _load(os.path.join(BENCH, "reference_tpch_keys.py"))
CELL = {s["name"]: s for s in json.load(open(os.path.join(BENCH, "traffic", "tpch_q15_closed.json")))["shapes"]}
Q15 = CELL["q15_1996q1"]
SHAPES = dict(
    CELL,
    # every group of the quarter, counted too: keys and counts exact
    every=dict(Q15, top=300_000, aggs=Q15["aggs"] + [["count", "*"]]),
    # a window that keeps no row (the generator's dates end on 1997-12-12)
    none=dict(Q15, filter=[["l_shipdate", ">=", "1998-07-01"], ["l_shipdate", "<", "1998-10-01"]]),
    # the quarter's blocks reach the device and no row of them passes: the finalize of an empty state
    nobody=dict(Q15, filter=Q15["filter"] + [["l_quantity", "=", 1], ["l_discount", "=", 0.05], ["l_tax", "=", 0.03],
                                             ["l_returnflag", "=", "A"], ["l_shipmode", "=", "AIR"]]),
    # no filter: every row through the full scan, over 100,000 live groups through the trim
    unfiltered=dict(Q15, filter=[], top=5, aggs=Q15["aggs"] + [["count", "*"]]),
)
PQL = {name: keys_ref.render_pql("lineitem", shape) for name, shape in SHAPES.items()}


def forget_programs():
    for cached in (kernel_mod.make_table_kernel, kernel_mod.make_packed_table_kernel,
                   kernel_mod.make_block_table_kernel, kernel_mod.make_packed_block_table_kernel):
        cached.cache_clear()


@pytest.fixture(autouse=True)
def blocks_of_512(monkeypatch):
    monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", str(ZONE_BLOCK))


@pytest.fixture(scope="module", params=sorted(ZONE_FORM))
def table(request):
    """(suppliers, the segments, the reference's answers over them)."""
    suppliers = request.param
    segments = [synthetic_lineitem_keys_segment(ROWS, seed=3700 + i, name=f"li{i}", suppliers=suppliers)
                for i in range(SEGMENTS)]
    ref = keys_ref.Reference(SHAPES)
    for seg in segments:
        ref.add(seg)
    return suppliers, segments, ref


@pytest.fixture(scope="module")
def served(table):
    """One broker and server over the table for the module's queries."""
    suppliers, segments, ref = table
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PINOT_TPU_ZONE_BLOCK", str(ZONE_BLOCK))
        forget_programs()
        broker = single_server_broker("lineitem", segments)
        try:
            yield suppliers, broker, ref
        finally:
            broker.local_servers[0].shutdown()
            forget_programs()


def held(reply: dict, name: str, ref) -> dict:
    got = keys_ref.compare(reply, SHAPES[name], ref.answers[name], ref.rows)
    assert got["count_errors"] == got["key_errors"] == got["reply_errors"] == 0, (got, reply.get("exceptions"), reply.get("cost"))
    assert got["sum_gap"] <= SUM_RTOL, got
    return got


def launches(resp, server) -> list:
    return [s for s in resp.trace_info["scopes"][server.name] if s["span"] == "laneDispatch"]


@pytest.mark.parametrize("seed", [7, 3700, 2**31 + 37])
def test_a_seeds_nine_older_columns_are_the_plain_lineitems(seed):
    keyed = synthetic_lineitem_keys_segment(5000, seed=seed, name="s", suppliers=1000)
    plain = synthetic_lineitem_segment(5000, seed=seed, name="s")
    assert list(keyed.columns) == list(plain.columns) + ["l_suppkey"]
    assert lineitem_keys_schema().column_names == [c.name for c in lineitem_schema().dimensions] + ["l_suppkey"] + [
        c.name for c in lineitem_schema().metrics]
    for name, column in plain.columns.items():
        assert np.array_equal(keyed.column(name).fwd, column.fwd), name
        assert list(keyed.column(name).dictionary.values) == list(column.dictionary.values), name
        assert keyed.column(name).metadata.is_sorted == column.metadata.is_sorted == (name == "l_shipdate")
    key = keyed.column("l_suppkey")
    assert list(key.dictionary.values) == list(range(1, 1001))  # every segment's dictionary holds every supplier
    counts = np.bincount(key.fwd, minlength=1000)
    assert counts.min() >= 0 and abs(counts.mean() - 5.0) < 1e-9 and counts.max() < 25  # uniform, as dbgen's is
    assert synthetic_lineitem_keys_segment(10, seed=seed).column("l_suppkey").dictionary.cardinality == 220_000


@pytest.mark.parametrize("quarter", sorted(CELL))
def test_q15_top_1_equals_the_reference_at_each_date_of_the_cell(served, quarter):
    suppliers, broker, ref = served
    server = broker.local_servers[0]
    resp = broker.handle_pql(PQL[quarter], trace=True)
    reply = resp.to_json()
    held(reply, quarter, ref)
    (group,) = reply["aggregationResults"][0]["groupByResult"]
    answer = ref.answers[quarter]
    assert int(group["group"][0]) == int(answer["keys"][np.argmax(answer["sums"][0])])
    cost = reply["cost"]
    assert cost["segmentsZonemap"] == SEGMENTS and "segmentsHost" not in cost and cost["exprAggs"] == 1
    assert cost["numGroupsLive"] == keys_ref.live_groups(answer) > 1000 and cost["numGroupsKept"] == 100
    # the digest of the whole state, where the reply shows one group of it
    assert cost["groupStateSumSq"] == pytest.approx(float(np.dot(answer["sums"][0], answer["sums"][0])), rel=1e-9)
    (launch,) = launches(resp, server)
    tags = launch["tags"]
    assert tags["program"].startswith("pinot_zone_gb") and tags["groupby"] == "scatter" and tags["expr"] == 1
    assert tags["blocks"] == ZONE_FORM[suppliers] and tags["cells"] == 2 * suppliers
    (finalize,) = [s for s in resp.trace_info["scopes"][server.name] if s["span"] == "finalize"]
    assert finalize["tags"]["groups"] == cost["numGroupsLive"]
    assert not [s for s in resp.trace_info["scopes"][server.name] if s["span"] == "groupTrim"]  # a timer, not a span


def test_every_group_of_the_quarter_with_keys_and_counts_exact(served):
    suppliers, broker, ref = served
    reply = broker.handle_pql(PQL["every"]).to_json()
    got = held(reply, "every", ref)
    answer = ref.answers["every"]
    revenue, counted = (r["groupByResult"] for r in reply["aggregationResults"])
    live = keys_ref.live_groups(answer)
    assert len(revenue) == len(counted) == live == reply["cost"]["numGroupsLive"] == reply["cost"]["numGroupsKept"]
    assert {int(g["group"][0]) for g in revenue} == {int(k) for k in answer["keys"][answer["counts"] > 0]}
    assert sum(int(float(g["value"])) for g in counted) == answer["matched"] == reply["numDocsScanned"]
    assert got["sum_gap"] > 0  # five decimals of a float64: compared, not skipped


def test_a_window_that_keeps_no_row(served):
    suppliers, broker, ref = served
    reply = broker.handle_pql(PQL["none"]).to_json()
    held(reply, "none", ref)
    assert reply["aggregationResults"][0]["groupByResult"] == [] and reply["numDocsScanned"] == 0
    assert reply["cost"].get("numGroupsLive", 0) == 0 and reply["totalDocs"] == SEGMENTS * ROWS


def test_a_quarter_in_which_no_row_passes_is_answered_on_the_device(served):
    suppliers, broker, ref = served
    server = broker.local_servers[0]
    assert ref.answers["nobody"]["matched"] == 0 < ref.answers["nobody"]["sorted_matched"]
    resp = broker.handle_pql(PQL["nobody"], trace=True)
    reply = resp.to_json()
    held(reply, "nobody", ref)
    assert reply["aggregationResults"][0]["groupByResult"] == [] and reply["numDocsScanned"] == 0
    assert "segmentsHost" not in reply["cost"] and "numGroupsLive" not in reply["cost"]  # a cost vector keeps no zero
    (launch,) = launches(resp, server)
    assert launch["tags"]["groupby"] == "scatter"
    (finalize,) = [s for s in resp.trace_info["scopes"][server.name] if s["span"] == "finalize"]
    assert finalize["tags"]["groups"] == 0 and server.executor.healing_stats()["hostFailovers"] == 0


def test_every_row_through_the_full_scan_and_the_trim(served):
    suppliers, broker, ref = served
    server = broker.local_servers[0]
    before = {m: server.metrics.meter(f"groupby.groups.{m}").count for m in ("live", "kept")}
    trims = server.metrics.timer("phase.groupTrim").count
    resp = broker.handle_pql(PQL["unfiltered"], trace=True)
    reply = resp.to_json()
    held(reply, "unfiltered", ref)
    live = keys_ref.live_groups(ref.answers["unfiltered"])
    assert 50_000 < live <= suppliers and reply["cost"]["segmentsFullScan"] == SEGMENTS
    # two aggregates trim to 100 each (5 x TOP is 25); a count of 2 to 12 rows ties at the boundary, capped
    assert 100 <= reply["cost"]["numGroupsKept"] <= 200 + 2 * 10_000
    assert server.metrics.meter("groupby.groups.live").count - before["live"] == live
    assert server.metrics.meter("groupby.groups.kept").count - before["kept"] == reply["cost"]["numGroupsKept"]
    assert server.metrics.timer("phase.groupTrim").count == trims + 1
    (launch,) = launches(resp, server)
    assert launch["tags"]["groupby"] == "scatter" and "blocks" not in launch["tags"]


def test_two_aggregates_of_opposite_order_through_the_trim(table, served, monkeypatch):
    """The sum's largest and the minimum's smallest, each trimmed to 100
    with its boundary's ties (some 4,000 groups' least quantity is 1),
    against numpy over the segments: the kept keys as the trim returns
    them, the counts and the digest of the whole state."""
    from pinot_tpu.engine import results

    suppliers, segments, _ = table
    _, broker, _ = served
    fwd = np.concatenate([seg.column("l_suppkey").fwd for seg in segments])  # every dictionary holds every supplier
    price, quantity = (np.concatenate([seg.column(c).dictionary.values[seg.column(c).fwd] for seg in segments])
                       .astype(np.float64) for c in ("l_extendedprice", "l_quantity"))
    live = np.nonzero(np.bincount(fwd, minlength=suppliers))[0]
    sums = np.bincount(fwd, weights=price, minlength=suppliers)[live]
    mins = np.full(suppliers, np.inf)
    np.minimum.at(mins, fwd, quantity)
    mins = mins[live]
    kept = np.nonzero((sums >= np.sort(sums)[-100]) | (mins <= np.sort(mins)[99]))[0]
    assert 1000 < np.count_nonzero(mins == mins.min()) < results.MAX_TRIM_TIES and kept.size > 1100

    seen = []
    real = results.trim_group_candidates
    monkeypatch.setattr(results, "trim_group_candidates", lambda *a: seen.append(real(*a)) or seen[-1])
    reply = broker.handle_pql("SELECT sum(l_extendedprice), min(l_quantity) FROM lineitem GROUP BY l_suppkey TOP 5").to_json()
    assert not reply["exceptions"] and not reply["cost"].get("segmentsHost")
    (keep,) = seen
    assert np.array_equal(keep, kept)
    cost = reply["cost"]
    assert cost["numGroupsLive"] == live.size and cost["numGroupsKept"] == kept.size
    assert cost["groupStateSumSq"] == pytest.approx(float(np.dot(sums, sums) + np.dot(mins, mins)), rel=1e-9)
    by_sum, by_min = (r["groupByResult"] for r in reply["aggregationResults"])
    top = np.argsort(-sums, kind="stable")[:5]
    assert [int(g["group"][0]) for g in by_sum] == [int(live[i]) + 1 for i in top]
    np.testing.assert_allclose([float(g["value"]) for g in by_sum], sums[top], rtol=SUM_RTOL)
    assert [float(g["value"]) for g in by_min] == [mins.min()] * 5
    assert {int(g["group"][0]) - 1 for g in by_min} <= set(live[mins == mins.min()].tolist())


def test_the_full_scan_equals_the_zone_tier(served, monkeypatch):
    suppliers, broker, ref = served
    zone = {name: broker.handle_pql(PQL[name]).to_json() for name in ("q15_1996q1", "every", "none")}
    monkeypatch.setenv("PINOT_TPU_ZONEMAP", "0")
    for name, by_zone in zone.items():
        reply = broker.handle_pql(PQL[name]).to_json()
        held(reply, name, ref)
        if name != "none":  # a filter that matches nothing is the postings tier's without the zone maps
            assert reply["cost"].get("segmentsFullScan") == SEGMENTS and "segmentsZonemap" not in reply["cost"]
            assert by_zone["cost"]["segmentsZonemap"] == SEGMENTS
        assert not reply["cost"].get("segmentsHost") and not by_zone["cost"].get("segmentsHost")
        assert reply["numDocsScanned"] == by_zone["numDocsScanned"]
        for ours, theirs in zip(reply["aggregationResults"], by_zone["aggregationResults"]):
            assert [g["group"] for g in ours["groupByResult"]] == [g["group"] for g in theirs["groupByResult"]]
            np.testing.assert_allclose([float(g["value"]) for g in ours["groupByResult"]],
                                       [float(g["value"]) for g in theirs["groupByResult"]], rtol=1e-9)
        for key in ("numGroupsLive", "numGroupsKept"):
            assert reply["cost"].get(key, 0) == by_zone["cost"].get(key, 0)
        assert reply["cost"].get("groupStateSumSq", 0) == pytest.approx(by_zone["cost"].get("groupStateSumSq", 0), rel=1e-9)


@pytest.mark.parametrize("lowerings", ["the_cpus", "the_chips_forced"])
def test_the_lowering_the_zone_form_and_the_marks_agree(table, monkeypatch, lowerings):
    """What the kernel builder asks (``groupby_lowering``, ``zone_blocks``)
    is what the launch is tagged and marked with.  Both K are over
    ``RADIX_GROUP_CAP``: with the chip's lowerings forced the rows are
    put in key order for the contraction (``radix`` / ``sorted``); the
    CPU's own answer stays the scatter."""
    from pinot_tpu.engine.executor import QueryExecutor

    suppliers, segments, ref = table
    if lowerings == "the_chips_forced":
        monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    forget_programs()
    plans = []
    run_kernel = QueryExecutor._run_kernel

    def spy(self, kernel, args, plan, *rest, **kw):
        plans.append(plan)
        return run_kernel(self, kernel, args, plan, *rest, **kw)

    monkeypatch.setattr(QueryExecutor, "_run_kernel", spy)
    broker = single_server_broker("lineitem", segments)
    server = broker.local_servers[0]
    try:
        resp = broker.handle_pql(PQL["q15_1996q1"], trace=True)
        held(resp.to_json(), "q15_1996q1", ref)
        (plan,), (launch,) = plans, launches(resp, server)
        assert plan.group_by.capacity == suppliers > kernel_mod.RADIX_GROUP_CAP
        forced = lowerings == "the_chips_forced"
        assert kernel_mod.groupby_lowering(plan) == launch["tags"]["groupby"] == ("radix" if forced else "scatter")
        assert kernel_mod.zone_blocks(plan) == launch["tags"]["blocks"] == ZONE_FORM[suppliers]
        assert (kernel_mod._state_cells(plan) <= kernel_mod._INPLACE_STATE_CELLS) == (ZONE_FORM[suppliers] == "inplace")
        assert kernel_mod.groupby_operands(plan) == launch["tags"]["operands"] == ("sorted" if forced else "staged")
        assert kernel_mod.groupby_cells(plan) == (launch["tags"]["cells"], 0)
        marks = {m: server.metrics.meter(m).count for m in (
            "groupby.lowering.scatter", "groupby.lowering.radix", "groupby.lowering.onehot", "groupby.operands.loop",
            "groupby.operands.sorted",
            "zone.blocks.inplace", "zone.blocks.gathered", "agg.expr.device", "agg.expr.host")}
        assert marks == {"groupby.lowering.scatter": int(not forced), "groupby.lowering.radix": int(forced),
                         "groupby.lowering.onehot": 0, "groupby.operands.loop": 0,
                         "groupby.operands.sorted": int(forced), "zone.blocks.inplace": int(ZONE_FORM[suppliers] == "inplace"),
                         "zone.blocks.gathered": int(ZONE_FORM[suppliers] == "gathered"),
                         "agg.expr.device": 1, "agg.expr.host": 0}
        assert server.executor.healing_stats()["hostFailovers"] == 0
    finally:
        server.shutdown()
        forget_programs()


@pytest.mark.parametrize("lowerings", ["the_cpus", "the_chips_forced"])
def test_q15_on_a_mesh_of_four(table, monkeypatch, lowerings):
    """The same plan through ``shard_map``: a segment a chip, the states
    merged across chips; a chip sorts its own segment's rows, so the
    chip's lowering needs no collective of its own."""
    suppliers, segments, ref = table
    forced = lowerings == "the_chips_forced"
    if forced:
        monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    forget_programs()
    broker = single_server_broker("lineitem", segments, topology=build_topology(jax.devices()[:4], 1, 4))
    server = broker.local_servers[0]
    try:
        for name in ("q15_1994q4", "every"):
            resp = broker.handle_pql(PQL[name], trace=True)
            held(resp.to_json(), name, ref)
            (launch,) = launches(resp, server)
            assert (launch["tags"]["groupby"], launch["tags"]["operands"]) == (
                ("radix", "sorted") if forced else ("scatter", "staged"))
            assert launch["tags"]["blocks"] == ZONE_FORM[suppliers]
        assert server.executor.healing_stats()["hostFailovers"] == 0
    finally:
        server.shutdown()
        forget_programs()
