"""The executor's prepared-query memo (``engine/executor.py _Prepared``,
ISSUE 32): what a repeated query may reuse, and what must never be stale.

A hit answers as the miss before it did, to the bit; every query that
reaches the tier ladder marks exactly one of ``plan.prepared.hit``,
``.miss`` and ``.stale``; the spans a benchmark reader divides by still
open once a query on a hit; the entry and byte bounds hold.  And the key
follows everything the derivations read: a segment loaded anew under its
name, a consuming segment between two ingests, a flipped setting; while
what is state and not a function of the key (an audit quarantine, a
poison mark, the staged table of the moment) is looked at on every query.
"""
import json
import os
import sys
import threading

import jax
import pytest

from pinot_tpu.engine import executor as executor_mod
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.pql.parser import parse_pql
from pinot_tpu.tools.datagen import synthetic_lineitem_segment

# upstream's Q0 to Q6 (BASELINE.md) and the K=6 TPC-H Q1 shape of ``traffic/suite_open.json``
SHAPES = {
    "q0": "SELECT sum(l_extendedprice), sum(l_discount) FROM lineitem",
    "q1": "SELECT sum(l_extendedprice) FROM lineitem WHERE l_returnflag = 'R'",
    "q2": "SELECT sum(l_extendedprice) FROM lineitem WHERE l_shipdate BETWEEN '1996-12-01' AND '1996-12-31'",
    "q3": "SELECT sum(l_extendedprice) FROM lineitem GROUP BY l_shipdate TOP 10",
    "q4": "SELECT sum(l_extendedprice), sum(l_quantity) FROM lineitem GROUP BY l_shipdate TOP 10",
    "q5": "SELECT sum(l_extendedprice) FROM lineitem WHERE l_shipdate BETWEEN '1995-01-01' AND '1996-12-31' "
          "GROUP BY l_shipdate TOP 10",
    "q6": "SELECT sum(l_extendedprice) FROM lineitem WHERE l_shipmode IN ('RAIL','FOB') AND "
          "l_receiptdate BETWEEN '1997-01-01' AND '1997-12-31' GROUP BY l_shipmode TOP 10",
    "k6": "SELECT sum(l_quantity), sum(l_extendedprice), sum(l_discount), count(*) FROM lineitem "
          "WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag, l_linestatus TOP 10",
}
OUTCOMES = ("hit", "miss", "stale")
# of a reply's cost vector, what one execution of a query repeats in the next: not its times,
# and not what the other caches of the path (uploaded inputs, an identical launch in flight) found
REPEATS = ("bytesScanned", "deviceBytes", "segmentsPostings", "segmentsBitsliced", "segmentsZonemap",
           "segmentsFullScan", "segmentsHost")


def _segments(n: int = 4, rows: int = 2500, seed: int = 320, prefix: str = "prep"):
    return [synthetic_lineitem_segment(rows, seed=seed + i, name=f"{prefix}{i}") for i in range(n)]


def _a_price_of_every_segment(segs) -> float:
    """A needle every segment's dictionary holds: one that a segment lacks
    leaves that segment out of the work (the value pruner, PR 48), and the
    tier's count of segments with it."""
    common = set.intersection(*(set(map(float, s.column("l_extendedprice").dictionary.values)) for s in segs))
    return sorted(common)[len(common) // 2]


def _marks(metrics) -> dict:
    return {o: metrics.meter(f"plan.prepared.{o}").count for o in OUTCOMES}


def _moved(metrics, before: dict) -> dict:
    return {o: n - before[o] for o, n in _marks(metrics).items()}


def _payload(reply) -> str:
    body = reply.to_json()
    assert not body["exceptions"], body["exceptions"]
    kept = {k: v for k, v in body.items() if k not in ("timeUsedMs", "requestId", "cost")}
    kept["cost"] = {k: body["cost"].get(k, 0) for k in REPEATS}
    return json.dumps(kept, sort_keys=True)


@pytest.fixture(scope="module")
def segments():
    return _segments()


@pytest.fixture(scope="module", params=["one_device", "mesh_1x4"])
def served(request, segments):
    """One server behind a broker: on one device, and over a 1x4 mesh of conftest's virtual devices."""
    from pinot_tpu.engine.mesh import build_topology
    from pinot_tpu.tools.cluster_harness import single_server_broker

    kwargs = {"topology": build_topology(jax.devices(), 1, 4)} if request.param == "mesh_1x4" else {}
    broker = single_server_broker("lineitem", segments, **kwargs)
    yield broker
    broker.local_servers[0].shutdown()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_hit_answers_as_the_miss_before_it_did(served, shape):
    metrics = served.local_servers[0].metrics
    spans = {p: metrics.timer(f"phase.{p}") for p in ("staging", "planBuild", "kernelPrep", "indexPath", "bitslicedPath")}
    before = _marks(metrics)
    first = served.handle_pql(SHAPES[shape])
    assert _moved(metrics, before) == {"hit": 0, "miss": 1, "stale": 0}
    assert "preparedHit" not in first.to_json()["cost"]
    counted = {p: t.count for p, t in spans.items()}
    second = served.handle_pql(SHAPES[shape])
    third = served.handle_pql(SHAPES[shape])
    assert _moved(metrics, before) == {"hit": 2, "miss": 1, "stale": 0}
    assert second.to_json()["cost"]["preparedHit"] == third.to_json()["cost"]["preparedHit"] == 1
    assert _payload(first) == _payload(second) == _payload(third)
    opened = {p: t.count - counted[p] for p, t in spans.items()}
    cost = second.to_json()["cost"]
    if cost.get("segmentsPostings"):  # a host tier answered: its verdict was kept, ``staging`` is relabelled
        assert opened == {"staging": 0, "planBuild": 0, "kernelPrep": 0, "indexPath": 2, "bitslicedPath": 0}
    elif cost.get("segmentsBitsliced"):
        assert opened == {"staging": 0, "planBuild": 0, "kernelPrep": 0, "indexPath": 0, "bitslicedPath": 2}
    else:  # the device path: the three spans the benchmark's readers count still open once a query
        assert opened == {"staging": 2, "planBuild": 2, "kernelPrep": 2, "indexPath": 0, "bitslicedPath": 0}


def test_explain_analyze_shows_the_hit(served):
    served.handle_pql(SHAPES["q1"])
    body = served.handle_pql("EXPLAIN ANALYZE " + SHAPES["q1"]).to_json()
    assert not body["exceptions"]
    assert body["cost"]["preparedHit"] == 1
    nodes = json.dumps(body)
    assert '"actualCost"' in nodes and '"preparedHit": 1' in nodes


def test_the_bounds_hold_under_a_thousand_distinct_literals(monkeypatch):
    """Each literal is a key of its own: entries stop at their bound, and
    with the byte bound brought down to a few entries' worth, bytes do."""
    segs = _segments(2, rows=600, seed=77, prefix="lit")
    for host_tier in ("PINOT_TPU_INVINDEX", "PINOT_TPU_BITSLICED"):  # every literal to the scan, which holds query inputs
        monkeypatch.setenv(host_tier, "0")
    ex = QueryExecutor()

    def ask(i: int) -> None:
        ex.execute(segs, parse_pql(f"SELECT count(*) FROM lineitem WHERE l_quantity > {i % 50} AND l_discount < 0.{i:04d}"))

    for i in range(1000):
        ask(i)
        assert len(ex._prepared) <= executor_mod._PREPARED_ENTRIES
    assert len(ex._prepared) == executor_mod._PREPARED_ENTRIES
    assert ex.metrics.gauge("plan.prepared.entries").value == executor_mod._PREPARED_ENTRIES
    assert _marks(ex.metrics) == {"hit": 0, "miss": 1000, "stale": 0}
    assert ex._prepared_bytes == sum(p.nbytes for p in ex._prepared.values()) > 0
    one = max(p.nbytes for p in ex._prepared.values())
    monkeypatch.setattr(executor_mod, "_PREPARED_BYTES", 8 * one)
    for i in range(1000, 1040):
        ask(i)
        assert ex._prepared_bytes <= 8 * one
    assert 0 < len(ex._prepared) <= 8
    assert ex._prepared_bytes == sum(p.nbytes for p in ex._prepared.values())
    # an entry over a quarter of the bound alone is not kept
    monkeypatch.setattr(executor_mod, "_PREPARED_BYTES", 2 * one)
    ask(2000)
    ask(2000)
    assert _marks(ex.metrics) == {"hit": 0, "miss": 1042, "stale": 0}
    assert ex._prepared_bytes == sum(p.nbytes for p in ex._prepared.values()) <= 2 * one


def test_a_segment_loaded_anew_under_its_name_misses_and_answers_from_the_new_rows():
    from pinot_tpu.tools.cluster_harness import single_server_broker

    old = _segments(2, seed=500, prefix="re")
    broker = single_server_broker("lineitem", old)
    server = broker.local_servers[0]
    try:
        count = lambda: broker.handle_pql("SELECT count(*) FROM lineitem").to_json()
        assert count()["aggregationResults"][0]["value"] == "5000"
        assert count()["cost"]["preparedHit"] == 1
        before = _marks(server.metrics)
        anew = synthetic_lineitem_segment(1000, seed=900, name=old[0].segment_name)
        assert anew.staging_token != old[0].staging_token
        server.remove_segment("lineitem", old[0].segment_name)
        server.add_segment("lineitem", anew)
        reply = count()
        assert reply["aggregationResults"][0]["value"] == "3500" and "preparedHit" not in reply["cost"]
        assert _moved(server.metrics, before) == {"hit": 0, "miss": 1, "stale": 0}
    finally:
        server.shutdown()


def test_a_consuming_segment_never_answers_with_an_older_count():
    from pinot_tpu.realtime.mutable import MutableSegment
    from pinot_tpu.tools.cluster_harness import single_server_broker
    from pinot_tpu.tools.datagen import lineitem_schema

    rows = synthetic_lineitem_segment(400, seed=11, name="rows")
    values = {c: [rows.column(c).dictionary.get(int(i)) for i in rows.column(c).fwd] for c in rows.columns}
    consuming = MutableSegment(lineitem_schema(), "lineitem__0__0", "lineitem")
    broker = single_server_broker("lineitem", [consuming])
    server = broker.local_servers[0]
    try:
        ingested = 0
        for step in (50, 1, 120, 7):
            for i in range(ingested, ingested + step):
                consuming.index({c: values[c][i] for c in values})
            ingested += step
            before = _marks(server.metrics)
            for again in range(3):  # between two ingests the same snapshot serves: a miss, then hits
                body = broker.handle_pql("SELECT count(*), sum(l_quantity) FROM lineitem").to_json()
                assert not body["exceptions"]
                assert body["aggregationResults"][0]["value"] == str(ingested)
                assert float(body["aggregationResults"][1]["value"]) == pytest.approx(sum(values["l_quantity"][:ingested]))
                assert body["cost"].get("preparedHit", 0) == (1 if again else 0)
            assert _moved(server.metrics, before) == {"hit": 2, "miss": 1, "stale": 0}
    finally:
        server.shutdown()


@pytest.mark.parametrize("setting,pql,tier_with,tier_without", [
    # a needle the host's postings answer, until the tier is switched off
    ("PINOT_TPU_INVINDEX", "needle", "segmentsPostings", "segmentsFullScan"),
    # a clustered date range the zone maps narrow, until they are switched off
    ("PINOT_TPU_ZONEMAP", SHAPES["q5"], "segmentsZonemap", "segmentsFullScan"),
])
def test_a_setting_flipped_between_two_identical_queries_is_followed(monkeypatch, setting, pql, tier_with, tier_without):
    segs = _segments(2, rows=70_000, seed=41, prefix="flip")
    if pql == "needle":
        pql = f"SELECT sum(l_quantity), count(*) FROM lineitem WHERE l_extendedprice = {_a_price_of_every_segment(segs)!r}"
    monkeypatch.delenv(setting, raising=False)
    monkeypatch.setenv("PINOT_TPU_BITSLICED", "0")  # between postings and the scan stands a third tier: not this test's
    ex = QueryExecutor()
    ask = lambda: ex.execute(segs, parse_pql(pql))
    first, again = ask(), ask()
    assert first.cost.get(tier_with) == 2 and again.cost.get(tier_with) == 2 and again.cost["preparedHit"] == 1
    monkeypatch.setenv(setting, "0")
    off = ask()
    assert off.cost.get(tier_without) == 2 and not off.cost.get(tier_with) and "preparedHit" not in off.cost
    assert off.num_docs_scanned == first.num_docs_scanned
    assert [p.finalize() for p in off.aggregations or []] == pytest.approx([p.finalize() for p in first.aggregations or []])
    monkeypatch.delenv(setting)
    back = ask()  # and back: the first entry is still there
    assert back.cost.get(tier_with) == 2 and back.cost["preparedHit"] == 1
    assert _marks(ex.metrics) == {"hit": 2, "miss": 2, "stale": 0}


def test_a_quarantine_or_a_poison_mark_set_between_two_identical_queries_sends_the_second_to_the_host(segments):
    from pinot_tpu.engine.plandigest import plan_shape_digest

    ex = QueryExecutor()
    ask = lambda pql: ex.execute(segments, parse_pql(pql))
    for pql, mark in (
        (SHAPES["q1"], lambda first: ex.audit_quarantine(plan_shape_digest(parse_pql(SHAPES["q1"])), "device", "a test's")),
        (SHAPES["k6"], lambda first: ex._poison((first._device_digest, tuple(s.segment_name for s in segments)), "a test's")),
    ):
        first = ask(pql)
        assert first.cost["segmentsFullScan"] == len(segments) and not first.cost.get("segmentsHost")
        mark(first)
        second = ask(pql)  # the entry is found, and the mark is still looked at
        assert second.cost["segmentsHost"] == len(segments) and not second.cost.get("segmentsFullScan")
        assert second.cost["preparedHit"] == 1
        assert second.num_docs_scanned == first.num_docs_scanned
        ex.clear_poisoned()
        third = ask(pql)
        assert third.cost["segmentsFullScan"] == len(segments) and third.cost["preparedHit"] == 1
    assert ex.healing_stats()["poisonSkips"] == 1
    assert _marks(ex.metrics) == {"hit": 4, "miss": 2, "stale": 0}


def test_a_demotion_between_two_identical_queries_stages_anew_and_marks_stale():
    from pinot_tpu.engine.device import clear_staging_cache
    from pinot_tpu.engine.residency import RESIDENCY

    clear_staging_cache()
    segs = _segments(2, seed=640, prefix="dem")
    ex = QueryExecutor()
    try:
        ask = lambda: ex.execute(segs, parse_pql(SHAPES["q6"]))
        first, again = ask(), ask()
        assert again.cost["preparedHit"] == 1
        kept = next(iter(ex._prepared.values())).device
        assert RESIDENCY.demote_for_pressure() > 0  # nothing is pinned between two queries
        after = ask()
        assert _marks(ex.metrics) == {"hit": 1, "miss": 1, "stale": 1}
        assert "preparedHit" not in after.cost and after.cost["segmentsFullScan"] == 2
        anew = next(iter(ex._prepared.values())).device
        assert anew is not kept and anew.token != kept.token
        assert ask().cost["preparedHit"] == 1
        for reply in (again, after):
            assert reply.num_docs_scanned == first.num_docs_scanned
            assert {k: [p.finalize() for p in v] for k, v in reply.groups.items()} == \
                   {k: [p.finalize() for p in v] for k, v in first.groups.items()}
    finally:
        clear_staging_cache()


def test_an_entry_keeps_neither_a_device_array_nor_a_segment_alive():
    """A demoted table's HBM is freed by dropping its arrays, an unloaded
    segment's memory by dropping the segment: an entry that held either
    would keep it, up to the memo's 256 entries."""
    import gc
    import weakref

    from pinot_tpu.engine import context
    from pinot_tpu.engine.device import clear_staging_cache

    segs = _segments(2, rows=70_000, seed=41, prefix="held")
    needle = f"SELECT sum(l_quantity), count(*) FROM lineitem WHERE l_extendedprice = {_a_price_of_every_segment(segs)!r}"
    ex = QueryExecutor()
    for pql in list(SHAPES.values()) + [needle]:
        ex.execute(segs, parse_pql(pql))
    found = ex.execute(segs, parse_pql(needle))
    assert found.cost["segmentsPostings"] == 2 and found.cost["preparedHit"] == 1  # the hand-off was kept
    from pinot_tpu.segment.invindex import release_postings

    release_postings(segs[0])  # as a segment's unload does: the kept hand-off's postings are gone, and decided again
    gc.collect()
    again = ex.execute(segs, parse_pql(needle))
    assert again.cost["segmentsPostings"] == 2 and again.num_docs_scanned == found.num_docs_scanned
    assert [p.finalize() for p in again.aggregations] == [p.finalize() for p in found.aggregations]
    held = [leaf for prep in ex._prepared.values()
            for part in (prep, prep.device) if part is not None
            for leaf in jax.tree_util.tree_leaves(list(part.values.values()))]
    assert held and not [type(x) for x in held if isinstance(x, jax.Array)]
    gone = [weakref.ref(s) for s in segs]
    del segs
    clear_staging_cache()
    context._context_cache.clear()
    gc.collect()
    assert [ref() for ref in gone] == [None, None]
    assert len(ex._prepared) == len(SHAPES) + 1


def test_queries_of_a_few_keys_from_many_threads_count_every_query_and_every_byte(segments, monkeypatch):
    """More threads than cores over one executor and a memo of four
    entries, so that finding, beginning, counting and turning out race:
    every query marks one outcome, every answer is the serial one, and
    the bytes counted are the bytes held."""
    monkeypatch.setattr(executor_mod, "_PREPARED_ENTRIES", 4)
    ex = QueryExecutor()
    texts = [SHAPES["q1"], SHAPES["q6"], SHAPES["k6"], SHAPES["q0"]] + [
        f"SELECT count(*) FROM lineitem WHERE l_quantity > {i}" for i in range(6)]
    serial = {pql: QueryExecutor().execute(segments, parse_pql(pql)).num_docs_scanned for pql in texts}
    threads, rounds, wrong = (os.cpu_count() or 4) + 4, 12, []

    def worker(k: int) -> None:
        for r in range(rounds):
            pql = texts[(k + r) % len(texts)]
            got = ex.execute(segments, parse_pql(pql)).num_docs_scanned
            if got != serial[pql]:
                wrong.append((pql, got))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=worker, args=(k,)) for k in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=240)
        assert not [t for t in pool if t.is_alive()]
    finally:
        sys.setswitchinterval(interval)
    assert not wrong
    assert sum(_marks(ex.metrics).values()) == threads * rounds
    assert len(ex._prepared) <= 4
    assert ex._prepared_bytes == sum(p.nbytes for p in ex._prepared.values())
