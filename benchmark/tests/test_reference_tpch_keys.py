"""The plain reference of ``tpch_lineitem_keys_1chip`` (PR 37): against a
brute-force Python loop, what ``compare`` catches (the top supplier
swapped for the second where the broker produces the answer among it),
its bfloat16 control failing by ``sum_gap`` alone, and the cell's files
as ISSUE 37 names them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run  # noqa: E402

ref_mod = run.load_module(os.path.join(BENCH, "reference_tpch_keys.py"))
CONFIG = json.load(open(os.path.join(BENCH, "configs", "tpch_lineitem_keys_1chip.json")))
TRAFFIC = json.load(open(os.path.join(BENCH, "traffic", "tpch_q15_closed.json")))
SHAPES = {s["name"]: s for s in TRAFFIC["shapes"]}
SUM_RTOL = CONFIG["guarantees"]["sum_rtol"]
SUPPLIERS = 500  # 4,000 rows a quarter over 500 keys: 8 a key, a few keys empty


def tiny_segments(seed: int, rows: int = 50_000, n: int = 2, suppliers: int = SUPPLIERS):
    from pinot_tpu.tools.datagen import synthetic_lineitem_keys_segment

    return [synthetic_lineitem_keys_segment(rows, seed=seed * 1000 + i, name=f"seg{i}", suppliers=suppliers)
            for i in range(n)]


def referee(segments, shapes=SHAPES, control=""):
    ref = ref_mod.Reference(shapes, control=control)
    for seg in segments:
        ref.add(seg)
    return ref


def brute_force(segments, shape: dict) -> dict:
    """Row at a time, python floats: {supplier: [revenue, rows]}."""
    (lo_col, lo_op, lo), (hi_col, hi_op, hi) = shape["filter"]
    assert (lo_col, lo_op, hi_col, hi_op) == ("l_shipdate", ">=", "l_shipdate", "<")
    out: dict = {}
    for seg in segments:
        cols = {c: np.asarray(seg.column(c).dictionary.values)[seg.column(c).fwd]
                for c in ("l_shipdate", "l_suppkey", "l_extendedprice", "l_discount")}
        for day, key, price, discount in zip(*cols.values()):
            if lo <= day < hi:
                acc = out.setdefault(int(key), [0.0, 0])
                acc[0] += float(price) * (1.0 - float(discount))
                acc[1] += 1
    return out


def test_reference_against_a_brute_force_loop():
    segments = tiny_segments(37, rows=20_000)
    ref = referee(segments)
    for name, shape in SHAPES.items():
        want, answer = brute_force(segments, shape), ref.answers[name]
        live = np.nonzero(answer["counts"])[0]
        assert [int(k) for k in answer["keys"][live]] == sorted(want)
        assert [int(c) for c in answer["counts"][live]] == [want[k][1] for k in sorted(want)]
        np.testing.assert_allclose(answer["sums"][0][live], [want[k][0] for k in sorted(want)], rtol=1e-12)
        assert answer["matched"] == answer["sorted_matched"] == sum(c for _, c in want.values())
        assert ref_mod.live_groups(answer) == len(want) and len(answer["keys"]) == SUPPLIERS
    assert ref.rows == 40_000 and ref.sorted_columns == {"l_shipdate"}


def test_segments_whose_dictionaries_differ_merge_by_key_value():
    """A segment that lacks some suppliers and one that brings new ones:
    the dense arrays grow and earlier sums keep their keys."""
    a, b = tiny_segments(5, rows=20_000, suppliers=300)[0], tiny_segments(6, rows=20_000, suppliers=500)[0]
    for order in ((a, b), (b, a)):
        ref = referee(order)
        for name, shape in SHAPES.items():
            want, answer = brute_force(order, shape), ref.answers[name]
            live = np.nonzero(answer["counts"])[0]
            assert [int(k) for k in answer["keys"][live]] == sorted(want)
            np.testing.assert_allclose(answer["sums"][0][live], [want[k][0] for k in sorted(want)], rtol=1e-12)


def honest_reply(shape: dict, ref, name: str, skip: int = 0) -> dict:
    """The reply a sound program gives: TOP n by the reference's own
    values (``skip`` groups from the top left out first)."""
    answer = ref.answers[name]
    live = np.nonzero(answer["counts"])[0]
    results = []
    for want in ref_mod.wanted(shape, answer):
        top = live[np.argsort(-want[live], kind="stable")][skip : skip + shape["top"]]
        results.append({"groupByResult": [{"group": [str(answer["keys"][i])], "value": f"{want[i]:.5f}"} for i in top]})
    return {"aggregationResults": results, "exceptions": [], "numDocsScanned": answer["matched"],
            "totalDocs": ref.rows, "numServersQueried": 1, "numServersResponded": 1,
            "cost": {"segmentsZonemap": 2, "numGroupsLive": ref_mod.live_groups(answer), "numGroupsKept": 100,
                     "groupStateSumSq": ref_mod.state_sum_sq(shape, answer)}}


def test_compare_catches_each_kind_of_fault():
    ref = referee(tiny_segments(3))
    clean = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
    for name, shape in SHAPES.items():
        got = ref_mod.compare(honest_reply(shape, ref, name), shape, ref.answers[name], ref.rows)
        assert dict(got, sum_gap=0.0) == clean and got["sum_gap"] < 1e-9, (name, got)  # five decimals of 1e5
    name = "q15_1996q1"
    shape, answer = SHAPES[name], ref.answers[name]
    judged = lambda reply: ref_mod.compare(reply, shape, answer, ref.rows)
    # the top supplier swapped for the second, with the second's own right revenue: only the TOP-n check sees it
    second = judged(honest_reply(shape, ref, name, skip=1))
    revenue = np.sort(answer["sums"][0])[::-1]
    assert second["sum_gap"] == pytest.approx((revenue[0] - revenue[1]) / revenue[0]) and second["sum_gap"] > SUM_RTOL
    assert second["key_errors"] == second["count_errors"] == 0
    reply = honest_reply(shape, ref, name)
    top = reply["aggregationResults"][0]["groupByResult"][0]
    top["value"] = repr(float(top["value"]) * (1 + 3 * SUM_RTOL))
    assert judged(reply)["sum_gap"] == pytest.approx(3 * SUM_RTOL, rel=1e-3)
    for wrong_key in ("0", str(SUPPLIERS + 1), "x"):  # no such supplier; not a supplier key at all
        reply = honest_reply(shape, ref, name)
        reply["aggregationResults"][0]["groupByResult"][0]["group"] = [wrong_key]
        assert judged(reply)["key_errors"] == 1, wrong_key
    reply = honest_reply(shape, ref, name)
    reply["aggregationResults"][0]["groupByResult"] = []
    assert judged(reply)["key_errors"] == 1  # a group is missing
    reply = honest_reply(shape, ref, name)
    reply["aggregationResults"][0]["groupByResult"] *= 2
    assert judged(reply)["key_errors"] == 1  # more groups than TOP 1
    assert judged(dict(honest_reply(shape, ref, name), numDocsScanned=answer["matched"] - 1))["count_errors"] == 1
    assert judged(dict(honest_reply(shape, ref, name), totalDocs=ref.rows + 1))["count_errors"] == 1
    live = ref_mod.live_groups(answer)
    digest = ref_mod.state_sum_sq(shape, answer)
    for cost in ({"numGroupsLive": live - 1}, {"numGroupsLive": live + 1}, {}):  # a group lost, invented, not counted
        assert judged(dict(honest_reply(shape, ref, name), cost=dict(cost, groupStateSumSq=digest)))["count_errors"] == 1, cost
    got = judged(dict(honest_reply(shape, ref, name), cost={"numGroupsLive": live}))  # no digest of the state
    assert got["sum_gap"] == 1.0 and got["count_errors"] == 0
    # two answering servers: the broker adds each server's own, so the count is bounded and the squares are not held
    two = dict(honest_reply(shape, ref, name), numServersQueried=2, numServersResponded=2)
    for have, errors in ((live, 0), (2 * live, 0), (live - 1, 1), (2 * live + 1, 1)):
        got = judged(dict(two, cost={"numGroupsLive": have, "groupStateSumSq": digest / 2}))
        assert (got["count_errors"], got["sum_gap"] < 1e-9) == (errors, True), (have, got)
    for fault in ({"exceptions": [{"message": "x"}]}, {"partialResponse": True}, {"numServersResponded": 0},
                  {"cost": {"segmentsHost": 2, "numGroupsLive": live}}, {"aggregationResults": []}):
        assert judged(dict(honest_reply(shape, ref, name), **fault))["reply_errors"] == 1, fault


@pytest.mark.parametrize("fault", ["dropped", "misplaced"])
def test_updates_lost_outside_the_top_group_are_caught_by_the_states_digest(fault):
    """What a TOP 1 reply cannot show: a scatter that drops one update in
    fifty, or adds it to the next key, in groups that are not the
    maximum.  The top supplier, its revenue, ``numDocsScanned`` and the
    live-group count are all right; ``groupStateSumSq`` is not."""
    segments = tiny_segments(9)
    ref = referee(segments)
    name = "q15_1996q1"
    shape, answer = SHAPES[name], ref.answers[name]
    top = int(np.argmax(answer["sums"][0]))
    rng = np.random.default_rng(9)
    state = answer["sums"][0].copy()
    moved = 0
    for seg in segments:  # the faulted state, row by row of the rows the filter passes
        cols = {c: np.asarray(seg.column(c).dictionary.values)[seg.column(c).fwd]
                for c in ("l_shipdate", "l_suppkey", "l_extendedprice", "l_discount")}
        lo, hi = shape["filter"][0][2], shape["filter"][1][2]
        rows = np.nonzero((cols["l_shipdate"] >= lo) & (cols["l_shipdate"] < hi))[0]
        at = np.searchsorted(answer["keys"], cols["l_suppkey"][rows])
        hit = rows[(rng.random(rows.size) < 0.02) & (at != top) & ((at + 1) % answer["keys"].size != top)]
        at, value = np.searchsorted(answer["keys"], cols["l_suppkey"][hit]), \
            cols["l_extendedprice"][hit].astype(np.float64) * (1.0 - cols["l_discount"][hit].astype(np.float64))
        np.subtract.at(state, at, value)
        if fault == "misplaced":
            np.add.at(state, (at + 1) % state.size, value)
        moved += hit.size
    assert moved > 50 and state[top] == answer["sums"][0][top] and int(np.argmax(state)) == top
    reply = honest_reply(shape, ref, name)
    reply["cost"]["groupStateSumSq"] = float(np.dot(state[answer["counts"] > 0], state[answer["counts"] > 0]))
    got = ref_mod.compare(reply, shape, answer, ref.rows)
    assert got["sum_gap"] > 3 * SUM_RTOL and got["count_errors"] == got["key_errors"] == got["reply_errors"] == 0, got


def test_an_empty_window_and_a_top_larger_than_the_groups():
    shapes = {
        "none": dict(SHAPES["q15_1996q1"], filter=[["l_shipdate", ">=", "1999-01-01"], ["l_shipdate", "<", "1999-04-01"]]),
        "all": dict(SHAPES["q15_1996q1"], top=10 * SUPPLIERS),
        "counted": dict(SHAPES["q15_1996q1"], top=3, aggs=[["count", "*"], ["avg", "l_discount"]]),
    }
    ref = referee(tiny_segments(4), shapes)
    for name, shape in shapes.items():
        got = ref_mod.compare(honest_reply(shape, ref, name), shape, ref.answers[name], ref.rows)
        assert got["count_errors"] == got["key_errors"] == got["reply_errors"] == 0 and got["sum_gap"] < 1e-5, (name, got)
    assert ref.answers["none"]["matched"] == 0 and ref_mod.live_groups(ref.answers["none"]) == 0
    assert honest_reply(shapes["none"], ref, "none")["aggregationResults"] == [{"groupByResult": []}]
    everything = honest_reply(shapes["all"], ref, "all")["aggregationResults"][0]["groupByResult"]
    assert len(everything) == ref_mod.live_groups(ref.answers["all"]) <= SUPPLIERS
    # a count's TOP n: a group returned in place of a fuller one is a key error, not a gap
    reply = honest_reply(shapes["counted"], ref, "counted", skip=1)
    got = ref_mod.compare(reply, shapes["counted"], ref.answers["counted"], ref.rows)
    counts = np.sort(ref.answers["counted"]["counts"])[::-1]
    assert got["key_errors"] == int(counts[0] > counts[3])
    with pytest.raises(ValueError, match="one column"):
        ref_mod.Reference({"ungrouped": {"aggs": [["sum", "l_quantity"]]}})
    with pytest.raises(ValueError, match="no aggregate"):
        ref_mod.Reference({"m": dict(SHAPES["q15_1996q1"], aggs=[["max", "l_quantity"]])})


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_fails_by_sum_gap_alone(seed):
    """The control at a size a test can hold (the chip's readings at the
    cell's own size are in the configuration's file and PERF.md): the
    reference computed in bfloat16, answered as a reply, shows a gap above
    ``sum_rtol`` and no other fault; the float64 reference against itself
    shows none."""
    segments = tiny_segments(seed)
    ref, control = referee(segments), referee(segments, control="bfloat16")
    gaps = ref_mod.control_gaps(ref, control)
    assert min(gaps.values()) > 3 * SUM_RTOL, gaps
    for name, shape in SHAPES.items():  # the control's own reply: counts, keys and the live-group count hold
        theirs = honest_reply(shape, control, name)
        got = ref_mod.compare(theirs, shape, ref.answers[name], ref.rows)
        assert got["count_errors"] == got["key_errors"] == got["reply_errors"] == 0, (name, got)
        assert got["sum_gap"] == pytest.approx(gaps[name], abs=1e-9) and got["sum_gap"] > SUM_RTOL
    assert max(ref_mod.control_gaps(ref, ref).values()) == 0.0
    with pytest.raises(ValueError):
        ref_mod.Reference(SHAPES, control="float16")


def test_shape_bytes_is_the_quarters_rows_times_seven_bytes():
    """The rows the sorted filter passes x the narrowest ids: l_suppkey 4 B
    at the cell's 220,000 keys (2 B at this test's 500), l_extendedprice
    2 B, l_discount 1 B; l_shipdate is searched, not read."""
    ref = referee(tiny_segments(1))
    answer = ref.answers["q15_1996q1"]
    assert ref.shape_bytes("q15_1996q1") == answer["sorted_matched"] * (2 + 2 + 1)
    assert 0.03 * ref.rows < answer["sorted_matched"] < 0.055 * ref.rows  # 84 of 2,000 dates
    wide = referee(tiny_segments(1, rows=2_000, n=1, suppliers=70_000))
    assert wide.shape_bytes("q15_1996q1") == wide.answers["q15_1996q1"]["sorted_matched"] * (4 + 2 + 1)


def test_the_cell_is_as_issue_37_names_it():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (cell,) = [w for w in manifest["workloads"] if w["name"] == "lineitem_topsupplier_closed"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tpch_lineitem_keys_1chip", "tpch_q15_closed", 1)
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"] and "clause 2.4.15" in entry["source"]
    assert entry["file"] == "benchmark/configs/tpch_lineitem_keys_1chip.json"
    assert (CONFIG["segments"], CONFIG["rows_per_segment"], CONFIG["chips"], CONFIG["reduced"]) == (16, 8_388_608, 1, [])
    assert CONFIG["schema"] == "pinot_tpu.tools.datagen:lineitem_keys_schema"
    assert CONFIG["generator"] == "pinot_tpu.tools.datagen:synthetic_lineitem_keys_segment"
    assert CONFIG["env"] == {"PINOT_TPU_AUDIT_SAMPLE_N": "0"}
    spec = json.load(open(os.path.join(BENCH, "configs", "tpch_lineitem_spec_1chip.json")))["guarantees"]
    for key in ("replication", "crc_verified_at_load", "result_cache", "counts_and_numDocsScanned", "segmentsHost",
                "partialResponse"):
        assert CONFIG["guarantees"][key] == spec[key], key
    # set from the chip's two readings, both in the file: 1e-4 was over a tenth of the control's smallest
    assert CONFIG["guarantees"]["sum_rtol"] == 1e-5 and "1.15e-7" in CONFIG["guarantees"]["sum_rtol_why"] and "2.95e-4" in CONFIG["guarantees"]["sum_rtol_why"]
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["keep_awake"], TRAFFIC["schedule_seed"], TRAFFIC["rehearse_s"],
            TRAFFIC["reference"]) == ("closed", 1, 1, 37, 1.0, "reference_tpch_keys")
    assert [(s["name"], s["share"], s["top"]) for s in TRAFFIC["shapes"]] == [
        ("q15_1996q1", 1, 1), ("q15_1993q2", 1, 1), ("q15_1994q4", 1, 1), ("q15_1997q3", 1, 1)]
    assert ref_mod.render_pql(CONFIG["table"], SHAPES["q15_1996q1"]) == (
        "SELECT sum(l_extendedprice*(1-l_discount)) FROM lineitem WHERE l_shipdate >= '1996-01-01' AND "
        "l_shipdate < '1996-04-01' GROUP BY l_suppkey TOP 1")
    for s, (lo, hi) in zip(TRAFFIC["shapes"], (("1996-01-01", "1996-04-01"), ("1993-04-01", "1993-07-01"),
                                               ("1994-10-01", "1995-01-01"), ("1997-07-01", "1997-10-01"))):
        assert s["filter"] == [["l_shipdate", ">=", lo], ["l_shipdate", "<", hi]] and s["group_by"] == ["l_suppkey"]
        assert s["aggs"] == [["sum", {"expr": "l_extendedprice*(1-l_discount)"}]]
    reported = {m["name"] for m in manifest["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])}
    assert reported == {"latency_p50_ms", "throughput_qps", "hbm_bytes_per_row", "setup_s"}
    make, schema = run.resolve(CONFIG["generator"]), run.resolve(CONFIG["schema"])()
    seg = make(1000, seed=2**31 + 37, name="s")
    assert seg.column("l_suppkey").dictionary.cardinality == 220_000 and schema.has_column("l_suppkey")


# -- a whole run of the cell, at a tiny size, without the chip -------------
RUN_SEED, RUN_SEGMENTS, RUN_ROWS = 2**31 + 37, 2, 20_000


@pytest.fixture(scope="module")
def cut_manifest(tmp_path_factory) -> str:
    """The real manifest, the cell's configuration with its two sizes cut."""
    out = tmp_path_factory.mktemp("keys")
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG["name"]]
    (out / "config.json").write_text(json.dumps(dict(CONFIG, segments=RUN_SEGMENTS, rows_per_segment=RUN_ROWS)))
    entry["file"] = str(out / "config.json")
    (out / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(out / "BENCHMARK.json")


@pytest.fixture(scope="module")
def run_reference():
    """The reference over the segments ``run.py`` makes from ``RUN_SEED``."""
    make = run.resolve(CONFIG["generator"])
    return referee([make(RUN_ROWS, seed=RUN_SEED * 1000 + i, name=f"seg{i}") for i in range(RUN_SEGMENTS)])


def run_cell(capsys, manifest: str, trace: int = 0) -> dict:
    import gc

    try:
        assert run.main(["--workload", "lineitem_topsupplier_closed", "--seed", str(RUN_SEED), "--seconds", "1",
                         "--trace", str(trace)], allow_cpu=True, manifest_path=manifest) == 0
    finally:
        gc.unfreeze()
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_run_of_the_cell_is_correct_and_counts_its_groups(capsys, cut_manifest, run_reference):
    out = run_cell(capsys, cut_manifest, trace=1)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 4
    ref = run_reference
    live = [ref_mod.live_groups(ref.answers[name]) for name in SHAPES]
    assert out["metrics"]["groups_live_mean"]["value"] == pytest.approx(sum(live) / len(live))
    assert out["metrics"]["groups_kept_mean"]["value"] == 100  # max(5 x TOP, 100); no tie at the boundary
    assert set(out["metrics"]) == {"compiles_in_window", "groups_live_mean", "groups_kept_mean"}  # counts; no time or share


def test_the_top_supplier_swapped_for_the_second_comes_out_not_correct(capsys, monkeypatch, cut_manifest, run_reference):
    """The rest of a run with the timed path broken underneath: an answer
    altered where the broker produces it, each shape's top supplier
    replaced by its second with the second's own right revenue."""
    from pinot_tpu.common.response import BrokerResponse

    ref = run_reference
    second_of = {}
    for name in SHAPES:
        answer = ref.answers[name]
        first, second = np.argsort(-answer["sums"][0], kind="stable")[:2]
        second_of[str(answer["keys"][first])] = (str(answer["keys"][second]), f"{answer['sums'][0][second]:.5f}")
    sound = BrokerResponse.to_json

    def broken(self):
        out = sound(self)
        for res in out["aggregationResults"]:
            for g in res.get("groupByResult", []):
                g["group"], g["value"] = [second_of[g["group"][0]][0]], second_of[g["group"][0]][1]
        return out

    monkeypatch.setattr(BrokerResponse, "to_json", broken)
    out = run_cell(capsys, cut_manifest)
    assert out["correct"] is False and out["failed"] == out["attempted"] > 0
    compared = out["compared"]
    assert compared["sum_gap"]["value"] > SUM_RTOL  # the TOP-n check alone: keys, counts and replies hold
    assert compared["count_errors"]["value"] == compared["key_errors"]["value"] == compared["reply_errors"]["value"] == 0


def test_a_state_that_lost_updates_under_a_right_top_supplier_comes_out_not_correct(capsys, monkeypatch, cut_manifest):
    """The timed path broken where no TOP 1 reply shows it: every seventh
    group of the fetched state but the maximum has lost a hundredth of its
    sum before the finalize.  The top supplier and its revenue are right in
    every reply; the state's digest on the cost vector is not."""
    from pinot_tpu.engine.executor import QueryExecutor

    sound = QueryExecutor._kept_group_keys

    def lossy(self, plan, ctx, outs):
        state = np.array(outs["gb_0"])
        low = np.nonzero(state)[0]
        low = low[low != int(np.argmax(state))][::7]
        state[low] *= 0.99
        outs["gb_0"] = state
        return sound(self, plan, ctx, outs)

    monkeypatch.setattr(QueryExecutor, "_kept_group_keys", lossy)
    out = run_cell(capsys, cut_manifest)
    assert out["correct"] is False and out["failed"] == out["attempted"] > 0
    compared = out["compared"]
    assert compared["sum_gap"]["value"] > SUM_RTOL
    assert compared["count_errors"]["value"] == compared["key_errors"]["value"] == compared["reply_errors"]["value"] == 0
