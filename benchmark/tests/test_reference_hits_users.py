"""The plain reference of ``clickbench_hits_users_1chip`` (PR 41): against
a brute-force count of python sets through the same sketch written a
third time; what ``compare`` catches (a reply that lost one segment's
registers, an estimate off by one, a wrong region under a right estimate:
each ``correct: false``); its bfloat16 control failing by ``sum_gap``
alone; and the cell's files as ISSUE 41 names them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import math
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

ref_mod = run.load_module(os.path.join(BENCH, "reference_hits_users.py"))
CELL = "hits_distinct_users_closed"
CONFIG = json.load(open(os.path.join(BENCH, "configs", "clickbench_hits_users_1chip.json")))
TRAFFIC = json.load(open(os.path.join(BENCH, "traffic", "hits_users_closed.json")))
SHAPES = {s["name"]: s for s in TRAFFIC["shapes"]}
SUM_RTOL = CONFIG["guarantees"]["sum_rtol"]
CLEAN = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
MASK = (1 << 64) - 1


def tiny_segments(seed: int, rows: int = 30_000, n: int = 3, users: int = 40_000):
    from pinot_tpu.tools.datagen import synthetic_hits_users_segment

    return [synthetic_hits_users_segment(rows, seed=seed * 1000 + i, name=f"seg{i}", users=users) for i in range(n)]


def referee(segments, shapes=SHAPES, control=""):
    ref = ref_mod.Reference(shapes, control=control)
    for seg in segments:
        ref.add(seg)
    return ref


def sketch_of(values) -> int:
    """The configuration's sketch a value at a time, in python ints and
    ``math``: the hash, the register and rank, the estimate."""
    registers = [0] * 256
    for v in values:
        x = (v + 0x9E3779B97F4A7C15) & MASK
        x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & MASK
        x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & MASK
        x ^= x >> 31
        rest = x >> 8
        rank = 57 if rest == 0 else (rest & -rest).bit_length()
        registers[x & 255] = max(registers[x & 255], rank)
    raw = (0.7213 / (1 + 1.079 / 256)) * 256 * 256 / sum(2.0 ** -r for r in registers)
    empty = registers.count(0)
    if raw <= 2.5 * 256 and empty:
        return round(256 * math.log(256 / empty))
    return round(raw)


def test_reference_against_sets_through_the_sketch_written_again():
    segments = tiny_segments(41, rows=20_000)
    ref = referee(segments)
    everyone, by_region, rows_of, adv_of, width_of = set(), {}, {}, {}, {}
    for seg in segments:
        cols = {c: np.asarray(seg.column(c).dictionary.values)[seg.column(c).fwd] for c in seg.columns}
        for user, region, adv, width in zip(cols["UserID"], cols["RegionID"], cols["AdvEngineID"], cols["ResolutionWidth"]):
            everyone.add(int(user))
            by_region.setdefault(int(region), set()).add(int(user))
            rows_of[int(region)] = rows_of.get(int(region), 0) + 1
            adv_of[int(region)] = adv_of.get(int(region), 0) + int(adv)
            width_of[int(region)] = width_of.get(int(region), 0) + int(width)
    total = ref.answers["users_total"]
    assert int(ref_mod.wanted(SHAPES["users_total"], total)[0][0]) == sketch_of(everyone)
    assert abs(sketch_of(everyone) - len(everyone)) < 0.25 * len(everyone)  # 6.5% standard error at 256 registers
    for name in ("users_by_region", "region_summary"):
        answer = ref.answers[name]
        live = np.nonzero(answer["counts"])[0]
        assert [int(k) for k in answer["keys"][live]] == sorted(by_region) and len(answer["keys"]) == 9_040
        assert [int(c) for c in answer["counts"][live]] == [rows_of[k] for k in sorted(by_region)]
        users = ref_mod.wanted(SHAPES[name], answer)[-1]
        assert [int(u) for u in users[live]] == [sketch_of(by_region[k]) for k in sorted(by_region)]
        assert answer["matched"] == ref.rows == 60_000
    summary = ref_mod.wanted(SHAPES["region_summary"], ref.answers["region_summary"])
    live = np.nonzero(ref.answers["region_summary"]["counts"])[0]
    assert [float(v) for v in summary[0][live]] == [float(adv_of[k]) for k in sorted(by_region)]
    np.testing.assert_allclose(summary[2][live], [width_of[k] / rows_of[k] for k in sorted(by_region)], rtol=1e-12)
    # small groups take the linear-counting branch, large ones the raw estimate: both are in the table
    sizes = [len(v) for v in by_region.values()]
    assert min(sizes) < 10 and max(sizes) > 700


def honest_reply(shape: dict, ref, name: str, skip: int = 0) -> dict:
    """The reply a sound program gives: TOP n of each aggregate by the
    reference's own values (``skip`` groups from the top left out first),
    a distinct count as an integer, and the state's digest."""
    answer = ref.answers[name]
    live = np.nonzero(answer["counts"])[0]
    results = []
    for (fn, _), want in zip(shape["aggs"], ref_mod.wanted(shape, answer)):
        text = (lambda v: str(int(v))) if fn in ("count", ref_mod.HLL) else (lambda v: f"{v:.5f}")
        if shape.get("group_by"):
            top = live[np.argsort(-want[live], kind="stable")][skip : skip + shape["top"]]
            results.append({"groupByResult": [{"group": [str(answer["keys"][i])], "value": text(want[i])} for i in top]})
        else:
            results.append({"value": text(want[0])})
    cost = dict(ref_mod.state_digest(shape, answer), segmentsFullScan=3, numGroupsKept=100) if shape.get("group_by") else {}
    return {"aggregationResults": results, "exceptions": [], "numDocsScanned": answer["matched"], "totalDocs": ref.rows,
            "numServersQueried": 1, "numServersResponded": 1, "cost": cost}


def test_compare_catches_each_kind_of_fault():
    segments = tiny_segments(3)
    ref = referee(segments)
    for name, shape in SHAPES.items():
        got = ref_mod.compare(honest_reply(shape, ref, name), shape, ref.answers[name], ref.rows)
        assert dict(got, sum_gap=0.0) == CLEAN and got["sum_gap"] < 1e-8, (name, got)  # five decimals of an average
    judged = lambda reply, name: ref_mod.compare(reply, SHAPES[name], ref.answers[name], ref.rows)
    # (1) a reply that lost one segment's registers: counts, keys and sums right, the estimates and their sum not
    lost = referee(segments[1:])
    for name in SHAPES:
        reply = honest_reply(SHAPES[name], ref, name)
        theirs = honest_reply(SHAPES[name], lost, name)
        reply["aggregationResults"][-1] = theirs["aggregationResults"][-1]
        reply["cost"].update({k: v for k, v in theirs["cost"].items() if k.startswith("groupStateHll")})
        got = judged(reply, name)
        assert got["count_errors"] > 0 and got["reply_errors"] == 0, (name, got)
    # (2) an estimate off by one, in the total and in one region of ten
    reply = honest_reply(SHAPES["users_total"], ref, "users_total")
    reply["aggregationResults"][0]["value"] = str(int(reply["aggregationResults"][0]["value"]) + 1)
    assert judged(reply, "users_total") == dict(CLEAN, count_errors=1)
    reply = honest_reply(SHAPES["users_by_region"], ref, "users_by_region")
    seventh = reply["aggregationResults"][0]["groupByResult"][6]
    seventh["value"] = str(int(seventh["value"]) - 1)
    assert judged(reply, "users_by_region") == dict(CLEAN, count_errors=1)
    seventh["value"] = seventh["value"] + ".5"  # not an integer at all
    assert judged(reply, "users_by_region")["count_errors"] >= 1
    # ... and in a region the reply does not show: the state's sum is one off, its squares hardly
    reply = honest_reply(SHAPES["users_by_region"], ref, "users_by_region")
    reply["cost"]["groupStateHllSum"] += 1
    got = judged(reply, "users_by_region")
    assert got["count_errors"] == 1 and got["key_errors"] == 0 and got["sum_gap"] < SUM_RTOL
    # (3) a wrong region under a right estimate: the best region left out, the eleventh in its place with its own
    # right count: only the TOP-n check sees it
    answer = ref.answers["users_by_region"]
    users = np.sort(ref_mod.wanted(SHAPES["users_by_region"], answer)[0])[::-1]
    got = judged(honest_reply(SHAPES["users_by_region"], ref, "users_by_region", skip=1), "users_by_region")
    assert got == dict(CLEAN, key_errors=int(users[0] > users[10])) and users[0] > users[10]
    # a region's key swapped for another's under the first's estimate: the estimate is not that region's
    reply = honest_reply(SHAPES["users_by_region"], ref, "users_by_region")
    first, last = reply["aggregationResults"][0]["groupByResult"][0], reply["aggregationResults"][0]["groupByResult"][-1]
    stranger = next(str(k) for k, c in zip(answer["keys"], answer["counts"]) if c > 0 and str(k) not in
                    {g["group"][0] for g in reply["aggregationResults"][0]["groupByResult"]})
    first["group"] = [stranger]
    got = judged(reply, "users_by_region")
    assert got["count_errors"] >= 1 or got["key_errors"] >= 1
    for wrong_key in ("0", "9041", "x"):  # no such region; not a region key at all
        reply = honest_reply(SHAPES["users_by_region"], ref, "users_by_region")
        reply["aggregationResults"][0]["groupByResult"][0]["group"] = [wrong_key]
        assert judged(reply, "users_by_region")["key_errors"] == 1, wrong_key
    del last
    # the count's list of the summary: a fuller region left out is a key error; a sum off is a gap
    got = judged(honest_reply(SHAPES["region_summary"], ref, "region_summary", skip=1), "region_summary")
    assert got["key_errors"] >= 1 and got["count_errors"] == 0
    reply = honest_reply(SHAPES["region_summary"], ref, "region_summary")
    top = reply["aggregationResults"][2]["groupByResult"][0]
    top["value"] = repr(float(top["value"]) * (1 + 3 * SUM_RTOL))
    assert judged(reply, "region_summary")["sum_gap"] == pytest.approx(3 * SUM_RTOL, rel=1e-2)
    # the state's other numbers
    digest = ref_mod.state_digest(SHAPES["region_summary"], ref.answers["region_summary"])
    for cost in ({"numGroupsLive": digest["numGroupsLive"] - 1}, {"numGroupsLive": digest["numGroupsLive"] + 1}):
        reply = honest_reply(SHAPES["region_summary"], ref, "region_summary")
        reply["cost"].update(cost)
        assert judged(reply, "region_summary")["count_errors"] == 1, cost
    reply = honest_reply(SHAPES["region_summary"], ref, "region_summary")
    reply["cost"]["groupStateSumSq"] *= 1 + 3 * SUM_RTOL
    assert judged(reply, "region_summary")["sum_gap"] == pytest.approx(3 * SUM_RTOL, rel=1e-2)
    reply = honest_reply(SHAPES["region_summary"], ref, "region_summary")
    reply["cost"] = {"numGroupsLive": digest["numGroupsLive"]}  # no digest of the state at all
    got = judged(reply, "region_summary")
    assert got["sum_gap"] == 1.0 and got["count_errors"] == 1
    two = dict(honest_reply(SHAPES["users_by_region"], ref, "users_by_region"), numServersQueried=2, numServersResponded=2)
    live = digest["numGroupsLive"]
    for have, errors in ((live, 0), (2 * live, 0), (live - 1, 1), (2 * live + 1, 1)):
        assert judged(dict(two, cost={"numGroupsLive": have}), "users_by_region") == dict(CLEAN, count_errors=errors), have
    for fault in ({"exceptions": [{"message": "x"}]}, {"partialResponse": True}, {"numServersResponded": 0},
                  {"cost": {"segmentsHost": 2}}, {"aggregationResults": []}):
        for name in SHAPES:
            assert judged(dict(honest_reply(SHAPES[name], ref, name), **fault), name)["reply_errors"] == 1, (name, fault)
    assert judged(dict(honest_reply(SHAPES["users_total"], ref, "users_total"), numDocsScanned=ref.rows - 1),
                  "users_total")["count_errors"] == 1
    with pytest.raises(ValueError, match="unfiltered"):
        ref_mod.Reference({"f": dict(SHAPES["users_total"], filter=[["RegionID", "=", 1]])})
    with pytest.raises(ValueError, match="no aggregate"):
        ref_mod.Reference({"m": {"aggs": [["distinctcount", "UserID"]]}})


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_fails_by_sum_gap_alone(seed):
    """The control at a size a test can hold (the chip's readings at the
    cell's own size are in the configuration's file and PERF.md): the
    measures and their sums in bfloat16 show a gap above ``sum_rtol`` in
    the shape that has them, and no other fault; the two shapes that only
    count distinct users have no float to round, which is why their
    control is a lost segment and an estimate off by one (above)."""
    segments = tiny_segments(seed)
    ref, control = referee(segments), referee(segments, control="bfloat16")
    gaps = ref_mod.control_gaps(ref, control)
    assert gaps["region_summary"] > 3 * SUM_RTOL and gaps["users_total"] == gaps["users_by_region"] == 0.0, gaps
    theirs = honest_reply(SHAPES["region_summary"], control, "region_summary")
    got = ref_mod.compare(theirs, SHAPES["region_summary"], ref.answers["region_summary"], ref.rows)
    assert got["count_errors"] == got["key_errors"] == got["reply_errors"] == 0, got
    assert got["sum_gap"] == pytest.approx(gaps["region_summary"], abs=1e-9) and got["sum_gap"] > SUM_RTOL
    assert max(ref_mod.control_gaps(ref, ref).values()) == 0.0
    with pytest.raises(ValueError):
        ref_mod.Reference(SHAPES, control="float16")


def test_shape_bytes_are_the_streams_the_region_ids_and_the_measures():
    ref = referee(tiny_segments(1, n=1))
    rows = ref.rows
    assert ref.shape_bytes("users_total") == rows * 2  # the register index and the rank, a byte each
    assert ref.shape_bytes("users_by_region") == rows * (2 + 2)  # and the region's id: 9,040 values, 2 B
    assert ref.shape_bytes("region_summary") == rows * (2 + 2 + 1 + 1)  # and two measures of 19 and 12 values


def test_the_cell_is_as_issue_41_names_it():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("clickbench_hits_users_1chip", "hits_users_closed", 1)
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert "queries.sql lines 5, 9, 10" in entry["source"] and entry["file"] == "benchmark/configs/clickbench_hits_users_1chip.json"
    assert (CONFIG["segments"], CONFIG["rows_per_segment"], CONFIG["chips"], CONFIG["reduced"]) == (12, 8_388_608, 1, [])
    assert CONFIG["schema"] == "pinot_tpu.tools.datagen:hits_users_schema"
    assert CONFIG["generator"] == "pinot_tpu.tools.datagen:synthetic_hits_users_segment"
    assert CONFIG["env"] == {"PINOT_TPU_AUDIT_SAMPLE_N": "0"}
    keys = json.load(open(os.path.join(BENCH, "configs", "tpch_lineitem_keys_1chip.json")))["guarantees"]
    for key in ("replication", "crc_verified_at_load", "result_cache", "counts_and_numDocsScanned", "segmentsHost",
                "partialResponse"):
        assert CONFIG["guarantees"][key] == keys[key], key
    assert "splitmix64" in CONFIG["guarantees"]["distinct_count_hash"] and "AS AN INTEGER" in CONFIG["guarantees"]["distinct_count"]
    assumed = " ".join(CONFIG["assumed"])
    for word in ("17,630,976", "9,040", "630,500", "exponent 0.7", "19,250,000", "exponent 1", "99.37%", "exact"):
        assert word in assumed, word
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["keep_awake"], TRAFFIC["schedule_seed"], TRAFFIC["rehearse_s"],
            TRAFFIC["reference"]) == ("closed", 1, 1, 41, 1.0, "reference_hits_users")
    assert [(s["name"], s["share"]) for s in TRAFFIC["shapes"]] == [("users_total", 1), ("users_by_region", 1), ("region_summary", 1)]
    assert [ref_mod.render_pql(CONFIG["table"], s) for s in TRAFFIC["shapes"]] == [
        "SELECT distinctcounthll(UserID) FROM hits",
        "SELECT distinctcounthll(UserID) FROM hits GROUP BY RegionID TOP 10",
        "SELECT sum(AdvEngineID), count(*), avg(ResolutionWidth), distinctcounthll(UserID) FROM hits GROUP BY RegionID TOP 10"]
    reported = {m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reported == {"latency_p50_ms", "throughput_qps", "hbm_bytes_per_row", "setup_s"}
    for reader in ("hll_sort_share", "hll_estimate_ms_mean", "hll_derive_s", "group_trim_ms_mean", "groups_live_mean",
                   "groups_kept_mean", "groupby_contraction_share"):
        (m,) = [m for m in manifest["per_layer"] if m["name"] == reader]
        assert CELL in m["workloads"], reader
    make, schema = run.resolve(CONFIG["generator"]), run.resolve(CONFIG["schema"])()
    seg = make(1000, seed=2**31 + 41, name="seg0")
    assert seg.column("RegionID").dictionary.cardinality == 9_040 and schema.has_column("UserID")


# -- a whole run of the cell, at a tiny size, without the chip -------------
RUN_SEED, RUN_SEGMENTS, RUN_ROWS = 2**31 + 41, 3, 20_000


@pytest.fixture(scope="module")
def cut_manifest(tmp_path_factory) -> str:
    """The real manifest, the cell's configuration with its two sizes cut."""
    out = tmp_path_factory.mktemp("hits")
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
        assert run.main(["--workload", CELL, "--seed", str(RUN_SEED), "--seconds", "1", "--trace", str(trace)],
                        allow_cpu=True, manifest_path=manifest) == 0
    finally:
        gc.unfreeze()
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_run_of_the_cell_is_correct_and_marks_its_lowerings(capsys, cut_manifest, run_reference):
    out = run_cell(capsys, cut_manifest, trace=1)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 3
    live = ref_mod.state_digest(SHAPES["users_by_region"], run_reference.answers["users_by_region"])["numGroupsLive"]
    assert out["metrics"]["groups_live_mean"]["value"] == live and out["metrics"]["groups_kept_mean"]["value"] >= 100
    assert set(out["metrics"]) == {"compiles_in_window", "groups_live_mean", "groups_kept_mean"}  # counts; no time or share


def assert_not_correct_by(out: dict, name: str, alone: bool = False) -> None:
    """``correct`` false by ``name``, with every reply there (and by no
    other number where ``alone``: registers that are off also move the
    state's sum of squares, which ``sum_gap`` holds)."""
    assert out["correct"] is False and out["failed"] > 0
    over = {k for k, v in out["compared"].items() if v["value"] > v["limit"]}
    assert name in over and "reply_errors" not in over and (over == {name} or not alone), out["compared"]


def test_a_reply_that_lost_one_segments_registers_comes_out_not_correct(capsys, monkeypatch, cut_manifest):
    """The timed path broken where no count shows it: the first segment's
    ranks never reach the device, so every register lacks what that
    segment's users would have raised.  Counts, keys and sums are right."""
    from pinot_tpu.engine import device

    sound = device._hll_streams

    def lossy(cols, S, n_pad, timer=None):
        hb, hr = sound(cols, S, n_pad, timer)
        hr[0] = 0
        return hb, hr

    monkeypatch.setattr(device, "_hll_streams", lossy)
    assert_not_correct_by(run_cell(capsys, cut_manifest), "count_errors")


def test_an_estimate_off_by_one_comes_out_not_correct(capsys, monkeypatch, cut_manifest):
    from pinot_tpu.engine import hll

    sound = hll.estimate_from_registers
    monkeypatch.setattr(hll, "estimate_from_registers", lambda regs: sound(regs) + 1)
    assert_not_correct_by(run_cell(capsys, cut_manifest), "count_errors")


def test_a_wrong_region_under_a_right_estimate_comes_out_not_correct(capsys, monkeypatch, cut_manifest, run_reference):
    """An answer altered where the broker produces it: the region with
    most users replaced by the best one left out, with that region's own
    right estimate.  Every number of the reply is some region's right
    number; the list is not the top ten."""
    from pinot_tpu.common.response import BrokerResponse

    answer = run_reference.answers["users_by_region"]
    users = ref_mod.wanted(SHAPES["users_by_region"], answer)[0]
    order = np.argsort(-users, kind="stable")
    assert users[order[0]] > users[order[10]]
    first, eleventh = str(answer["keys"][order[0]]), order[10]
    sound = BrokerResponse.to_json

    def broken(self):
        out = sound(self)
        if len(out["aggregationResults"]) == 1:  # users_by_region
            for g in out["aggregationResults"][0].get("groupByResult", []):
                if g["group"] == [first]:
                    g["group"], g["value"] = [str(answer["keys"][eleventh])], str(int(users[eleventh]))
        return out

    monkeypatch.setattr(BrokerResponse, "to_json", broken)
    assert_not_correct_by(run_cell(capsys, cut_manifest), "key_errors", alone=True)
