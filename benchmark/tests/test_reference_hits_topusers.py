"""The plain reference of ``clickbench_hits_topusers_1chip`` (PR 43):
against a brute-force count in a python dict; what ``compare`` catches (a
wrong user under a right count, a count off by one, a live count off by
one, a digest off by a segment: each ``correct: false``); its control (a
state that lost one segment's rows of one user) failing by ``sum_gap``
alone; the generator's precondition; and the cell's files as ISSUE 43
names them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import collections
import copy
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

ref_mod = run.load_module(os.path.join(BENCH, "reference_hits_topusers.py"))
CELL = "hits_top_users_closed"
CONFIG = json.load(open(os.path.join(BENCH, "configs", "clickbench_hits_topusers_1chip.json")))
TRAFFIC = json.load(open(os.path.join(BENCH, "traffic", "hits_topusers_closed.json")))
SHAPES = {s["name"]: s for s in TRAFFIC["shapes"]}
SHAPE = SHAPES["top_users"]
SUM_RTOL = CONFIG["guarantees"]["sum_rtol"]
CLEAN = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}


def tiny_segments(seed: int, rows: int = 30_000, n: int = 3, users: int = 40_000):
    from pinot_tpu.tools.datagen import synthetic_hits_users_segment

    return [synthetic_hits_users_segment(rows, seed=seed * 1000 + i, name=f"seg{i}", users=users) for i in range(n)]


def referee(segments, control=""):
    ref = ref_mod.Reference(SHAPES, control=control)
    for seg in segments:
        ref.add(seg)
    return ref


def honest_reply(ref, skip: int = 0) -> dict:
    """The reply a sound program gives: the ten users with most rows, in
    order (``skip`` from the top left out first), and the state's digest."""
    answer = ref.answers["top_users"]
    top = np.lexsort((answer["keys"], -answer["counts"]))[skip : skip + SHAPE["top"]]
    groups = [{"group": [str(answer["keys"][i])], "value": str(int(answer["counts"][i]))} for i in top]
    cost = dict(ref_mod.state_digest(answer), segmentsFullScan=3, numGroupsKept=100)
    return {"aggregationResults": [{"function": "count_star", "groupByResult": groups}], "exceptions": [],
            "numDocsScanned": answer["matched"], "totalDocs": ref.rows, "numServersQueried": 1, "numServersResponded": 1,
            "cost": cost}


def held(ref, reply) -> dict:
    return ref_mod.compare(reply, SHAPE, ref.answers["top_users"], ref.rows)


@pytest.fixture(scope="module")
def ref():
    return referee(tiny_segments(3))


def test_reference_against_a_count_in_a_python_dict(ref):
    tally = collections.Counter()
    for seg in tiny_segments(3):
        col = seg.column("UserID")
        tally.update(np.asarray(col.dictionary.values)[col.fwd].tolist())
    answer = ref.answers["top_users"]
    assert dict(zip(answer["keys"].tolist(), answer["counts"].tolist())) == dict(tally)
    assert answer["matched"] == ref.rows == 90_000 and ref.shape_bytes("top_users") == 4 * 90_000
    assert answer["digest"] == {"numGroupsLive": len(tally), "groupStateSumSq": sum(c * c for c in tally.values())}
    assert held(ref, honest_reply(ref)) == CLEAN


FAULTS = ["a_wrong_user_under_a_right_count", "a_count_off_by_one", "a_live_count_off_by_one",
          "a_digest_off_by_a_segment", "the_eleventh_for_the_tenth", "out_of_order", "nine_users",
          "a_user_twice", "half_a_count", "a_host_made_answer", "a_partial_answer", "rows_missing"]


@pytest.mark.parametrize("fault", FAULTS)
def test_compare_catches_each_kind_of_fault(ref, fault):
    answer = ref.answers["top_users"]
    reply = honest_reply(ref)
    groups = reply["aggregationResults"][0]["groupByResult"]
    order = np.lexsort((answer["keys"], -answer["counts"]))
    by = "count_errors"
    if fault == "a_wrong_user_under_a_right_count":
        groups[3]["group"] = [str(answer["keys"][order[500]])]  # a user of the table, with the fourth's count
    elif fault == "a_count_off_by_one":
        groups[9]["value"] = str(int(groups[9]["value"]) + 1)
    elif fault == "a_live_count_off_by_one":
        reply["cost"]["numGroupsLive"] -= 1
    elif fault == "a_digest_off_by_a_segment":
        # what a server reads that dropped the first segment's rows of the eleventh user: right top ten, right live count
        lost = referee(tiny_segments(3), control="drop_rank11").dropped("top_users")
        assert np.array_equal(np.sort(lost["counts"])[-10:], np.sort(answer["counts"])[-10:])
        reply["cost"]["groupStateSumSq"] = float(ref_mod.state_digest(lost)["groupStateSumSq"])
        by = "sum_gap"
    elif fault == "the_eleventh_for_the_tenth":
        assert answer["counts"][order[10]] < answer["counts"][order[9]]
        groups[9] = {"group": [str(answer["keys"][order[10]])], "value": str(int(answer["counts"][order[10]]))}
        by = "key_errors"
    elif fault == "out_of_order":
        groups[0], groups[1] = groups[1], groups[0]
        by = "key_errors"
    elif fault == "nine_users":
        groups.pop()
        by = "key_errors"
    elif fault == "a_user_twice":
        groups[5] = dict(groups[4])
        by = "key_errors"
    elif fault == "half_a_count":
        groups[2]["value"] = groups[2]["value"] + ".5"
    elif fault == "a_host_made_answer":
        reply["cost"]["segmentsHost"] = 3
        by = "reply_errors"
    elif fault == "a_partial_answer":
        reply["partialResponse"] = True
        by = "reply_errors"
    elif fault == "rows_missing":
        reply["numDocsScanned"] -= 1
    got = held(ref, reply)
    limits = {"sum_gap": SUM_RTOL, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
    assert got[by] > limits[by], (fault, got)  # correct: false
    if fault in ("a_live_count_off_by_one", "a_digest_off_by_a_segment", "the_eleventh_for_the_tenth", "rows_missing"):
        assert all(got[k] <= limits[k] for k in limits if k != by), (fault, got)  # by that number alone


def test_a_tie_at_the_cut_may_return_either_user(ref):
    answer = copy.deepcopy(ref.answers["top_users"])
    order = np.lexsort((answer["keys"], -answer["counts"]))
    answer["counts"][order[10]] = answer["counts"][order[9]]  # the eleventh ties the tenth
    answer["digest"] = ref_mod.state_digest(answer)
    for tenth in (order[9], order[10]):
        groups = [{"group": [str(answer["keys"][i])], "value": str(int(answer["counts"][i]))} for i in list(order[:9]) + [tenth]]
        reply = dict(honest_reply(ref), aggregationResults=[{"groupByResult": groups}], cost=dict(answer["digest"]))
        assert ref_mod.compare(reply, SHAPE, answer, ref.rows) == CLEAN


@pytest.mark.parametrize("rank", [1, 11, 100])
def test_the_control_fails_by_sum_gap_alone(rank):
    segments = tiny_segments(5)
    sound, control = referee(segments), referee(segments, control=f"drop_rank{rank}")
    gaps = ref_mod.control_gaps(sound, control)
    assert set(gaps) == {"top_users"} and gaps["top_users"] > SUM_RTOL
    lost = control.dropped("top_users")
    assert lost["counts"].sum() < sound.answers["top_users"]["counts"].sum()
    assert np.count_nonzero(lost["counts"] != sound.answers["top_users"]["counts"]) == 1  # one user's count, no other
    with pytest.raises(ValueError, match="drop_rank"):
        ref_mod.Reference(SHAPES, control="bfloat16")  # the shape has no float to round


def test_only_the_cells_shape_is_answered():
    for shape in (dict(SHAPE, filter=[["RegionID", "<", 5]]), dict(SHAPE, aggs=[["sum", "AdvEngineID"]]),
                  dict(SHAPE, group_by=["UserID", "RegionID"])):
        with pytest.raises(ValueError, match="count"):
            ref_mod.Reference({"x": shape})


def test_the_cell_is_as_issue_43_names_it():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("clickbench_hits_topusers_1chip", "hits_topusers_closed", 1)
    assert len(cell["why"]) <= 200
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert "queries.sql line 16" in entry["source"] and entry["file"] == "benchmark/configs/clickbench_hits_topusers_1chip.json"
    assert (CONFIG["segments"], CONFIG["rows_per_segment"], CONFIG["chips"], CONFIG["reduced"]) == (12, 8_388_608, 1, [])
    assert CONFIG["table"] == "hits" and CONFIG["schema"] == "pinot_tpu.tools.datagen:hits_users_schema"
    assert CONFIG["generator"] == "benchmark.hits_topusers_table:segment"
    assert CONFIG["env"] == {"PINOT_TPU_AUDIT_SAMPLE_N": "0"}
    sibling = json.load(open(os.path.join(BENCH, "configs", "clickbench_hits_users_1chip.json")))
    for key in ("replication", "crc_verified_at_load", "result_cache", "segmentsHost", "partialResponse", "sum_rtol"):
        assert CONFIG["guarantees"][key] == sibling["guarantees"][key], key
    for key in ("counts_and_numDocsScanned", "keys_and_numGroupsLive", "whole_state"):
        assert "exact" in CONFIG["guarantees"][key] or "sum_rtol" in CONFIG["guarantees"][key], key
    assumed = " ".join(CONFIG["assumed"])
    for word in ("17,630,976", "exponent 0.7", "19,250,000", "197,000", "TOP 10", "UserID beside the count"):
        assert word in assumed, word
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["keep_awake"], TRAFFIC["schedule_seed"], TRAFFIC["rehearse_s"],
            TRAFFIC["reference"]) == ("closed", 1, 1, 43, 1.0, "reference_hits_topusers")
    assert [(s["name"], s["share"], s["group_by"], s["top"], s["aggs"]) for s in TRAFFIC["shapes"]] == [
        ("top_users", 1, ["UserID"], 10, [["count", "*"]])]
    assert ref_mod.render_pql(CONFIG["table"], SHAPE) == "SELECT count(*) FROM hits GROUP BY UserID TOP 10"
    reported = {m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reported == {"latency_p50_ms", "throughput_qps", "hbm_bytes_per_row", "setup_s"}
    for reader in ("plan_build_ms_mean", "lane_queue_ms_mean", "lane_launch_ms_mean", "device_wait_ms_mean",
                   "d2h_unpack_ms_mean", "lane_busy_share", "plan_prepared_hit_share", "group_trim_ms_mean",
                   "groups_live_mean", "groups_kept_mean", "groupby_runs_share", "group_state_fetch_kb_mean",
                   "global_dict_build_s"):
        (m,) = [m for m in manifest["per_layer"] if m["name"] == reader]
        assert CELL in m["workloads"], reader  # by membership: a later cell appends to the same lists
    for reader in ("groupby_contraction_share", "groupby_sorted_share"):  # they divide by three marks: a 'runs' launch is none
        (m,) = [m for m in manifest["per_layer"] if m["name"] == reader]
        assert CELL not in m["workloads"], reader


@pytest.mark.parametrize("planner", ["this_program", "says_host", "has_no_such_name"])
def test_the_generator_holds_the_precondition_before_the_first_segment(monkeypatch, planner):
    from pinot_tpu.engine import plan

    make = run.resolve(CONFIG["generator"])
    if planner == "this_program":
        seg = make(1000, seed=2**31 + 43, name="seg0")
        sibling = run.resolve("pinot_tpu.tools.datagen:synthetic_hits_users_segment")(1000, seed=2**31 + 43, name="seg0")
        assert seg.compute_crc() == sibling.compute_crc() and seg.num_docs == 1000  # the sibling's segment, unchanged
        return
    if planner == "says_host":
        monkeypatch.setattr(plan, "group_runs_host_reason", lambda request, capacity: "aggregate:count")
    else:
        monkeypatch.delattr(plan, "group_runs_host_reason")
    with pytest.raises(RuntimeError, match="answered from the chip"):
        make(1000, seed=1, name="seg0")


# -- a whole run of the cell, at a tiny size, without the chip -------------
RUN_SEED, RUN_SEGMENTS, RUN_ROWS = 2**31 + 43, 3, 20_000


@pytest.fixture(scope="module")
def cut_manifest(tmp_path_factory) -> str:
    """The real manifest, the cell's configuration with its two sizes cut."""
    out = tmp_path_factory.mktemp("topusers")
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG["name"]]
    (out / "config.json").write_text(json.dumps(dict(CONFIG, segments=RUN_SEGMENTS, rows_per_segment=RUN_ROWS)))
    entry["file"] = str(out / "config.json")
    (out / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(out / "BENCHMARK.json")


@pytest.fixture()
def dense_holders_of_4096(monkeypatch):
    """The program's bound cut as the table is: 2^20 keys are too many for
    a test, so the dense holders end under the cut table's users and the
    run goes through the lowering the cell is there for."""
    from pinot_tpu.engine import config, kernel

    monkeypatch.setattr(config, "MAX_GROUP_CAPACITY", 1 << 12)
    yield
    for cached in (kernel.make_table_kernel, kernel.make_packed_table_kernel):
        cached.cache_clear()


def run_cell(capsys, monkeypatch, manifest: str, trace: int = 0) -> tuple:
    """(the result line, every reader's answer before run.py drops the
    times of a CPU run)."""
    import gc
    import types

    read, load_module = {}, run.load_module

    def recording(path: str):
        module = load_module(path)
        if os.path.basename(os.path.dirname(path)) != "layer_metrics":
            return module
        name = os.path.basename(path)[:-3]

        def read_and_record(r):
            read[name] = module.read(r)
            return read[name]

        return types.SimpleNamespace(read=read_and_record)

    monkeypatch.setattr(run, "load_module", recording)
    try:
        assert run.main(["--workload", CELL, "--seed", str(RUN_SEED), "--seconds", "1", "--trace", str(trace)],
                        allow_cpu=True, manifest_path=manifest) == 0
    finally:
        gc.unfreeze()
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1]), read


def test_a_run_of_the_cell_is_correct_through_the_runs_lowering(capsys, monkeypatch, cut_manifest, dense_holders_of_4096):
    out, read = run_cell(capsys, monkeypatch, cut_manifest, trace=1)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 3
    make = run.resolve(CONFIG["generator"])
    want = referee([make(RUN_ROWS, seed=RUN_SEED * 1000 + i, name=f"seg{i}") for i in range(RUN_SEGMENTS)])
    live = want.answers["top_users"]["digest"]["numGroupsLive"]
    assert live > 1 << 12 and out["metrics"]["groups_live_mean"]["value"] == live
    assert read["groupby_runs_share"] == 100.0 and read["global_dict_build_s"] > 0
    assert 0 < read["group_state_fetch_kb_mean"] < 400  # candidates, not the table's users
    assert set(out["metrics"]) == {"compiles_in_window", "groups_live_mean", "groups_kept_mean"}  # counts; no time or share


def assert_not_correct_by(out: dict, name: str) -> None:
    assert out["correct"] is False and out["failed"] > 0
    over = {k for k, v in out["compared"].items() if v["value"] > v["limit"]}
    assert over == {name}, out["compared"]


def test_a_wrong_user_under_a_right_count_comes_out_not_correct(capsys, monkeypatch, cut_manifest, dense_holders_of_4096):
    """An answer altered where the broker produces it: the first user's
    name replaced by a user the table does not hold."""
    from pinot_tpu.common.response import BrokerResponse

    sound = BrokerResponse.to_json

    def broken(self):
        out = sound(self)
        for result in out.get("aggregationResults") or []:
            groups = result.get("groupByResult") or []
            if groups:
                groups[0]["group"] = ["42"]
        return out

    monkeypatch.setattr(BrokerResponse, "to_json", broken)
    assert_not_correct_by(run_cell(capsys, monkeypatch, cut_manifest)[0], "key_errors")


def test_a_run_split_in_two_under_a_right_top_ten_comes_out_not_correct(capsys, monkeypatch, cut_manifest, dense_holders_of_4096):
    """The timed path broken where no returned count shows it: the
    program's digest loses a thousandth (a user's run split, a count
    dropped).  The ten users and their counts are right."""
    from pinot_tpu.engine.executor import QueryExecutor

    sound = QueryExecutor._kept_run_keys

    def lossy(self, plan, outs):
        live, digest, keys = sound(self, plan, outs)
        return live, {k: v * 0.999 for k, v in digest.items()}, keys

    monkeypatch.setattr(QueryExecutor, "_kept_run_keys", lossy)
    assert_not_correct_by(run_cell(capsys, monkeypatch, cut_manifest)[0], "sum_gap")
