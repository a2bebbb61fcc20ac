"""The plain reference of ``ssb_lineorder_flat_1chip`` (PR 47): against a
brute-force sum in a python dict over the rows; what ``compare`` catches
(a wrong city under a right sum, a sum off by a thousandth, a live count
off by one, a digest that lost one segment: each ``correct: false``); its
control (bfloat16) failing by ``sum_gap`` alone; a whole run of the cell
at a tiny size, sound and broken where the broker produces the reply;
and the cell's files as ISSUE 47 names them, looked up by membership.

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

ref_mod = run.load_module(os.path.join(BENCH, "reference_ssb_flat.py"))
CELL = "ssb_flat_drilldown_closed"
CONFIG = json.load(open(os.path.join(BENCH, "configs", "ssb_lineorder_flat_1chip.json")))
TRAFFIC = json.load(open(os.path.join(BENCH, "traffic", "ssb_flat_drilldown_closed.json")))
SHAPES = {s["name"]: s for s in TRAFFIC["shapes"]}
SUM_RTOL = CONFIG["guarantees"]["sum_rtol"]
LIMITS = {"sum_gap": SUM_RTOL, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
CLEAN = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
SEGMENTS, ROWS = 3, 20_000


def tiny_segments(seed: int, n: int = SEGMENTS, rows: int = ROWS):
    from pinot_tpu.tools.datagen import synthetic_lineorder_flat_segment

    return [synthetic_lineorder_flat_segment(rows, seed=seed * 1000 + i, name=f"seg{i}", segments=n) for i in range(n)]


def referee(segments, control="", shapes=SHAPES):
    ref = ref_mod.Reference(shapes, control=control)
    for seg in segments:
        ref.add(seg)
    return ref


def honest_reply(ref, name: str) -> dict:
    """The reply a sound program gives for a shape: every live group, and
    the state's two numbers."""
    shape, answer = SHAPES[name], ref.answers[name]
    (want,) = ref_mod.wanted(shape, answer)
    places = np.nonzero(answer["counts"])
    order = np.argsort(-want[places], kind="stable")
    groups = [{"group": [str(keys[at[i]]) for keys, at in zip(answer["keys"], places)], "value": f"{want[places][i]:.5f}"}
              for i in order]
    cost = {"numGroupsLive": ref_mod.live_groups(answer), "groupStateSumSq": ref_mod.state_sum_sq(shape, answer),
            "numGroupsKept": len(groups), "segmentsFullScan": 3}
    return {"aggregationResults": [{"function": "sum", "groupByResult": groups}], "exceptions": [],
            "numDocsScanned": answer["matched"], "totalDocs": ref.rows, "numServersQueried": 1, "numServersResponded": 1,
            "cost": cost}


@pytest.fixture(scope="module")
def ref():
    return referee(tiny_segments(7))


def rows_of(segments, columns):
    for seg in segments:
        cols = []
        for c in columns:
            col = seg.column(c)
            values = col.dictionary.values
            cols.append((np.asarray(values, dtype=object) if isinstance(values, list) else np.asarray(values))[col.fwd])
        yield from zip(*cols)


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_reference_against_a_sum_in_a_python_dict(ref, name):
    shape, answer = SHAPES[name], ref.answers[name]
    tests = {"=": lambda v, a: v == a, "in": lambda v, a: v in a, "or": lambda v, a: v in a,
             "between": lambda v, a: a[0] <= v <= a[1]}
    filter_cols = [c for c, _, _ in shape["filter"]]
    measures = ["lo_revenue", "lo_supplycost"]
    tally, count = collections.defaultdict(float), collections.Counter()
    for row in rows_of(tiny_segments(7), filter_cols + shape["group_by"] + measures):
        if all(tests[op](v, arg) for v, (_, op, arg) in zip(row, shape["filter"])):
            key = tuple(str(k) for k in row[len(filter_cols):-2])
            revenue, cost = int(row[-2]), int(row[-1])
            tally[key] += revenue - cost if isinstance(shape["aggs"][0][1], dict) else revenue
            count[key] += 1
    reply = honest_reply(ref, name)
    have = {tuple(g["group"]): float(g["value"]) for g in reply["aggregationResults"][0]["groupByResult"]}
    assert have == pytest.approx(dict(tally)) and answer["matched"] == sum(count.values())
    assert ref_mod.live_groups(answer) == len(tally)
    assert ref_mod.state_sum_sq(shape, answer) == pytest.approx(sum(v * v for v in tally.values()))
    assert ref_mod.compare(reply, shape, answer, ref.rows) == CLEAN
    assert ref.rows == SEGMENTS * ROWS


def test_shape_bytes_count_the_segments_a_date_leaf_cannot_prune(ref):
    # filter and key columns at one byte an id (p_brand's thousand values: two), 4 B a measure;
    # a segment whose dictionary holds no value of a date leaf is left out whole
    assert ref.shape_bytes("q3_1") == 3 * ROWS * (2 + 3 + 4)  # two regions; two nations and the year; revenue
    assert ref.shape_bytes("q3_4") == 1 * ROWS * (2 + 1 + 1 + 4)  # Dec1997 lies in the last of three ranges
    assert ref.shape_bytes("q4_3") == 1 * ROWS * (1 + 1 + 1 + 1 + 1 + 2 + 8)  # 1997 and 1998 too; two measures
    assert ref.shape_bytes("q4_1") == 3 * ROWS * (3 + 2 + 8)


FAULTS = ["a_wrong_city_under_a_right_sum", "a_sum_off_by_a_thousandth", "a_live_count_off_by_one",
          "a_digest_that_lost_one_segment", "a_group_missing", "a_group_twice", "a_year_that_is_no_number",
          "a_host_made_answer", "a_partial_answer", "rows_missing"]


@pytest.mark.parametrize("name", ["q3_2", "q4_3"])
@pytest.mark.parametrize("fault", FAULTS)
def test_compare_catches_each_kind_of_fault(ref, fault, name):
    shape, answer = SHAPES[name], ref.answers[name]
    reply = honest_reply(ref, name)
    groups = reply["aggregationResults"][0]["groupByResult"]
    city = shape["group_by"].index("s_city")
    year = shape["group_by"].index("d_year")
    by = "key_errors"
    if fault == "a_wrong_city_under_a_right_sum":
        groups[0]["group"][city] = "UNITED KI1"  # a city of the table, outside the filter
    elif fault == "a_sum_off_by_a_thousandth":
        groups[-1]["value"] = f"{float(groups[-1]['value']) * 1.001:.5f}"
        by = "sum_gap"
    elif fault == "a_live_count_off_by_one":
        reply["cost"]["numGroupsLive"] += 1
        by = "count_errors"
    elif fault == "a_digest_that_lost_one_segment":
        lost = referee(tiny_segments(7)[:-1])  # 1996 to 1998 gone from the state, under a whole reply
        reply["cost"]["groupStateSumSq"] = ref_mod.state_sum_sq(shape, lost.answers[name])
        by = "sum_gap"
    elif fault == "a_group_missing":
        groups.pop()
    elif fault == "a_group_twice":
        groups[1] = dict(groups[0])
    elif fault == "a_year_that_is_no_number":
        groups[0]["group"][year] = "MCMXCVII"
    elif fault == "a_host_made_answer":
        reply["cost"]["segmentsHost"] = 3
        by = "reply_errors"
    elif fault == "a_partial_answer":
        reply["partialResponse"] = True
        by = "reply_errors"
    elif fault == "rows_missing":
        reply["numDocsScanned"] -= 1
        by = "count_errors"
    got = ref_mod.compare(reply, shape, answer, ref.rows)
    assert got[by] > LIMITS[by], (fault, got)  # correct: false
    assert all(got[k] <= LIMITS[k] for k in LIMITS if k != by), (fault, got)  # by that number alone


def test_a_postings_reply_is_held_as_a_devices(ref):
    """The tier that answered is the program's choice and is not held; its
    numbers are: the same reply under ``segmentsPostings``."""
    reply = honest_reply(ref, "q3_3")
    reply["cost"] = dict(reply["cost"], segmentsPostings=3)
    del reply["cost"]["segmentsFullScan"]
    assert ref_mod.compare(reply, SHAPES["q3_3"], ref.answers["q3_3"], ref.rows) == CLEAN
    del reply["cost"]["groupStateSumSq"]  # a host tier that digests nothing
    assert ref_mod.compare(reply, SHAPES["q3_3"], ref.answers["q3_3"], ref.rows)["sum_gap"] > SUM_RTOL


def test_a_smaller_top_may_not_leave_a_better_group_out(ref):
    shape = dict(SHAPES["q3_1"], top=10)
    answer = ref.answers["q3_1"]
    reply = honest_reply(ref, "q3_1")
    groups = reply["aggregationResults"][0]["groupByResult"]
    reply["aggregationResults"][0]["groupByResult"] = groups[:10]
    assert ref_mod.compare(reply, shape, answer, ref.rows) == CLEAN
    reply["aggregationResults"][0]["groupByResult"] = groups[1:11]
    got = ref_mod.compare(reply, shape, answer, ref.rows)
    assert got["sum_gap"] > SUM_RTOL and got["key_errors"] == 0


def test_the_control_fails_by_sum_gap_alone():
    segments = tiny_segments(9)
    sound, control = referee(segments), referee(segments, control="bfloat16")
    gaps = ref_mod.control_gaps(sound, control)
    live = {name: ref_mod.live_groups(a) for name, a in sound.answers.items()}
    assert set(gaps) == set(SHAPES)
    # every live shape fails by sum_gap alone; a group of a few rows (q3_3 at this size) reads nearer the limit than
    # the cell's groups of 25 to 61,000 rows, whose readings on the chip's segments PERF.md section 2 gives
    assert all(gaps[name] > SUM_RTOL for name in gaps if live[name]) and max(gaps.values()) > 10 * SUM_RTOL, gaps
    for name in SHAPES:
        assert control.answers[name]["matched"] == sound.answers[name]["matched"]
        assert np.array_equal(control.answers[name]["counts"], sound.answers[name]["counts"])
    assert ref_mod.control_gaps(sound, sound) == {name: 0.0 for name in SHAPES}
    with pytest.raises(ValueError, match="control"):
        ref_mod.Reference(SHAPES, control="float16")


def test_what_the_reference_does_not_answer_is_refused_by_name():
    with pytest.raises(ValueError, match="aggregate"):
        ref_mod.Reference({"x": dict(SHAPES["q3_1"], aggs=[["max", "lo_revenue"]])})
    with pytest.raises(ValueError, match="operator"):
        ref_mod.Reference({"x": dict(SHAPES["q3_1"], filter=[["c_city", "like", "UNITED%"]])})


def test_the_cell_is_as_issue_47_names_it():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ssb_lineorder_flat_1chip", "ssb_flat_drilldown_closed", 1)
    assert len(cell["why"]) <= 200
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200 and entry["reduced"] == CONFIG["reduced"]
    assert entry["source"].startswith("Star Schema Benchmark (O'Neil, O'Neil, Chen, Revilak, 2009) as lineorder_flat")
    assert entry["file"] == "benchmark/configs/ssb_lineorder_flat_1chip.json"
    assert (CONFIG["rows_per_segment"], CONFIG["chips"], CONFIG["table"]) == (8_388_608, 1, "lineorder_flat")
    assert CONFIG["segments"] == (16 if not CONFIG["reduced"] else 12) and CONFIG["reduced"] in ([], ["segments"])
    assert CONFIG["schema"] == "pinot_tpu.tools.datagen:lineorder_flat_schema"
    assert CONFIG["generator"] == "benchmark.ssb_flat_table:segment"
    assert CONFIG["env"] == {"PINOT_TPU_AUDIT_SAMPLE_N": "0"}
    sibling = json.load(open(os.path.join(BENCH, "configs", "tpch_lineitem_keys_1chip.json")))
    for key in ("replication", "crc_verified_at_load", "result_cache", "segmentsHost", "partialResponse"):
        assert CONFIG["guarantees"][key] == sibling["guarantees"][key], key
    assert CONFIG["guarantees"]["sum_rtol"] in (1e-5, 1e-4)
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["keep_awake"], TRAFFIC["schedule_seed"], TRAFFIC["rehearse_s"],
            TRAFFIC["reference"]) == ("closed", 1, 1, 47, 1.0, "reference_ssb_flat")
    assert [(s["name"], s["share"], s["top"]) for s in TRAFFIC["shapes"]] == [
        ("q3_1", 1, 150), ("q3_2", 1, 600), ("q3_3", 1, 24), ("q3_4", 1, 4), ("q4_1", 1, 35), ("q4_2", 1, 100), ("q4_3", 1, 800)]
    assert ref_mod.render_pql(CONFIG["table"], SHAPES["q3_3"]) == (
        "SELECT sum(lo_revenue) FROM lineorder_flat WHERE c_city IN ('UNITED KI1','UNITED KI5') AND "
        "s_city IN ('UNITED KI1','UNITED KI5') AND d_year BETWEEN 1992 AND 1997 GROUP BY c_city, s_city, d_year TOP 24")
    assert ref_mod.render_pql(CONFIG["table"], SHAPES["q4_3"]) == (
        "SELECT sum(lo_revenue - lo_supplycost) FROM lineorder_flat WHERE c_region = 'AMERICA' AND "
        "s_nation = 'UNITED STATES' AND (d_year = 1997 OR d_year = 1998) AND p_category = 'MFGR#14' "
        "GROUP BY d_year, s_city, p_brand TOP 800")
    reported = {m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reported == {"latency_p50_ms", "throughput_qps", "hbm_bytes_per_row", "setup_s"}
    listed = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])}  # by membership
    assert {"tier_postings_share", "tier_host_share", "host_index_build_s", "index_path_ms_mean",
            "group_keyspace_cells_mean", "groups_live_mean", "groups_kept_mean", "group_trim_ms_mean",
            "group_state_fetch_kb_mean", "expr_device_share", "groupby_contraction_share", "groupby_sorted_share",
            "groupby_runs_share", "plan_prepared_hit_share"} <= listed
    for name in ("tier_postings_share", "tier_host_share", "host_index_build_s", "index_path_ms_mean",
                 "group_keyspace_cells_mean"):  # this PR's: for this cell alone
        (m,) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL], name


# -- a whole run of the cell, at a tiny size, without the chip -------------
RUN_SEED = 2**31 + 47


@pytest.fixture(scope="module")
def cut_manifest(tmp_path_factory) -> str:
    """The real manifest, the cell's configuration with its two sizes cut."""
    out = tmp_path_factory.mktemp("ssb")
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG["name"]]
    (out / "config.json").write_text(json.dumps(dict(CONFIG, segments=SEGMENTS, rows_per_segment=ROWS)))
    entry["file"] = str(out / "config.json")
    (out / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(out / "BENCHMARK.json")


@pytest.fixture()
def three_date_ranges(monkeypatch):
    """The generator's count of date ranges cut as the table is, so that
    three segments still span 1992 to 1998 and every shape finds rows."""
    import benchmark.ssb_flat_table as table

    monkeypatch.setattr(table, "SEGMENTS", SEGMENTS)


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


def test_a_run_of_the_cell_is_correct_and_its_readers_read(capsys, monkeypatch, cut_manifest, three_date_ranges):
    out, read = run_cell(capsys, monkeypatch, cut_manifest, trace=1)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 7
    assert read["tier_postings_share"] == pytest.approx(200.0 / 7) and read["tier_host_share"] == 0.0
    assert read["host_index_build_s"] > 0 and read["index_path_ms_mean"] > 0
    # five device group-bys a round: 4,375 + 437,500 + 175 + 4,375 + 1,750,000 cells planned
    assert read["group_keyspace_cells_mean"] == pytest.approx(2_196_425 / 5)
    assert out["metrics"]["group_keyspace_cells_mean"]["value"] > 100 * out["metrics"]["groups_live_mean"]["value"] > 0
    assert read["groupby_runs_share"] == pytest.approx(20.0) and read["expr_device_share"] == 100.0
    assert read["plan_prepared_hit_share"] == 100.0 and out["metrics"]["compiles_in_window"]["value"] == 0


def assert_not_correct_by(out: dict, name: str) -> None:
    assert out["correct"] is False and out["failed"] > 0
    over = {k for k, v in out["compared"].items() if v["value"] > v["limit"]}
    assert over == {name}, out["compared"]


BROKEN = {
    "a_wrong_city_under_a_right_sum": "key_errors",
    "a_sum_off_by_a_thousandth": "sum_gap",
    "a_live_count_off_by_one": "count_errors",
    "a_digest_that_lost_a_thousandth": "sum_gap",
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_reply_altered_where_the_broker_produces_it_comes_out_not_correct(capsys, monkeypatch, cut_manifest,
                                                                          three_date_ranges, fault):
    from pinot_tpu.common.response import BrokerResponse

    sound = BrokerResponse.to_json

    def broken(self):
        out = sound(self)
        groups = [g for result in out.get("aggregationResults") or [] for g in result.get("groupByResult") or []]
        cost = out.get("cost") or {}
        if fault == "a_wrong_city_under_a_right_sum":
            for g in groups[:1]:
                g["group"] = ["ATLANTIS 0" if "UNITED" in k or k[-1:].isdigit() and not k.isdigit() else k for k in g["group"]]
        elif fault == "a_sum_off_by_a_thousandth":
            for g in groups[:1]:
                g["value"] = f"{float(g['value']) * 1.001:.5f}"
        elif fault == "a_live_count_off_by_one" and "numGroupsLive" in cost:
            cost["numGroupsLive"] += 1
        elif fault == "a_digest_that_lost_a_thousandth" and "groupStateSumSq" in cost:
            cost["groupStateSumSq"] *= 0.999
        return out

    monkeypatch.setattr(BrokerResponse, "to_json", broken)
    assert_not_correct_by(run_cell(capsys, monkeypatch, cut_manifest)[0], BROKEN[fault])
