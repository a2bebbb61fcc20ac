"""The plain reference of ``clickbench_hits_search_1chip`` (PR 50): against
a brute-force sort of python tuples; what ``compare`` catches (a later
row in place of an earlier one, a reply out of order, one that lost a
segment, one of nine rows, a ``numDocsScanned`` off by one: each
``correct: false`` by that number alone) and what it lets pass (every
right choice among rows tied on the key); its control (a table that lost
a segment's matching rows); the cell's files as ISSUE 50 names them; and
whole runs of the cell at a tiny size.

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

ref_mod = run.load_module(os.path.join(BENCH, "reference_hits_search.py"))
CELL = "hits_search_selection_closed"
CONFIG = json.load(open(os.path.join(BENCH, "configs", "clickbench_hits_search_1chip.json")))
TRAFFIC = json.load(open(os.path.join(BENCH, "traffic", "hits_search_selection_closed.json")))
SHAPES = {s["name"]: s for s in TRAFFIC["shapes"]}
CLEAN = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
LIMITS = {"sum_gap": CONFIG["guarantees"]["sum_rtol"], "count_errors": 0, "key_errors": 0, "reply_errors": 0}


def tiny_segments(seed: int, rows: int = 20_000, n: int = 3, phrases: int = 40_000):
    from pinot_tpu.tools.datagen import synthetic_hits_search_segment

    return [synthetic_hits_search_segment(rows, seed=seed * 1000 + i, name=f"seg{i}", users=50_000, phrases=phrases)
            for i in range(n)]


def referee(segments, control="", shapes=SHAPES):
    ref = ref_mod.Reference(shapes, control=control)
    for seg in segments:
        ref.add(seg)
    return ref


def brute_force(segments, shape):
    """Every matching row as (key tuple, selected tuple), sorted by python."""
    out = []
    for seg in segments:
        cols = {name: np.asarray(seg.column(name).dictionary.values, dtype=object)[seg.column(name).fwd]
                for name in {c for c, _ in shape["order_by"]} | set(shape["select"]) | {c for c, _, _ in shape["filter"]}}
        keep = np.ones(seg.num_docs, dtype=bool)
        for col, op, arg in shape["filter"]:
            assert op == "<>"
            keep &= cols[col] != arg
        for i in np.nonzero(keep)[0]:
            out.append((tuple(cols[c][i] for c, _ in shape["order_by"]), tuple(cols[c][i] for c in shape["select"])))
    return sorted(out)


def held(ref, name, reply) -> dict:
    return ref_mod.compare(reply, SHAPES[name], ref.answers[name], ref.rows)


def honest(ref, name) -> dict:
    return ref_mod.canonical_reply(ref.answers[name], SHAPES[name], ref.rows)


@pytest.fixture(scope="module")
def segments():
    return tiny_segments(3)


@pytest.fixture(scope="module")
def ref(segments):
    return referee(segments)


@pytest.mark.parametrize("name", list(SHAPES))
def test_reference_against_a_sort_of_python_tuples(ref, segments, name):
    rows = brute_force(segments, SHAPES[name])
    answer = ref.answers[name]
    cut = rows[9][0]
    want = [r for r in rows if r[0] <= cut]  # every row at or under the tenth's key
    assert answer["matched"] == len(rows) and answer["limit"] == 10
    assert [tuple(int(v) if isinstance(v, (int, np.integer)) else v for v in k) for k in answer["keys"]] == [
        tuple(int(v) if isinstance(v, (int, np.integer)) else v for v in k) for k, _ in want]
    assert sorted(answer["values"]) == sorted(v for _, v in want)
    assert held(ref, name, honest(ref, name)) == CLEAN
    columns = 1 if name == "by_phrase" else 2
    assert ref.rows == 60_000 and ref.shape_bytes(name) == 4 * columns * 60_000


FAULTS = ["a_later_row_for_an_earlier", "out_of_order", "lost_a_segment", "nine_rows", "rows_missing",
          "a_row_twice", "a_row_the_table_lacks", "eleven_rows", "a_host_made_answer", "a_partial_answer",
          "another_column"]


@pytest.mark.parametrize("name", list(SHAPES))
@pytest.mark.parametrize("fault", FAULTS)
def test_compare_catches_each_kind_of_fault(ref, segments, name, fault):
    shape = SHAPES[name]
    reply = honest(ref, name)
    rows = reply["selectionResults"]["results"]
    every = brute_force(segments, shape)
    by, alone = "key_errors", False
    if fault == "a_later_row_for_an_earlier":
        later = next(v for k, v in every if k > every[9][0] and [v[0]] not in rows)  # from above the cut
        rows[4] = list(later)
        alone = True
    elif fault == "out_of_order":
        i = next(i for i in range(9) if every[i][0] != every[i + 1][0] and rows[i] != rows[i + 1])
        rows[i], rows[i + 1] = rows[i + 1], rows[i]
        alone = True
    elif fault == "lost_a_segment":
        lost = referee(segments, control="drop_segment0")
        reply = ref_mod.canonical_reply(lost.answers[name], shape, ref.rows)
        got = held(ref, name, reply)
        assert got["count_errors"] == 1 and got["reply_errors"] == 0 and got["sum_gap"] == 0.0
        if name != "by_phrase":  # segment 0 holds the earliest days: every row of the reply is gone
            assert got["key_errors"] == 1
        return
    elif fault == "nine_rows":
        rows.pop()
        alone = True
    elif fault == "rows_missing":
        reply["numDocsScanned"] -= 1
        by, alone = "count_errors", True
    elif fault == "a_row_twice":
        i = next(i for i in range(9) if rows[i] != rows[i + 1])
        rows[i + 1] = list(rows[i])
    elif fault == "a_row_the_table_lacks":
        rows[0] = ["no such phrase"]
    elif fault == "eleven_rows":
        rows.append(list(rows[-1]))
    elif fault == "a_host_made_answer":
        reply["cost"]["segmentsHost"] = 3
        by = "reply_errors"
    elif fault == "a_partial_answer":
        reply["partialResponse"] = True
        by = "reply_errors"
    elif fault == "another_column":
        reply["selectionResults"]["columns"] = ["EventTime"]
        by = "reply_errors"
    got = held(ref, name, reply)
    assert got[by] > LIMITS[by], (fault, got)  # correct: false
    if alone:
        assert all(got[k] <= LIMITS[k] for k in LIMITS if k != by), (fault, got)  # by that number alone


def tied_answer():
    """An answer whose cut falls inside a tie: seconds 1, 2, 2 and then
    twenty rows of second 3, two of them the phrase 'pp', one each 'a'..'r'."""
    at_cut = ["pp", "pp"] + [chr(ord("a") + i) for i in range(18)]
    keys = [(1,), (2,), (2,)] + [(3,)] * 20
    values = [("x",), ("y",), ("z",)] + [(v,) for v in at_cut]
    return {"keys": keys, "values": values, "matched": 500, "limit": 10}


def tied_reply(rows: list) -> dict:
    return {"selectionResults": {"columns": ["SearchPhrase"], "results": [[r] for r in rows]}, "exceptions": [],
            "numDocsScanned": 500, "totalDocs": 1000, "numServersQueried": 1, "numServersResponded": 1, "cost": {}}


@pytest.mark.parametrize("rows,right", [
    (["x", "y", "z", "pp", "pp", "a", "b", "c", "d", "e"], True),   # the first seven of the tie, as kept
    (["x", "z", "y", "r", "q", "p", "o", "n", "m", "l"], True),   # another right choice: the tie's last, the twos swapped
    (["x", "y", "z", "pp", "a", "pp", "b", "c", "d", "e"], True),   # rows tied on the key come in any order
    (["x", "y", "z", "pp", "pp", "pp", "a", "b", "c", "d"], False),  # 'pp' oftener than the table holds it
    (["x", "y", "z", "a", "b", "c", "d", "e", "f", "s"], False),  # 's' is no row at the cut's key
    (["y", "x", "z", "a", "b", "c", "d", "e", "f", "g"], False),  # a row of second 2 before the row of second 1
    (["x", "y", "a", "b", "c", "d", "e", "f", "g", "h"], False),  # a row under the cut left out
])
def test_a_cut_inside_a_tie_takes_every_right_answer_and_no_other(rows, right):
    got = ref_mod.compare(tied_reply(rows), SHAPES["by_time"], tied_answer(), 1000)
    assert (got == CLEAN) is right, got
    if not right:
        assert got == dict(CLEAN, key_errors=1)


def test_the_reference_keeps_every_row_tied_at_the_cut(segments):
    """ORDER BY a column of three values a segment: the tenth key is the
    first day, and every matching row of that day is at the cut."""
    shape = {"name": "by_day", "select": ["SearchPhrase"], "filter": [["SearchPhrase", "<>", ""]],
             "order_by": [["EventDate", "asc"]], "limit": 10}
    ref = referee(segments, shapes={"by_day": shape})
    answer = ref.answers["by_day"]
    first = min(seg.column("EventDate").dictionary.values[0] for seg in segments)
    rows = [r for r in brute_force(segments, shape) if r[0] == (first,)]
    assert len(answer["keys"]) == len(rows) > 100 and set(answer["keys"]) == {(first,)}
    for pick in (rows[:10], rows[-10:], rows[5:95:9]):  # any ten of them, in any order
        reply = ref_mod.canonical_reply(answer, shape, ref.rows)
        reply["selectionResults"]["results"] = [list(v) for _, v in pick]
        assert ref_mod.compare(reply, shape, answer, ref.rows) == CLEAN


@pytest.mark.parametrize("way", ["asc", "desc"])
def test_descending_keys_and_no_order(segments, way):
    shape = {"name": "s", "select": ["SearchPhrase", "EventTime"], "filter": [["SearchPhrase", "<>", ""]],
             "order_by": [["EventTime", way], ["SearchPhrase", "desc"]], "limit": 7}
    ref = referee(segments, shapes={"s": shape})
    every = brute_force(segments, shape)
    times = sorted({k[0] for k, _ in every}, reverse=way == "desc")
    want = sorted(every, key=lambda r: (times.index(r[0][0]) if r[0][0] in times[:8] else 99, [-ord(c) for c in r[0][1]] + [1]))[:7]
    assert [v for v in ref.answers["s"]["values"][:7]] == [v for _, v in want]
    assert ref_mod.render_pql("hits", shape).endswith(
        f"ORDER BY EventTime{' DESC' if way == 'desc' else ''}, SearchPhrase DESC LIMIT 7")
    free = dict(shape, order_by=[])
    loose = referee(segments, shapes={"s": free})
    reply = ref_mod.canonical_reply(loose.answers["s"], free, loose.rows)
    assert ref_mod.compare(reply, free, loose.answers["s"], loose.rows) == CLEAN
    reply["selectionResults"]["results"].pop()
    assert ref_mod.compare(reply, free, loose.answers["s"], loose.rows)["key_errors"] == 1


@pytest.mark.parametrize("k", [0, 1, 2])
def test_the_control_drops_a_segment(segments, k):
    sound, control = referee(segments), referee(segments, control=f"drop_segment{k}")
    gaps = ref_mod.control_gaps(sound, control)
    assert set(gaps) == set(SHAPES)
    # the segments are three days each, one after the other: the earliest seconds are segment 0's
    assert (gaps["by_time"], gaps["by_time_phrase"]) == ((1.0, 1.0) if k == 0 else (0.0, 0.0))
    for name in SHAPES:
        assert control.answers[name]["matched"] < sound.answers[name]["matched"] and control.rows == sound.rows
        got = held(sound, name, ref_mod.canonical_reply(control.answers[name], SHAPES[name], sound.rows))
        assert got["count_errors"] == 1 and got["key_errors"] == int(gaps[name])  # correct: false either way
    with pytest.raises(ValueError, match="drop_segment"):
        ref_mod.Reference(SHAPES, control="bfloat16")  # no shape holds a float to round


def test_only_a_selection_is_answered():
    for shape in ({"name": "x", "aggs": [["count", "*"]]}, dict(SHAPES["by_time"], filter=[["SearchPhrase", "like", "a%"]]),
                  {k: v for k, v in SHAPES["by_time"].items() if k != "limit"}):
        with pytest.raises(ValueError, match="selection"):
            ref_mod.Reference({"x": shape})


def test_the_cell_is_as_issue_50_names_it():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("clickbench_hits_search_1chip", CELL, 1)
    assert len(cell["why"]) <= 200
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert "queries.sql lines 25-27" in entry["source"] and entry["file"] == "benchmark/configs/clickbench_hits_search_1chip.json"
    assert (CONFIG["segments"], CONFIG["rows_per_segment"], CONFIG["chips"], CONFIG["reduced"]) == (12, 8_388_608, 1, [])
    assert CONFIG["table"] == "hits" and CONFIG["schema"] == "pinot_tpu.tools.datagen:hits_search_schema"
    assert CONFIG["generator"] == "benchmark.hits_search_table:segment"
    assert CONFIG["env"] == {"PINOT_TPU_AUDIT_SAMPLE_N": "0"}
    sibling = json.load(open(os.path.join(BENCH, "configs", "clickbench_hits_topusers_1chip.json")))
    for key in ("replication", "crc_verified_at_load", "result_cache", "segmentsHost", "partialResponse"):
        assert CONFIG["guarantees"][key] == sibling["guarantees"][key], key
    assert CONFIG["guarantees"]["sum_rtol"] == 0 and "no shape of the cell holds a float" in CONFIG["guarantees"]["sum_rtol_why"]
    for word in ("min(10, matching rows)", "nondecreasing", "under the cut", "none oftener", "key_error", "each of them passes"):
        assert word in CONFIG["guarantees"]["selection"], word
    assumed = " ".join(CONFIG["assumed"])
    for word in ("86.9%", "6,019,103", "exponent 0.8", "22,672,621", "783,626", "259,200", "3,110,400", "1..90", "LIMIT 10",
                 "from memory"):
        assert word in assumed, word
    assert (TRAFFIC["loop"], TRAFFIC["clients"], TRAFFIC["keep_awake"], TRAFFIC["schedule_seed"], TRAFFIC["rehearse_s"],
            TRAFFIC["reference"]) == ("closed", 1, 1, 50, 1.0, "reference_hits_search")
    assert [(s["name"], s["share"]) for s in TRAFFIC["shapes"]] == [("by_time", 1), ("by_phrase", 1), ("by_time_phrase", 1)]
    lines = "SELECT SearchPhrase FROM hits WHERE SearchPhrase <> '' ORDER BY {} LIMIT 10"
    assert [ref_mod.render_pql(CONFIG["table"], s) for s in TRAFFIC["shapes"]] == [
        lines.format("EventTime"), lines.format("SearchPhrase"), lines.format("EventTime, SearchPhrase")]
    reported = {m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reported == {"latency_p50_ms", "throughput_qps", "hbm_bytes_per_row", "setup_s"}
    for reader in ("plan_build_ms_mean", "lane_queue_ms_mean", "lane_launch_ms_mean", "device_wait_ms_mean",
                   "d2h_unpack_ms_mean", "lane_busy_share", "plan_prepared_hit_share", "global_dict_build_s",
                   "tier_host_share", "selection_sort_share", "selection_rows_ms_mean", "selection_candidates_mean"):
        (m,) = [m for m in manifest["per_layer"] if m["name"] == reader]
        assert CELL in m["workloads"], reader  # by membership: a later cell appends to the same lists
    for reader in ("groupby_contraction_share", "groupby_runs_share", "group_trim_ms_mean", "hll_sort_share"):
        (m,) = [m for m in manifest["per_layer"] if m["name"] == reader]
        assert CELL not in m["workloads"], reader  # the cell launches no group-by


def test_the_generator_is_the_programs_own_unchanged():
    make = run.resolve(CONFIG["generator"])
    seg = make(1000, seed=2**31 + 50, name="seg0")
    own = run.resolve("pinot_tpu.tools.datagen:synthetic_hits_search_segment")(1000, seed=2**31 + 50, name="seg0")
    assert seg.compute_crc() == own.compute_crc() and seg.num_docs == 1000
    assert [f.name for f in run.resolve(CONFIG["schema"])().all_fields()] == [
        "SearchPhrase", "EventTime", "SearchEngineID", "UserID", "EventDate"]


# -- a whole run of the cell, at a tiny size, without the chip -------------
RUN_SEED, RUN_SEGMENTS, RUN_ROWS = 2**31 + 50, 3, 20_000


@pytest.fixture(scope="module")
def cut_manifest(tmp_path_factory) -> str:
    """The real manifest, the cell's configuration with its two sizes cut."""
    out = tmp_path_factory.mktemp("search")
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG["name"]]
    (out / "config.json").write_text(json.dumps(dict(CONFIG, segments=RUN_SEGMENTS, rows_per_segment=RUN_ROWS)))
    entry["file"] = str(out / "config.json")
    (out / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(out / "BENCHMARK.json")


def run_cell(capsys, monkeypatch, manifest: str, trace: int = 0, control: str = "") -> tuple:
    """(the result line, every reader's answer before run.py drops the
    times of a CPU run, everything printed)."""
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
        assert run.main(["--workload", CELL, "--seed", str(RUN_SEED), "--seconds", "1", "--trace", str(trace)]
                        + (["--control", control] if control else []), allow_cpu=True, manifest_path=manifest) == 0
    finally:
        gc.unfreeze()
    printed = capsys.readouterr().out.strip().splitlines()
    return json.loads(printed[-1]), read, printed


def test_a_run_of_the_cell_is_correct_and_its_readers_read(capsys, monkeypatch, cut_manifest):
    out, read, printed = run_cell(capsys, monkeypatch, cut_manifest, trace=1, control="drop_segment0")
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 3
    assert all(v["value"] == 0 for v in out["compared"].values())
    assert read["selection_candidates_mean"] == RUN_SEGMENTS * 10 and read["selection_rows_ms_mean"] > 0
    assert read["selection_sort_share"] is not None and read["tier_host_share"] == 0.0
    assert read["plan_prepared_hit_share"] == 100.0 and read["global_dict_build_s"] > 0
    assert set(out["metrics"]) == {"compiles_in_window", "selection_candidates_mean"}  # counts; no time or share
    (control,) = [line for line in printed if line.startswith("# control drop_segment0")]
    gaps = json.loads(control.split("shape: ")[1].split(" limit")[0])
    assert (gaps["by_time"], gaps["by_time_phrase"]) == (1.0, 1.0) and gaps["by_phrase"] in (0.0, 1.0)


def assert_not_correct_by(out: dict, name: str) -> None:
    assert out["correct"] is False and out["failed"] > 0
    over = {k for k, v in out["compared"].items() if v["value"] > v["limit"]}
    assert over == {name}, out["compared"]


def altered_replies(monkeypatch, alter) -> None:
    """An answer altered where the broker produces it."""
    from pinot_tpu.common.response import BrokerResponse

    sound = BrokerResponse.to_json

    def broken(self):
        out = sound(self)
        if out.get("selectionResults"):
            alter(out)
        return out

    monkeypatch.setattr(BrokerResponse, "to_json", broken)


@pytest.mark.parametrize("fault", ["a_later_row_for_an_earlier", "out_of_order", "nine_rows", "rows_missing"])
def test_an_altered_reply_comes_out_not_correct(capsys, monkeypatch, cut_manifest, fault):
    def alter(out):
        rows = out["selectionResults"]["results"]
        if fault == "a_later_row_for_an_earlier":
            rows[2] = ["яяяя no row of the table sorts this late"]
        elif fault == "out_of_order" and rows[0] != rows[-1]:
            rows[0], rows[-1] = rows[-1], rows[0]
        elif fault == "nine_rows":
            rows.pop()
        elif fault == "rows_missing":
            out["numDocsScanned"] -= 1

    altered_replies(monkeypatch, alter)
    assert_not_correct_by(run_cell(capsys, monkeypatch, cut_manifest)[0], "count_errors" if fault == "rows_missing" else "key_errors")


def test_a_reply_that_lost_a_segment_comes_out_not_correct(capsys, monkeypatch, cut_manifest):
    """The timed path broken where a selection is finalized: the first
    segment's candidates are dropped and its matching rows not counted."""
    from pinot_tpu.engine.executor import QueryExecutor

    sound = QueryExecutor._finalize

    def lossy(self, request, plan, *args, **kwargs):
        res = sound(self, request, plan, *args, **kwargs)
        if res.selection_rows:
            res.selection_rows = res.selection_rows[10:]
            res.num_docs_scanned -= 100
        return res

    monkeypatch.setattr(QueryExecutor, "_finalize", lossy)
    out = run_cell(capsys, monkeypatch, cut_manifest)[0]
    assert out["correct"] is False
    assert {k for k, v in out["compared"].items() if v["value"] > v["limit"]} == {"key_errors", "count_errors"}
