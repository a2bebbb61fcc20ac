"""The benchmark's own tests: its arithmetic, its reference, and a
rehearsal of a whole run at a tiny size on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

A run here goes through ``run.main(..., allow_cpu=True)``, which skips
the look for a chip and drives the rest; it prints counts and the
device's name and no time under a metric's name.  The command itself
refuses to run without a TPU (``test_command_refuses_without_a_tpu``).
"""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import loadgen  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

SUM_RTOL = json.load(open(os.path.join(BENCH, "configs", "tpch_lineitem_1chip.json")))["guarantees"]["sum_rtol"]
TRAFFIC = {n: json.load(open(os.path.join(BENCH, "traffic", n + ".json"))) for n in ("suite_open", "groupby_closed")}


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory) -> str:
    """The real manifest with every cell moved onto the tiny configuration."""
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    manifest["configs"] = [{"name": "tiny", "source": "tests only", "file": "benchmark/tests/tiny_config.json",
                            "reduced": ["segments", "rows_per_segment"], "why": "a rehearsal on the CPU"}]
    for w in manifest["workloads"]:
        w["config"] = "tiny"
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# -- the schedule and the percentile -------------------------------------


def test_schedule_repeats_and_holds_exact_shares():
    t = TRAFFIC["suite_open"]
    a, b = loadgen.open_schedule(t, 20.0), loadgen.open_schedule(t, 20.0)
    assert a == b and len(a) > 0
    block = sum(s["share"] for s in t["shapes"])
    assert len(a) % block == 0
    for i in range(0, len(a), block):
        names = sorted(n for n, _ in a[i : i + block])
        assert names == sorted(s["name"] for s in t["shapes"] for _ in range(s["share"]))
    dues = [d for _, d in a]
    assert dues == sorted(dues) and dues[-1] <= 20.0
    # a shorter window is a prefix: the rehearsal offers the window's first queries
    short = loadgen.open_schedule(t, 5.0)
    assert short == a[: len(short)]
    # the rate is the file's: mean gap within 10% over some thousand arrivals
    long = loadgen.open_schedule(t, 100.0)
    assert abs(len(long) / long[-1][1] / t["rate_qps"] - 1.0) < 0.1
    # another schedule_seed is another schedule
    assert loadgen.open_schedule(dict(t, schedule_seed=t["schedule_seed"] + 1), 20.0) != a


@pytest.mark.parametrize("p", [0, 50, 95, 99, 100])
def test_percentile_is_numpys(p):
    rng = np.random.default_rng(p)
    for n in (1, 2, 19, 400):
        v = rng.exponential(size=n).tolist()
        assert loadgen.percentile(v, p) == pytest.approx(np.percentile(v, p), rel=1e-12)


# -- the reference -------------------------------------------------------


# upstream Q2 is in no cell yet (the host's postings tier answers it; PERF.md, Open questions)
Q2 = {"name": "q2", "share": 1, "filter": [["l_shipdate", "between", ["1996-12-01", "1996-12-31"]]],
      "aggs": [["sum", "l_extendedprice"]]}


def all_shapes() -> dict:
    return dict({s["name"]: s for t in TRAFFIC.values() for s in t["shapes"]}, q2=Q2)


def tiny_segments(seed: int, rows: int = 3000):
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    return [synthetic_lineitem_segment(rows, seed=seed * 1000 + i, name=f"seg{i}") for i in range(2)]


def test_reference_agrees_with_the_scan_engine_on_q0_to_q6():
    """Two plain implementations of the same semantics: numpy over the
    dictionaries here, the repo's row-at-a-time oracle there."""
    from pinot_tpu.pql.parser import parse_pql
    from pinot_tpu.tools.datagen import lineitem_schema
    from pinot_tpu.tools.scan_engine import ScanQueryProcessor

    shapes = all_shapes()
    assert set(shapes) == {"q0", "q1", "q2", "q3", "q4", "q5", "q6", "k6"}
    segments = tiny_segments(7)
    ref = reference.Reference(shapes)
    for seg in segments:
        ref.add(seg)
    oracle = ScanQueryProcessor(lineitem_schema(), [r for seg in segments for r in seg.rows()])
    for name, shape in shapes.items():
        reply = oracle.execute(parse_pql(reference.render_pql("lineitem", shape))).to_json()
        got = reference.compare(reply, shape, ref.answers[name], ref.rows)
        assert got["count_errors"] == got["key_errors"] == got["reply_errors"] == 0, (name, got)
        assert got["sum_gap"] < 1e-9, (name, got)  # both sum in float64


def test_compare_catches_each_kind_of_fault():
    shapes = all_shapes()
    ref = reference.Reference(shapes)
    for seg in tiny_segments(3):
        ref.add(seg)
    shape, answer = shapes["k6"], ref.answers["k6"]

    def reply(**changes):
        aggs = []
        for i, (fn, col) in enumerate(shape["aggs"]):
            aggs.append({"function": f"{fn}_{col}", "groupByResult": [
                {"group": list(k), "value": str(v[i])} for k, v in answer["groups"].items()]})
        out = {"aggregationResults": aggs, "exceptions": [], "partialResponse": False,
               "numDocsScanned": answer["matched"], "totalDocs": ref.rows, "numSegmentsUnserved": 0,
               "numServersQueried": 1, "numServersResponded": 1, "cost": {"segmentsFullScan": 2}}
        out.update(changes)
        return out

    clean = reference.compare(reply(), shape, answer, ref.rows)
    assert clean == {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}
    assert reference.compare(reply(numDocsScanned=answer["matched"] - 1), shape, answer, ref.rows)["count_errors"] == 1
    assert reference.compare(reply(partialResponse=True), shape, answer, ref.rows)["reply_errors"] == 1
    assert reference.compare(reply(cost={"segmentsFullScan": 1, "segmentsHost": 1}), shape, answer, ref.rows)["reply_errors"] == 1
    assert reference.compare(reply(numSegmentsUnserved=1), shape, answer, ref.rows)["reply_errors"] == 1
    assert reference.compare(reply(numServersResponded=0), shape, answer, ref.rows)["reply_errors"] == 1
    assert reference.compare(reply(exceptions=[{"errorCode": 1}]), shape, answer, ref.rows)["reply_errors"] == 1
    assert reference.compare(reply(cost={"segmentsPostings": 2}), shape, answer, ref.rows)["reply_errors"] == 0
    off = reply()
    g = off["aggregationResults"][1]["groupByResult"][0]
    g["value"] = str(float(g["value"]) * (1 + 3e-4))
    assert reference.compare(off, shape, answer, ref.rows)["sum_gap"] == pytest.approx(3e-4, rel=1e-3)
    off = reply()
    g = off["aggregationResults"][3]["groupByResult"][0]
    g["value"] = str(int(g["value"]) + 1)
    assert reference.compare(off, shape, answer, ref.rows)["count_errors"] == 1
    off = reply()
    off["aggregationResults"][0]["groupByResult"].pop()
    assert reference.compare(off, shape, answer, ref.rows)["key_errors"] == 1


def test_top_n_may_not_return_a_worse_group_for_a_better_one():
    shapes = all_shapes()
    ref = reference.Reference(shapes)
    for seg in tiny_segments(5):
        ref.add(seg)
    shape, answer = shapes["q3"], ref.answers["q3"]
    ranked = sorted(answer["groups"].items(), key=lambda kv: -kv[1][0])

    def reply(groups):
        return {"aggregationResults": [{"function": "sum", "groupByResult": [
            {"group": list(k), "value": str(v[0])} for k, v in groups]}], "exceptions": [],
            "partialResponse": False, "numDocsScanned": answer["matched"], "totalDocs": ref.rows,
            "numServersQueried": 1, "numServersResponded": 1, "cost": {"segmentsFullScan": 2}}

    assert reference.compare(reply(ranked[:10]), shape, answer, ref.rows)["sum_gap"] == 0.0
    swapped = ranked[:9] + [ranked[200]]
    assert reference.compare(reply(swapped), shape, answer, ref.rows)["sum_gap"] > SUM_RTOL


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_fails_the_limit(traffic, seed):
    """The control at a size a test can hold (the chip readings at the
    cells' own size are in PERF.md): the reference computed in bfloat16
    shows a gap above ``sum_rtol`` in every cell, and the float64
    reference against itself none."""
    shapes = {s["name"]: s for s in TRAFFIC[traffic]["shapes"]}
    ref, control = reference.Reference(shapes), reference.Reference(shapes, control="bfloat16")
    for seg in tiny_segments(seed, rows=20000):
        ref.add(seg)
        control.add(seg)
    gaps = reference.control_gaps(ref, control)
    assert max(gaps.values()) > 3 * SUM_RTOL, gaps
    assert max(reference.control_gaps(ref, ref).values()) == 0.0


def test_shape_bytes_counts_ids_at_their_narrowest_width():
    shapes = all_shapes()
    ref = reference.Reference(shapes)
    for seg in tiny_segments(1):
        ref.add(seg)
    assert ref.sorted_columns == {"l_shipdate"}
    # q0: price (16,384 values, 2 B) + discount (11 values, 1 B), every row
    assert ref.shape_bytes("q0") == ref.rows * 3
    # q2 filters the sorted column alone: only the matched rows' prices
    assert ref.shape_bytes("q2") == ref.answers["q2"]["matched"] * 2
    # q6: shipmode (1) + receiptdate (2) + price (2)
    assert ref.shape_bytes("q6") == ref.rows * 5


# -- the trace reduction -------------------------------------------------


MS = 1e6
HAND_MADE = {
    "devices": {
        "/device:TPU:0": [("jit_a/fusion", 10 * MS, 5 * MS), ("jit_a/copy", 15 * MS, 5 * MS),
                          ("jit_b/fusion", 60 * MS, 10 * MS), ("jit_b/late", 95 * MS, 20 * MS)],
        "/device:TPU:1": [("jit_a/fusion", 10 * MS, 10 * MS)],
    },
    "client": [("q0", 5 * MS, 25 * MS), ("q1", 55 * MS, 20 * MS), ("q2", 90 * MS, 30 * MS)],
}
# the program's annotations around q0 and q1, nested as the program nests them, on three threads
PINOT_SPANS = [("httpTotal", 6 * MS, 23 * MS), ("scatterGather", 7 * MS, 20 * MS),  # the HTTP thread
               ("planBuild", 7.5 * MS, 1.5 * MS), ("laneWait", 9 * MS, 17 * MS),  # the worker, inside scatterGather
               ("deviceWait", 9.5 * MS, 11 * MS), ("d2hUnpack", 20.5 * MS, 2 * MS),
               ("render", 27.5 * MS, 1 * MS),
               ("httpTotal", 56 * MS, 18 * MS), ("deviceWait", 59 * MS, 12 * MS), ("render", 72 * MS, 1.5 * MS)]


def test_reduce_on_a_hand_made_trace():
    ms = MS
    r = trace_reduce.reduce(HAND_MADE, (0.0, 100 * ms))
    # device 0 is busy 10-20, 60-70, 95-100 (clipped); device 1 10-20
    assert r["busy_s"] == pytest.approx((25 + 10) / 2 / 1e3)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["queries"] == 2 and r["queries_by_shape"] == {"q0": 1, "q1": 1}  # q2 ends outside
    assert r["device_ops"][0] == ["jit_a/fusion", pytest.approx(0.0075)]
    gaps = dict(r["idle_gaps"])
    # device 0 is idle 0-10, 20-60, 70-95: nobody's 0-5, 30-55, 75-90; q0's 5-10, 20-30; q1's 55-60, 70-75; q2's 90-95.
    # A trace with no annotation of the program in it: the query is known, the host's cause is not
    assert gaps == {"no_query_in_flight": pytest.approx(0.045),
                    "query_in_flight:q0:host_cause_not_attributed": pytest.approx(0.015),
                    "query_in_flight:q1:host_cause_not_attributed": pytest.approx(0.010),
                    "query_in_flight:q2:host_cause_not_attributed": pytest.approx(0.005)}
    assert r["idle_by_span"] == {"host_cause_not_attributed": pytest.approx(0.030)}
    assert sum(gaps.values()) + 0.025 == pytest.approx(0.1)


def test_idle_goes_to_the_innermost_span_open_on_the_host():
    ms = MS
    loaded = dict(HAND_MADE, spans=PINOT_SPANS)
    # a window that ends with q0's reply: device 0 is idle 0-10 and 20-30, q0 is in flight from 5
    r = trace_reduce.reduce(loaded, (0.0, 30 * ms))
    gaps = dict(r["idle_gaps"])
    # 5-10: no span of the program before the handler's entry at 6, httpTotal 6-7, scatterGather 7-7.5,
    # planBuild 7.5-9, laneWait 9-9.5, deviceWait 9.5-10
    want = {"host_cause_not_attributed": 1.0 + 1.0, "planBuild": 1.5,
            # 20-30: deviceWait to 20.5, d2hUnpack to 22.5, laneWait to 26, scatterGather to 27, httpTotal to 29 but
            # for render 27.5-28.5, and nothing after it
            "deviceWait": 0.5 + 0.5, "d2hUnpack": 2.0, "laneWait": 0.5 + 3.5, "scatterGather": 0.5 + 1.0,
            "httpTotal": 1.0 + 0.5 + 0.5, "render": 1.0}
    assert gaps == dict({f"query_in_flight:q0:{k}": pytest.approx(v / 1e3) for k, v in want.items()},
                        no_query_in_flight=pytest.approx(0.005))
    assert r["idle_by_span"] == {k: pytest.approx(v / 1e3) for k, v in want.items()}
    assert list(r["idle_by_span"])[0] == "laneWait"  # largest first
    # the whole trace: q1's 55-60 and 70-75 lie under its httpTotal, deviceWait and render; q2 has no span under it
    r = trace_reduce.reduce(loaded, (0.0, 100 * ms), top=20)
    gaps = dict(r["idle_gaps"])
    assert gaps["no_query_in_flight"] == pytest.approx(0.045)  # a span open where no query is in flight changes nothing
    assert gaps["query_in_flight:q0:planBuild"] == pytest.approx(0.0015)
    assert gaps["query_in_flight:q1:deviceWait"] == pytest.approx(0.002)
    assert gaps["query_in_flight:q1:httpTotal"] == pytest.approx(0.0045)
    assert gaps["query_in_flight:q1:render"] == pytest.approx(0.0015)
    assert gaps["query_in_flight:q1:host_cause_not_attributed"] == pytest.approx(0.002)
    assert gaps["query_in_flight:q2:host_cause_not_attributed"] == pytest.approx(0.005)
    assert sum(gaps.values()) + 0.025 == pytest.approx(0.1)


def test_two_queries_in_flight_share_the_span_that_opened_last():
    """The limit of the pairing, as the module's text states it: shape and
    span each come from the span that opened last, so the pair can name
    one query's shape beside the other's span; the sums by span hold."""
    ms = MS
    loaded = {"devices": {"/device:TPU:0": [("jit_a/fusion", 0.0, 1 * ms), ("jit_a/fusion", 21 * ms, 1 * ms)]},
              "client": [("k6", 1 * ms, 19 * ms), ("q0", 5 * ms, 10 * ms)],  # q0 is sent while k6 waits, and ends first
              "spans": [("httpTotal", 2 * ms, 17 * ms), ("deviceWait", 3 * ms, 15 * ms),  # k6's
                        ("httpTotal", 6 * ms, 8 * ms), ("render", 12 * ms, 1 * ms)]}  # q0's
    r = trace_reduce.reduce(loaded, (0.0, 22 * ms))
    gaps = dict(r["idle_gaps"])
    assert gaps == {
        "no_query_in_flight": pytest.approx(0.001),  # 20-21
        "query_in_flight:k6:host_cause_not_attributed": pytest.approx(0.002),  # 1-2 and 19-20
        "query_in_flight:k6:httpTotal": pytest.approx(0.002),  # 2-3 and 18-19
        "query_in_flight:k6:deviceWait": pytest.approx(0.002 + 0.003),  # 3-5, and 15-18 once q0 has its reply
        "query_in_flight:q0:deviceWait": pytest.approx(0.001 + 0.001),  # 5-6 and 14-15: q0's shape beside k6's span
        "query_in_flight:q0:httpTotal": pytest.approx(0.006 + 0.001),  # 6-12, 13-14
        "query_in_flight:q0:render": pytest.approx(0.001)}
    assert r["idle_by_span"] == {"httpTotal": pytest.approx(0.009), "deviceWait": pytest.approx(0.007),
                                 "host_cause_not_attributed": pytest.approx(0.002), "render": pytest.approx(0.001)}
    # ten operations are listed, where there are more
    many = {"devices": {"/device:TPU:0": [(f"jit_a/op{i}", i * ms, (i + 1) * ms / 100) for i in range(30)]}, "client": []}
    r = trace_reduce.reduce(many, (0.0, 40 * ms))
    assert len(r["device_ops"]) == 10 and r["device_ops"][0][0] == "jit_a/op29"


def test_pieces_cover_an_interval_by_segment_and_by_nothing():
    seg = [[2.0, 4.0, "a"], [4.0, 5.0, "b"], [8.0, 12.0, "c"]]
    assert trace_reduce.pieces(seg, 3.0, 10.0) == [(3.0, 4.0, "a"), (4.0, 5.0, "b"), (5.0, 8.0, None), (8.0, 10.0, "c")]
    assert trace_reduce.pieces(seg, 0.0, 1.0) == [(0.0, 1.0, None)]
    assert trace_reduce.pieces(seg, 13.0, 14.0) == [(13.0, 14.0, None)]
    assert trace_reduce.pieces([], 0.0, 1.0) == [(0.0, 1.0, None)]
    assert trace_reduce.pieces(seg, 5.0, 5.0) == []


def test_innermost_segments_take_the_span_that_opened_last():
    seg = trace_reduce.innermost_segments([("a", 0.0, 10.0), ("b", 2.0, 3.0), ("c", 4.0, 8.0), ("d", 20.0, 1.0)])
    # c opens inside b and outlives it; a shows again when c ends; nothing is open from 12 to 20
    assert seg == [[0.0, 2.0, "a"], [2.0, 4.0, "b"], [4.0, 12.0, "c"], [20.0, 21.0, "d"]]
    assert trace_reduce.innermost_segments([]) == []


def test_load_reads_a_recorded_trace():
    """A few events cut from a trace of the open cell on the v5e
    (``recorded_trace.txt``, the profiler's own text form)."""
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "recorded_trace.txt")) as f:
        data = ProfileData.from_text_proto(f.read())
    loaded = trace_reduce.load(data)
    assert sorted(loaded["devices"]) == ["/device:TPU:0"]
    assert len(loaded["client"]) >= 1
    spans = loaded["client"]
    r = trace_reduce.reduce(loaded, (min(s[1] for s in spans), max(s[1] + s[2] for s in spans)))
    assert 0 < r["busy_s"] < r["window_s"]
    assert all(name.startswith("jit_") for name, _ in r["device_ops"])
    assert sorted(name for name, _, _ in loaded["spans"]) == ["deviceWait", "httpTotal"]
    # before the first kernel the device idles under k6's httpTotal, then 0.444 ms under its deviceWait,
    # which also covers the 0.081 ms between the kernel's last operation and the wait's end
    assert r["idle_by_span"]["deviceWait"] == pytest.approx(0.5243e-3, rel=1e-3)
    assert r["idle_by_span"]["httpTotal"] == pytest.approx(7.0e-3 + 4.9e-3)  # 96-103 and 117.1-122 ms
    assert r["idle_by_span"]["host_cause_not_attributed"] > 0  # q0's query has no annotation under it


# -- a whole run, at a tiny size, without the chip -------------------------


@pytest.mark.parametrize("workload", ["lineitem_suite_open", "lineitem_groupby_closed"])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_counts_and_no_time(capsys, tiny_manifest, workload, trace):
    assert run.main(["--workload", workload, "--seed", str(2**31 + 5), "--seconds", "2", "--trace", str(trace)],
                    allow_cpu=True, manifest_path=tiny_manifest) == 0
    printed, err = capsys.readouterr()
    out = json.loads(printed.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert all(m["unit"] in ("count", "B/row") for m in out["metrics"].values())
    # each number compared beside its limit: the line's last key, and the last lines of standard error
    assert list(out)[-1] == "compared" and set(out["compared"]) == {"sum_gap", "count_errors", "key_errors", "reply_errors"}
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())
    assert err.strip().splitlines()[-1] == "compared reply_errors: 0 limit 0"
    assert "# window by shape: " in printed
    if trace:
        assert out["metrics"]["compiles_in_window"]["value"] == 0
        assert all(len(v) <= 10 for v in out["breakdown"].values())
    else:
        assert out["metrics"]["hbm_bytes_per_row"]["value"] > 0


@pytest.mark.parametrize("fault", ["count", "sum", "dropped_rows"])
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch, tiny_manifest, fault):
    """The rest of a run with the timed path broken underneath: an answer
    altered where the broker produces it."""
    from pinot_tpu.common.response import BrokerResponse

    sound = BrokerResponse.to_json

    def broken(self):
        out = sound(self)
        if fault == "count":
            out["numDocsScanned"] += 1
        elif fault == "sum":
            for res in out["aggregationResults"]:
                for g in res.get("groupByResult", [res]):
                    g["value"] = str(float(g["value"]) * (1 + 1e-3))
        else:  # a part of the table left out of every sum and count
            for res in out["aggregationResults"]:
                for g in res.get("groupByResult", [res]):
                    g["value"] = str(float(g["value"]) * 15 / 16)
        return out

    monkeypatch.setattr(BrokerResponse, "to_json", broken)
    assert run.main(["--workload", "lineitem_suite_open", "--seed", "11", "--seconds", "2", "--trace", "0"],
                    allow_cpu=True, manifest_path=tiny_manifest) == 0
    out = last_line(capsys)
    assert out["correct"] is False and out["failed"] == out["attempted"] > 0


def test_new_config_traffic_and_metric_are_new_files_plus_one_entry(tmp_path, capsys, tiny_manifest):
    """What a later PR does: it adds a configuration file, a traffic file
    and a reader, and one entry each to the manifest, and edits no file
    that is there."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}
    manifest = json.load(open(tiny_manifest))
    cfg = json.load(open(os.path.join(HERE, "tiny_config.json")))
    (root / "benchmark" / "configs" / "tiny3.json").write_text(json.dumps(dict(cfg, name="tiny3", segments=3)))
    (root / "benchmark" / "traffic" / "only_q6.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2, "schedule_seed": 1, "rehearse_s": 0.5,
         "shapes": [s for s in TRAFFIC["suite_open"]["shapes"] if s["name"] == "q6"]}))
    (root / "benchmark" / "layer_metrics" / "lane_dispatches.py").write_text(
        "def read(run):\n    return run.delta('server.meter.lane.dispatches')\n")
    manifest["configs"].append({"name": "tiny3", "source": "tests", "file": "benchmark/configs/tiny3.json",
                                "reduced": [], "why": "a new file"})
    manifest["workloads"].append({"name": "new_cell", "config": "tiny3", "traffic": "only_q6", "chips": 1,
                                  "why": "a new entry"})
    manifest["per_layer"].append({"name": "lane_dispatches", "unit": "count", "better": "lower",
                                  "source": "program_counter", "layer": "lane: trace, compile, launch",
                                  "moves": "latency_p50_ms", "workloads": ["new_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    copy = run.load_module(str(root / "benchmark" / "run.py"))
    assert copy.main(["--workload", "new_cell", "--seed", "4", "--seconds", "1", "--trace", "1"],
                     allow_cpu=True) == 0
    out = last_line(capsys)
    assert out["correct"] is True
    assert out["metrics"]["lane_dispatches"]["value"] > 0
    assert {p: p.read_bytes() for p in before} == before


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "lineitem_suite_open",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "need 1 tpu chip" in done.stderr


def test_manifest_names_files_that_exist():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in manifest["configs"]:
        assert json.load(open(os.path.join(ROOT, c["file"])))["source"] == c["source"]
        assert json.load(open(os.path.join(ROOT, c["file"])))["reduced"] == c["reduced"]
    for w in manifest["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    for kind, folder in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in manifest[kind]:
            assert os.path.exists(os.path.join(BENCH, folder, m["name"] + ".py")), m["name"]
    moved = {m["name"] for m in manifest["end_to_end"]}
    assert all(m["moves"] in moved for m in manifest["per_layer"])


def _gone(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except OSError:
        return True


def test_kept_awake_spins_beside_the_run_and_leaves_no_process(monkeypatch):
    started = []
    popen = subprocess.Popen
    monkeypatch.setattr(run.subprocess, "Popen", lambda *a, **k: started.append(popen(*a, **k)) or started[-1])
    with run.kept_awake(2):
        assert len(started) == 2 and all(p.poll() is None for p in started)
    assert all(p.returncode is not None for p in started)
    with run.kept_awake(0):
        assert len(started) == 2
    # a spinner whose parent dies before it could stop it ends by itself
    monkeypatch.undo()
    orphaner = ("import os, subprocess, sys\n"
                f"print(subprocess.Popen([sys.executable, '-I', '-S', '-c', {run.SPINNER!r}, str(os.getpid())],\n"
                "                       stdout=subprocess.DEVNULL).pid)\n")
    pid = int(subprocess.run([sys.executable, "-c", orphaner], capture_output=True, text=True, check=True,
                             timeout=60).stdout)
    deadline = time.time() + 10
    while not _gone(pid) and time.time() < deadline:
        time.sleep(0.05)
    assert _gone(pid)


def test_by_shape_counts_the_replies_over_three_times_their_shapes_median():
    samples = [{"shape": "q3", "latency_ms": x} for x in (30.0, 31.0, 32.0, 33.0, 150.0)]
    samples += [{"shape": "q4", "latency_ms": x} for x in (40.0, 44.0)]
    assert run.by_shape(samples, loadgen.percentile) == {
        "q3": {"n": 5, "p50_ms": 32.0, "over_3x_p50": 1}, "q4": {"n": 2, "p50_ms": 42.0, "over_3x_p50": 0}}
