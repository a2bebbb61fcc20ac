"""ClickBench's search phrases (PR 50): the selections of ``queries.sql``
lines 25 to 27 (``SearchPhrase WHERE SearchPhrase <> '' ORDER BY EventTime
| SearchPhrase | both LIMIT 10``) and lines 13, 15 and 20 on the same
table, through an in-process cluster and its broker, against the
benchmark's plain reference (``benchmark/reference_hits_search.py``:
numpy, nothing of the program), numpy written here, and the row-by-row
scan engine (``tools/scan_engine.py``); both forms a sorted selection
takes on the device, by name; a cut inside a tie; DESC on each key; the
generator; the table dictionary of a STRING column; the shadow auditor on
ties.  The cell itself is rehearsed with the others in
``test_benchmark_rehearsal.py``."""
import collections
import importlib.util
import json
import os
import time

import numpy as np
import pytest

from pinot_tpu.common.schema import DataType
from pinot_tpu.engine import config
from pinot_tpu.engine import kernel as kernel_mod
from pinot_tpu.engine.context import TableContext
from pinot_tpu.engine.results import IntermediateResult
from pinot_tpu.pql import parse_pql
from pinot_tpu.segment.dictionary import Dictionary
from pinot_tpu.tools import datagen
from pinot_tpu.tools.cluster_harness import InProcessCluster
from pinot_tpu.tools.scan_engine import ScanQueryProcessor
from pinot_tpu.utils import audit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
# 40,000 rows a segment: the table then holds over 100,000 seconds and over 10,000 phrases,
# whose product passes the chip's packed key space (2^30), as the cell's does
SEGMENTS, ROWS, USERS, PHRASES = 3, 40_000, 50_000, 80_000
SEED = 2**31 + 50
CHIP_KEY_SPACE = 2**30  # config.max_key_space() without x64, as the chip runs


def _load(path: str):
    spec = importlib.util.spec_from_file_location("search_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref_mod = _load(os.path.join(BENCH, "reference_hits_search.py"))
CONFIG = json.load(open(os.path.join(BENCH, "configs", "clickbench_hits_search_1chip.json")))
SHAPES = {s["name"]: s for s in json.load(open(os.path.join(BENCH, "traffic", "hits_search_selection_closed.json")))["shapes"]}
NOT_EMPTY = "WHERE SearchPhrase <> ''"
LINES = {  # ClickBench's queries.sql, as PQL states them
    "13": f"SELECT COUNT(*) FROM hits {NOT_EMPTY} GROUP BY SearchPhrase TOP 10",
    "15": f"SELECT COUNT(*) FROM hits {NOT_EMPTY} GROUP BY SearchEngineID, SearchPhrase TOP 10",
    "20": "SELECT UserID FROM hits WHERE UserID = {user}",
    "25": ref_mod.render_pql("hits", SHAPES["by_time"]),
    "26": ref_mod.render_pql("hits", SHAPES["by_phrase"]),
    "27": ref_mod.render_pql("hits", SHAPES["by_time_phrase"]),
}
SHAPE_OF = {"25": "by_time", "26": "by_phrase", "27": "by_time_phrase"}
CLEAN = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}


def forget_programs():
    for cached in (kernel_mod.make_table_kernel, kernel_mod.make_packed_table_kernel,
                   kernel_mod.make_block_table_kernel, kernel_mod.make_packed_block_table_kernel):
        cached.cache_clear()


@pytest.fixture(scope="module")
def segments():
    return [datagen.synthetic_hits_search_segment(ROWS, seed=SEED * 1000 + i, name=f"seg{i}", users=USERS, phrases=PHRASES)
            for i in range(SEGMENTS)]


@pytest.fixture(scope="module")
def columns(segments):
    """Every column of the table by value, a row each, in numpy."""
    return {name: np.concatenate([np.asarray(seg.column(name).dictionary.values, dtype=object)[seg.column(name).fwd]
                                  for seg in segments]) for name in segments[0].columns}


@pytest.fixture(scope="module")
def reference(segments):
    ref = ref_mod.Reference(SHAPES)
    for seg in segments:
        ref.add(seg)
    return ref


@pytest.fixture(scope="module")
def oracle(segments):
    return ScanQueryProcessor(datagen.hits_search_schema(), [row for seg in segments for row in seg.rows()])


@pytest.fixture(scope="module")
def cluster(segments, tmp_path_factory):
    """The chip's packed key space in force (the CPU's, under x64, is
    2^62: no table of a test passes it), so that line 27's key, the
    product of the table's seconds and phrases, is as wide here as in the
    cell: wider than a packed key."""
    patch = pytest.MonkeyPatch()
    patch.setattr(config, "max_key_space", lambda: CHIP_KEY_SPACE)
    forget_programs()
    c = InProcessCluster(num_servers=1, data_dir=str(tmp_path_factory.mktemp("search")))
    try:
        physical = c.add_offline_table(datagen.hits_search_schema())
        for seg in segments:
            c.upload(physical, seg)
        yield c
    finally:
        c.stop()
        patch.undo()
        forget_programs()


def ask(cluster, pql: str) -> dict:
    reply = cluster.query(pql).to_json()
    assert not reply["exceptions"] and not reply.get("partialResponse"), reply
    assert reply["cost"].get("segmentsHost", 0) == 0, reply["cost"]
    return reply


# ---------------------------------------------------------------------------
# the six lines through the broker
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("line", sorted(LINES))
def test_a_line_of_clickbench_is_answered_from_the_device(cluster, columns, reference, oracle, line):
    pql = LINES[line]
    phrase, engine, user = columns["SearchPhrase"], columns["SearchEngineID"], columns["UserID"]
    asked = phrase != ""
    if line == "20":
        wanted = collections.Counter(user.tolist()).most_common(1)[0][0]  # a user of several rows
        pql = pql.format(user=wanted)
    reply = ask(cluster, pql)
    scan = oracle.execute(parse_pql(pql)).to_json()
    assert reply["totalDocs"] == SEGMENTS * ROWS == scan["totalDocs"]
    assert reply["numDocsScanned"] == scan["numDocsScanned"]
    if line in SHAPE_OF:
        name = SHAPE_OF[line]
        assert ref_mod.compare(reply, SHAPES[name], reference.answers[name], reference.rows) == CLEAN
        # the scan engine's own rows are a right answer too, by the same rule
        assert ref_mod.compare(dict(reply, selectionResults=scan["selectionResults"]), SHAPES[name],
                               reference.answers[name], reference.rows) == CLEAN
        assert reply["numDocsScanned"] == int(asked.sum())
    elif line == "20":
        rows = reply["selectionResults"]["results"]
        assert reply["numDocsScanned"] == int((user == wanted).sum()) > 1
        assert len(rows) == min(10, reply["numDocsScanned"]) and all(r == [str(wanted)] for r in rows)
        assert rows == scan["selectionResults"]["results"]
    else:
        keys = phrase[asked] if line == "13" else np.asarray(
            [f"{e}\x00{p}" for e, p in zip(engine[asked], phrase[asked])], dtype=object)
        counts = collections.Counter(keys.tolist())
        groups = reply["aggregationResults"][0]["groupByResult"]
        have = [("\x00".join(g["group"]), int(float(g["value"]))) for g in groups]
        assert [v for _, v in have] == sorted(counts.values(), reverse=True)[:10]  # the ten largest counts, in order
        assert all(counts[k] == v for k, v in have) and len({k for k, _ in have}) == 10  # each its own key's
        assert [int(float(g["value"])) for g in scan["aggregationResults"][0]["groupByResult"]] == [v for _, v in have]
        assert reply["numDocsScanned"] == int(asked.sum())


@pytest.mark.parametrize("name,lowering,packed", [("by_time", "topk", True), ("by_phrase", "topk", True),
                                                  ("by_time_phrase", "sort", False)])
def test_the_form_a_selection_takes_is_the_plans(cluster, columns, name, lowering, packed):
    """Both forms of ``kernel._selection_outputs``, by name, as EXPLAIN
    states them and as the launch marks them: nothing is shrunk, the
    plan says which, from the sort columns' table cardinalities."""
    pql = ref_mod.render_pql("hits", SHAPES[name])
    cards = {"EventTime": len(set(columns["EventTime"].tolist())), "SearchPhrase": len(set(columns["SearchPhrase"].tolist()))}
    node = cluster.query("EXPLAIN PLAN FOR " + pql).to_json()["explain"]["servers"][0]
    assert {s["tier"] for s in node["segments"]} == {"fullScan"}
    record = node["device"]["selection"]
    sort_columns = [col for col, _ in SHAPES[name]["order_by"]]
    space = int(np.prod([cards[c] for c in sort_columns], dtype=object))
    assert record == {"lowering": lowering, "k": 10, "sortColumns": sort_columns,
                      "sortCardinalities": [cards[c] for c in sort_columns], "keySpace": space, "packed": packed}
    assert (space <= CHIP_KEY_SPACE) is packed
    meter = cluster.servers[0].metrics.meter(f"selection.lowering.{lowering}")
    candidates, rows = cluster.servers[0].metrics.meter("selection.candidates"), cluster.servers[0].metrics.timer("phase.selectionRows")
    before = (meter.count, candidates.count, rows.count)
    ask(cluster, pql)
    assert (meter.count, candidates.count, rows.count) == (before[0] + 1, before[1] + SEGMENTS * 10, before[2] + 1)


@pytest.mark.parametrize("order_by", [[["EventTime", "desc"]], [["SearchPhrase", "desc"]],
                                      [["EventTime", "desc"], ["SearchPhrase", "asc"]],
                                      [["EventTime", "asc"], ["SearchPhrase", "desc"]],
                                      [["EventDate", "asc"]], [["EventDate", "desc"], ["SearchEngineID", "desc"]]])
def test_descending_keys_and_a_cut_inside_a_wide_tie(cluster, segments, oracle, order_by):
    """DESC on each key, and ORDER BY columns of three and of ninety
    values, where the ten rows are ten of thousands tied at the cut."""
    shape = dict(SHAPES["by_time"], order_by=order_by, select=["SearchPhrase", "EventTime"])
    ref = ref_mod.Reference({"s": shape})
    for seg in segments:
        ref.add(seg)
    pql = ref_mod.render_pql("hits", shape)
    reply = ask(cluster, pql)
    assert ref_mod.compare(reply, shape, ref.answers["s"], ref.rows) == CLEAN
    scan = oracle.execute(parse_pql(pql)).to_json()
    assert ref_mod.compare(dict(reply, selectionResults=scan["selectionResults"]), shape, ref.answers["s"], ref.rows) == CLEAN
    if order_by[0][0] == "EventDate":  # the cut falls inside a tie: of thousands of rows on the day alone
        assert len(ref.answers["s"]["keys"]) > (1000 if len(order_by) == 1 else 10)


def test_twenty_rows_share_the_tenth_key(tmp_path):
    """A table built so that the cut falls inside a tie: five seconds of
    one row each, then twenty rows of one second, over two segments.
    Every right reply passes ``compare``; a reply with a row from above
    the cut does not."""
    from pinot_tpu.segment.columnar import build_segment_from_columns

    schema = datagen.hits_search_schema()

    def segment(name, times, phrases):
        n = len(times)
        return build_segment_from_columns(schema, {
            "SearchPhrase": np.asarray(phrases, dtype=object), "EventTime": np.asarray(times, dtype=np.int64),
            "SearchEngineID": np.ones(n, dtype=np.int32), "UserID": np.arange(n, dtype=np.int64),
            "EventDate": np.full(n, 15887, dtype=np.int32)}, n, "hits", name)

    t0 = 15887 * 86_400
    a = segment("seg0", [t0 + 1, t0 + 2, t0 + 3] + [t0 + 9] * 12 + [t0 + 50] * 5 + [t0 + 7],
                ["a1", "a2", "a3"] + [f"tie{i:02d}" for i in range(12)] + [f"late{i}" for i in range(5)] + [""])
    b = segment("seg1", [t0 + 4, t0 + 5] + [t0 + 9] * 8 + [t0 + 60] * 4,
                ["b4", "b5"] + [f"tie{i:02d}" for i in range(12, 20)] + [f"later{i}" for i in range(4)])
    ref = ref_mod.Reference({"by_time": SHAPES["by_time"]})
    ref.add(a)
    ref.add(b)
    answer = ref.answers["by_time"]
    assert answer["matched"] == 34 and len(answer["keys"]) == 25 and answer["keys"][9] == (t0 + 9,)
    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path))
    try:
        physical = cluster.add_offline_table(schema)
        cluster.upload(physical, a)
        cluster.upload(physical, b)
        reply = ask(cluster, LINES["25"])
    finally:
        cluster.stop()
    assert ref_mod.compare(reply, SHAPES["by_time"], answer, ref.rows) == CLEAN
    rows = reply["selectionResults"]["results"]
    assert [r[0] for r in rows[:5]] == ["a1", "a2", "a3", "b4", "b5"] and all(r[0].startswith("tie") for r in rows[5:])
    for five in (["tie19", "tie00", "tie07", "tie12", "tie03"], ["tie15", "tie16", "tie17", "tie18", "tie19"]):
        other = dict(reply, selectionResults={"columns": ["SearchPhrase"], "results": rows[:5] + [[p] for p in five]})
        assert ref_mod.compare(other, SHAPES["by_time"], answer, ref.rows) == CLEAN  # another right choice
    wrong = dict(reply, selectionResults={"columns": ["SearchPhrase"], "results": rows[:9] + [["late0"]]})
    assert ref_mod.compare(wrong, SHAPES["by_time"], answer, ref.rows) == dict(CLEAN, key_errors=1)


# ---------------------------------------------------------------------------
# the shadow auditor on ties
# ---------------------------------------------------------------------------


def _selection_result(rows):
    res = IntermediateResult(total_docs=1000, num_docs_scanned=40)
    res.selection_rows = [([t], [p]) for t, p in rows]
    res.selection_columns = ["SearchPhrase"]
    return res


def test_the_auditor_reads_another_right_choice_among_tied_rows_as_no_divergence():
    request = parse_pql(LINES["25"])
    under = [(1, "a"), (2, "b"), (2, "c"), (3, "d")]
    tied = [(9, f"tie{i:02d}") for i in range(20)]
    every = _selection_result(under + tied + [(50, "late")] * 3)  # the host oracle keeps every matching row
    device = _selection_result(under + tied[:6])
    assert audit.payloads_equivalent(audit.canonical_payload(request, device), audit.canonical_payload(request, every))
    assert audit.results_equivalent(request, device, every)
    # another six of the twenty tied at the cut, and the rows of second 2 the other way round
    other = _selection_result([under[0], under[2], under[1], under[3]] + tied[11:17])
    assert not audit.payloads_equivalent(audit.canonical_payload(request, other), audit.canonical_payload(request, every))
    assert audit.results_equivalent(request, other, every)
    for wrong in (
        under + tied[:5] + [(50, "late")],              # a row from above the cut
        under + tied[:5] + [(9, "no such row")],        # a row the table lacks at the cut's key
        under + tied[:5] + [tied[0]],                   # a row of the tie oftener than the table holds it
        under[:3] + tied[:7],                           # a row under the cut left out
        under + tied[:5],                               # nine rows
    ):
        assert not audit.results_equivalent(request, _selection_result(wrong), every), wrong
    count = parse_pql("SELECT count(*) FROM hits")
    a, b = IntermediateResult(total_docs=10), IntermediateResult(total_docs=10)
    assert audit.results_equivalent(count, a, b)  # any other shape: the payloads, as before


def test_a_device_selection_audited_on_the_host_does_not_diverge(cluster, segments):
    """Lines 25 and 27 and an ORDER BY of three values through the
    auditor's own comparison: the device's reply against the host
    oracle's rows."""
    executor = cluster.servers[0].executor
    for pql in (LINES["25"], LINES["27"], f"SELECT SearchPhrase FROM hits {NOT_EMPTY} ORDER BY EventDate LIMIT 10"):
        request = parse_pql(pql)
        produced = executor.execute(segments, request)
        assert not produced.cost.get("segmentsHost")
        oracle = executor.execute_host_oracle(segments, request)
        assert len(oracle.selection_rows) > 10 * SEGMENTS  # every matching row, not a window
        assert audit.results_equivalent(request, produced, oracle), pql


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------


def test_a_seed_repeats_its_segment_bit_for_bit():
    a, b = (datagen.synthetic_hits_search_segment(5_000, seed=SEED, name="seg3", users=USERS, phrases=PHRASES) for _ in range(2))
    assert a.compute_crc() == b.compute_crc()
    for name in a.columns:
        assert np.array_equal(a.column(name).fwd, b.column(name).fwd)
        assert list(a.column(name).dictionary.values) == list(b.column(name).dictionary.values)
    other = datagen.synthetic_hits_search_segment(5_000, seed=SEED + 1, name="seg3", users=USERS, phrases=PHRASES)
    assert other.compute_crc() != a.compute_crc()
    # the users' table's UserID, row for row, for a seed
    users = datagen.synthetic_hits_users_segment(5_000, seed=SEED, name="seg3", users=USERS)
    assert np.array_equal(np.asarray(users.column("UserID").dictionary.values)[users.column("UserID").fwd],
                          np.asarray(a.column("UserID").dictionary.values)[a.column("UserID").fwd])


def test_the_shares_are_the_configurations(segments, columns):
    assumed = " ".join(CONFIG["assumed"])
    phrase, when, day, engine = (columns[c] for c in ("SearchPhrase", "EventTime", "EventDate", "SearchEngineID"))
    empty = float((phrase == "").mean())
    assert f"{datagen.HITS_PHRASE_EMPTY_SHARE:.1%}" in assumed and abs(empty - datagen.HITS_PHRASE_EMPTY_SHARE) < 0.01
    # the count of distinct phrases is the law's (hits_expected_distinct): here, at 15,700 draws, within
    # three of its own deviations (some 0.6% each); at the cell's size the configuration's numbers within 1%
    asked = int((phrase != "").sum())
    distinct = len(set(phrase[phrase != ""].tolist()))
    assert abs(distinct / datagen.hits_expected_distinct(asked, PHRASES, datagen.HITS_PHRASE_EXPONENT) - 1) < 0.02
    a_segment = segments[0].column("SearchPhrase").dictionary.cardinality - 1
    assert abs(a_segment / datagen.hits_expected_distinct(asked // SEGMENTS, PHRASES, datagen.HITS_PHRASE_EXPONENT) - 1) < 0.03
    # the configuration's own numbers are the law's at its size (the table of 22.7M ranks, once)
    table_rows = round(CONFIG["segments"] * CONFIG["rows_per_segment"] * (1 - datagen.HITS_PHRASE_EMPTY_SHARE))
    for rows, stated in ((table_rows, 6_019_103), (table_rows // CONFIG["segments"], 783_626)):
        law = datagen.hits_expected_distinct(rows, datagen.HITS_PHRASES, datagen.HITS_PHRASE_EXPONENT)
        assert abs(law / stated - 1) < 0.01 and f"{stated:,}" in assumed
    assert "exponent 0.8" in assumed and f"{datagen.HITS_PHRASES:,}" in assumed
    # a second of the row's own day, not sorted inside it; the engine 0 exactly where the phrase is empty
    assert np.array_equal(when // 86_400, day) and np.any(np.diff(when[: ROWS // 4].astype(np.int64)) < 0)
    assert np.array_equal(engine == 0, phrase == "") and 1 <= engine[phrase != ""].min() and engine.max() <= 90
    for seg in segments:
        values = seg.column("SearchPhrase").dictionary.values
        assert values == sorted(values) and len(set(values)) == len(values) and values[0] == ""
        sizes = [len(v.encode("utf-8")) for v in values[1:]]
        assert 3 <= min(sizes) and max(sizes) <= 59 and all(2 <= len(v.split(" ")) <= 6 for v in values[1:])
        assert seg.column("EventDate").metadata.is_sorted and not seg.column("EventTime").metadata.is_sorted


def test_a_phrase_is_its_ranks_in_every_segment(segments):
    ranks = np.arange(0, 200_000, 7)
    spelled = datagen.hits_phrase_bytes(ranks)
    assert np.array_equal(spelled, datagen.hits_phrase_bytes(ranks))  # a fixed function of the rank
    assert np.unique(spelled.view("S60").ravel()).size == ranks.size  # no two ranks share a phrase
    assert np.unique(datagen.hits_phrase_bytes(np.arange(2**25 - 50_000, 2**25)).view("S60").ravel()).size == 50_000
    shared = set(segments[0].column("SearchPhrase").dictionary.values) & set(segments[1].column("SearchPhrase").dictionary.values)
    assert len(shared) > 100  # the popular ranks are in both, as the same strings
    every = {bytes(row).rstrip(b"\x00").decode("utf-8") for row in datagen.hits_phrase_bytes(np.arange(PHRASES))}
    for seg in segments:
        assert set(seg.column("SearchPhrase").dictionary.values[1:]) <= every
    # popularity says nothing of the place in the dictionary: the 100 heaviest ranks are spread over it
    top = sorted(bytes(row).rstrip(b"\x00") for row in datagen.hits_phrase_bytes(np.arange(100)))
    assert top[0][:1] != top[-1][:1]
    with pytest.raises(ValueError, match="25 bits"):
        datagen.synthetic_hits_search_segment(10, phrases=2**25 + 1)


# ---------------------------------------------------------------------------
# the table dictionary of a STRING column
# ---------------------------------------------------------------------------


def test_the_table_dictionary_of_600_000_strings_is_the_union_by_value():
    """Three segments of 200,000 phrases each, a third of them shared:
    the table dictionary is ``np.unique`` of their union and each remap
    sends a segment's id to its value's place in it."""
    class _Column:
        def __init__(self, dictionary):
            self.dictionary = dictionary

    class _Segment:
        def __init__(self, i, values):
            self.segment_name, self._column = f"seg{i}", _Column(Dictionary(DataType.STRING, values))

        def column(self, name):
            return self._column

    per_segment = 200_000
    pools = []
    for i in range(3):
        ranks = np.concatenate([np.arange(per_segment // 3), (i + 1) * 1_000_000 + np.arange(per_segment - per_segment // 3)])
        spelled = datagen.hits_phrase_bytes(ranks).view("S60").ravel()
        pools.append([""] + datagen._phrase_strings(np.sort(spelled).view(np.uint8).reshape(-1, 60)))
    t0 = time.perf_counter()
    column = TableContext([_Segment(i, values) for i, values in enumerate(pools)]).column("SearchPhrase")
    took = time.perf_counter() - t0
    union = np.unique(np.concatenate([np.asarray(values, dtype=np.str_) for values in pools]))
    assert column.global_cardinality == union.size == 1 + 3 * per_segment - 2 * (per_segment // 3)
    assert np.array_equal(np.asarray(column.global_dict.values, dtype=np.str_), union)
    for values, remap in zip(pools, column.remaps):
        assert remap.dtype == np.int32 and np.array_equal(union[remap], np.asarray(values, dtype=np.str_))
    assert took < 60.0, took  # seconds here; the cell's 12 x 783,000 are timed on the chip's host (global_dict_build_s)
