"""Value pruning of segments (PR 48): a segment whose dictionaries a
filter empties is left out of the work, and the device program runs over
the live segments of the table that is already staged.

(i) the verdict (``pruner.value_dead``) against brute force, exhaustive
over small random segments; (ii) every device lowering over 16 small
date-range segments answers a filter that leaves 4, 3 (an empty slot, a
dead segment between, three that lie apart: the whole launch), 2 (a gap)
and 1 of them bit for bit as the whole launch and as
``execute_host_oracle``, with the accounting the cost vector states, also
where the window is over the row budget and launches in chunks, under a
mesh (the whole launch) and in the bit-sliced tier (a whole pass);
(iii) one staged table and one table context whatever the segment set;
(iv) two launches of one plan over different sets are not coalesced.
The SSB queries' own case is in ``test_ssb_flat.py``, EXPLAIN's in
``test_explain.py``."""
import itertools

import numpy as np
import pytest

from pinot_tpu.common.request import FilterOperator, FilterQueryTree, RangeSpec
from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.engine import config, device, ladder
from pinot_tpu.engine import context as context_mod
from pinot_tpu.engine import kernel as kernel_mod
from pinot_tpu.engine import pruner
from pinot_tpu.engine.dispatch import DeviceLane
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.engine.host_fallback import _segment_mask
from pinot_tpu.pql import parse_pql
from pinot_tpu.segment.columnar import build_segment_from_columns
from pinot_tpu.utils.audit import canonical_payload
from pinot_tpu.utils.metrics import ServerMetrics

SEGMENTS, ROWS, DAYS = 16, 2048, 100  # a segment is DAYS days of the table's 1,600


def forget_programs():
    for cached in (kernel_mod.make_table_kernel, kernel_mod.make_packed_table_kernel,
                   kernel_mod.make_block_table_kernel, kernel_mod.make_packed_block_table_kernel):
        cached.cache_clear()


# ---------------------------------------------------------------------------
# (i) the verdict against brute force
# ---------------------------------------------------------------------------

TINY = Schema(
    "tiny",
    dimensions=[FieldSpec("i", DataType.INT), FieldSpec("s", DataType.STRING),
                FieldSpec("tags", DataType.INT, single_value=False)],
    metrics=[FieldSpec("m", DataType.INT, FieldType.METRIC)],
)


def tiny_segment(seed: int):
    """A few rows over a few values with gaps: ints of 10..30 in steps
    that leave holes, strings 'b'..'h', so that a literal falls outside,
    between and at the ends of a dictionary."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(3, 9))
    ints = rng.choice(np.arange(10, 31, 2), size=n).astype(np.int32)
    strs = rng.choice(np.array(list("bdfh"), dtype=object), size=n)
    counts = rng.integers(1, 3, size=n)
    tags = rng.choice(np.arange(10, 31, 2), size=int(counts.sum())).astype(np.int32)
    columns = {"i": ints, "s": strs, "tags": (tags, np.concatenate([[0], np.cumsum(counts)])), "m": np.ones(n, dtype=np.int32)}
    return build_segment_from_columns(TINY, columns, n, "tiny", f"tiny{seed}")


def leaf(column, op, values=(), rng_spec=None):
    return FilterQueryTree(operator=op, column=column, values=[str(v) for v in values], range_spec=rng_spec)


def _leaves():
    ints = (8, 10, 11, 12, 20, 29, 30, 31, 40)  # outside, at the ends, between, inside
    strs = ("a", "b", "c", "d", "h", "i")
    out = {}
    for column, literals in (("i", ints), ("s", strs), ("tags", ints)):
        for v in literals:
            out[f"{column}={v}"] = leaf(column, FilterOperator.EQUALITY, [v])
            out[f"{column}!={v}"] = leaf(column, FilterOperator.NOT, [v])
        for a, b in itertools.combinations(literals[::2], 2):
            out[f"{column} in({a},{b})"] = leaf(column, FilterOperator.IN, [a, b])
            out[f"{column} not in({a},{b})"] = leaf(column, FilterOperator.NOT_IN, [a, b])
        for lo, hi in itertools.combinations(literals, 2):
            for inc_lo, inc_hi in ((True, True), (False, False), (True, False)):
                out[f"{column} {'[' if inc_lo else '('}{lo},{hi}{']' if inc_hi else ')'}"] = leaf(
                    column, FilterOperator.RANGE, rng_spec=RangeSpec(str(lo), str(hi), inc_lo, inc_hi))
        for bound in literals:
            out[f"{column}>{bound}"] = leaf(column, FilterOperator.RANGE, rng_spec=RangeSpec(str(bound), None, False, True))
            out[f"{column}<={bound}"] = leaf(column, FilterOperator.RANGE, rng_spec=RangeSpec(None, str(bound), True, True))
    return out


LEAVES = _leaves()


def _trees():
    """Nested AND / OR over a spread of the leaves, a dead child beside a
    live one on either side."""
    rng = np.random.default_rng(48)
    names = sorted(LEAVES)
    out = {}
    for k in range(60):
        a, b, c = (LEAVES[names[j]] for j in rng.choice(len(names), size=3, replace=False))
        inner = FilterQueryTree(operator=FilterOperator.OR if k % 2 else FilterOperator.AND, children=[a, b])
        out[f"tree{k}"] = FilterQueryTree(operator=FilterOperator.AND if k % 2 else FilterOperator.OR, children=[inner, c])
    return out


TREES = _trees()


@pytest.mark.parametrize("kind", ["EQUALITY", "IN", "RANGE", "NOT", "NOT_IN", "nested"])
def test_a_segment_is_called_dead_only_if_no_row_of_it_matches(kind):
    """Exhaustive over 40 small random segments: dead implies no row
    matches (the verdict is sound); for a single-value EQUALITY, IN or
    RANGE leaf no row matching implies dead (it is exact, the segment's
    dictionary holding just its rows' values); NOT, NOT IN and a
    multi-value column are never called dead."""
    trees = TREES if kind == "nested" else {n: t for n, t in LEAVES.items() if t.operator.name == kind}
    assert trees
    called_dead = 0
    for seed in range(40):
        seg = tiny_segment(seed)
        for name, tree in trees.items():
            why = pruner.value_dead(seg, tree)
            matches = bool(_segment_mask(seg, tree, 0, seg.num_docs).any())
            assert not (why is not None and matches), (name, seed, why)
            if kind in ("EQUALITY", "IN", "RANGE") and tree.column != "tags":
                assert (why is not None) == (not matches), (name, seed)
                if why is not None:
                    assert tree.column in why and "[" in why  # the leaf, the column, the segment's [min, max]
            if kind in ("NOT", "NOT_IN") or (kind != "nested" and tree.column == "tags"):
                assert why is None, (name, seed)
            called_dead += why is not None
    assert called_dead or kind in ("NOT", "NOT_IN")


def test_a_literal_the_columns_type_does_not_take_is_left_to_the_plan():
    assert pruner.value_dead(tiny_segment(1), leaf("i", FilterOperator.EQUALITY, ["x"])) is None


# ---------------------------------------------------------------------------
# (ii) every device lowering over 16 date-range segments
# ---------------------------------------------------------------------------

DATED = Schema(
    "dated",
    dimensions=[FieldSpec("wk", DataType.INT), FieldSpec("mon", DataType.STRING), FieldSpec("seq", DataType.INT),
                FieldSpec("g4", DataType.INT), FieldSpec("g100", DataType.INT), FieldSpec("g50", DataType.STRING),
                FieldSpec("g20", DataType.INT), FieldSpec("uid", DataType.LONG)],
    metrics=[FieldSpec("v", DataType.INT, FieldType.METRIC), FieldSpec("w", DataType.INT, FieldType.METRIC)],
)


def dated_segment(i: int, seed: int = 48):
    """Segment ``i``: days [i * DAYS, (i + 1) * DAYS) in random order; ``wk``
    is the day's week of ten days (ten values a segment, not sorted),
    ``mon`` its month of DAYS days (one value a segment: sorted), ``seq``
    the row's number (sorted).  Integer measures, so that every sum is
    exact whatever order it is added in."""
    rng = np.random.default_rng(seed * 1000 + i)
    day = rng.integers(i * DAYS, (i + 1) * DAYS, size=ROWS)
    columns = {
        "wk": (day // 10).astype(np.int32),
        "mon": np.array([f"m{d // DAYS:02d}" for d in day], dtype=object),
        "seq": np.arange(ROWS, dtype=np.int32),
        "g4": rng.integers(0, 4, size=ROWS).astype(np.int32),
        "g100": rng.integers(0, 100, size=ROWS).astype(np.int32),
        "g50": np.array([f"k{x:02d}" for x in rng.integers(0, 50, size=ROWS)], dtype=object),
        "g20": rng.integers(0, 20, size=ROWS).astype(np.int32),
        "uid": rng.integers(0, 3000, size=ROWS).astype(np.int64) * 7919,
        "v": rng.integers(1, 1000, size=ROWS).astype(np.int32),
        "w": rng.integers(1, 50, size=ROWS).astype(np.int32),
    }
    return build_segment_from_columns(DATED, columns, ROWS, "dated", f"dated{i:02d}")


@pytest.fixture(scope="module")
def dated():
    return [dated_segment(i) for i in range(SEGMENTS)]


# the filter, the segments it can match, and the launch's window: its slots (-1: none) and its first (None: the whole launch)
FILTERS = {
    "4_of_16": ("wk BETWEEN 120 AND 159", (12, 13, 14, 15), (12, 13, 14, 15), 12),
    "3_of_16_an_empty_slot": ("wk BETWEEN 130 AND 159", (13, 14, 15), (-1, 13, 14, 15), 12),  # the window ends with the table
    "3_of_16_a_dead_one_between": ("(wk BETWEEN 80 AND 99 OR wk = 115)", (8, 9, 11), (8, 9, -1, 11), 8),
    "2_of_16_a_gap": ("(wk = 125 OR wk = 155)", (12, 15), (12, -1, -1, 15), 12),  # the span is the window's size
    "3_of_16_apart": ("(wk = 5 OR wk = 75 OR wk = 155)", (0, 7, 15), None, None),  # a span of the table: the whole launch
    "1_of_16": ("mon = 'm14'", (14,), (14,), 14),
}
# the lowering (kernel.groupby_lowering / groupby_operands / hll_lowering, or what else the program is), its query
LOWERINGS = {
    "loop": "SELECT SUM(v), COUNT(*) FROM dated WHERE {f} GROUP BY g4 TOP 10",
    "onehot": "SELECT SUM(v), MIN(w), MAX(w) FROM dated WHERE {f} GROUP BY g100 TOP 100",
    "radix": "SELECT SUM(v), AVG(w) FROM dated WHERE {f} GROUP BY g100, g50 TOP 5000",
    "sorted": "SELECT SUM(v), COUNT(*) FROM dated WHERE {f} GROUP BY g100, g50, g20 TOP 3000",
    "runs": "SELECT COUNT(*), SUM(v) FROM dated WHERE {f} GROUP BY g100, g50 TOP 40",
    "aggregate": "SELECT SUM(v), COUNT(*), MIN(w), MAX(v), AVG(w) FROM dated WHERE {f}",
    "selection": "SELECT wk, g50, v FROM dated WHERE {f} AND g4 = 2 ORDER BY v DESC, seq LIMIT 25",
    "hll_sort": "SELECT DISTINCTCOUNTHLL(uid) FROM dated WHERE {f} GROUP BY g100 TOP 100",
    "zone": "SELECT SUM(v), COUNT(*) FROM dated WHERE {f} AND seq < 200",
}


def serve(executor, segments, pql):
    request = parse_pql(pql)
    result = executor.execute(segments, request)
    return request, result


@pytest.mark.parametrize("case", sorted(FILTERS))
@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
def test_a_launch_over_the_live_segments_answers_as_the_whole_launch_and_the_oracle(monkeypatch, dated, lowering, case):
    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")  # the chip's lowerings, on the CPU
    monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", "128")
    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")  # the device tier is what is under test
    monkeypatch.setenv("PINOT_TPU_HLL_PRESENCE", "0")  # the per-row register streams, which the 'sort' lowering reads
    if lowering == "runs":
        monkeypatch.setattr(config, "MAX_GROUP_CAPACITY", 1 << 12)  # under g100 x g50's 5,000 keys
    forget_programs()
    text, live, slots, first = FILTERS[case]
    count = SEGMENTS if slots is None else len(slots)
    pql = LOWERINGS[lowering].format(f=text)
    launches = []
    real_run = QueryExecutor._run_kernel

    def spy(self, kernel, args, plan, *a, **kw):
        launches.append((plan, kw.get("segments"), args[1] if not callable(args) else None, kernel))
        return real_run(self, kernel, args, plan, *a, **kw)

    monkeypatch.setattr(QueryExecutor, "_run_kernel", spy)
    try:
        executor = QueryExecutor(metrics=ServerMetrics("t"))
        request, part = serve(executor, dated, pql)
        plan, tag, q, kernel = launches[-1]
        assert tag == f"{count}/{SEGMENTS}"
        if slots is None:
            assert "segments" not in q
        else:
            assert np.asarray(q["segments"]["slots"]).tolist() == list(slots) and int(q["segments"]["first"]) == first
        # the lowering the case names is the one that ran
        if lowering in ("loop", "onehot", "radix", "sorted", "runs"):
            named = kernel_mod.groupby_operands(plan) if lowering in ("loop", "sorted") else kernel_mod.groupby_lowering(plan)
            assert named == lowering
        if lowering == "hll_sort":
            assert kernel_mod.hll_lowering(plan) == "sort"
        assert ("zone" in kernel.__name__) == (lowering == "zone")

        # the whole launch: the same plan over all sixteen, the filter rejecting the rest
        monkeypatch.setattr(ladder, "launch_segments", lambda scanned, staged, mesh: None)
        _, whole = serve(QueryExecutor(metrics=ServerMetrics("t")), dated, pql)
        assert launches[-1][0] == plan and launches[-1][1] == f"{SEGMENTS}/{SEGMENTS}" and "segments" not in launches[-1][2]
        oracle = executor.execute_host_oracle(dated, request)
    finally:
        forget_programs()

    assert canonical_payload(request, part) == canonical_payload(request, whole) == canonical_payload(request, oracle)
    for result in (part, whole):
        assert result.total_docs == SEGMENTS * ROWS
        assert result.num_segments_queried == len(live)
        assert result.cost["segmentsPruned"] == SEGMENTS - len(live)
        assert result.cost["segmentsZonemap" if lowering == "zone" else "segmentsFullScan"] == len(live)
        assert not result.cost.get("segmentsHost")
    assert part.num_docs_scanned == whole.num_docs_scanned == oracle.num_docs_scanned > 0
    assert part.cost.get("numGroupsLive") == whole.cost.get("numGroupsLive")
    assert oracle.num_segments_queried == SEGMENTS and "segmentsPruned" not in oracle.cost  # the oracle prunes by no value
    snap = executor.metrics.snapshot()["meters"]
    assert snap["prune.segments.offered"]["count"] == SEGMENTS and snap["prune.segments.value"]["count"] == SEGMENTS - len(live)


@pytest.mark.parametrize("case", ["4_of_16", "3_of_16_a_dead_one_between", "2_of_16_a_gap"])
@pytest.mark.parametrize("lowering", ["aggregate", "loop", "onehot", "radix"])
def test_a_window_over_the_row_budget_launches_in_chunks(monkeypatch, dated, lowering, case):
    """A window of four segments over a budget of two segments' rows is
    two dispatches of the table program, each taking its own half of the
    window from the whole resident columns: a dead segment between and a
    gap fall into a chunk as empty slots."""
    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")
    forget_programs()
    text, live, slots, first = FILTERS[case]
    pql = LOWERINGS[lowering].format(f=text)
    launches = []
    real_run = QueryExecutor._run_kernel

    def spy(self, kernel, args, plan, *a, **kw):
        launches.append((kw.get("segments"), args[1] if not callable(args) else None, kernel))
        return real_run(self, kernel, args, plan, *a, **kw)

    monkeypatch.setattr(QueryExecutor, "_run_kernel", spy)
    windows = []
    real_view = kernel_mod.launch_view
    monkeypatch.setattr(kernel_mod, "launch_view", lambda segs, q: windows.append(q["segments"]["slots"].shape[0]) or real_view(segs, q))
    try:
        request, single = serve(QueryExecutor(metrics=ServerMetrics("t")), dated, pql)
        assert hasattr(launches[-1][2], "lower") and windows == [4]  # one program over the window of four
        forget_programs()
        del windows[:]
        monkeypatch.setenv("PINOT_TPU_CHUNK_ROWS", str(2 * ROWS))
        executor = QueryExecutor(metrics=ServerMetrics("t"))
        _, chunked = serve(executor, dated, pql)
        tag, q, kernel = launches[-1]
        assert tag == f"4/{SEGMENTS}" and np.asarray(q["segments"]["slots"]).tolist() == list(slots)
        assert not hasattr(kernel, "lower") and windows == [2]  # a sequence of launches, traced once at two slots
        oracle = executor.execute_host_oracle(dated, request)
    finally:
        forget_programs()
    assert canonical_payload(request, chunked) == canonical_payload(request, single) == canonical_payload(request, oracle)
    assert chunked.num_docs_scanned == oracle.num_docs_scanned > 0
    assert chunked.num_segments_queried == len(live) and chunked.cost["segmentsPruned"] == SEGMENTS - len(live)
    assert chunked.cost["segmentsFullScan"] == len(live) and not chunked.cost.get("segmentsHost")


@pytest.mark.parametrize("case", ["4_of_16", "1_of_16"])
def test_a_sharded_placement_launches_whole_and_counts_the_dead_as_pruned(monkeypatch, dated, case):
    """Under a mesh the launch is the whole table's (a window across
    shards would be a collective): the filter rejects the dead segments'
    rows on their own chips, and the counts are the verdict's all the
    same; the host tiers there work over the scanned segments alone."""
    from pinot_tpu.parallel import default_mesh

    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")
    text, live, _slots, _first = FILTERS[case]
    pql = LOWERINGS["onehot"].format(f=text)
    launches = []
    real_run = QueryExecutor._run_kernel

    def spy(self, kernel, args, plan, *a, **kw):
        launches.append((kw.get("segments"), args[1] if not callable(args) else None, kernel))
        return real_run(self, kernel, args, plan, *a, **kw)

    monkeypatch.setattr(QueryExecutor, "_run_kernel", spy)
    executor = QueryExecutor(metrics=ServerMetrics("t"), mesh=default_mesh())
    request, sharded = serve(executor, dated, pql)
    tag, q, kernel = launches[-1]
    assert tag == f"{SEGMENTS}/{SEGMENTS}" and "segments" not in q and "mesh" in kernel.__name__
    _, single = serve(QueryExecutor(metrics=ServerMetrics("t")), dated, pql)
    assert launches[-1][0] == f"{len(_slots)}/{SEGMENTS}"
    oracle = executor.execute_host_oracle(dated, request)
    assert canonical_payload(request, sharded) == canonical_payload(request, single) == canonical_payload(request, oracle)
    assert sharded.total_docs == SEGMENTS * ROWS and sharded.num_docs_scanned == oracle.num_docs_scanned > 0
    assert sharded.num_segments_queried == len(live)
    assert sharded.cost["segmentsPruned"] == SEGMENTS - len(live) and sharded.cost["segmentsFullScan"] == len(live)
    snap = executor.metrics.snapshot()["meters"]
    assert snap["prune.segments.offered"]["count"] == SEGMENTS and snap["prune.segments.value"]["count"] == SEGMENTS - len(live)
    # the forced host path under the mesh reads the scanned segments alone
    monkeypatch.setattr(config, "MAX_GROUP_CAPACITY", 1 << 12)
    host_request, host = serve(executor, dated, f"SELECT MAX(v) FROM dated WHERE {text} GROUP BY g100, g50 TOP 10")
    assert host.cost["segmentsHost"] == len(live) and host.cost["segmentsPruned"] == SEGMENTS - len(live)
    assert canonical_payload(host_request, host) == canonical_payload(host_request, executor.execute_host_oracle(dated, host_request))


@pytest.mark.parametrize("case", ["4_of_16", "3_of_16_a_dead_one_between", "3_of_16_apart"])
def test_the_bitsliced_tier_passes_whole_and_counts_by_the_verdict(monkeypatch, dated, case):
    """The bit-sliced tier's pass is over every live segment's planes as
    they are staged (a dead segment's count is zero); what it counts as
    queried and as pruned is the verdict's, as in the other tiers."""
    monkeypatch.setenv("PINOT_TPU_BITSLICED", "force")
    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")
    text, live, _slots, _first = FILTERS[case]
    executor = QueryExecutor(metrics=ServerMetrics("t"))
    request, result = serve(executor, dated, f"SELECT COUNT(*), SUM(v), MIN(w), MAX(w) FROM dated WHERE {text}")
    assert result.cost["segmentsBitsliced"] == len(live) and result.cost["segmentsPruned"] == SEGMENTS - len(live)
    assert result.num_segments_queried == len(live) and result.total_docs == SEGMENTS * ROWS
    assert not result.cost.get("segmentsFullScan") and not result.cost.get("segmentsHost")
    oracle = executor.execute_host_oracle(dated, request)
    assert canonical_payload(request, result) == canonical_payload(request, oracle) and result.num_docs_scanned == oracle.num_docs_scanned > 0


def test_a_filter_that_leaves_no_segment_and_one_that_leaves_all(dated):
    executor = QueryExecutor(metrics=ServerMetrics("t"))
    request, none = serve(executor, dated, "SELECT SUM(v), COUNT(*) FROM dated WHERE wk = 999 GROUP BY g4 TOP 10")
    assert none.groups == {} and none.total_docs == SEGMENTS * ROWS and none.cost["segmentsPruned"] == SEGMENTS
    assert canonical_payload(request, none) == canonical_payload(request, executor.execute_host_oracle(dated, request))
    _, every = serve(executor, dated, "SELECT SUM(v) FROM dated WHERE wk >= 0 GROUP BY g4 TOP 10")
    assert "segmentsPruned" not in every.cost and every.cost["segmentsFullScan"] == SEGMENTS
    _, fifteen = serve(executor, dated, "SELECT SUM(v) FROM dated WHERE wk < 150 GROUP BY g4 TOP 10")  # L = 16 = S: the whole launch
    assert fifteen.cost["segmentsPruned"] == 1 and fifteen.cost["segmentsFullScan"] == 15


@pytest.mark.parametrize("tier", ["postings", "host"])
def test_the_host_tiers_work_over_the_scanned_segments_alone(monkeypatch, dated, tier):
    """The postings tier resolves its driving leaf in the live segments
    and weighs its matches against their rows; the forced host path reads
    no other either."""
    executor = QueryExecutor(metrics=ServerMetrics("t"))
    if tier == "postings":
        pql = "SELECT SUM(v), COUNT(*) FROM dated WHERE g100 = 7 AND g20 = 3 AND mon = 'm14' GROUP BY g4 TOP 10"
        resolved = []
        from pinot_tpu.segment import invindex

        real = invindex.InvertedIndex.resolve_table
        monkeypatch.setattr(invindex.InvertedIndex, "resolve_table", lambda self, t: resolved.append(1) or real(self, t))
    else:
        monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")
        monkeypatch.setattr(config, "MAX_GROUP_CAPACITY", 1 << 12)
        pql = "SELECT MAX(v) FROM dated WHERE mon = 'm14' GROUP BY g100, g50 TOP 10"  # max has no run form: the host's
    request, result = serve(executor, dated, pql)
    key = "segmentsPostings" if tier == "postings" else "segmentsHost"
    assert result.cost[key] == 1 and result.cost["segmentsPruned"] == 15 and result.num_segments_queried == 1
    assert result.total_docs == SEGMENTS * ROWS
    assert canonical_payload(request, result) == canonical_payload(request, executor.execute_host_oracle(dated, request))
    if tier == "postings":
        assert len(resolved) == 1  # one segment's postings walked, not sixteen


@pytest.mark.parametrize("trace", [False, True])
def test_the_verdict_is_a_stretch_of_prune_between_two_of_staging(dated, trace):
    """A query that derives the verdict times it as ``prune``, on the
    chain of phases: ``staging`` stops for it and starts again, so the
    two leaves never cover one instant; a repeated text finds the
    verdict kept and has one of each."""
    from pinot_tpu.utils import trace as trace_mod

    executor = QueryExecutor(metrics=ServerMetrics("t"))
    pql = "SELECT SUM(v), COUNT(*) FROM dated WHERE wk BETWEEN 120 AND 159 GROUP BY g4 TOP 10"
    counts = lambda: [executor.metrics.snapshot()["timers"][k]["count"] for k in ("phase.prune", "phase.staging")]
    ctx = trace_mod.TraceContext(enabled=True, scope="server") if trace else None
    tokens = trace_mod.set_current(ctx) if trace else None
    try:
        serve(executor, dated, pql)
        assert counts() == [2, 2]
        serve(executor, dated, pql)
        assert counts() == [3, 3]
    finally:
        if trace:
            trace_mod.reset_current(tokens)
    if trace:
        spans = [(sp["startMs"], sp["startMs"] + sp["ms"], sp["span"]) for sp in ctx.spans if sp["span"] in ("prune", "staging")]
        assert [name for _s, _e, name in spans[:4]] == ["prune", "staging", "prune", "staging"]  # in the order they closed
        assert all(a[1] <= b[0] + 0.002 for a, b in zip(spans, spans[1:]))  # one after the other, none inside another


def test_a_poisoned_plan_is_poisoned_at_every_launch_size(dated):
    """The lane knows a part launch as ``<planDigest>.L<L>`` (a compile of
    its own); a plan poisoned by the digest EXPLAIN prints fails at that
    size too, and the host's answer reads the scanned segments alone."""
    from pinot_tpu.common.faults import DeviceFaultInjector
    from pinot_tpu.tools.cluster_harness import single_server_broker

    inj = DeviceFaultInjector(seed=48)
    broker = single_server_broker("dated", dated, pipeline=True, device_fault_injector=inj)
    try:
        pql = "SELECT SUM(v), COUNT(*) FROM dated WHERE wk BETWEEN 120 AND 159 GROUP BY g4 TOP 10"
        digest = broker.handle_pql("EXPLAIN " + pql).explain["servers"][0]["device"]["planDigest"]
        healthy = broker.handle_pql(pql).to_json()
        assert inj.launches[-1].digest == digest + ".L4" and healthy["cost"]["segmentsFullScan"] == 4
        inj.poison_plan(digest)
        healed = broker.handle_pql(pql).to_json()
        assert (inj.launches[-1].digest, inj.launches[-1].outcome) == (digest + ".L4", "poison")
        assert healed["cost"]["segmentsHost"] == 4 and healed["cost"]["segmentsPruned"] == 12 and healed["numSegmentsQueried"] == 4
        assert healed["aggregationResults"] == healthy["aggregationResults"] and healed["totalDocs"] == SEGMENTS * ROWS
    finally:
        broker.local_servers[0].shutdown()


# ---------------------------------------------------------------------------
# (iii) one staged table, one table context
# ---------------------------------------------------------------------------


def test_one_staged_table_and_one_context_after_three_segment_sets(dated):
    device.clear_staging_cache()
    context_mod._context_cache.clear()
    executor = QueryExecutor(metrics=ServerMetrics("t"))
    query = "SELECT SUM(v), COUNT(*) FROM dated WHERE {f} GROUP BY g100 TOP 100"
    serve(executor, dated, query.format(f="wk >= 0"))
    staged_bytes, tables = device.LEDGER.total_bytes(), device.LEDGER.table_count()
    contexts = len(context_mod._context_cache)
    assert tables == 1 and contexts == 1 and staged_bytes > 0
    # three sets over the columns the first query staged (a staged table is keyed by its segments and columns)
    for text, live in (("wk BETWEEN 120 AND 159", 4), ("(wk = 125 OR wk = 155)", 2), ("wk BETWEEN 140 AND 149", 1)):
        _, result = serve(executor, dated, query.format(f=text))
        assert result.cost["segmentsPruned"] == SEGMENTS - live and result.cost["segmentsFullScan"] == live
        assert (device.LEDGER.total_bytes(), device.LEDGER.table_count()) == (staged_bytes, tables)
        assert len(context_mod._context_cache) == contexts
        assert len(device._stage_cache) == 1


# ---------------------------------------------------------------------------
# (iv) two launches of one plan over different sets are not coalesced
# ---------------------------------------------------------------------------


def test_two_launches_of_one_plan_over_different_segments_are_told_apart(monkeypatch, dated):
    """The same plan, the same per-segment tables (every segment holds
    its own ten weeks, so ``wk``'s bounds in a segment's dictionary read
    the same), another four segments: the positions are an input, so the
    digest, the lane's coalesce key and the uploaded inputs' key differ."""
    lane = DeviceLane(metrics=ServerMetrics("t"))
    keys = []
    real_submit = lane.submit
    monkeypatch.setattr(lane, "submit", lambda key, *a, **kw: keys.append(key) or real_submit(key, *a, **kw))
    try:
        executor = QueryExecutor(metrics=ServerMetrics("t"), lane=lane)
        query = "SELECT SUM(v), COUNT(*) FROM dated WHERE wk BETWEEN {a} AND {b} GROUP BY g100 TOP 100"
        _, first = serve(executor, dated, query.format(a=120, b=159))
        _, second = serve(executor, dated, query.format(a=80, b=119))
        _, again = serve(executor, dated, query.format(a=120, b=159))
    finally:
        lane.close()
    (plan1, token1, digest1, _), (plan2, token2, digest2, _), third = keys
    assert plan1 == plan2 and token1 == token2 and digest1 != digest2
    assert third == keys[0]  # the same text is the same launch: it may coalesce, and finds its inputs uploaded
    assert again.cost.get("qinputCacheHits") == 1 and not second.cost.get("qinputCacheHits")
    assert first.num_docs_scanned == second.num_docs_scanned == 4 * ROWS
    assert {k: [p.finalize() for p in v] for k, v in first.groups.items()} != {k: [p.finalize() for p in v] for k, v in second.groups.items()}
