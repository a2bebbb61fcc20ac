"""ClickBench's distinct-user queries (PR 41): ``distinctcounthll(UserID)``
alone and by ``RegionID``, through a networked cluster (every role over
its real protocol) against the benchmark's plain reference
(``benchmark/reference_hits_users.py``: numpy, nothing of the program),
estimates equal as integers; the one function of the plan that says which
lowering an HLL aggregate takes (``kernel.hll_lowering``), which the
kernel builder, the reduce spec, the launch's tag and its mark all ask;
the hash an integral dictionary takes in bulk; the estimator over a stack
of register rows; and the skewed generator's realised counts against what
its parameters predict.  The cell itself is rehearsed with the other six
in ``test_benchmark_rehearsal.py`` (it reads ``BENCHMARK.json``)."""
import importlib.util
import json
import os

import numpy as np
import pytest

from pinot_tpu.common.schema import DataType
from pinot_tpu.engine import hll as hll_mod
from pinot_tpu.engine import kernel as kernel_mod
from pinot_tpu.segment.dictionary import Dictionary
from pinot_tpu.tools import datagen
from pinot_tpu.tools.cluster_harness import single_server_broker
from pinot_tpu.utils.metrics import prometheus_text

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEGMENTS, ROWS, USERS = 3, 20_000, 30_000
SEEDS = (4100, 2**31 + 41)
SUM_RTOL = 1e-6  # float64 on the CPU: the gap is the reply's five decimals


def _load(path: str):
    spec = importlib.util.spec_from_file_location("hits_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref_mod = _load(os.path.join(BENCH, "reference_hits_users.py"))
SHAPES = {s["name"]: s for s in json.load(open(os.path.join(BENCH, "traffic", "hits_users_closed.json")))["shapes"]}
PQL = {name: ref_mod.render_pql("hits", shape) for name, shape in SHAPES.items()}


def forget_programs():
    for cached in (kernel_mod.make_table_kernel, kernel_mod.make_packed_table_kernel,
                   kernel_mod.make_block_table_kernel, kernel_mod.make_packed_block_table_kernel):
        cached.cache_clear()


def make_segments(seed: int):
    return [datagen.synthetic_hits_users_segment(ROWS, seed=seed * 1000 + i, name=f"seg{i}", users=USERS) for i in range(SEGMENTS)]


def referred(segments):
    ref = ref_mod.Reference(SHAPES)
    for seg in segments:
        ref.add(seg)
    return ref


@pytest.fixture(scope="module", params=SEEDS)
def networked(request, tmp_path_factory):
    """Controller, one server and a broker over HTTP and TCP, the table
    uploaded through the controller with a real CRC; and the reference."""
    from pinot_tpu.common.tableconfig import TableConfig
    from pinot_tpu.tools.cluster_harness import NetworkedCluster

    segments = make_segments(request.param)
    cluster = NetworkedCluster(num_servers=1, data_dir=str(tmp_path_factory.mktemp("hits")))
    try:
        cluster.controller.add_schema(datagen.hits_users_schema())
        physical = cluster.controller.add_table(TableConfig(table_name="hits", table_type="OFFLINE", replication=1))
        for seg in segments:
            seg.metadata.crc = seg.compute_crc()
            seg.metadata.custom["dataCrc"] = True
            cluster.controller.upload_segment(physical, seg)
        cluster.wait(lambda: cluster.query("SELECT count(*) FROM hits").to_json().get("totalDocs") == SEGMENTS * ROWS,
                     what="the broker serving every segment")
        yield cluster, referred(segments)
    finally:
        cluster.stop()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_cells_shapes_through_a_networked_cluster_equal_the_reference(networked, shape):
    cluster, ref = networked
    reply = cluster.query(PQL[shape]).to_json()
    got = ref_mod.compare(reply, SHAPES[shape], ref.answers[shape], ref.rows)
    assert got["count_errors"] == got["key_errors"] == got["reply_errors"] == 0, (got, reply.get("exceptions"), reply.get("cost"))
    assert got["sum_gap"] <= SUM_RTOL, got
    cost = reply["cost"]
    assert cost.get("segmentsHost", 0) == 0 and cost["deviceMs"] > 0
    if shape == "users_total":
        # one integer, the reference's; within the sketch's error of the exact count
        have = int(reply["aggregationResults"][0]["value"])
        assert have == int(ref_mod.wanted(SHAPES[shape], ref.answers[shape])[0][0])
        assert "numGroupsLive" not in cost
        return
    digest = ref_mod.state_digest(SHAPES[shape], ref.answers[shape])
    assert cost["numGroupsLive"] == digest["numGroupsLive"] > 100 and cost["numGroupsKept"] >= 10
    assert cost["groupStateHllSum"] == digest["groupStateHllSum"]  # every live group's estimate, as integers
    assert cost["groupStateHllSumSq"] == pytest.approx(digest["groupStateHllSumSq"], rel=1e-12)
    assert cost.get("groupStateSumSq", 0.0) == pytest.approx(digest["groupStateSumSq"], rel=1e-9)


def test_the_reference_counts_what_a_set_counts_through_the_same_sketch():
    """The reference's total against python's own set through the
    program's scalar sketch: two writings of one estimator."""
    segments = make_segments(SEEDS[0])
    ref = referred(segments)
    users = set()
    for seg in segments:
        col = seg.column("UserID")
        users.update(int(v) for v in np.asarray(col.dictionary.values)[np.unique(col.fwd)])
    want = hll_mod.hll_estimate_exact_values(users)
    assert int(ref_mod.wanted(SHAPES["users_total"], ref.answers["users_total"])[0][0]) == want
    assert abs(want - len(users)) < 0.25 * len(users)  # the sketch's standard error is 6.5% at 256 registers


# ---------------------------------------------------------------------------
# one function says which lowering an HLL aggregate takes
# ---------------------------------------------------------------------------


def _served_once(monkeypatch, segments, pql):
    """(the plan, the launch's span, the reply, the server) of one query."""
    from pinot_tpu.engine.executor import QueryExecutor

    plans = []
    run_kernel = QueryExecutor._run_kernel

    def spy(self, kernel, args, plan, *rest, **kw):
        plans.append(plan)
        return run_kernel(self, kernel, args, plan, *rest, **kw)

    monkeypatch.setattr(QueryExecutor, "_run_kernel", spy)
    forget_programs()
    broker = single_server_broker("hits", segments)
    server = broker.local_servers[0]
    try:
        resp = broker.handle_pql(pql, trace=True)
        (launch,) = [s for s in resp.trace_info["scopes"][server.name] if s["span"] == "laneDispatch"]
        (plan,) = plans
        marks = {k: server.metrics.meter(f"hll.lowering.{k}").count for k in ("matmul", "sort", "scatter", "pairs")}
        served = prometheus_text(server.metrics)  # what /metrics serves
        series = [f"pinot_tpu_server_hll_lowering_{k}_total" for k in marks if marks[k]]
        series += ["pinot_tpu_server_phase_hllDerive_ms_count"]
        series += ["pinot_tpu_server_phase_hllEstimate_ms_count"] * (plan.group_by is not None)
        assert all(f"\n{name}{{" in served for name in series), series
        assert "# HELP pinot_tpu_server_phase_hllDerive_ms " in served  # the catalog describes it
        return plan, launch, resp.to_json(), marks, server.executor.healing_stats()["hostFailovers"]
    finally:
        server.shutdown()
        forget_programs()


# (shape, the chip's lowerings forced, what hll_lowering answers)
LOWERINGS = [
    ("users_total", False, "scatter"),  # the CPU's own: no matrix unit
    ("users_total", True, "matmul"),  # the chip's: 16,384 (register, rank) cells on the contraction
    ("users_by_region", False, "scatter"),  # the CPU's own: the sorted form's sum is a Pallas call
    ("users_by_region", True, "sort"),  # 9,040 regions: over the contraction's 16 groups, under 65,536
    ("region_summary", True, "sort"),
]


@pytest.mark.parametrize("shape,forced,lowering", LOWERINGS)
def test_the_kernel_the_reduce_spec_the_tag_and_the_mark_ask_one_function(monkeypatch, shape, forced, lowering):
    if forced:
        monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    segments = make_segments(SEEDS[0])[:2]
    ref = referred(segments)
    plan, launch, reply, marks, failovers = _served_once(monkeypatch, segments, PQL[shape])
    assert kernel_mod.hll_lowering(plan) == launch["tags"]["hll"] == lowering
    assert marks == {k: int(k == lowering) for k in marks} and failovers == 0
    if plan.group_by is not None:
        assert plan.group_by.capacity == datagen.HITS_REGIONS  # the cell's own capacity: every dictionary holds every region
        hll_at = next(i for i, a in enumerate(plan.aggs) if a.kind == "hll")
        assert kernel_mod.output_reducers(plan)[f"gb_{hll_at}"] == "max"  # dense registers, whatever lowering built them
        assert kernel_mod.zone_blocks(plan) == "gathered" and "blocks" not in launch["tags"]  # no filter: no zone launch
    got = ref_mod.compare(reply, SHAPES[shape], ref.answers[shape], ref.rows)
    assert got["count_errors"] == got["key_errors"] == got["reply_errors"] == 0 and got["sum_gap"] <= SUM_RTOL, got


@pytest.mark.parametrize("answer,reducer", [("scatter", "max"), ("sort", "max")])
def test_another_answer_of_the_function_is_another_program_and_the_same_registers(monkeypatch, answer, reducer):
    """The kernel builder and the reduce spec follow what the function
    says, whatever it says: two register lowerings of one grouped query
    give the reference's estimates (the third, the contraction, takes 16
    groups at the most: ``test_engine.py`` holds the three at a small
    capacity)."""
    monkeypatch.setattr(kernel_mod, "hll_lowering", lambda plan: answer if any(a.kind == "hll" for a in plan.aggs) else None)
    segments = make_segments(SEEDS[1])[:1]
    ref = referred(segments)
    plan, launch, reply, marks, failovers = _served_once(monkeypatch, segments, PQL["users_by_region"])
    assert launch["tags"]["hll"] == answer and marks[answer] == 1 and failovers == 0
    assert kernel_mod.output_reducers(plan)["gb_0"] == reducer
    got = ref_mod.compare(reply, SHAPES["users_by_region"], ref.answers["users_by_region"], ref.rows)
    assert got == {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}


@pytest.mark.parametrize("groups,forced,lowering", [
    (None, True, "matmul"), (None, False, "scatter"), (16, True, "matmul"), (16, False, "scatter"), (17, True, "sort"),
    (17, False, "scatter"), (9_040, True, "sort"), (9_040, False, "scatter"), (65_536, True, "sort"), (65_537, True, "scatter"),
    (65_537, False, "scatter"),
])
def test_the_gates_by_capacity(monkeypatch, groups, forced, lowering):
    from types import SimpleNamespace

    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1" if forced else "0")
    agg = SimpleNamespace(kind="hll", sort_pairs=False)
    plan = SimpleNamespace(aggs=(SimpleNamespace(kind="scalar", sort_pairs=False), agg),
                           group_by=None if groups is None else SimpleNamespace(capacity=groups))
    assert kernel_mod.hll_lowering(plan) == lowering
    assert kernel_mod.hll_lowering(SimpleNamespace(aggs=plan.aggs[:1], group_by=plan.group_by)) is None
    agg.sort_pairs = True
    assert kernel_mod.hll_lowering(plan) == "pairs"


# ---------------------------------------------------------------------------
# the hash in bulk, the estimator over a stack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stored,values", [
    (DataType.LONG, [-(2**63), -5, 0, 1, 5, 255, 256, 2**40 + 3, 2**63 - 1]),
    (DataType.INT, [-7, 0, 3, 2**31 - 1]),
    (DataType.DOUBLE, [-2.5, -2.0, 0.0, 0.5, 5.0, 1e12, 1.5e300]),
    (DataType.STRING, ["", "5", "a", "region"]),
])
def test_a_dictionarys_tables_are_each_entrys_own_hash(stored, values):
    d = Dictionary(stored, values)
    bt, rt = hll_mod.dictionary_tables(d)
    each = [hll_mod.bucket_and_rho(hll_mod.value_hash64(d.get(j))) for j in range(d.cardinality)]
    assert [(int(b), int(r)) for b, r in zip(bt, rt)] == each
    if stored != DataType.STRING:
        ints = [v for v in values if float(v).is_integer() and abs(v) < 2**62]
        assert [hll_mod.value_hash64(v) for v in ints] == [hll_mod.value_hash64(int(v)) for v in ints]  # 5.0 as 5
        assert [int(h) for h in hll_mod.hash64_integers(np.array([int(v) for v in ints], dtype=np.int64))] == [
            hll_mod.value_hash64(int(v)) for v in ints]


def test_the_rank_of_a_hash_whose_other_bits_are_zero():
    hashes = np.array([0, 255, 256, 1 << 63, (1 << 64) - 1], dtype=np.uint64)
    b, r = hll_mod.buckets_and_rhos(hashes)
    assert [(int(x), int(y)) for x, y in zip(b, r)] == [hll_mod.bucket_and_rho(int(h)) for h in hashes] == [
        (0, 57), (255, 57), (0, 1), (0, 56), (255, 1)]


@pytest.mark.parametrize("n", [0, 1, 40, 600, 700, 20_000, 3_000_000])
def test_the_estimator_over_a_stack_is_the_estimator_of_each_row_and_the_references(n):
    rng = np.random.default_rng(n)
    stack = np.stack([hll_mod.registers_from_values(rng.integers(0, 1 << 50, n + i)) for i in range(4)])
    each = [hll_mod.estimate_from_registers(row) for row in stack]
    assert all(isinstance(e, int) for e in each)
    assert list(hll_mod.estimate_from_registers(stack)) == each == list(ref_mod.estimate(stack))
    if n >= 600:
        assert all(abs(e - (n + i)) < 0.3 * (n + i) for i, e in enumerate(each))


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,users", [(200_000, 300_000), (100_000, 1_000_000)])
def test_the_generators_distinct_users_are_what_its_parameters_predict(rows, users):
    seg = datagen.synthetic_hits_users_segment(rows, seed=41 + rows, name="seg2", users=users)
    user = seg.column("UserID")
    assert np.unique(user.fwd).size == user.dictionary.cardinality  # the dictionary holds the ids drawn, no other
    predicted = datagen.hits_expected_distinct(rows, users, datagen.HITS_USER_EXPONENT)
    assert abs(user.dictionary.cardinality - predicted) < 0.02 * predicted
    heaviest = np.bincount(user.fwd).max() / rows
    top = datagen.zipf_cdf(users, datagen.HITS_USER_EXPONENT)[0]
    assert 0.7 * top < heaviest < 1.3 * top  # skewed, not uniform: the first id's share is Zipf's


def test_the_generators_other_columns(monkeypatch):
    rows = 300_000
    seg = datagen.synthetic_hits_users_segment(rows, seed=4141, name="seg7", users=100_000)
    again = datagen.synthetic_hits_users_segment(rows, seed=4141, name="seg7", users=100_000)
    assert list(seg.columns) == [s.name for s in datagen.hits_users_schema().all_fields()]
    for name in seg.columns:
        assert np.array_equal(seg.column(name).fwd, again.column(name).fwd), name
    region = seg.column("RegionID")
    assert list(region.dictionary.values) == list(range(1, datagen.HITS_REGIONS + 1))
    share = np.sort(np.bincount(region.fwd, minlength=datagen.HITS_REGIONS))[::-1] / rows
    harmonic = np.sum(1.0 / np.arange(1, datagen.HITS_REGIONS + 1))
    assert abs(share[0] - 1 / harmonic) < 0.02 and share[0] > 5 * share[20]  # Zipf's law, exponent 1: a tenth on the first
    # a user's home region: nine hits in ten of a heavy user fall in one region
    user = seg.column("UserID")
    heavy = np.argmax(np.bincount(user.fwd))
    theirs = np.bincount(region.fwd[user.fwd == heavy])
    assert 0.8 < theirs.max() / theirs.sum() <= 1.0
    adv = seg.column("AdvEngineID")
    assert list(adv.dictionary.values) == list(range(19))
    assert abs(np.mean(adv.fwd != 0) - datagen.HITS_ADV_SHARE) < 0.001
    width = seg.column("ResolutionWidth")
    assert 1_450 < np.asarray(width.dictionary.values)[width.fwd].mean() < 1_600 and width.dictionary.cardinality == 12
    day = seg.column("EventDate")
    assert day.metadata.is_sorted and np.all(np.diff(day.fwd) >= 0) and seg.metadata.time_column == "EventDate"
    assert list(day.dictionary.values) == [15887 + 21, 15887 + 22, 15887 + 23]  # segment 7: a contiguous run of dates
