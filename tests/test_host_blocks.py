"""The host path in row blocks (ISSUE 29): the block-streamed pass
gives the whole-segment float64 answer, takes ``ceil(rows / block)``
steps a segment, and the shadow auditor drives it step by step on its
own thread, counts what it offered, took, dropped and finished, and
still catches a corrupted device answer."""
import math
import threading
import time

import numpy as np
import pytest

from pinot_tpu.engine import config
from pinot_tpu.engine import host_fallback as hf
from pinot_tpu.engine.context import get_table_context
from pinot_tpu.engine.executor import QueryExecutor
from pinot_tpu.engine.reduce import reduce_to_response
from pinot_tpu.engine.results import IntermediateResult
from pinot_tpu.pql import optimize_request, parse_pql
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.tools.datagen import make_test_schema, random_rows, synthetic_lineitem_segment
from pinot_tpu.utils.audit import SamplerBudget, ShadowAuditor

ROWS = 1000  # a segment; not a multiple of any block size below
WHOLE = 1 << 30  # a block that holds any segment: the whole-segment computation

# the four shapes of benchmark/traffic/suite_open.json, then the cases ISSUE 29 names
LINEITEM_CASES = {
    "q0": "SELECT sum(l_extendedprice), sum(l_discount) FROM lineitem",
    "q1": "SELECT sum(l_extendedprice) FROM lineitem WHERE l_returnflag = 'R'",
    "q6": "SELECT sum(l_extendedprice) FROM lineitem WHERE l_shipmode IN ('RAIL','FOB') "
          "AND l_receiptdate BETWEEN '1997-01-01' AND '1997-12-31' GROUP BY l_shipmode TOP 10",
    "k6": "SELECT sum(l_quantity), sum(l_extendedprice), sum(l_discount), count(*) FROM lineitem "
          "WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag, l_linestatus TOP 10",
    "shipdate_2000_groups": "SELECT sum(l_extendedprice), count(*) FROM lineitem GROUP BY l_shipdate TOP 10",
    "empty_match": "SELECT sum(l_extendedprice), count(*) FROM lineitem WHERE l_shipdate > '2100-01-01'",
    "empty_match_grouped": "SELECT sum(l_quantity) FROM lineitem WHERE l_shipdate > '2100-01-01' "
                           "GROUP BY l_returnflag TOP 10",
    # l_shipdate is sorted within a segment: the earliest dates lie in its first block alone
    "one_block_only": "SELECT sum(l_extendedprice), count(*) FROM lineitem WHERE l_shipdate <= '1992-01-20'",
    "min_max_avg": "SELECT min(l_discount), max(l_extendedprice), avg(l_quantity), "
                   "minmaxrange(l_tax) FROM lineitem WHERE l_returnflag = 'R'",
    "min_max_avg_grouped": "SELECT min(l_discount), max(l_extendedprice), avg(l_quantity), minmaxrange(l_tax), "
                           "count(*) FROM lineitem GROUP BY l_shipmode, l_linestatus TOP 20",
    # a key space over _DENSE_GROUP_SPACE: the sorted-key states and their merges
    "sparse_keys": "SELECT sum(l_quantity), min(l_tax), max(l_tax), count(*) FROM lineitem "
                   "GROUP BY l_shipdate, l_receiptdate TOP 15",
    "distinct_grouped": "SELECT distinctcount(l_shipdate), distinctcounthll(l_quantity), count(*) FROM lineitem "
                        "WHERE l_returnflag = 'R' GROUP BY l_extendedprice TOP 10",
    "selection": "SELECT l_quantity, l_shipmode FROM lineitem WHERE l_returnflag = 'R' LIMIT 7",
    "selection_sorted": "SELECT l_quantity, l_extendedprice FROM lineitem WHERE l_returnflag = 'R' "
                        "ORDER BY l_extendedprice DESC LIMIT 7",
}
MV_CASES = {
    "mv_key": "SELECT count(*), sum(metInt) FROM testTable GROUP BY dimStrMV TOP 10",
    "mv_filter": "SELECT count(*), sum(metDouble), max(metInt) FROM testTable WHERE dimIntMV IN (1, 2, 3)",
    "mv_filter_negated": "SELECT count(*), sum(metInt) FROM testTable WHERE dimStrMV <> 'a' GROUP BY dimStr TOP 10",
}


@pytest.fixture(scope="module")
def lineitem_segments():
    return [synthetic_lineitem_segment(ROWS, seed=2900 + i, name=f"blk{i}") for i in range(3)]


@pytest.fixture(scope="module")
def mv_segments():
    schema = make_test_schema()
    rows = random_rows(schema, 700, seed=29)
    return [build_segment(schema, rows[:333], "testTable", "mvblk0"),
            build_segment(schema, rows[333:], "testTable", "mvblk1")]


def host_answer(segments, pql: str, block: int, monkeypatch):
    """(result, steps) of one pass of the host path at this block size."""
    monkeypatch.setattr(config, "HOST_BLOCK_ROWS", block)
    request = optimize_request(parse_pql(pql))
    steps = QueryExecutor().host_oracle_steps(segments, request)
    n = 0
    while True:
        try:
            next(steps)
        except StopIteration as done:
            return request, done.value, n
        n += 1


def payload(request, result) -> dict:
    """What a client sees of one result, accounting stripped."""
    from pinot_tpu.utils.audit import canonical_payload

    return canonical_payload(request, result)


def partial_numbers(p) -> tuple:
    """(exact parts, float64 sums) of one partial state."""
    name = type(p).__name__
    if name == "SumPartial":
        return (), (p.total,)
    if name == "AvgPartial":
        return (p.count,), (p.total,)
    if name == "CountPartial":
        return (p.count,), ()
    if name in ("MinPartial", "MaxPartial"):
        return (p.value,), ()
    if name == "MinMaxRangePartial":
        return (p.mn, p.mx), ()
    if name == "DistinctPartial":
        return (tuple(sorted(np.asarray(list(p.values)).tolist())),), ()
    if name == "HllPartial":
        return (p.registers.tobytes(),), ()
    raise AssertionError(name)


def assert_same_answer(got: IntermediateResult, want: IntermediateResult) -> None:
    """Counts, keys, minima and maxima exactly; float64 sums to 1e-12
    relative, the reassociation alone."""
    assert got.num_docs_scanned == want.num_docs_scanned
    assert got.total_docs == want.total_docs
    assert got.selection_rows == want.selection_rows
    states = lambda r: ({(): r.aggregations} if r.groups is None else r.groups)
    if got.aggregations is None and got.groups is None:
        assert want.aggregations is None and want.groups is None
        return
    assert list(states(got)) == list(states(want))  # the same groups, in the same order
    for key, partials in states(got).items():
        for g, w in zip(partials, states(want)[key], strict=True):
            assert type(g) is type(w)
            exact_g, sums_g = partial_numbers(g)
            exact_w, sums_w = partial_numbers(w)
            assert exact_g == exact_w, (key, g, w)
            for a, b in zip(sums_g, sums_w, strict=True):
                assert math.isclose(a, b, rel_tol=1e-12, abs_tol=0.0), (key, a, b)


@pytest.mark.parametrize("block", [64, 333])
@pytest.mark.parametrize("case", sorted(LINEITEM_CASES))
def test_streamed_pass_equals_whole_segment(case, block, lineitem_segments, monkeypatch):
    pql = LINEITEM_CASES[case]
    request, want, whole_steps = host_answer(lineitem_segments, pql, WHOLE, monkeypatch)
    _, got, steps = host_answer(lineitem_segments, pql, block, monkeypatch)
    assert_same_answer(got, want)
    # the client's payload too, through the real reduce
    assert payload(request, got) == payload(request, want)
    live = len(lineitem_segments) if "2100" not in pql else None  # a pruned segment takes no step
    if live is not None:
        assert whole_steps == live
        assert steps == live * math.ceil(ROWS / block)


@pytest.mark.parametrize("block", [50, 333])
@pytest.mark.parametrize("case", sorted(MV_CASES))
def test_streamed_pass_equals_whole_segment_multi_value(case, block, mv_segments, monkeypatch):
    _, want, _ = host_answer(mv_segments, MV_CASES[case], WHOLE, monkeypatch)
    _, got, steps = host_answer(mv_segments, MV_CASES[case], block, monkeypatch)
    assert_same_answer(got, want)
    assert steps == sum(math.ceil(s.num_docs / block) for s in mv_segments)


@pytest.mark.parametrize("case", ["q0", "q1", "min_max_avg"])
def test_streamed_aggregation_equals_plain_numpy(case, lineitem_segments, monkeypatch):
    """Against numpy written here, not against the code's own whole-segment pass."""
    _, got, _ = host_answer(lineitem_segments, LINEITEM_CASES[case], 64, monkeypatch)

    def column(name):
        return np.concatenate([np.asarray(s.column(name).dictionary.values, dtype=np.float64)[s.column(name).fwd]
                               for s in lineitem_segments])

    if case == "q0":
        keep = np.ones(ROWS * len(lineitem_segments), dtype=bool)
    else:
        keep = np.concatenate([np.asarray(s.column("l_returnflag").dictionary.values)[s.column("l_returnflag").fwd]
                               for s in lineitem_segments]) == "R"
    assert got.num_docs_scanned == int(keep.sum())
    if case == "min_max_avg":
        mn, mx, avg, rng = got.aggregations
        assert mn.value == column("l_discount")[keep].min()
        assert mx.value == column("l_extendedprice")[keep].max()
        assert avg.count == keep.sum()
        assert math.isclose(avg.total, column("l_quantity")[keep].sum(), rel_tol=1e-12)
        assert (rng.mn, rng.mx) == (column("l_tax")[keep].min(), column("l_tax")[keep].max())
    else:
        columns = ["l_extendedprice", "l_discount"][: len(got.aggregations)]
        for partial, name in zip(got.aggregations, columns, strict=True):
            assert math.isclose(partial.total, column(name)[keep].sum(), rel_tol=1e-12)


@pytest.mark.parametrize("block", [64, 250, 1000, 1001])
def test_steps_are_ceil_rows_over_block_a_segment(block, lineitem_segments, monkeypatch):
    for pql in (LINEITEM_CASES["q0"], LINEITEM_CASES["k6"], "SELECT distinctcount(l_shipmode) FROM lineitem"):
        _, _, steps = host_answer(lineitem_segments, pql, block, monkeypatch)
        assert steps == len(lineitem_segments) * math.ceil(ROWS / block), pql


def test_resolver_backed_rows_are_cut_into_blocks(lineitem_segments, monkeypatch):
    """A postings-backed resolver (engine/invindex_path.py) hands whole
    segments' row ids; the pass cuts them into blocks of the same size."""
    monkeypatch.setattr(config, "HOST_BLOCK_ROWS", 100)
    request = optimize_request(parse_pql(LINEITEM_CASES["q1"]))
    ctx = get_table_context(lineitem_segments)
    total = sum(s.num_docs for s in lineitem_segments)
    want = hf.execute_host(lineitem_segments, ctx, request, total, None)
    resolved = [np.flatnonzero(hf._segment_mask(s, request.filter)) for s in lineitem_segments]
    steps = hf.execute_host_steps(lineitem_segments, ctx, request, total, None,
                                  matched_rows=lambda si, seg: resolved[si])
    n = 0
    while True:
        try:
            next(steps)
        except StopIteration as done:
            got = done.value
            break
        n += 1
    assert n == sum(math.ceil(r.size / 100) for r in resolved)
    assert_same_answer(got, want)


@pytest.mark.parametrize("case", ["q6", "k6", "sparse_keys", "min_max_avg_grouped", "distinct_grouped"])
def test_host_failover_serves_the_blocked_answer(case, lineitem_segments, monkeypatch):
    """The heal path's failover and the oracle are one implementation: a
    query whose device launch fails is answered by ``execute_host``, with
    the payload the whole-segment pass gives."""
    pql = LINEITEM_CASES[case]
    request, want, _ = host_answer(lineitem_segments, pql, WHOLE, monkeypatch)
    monkeypatch.setattr(config, "HOST_BLOCK_ROWS", 128)
    executor = QueryExecutor()

    def broken(*args, **kwargs):
        raise RuntimeError("injected device failure")

    monkeypatch.setattr(executor, "_run_kernel", broken)
    healed = executor.execute(lineitem_segments, optimize_request(parse_pql(pql)))
    assert healed.cost.get("segmentsHost") == len(lineitem_segments)
    # 2,000 x 2,000 keys are over the device's capacity: there the host tier answers by plan
    assert executor.healing_stats()["hostFailovers"] == (0 if case == "sparse_keys" else 1)
    assert payload(request, healed) == payload(request, want)


# ------------------------------------------------------------ the auditor
class _Result:
    exceptions: list = []
    _served_tier = "device"


def _instance():
    from pinot_tpu.utils.metrics import ServerMetrics

    class _FlightRecorder:
        dumps: list = []

        def maybe_dump(self, kind, payload):
            self.dumps.append((kind, payload))

    class _Instance:
        name = "blocks"
        metrics = ServerMetrics("host-blocks-test")
        executor = QueryExecutor()
        flightrec = _FlightRecorder()

    return _Instance()


def _wait_for(condition, seconds: float = 20.0) -> None:
    deadline = time.monotonic() + seconds
    while not condition():
        assert time.monotonic() < deadline, "the auditor did not finish in time"
        time.sleep(0.01)


def test_auditor_runs_the_steps_on_its_thread_and_regains_control_between_them(lineitem_segments, monkeypatch):
    block = 128
    monkeypatch.setattr(config, "HOST_BLOCK_ROWS", block)
    inst = _instance()
    between = []
    real = ShadowAuditor._between_steps
    monkeypatch.setattr(ShadowAuditor, "_between_steps",
                        lambda self: (between.append(threading.current_thread().name), real(self))[1])
    auditor = ShadowAuditor(inst, sample_n=1, budget=SamplerBudget(per_s=1000.0, burst=8.0))
    try:
        request = optimize_request(parse_pql(LINEITEM_CASES["k6"]))
        served = QueryExecutor().execute_host_oracle(lineitem_segments, request)
        served._served_tier = "device"
        assert auditor.offer({"requestId": "r1", "table": "lineitem"}, request, lineitem_segments, served)
        _wait_for(lambda: inst.metrics.meter("audit.samples").count == 1)
        steps = len(lineitem_segments) * math.ceil(ROWS / block)
        assert between == [f"audit-{inst.name}"] * steps  # after every step, on the worker
        assert inst.metrics.timer("audit.stepMs").count == steps
        assert inst.metrics.timer("audit.shadowMs").count == 1
        snap = inst.metrics.snapshot()
        assert snap["gauges"]["audit.stepMaxMs"] == inst.metrics.timer("audit.stepMs").percentile(100) > 0
        assert inst.metrics.timer("audit.shadowMs").total_ms >= inst.metrics.timer("audit.stepMs").total_ms
        assert inst.metrics.meter("audit.divergences").count == 0
    finally:
        auditor.stop()


@pytest.mark.parametrize("per_s,burst,offers", [(1000.0, 64.0, 12), (0.0, 1.0, 5), (0.001, 3.0, 9)])
def test_audit_offered_samples_dropped_add_up(per_s, burst, offers, lineitem_segments, monkeypatch):
    monkeypatch.setattr(config, "HOST_BLOCK_ROWS", 256)
    inst = _instance()
    auditor = ShadowAuditor(inst, sample_n=2, budget=SamplerBudget(per_s=per_s, burst=burst))
    meter = lambda name: inst.metrics.meter("audit." + name).count
    try:
        request = optimize_request(parse_pql(LINEITEM_CASES["q1"]))
        served = QueryExecutor().execute_host_oracle(lineitem_segments, request)
        served._served_tier = "device"
        host_served = _Result()
        host_served._served_tier = "host"
        for i in range(2 * offers):
            auditor.offer({"requestId": f"r{i}", "table": "lineitem"}, request, lineitem_segments, served)
        # a reply the host served is its own oracle: never offered, however the counter falls
        auditor.offer({}, request, lineitem_segments, host_served)
        auditor.offer({}, request, lineitem_segments, host_served)
        assert meter("offered") == offers  # every 2nd eligible answer, before budget and queue
        _wait_for(lambda: meter("samples") + meter("dropped") + meter("errors") == offers)
        assert meter("errors") == 0 and meter("divergences") == 0
        took = 0 if per_s == 0 else min(offers, int(burst)) if per_s < 1 else offers
        queued = min(took, ShadowAuditor._QUEUE_MAX + 1)  # one may already be in its pass
        assert meter("samples") >= min(took, ShadowAuditor._QUEUE_MAX) and meter("samples") <= queued
        assert meter("dropped") == offers - meter("samples")
        snap = auditor.snapshot()
        assert (snap["offered"], snap["samples"], snap["dropped"]) == (offers, meter("samples"), meter("dropped"))
    finally:
        auditor.stop()


def test_streamed_oracle_catches_a_corrupted_answer(lineitem_segments, monkeypatch):
    """An answer off by more than the tolerance is still a divergence
    when the oracle comes in blocks: quarantined, with ``audit.detectMs``."""
    from pinot_tpu.common.faults import apply_result_corruption

    monkeypatch.setattr(config, "HOST_BLOCK_ROWS", 100)
    inst = _instance()
    auditor = ShadowAuditor(inst, sample_n=1, budget=SamplerBudget(per_s=1000.0, burst=8.0))
    try:
        request = optimize_request(parse_pql(LINEITEM_CASES["k6"]))
        honest = QueryExecutor().execute_host_oracle(lineitem_segments, request)
        honest._served_tier = "device"
        wrong = QueryExecutor().execute_host_oracle(lineitem_segments, request)
        wrong._served_tier = "device"
        apply_result_corruption(wrong, 1000.0)
        assert auditor.offer({"requestId": "ok", "table": "lineitem"}, request, lineitem_segments, honest)
        assert auditor.offer({"requestId": "bad", "table": "lineitem"}, request, lineitem_segments, wrong)
        _wait_for(lambda: inst.metrics.meter("audit.samples").count == 2)
        _wait_for(lambda: inst.metrics.meter("audit.divergences").count == 1)
        assert inst.metrics.meter("audit.quarantines").count == 1
        assert inst.metrics.timer("audit.detectMs").count == 1
        quarantined = inst.executor.audit_quarantined_snapshot()
        assert [q["tier"] for q in quarantined] == ["device"]
        assert inst.flightrec.dumps and inst.flightrec.dumps[-1][0] == "auditDivergence"
    finally:
        auditor.stop()


def test_chaos_twin_with_a_multi_step_oracle(tmp_path, monkeypatch):
    """``test_audit.py``'s chaos twin again with blocks smaller than a
    segment: corruption through ``DeviceFaultInjector.corrupt_results``
    is detected and quarantined by a pass of several steps."""
    from pinot_tpu.tools.cluster_harness import run_audit_divergence_scenario

    monkeypatch.setattr(config, "HOST_BLOCK_ROWS", 16)  # 48-row segments: three steps each
    steps = []
    real = ShadowAuditor._between_steps
    monkeypatch.setattr(ShadowAuditor, "_between_steps", lambda self: (steps.append(1), real(self))[1])
    # one client, so that the worker (every answer sampled, six steps a pass) keeps up with it
    res = run_audit_divergence_scenario(load_s=1.0, detect_budget_s=20.0, data_dir=str(tmp_path),
                                        clients=1, corrupt_n=12)
    assert res["detected"], res
    assert res["quarantined"] and res["quarantined"][0]["tier"] == "device"
    assert res["failedQueries"] == 0 and res["postQuarantineMismatches"] == 0
    assert len(steps) >= 6 * res["divergences"]


def test_a_stopped_auditor_abandons_its_pass(lineitem_segments, monkeypatch):
    monkeypatch.setattr(config, "HOST_BLOCK_ROWS", 8)  # 375 steps: stop falls inside the pass
    inst = _instance()
    auditor = ShadowAuditor(inst, sample_n=1, budget=SamplerBudget(per_s=1000.0, burst=8.0))
    real = ShadowAuditor._between_steps
    monkeypatch.setattr(ShadowAuditor, "_between_steps", lambda self: (time.sleep(0.01), real(self))[1])
    request = optimize_request(parse_pql(LINEITEM_CASES["k6"]))
    served = QueryExecutor().execute_host_oracle(lineitem_segments, request)
    served._served_tier = "device"
    assert auditor.offer({"requestId": "r", "table": "lineitem"}, request, lineitem_segments, served)
    _wait_for(lambda: inst.metrics.timer("audit.stepMs").count >= 2)
    auditor.stop()
    assert not auditor._thread.is_alive()
    assert inst.metrics.meter("audit.samples").count == 0
    assert inst.metrics.meter("audit.errors").count == 0
