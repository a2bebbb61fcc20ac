"""ClickBench's top users (PR 43): ``COUNT(*)`` by ``UserID``, TOP 10, a
group-by over more keys than a dense state holds, answered by the device
through the runs lowering (``kernel.groupby_lowering`` 'runs': the
table's rows sorted by group id once, a run a group, the per-server
trim, the live count and the digest made in the program).  Through a
networked cluster (every role over its real protocol) against the
benchmark's plain reference (``benchmark/reference_hits_topusers.py``:
numpy, nothing of the program) and against the host path
(``engine/host_fallback.py``); a filter, a sum and an average beside the
count; the shapes of keys a trim has to get right; what the planner still
sends to the host, by name; that nothing of the key space's size leaves
the program.  ``config.MAX_GROUP_CAPACITY`` is patched under the table's
users here, since 2^20 of them are too many for a test.  The cell itself
is rehearsed with the other seven in ``test_benchmark_rehearsal.py``."""
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.engine import config
from pinot_tpu.engine import kernel as kernel_mod
from pinot_tpu.engine import plan as plan_mod
from pinot_tpu.engine.results import MAX_TRIM_TIES, trim_group_candidates
from pinot_tpu.pql import parse_pql
from pinot_tpu.segment.columnar import build_segment_from_columns
from pinot_tpu.tools import datagen
from pinot_tpu.tools.cluster_harness import single_server_broker
from pinot_tpu.utils.metrics import prometheus_text

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEGMENTS, ROWS, USERS = 3, 20_000, 30_000
SEEDS = (4300, 2**31 + 43)
CAPACITY = 1 << 12  # the dense holders' bound here: under the 20,000 users of a table, over nothing else's key space


def _load(path: str):
    spec = importlib.util.spec_from_file_location("topusers_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref_mod = _load(os.path.join(BENCH, "reference_hits_topusers.py"))
SHAPES = {s["name"]: s for s in json.load(open(os.path.join(BENCH, "traffic", "hits_topusers_closed.json")))["shapes"]}
LINE_16 = ref_mod.render_pql("hits", SHAPES["top_users"])


def forget_programs():
    for cached in (kernel_mod.make_table_kernel, kernel_mod.make_packed_table_kernel,
                   kernel_mod.make_block_table_kernel, kernel_mod.make_packed_block_table_kernel):
        cached.cache_clear()


def make_segments(seed: int):
    return [datagen.synthetic_hits_users_segment(ROWS, seed=seed * 1000 + i, name=f"seg{i}", users=USERS) for i in range(SEGMENTS)]


@pytest.fixture(autouse=True)
def dense_holders_of_4096(monkeypatch):
    monkeypatch.setattr(config, "MAX_GROUP_CAPACITY", CAPACITY)
    forget_programs()
    yield
    forget_programs()


def host_answer(segments, pql: str):
    """The host path's own partial answer (engine/host_fallback.py)."""
    from pinot_tpu.engine.context import get_table_context
    from pinot_tpu.engine.host_fallback import execute_host

    return execute_host(segments, get_table_context(segments), parse_pql(pql), sum(s.num_docs for s in segments), None)


def held_to_the_host(reply: dict, host, top: int) -> None:
    """Every list of a reply against the host path's groups: the values
    are its n largest, in order, and each key's value is that key's."""
    for i, result in enumerate(reply["aggregationResults"]):
        have = [(tuple(g["group"]), float(g["value"])) for g in result["groupByResult"]]
        every = {tuple(key): float(partials[i].finalize()) for key, partials in host.groups.items()}
        best = sorted(every.values(), reverse=True)[:top]
        assert [v for _, v in have] == pytest.approx(best, rel=1e-9), result["function"]
        assert all(every[k] == pytest.approx(v, rel=1e-9) for k, v in have), result["function"]


# ---------------------------------------------------------------------------
# line 16 through a networked cluster
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=SEEDS)
def networked(request, tmp_path_factory):
    """Controller, one server and a broker over HTTP and TCP, the table
    uploaded through the controller with a real CRC; the segments; the
    reference over them."""
    from pinot_tpu.common.tableconfig import TableConfig
    from pinot_tpu.tools.cluster_harness import NetworkedCluster

    segments = make_segments(request.param)
    ref = ref_mod.Reference(SHAPES)
    for seg in segments:
        ref.add(seg)
    cluster = NetworkedCluster(num_servers=1, data_dir=str(tmp_path_factory.mktemp("topusers")))
    try:
        cluster.controller.add_schema(datagen.hits_users_schema())
        physical = cluster.controller.add_table(TableConfig(table_name="hits", table_type="OFFLINE", replication=1))
        for seg in segments:
            seg.metadata.crc = seg.compute_crc()
            seg.metadata.custom["dataCrc"] = True
            cluster.controller.upload_segment(physical, seg)
        cluster.wait(lambda: cluster.query("SELECT count(*) FROM hits").to_json().get("totalDocs") == SEGMENTS * ROWS,
                     what="the broker serving every segment")
        yield cluster, segments, ref
    finally:
        cluster.stop()


@pytest.mark.parametrize("against", ["reference", "host"])
def test_line_16_through_a_networked_cluster(networked, against):
    cluster, segments, ref = networked
    reply = cluster.query(LINE_16).to_json()
    cost = reply["cost"]
    assert cost.get("segmentsHost", 0) == 0 and cost["deviceMs"] > 0 and not reply.get("exceptions"), reply
    answer = ref.answers["top_users"]
    assert answer["keys"].size > CAPACITY  # over the dense holders' bound: the runs lowering answered
    if against == "host":
        held_to_the_host(reply, host_answer(segments, LINE_16), 10)
        return
    got = ref_mod.compare(reply, SHAPES["top_users"], answer, ref.rows)
    assert got == {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0}, (got, cost)
    assert cost["numGroupsLive"] == answer["keys"].size == answer["digest"]["numGroupsLive"]
    assert cost["groupStateSumSq"] == answer["digest"]["groupStateSumSq"]  # integers, exact in float64
    assert 10 <= cost["numGroupsKept"] <= 100 + MAX_TRIM_TIES


# ---------------------------------------------------------------------------
# a filter, a sum and an average beside the count
# ---------------------------------------------------------------------------

QUERIES = {
    "filter": "SELECT COUNT(*) FROM hits WHERE RegionID < 200 GROUP BY UserID TOP 10",
    "nobody": "SELECT COUNT(*) FROM hits WHERE RegionID < 200 AND AdvEngineID > 17 AND ResolutionWidth > 2000 AND EventDate > 15890 GROUP BY UserID TOP 10",
    "sum_avg": "SELECT COUNT(*), sum(AdvEngineID), avg(ResolutionWidth) FROM hits GROUP BY UserID TOP 7",
    "expression": "SELECT sum(ResolutionWidth*(1+AdvEngineID)), avg(AdvEngineID) FROM hits WHERE RegionID < 3000 GROUP BY UserID TOP 5",
    "two_keys": "SELECT COUNT(*), sum(ResolutionWidth) FROM hits GROUP BY RegionID, AdvEngineID, ResolutionWidth TOP 12",
}


@pytest.fixture(scope="module")
def table():
    return make_segments(SEEDS[0])


def _served_once(monkeypatch, segments, pql, table_name="hits"):
    """(the plan launched, the launch's span, the reply, the server's
    metrics as /metrics serves them, its host failovers) of one query."""
    from pinot_tpu.engine.executor import QueryExecutor

    plans = []
    run_kernel = QueryExecutor._run_kernel

    def spy(self, kernel, args, plan, *rest, **kw):
        plans.append((plan, kernel, args))
        return run_kernel(self, kernel, args, plan, *rest, **kw)

    monkeypatch.setattr(QueryExecutor, "_run_kernel", spy)
    broker = single_server_broker(table_name, segments)
    server = broker.local_servers[0]
    try:
        resp = broker.handle_pql(pql, trace=True)
        launches = [s for s in resp.trace_info["scopes"][server.name] if s["span"] == "laneDispatch"]
        return plans, launches, resp.to_json(), prometheus_text(server.metrics), server
    finally:
        server.shutdown()


@pytest.mark.parametrize("query", sorted(QUERIES))
def test_a_filter_sums_and_averages_equal_the_host_paths(monkeypatch, table, query):
    pql = QUERIES[query]
    plans, launches, reply, _, server = _served_once(monkeypatch, table, pql)
    assert not reply.get("exceptions") and reply["cost"].get("segmentsHost", 0) == 0, reply
    ((plan, _, _),) = plans
    assert kernel_mod.groupby_lowering(plan) == launches[0]["tags"]["groupby"] == "runs"
    assert plan.group_by.capacity > CAPACITY
    assert server.metrics.meter("groupby.lowering.runs").count == 1 and server.executor.healing_stats()["hostFailovers"] == 0
    host = host_answer(table, pql)
    held_to_the_host(reply, host, parse_pql(pql).group_by.top_n)
    assert reply["numDocsScanned"] == host.num_docs_scanned
    assert reply["cost"].get("numGroupsLive", 0) == len(host.groups) or len(host.groups) > 100  # the host trims too
    if query == "nobody":
        assert reply["cost"].get("numGroupsLive", 0) == 0 and reply["aggregationResults"][0]["groupByResult"] == []


# ---------------------------------------------------------------------------
# the keys a trim has to get right
# ---------------------------------------------------------------------------


def users_table(per_segment):
    """Segments of the hits schema from each segment's UserID a row."""
    out = []
    for i, users in enumerate(per_segment):
        users = np.asarray(users, dtype=np.int64)
        n = users.size
        columns = {
            "UserID": users,
            "RegionID": (users % 97).astype(np.int32),
            "AdvEngineID": (np.arange(n) % 5).astype(np.int32),
            "ResolutionWidth": (1000 + users % 13).astype(np.int32),
            "EventDate": np.full(n, 15_887 + i, dtype=np.int32),
        }
        out.append(build_segment_from_columns(datagen.hits_users_schema(), columns, n, "hits", f"edge{i}"))
    return out


def _edge_tables():
    ids = np.arange(1, 6001, dtype=np.int64) * 7919  # 6,000 users: over the patched bound
    rng = np.random.default_rng(43)
    once = [ids.copy() for _ in range(3)]  # every user one row a segment
    # one user with rows in every segment, one in a single segment, over a floor of one row a user
    spread = [np.concatenate([ids, np.full(50, ids[17])]) for _ in range(3)]
    spread[1] = np.concatenate([spread[1], np.full(120, ids[4000])])
    # ties at the TOP cut: twelve users of 40 rows for a TOP 10
    top_ties = [np.concatenate([ids, np.repeat(ids[100:112], 13 + (i == 0))]) for i in range(3)]
    # ties at the trim's boundary: 90 users ahead, then 30 tied where the trim of 100 cuts
    boundary = [np.concatenate([ids, np.repeat(ids[:90], 3 + np.arange(90) % 4), np.repeat(ids[200:230], 2)]) for _ in range(3)]
    # more boundary ties than MAX_TRIM_TIES admits: every row its own user, 12,000 of them
    own = np.arange(1, 12_001, dtype=np.int64) * 104_729
    return {
        "a_user_in_every_segment_and_one_in_one": ([rng.permutation(s) for s in spread], 10),
        "ties_at_the_top_cut": ([rng.permutation(s) for s in top_ties], 10),
        "ties_at_the_trims_boundary": ([rng.permutation(s) for s in boundary], 10),
        "every_row_one_user": ([np.full(5000, 77, dtype=np.int64)] * 2 + [ids], 10),  # and a tail, to be over the bound
        "every_row_its_own_user": ([own[:4000], own[4000:8000], own[8000:]], 10),
        "every_user_in_every_segment": (once, 3),
    }


def _block_edge_tables():
    """What a blocked pass over the sorted ids can get wrong (PR 49), the
    block read from the module: ``(users a segment, TOP, filter)``.  The
    table's rows in id order are the users' runs in ascending id, so a
    user's count places its run; a filter is ``(PQL, rows it keeps)`` over
    ``users_table``'s columns (RegionID = user % 97, AdvEngineID = the
    row's number in its segment % 5), ranges, which the scan answers."""
    block = kernel_mod._RUNS_BLOCK
    ids = np.arange(1, 6001, dtype=np.int64) * 7919  # 6,000 users: over the patched bound
    rng = np.random.default_rng(49)

    def dealt(users):  # the table's rows in any order, over three segments of unequal length
        users = rng.permutation(users)
        return [users[: block // 3], *np.array_split(users[block // 3:], 2)]

    def with_counts(counts):  # ids[i] has counts[i] rows, every other user one
        return dealt(np.concatenate([ids, *[np.full(c - 1, ids[i]) for i, c in counts.items()]]))

    def placed(users, slots):  # ``slots`` users of a region under 49 at rows whose AdvEngineID is 4, the others of them elsewhere
        low = users[users % 97 < 49]
        out = np.empty(users.size, dtype=np.int64)
        row = np.arange(users.size)
        here = np.concatenate([row[row % 5 == 4][:slots], row[row % 5 != 4][: low.size - slots]])
        out[here] = low
        out[np.setdiff1d(row, here)] = users[users % 97 >= 49]
        return out

    low_region_engine_4 = ("WHERE RegionID < 49 AND AdvEngineID > 3", lambda users, row: (users % 97 < 49) & (row % 5 == 4))
    return {
        "a_run_over_several_whole_blocks": (with_counts({2999: 3 * block + 17, 3000: 2}), 10, None),
        # run 0 fills block 0, runs 1 and 2 half a block each: run 2 ends on a block's last row, run 3 starts on a block's first
        "runs_that_end_and_start_on_a_blocks_edge": (with_counts({0: block, 1: block // 2, 2: block // 2, 5999: 3}), 10, None),
        "rows_that_are_no_whole_number_of_blocks": ([ids[:2000], ids[2000:4000], np.concatenate([ids[4000:], ids[:7]])], 10, None),
        "the_longest_run_a_power_of_two": (with_counts({41: 4096, 5000: 1024}), 10, None),
        "the_longest_run_a_power_of_two_and_one": (with_counts({41: 4097, 5000: 1024}), 10, None),
        # run 0 fills block 0, runs 1 and 2 half a block each, then a run over three blocks, a power of two and one more
        "every_boundary_in_one_table": (with_counts({0: block, 1: block // 2, 2: block // 2, 2999: 3 * block + 17, 41: 4096, 5000: 4097}), 10, None),
        "every_row_filtered": ([placed(ids, 0)] * 2, 10, low_region_engine_4),
        "one_live_row": ([placed(ids, 1), placed(ids, 0)], 10, low_region_engine_4),
    }


BLOCK_EDGES = _block_edge_tables()
EDGES = {**{case: (*table, None) for case, table in _edge_tables().items()}, **BLOCK_EDGES}


@pytest.mark.parametrize("case", sorted(EDGES))
def test_the_trim_the_live_count_and_the_digest_are_numpys(monkeypatch, case):
    per_segment, top, where = EDGES[case]
    segments = users_table(per_segment)
    pql = f"SELECT COUNT(*) FROM hits {where[0] if where else ''} GROUP BY UserID TOP {top}"
    plans, launches, reply, _, server = _served_once(monkeypatch, segments, pql)
    assert launches[0]["tags"]["groupby"] == "runs" and not reply.get("exceptions"), reply
    kept = [users[where[1](users, np.arange(users.size))] if where else users for users in map(np.asarray, per_segment)]
    users, counts = np.unique(np.concatenate(kept), return_counts=True)
    cost = reply["cost"]
    assert cost.get("numGroupsLive", 0) == users.size and cost.get("segmentsHost", 0) == 0
    assert cost.get("groupStateSumSq", 0.0) == float(np.sum(counts.astype(np.int64) ** 2))
    # the per-server trim over a dense state of the same counts, key order the dictionary's (ascending value)
    assert cost.get("numGroupsKept", 0) == trim_group_candidates([counts.astype(np.float64)], [False], top, users.size).size
    groups = reply["aggregationResults"][0]["groupByResult"]
    assert [int(float(g["value"])) for g in groups] == sorted(counts.tolist(), reverse=True)[:top]
    count_of = dict(zip(users.tolist(), counts.tolist()))
    assert all(count_of[int(g["group"][0])] == int(float(g["value"])) for g in groups)
    assert len({g["group"][0] for g in groups}) == min(top, users.size)
    if case == "every_row_its_own_user":
        assert cost["numGroupsKept"] == MAX_TRIM_TIES  # the cap on boundary ties, as the dense trim has it


# the same boundaries with columns carried: every unfiltered one in ONE table (a compile a table is what a case costs)
CARRIED_EDGES = ("every_boundary_in_one_table", "every_row_filtered", "one_live_row")


@pytest.mark.parametrize("case", CARRIED_EDGES)
def test_a_blocks_edges_with_a_sum_and_an_average_carried_equal_the_host_paths(monkeypatch, case):
    per_segment, top, where = BLOCK_EDGES[case]
    segments = users_table(per_segment)
    pql = f"SELECT COUNT(*), sum(AdvEngineID), avg(ResolutionWidth) FROM hits {where[0] if where else ''} GROUP BY UserID TOP {top}"
    plans, launches, reply, _, server = _served_once(monkeypatch, segments, pql)
    assert launches[0]["tags"]["groupby"] == "runs" and not reply.get("exceptions"), reply
    assert reply["cost"].get("segmentsHost", 0) == 0 and server.executor.healing_stats()["hostFailovers"] == 0
    host = host_answer(segments, pql)
    held_to_the_host(reply, host, top)
    assert reply["numDocsScanned"] == host.num_docs_scanned
    assert reply["cost"].get("numGroupsLive", 0) == len(host.groups) or len(host.groups) > 100  # the host trims too


@pytest.mark.parametrize("with_dist", [False, True])
def test_the_pass_hands_on_its_carry_and_its_summaries_over_many_blocks_and_tiles(monkeypatch, with_dist):
    """``kernel._run_lengths`` alone against numpy, its block cut to one
    tile so that 1,100 blocks fill two tiles of summaries: a run over
    several blocks, runs on both sides of every kind of edge, filtered
    rows last."""
    import jax.numpy as jnp

    monkeypatch.setattr(kernel_mod, "_RUNS_BLOCK", 1024)
    rng = np.random.default_rng(49)
    n, capacity = 1100 * 1024, 100_000
    lengths = np.concatenate([[1024, 512, 512, 3000, 1], rng.integers(1, 40, size=60_000)])
    lengths = lengths[np.cumsum(lengths) <= n - 5000]
    ids = np.concatenate([np.repeat(np.sort(rng.choice(capacity, lengths.size, replace=False)), lengths),
                          np.full(n - lengths.sum(), capacity)]).astype(np.int32)
    order, dist, ends, squares, longest = kernel_mod._run_lengths(jnp.asarray(ids), capacity, with_dist)
    last = np.cumsum(lengths) - 1
    want = np.full(n, np.iinfo(np.int32).min, dtype=np.int32)
    want[last] = lengths
    assert np.array_equal(np.asarray(order), want)
    by_block = want.reshape(-1, 1024)
    assert np.array_equal(np.asarray(ends), np.sum(by_block > 0, axis=1)) and np.array_equal(np.asarray(longest), by_block.max(axis=1))
    assert np.array_equal(np.asarray(squares), np.sum(np.where(by_block > 0, by_block.astype(np.float64) ** 2, 0), axis=1))
    if with_dist:
        first = np.repeat(np.concatenate([[0], last + 1]), np.concatenate([lengths, [n - lengths.sum()]]))
        assert np.array_equal(np.asarray(dist), np.arange(n) - first)
    else:
        assert dist is None


# ---------------------------------------------------------------------------
# what still goes to the host above the bound, by name
# ---------------------------------------------------------------------------

REFUSED = {
    "min": ("SELECT min(ResolutionWidth) FROM hits GROUP BY UserID TOP 10", "aggregate:min"),
    "max": ("SELECT COUNT(*), max(ResolutionWidth) FROM hits GROUP BY UserID TOP 10", "aggregate:max"),
    "minmaxrange": ("SELECT minmaxrange(ResolutionWidth) FROM hits GROUP BY UserID TOP 10", "aggregate:minmaxrange"),
    "distinctcount": ("SELECT distinctcount(RegionID) FROM hits GROUP BY UserID TOP 10", "aggregate:distinctcount"),
    "distinctcounthll": ("SELECT distinctcounthll(RegionID) FROM hits GROUP BY UserID TOP 10", "aggregate:distinctcounthll"),
    "percentile": ("SELECT percentile50(ResolutionWidth) FROM hits GROUP BY UserID TOP 10", "aggregate:percentile50"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_the_host_answers_by_name_in_explain_and_in_a_meter(table, case):
    pql, reason = REFUSED[case]
    assert plan_mod.group_runs_host_reason(parse_pql(pql), CAPACITY + 1) == reason
    assert plan_mod.group_runs_host_reason(parse_pql(pql), CAPACITY) is None  # a dense holder takes it
    broker = single_server_broker("hits", table[:1])
    server = broker.local_servers[0]
    try:
        node = broker.handle_pql("EXPLAIN " + pql).explain["servers"][0]
        records = [r for r in node["segments"] if r["tier"] == "host"]
        assert records and all(r["groupByHostReason"] == reason and reason in r["reason"] for r in records), node
        reply = broker.handle_pql(pql).to_json()
        assert reply["cost"]["segmentsHost"] == 1 and not reply.get("exceptions")
        assert server.metrics.meter("groupby.forcedHost.aggregate").count == 1
        assert "pinot_tpu_server_groupby_forcedHost_aggregate_total" in prometheus_text(server.metrics)
    finally:
        server.shutdown()


def test_a_multi_value_key_a_mesh_no_top_and_a_wide_key_space_are_named(monkeypatch):
    line_16 = parse_pql(LINE_16)
    assert plan_mod.group_runs_host_reason(line_16, 17_630_976) is None  # the cell's own plan: the device's
    assert plan_mod.group_runs_host_reason(line_16, 17_630_976, mv_key=True) == "multiValueKey"
    assert plan_mod.group_runs_host_reason(line_16, 17_630_976, mesh=True) == "mesh"
    assert plan_mod.group_runs_host_reason(line_16, config.max_key_space() + 1) == "keySpace"
    assert plan_mod.group_runs_host_reason(parse_pql(LINE_16.replace("TOP 10", "TOP 0")), 17_630_976) == "noTopN"
    five = "SELECT sum(a), sum(b), avg(c), sum(d) FROM t GROUP BY k TOP 10"
    assert plan_mod.group_runs_host_reason(parse_pql(five), 17_630_976) == "measures"
    assert plan_mod.group_runs_host_reason(parse_pql(five), 1 << 10) is None


def test_a_multi_value_key_over_the_bound_is_the_hosts(monkeypatch):
    schema = Schema("tagged", dimensions=[FieldSpec("tag", DataType.INT_ARRAY if hasattr(DataType, "INT_ARRAY") else DataType.INT,
                                                    single_value=False)],
                    metrics=[FieldSpec("m", DataType.INT, FieldType.METRIC)])
    n = 6000
    flat = np.arange(2 * n, dtype=np.int32)  # two tags a row, every tag its own: 12,000 keys
    segment = build_segment_from_columns(schema, {"tag": (flat, np.arange(0, 2 * n + 1, 2)), "m": np.ones(n, dtype=np.int32)},
                                         n, "tagged", "tagged0")
    broker = single_server_broker("tagged", [segment])
    server = broker.local_servers[0]
    try:
        pql = "SELECT COUNT(*) FROM tagged GROUP BY tag TOP 3"
        record = broker.handle_pql("EXPLAIN " + pql).explain["servers"][0]["segments"][0]
        assert record["tier"] == "host" and record["groupByHostReason"] == "multiValueKey"
        reply = broker.handle_pql(pql).to_json()
        assert reply["cost"]["segmentsHost"] == 1 and server.metrics.meter("groupby.forcedHost.multiValueKey").count == 1
    finally:
        server.shutdown()


def test_a_mesh_keeps_the_hosts_answer_and_says_so(monkeypatch, table):
    """The four-chip path (``PINOT_TPU_MESH_SHAPE``): the planner is told
    the query would run sharded, and the host answers, by name."""
    from pinot_tpu.engine.context import get_table_context

    request = parse_pql(LINE_16)
    ctx = get_table_context(table)
    assert not plan_mod.plan_forced_host(request, ctx)
    assert plan_mod.plan_forced_host(request, ctx, mesh=True)
    assert plan_mod.group_by_host_reason(request, ctx, mesh=True) == "mesh"


# ---------------------------------------------------------------------------
# nothing of the key space's size leaves the program; /metrics; the generator
# ---------------------------------------------------------------------------


def test_no_output_of_the_lowering_has_the_key_spaces_size(monkeypatch, table):
    plans, launches, reply, served, server = _served_once(monkeypatch, table, QUERIES["sum_avg"])
    ((plan, kernel, args),) = plans
    args = args() if callable(args) else args
    K = plan.group_by.capacity
    assert K > CAPACITY and kernel_mod.output_reducers(plan) == {"num_docs": "sum", "gb_rows": "runs"}
    assert not kernel_mod.plan_chunkable(plan) and kernel_mod.zone_blocks(plan) == "gathered"
    assert kernel_mod.groupby_operands(plan) == "staged" and kernel_mod.groupby_cells(plan)[0] == 0
    jaxpr = jax.make_jaxpr(kernel_mod.make_table_kernel(plan))(*args)
    trim = max(5 * plan.group_by.top_n, 100)
    places = 3 * (trim + max(MAX_TRIM_TIES, trim))  # three aggregates' candidate lists
    rows = SEGMENTS * ROWS
    for var in jaxpr.jaxpr.outvars:
        size = int(np.prod(var.aval.shape))
        assert size <= min(places, 3 * rows) and size != K, var.aval
    # and what the finalize was handed is what the meter says: kilobytes
    fetched = server.metrics.meter("groupby.stateFetchBytes").count
    assert 0 < fetched < 64 * places
    for name in ("groupby_lowering_runs_total", "groupby_stateFetchBytes_total", "phase_globalDictBuild_ms_count"):
        assert f"\npinot_tpu_server_{name}{{" in served, name
    for name in ("groupby_lowering_runs_total", "groupby_stateFetchBytes_total", "phase_globalDictBuild_ms"):
        assert f"# HELP pinot_tpu_server_{name} " in served, name  # the catalog describes them


def test_the_global_dictionary_is_built_once_a_table_under_its_timer():
    broker = single_server_broker("hits", make_segments(77))  # segments no context has seen
    server = broker.local_servers[0]
    try:
        for _ in range(2):
            assert not broker.handle_pql(LINE_16).to_json().get("exceptions")
        timer = server.metrics.timer("phase.globalDictBuild")
        assert timer.count == 1 and timer.total_ms > 0  # UserID's, by the first query; the second found it
    finally:
        server.shutdown()


@pytest.mark.parametrize("answer", ["missing", "aggregate:count"])
def test_the_cells_generator_fails_at_once_where_the_planner_says_host(monkeypatch, answer):
    generator = _load(os.path.join(BENCH, "hits_topusers_table.py"))
    assert generator.segment(64, seed=1, name="hits0").num_docs == 64  # this program: the device answers
    if answer == "missing":
        monkeypatch.delattr(plan_mod, "group_runs_host_reason")
    else:
        monkeypatch.setattr(plan_mod, "group_runs_host_reason", lambda request, capacity: answer)
    with pytest.raises(RuntimeError, match="answered from the chip"):
        generator.segment(64, seed=1, name="hits0")
