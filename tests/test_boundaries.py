"""Layer boundaries (PR 25): the one helper of ``utils/trace.py``, the
wall-time cover of a query over HTTP, the jitted programs' names, the
lane's device-busy window and the profiler's capture summary."""
import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

import pinot_tpu.utils.trace as trace_mod
from pinot_tpu.utils.metrics import ServerMetrics
from pinot_tpu.utils.tailsample import TailSampler, phase_self_ms
from pinot_tpu.utils.trace import TraceContext, boundary, measured, phases

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# -- the helper -------------------------------------------------------------


def test_boundary_emits_timer_span_and_annotation_from_one_call(tmp_path):
    import jax
    from jax.profiler import ProfileData

    metrics = ServerMetrics("s0")
    ctx = TraceContext(enabled=True, scope="s0", trace_id="rid-7")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with boundary("outer", ctx, metrics.timer("phase.outer")):
            with boundary("launch", ctx, metrics.timer("phase.launch"), program="pinot_scan_agg_0"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    # (a) the timers
    assert metrics.timer("phase.launch").count == 1 and metrics.timer("phase.outer").count == 1
    assert 2.0 <= metrics.timer("phase.launch").total_ms <= metrics.timer("phase.outer").total_ms
    # (b) the spans, nested, with the timer's milliseconds
    outer, launch = ctx.to_dict()["s0"]
    assert (outer["span"], launch["span"], launch["parent"]) == ("outer", "launch", outer["id"])
    assert launch["ms"] == pytest.approx(metrics.timer("phase.launch").total_ms, abs=1e-3)
    assert launch["tags"] == {"program": "pinot_scan_agg_0"}
    # (c) the same intervals on the profiler's host plane, with the request id
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    events = {
        e.name: (e.duration_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name.startswith("pinot:")
    }
    assert set(events) == {"pinot:outer", "pinot:launch"}
    assert events["pinot:launch"][1] == {"rid": "rid-7", "program": "pinot_scan_agg_0"}
    assert events["pinot:launch"][0] / 1e6 == pytest.approx(launch["ms"], abs=0.5)


@pytest.mark.parametrize("ctx", [None, trace_mod.NULL_TRACE, TraceContext(enabled=False)])
def test_boundary_allocates_no_span_when_the_tree_is_disabled(ctx):
    metrics = ServerMetrics("s0")
    before = trace_mod.SPAN_ALLOCATIONS
    with boundary("parse", ctx, metrics.timer("phase.parse"), requestId="r") as b:
        b.tag(coalesced=True)
        measured("queueWait", 1.5, ctx, metrics.timer("phase.schedulerWait"))
    cursor = phases(lambda name, **tags: boundary(name, ctx, metrics.timer(f"phase.{name}"), **tags))
    cursor.enter("staging")
    cursor.relabel("indexPath")
    cursor.stop()
    assert trace_mod.SPAN_ALLOCATIONS == before
    assert b.span_id is None
    # the timers are kept all the same
    assert metrics.timer("phase.parse").count == 1
    assert metrics.timer("phase.schedulerWait").total_ms == 1.5
    assert metrics.timer("phase.indexPath").count == 1 and metrics.timer("phase.staging").count == 0


def test_open_spans_read_their_time_so_far_and_reserved_ids_parent_other_threads():
    ctx = TraceContext(enabled=True, scope="b0", trace_id="r")
    root = boundary("httpTotal", ctx).start()
    aid = ctx.reserve()

    def pool_thread():
        with boundary("serializeRequest", ctx, parent=aid):
            pass

    t = threading.Thread(target=pool_thread)
    t.start()
    t.join()
    ctx.add("serverAttempt", 1.0, span_id=aid)
    time.sleep(0.002)
    cut = {s["span"]: s for s in ctx.to_dict()["b0"]}
    assert cut["httpTotal"]["tags"]["open"] is True and cut["httpTotal"]["ms"] >= 2.0
    assert cut["serializeRequest"]["parent"] == aid == cut["serverAttempt"]["id"]
    assert cut["serverAttempt"]["parent"] == cut["httpTotal"]["id"]
    root.stop()
    done = {s["span"]: s for s in ctx.to_dict()["b0"]}
    assert "tags" not in done["httpTotal"] and done["httpTotal"]["ms"] >= cut["httpTotal"]["ms"]


def test_attach_gives_an_open_boundary_its_span_with_its_own_start():
    """A connection's boundaries begin before the request is read: the
    tree and the timer come later, the start stays the boundary's."""
    metrics = ServerMetrics("b0")
    t_open = time.time() * 1000.0  # the wall clock as the caller reads it when the two begin
    early = boundary("httpConnection").start()
    inner = boundary("httpHead").start()
    time.sleep(0.003)
    ctx = TraceContext(enabled=True, scope="b0", trace_id="r-1")
    early.attach(ctx, metrics.timer("httpConnection"), t_open, requestId="r-1")
    measured("httpAccept", 0.25, ctx, metrics.timer("phase.httpAccept"), start_ms=t_open - 0.25)
    inner.attach(ctx, metrics.timer("phase.httpHead"), t_open)
    inner.stop()
    with boundary("httpTotal", ctx):
        pass
    cut = {s["span"]: s for s in ctx.to_dict()["b0"]}
    assert cut["httpConnection"]["tags"] == {"requestId": "r-1", "open": True}
    early.stop()
    root, accept, head, total = ctx.to_dict()["b0"]
    assert [s["span"] for s in (root, accept, head, total)] == ["httpConnection", "httpAccept", "httpHead", "httpTotal"]
    assert root["parent"] is None and {accept["parent"], head["parent"], total["parent"]} == {root["id"]}
    # the spans begin where the boundaries began, not where the tree was made
    assert root["startMs"] == head["startMs"] == int(t_open * 1000.0) / 1000.0 < total["startMs"]
    assert accept["startMs"] == pytest.approx(t_open - 0.25, abs=0.002) and accept["ms"] == 0.25
    assert 3.0 <= head["ms"] <= root["ms"] and total["startMs"] >= head["startMs"] + head["ms"] - 1.0
    assert head["ms"] == pytest.approx(metrics.timer("phase.httpHead").total_ms, abs=1e-3)
    assert metrics.timer("httpConnection").count == metrics.timer("phase.httpAccept").count == 1


@pytest.mark.parametrize("ctx", [None, trace_mod.NULL_TRACE, TraceContext(enabled=False)])
def test_attach_allocates_no_span_when_the_tree_is_disabled(ctx):
    metrics = ServerMetrics("b0")
    before = trace_mod.SPAN_ALLOCATIONS
    early = boundary("httpHead").start()
    early.attach(ctx, metrics.timer("phase.httpHead"), 1.0, requestId="r")
    measured("httpAccept", 0.2, ctx, metrics.timer("phase.httpAccept"), start_ms=1.0)
    early.stop()
    assert trace_mod.SPAN_ALLOCATIONS == before and early.span_id is None
    assert metrics.timer("phase.httpHead").count == 1 and metrics.timer("phase.httpAccept").total_ms == 0.2
    # a boundary that is over takes the timer and no span
    early.attach(TraceContext(enabled=True, scope="b0"), metrics.timer("phase.httpHead"), 1.0)
    assert trace_mod.SPAN_ALLOCATIONS == before and metrics.timer("phase.httpHead").count == 1


# -- a query's wall time, from inside the program ----------------------------


@pytest.fixture(scope="module")
def http_cluster(tmp_path_factory):
    from pinot_tpu.tools.cluster_harness import InProcessCluster
    from pinot_tpu.tools.datagen import lineitem_schema, synthetic_lineitem_segment

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path_factory.mktemp("data")), http=True)
    try:
        table = cluster.add_offline_table(lineitem_schema())
        for i in range(2):
            cluster.upload(table, synthetic_lineitem_segment(5000, seed=i, name=f"seg{i}"))
        yield cluster
    finally:
        cluster.stop()
        for server in cluster.servers:
            server.shutdown()


def _post(cluster, pql: str, **extra) -> dict:
    req = urllib.request.Request(
        f"http://{cluster.http.host}:{cluster.http.port}/query",
        json.dumps(dict(extra, pql=pql)).encode(), {"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req, timeout=60).read())


def _get(cluster, path: str, **params):
    url = f"http://{cluster.http.host}:{cluster.http.port}{path}"
    if params:
        url += "?" + urllib.parse.urlencode(params)
    body = urllib.request.urlopen(url, timeout=60).read()
    return body.decode() if path == "/metrics" else json.loads(body)


def _finished_tail(cluster, request_id: str) -> dict:
    """The retained entry once the connection is closed: the client has
    its reply before ``httpClose`` ends and the finished tree is handed over."""
    for _ in range(200):
        entry = cluster.broker.tail.get(request_id)
        if entry and any(s["span"] == "httpClose" for s in entry["scopes"][cluster.broker.name]):
            return entry
        time.sleep(0.01)
    raise AssertionError(f"no finished tail for {request_id}: {entry}")


K6 = ("SELECT sum(l_extendedprice), count(*) FROM lineitem "
      "GROUP BY l_returnflag, l_linestatus TOP 10")

CONNECTION_SPANS = ("httpConnection", "httpAccept", "httpHead", "httpTotal", "httpClose")
BROKER_SPANS = {*CONNECTION_SPANS, "httpRead", "query", "parse", "route", "scatterGather", "serverAttempt",
                "attemptSubmit", "poolQueue", "serializeRequest", "deserializeResult", "gatherWake", "reduce", "bookkeeping",
                "render"}
SERVER_SPANS = {"serverQuery", "queueWait", "serverParse", "segmentAcquire", "planAndExecute", "prune",
                "staging", "planBuild", "kernelPrep", "laneWait", "laneQueue", "laneDispatch", "laneDeliver",
                "laneWake", "planExec", "deviceWait", "d2hUnpack", "finalize", "workerWake",
                "serverBookkeeping"}


def test_self_times_under_httpTotal_sum_to_its_duration(http_cluster, monkeypatch):
    cluster = http_cluster
    _post(cluster, K6)  # warm: staging and the compile are not this test's
    monkeypatch.setattr(cluster.broker.tail, "slow_ms", 0.0)  # keep every tree
    reply = _post(cluster, K6, trace=True)
    assert not reply["exceptions"]
    # the reply's own tree is cut before the reply is rendered: its root is still open
    cut = {s["span"]: s for s in reply["traceInfo"]["scopes"][cluster.broker.name]}
    assert cut["httpTotal"]["tags"]["open"] is True and "render" not in cut
    # the finished one is the retained tail's
    entry = _finished_tail(cluster, reply["requestId"])
    scopes = entry["scopes"]
    server = cluster.servers[0].name
    assert {s["span"] for s in scopes[cluster.broker.name]} == BROKER_SPANS
    assert {s["span"] for s in scopes[server]} == SERVER_SPANS
    spans = [s for part in scopes.values() for s in part]
    assert not any("open" in s.get("tags", {}) for s in spans)
    # one tree: every span hangs, through its parents, under the connection's root
    by_id = {s["id"]: s for s in spans}
    (root,) = [s for s in spans if s["parent"] is None]
    assert root["span"] == "httpConnection"
    for s in spans:
        while s["parent"] is not None:
            s = by_id[s["parent"]]
        assert s is root
    # and under httpTotal the self times add up to it: nothing is counted twice, nothing hangs outside
    (total,) = [s for s in spans if s["span"] == "httpTotal"]

    def under_total(s) -> bool:
        while s is not total and s["parent"] is not None:
            s = by_id[s["parent"]]
        return s is total

    subtree = {scope: [dict(s, parent=None) if s is total else s for s in part if under_total(s)]
               for scope, part in scopes.items()}
    assert sum(phase_self_ms(subtree).values()) == pytest.approx(total["ms"], rel=0.05)
    assert entry["phaseSelfMs"]["render"] > 0
    # the launch and the wait say which program
    program = {s["span"]: s.get("tags", {}).get("program") for s in scopes[server]}
    assert program["laneDispatch"] == program["deviceWait"]
    assert program["laneDispatch"].startswith("pinot_scan_gb6_")
    # the timers an operator or the benchmark reads are the same intervals
    broker, srv = cluster.broker.metrics, cluster.servers[0].metrics
    assert broker.timer("httpTotal").count == broker.timer("phase.render").count >= 2
    for name in ("phase.deserializeRequest", "phase.serializeResult", "phase.laneQueue", "phase.deviceWait"):
        assert srv.timer(name).count >= 2, name


def test_direct_call_keeps_one_root_and_a_disabled_sampler_allocates_nothing(http_cluster, monkeypatch):
    broker = http_cluster.broker
    resp = broker.handle_pql(K6, trace=True)
    spans = [s for part in resp.trace_info["scopes"].values() for s in part]
    assert [s["span"] for s in spans if s["parent"] is None] == ["query"]
    assert not any(s["span"] in ("bookkeeping", "httpTotal") for s in spans)
    monkeypatch.setattr(broker.tail, "enabled", False)
    _post(http_cluster, K6)
    before = trace_mod.SPAN_ALLOCATIONS
    assert not _post(http_cluster, K6)["exceptions"]
    assert trace_mod.SPAN_ALLOCATIONS == before
    assert broker.metrics.timer("phase.bookkeeping").count >= 3


# -- a connection's life, accept to close (PR 39) -------------------------------


@pytest.mark.parametrize("method", ["POST", "GET"])
def test_a_query_leaves_one_tree_under_its_connection(http_cluster, monkeypatch, method):
    cluster = http_cluster
    monkeypatch.setattr(cluster.broker.tail, "slow_ms", 0.0)  # keep every tree
    reply = _post(cluster, K6) if method == "POST" else _get(cluster, "/query", pql=K6)
    assert not reply["exceptions"]
    scopes = _finished_tail(cluster, reply["requestId"])["scopes"]
    spans = [s for part in scopes.values() for s in part]
    by_id = {s["id"]: s for s in spans}
    (root,) = [s for s in spans if s["parent"] is None]
    assert root["span"] == "httpConnection" and root["tags"] == {"requestId": reply["requestId"]}
    end = lambda s: s["startMs"] + s["ms"]
    # startMs is cut and ms rounded to the microsecond; a span's start is the wall clock's and its length the
    # monotonic clock's, read one after the other, so an end set beside another span's start gets a millisecond
    slack, loose = 0.002, 1.0
    # httpHead, httpTotal, httpClose follow one another under the root and fill it
    accept, head, total, close = (next(s for s in spans if s["span"] == n) for n in CONNECTION_SPANS[1:])
    assert {s["parent"] for s in (accept, head, total, close)} == {root["id"]}
    assert root["startMs"] <= head["startMs"] < total["startMs"] < close["startMs"]
    assert end(head) <= total["startMs"] + loose and end(total) <= close["startMs"] + loose
    assert end(close) <= end(root) + loose
    assert 0.8 * root["ms"] <= head["ms"] + total["ms"] + close["ms"] <= root["ms"] + 3 * slack
    # httpAccept is the accept loop's: it lies before the connection's thread ran and ends where that begins
    assert accept["ms"] > 0 and accept["startMs"] < root["startMs"]
    assert end(accept) <= head["startMs"] + loose and end(accept) == pytest.approx(root["startMs"], abs=loose)
    # no other span is older than its parent
    for s in spans:
        if s["parent"] is not None and s is not accept:
            assert s["startMs"] >= by_id[s["parent"]]["startMs"] - slack, (s, by_id[s["parent"]])
    # the self times add up to the life from accept to close, the root's and before it httpAccept's: the three
    # around the handler are leaves, and the root keeps what its children leave of it (the few statements between
    # them, less httpAccept, which is counted under it and lies before it); httpTotal's own subtree is the
    # test's above
    self_ms = phase_self_ms(scopes)
    assert [self_ms[n] for n in ("httpAccept", "httpHead", "httpClose")] == [accept["ms"], head["ms"], close["ms"]]
    left = root["ms"] - accept["ms"] - head["ms"] - total["ms"] - close["ms"]
    assert self_ms.get("httpConnection", 0.0) == pytest.approx(max(0.0, left), abs=slack)
    under_total = sum(self_ms.values()) - sum(self_ms.get(n, 0.0) for n in CONNECTION_SPANS if n != "httpTotal")
    assert under_total >= total["ms"] - 0.01  # floored, so never under it; over it only where threads overlapped


CONNECTION_TIMERS = ("phase.httpAccept", "phase.httpHead", "phase.httpClose", "httpConnection", "httpTotal")


def _connection_counts(cluster) -> list:
    for _ in range(2000):  # the last connection's close ends after its client has the reply
        if not cluster.http._httpd.lives:
            break
        time.sleep(0.005)
    return [cluster.broker.metrics.timer(name).count for name in CONNECTION_TIMERS]


@pytest.mark.parametrize("path", ["/metrics", "/health", "/debug/queries", "/debug/tails", "/nowhere"])
def test_connection_timers_count_queries_alone(http_cluster, path):
    """One update a query of each timer a reader divides by ``httpTotal``'s
    count; a path that is not a query marks none and builds no tree."""
    before = _connection_counts(http_cluster)
    assert not _post(http_cluster, K6)["exceptions"]
    assert not _get(http_cluster, "/query", pql=K6)["exceptions"]
    after = _connection_counts(http_cluster)
    assert [b - a for a, b in zip(before, after)] == [2] * len(CONNECTION_TIMERS)
    allocated, ids = trace_mod.SPAN_ALLOCATIONS, http_cluster.broker._request_id
    try:
        _get(http_cluster, path)
    except urllib.error.HTTPError as e:
        assert (path, e.code) == ("/nowhere", 404)
    assert _connection_counts(http_cluster) == after
    assert (trace_mod.SPAN_ALLOCATIONS, http_cluster.broker._request_id) == (allocated, ids)
    assert not http_cluster.http._httpd.lives  # every connection took itself out


def test_a_disabled_sampler_builds_no_span_for_the_connection(http_cluster, monkeypatch):
    """``PINOT_TPU_TAIL_TRACE=0``: the timers are kept, no span dict is built."""
    assert TailSampler(enabled=None).enabled is True
    monkeypatch.setenv("PINOT_TPU_TAIL_TRACE", "0")
    assert TailSampler(enabled=None).enabled is False
    monkeypatch.setattr(http_cluster.broker.tail, "enabled", False)
    _post(http_cluster, K6)
    counts, before = _connection_counts(http_cluster), trace_mod.SPAN_ALLOCATIONS
    assert not _post(http_cluster, K6)["exceptions"]
    assert not _get(http_cluster, "/query", pql=K6)["exceptions"]
    assert [b - a for a, b in zip(counts, _connection_counts(http_cluster))] == [2] * len(CONNECTION_TIMERS)
    assert trace_mod.SPAN_ALLOCATIONS == before


def test_connection_spans_are_on_the_profilers_host_plane(http_cluster, monkeypatch, tmp_path):
    """Under a capture the three intervals around the handler are
    ``pinot:`` annotations as long as their spans, so that
    ``trace_reduce`` gives them the idle they cover."""
    import jax
    from jax.profiler import ProfileData

    cluster = http_cluster
    _post(cluster, K6)
    _connection_counts(cluster)  # the warm-up's connection is closed before the capture begins
    monkeypatch.setattr(cluster.broker.tail, "slow_ms", 0.0)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        reply = _post(cluster, K6)
        entry = _finished_tail(cluster, reply["requestId"])
        # the accept loop closes its annotation when it has the interpreter back: before it takes the next connection
        _get(cluster, "/health")
    finally:
        jax.profiler.stop_trace()
    rid = reply["requestId"]
    spans = {s["span"]: s for s in entry["scopes"][cluster.broker.name]}
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("pinot:http"):
                        events.setdefault(e.name[len("pinot:"):], []).append(
                            (e.start_ns, e.duration_ns / 1e6, dict(e.stats)))
    assert set(events) == {"httpAccept", "httpConnection", "httpHead", "httpTotal", "httpRead", "httpClose"}
    # the request id is known at the handler's entry: the root, open then, gets it (/health's has none);
    # the three around the handler are measured, and marked for the profiler without one
    mine = {name: [e for e in found if e[2].get("rid") == rid] for name, found in events.items()}
    assert {name: len(found) for name, found in mine.items()} == {
        "httpAccept": 0, "httpConnection": 1, "httpHead": 0, "httpTotal": 1, "httpRead": 1, "httpClose": 0}
    (began, lasted, _), = mine["httpConnection"]
    for name in ("httpHead", "httpClose"):  # the query's own: the one that began inside its connection's
        mine[name] = [e for e in events[name] if began <= e[0] <= began + lasted * 1e6]
        assert len(mine[name]) == 1, (name, events[name])
    for name in ("httpHead", "httpClose", "httpConnection", "httpTotal"):
        assert mine[name][0][1] == pytest.approx(spans[name]["ms"], abs=0.5), name
    # httpAccept's is the accept loop's own: the one that began last before the connection's.  Never shorter
    # than the span, which ends at the connection thread's first statement; longer by the loop's wait for
    # the interpreter
    accept_ms = max(e for e in events["httpAccept"] if e[0] <= began)[1]
    assert spans["httpAccept"]["ms"] - 0.5 <= accept_ms <= spans["httpAccept"]["ms"] + 1000
    # outside a capture none of the three is made
    assert trace_mod.marked("httpHead") is None and not trace_mod.capturing()


def test_a_retained_tail_over_http_holds_the_close_finished(http_cluster, monkeypatch):
    """``/debug/tails?requestId=``: ``TailSampler.complete`` gets the tree
    after ``httpClose``, not before it."""
    cluster = http_cluster
    monkeypatch.setattr(cluster.broker.tail, "slow_ms", 0.0)
    reply = _post(cluster, K6)
    _finished_tail(cluster, reply["requestId"])
    entry = _get(cluster, "/debug/tails", requestId=reply["requestId"])
    spans = {s["span"]: s for s in entry["scopes"][cluster.broker.name]}
    for name in CONNECTION_SPANS:
        assert "open" not in spans[name].get("tags", {}) and spans[name]["ms"] > 0, name
    assert entry["phaseSelfMs"]["httpClose"] == spans["httpClose"]["ms"]
    assert {"httpAccept", "httpHead", "httpClose"} <= set(entry["phaseSelfMs"])
    # the reply's own tree was cut before the reply was rendered: the connection and the handler still open
    cut = {s["span"]: s for s in _post(cluster, K6, trace=True)["traceInfo"]["scopes"][cluster.broker.name]}
    assert cut["httpConnection"]["tags"]["open"] is True and cut["httpTotal"]["tags"]["open"] is True
    assert "open" not in cut["httpHead"].get("tags", {}) and "httpClose" not in cut and cut["httpAccept"]["ms"] > 0


# -- where a group-by's operands are built ------------------------------------

OPERANDS_SHAPES = {
    # shape: (PQL, the launch's groupby= tag, its operands= tag)
    "k6": (K6, "onehot", "loop"),
    "min_beside_a_sum": ("SELECT min(l_extendedprice), sum(l_quantity) FROM lineitem GROUP BY l_returnflag TOP 10",
                         "onehot", "staged"),
    "by_date": ("SELECT sum(l_extendedprice) FROM lineitem GROUP BY l_shipdate TOP 10", "radix", "staged"),
    "no_group_by": ("SELECT sum(l_extendedprice), sum(l_discount) FROM lineitem", None, None),
}


@pytest.fixture
def contractions_forced(monkeypatch):
    """The chip's group-by lowerings on the CPU: the programs the module's
    cluster compiled without the switch are forgotten, and those of this
    test after it."""
    from pinot_tpu.engine import kernel as kernel_mod

    def forget_programs():
        kernel_mod.make_table_kernel.cache_clear()
        kernel_mod.make_packed_table_kernel.cache_clear()

    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    forget_programs()
    yield kernel_mod
    forget_programs()


def _launch_tags(cluster, pql):
    reply = _post(cluster, pql, trace=True)
    assert not reply["exceptions"]
    (launch,) = [s for s in reply["traceInfo"]["scopes"][cluster.servers[0].name] if s["span"] == "laneDispatch"]
    return launch["tags"]


@pytest.mark.parametrize("shape", sorted(OPERANDS_SHAPES))
def test_launch_says_where_a_groupbys_operands_are_built(http_cluster, contractions_forced, shape):
    """``operands=loop|staged`` beside ``groupby=`` on ``laneDispatch``,
    and one ``groupby.operands.loop`` mark a launch that builds them in
    the row loop: the answer of ``kernel.groupby_operands``."""
    pql, groupby, operands = OPERANDS_SHAPES[shape]
    meter = http_cluster.servers[0].metrics.meter("groupby.operands.loop")
    before = meter.count
    tags = _launch_tags(http_cluster, pql)
    assert (tags.get("groupby"), tags.get("operands")) == (groupby, operands)
    assert meter.count - before == (operands == "loop")


def test_operands_tag_and_kernel_builder_ask_one_function(http_cluster, contractions_forced, monkeypatch):
    kernel_mod = contractions_forced
    built = []
    loop_kernel = kernel_mod._make_loop_groupby_kernel
    monkeypatch.setattr(kernel_mod, "_make_loop_groupby_kernel", lambda plan: built.append(plan) or loop_kernel(plan))
    assert _launch_tags(http_cluster, K6)["operands"] == "loop" and len(built) == 1
    # the function answers otherwise: the tag and the program follow it together
    monkeypatch.setattr(kernel_mod, "groupby_operands", lambda plan: "staged")
    kernel_mod.make_table_kernel.cache_clear()
    kernel_mod.make_packed_table_kernel.cache_clear()
    meter = http_cluster.servers[0].metrics.meter("groupby.operands.loop")
    before = meter.count
    assert _launch_tags(http_cluster, K6)["operands"] == "staged"
    assert len(built) == 1 and meter.count == before


# -- kernel names -----------------------------------------------------------

_NAME_SNIPPET = """
from pinot_tpu.engine.kernel import kernel_name, make_table_kernel
from pinot_tpu.engine.plan import StaticAgg, StaticGroupBy, StaticPlan
agg = (StaticAgg("sum", "sum", "l_extendedprice", False, "scalar", use_raw=True),)
flat = StaticPlan(None, (), agg, None, None, True)
grouped = StaticPlan(None, (), agg, StaticGroupBy(("l_returnflag", "l_linestatus"), (False, False), (3, 2), 6, 10), None, True)
print(kernel_name("scan", flat), kernel_name("scan", grouped), kernel_name("zone", flat), make_table_kernel(flat).__name__)
"""


def test_program_names_repeat_across_processes_and_differ_by_shape():
    outs = []
    for hash_seed in ("1", "2"):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED=hash_seed)
        done = subprocess.run([sys.executable, "-c", _NAME_SNIPPET], env=env, cwd=ROOT, capture_output=True,
                              text=True, timeout=300)
        assert done.returncode == 0, done.stderr
        outs.append(done.stdout.split())
    assert outs[0] == outs[1]
    flat, grouped, zone, jitted = outs[0]
    assert flat.startswith("pinot_scan_agg_") and grouped.startswith("pinot_scan_gb6_")
    assert zone == flat.replace("_scan_", "_zone_") and jitted == flat
    assert flat[-8:] != grouped[-8:] and len(flat.rsplit("_", 1)[1]) == 8


def test_the_packed_program_is_jitted_under_its_name():
    import jax.numpy as jnp

    from pinot_tpu.engine.packing import make_packed_kernel

    kernel = make_packed_kernel(lambda x: {"a": x * 2}, "pinot_scan_agg_0123abcd")
    assert kernel.__name__ == "pinot_scan_agg_0123abcd"
    assert "jit_pinot_scan_agg_0123abcd" in kernel.lower(jnp.ones(4)).as_text()
    assert "jit_packed" not in kernel.lower(jnp.ones(4)).as_text()


# -- the lane's busy window ---------------------------------------------------


@pytest.mark.parametrize("waiter_reports_ms", [None, 20.0])
def test_lane_device_busy_closes_when_the_output_is_ready(waiter_reports_ms):
    """A launch whose call returns at once and whose output turns ready
    50 ms later: the window is those 50 ms (20 where a waiter saw the
    output first), not the launch call's microseconds."""
    from pinot_tpu.engine.dispatch import DeviceLane

    metrics = ServerMetrics("s0")
    lane = DeviceLane(metrics=metrics, stall_timeout_s=0)
    try:
        ready_at = []

        def launch():
            ready_at.append(time.monotonic() + 0.050)
            return "handle"

        ticket = lane.submit("k", launch, pending=lambda value: time.monotonic() < ready_at[0])
        assert ticket.result(time.monotonic() + 5) == "handle"
        busy = metrics.timer("lane.deviceBusy")
        assert busy.count == 0  # the call has returned, the output is outstanding
        assert lane.occupancy_read("t")["inflight"] == 1
        if waiter_reports_ms is not None:
            time.sleep(waiter_reports_ms / 1000.0)
            lane.output_ready(ticket)
        deadline = time.monotonic() + 2
        while busy.count == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        expected = waiter_reports_ms or 50.0
        assert busy.count == 1 and expected - 2 <= busy.total_ms <= expected + 30
        launch_call = metrics.timer("phase.laneDispatch")
        assert launch_call.count == 1 and launch_call.total_ms < 5
        occ = lane.occupancy_read("t")
        assert occ["inflight"] == 0 and 0 < occ["busyFraction"] <= 1
        assert metrics.timer("phase.laneQueue").count == 1
    finally:
        lane.close()


# -- the capture summary ------------------------------------------------------


def test_profile_summary_on_a_recorded_capture():
    """``data/pinot_capture.txt``: a few events cut from a capture of the
    open cell on the v5e, in the profiler's own text form."""
    from jax.profiler import ProfileData

    from pinot_tpu.server import profiler

    with open(os.path.join(HERE, "data", "pinot_capture.txt")) as f:
        loaded = profiler.load_capture(ProfileData.from_text_proto(f.read()))
    assert sorted(loaded["devices"]) == ["/device:TPU:0"]
    summary = profiler.summarize(loaded)
    device = summary["devices"]["/device:TPU:0"]
    assert 0 < device["busyShare"] < 1
    assert device["busyS"] + summary["idleS"] == pytest.approx(summary["windowS"])
    # programs by name, XLA's fingerprint dropped
    assert all(name.startswith("jit_pinot_") and "(" not in name for name in summary["programs"])
    assert sum(summary["programs"].values()) >= device["busyS"]
    # the gap before the launch is plan build's, the gap between the queries is nobody's
    assert summary["idle"]["pinot:planBuild"] > 0
    assert summary["idle"][profiler.NO_QUERY] > 0
    assert sum(summary["idle"].values()) == pytest.approx(summary["idleS"])


def test_summarize_is_arithmetic_on_lists():
    from pinot_tpu.server import profiler

    ms = 1e6
    loaded = {
        "devices": {
            "/device:TPU:0": {"ops": [(10 * ms, 15 * ms), (15 * ms, 20 * ms), (60 * ms, 70 * ms)],
                              "programs": [("jit_pinot_scan_agg_aa", 10 * ms, 20 * ms),
                                           ("jit_pinot_scan_gb6_bb", 60 * ms, 70 * ms)]},
            "/device:TPU:1": {"ops": [(10 * ms, 20 * ms)],
                              "programs": [("jit_pinot_scan_agg_aa", 10 * ms, 20 * ms)]},
        },
        # a query 5-30 whose planBuild runs 6-9 on another thread, and one 55-80
        "spans": [("pinot:httpTotal", 5 * ms, 30 * ms), ("pinot:planBuild", 6 * ms, 9 * ms),
                  ("pinot:deviceWait", 9 * ms, 21 * ms), ("pinot:httpTotal", 55 * ms, 80 * ms)],
    }
    s = profiler.summarize(loaded)
    assert s["windowS"] == pytest.approx(0.075)
    assert s["devices"]["/device:TPU:0"]["busyS"] == pytest.approx(0.020)
    assert s["devices"]["/device:TPU:1"]["busyShare"] == pytest.approx(10 / 75)
    assert s["programs"] == {"jit_pinot_scan_agg_aa": pytest.approx(0.010),
                             "jit_pinot_scan_gb6_bb": pytest.approx(0.005)}
    # device 0's gaps: 5-10, 20-60, 70-80
    assert s["idle"] == {
        "pinot:httpTotal": pytest.approx(0.001 + 0.009 + 0.005 + 0.010),  # 5-6, 21-30, 55-60, 70-80
        "pinot:planBuild": pytest.approx(0.003),
        "pinot:deviceWait": pytest.approx(0.001 + 0.001),  # 9-10 before the kernel, 20-21 after it
        profiler.NO_QUERY: pytest.approx(0.025),  # 30-55
    }
    assert s["idleS"] == pytest.approx(0.055)


def test_profiler_stop_returns_the_summary(tmp_path):
    """``POST /debug/profile/stop`` is ``DeviceProfiler.stop``: a real
    capture on the CPU has host planes and no device plane, so the
    summary is empty but there, and written beside the trace."""
    from pinot_tpu.server.profiler import SUMMARY_FILE, DeviceProfiler

    prof = DeviceProfiler(base_dir=str(tmp_path))
    started = prof.start(timeout_s=30)
    with boundary("planBuild", None):
        time.sleep(0.001)
    stopped = prof.stop()
    assert stopped["active"] is False
    assert stopped["summary"] == {"windowS": 0.0, "devices": {}, "programs": {}, "idle": {}, "idleS": 0.0}
    assert os.path.exists(os.path.join(started["dir"], SUMMARY_FILE))
    # a capture with nothing to read says so and stays a capture
    fake = DeviceProfiler(base_dir=str(tmp_path / "fake"), trace_api=(lambda d: None, lambda: None))
    fake.start()
    assert "no .xplane.pb" in fake.stop()["summary"]["error"]
