"""Warm-start resilience (ISSUE 16): persistent compile cache, fleet
plan prewarming, and readiness-gated movement.

Tier-1 guards: the plan ledger classifies restarts honestly (a corrupt
or alien entry is a MISS, never a crash, and every topology axis —
jax version, platform, device count/kind, x64 — separates cache keys);
a fresh server over a warm cache serves its first query as
``compile.persistentHit`` with ``compile.cold == 0``; the prewarm
worker compiles the fleet's hot shapes on its background thread without
ever blocking the serving path; the stabilizer defers trims while the
surviving cover is still warming (bounded by the prewarm timeout); the
broker deprioritizes — never excludes — warming replicas; and the
``rolling-restart-warm`` chaos scenario holds the whole story end to
end (zero failed queries, zero cold compiles on restarted servers).
"""
import json
import os
import threading
import time

import pytest

from pinot_tpu.broker.health import ServerHealthTracker
from pinot_tpu.broker.routing import RoutingTableProvider
from pinot_tpu.controller.resource_manager import ClusterResourceManager
from pinot_tpu.controller.stabilizer import SelfStabilizer
from pinot_tpu.engine import compilecache
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.tools.cluster_harness import (
    run_rolling_restart_warm_scenario,
    single_server_broker,
)
from pinot_tpu.tools.datagen import make_test_schema, random_rows

PQL = "SELECT sum(metInt), count(*) FROM warmT GROUP BY dimStr TOP 5"


@pytest.fixture
def cache_isolation():
    """Persistent-cache tests re-point the compile cache; put the
    session's own root (tests/conftest.py) back afterwards."""
    prev = compilecache.cache_root()
    yield
    compilecache._reset_for_tests()
    compilecache.configure_jax_cache(root=prev)


def _meter(server, name):
    snap = server.metrics.snapshot()["meters"]
    return int(snap.get(name, {}).get("count", 0))


def _build_segments(seed=11, num=2, rows_per=60):
    schema = make_test_schema(with_mv=False)
    rows = random_rows(schema, rows_per * num, seed=seed)
    return [
        build_segment(
            schema, rows[i * rows_per : (i + 1) * rows_per], "warmT", f"seg{i}"
        )
        for i in range(num)
    ]


# ------------------------------------------------------------------
# plan ledger: cache-key safety
# ------------------------------------------------------------------
def test_ledger_hit_and_every_corruption_is_a_miss(tmp_path):
    """record -> known roundtrip; every damaged-entry mode is a MISS,
    never an exception — the ledger is advisory accounting only."""
    root = str(tmp_path)
    fp = compilecache.topology_fingerprint()
    assert compilecache.record_plan("d1a2b3c4", fp, root=root)
    assert compilecache.known_plan("d1a2b3c4", fp, root=root)
    # unknown digest / wrong fingerprint: plain misses
    assert not compilecache.known_plan("eeeeeeee", fp, root=root)
    assert not compilecache.known_plan("d1a2b3c4", "0" * 16, root=root)
    assert not compilecache.known_plan("", fp, root=root)

    # corrupt the entry in place: not JSON at all
    path = compilecache._plan_path(root, "d1a2b3c4", fp)
    with open(path, "w") as f:
        f.write("\x00garbage not json")
    assert not compilecache.known_plan("d1a2b3c4", fp, root=root)

    # valid JSON, wrong shape (a list, not a dict)
    with open(path, "w") as f:
        json.dump(["alien"], f)
    assert not compilecache.known_plan("d1a2b3c4", fp, root=root)

    # alien entry: a file whose recorded digest/fingerprint disagree
    # with its filename (e.g. copied from another cache root)
    with open(path, "w") as f:
        json.dump({"digest": "other", "fingerprint": fp}, f)
    assert not compilecache.known_plan("d1a2b3c4", fp, root=root)
    with open(path, "w") as f:
        json.dump({"digest": "d1a2b3c4", "fingerprint": "alienfp"}, f)
    assert not compilecache.known_plan("d1a2b3c4", fp, root=root)

    # truncated (crash mid-write without the atomic rename)
    with open(path, "w") as f:
        f.write('{"digest": "d1a2b')
    assert not compilecache.known_plan("d1a2b3c4", fp, root=root)

    # a healthy re-record repairs the entry
    assert compilecache.record_plan("d1a2b3c4", fp, root=root)
    assert compilecache.known_plan("d1a2b3c4", fp, root=root)

    # a hostile digest cannot escape the ledger directory
    evil = compilecache._plan_path(root, "../../escape", fp)
    assert evil.startswith(os.path.join(root, "plans"))


def test_fingerprint_every_axis_separates_keys():
    """jax version, platform, device count, device kind, and x64 each
    change the fingerprint — a cache written on a different mesh or jax
    build can miss, never poison."""
    base = compilecache.topology_fingerprint()
    assert base == compilecache.topology_fingerprint()  # stable
    variants = [
        compilecache.topology_fingerprint(jax_version="99.99.99"),
        compilecache.topology_fingerprint(platform="tpu"),
        compilecache.topology_fingerprint(device_count=1024),
        compilecache.topology_fingerprint(device_kind="TPU v9"),
        compilecache.topology_fingerprint(x64=not True),
    ]
    # x64 override must actually differ from the session default
    variants[-1] = compilecache.topology_fingerprint(
        x64=not __import__("jax").config.jax_enable_x64
    )
    assert all(v != base for v in variants), variants
    assert len(set(variants)) == len(variants)  # axes don't collide

    # a plan recorded under one topology is unknown under another
    fp_a = compilecache.topology_fingerprint(device_count=8)
    fp_b = compilecache.topology_fingerprint(device_count=16)
    import tempfile

    with tempfile.TemporaryDirectory() as root:
        assert compilecache.record_plan("abcd1234", fp_a, root=root)
        assert compilecache.known_plan("abcd1234", fp_a, root=root)
        assert not compilecache.known_plan("abcd1234", fp_b, root=root)


def test_cache_root_defaults_to_checkout_and_env_places_it(
    tmp_path, monkeypatch, cache_isolation
):
    """Unset, the cache lives at ``<checkout>/.jax_cache`` (a fixed path
    from the package's own location); ``JAX_COMPILATION_CACHE_DIR``
    places it from outside, and then jax already holds the directory —
    this module must not re-point it."""
    import jax

    import pinot_tpu

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(pinot_tpu.__file__)))
    compilecache._reset_for_tests()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compilecache.cache_root() == os.path.join(checkout, ".jax_cache")

    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert compilecache.cache_root() == placed
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: (updates.append(k), real_update(k, v))
    )
    assert compilecache.configure_jax_cache() == placed
    assert "jax_compilation_cache_dir" not in updates
    assert "jax_persistent_cache_min_compile_time_secs" in updates
    # the ledger follows: <that dir>/plans/, and nowhere else
    fp = compilecache.topology_fingerprint()
    assert compilecache.record_plan("d1a2b3c4", fp)
    assert os.listdir(placed) == ["plans"]
    assert compilecache.known_plan("d1a2b3c4", fp)

    # an explicit root= (tests, harnesses) wins over the environment
    own = str(tmp_path / "own")
    assert compilecache.configure_jax_cache(root=own) == own
    assert compilecache.cache_root() == own
    assert "jax_compilation_cache_dir" in updates


# ------------------------------------------------------------------
# compile accounting across a restart
# ------------------------------------------------------------------
def test_persistent_hit_classification_across_restart(
    tmp_path, monkeypatch, cache_isolation
):
    """Server generation 1 compiles cold (``persistentMiss``); a fresh
    server over the same cache root classifies its first launch
    ``persistentHit`` with ``compile.cold == 0``, and EXPLAIN reports
    the r16 compile states (cold -> persistent -> warm) along the way."""
    compilecache.configure_jax_cache(root=str(tmp_path))

    broker1 = single_server_broker("warmT", _build_segments(), pipeline=True)
    s1 = broker1.local_servers[0]
    try:
        pre = broker1.handle_pql("EXPLAIN " + PQL)
        assert pre.explain["servers"][0]["device"]["compile"]["state"] == "cold"
        resp = broker1.handle_pql(PQL)
        assert not resp.exceptions, resp.exceptions
        assert _meter(s1, "compile.cold") == 1
        assert _meter(s1, "compile.persistentMiss") == 1
        assert _meter(s1, "compile.persistentHit") == 0
    finally:
        s1.shutdown()

    # "restart": a genuinely fresh instance — empty lane compile
    # registries — sharing only the on-disk cache root
    broker2 = single_server_broker("warmT", _build_segments(), pipeline=True)
    s2 = broker2.local_servers[0]
    try:
        pre = broker2.handle_pql("EXPLAIN " + PQL)
        comp = pre.explain["servers"][0]["device"]["compile"]
        assert comp["state"] == "persistent", comp  # ledger-proven warm
        resp = broker2.handle_pql(PQL)
        assert not resp.exceptions, resp.exceptions
        assert _meter(s2, "compile.cold") == 0
        assert _meter(s2, "compile.persistentHit") == 1
        assert _meter(s2, "compile.persistentMiss") == 0
        post = broker2.handle_pql("EXPLAIN " + PQL)
        assert (
            post.explain["servers"][0]["device"]["compile"]["state"] == "warm"
        )
    finally:
        s2.shutdown()


# ------------------------------------------------------------------
# prewarm worker
# ------------------------------------------------------------------
def test_prewarm_compiles_ahead_and_reports_readiness(
    tmp_path, monkeypatch, cache_isolation
):
    """The worker replays the fleet workload feed through phantom
    staging BEFORE any query: the first serving query is classified
    ``compile.prewarmed`` (never cold), and the warming flag flips
    synchronously on request and clears when the pass drains."""
    compilecache.configure_jax_cache(root=str(tmp_path))

    # generation 1 records the workload shape the fleet feed serves
    broker1 = single_server_broker("warmT", _build_segments(), pipeline=True)
    s1 = broker1.local_servers[0]
    try:
        resp = broker1.handle_pql(PQL)
        assert not resp.exceptions, resp.exceptions
        entries = broker1.workload_snapshot(top=8)["topByCount"]
        assert entries and entries[0]["exemplarPql"]
    finally:
        s1.shutdown()

    broker2 = single_server_broker("warmT", _build_segments(), pipeline=True)
    s2 = broker2.local_servers[0]
    try:
        assert not s2.prewarm.enabled  # no feed wired yet: always ready
        s2.prewarm.workload_source = lambda tables, n: entries
        s2.prewarm.request_prewarm("warmT")
        assert s2.prewarm.warming  # synchronous flip: heartbeats see it
        deadline = time.monotonic() + 30.0
        while s2.prewarm.warming and time.monotonic() < deadline:
            time.sleep(0.005)
        assert not s2.prewarm.warming, s2.prewarm.state()
        assert _meter(s2, "prewarm.compiled") >= 1
        assert _meter(s2, "compile.prewarmed") >= 1
        assert _meter(s2, "compile.cold") == 0
        assert _meter(s2, "prewarm.failed") == 0
        # EXPLAIN reports HOW the executable arrived before it serves
        pre = broker2.handle_pql("EXPLAIN " + PQL)
        comp = pre.explain["servers"][0]["device"]["compile"]
        assert comp["state"] == "prewarmed", comp
        # first serving query: the executable is already resident
        resp = broker2.handle_pql(PQL)
        assert not resp.exceptions, resp.exceptions
        assert _meter(s2, "compile.cold") == 0
        assert _meter(s2, "compile.warm") >= 1
        st = s2.prewarm.state()
        assert st["ready"] and st["compiled"] >= 1
    finally:
        s2.shutdown()


def test_prewarm_never_blocks_serving():
    """A pass parked inside the workload fetch must not delay a live
    query: prewarm work happens strictly on the background thread."""
    broker = single_server_broker("warmT", _build_segments(), pipeline=True)
    server = broker.local_servers[0]
    entered = threading.Event()
    release = threading.Event()

    def stalled_source(tables, n):
        entered.set()
        release.wait(timeout=10.0)
        return []

    try:
        server.prewarm.workload_source = stalled_source
        server.prewarm.request_prewarm()
        assert entered.wait(timeout=5.0)
        # the worker is wedged mid-pass; serving proceeds regardless
        resp = broker.handle_pql(PQL)
        assert not resp.exceptions, resp.exceptions
        assert server.prewarm.warming  # still mid-pass the whole time
    finally:
        release.set()
        server.shutdown()
    assert not server.prewarm.warming  # stop() clears the flag


def test_prewarm_disabled_without_feed_or_topk():
    """No workload source (plain in-process instances) or top_k == 0
    means the worker never starts and the server is simply ready."""
    broker = single_server_broker("warmT", _build_segments(), pipeline=True)
    server = broker.local_servers[0]
    try:
        assert not server.prewarm.enabled
        server.prewarm.request_prewarm("warmT")
        assert not server.prewarm.warming
        assert server.prewarm._thread is None  # nothing ever spawned
        server.prewarm.workload_source = lambda tables, n: []
        server.prewarm.top_k = 0
        assert not server.prewarm.enabled
        server.prewarm.request_prewarm("warmT")
        assert not server.prewarm.warming
        assert server.prewarm.state()["ready"]
    finally:
        server.shutdown()


# ------------------------------------------------------------------
# readiness-gated movement
# ------------------------------------------------------------------
def test_trim_defers_for_warming_cover_then_times_out():
    """``_destinations_ready``: a trim waits while the surviving cover
    is still prewarming — ``rebalanceTrimDeferred`` in the event ring,
    ``rebalance.prewarmDeferrals`` marked — and proceeds anyway past
    the bounded prewarm window (``rebalancePrewarmTimeout``)."""
    clock = [100.0]
    st = SelfStabilizer(ClusterResourceManager(), grace_s=5.0, now=lambda: clock[0])
    st.prewarm_timeout_s = 10.0
    warming = {"serverB"}
    st.readiness_fn = lambda s: s not in warming
    serving = ["serverA", "serverB"]

    # everyone ready: trim proceeds, no wait recorded
    assert st._destinations_ready("t_OFFLINE", "s0", serving, 1)
    assert not st._warm_waits

    # victim A leaves only cover B, which is warming: defer
    assert not st._destinations_ready(
        "t_OFFLINE", "s0", serving, 1, victim="serverA", dst="serverB"
    )
    ev = st.events()[-1]
    assert ev["event"] == "rebalanceTrimDeferred"
    assert ev["server"] == "serverA" and ev["dst"] == "serverB"
    assert ev["reason"] == "destination warming"
    assert st.metrics.meter("rebalance.prewarmDeferrals").count == 1
    assert ("t_OFFLINE", "s0") in st._warm_waits

    # still inside the window: keeps deferring
    clock[0] = 105.0
    assert not st._destinations_ready(
        "t_OFFLINE", "s0", serving, 1, victim="serverA", dst="serverB"
    )
    assert st.metrics.meter("rebalance.prewarmDeferrals").count == 2

    # destination finishes warming: trim proceeds and the wait clears
    warming.clear()
    assert st._destinations_ready(
        "t_OFFLINE", "s0", serving, 1, victim="serverA", dst="serverB"
    )
    assert not st._warm_waits

    # a wedged prewarm cannot pin the surplus replica forever: the
    # deferral is bounded by the prewarm window
    warming.add("serverB")
    clock[0] = 200.0
    assert not st._destinations_ready(
        "t_OFFLINE", "s0", serving, 1, victim="serverA", dst="serverB"
    )
    clock[0] = 211.0  # past prewarm_timeout_s
    assert st._destinations_ready(
        "t_OFFLINE", "s0", serving, 1, victim="serverA", dst="serverB"
    )
    assert st.events()[-1]["event"] == "rebalancePrewarmTimeout"
    assert not st._warm_waits  # timeout clears the clock too

    # a broken readiness probe must never freeze movement
    def boom(server):
        raise RuntimeError("probe down")

    st.readiness_fn = boom
    assert st._destinations_ready(
        "t_OFFLINE", "s0", serving, 1, victim="serverA"
    )

    # no probe wired (pre-r16 clusters): everyone is ready
    st.readiness_fn = None
    assert st._ready("anything")


# ------------------------------------------------------------------
# broker routing: deprioritize, never exclude
# ------------------------------------------------------------------
def test_routing_deprioritizes_warming_replica():
    provider = RoutingTableProvider(num_tables=4)
    segments = [f"seg{i}" for i in range(4)]
    view = {seg: {"s1": "ONLINE", "s2": "ONLINE"} for seg in segments}
    provider.update("t_OFFLINE", view)
    health = ServerHealthTracker()

    # s1 warming: every segment re-routes onto the ready replica
    health.set_warming("s1", True)
    for _ in range(10):
        rt = provider.find_servers("t_OFFLINE", health=health)
        assert set(rt) == {"s2"}, rt
        assert sorted(sum(rt.values(), [])) == segments

    # warming cleared (e.g. heartbeat reports ready): s1 serves again
    health.set_warming("s1", False)
    seen = set()
    for _ in range(40):
        seen.update(provider.find_servers("t_OFFLINE", health=health))
    assert seen == {"s1", "s2"}

    # a warming replica that is all that is left still serves —
    # deprioritized is never excluded
    sole = {seg: {"s1": "ONLINE"} for seg in segments}
    provider.update("sole_OFFLINE", sole)
    health.set_warming("s1", True)
    rt = provider.find_servers("sole_OFFLINE", health=health)
    assert set(rt) == {"s1"}
    assert sorted(sum(rt.values(), [])) == segments

    # the wholesale clusterstate refresh path drives the same flag
    health.set_warming_servers({"s2"})
    assert health.is_warming("s2") and not health.is_warming("s1")
    assert health.warming_servers() == {"s2"}


# ------------------------------------------------------------------
# chaos acceptance — the same scenario code the CLI runs
# ------------------------------------------------------------------
@pytest.mark.chaos
def test_rolling_restart_warm_acceptance(tmp_path, cache_isolation):
    out = run_rolling_restart_warm_scenario(
        data_dir=str(tmp_path / "data"), cache_dir=str(tmp_path / "cache")
    )
    assert out["failedQueries"] == 0, out.get("failures")
    # the warm-start bar: every restarted server came up with ZERO cold
    # compiles — its first launches were persistent-cache or prewarm
    assert out["coldCompilesOnRestarted"] == 0, out["servers"]
    assert out["warmStartsOnRestarted"] >= 1, out["servers"]
    # movement provably waited on warming destinations
    assert out["trimDeferrals"] >= 1, out
    assert out["prewarmDeferralMeter"] >= out["trimDeferrals"]
    assert out["prewarmTimeouts"] == 0, out
    # prewarm never entered a serving lane on the restarted servers
    assert out["laneWatchdogClean"], out["servers"]
    assert out["p99Bounded"], (out["rollP99Ms"], out["p99LimitMs"])
    assert out["noSegmentLoss"] and out["finalComplete"], out
