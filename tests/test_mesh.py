"""Mesh execution plane (ISSUE 12): pod-scale multichip serving with
per-chip-group lanes, on the forced 8-device CPU host (conftest).

Covers: topology construction from env, shape-hashed lane routing,
byte-identical payloads sharded vs single-lane across a mixed query
set, lane-group coalesce/shed/heal units, chaos (one poisoned plan on
one lane heals via host fallback while other lanes keep serving),
sharded staging-ledger accounting + eviction, per-lane utilization
attribution with sum-consistent rollups, and the EXPLAIN mesh node
whose phantom digest matches real sharded execution exactly.
"""
import json
import threading
import time

import jax
import pytest

from pinot_tpu.engine.mesh import (
    ChipGroup,
    MeshTopology,
    build_topology,
    collective_names,
)

NUM_SEGMENTS = 6  # not divisible by 4 or 8 -> exercises mesh padding


def _segments(n=NUM_SEGMENTS, rows=2500, prefix="msh"):
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    return [
        synthetic_lineitem_segment(rows, seed=31 + i, name=f"{prefix}{i}")
        for i in range(n)
    ]


def _mixed_workload(segments):
    """Four shapes: the Q1 group-by scan, an IN+range group-by, a
    selective needle, an HLL distinct."""
    d_price = segments[0].column("l_extendedprice").dictionary
    pv = d_price.get(d_price.cardinality // 2)
    return [
        "SELECT sum(l_quantity), sum(l_extendedprice), sum(l_discount), count(*) "
        "FROM lineitem WHERE l_shipdate <= '1998-09-02' "
        "GROUP BY l_returnflag, l_linestatus TOP 10",
        "SELECT sum(l_extendedprice) FROM lineitem "
        "WHERE l_shipmode IN ('RAIL','FOB') AND "
        "l_receiptdate BETWEEN '1997-01-01' AND '1997-12-31' "
        "GROUP BY l_shipmode TOP 10",
        f"SELECT sum(l_quantity), count(*) FROM lineitem "
        f"WHERE l_extendedprice = {pv!r}",
        "SELECT distinctcounthll(l_shipdate) FROM lineitem "
        "GROUP BY l_returnflag TOP 10",
    ]


def _strip(resp) -> str:
    """Canonical payload for the byte-identity differential: timing,
    request identity, and the path-dependent cost vector excluded."""
    return json.dumps(
        {
            k: v
            for k, v in resp.to_json().items()
            if k not in ("timeUsedMs", "requestId", "cost")
        },
        sort_keys=True,
    )


@pytest.fixture(scope="module")
def lineitem_segments():
    return _segments()


@pytest.fixture(scope="module")
def mesh_broker(lineitem_segments):
    """One server carved into 2 lanes x 4 chips over the 8 virtual CPU
    devices, behind an in-process broker."""
    from pinot_tpu.tools.cluster_harness import single_server_broker

    topo = build_topology(jax.devices(), 2, 4)
    broker = single_server_broker(
        "lineitem", lineitem_segments, topology=topo
    )
    yield broker
    broker.local_servers[0].shutdown()


# ---------------------------------------------------------------------------
# topology construction
# ---------------------------------------------------------------------------


def test_default_topology_is_trivial_single_lane(monkeypatch):
    monkeypatch.delenv("PINOT_TPU_MESH_SHAPE", raising=False)
    monkeypatch.delenv("PINOT_TPU_LANES", raising=False)
    topo = MeshTopology.from_env()
    assert topo.trivial
    assert topo.num_lanes == 1
    assert topo.primary_mesh is None
    snap = topo.snapshot()
    assert snap["shape"] == "1x1" and snap["shardAxis"] is None


@pytest.mark.parametrize(
    "shape,lanes,want",
    [
        ("2x4", None, (2, 4)),
        ("8", None, (1, 8)),
        (None, "4", (4, 2)),
        (None, "2", (2, 4)),
        ("4x2", "4", (4, 2)),
        ("junk", None, (1, 8)),  # junk shape degrades, never raises
        ("64x64", None, (8, 1)),  # impossible request clamps to devices
    ],
)
def test_topology_env_parsing(monkeypatch, shape, lanes, want):
    monkeypatch.delenv("PINOT_TPU_MESH_SHAPE", raising=False)
    monkeypatch.delenv("PINOT_TPU_LANES", raising=False)
    if shape is not None:
        monkeypatch.setenv("PINOT_TPU_MESH_SHAPE", shape)
    if lanes is not None:
        monkeypatch.setenv("PINOT_TPU_LANES", lanes)
    topo = MeshTopology.from_env()
    assert (topo.num_lanes, topo.devices_per_lane) == want
    # groups own disjoint devices and each carries its own mesh
    seen = set()
    for g in topo.groups:
        ids = {d.id for d in g.devices}
        assert not ids & seen
        seen |= ids
        assert g.mesh is not None and int(g.mesh.devices.size) == g.size


def test_from_mesh_legacy_adapter():
    from pinot_tpu.parallel import default_mesh

    topo = MeshTopology.from_mesh(default_mesh())
    assert topo.num_lanes == 1 and not topo.trivial
    assert int(topo.primary_mesh.devices.size) == 8
    assert MeshTopology.from_mesh(None).trivial


def test_collective_names_reflect_plan_reducers(lineitem_segments):
    from pinot_tpu.engine.context import get_table_context
    from pinot_tpu.engine.explain import _phantom_staged
    from pinot_tpu.engine.plan import build_static_plan
    from pinot_tpu.pql import optimize_request, parse_pql

    req = optimize_request(
        parse_pql("SELECT sum(l_quantity), min(l_quantity) FROM lineitem")
    )
    ctx = get_table_context(lineitem_segments)
    phantom = _phantom_staged(
        lineitem_segments, ["l_quantity"], ("l_quantity",), (), ()
    )
    plan = build_static_plan(req, ctx, phantom)
    names = collective_names(plan)
    assert "psum" in names and "pmin" in names


# ---------------------------------------------------------------------------
# lane-group units: routing, coalesce, shed, heal
# ---------------------------------------------------------------------------


def _bare_group(n=4, metrics=None, **kwargs):
    from pinot_tpu.engine.dispatch import LaneGroup

    topo = MeshTopology(
        groups=tuple(ChipGroup(index=i) for i in range(n)), source="env"
    )
    return LaneGroup(topo, metrics=metrics, **kwargs)


def test_lane_selection_is_stable_and_spread():
    lg = _bare_group(4)
    try:
        idx = {f"shape{i}": lg.lane_index(f"shape{i}") for i in range(256)}
        # deterministic: same key always lands on the same lane
        for k, v in idx.items():
            assert lg.lane_index(k) == v
            assert lg.select(k).index == v
            assert lg.select(k).group is lg.topology.groups[v]
        # and shapes actually spread across the group
        assert len(set(idx.values())) == 4
    finally:
        lg.close()


def test_lane_group_coalesces_identical_dispatches():
    lg = _bare_group(2)
    try:
        release = threading.Event()

        def slow_launch():
            release.wait(5.0)
            return {"v": 1}

        sel = lg.select("shapeA")
        t1 = sel.lane.submit(("k", 1), slow_launch, pending=lambda v: False)
        t2 = sel.lane.submit(("k", 1), slow_launch, pending=lambda v: False)
        release.set()
        assert t1.result(time.monotonic() + 10) == {"v": 1}
        assert t2.result(time.monotonic() + 10) == {"v": 1}
        assert t2.coalesced  # rode the identical in-flight dispatch
        stats = lg.stats()
        assert stats["coalesceHits"] >= 1
        assert stats["lanes"][sel.index]["coalesceHits"] >= 1
    finally:
        lg.close()


def test_lane_group_sheds_expired_waiters_per_lane():
    from pinot_tpu.server.scheduler import QueryAbandonedError

    lg = _bare_group(2)
    try:
        sel = lg.select("shapeB")
        expired = time.monotonic() - 1.0
        ticket = sel.lane.submit(("dead", 1), lambda: {"v": 2}, deadline=expired)
        with pytest.raises(QueryAbandonedError):
            ticket.result(time.monotonic() + 5)
        assert lg.stats()["shed"] >= 1
        assert lg.stats()["lanes"][sel.index]["shed"] >= 1
    finally:
        lg.close()


def test_lane_group_rollup_sums_per_lane_stats():
    lg = _bare_group(3)
    try:
        for key in ("a", "b", "c", "d", "e"):
            sel = lg.select(key)
            sel.lane.submit((key, 1), lambda: {"v": key}, pending=lambda v: False
                            ).result(time.monotonic() + 5)
        stats = lg.stats()
        per_lane = stats["lanes"]
        assert len(per_lane) == 3
        for field in ("dispatches", "shed", "coalesceHits", "deviceFailures"):
            assert stats[field] == sum(l[field] for l in per_lane)
        assert stats["dispatches"] == 5
    finally:
        lg.close()


def test_single_group_lane_is_premesh_shape():
    """A single-group LaneGroup is byte-compatible with the pre-mesh
    single lane: verbatim stats (no "lanes" key), unprefixed metrics."""
    from pinot_tpu.utils.metrics import ServerMetrics

    m = ServerMetrics("premesh")
    lg = _bare_group(1, metrics=m)
    try:
        assert lg.primary is lg.lanes[0]
        assert lg.lanes[0].index is None
        stats = lg.stats()
        assert "lanes" not in stats
        assert lg.select("anything").index == 0
        snap = m.snapshot()
        assert "lane.depth" in snap["gauges"]
        assert not any(g.startswith("lane.0.") for g in snap["gauges"])
    finally:
        lg.close()


# ---------------------------------------------------------------------------
# serving: byte-identical payloads sharded vs single-lane
# ---------------------------------------------------------------------------


def test_sharded_payloads_byte_identical_to_single_lane(
    lineitem_segments, mesh_broker
):
    """The mixed query set (plus COUNT(*) and a selection) through a
    2x4 lane-group server serves byte-identical payloads to the
    single-lane server — the mesh is a pure execution-plane change."""
    from pinot_tpu.tools.cluster_harness import single_server_broker
    single = single_server_broker("lineitem", lineitem_segments)
    try:
        queries = _mixed_workload(lineitem_segments) + [
            "SELECT count(*) FROM lineitem",
            "SELECT l_returnflag, l_quantity FROM lineitem "
            "ORDER BY l_quantity DESC LIMIT 7",
        ]
        for pql in queries:
            a = single.handle_pql(pql)
            b = mesh_broker.handle_pql(pql)
            assert not a.exceptions, (pql, a.exceptions)
            assert not b.exceptions, (pql, b.exceptions)
            assert _strip(a) == _strip(b), pql
        # the mesh server really executed on device lanes (no silent
        # host healing — the regression the shard_map kwarg fix covers)
        server = mesh_broker.local_servers[0]
        heal = server.executor.healing_stats()
        assert heal["hostFailovers"] == 0 and heal["deviceFailures"] == 0
        assert server.lanes.stats()["dispatches"] >= 1
    finally:
        single.local_servers[0].shutdown()


def test_radix_groupby_sharded_equals_single_device(lineitem_segments, monkeypatch):
    """A group-by over the dates (above the one-level gate) through
    ``make_sharded_table_kernel`` on the 8-device CPU mesh, with the
    two-level contraction forced: every state equals the single-device
    program's, so the psum merges what the scatter's states would."""
    import numpy as np

    from pinot_tpu.engine import kernel as kernel_mod
    from pinot_tpu.engine.context import get_table_context
    from pinot_tpu.engine.device import get_staged, segment_arrays
    from pinot_tpu.engine.plan import build_query_inputs, build_static_plan
    from pinot_tpu.parallel import default_mesh
    from pinot_tpu.parallel.multichip import make_sharded_table_kernel
    from pinot_tpu.pql import optimize_request, parse_pql
    from jax.sharding import NamedSharding, PartitionSpec as P

    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    kernel_mod.make_table_kernel.cache_clear()
    req = optimize_request(parse_pql(
        "SELECT sum(l_extendedprice), count(*), avg(l_quantity) FROM lineitem GROUP BY l_shipdate TOP 10"))
    ctx = get_table_context(lineitem_segments)
    needed = sorted(set(req.referenced_columns()))

    def states(kernel_of, **staging):
        staged = get_staged(lineitem_segments, needed, gfwd_columns=("l_shipdate",), ctx=ctx, **staging)
        plan = build_static_plan(req, ctx, staged)
        assert kernel_mod.groupby_lowering(plan) == "radix", plan.group_by.capacity
        outs = kernel_of(plan)(segment_arrays(staged, needed), build_query_inputs(req, plan, ctx, staged))
        return jax.tree_util.tree_map(np.asarray, outs)

    try:
        single = states(kernel_mod.make_table_kernel)
        mesh = default_mesh()
        sharded = states(lambda plan: make_sharded_table_kernel(plan, mesh), pad_segments_to=8,
                         sharding=NamedSharding(mesh, P("segments")))
    finally:
        kernel_mod.make_table_kernel.cache_clear()
    assert single["gb_presence"].sum() > 512
    for key, value in single.items():
        for a, b in zip(jax.tree_util.tree_leaves(value), jax.tree_util.tree_leaves(sharded[key])):
            if a.dtype.kind == "f":
                np.testing.assert_allclose(b, a, rtol=1e-6, err_msg=key)
            else:
                assert (a == b).all(), key


def test_loop_groupby_on_the_mesh_marks_operands_loop(lineitem_segments, mesh_broker, monkeypatch):
    """The open cell's K=6 shape through the sharded kernel with the
    contractions forced: the launch is tagged ``operands=loop`` and
    marks ``groupby.operands.loop`` once, and the reply is the one the
    scatter gives on the module's mesh server."""
    from pinot_tpu.tools.cluster_harness import single_server_broker

    pql = ("SELECT sum(l_quantity), sum(l_extendedprice), sum(l_discount), count(*) FROM lineitem "
           "WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag, l_linestatus TOP 10")
    scattered = mesh_broker.handle_pql(pql)
    monkeypatch.setenv("PINOT_TPU_GROUPBY_MATMUL", "1")
    broker = single_server_broker("lineitem", lineitem_segments, topology=build_topology(jax.devices(), 2, 4))
    server = broker.local_servers[0]
    try:
        resp = broker.handle_pql(pql, trace=True)
        assert not resp.exceptions and not scattered.exceptions
        (launch,) = [s for s in resp.trace_info["scopes"][server.name] if s["span"] == "laneDispatch"]
        assert launch["tags"]["program"].startswith("pinot_mesh_gb6_")
        assert (launch["tags"]["groupby"], launch["tags"]["operands"]) == ("onehot", "loop")
        assert server.metrics.meter("groupby.operands.loop").count == 1
        assert server.executor.healing_stats()["hostFailovers"] == 0
        got, want = resp.to_json()["aggregationResults"], scattered.to_json()["aggregationResults"]
        assert [a["function"] for a in got] == [a["function"] for a in want]
        for a, b in zip(got, want):
            assert [e["group"] for e in a["groupByResult"]] == [e["group"] for e in b["groupByResult"]]
            for x, y in zip(a["groupByResult"], b["groupByResult"]):
                assert float(x["value"]) == pytest.approx(float(y["value"]), rel=1e-9)
    finally:
        server.shutdown()


def test_mesh_status_reports_topology_and_lanes(mesh_broker):
    server = mesh_broker.local_servers[0]
    status = server.status()
    assert status["mesh"]["lanes"] == 2
    assert status["mesh"]["devicesPerLane"] == 4
    assert status["mesh"]["shardAxis"] == "segments"
    assert len(status["lane"]["lanes"]) == 2
    snap = status["metrics"]
    assert snap["gauges"]["mesh.lanes"] == 2
    assert "lane.0.depth" in snap["gauges"] and "lane.1.depth" in snap["gauges"]


# ---------------------------------------------------------------------------
# chaos: one poisoned plan on one lane heals via host fallback while
# the other lanes keep serving from their chips
# ---------------------------------------------------------------------------


def _strip_heal(resp) -> str:
    """Payload canonicalization across the device/host tiers: the
    entries-scanned counters are tier-dependent by design (zone maps /
    postings scan fewer entries; the host path counts differently —
    test_selfheal strips the same two), the DATA must match exactly."""
    return json.dumps(
        {
            k: v
            for k, v in resp.to_json().items()
            if k
            not in (
                "timeUsedMs",
                "requestId",
                "cost",
                "numEntriesScannedInFilter",
                "numEntriesScannedPostFilter",
            )
        },
        sort_keys=True,
    )


def test_poisoned_plan_on_one_lane_heals_while_others_serve(lineitem_segments):
    from pinot_tpu.common.faults import DeviceFaultInjector
    from pinot_tpu.tools.cluster_harness import single_server_broker

    inj = DeviceFaultInjector(seed=7)
    topo = build_topology(jax.devices(), 2, 4)
    broker = single_server_broker(
        "lineitem",
        lineitem_segments,
        topology=topo,
        device_fault_injector=inj,
    )
    server = broker.local_servers[0]
    try:
        victim_q = "SELECT sum(l_quantity) FROM lineitem GROUP BY l_returnflag TOP 5"
        healthy_q = "SELECT count(*) FROM lineitem"
        # learn the device-plan digest and lane WITHOUT serving: EXPLAIN
        dev = broker.handle_pql("EXPLAIN " + victim_q).explain["servers"][0]["device"]
        victim_digest = dev["planDigest"]
        victim_lane = dev["mesh"]["laneIndex"]
        # sanity: the two shapes route to different lanes (chosen so)
        healthy_dev = broker.handle_pql("EXPLAIN " + healthy_q).explain[
            "servers"
        ][0]["device"]
        baseline = _strip_heal(broker.handle_pql(victim_q))

        inj.poison_plan(victim_digest)
        poisoned = broker.handle_pql(victim_q)
        assert not poisoned.exceptions
        # healed via host fallback, byte-identical answer
        assert _strip_heal(poisoned) == baseline
        heal = server.executor.healing_stats()
        assert heal["hostFailovers"] >= 1
        assert heal["poisonedPlans"] >= 1

        # the OTHER lanes keep serving on device: a healthy shape still
        # dispatches and adds zero new failures
        before = server.lanes.stats()["dispatches"]
        ok = broker.handle_pql(healthy_q)
        assert not ok.exceptions
        if healthy_dev["mesh"]["laneIndex"] != victim_lane:
            assert server.lanes.stats()["dispatches"] >= before
        assert server.executor.healing_stats()["deviceFailures"] == heal[
            "deviceFailures"
        ]

        # repeat offenders skip the device entirely (quarantine), still
        # byte-identical
        again = broker.handle_pql(victim_q)
        assert not again.exceptions and _strip_heal(again) == baseline
        assert server.executor.healing_stats()["poisonSkips"] >= 1
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# sharded staging ledger + staging-token invariant
# ---------------------------------------------------------------------------


def test_ledger_attributes_sharded_staging_per_device():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pinot_tpu.engine.device import (
        LEDGER,
        evict_staged_segment,
        get_staged,
    )

    segs = _segments(n=4, rows=600, prefix="led")
    topo = build_topology(jax.devices(), 2, 4)
    group = topo.groups[1]  # devices 4-7: distinguishable from default
    sh = NamedSharding(group.mesh, P("segments"))
    st = get_staged(segs, ["l_quantity", "l_shipdate"], pad_segments_to=4, sharding=sh)
    try:
        snap = LEDGER.snapshot()
        entry = next(
            e for e in snap["entries"] if set(e["segments"]) == {s.segment_name for s in segs}
        )
        # per-device attribution: every chip of the group holds its
        # shard, and the per-device bytes sum EXACTLY to the entry total
        ids = {f"cpu:{d.id}" for d in group.devices}
        assert set(entry["devices"]) == ids
        assert sum(entry["devices"].values()) == entry["bytes"]
        assert set(snap["byDevice"]).issuperset(ids)

        # same segments on a DIFFERENT placement = a distinct staged
        # copy with its own token (no stale alias across chip groups)
        sh0 = NamedSharding(topo.groups[0].mesh, P("segments"))
        st0 = get_staged(
            segs, ["l_quantity", "l_shipdate"], pad_segments_to=4, sharding=sh0
        )
        assert st0.token != st.token

        # eviction drops EVERY placement holding the segment, and a
        # re-stage mints a fresh token (the PR 3 invariant, sharded)
        dropped = evict_staged_segment(segs[0].segment_name)
        assert dropped >= 2
        st2 = get_staged(
            segs, ["l_quantity", "l_shipdate"], pad_segments_to=4, sharding=sh
        )
        assert st2.token not in (st.token, st0.token)
    finally:
        evict_staged_segment(segs[0].segment_name)


# ---------------------------------------------------------------------------
# per-lane utilization attribution + rollup consistency
# ---------------------------------------------------------------------------


def test_per_lane_utilization_rollup_equals_sum_of_lane_snapshots(mesh_broker):
    server = mesh_broker.local_servers[0]
    segs = mesh_broker.local_servers[0].data_manager.table("lineitem_OFFLINE")
    for pql in _mixed_workload(_segments()):  # drive some device work
        mesh_broker.handle_pql(pql)
    du = server.device_utilization()
    assert du["mesh"]["lanes"] == 2

    recent = du["recent"]
    lanes = recent["lanes"]
    assert len(lanes) == 2
    # rollup totals equal the sum of the per-lane snapshots EXACTLY
    assert recent["queries"] == sum(l["queries"] for l in lanes)
    assert recent["deviceBytes"] == sum(l["deviceBytes"] for l in lanes)
    assert recent["achievedBytesPerSec"] == sum(
        l["achievedBytesPerSec"] for l in lanes
    )
    assert recent["achievedFlopsPerSec"] == sum(
        l["achievedFlopsPerSec"] for l in lanes
    )
    assert recent["queries"] >= 1  # device work actually attributed

    occ = du["occupancy"]
    occ_lanes = occ["lanes"]
    assert len(occ_lanes) == 2
    assert occ["depth"] == sum(l["depth"] for l in occ_lanes)
    assert occ["busyFraction"] == round(
        sum(l["busyFraction"] for l in occ_lanes), 6
    )


# ---------------------------------------------------------------------------
# EXPLAIN mesh node: decision reported, phantom digest matches real
# sharded execution exactly
# ---------------------------------------------------------------------------


def test_explain_reports_mesh_decision_and_digest_matches(mesh_broker):
    q = "SELECT sum(l_extendedprice), count(*) FROM lineitem GROUP BY l_linestatus TOP 5"
    pre = mesh_broker.handle_pql("EXPLAIN " + q)
    dev = pre.explain["servers"][0]["device"]
    mesh_node = dev["mesh"]
    assert mesh_node["shape"] == "2x4"
    assert mesh_node["lanes"] == 2
    assert mesh_node["shardAxis"] == "segments"
    assert "psum" in mesh_node["collective"]
    assert mesh_node["laneIndex"] in (0, 1)

    # real sharded execution compiles the IDENTICAL plan digest on the
    # lane EXPLAIN predicted
    resp = mesh_broker.handle_pql(q)
    assert not resp.exceptions
    server = mesh_broker.local_servers[0]
    lane = server.lanes.lanes[mesh_node["laneIndex"]]
    assert lane.compile_info(dev["planDigest"]) is not None
    post = mesh_broker.handle_pql("EXPLAIN " + q)
    post_dev = post.explain["servers"][0]["device"]
    assert post_dev["planDigest"] == dev["planDigest"]
    assert post_dev["compile"]["state"] == "warm"


# ---------------------------------------------------------------------------
# acceptance (slow): sharded execution beats a single lane by >= 3x on
# the scan-heavy shapes on virtual CPU devices; slow, so tier-1 stays
# deterministic (the chip's number is the mesh4 cell's, PERF.md)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_speedup_over_single_lane():
    import os

    import numpy as np

    if (os.cpu_count() or 1) < 8:
        pytest.skip(
            "virtual CPU devices share host cores: a host with fewer "
            "cores than mesh devices cannot express the parallel "
            "speedup this test measures (wall-clock is core-bound, "
            "not device-bound) — run on an 8+-core host or real chips"
        )

    from pinot_tpu.engine.context import get_table_context
    from pinot_tpu.engine.device import get_staged, segment_arrays
    from pinot_tpu.engine.kernel import make_table_kernel
    from pinot_tpu.engine.plan import build_query_inputs, build_static_plan
    from pinot_tpu.parallel import default_mesh
    from pinot_tpu.parallel.multichip import make_sharded_table_kernel
    from pinot_tpu.pql import optimize_request, parse_pql

    segs = _segments(n=8, rows=120_000, prefix="spd")
    req = optimize_request(
        parse_pql(
            "SELECT sum(l_quantity), sum(l_extendedprice), count(*) "
            "FROM lineitem GROUP BY l_returnflag TOP 5"
        )
    )
    ctx = get_table_context(segs)
    needed = sorted(set(req.referenced_columns()))

    def bench(kernel, staged):
        q = build_query_inputs(req, build_static_plan(req, ctx, staged), ctx, staged)
        arrays = segment_arrays(staged, needed)
        outs = kernel(arrays, q)
        np.asarray(next(iter(outs.values()))[0] if isinstance(next(iter(outs.values())), tuple) else next(iter(outs.values())))
        t0 = time.perf_counter()
        for _ in range(8):
            outs = kernel(arrays, q)
        leaf = next(iter(outs.values()))
        while isinstance(leaf, (tuple, list)):
            leaf = leaf[0]
        np.asarray(leaf)
        return time.perf_counter() - t0

    staged1 = get_staged(segs, needed, gfwd_columns=("l_returnflag",), ctx=ctx)
    plan1 = build_static_plan(req, ctx, staged1)
    t_single = bench(make_table_kernel(plan1), staged1)

    mesh = default_mesh()
    from jax.sharding import NamedSharding, PartitionSpec as P

    staged8 = get_staged(
        segs,
        needed,
        pad_segments_to=8,
        gfwd_columns=("l_returnflag",),
        ctx=ctx,
        sharding=NamedSharding(mesh, P("segments")),
    )
    plan8 = build_static_plan(req, ctx, staged8)
    t_mesh = bench(make_sharded_table_kernel(plan8, mesh), staged8)
    assert t_single / max(t_mesh, 1e-9) >= 3.0, (t_single, t_mesh)
