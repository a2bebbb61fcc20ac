"""Networked control plane: controller, servers, broker as real OS
processes coordinated over HTTP (the multi-JVM ClusterTest analog —
``pinot-integration-tests/.../ClusterTest.java:62`` — but with actual
process boundaries instead of one JVM).

Covers: instance registration + heartbeats, transition messages +
acks (segment download with local cache), broker cluster-state polling
for routing, liveness-based failover when a server is SIGKILLed.
"""
import json
import os
import select
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

from pinot_tpu.common.tableconfig import TableConfig
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.format import SEGMENT_FILE_NAME, write_segment
from pinot_tpu.tools.datagen import make_test_schema, random_rows

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TABLE = "netTable"
PHYSICAL = "netTable_OFFLINE"


def _admin_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if os.environ.get("PINOT_TPU_LOGLEVEL"):
        env["PINOT_TPU_LOGLEVEL"] = os.environ["PINOT_TPU_LOGLEVEL"]
    return env


def _spawn(args, ready_prefix="READY"):
    # PINOT_TPU_TEST_LOGDIR=<dir> tees each child's stderr to a file —
    # the only way to see why a spawned role stalled in a flaky run
    log_dir = os.environ.get("PINOT_TPU_TEST_LOGDIR")
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        name = "_".join(a.lstrip("-") for a in args[:3]).replace("/", "_")
        stderr = open(os.path.join(log_dir, f"{name}_{time.time():.0f}.err"), "w")
    else:
        stderr = subprocess.DEVNULL
    proc = subprocess.Popen(
        [sys.executable, "-m", "pinot_tpu.tools.admin", *args],
        cwd=REPO_ROOT,
        env=_admin_env(),
        stdout=subprocess.PIPE,
        stderr=stderr,
        text=True,
    )
    deadline = time.time() + 90
    while time.time() < deadline:
        # select so a child that hangs without printing can't block
        # readline() forever past the deadline
        ready, _, _ = select.select([proc.stdout], [], [], 1.0)
        if ready:
            line = proc.stdout.readline()
            if line.startswith(ready_prefix):
                return proc, line.split()[-1]
        if proc.poll() is not None:
            raise RuntimeError(f"process exited early: {args}")
    proc.kill()
    raise RuntimeError(f"no READY from {args}")


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def _post_json(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def _wait_for(cond, timeout=30, interval=0.25, what="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")



def _bring_up_cluster(tmp_path, ctrl_url, procs, schema, rows):
    """Spawn 2 servers + broker against a running controller, create the
    schema/table, upload two 200-row segments; returns broker_url."""
    for name in ("s0", "s1"):
        p, _addr = _spawn(
            ["StartServer", "-controller", ctrl_url, "-name", name,
             "-data-dir", str(tmp_path / f"cache_{name}")]
        )
        procs.append(p)
    broker_proc, broker_url = _spawn(
        ["StartBroker", "-controller", ctrl_url, "-port", "0"]
    )
    procs.append(broker_proc)

    _post_json(ctrl_url + "/schemas", schema.to_json())
    config = TableConfig(table_name=TABLE, table_type="OFFLINE", replication=2)
    _post_json(ctrl_url + "/tables", config.to_json())
    for i in range(2):
        seg = build_segment(schema, rows[i * 200 : (i + 1) * 200], PHYSICAL, f"net_{i}")
        d = str(tmp_path / f"build_{i}")
        write_segment(seg, d)
        with open(os.path.join(d, SEGMENT_FILE_NAME), "rb") as f:
            data = f.read()
        req = urllib.request.Request(
            ctrl_url + f"/segments/{PHYSICAL}", data=data,
            headers={"Content-Type": "application/octet-stream"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            assert json.loads(r.read())["status"] == "ok"
    return broker_url


@pytest.mark.slow
def test_networked_cluster_end_to_end(tmp_path):
    schema = make_test_schema(with_mv=False)
    schema.schema_name = TABLE
    rows = random_rows(schema, 400, seed=29)

    procs = []
    try:
        ctrl_proc, ctrl_url = _spawn(
            ["StartController", "-port", "0", "-data-dir", str(tmp_path / "store"),
             "-heartbeat-timeout", "2.0"]
        )
        procs.append(ctrl_proc)

        broker_url = _bring_up_cluster(tmp_path, ctrl_url, procs, schema, rows)
        # srv procs are procs[1:3] in spawn order (s0, s1)
        srv_procs = {"s0": procs[1], "s1": procs[2]}

        # transitions are async messages: wait until both replicas report ONLINE
        def _all_online():
            view = _get(ctrl_url + f"/tables/{PHYSICAL}/externalview")
            return (
                len(view) == 2
                and all(
                    set(replicas) == {"s0", "s1"}
                    and all(st == "ONLINE" for st in replicas.values())
                    for replicas in view.values()
                )
            )

        _wait_for(_all_online, timeout=60, what="segments ONLINE on both servers")

        # broker picked the view up by polling cluster state
        def _query(pql):
            return _post_json(broker_url + "/query", {"pql": pql})

        def _full_count():
            resp = _query(f"SELECT count(*) FROM {TABLE}")
            return resp.get("numDocsScanned") == 400 and not resp.get("exceptions")

        _wait_for(_full_count, timeout=60, what="broker routing serving all segments")

        expected_sum = sum(r["metInt"] for r in rows)
        resp = _query(f"SELECT sum(metInt) FROM {TABLE}")
        assert not resp["exceptions"]
        got = float(resp["aggregationResults"][0]["value"])
        assert got == pytest.approx(expected_sum, rel=1e-6)

        # one process for each chip: after serving queries, only the
        # server processes have a backend up (the platform the
        # environment chose); the controller and the broker have none
        assert _get(ctrl_url + "/health")["jax"]["backendInitialized"] is False
        assert _get(broker_url + "/health")["jax"]["backendInitialized"] is False
        util = _get(ctrl_url + "/debug/utilization")
        assert {
            name: s["device"]["platform"]["platform"]
            for name, s in util["servers"].items()
        } == {"s0": "cpu", "s1": "cpu"}

        # SIGKILL one server: heartbeats stop, controller marks it dead,
        # broker reroutes to the surviving replica -> still full results
        srv_procs["s0"].send_signal(signal.SIGKILL)
        srv_procs["s0"].wait(timeout=10)

        def _s0_dead():
            state = _get(ctrl_url + "/clusterstate")
            return "s0" not in state["servers"]

        _wait_for(_s0_dead, timeout=20, what="controller declaring s0 dead")

        def _failover_ok():
            resp = _query(f"SELECT count(*) FROM {TABLE}")
            return resp.get("numDocsScanned") == 400 and not resp.get("exceptions")

        _wait_for(_failover_ok, timeout=30, what="failover to surviving replica")

        # restart s0 under the same name + cache dir: re-registration must
        # reconcile (replay ideal state) and reload from the local cache
        p, _addr = _spawn(
            ["StartServer", "-controller", ctrl_url, "-name", "s0",
             "-data-dir", str(tmp_path / "cache_s0")]
        )
        procs.append(p)
        _wait_for(_all_online, timeout=60, what="restarted s0 back ONLINE")
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()


@pytest.mark.slow
def test_controller_sigkill_restart_recovers_cluster(tmp_path):
    """SIGKILL the controller process and restart it over the same data
    dir: metadata recovers from the property store, servers re-register
    and replay ideal state, the broker resumes routing — and while the
    controller is down, already-routed queries keep serving (the
    ZK-outage-tolerance analog)."""
    import socket

    schema = make_test_schema(with_mv=False)
    schema.schema_name = TABLE
    rows = random_rows(schema, 400, seed=31)

    # fixed controller port so restarted process is reachable at the
    # same URL the servers/brokers hold
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    ctrl_port = s.getsockname()[1]
    s.close()
    data_dir = str(tmp_path / "store")

    def start_controller():
        return _spawn(
            ["StartController", "-port", str(ctrl_port), "-data-dir", data_dir,
             "-heartbeat-timeout", "2.0"]
        )

    procs = []
    try:
        ctrl_proc, ctrl_url = start_controller()
        procs.append(ctrl_proc)

        broker_url = _bring_up_cluster(tmp_path, ctrl_url, procs, schema, rows)

        def _query(pql):
            return _post_json(broker_url + "/query", {"pql": pql})

        def _full_count():
            resp = _query(f"SELECT count(*) FROM {TABLE}")
            return resp.get("numDocsScanned") == 400 and not resp.get("exceptions")

        _wait_for(_full_count, timeout=60, what="cluster serving all segments")

        # --- SIGKILL the controller ---
        ctrl_proc.send_signal(signal.SIGKILL)
        ctrl_proc.wait(timeout=10)

        # data plane survives the control-plane outage: the broker keeps
        # its last routing table and servers keep serving
        time.sleep(1.0)
        assert _full_count(), "queries must keep serving while controller is down"

        # --- restart controller over the same data dir ---
        ctrl_proc2, ctrl_url2 = start_controller()
        procs.append(ctrl_proc2)
        assert ctrl_url2 == ctrl_url

        # recovered metadata visible immediately from the property store
        tables = _get(ctrl_url + "/tables")
        assert PHYSICAL in tables["tables"]
        ideal = _get(ctrl_url + f"/tables/{PHYSICAL}/idealstate")
        assert set(ideal) == {"net_0", "net_1"}

        # servers re-register via heartbeat 'reregister', replay ideal
        # state, external view refills, broker routing resumes
        def _view_refilled():
            view = _get(ctrl_url + f"/tables/{PHYSICAL}/externalview")
            return len(view) == 2 and all(
                st == "ONLINE"
                for replicas in view.values()
                for st in replicas.values()
            ) and all(len(r) == 2 for r in view.values())

        _wait_for(_view_refilled, timeout=60, what="external view refilled after restart")
        _wait_for(_full_count, timeout=30, what="queries after controller restart")

        expected_sum = sum(r["metInt"] for r in rows)
        resp = _query(f"SELECT sum(metInt) FROM {TABLE}")
        assert not resp["exceptions"]
        assert float(resp["aggregationResults"][0]["value"]) == pytest.approx(
            expected_sum, rel=1e-6
        )
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
