"""True multi-process multi-host execution: two OS
processes bring up ``jax.distributed.initialize`` (coordinator, process
ids, global device view — the real multi-host runtime wiring, not mesh
reshaping), build the 2-D (hosts, chips) mesh with
``make_multihost_mesh``, and run the production sharded query kernel
through a collective that crosses the process boundary.

Reference analog: the multi-server in-process cluster harness
(``pinot-integration-tests/.../ClusterTest.java:62``) — here at the
SPMD layer.  Skips when the CPU cross-process collective backend
(gloo) is unavailable in this jax build; the wiring under test is
real either way."""
import os
import socket
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_distributed_mesh():
    coordinator = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    # the worker pins its own platform/device-count flags; scrub any
    # conftest-inherited backend state
    env.pop("XLA_FLAGS", None)
    env["PINOT_TPU_TESTS"] = ""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(WORKER)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, coordinator, "2", str(pid)],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(WORKER))),
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multihost workers timed out")

    for rc, out, err in outs:
        if rc != 0:
            low = (err or "").lower()
            if "gloo" in low or "collectives" in low or "cross-host" in low or "unimplemented" in low:
                pytest.skip(f"CPU cross-process collectives unavailable: {err[-400:]}")
            pytest.fail(f"worker failed rc={rc}\nstdout={out}\nstderr={err[-2000:]}")

    # both processes observe the SAME globally-reduced count: 8
    # segments x 512 rows, filter matches everything
    results = [
        line for rc, out, _ in outs for line in out.splitlines() if line.startswith("RESULT")
    ]
    assert len(results) == 2, results
    vals = {line.split("num_docs=")[1] for line in results}
    assert vals == {"4096.0"}, results


SERVE_WORKER = os.path.join(os.path.dirname(__file__), "multihost_serve_worker.py")


@pytest.mark.slow
def test_broker_pql_through_multihost_mesh():
    """End-to-end PQL answered by a multi-host mesh:
    a real BrokerRequestHandler scatter-gathers to the LEAD host of a
    2-process (hosts, chips) mesh-serving group; the lead fans the
    query to the follower so both enter the sharded kernel's
    cross-process collectives, and the broker merges the one reply."""
    import time

    coordinator = f"127.0.0.1:{_free_port()}"
    lead_port, follower_port = _free_port(), _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PINOT_TPU_TESTS"] = ""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(SERVE_WORKER)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    args = {
        0: [coordinator, "2", "0", str(lead_port), str(follower_port)],
        1: [coordinator, "2", "1", str(follower_port)],
    }
    # stdout/stderr go to FILES: a chatty worker blocking on a full
    # stderr pipe would deadlock the readiness loop below
    import tempfile

    logdir = tempfile.mkdtemp(prefix="meshserve_")
    outs = [open(os.path.join(logdir, f"w{pid}.out"), "w+") for pid in (0, 1)]
    errs = [open(os.path.join(logdir, f"w{pid}.err"), "w+") for pid in (0, 1)]
    procs = [
        subprocess.Popen(
            [sys.executable, SERVE_WORKER, *args[pid]],
            stdout=outs[pid],
            stderr=errs[pid],
            text=True,
            env=env,
            cwd=repo_root,
        )
        for pid in (0, 1)
    ]

    def read(f):
        f.flush()
        f.seek(0)
        return f.read()

    try:
        # wait for both hosts to report SERVING (coordinator + mesh up)
        deadline = time.time() + 240
        serving = set()
        while len(serving) < 2 and time.time() < deadline:
            for i, p in enumerate(procs):
                if i in serving:
                    continue
                if p.poll() is not None:
                    err = read(errs[i])
                    low = err.lower()
                    if "gloo" in low or "collectives" in low or "unimplemented" in low:
                        pytest.skip(f"CPU cross-process collectives unavailable: {err[-300:]}")
                    pytest.fail(f"worker {i} died rc={p.returncode}\n{err[-2000:]}")
                if "SERVING" in read(outs[i]):
                    serving.add(i)
            time.sleep(0.2)
        assert len(serving) == 2, "mesh hosts did not come up in time"

        from pinot_tpu.broker.broker import BrokerRequestHandler
        from pinot_tpu.broker.routing import RoutingTableProvider
        from pinot_tpu.transport.tcp import TcpTransport

        routing = RoutingTableProvider()
        routing.update(
            "lineitem", {f"mh{i}": {"meshhost0": "ONLINE"} for i in range(8)}
        )
        broker = BrokerRequestHandler(
            TcpTransport(),
            {"meshhost0": ("127.0.0.1", lead_port)},
            routing=routing,
            timeout_ms=240_000.0,
        )
        resp = broker.handle_pql(
            "SELECT sum(l_quantity), count(*) FROM lineitem "
            "WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag TOP 10"
        )
        assert not resp.exceptions, resp.exceptions
        assert resp.num_docs_scanned == 4096  # all 8 x 512 rows, via the mesh
        counts = {
            tuple(g.group): g.value
            for g in resp.aggregation_results[1].group_by_result
        }
        assert sum(counts.values()) == 4096
        # second query exercises steady-state ordering across processes
        resp2 = broker.handle_pql("SELECT count(*) FROM lineitem")
        assert not resp2.exceptions, resp2.exceptions
        assert resp2.aggregation_results[0].value == 4096.0

        # follower death: the lead's liveness preflight must fail the
        # query fast (error response) instead of wedging the collective
        procs[1].terminate()
        try:
            procs[1].wait(timeout=10)
        except subprocess.TimeoutExpired:
            procs[1].kill()  # CPU-only worker: SIGKILL is safe
            procs[1].wait(timeout=10)
        t0 = time.time()
        resp3 = broker.handle_pql("SELECT count(*) FROM lineitem")
        assert resp3.exceptions, "dead follower must surface as a query error"
        assert "unreachable" in resp3.exceptions[0].message
        assert time.time() - t0 < 60, "follower-down detection took too long"
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
        for f in outs + errs:
            f.close()


@pytest.mark.slow
def test_mesh_follower_death_between_preflight_and_collective():
    """The HARD failure window: the follower answers the
    lead's liveness ping, then dies on query receipt — after preflight,
    before collective entry.  The lead's forward-grace watch must (1)
    fail THIS query with a typed error instead of entering the doomed
    psum barrier, and (2) mark the group degraded so every later query
    errors fast until the group is restarted."""
    import time

    coordinator = f"127.0.0.1:{_free_port()}"
    lead_port, follower_port = _free_port(), _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PINOT_TPU_TESTS"] = ""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(SERVE_WORKER)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    follower_env = dict(env)
    follower_env["PINOT_TPU_MESH_TEST_EXIT_ON_QUERY"] = "1"
    args = {
        0: [coordinator, "2", "0", str(lead_port), str(follower_port)],
        1: [coordinator, "2", "1", str(follower_port)],
    }
    import tempfile

    logdir = tempfile.mkdtemp(prefix="meshdeath_")
    outs = [open(os.path.join(logdir, f"w{pid}.out"), "w+") for pid in (0, 1)]
    errs = [open(os.path.join(logdir, f"w{pid}.err"), "w+") for pid in (0, 1)]
    procs = [
        subprocess.Popen(
            [sys.executable, SERVE_WORKER, *args[pid]],
            stdout=outs[pid],
            stderr=errs[pid],
            text=True,
            env=env if pid == 0 else follower_env,
            cwd=repo_root,
        )
        for pid in (0, 1)
    ]

    def read(f):
        f.flush()
        f.seek(0)
        return f.read()

    try:
        deadline = time.time() + 240
        serving = set()
        while len(serving) < 2 and time.time() < deadline:
            for i, p in enumerate(procs):
                if i in serving:
                    continue
                if p.poll() is not None:
                    err = read(errs[i])
                    low = err.lower()
                    if "gloo" in low or "collectives" in low or "unimplemented" in low:
                        pytest.skip(f"CPU cross-process collectives unavailable: {err[-300:]}")
                    pytest.fail(f"worker {i} died rc={p.returncode}\n{err[-2000:]}")
                if "SERVING" in read(outs[i]):
                    serving.add(i)
            time.sleep(0.2)
        assert len(serving) == 2, "mesh hosts did not come up in time"

        from pinot_tpu.broker.broker import BrokerRequestHandler
        from pinot_tpu.broker.routing import RoutingTableProvider
        from pinot_tpu.transport.tcp import TcpTransport

        routing = RoutingTableProvider()
        routing.update(
            "lineitem", {f"mh{i}": {"meshhost0": "ONLINE"} for i in range(8)}
        )
        broker = BrokerRequestHandler(
            TcpTransport(),
            {"meshhost0": ("127.0.0.1", lead_port)},
            routing=routing,
            timeout_ms=240_000.0,
        )
        # the follower pings PONG (alive), then _exit(17)s on the query
        t0 = time.time()
        resp = broker.handle_pql("SELECT count(*) FROM lineitem")
        took = time.time() - t0
        assert resp.exceptions, "mid-query follower death must error, not hang"
        assert "between preflight and collective entry" in resp.exceptions[0].message
        assert took < 60, f"mid-query death detection took {took:.0f}s"
        try:
            rc = procs[1].wait(timeout=10)
        except subprocess.TimeoutExpired:
            rc = None
        assert rc == 17, f"follower should have exited via the hook (rc={rc})"

        # the group is now degraded: every subsequent query errors FAST
        t0 = time.time()
        resp2 = broker.handle_pql("SELECT count(*) FROM lineitem")
        assert resp2.exceptions
        assert "degraded" in resp2.exceptions[0].message
        assert time.time() - t0 < 15, "degraded replies must be immediate"
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
        for f in outs + errs:
            f.close()
