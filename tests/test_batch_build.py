"""Multiprocess batch segment build + push (pinot-hadoop analog,
``SegmentCreationJob.java`` / ``SegmentTarPushJob.java``)."""
import csv
import json
import urllib.request

import pytest

from pinot_tpu.common.schema import Schema
from pinot_tpu.controller.controller import ControllerHttpServer
from pinot_tpu.tools.batch_build import BatchBuildSpec, run_batch_build
from pinot_tpu.tools.datagen import make_test_schema, random_rows


def _write_inputs(tmp_path, schema: Schema, shards: int, rows_per: int):
    paths = []
    cols = [f.name for f in schema.all_fields()]
    for i in range(shards):
        rows = random_rows(schema, rows_per, seed=100 + i)
        p = tmp_path / f"shard{i}.csv"
        with open(p, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(cols)
            for r in rows:
                w.writerow([r[c] for c in cols])
        paths.append(str(p))
    return paths


@pytest.fixture()
def schema_file(tmp_path):
    schema = make_test_schema(with_mv=False)
    p = tmp_path / "schema.json"
    p.write_text(json.dumps(schema.to_json()))
    return schema, str(p)


def test_batch_build_multiprocess(tmp_path, schema_file):
    schema, schema_path = schema_file
    inputs = _write_inputs(tmp_path, schema, shards=3, rows_per=40)
    spec = BatchBuildSpec(
        schema_file=schema_path,
        table="bb",
        input_files=inputs,
        out_dir=str(tmp_path / "out"),
    )
    results = run_batch_build(spec, workers=3)
    assert [r["segment"] for r in results] == ["bb_0", "bb_1", "bb_2"]
    assert all(r["docs"] == 40 and not r["pushed"] for r in results)

    from pinot_tpu.segment.format import read_segment

    for r in results:
        seg = read_segment(r["path"])
        assert seg.num_docs == 40


def test_batch_build_and_push_to_controller(tmp_path, schema_file):
    from pinot_tpu.tools.cluster_harness import InProcessCluster

    schema, schema_path = schema_file
    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path / "ctrl"))
    physical = cluster.add_offline_table(schema)
    http = ControllerHttpServer(cluster.controller)
    http.start()
    try:
        inputs = _write_inputs(tmp_path, schema, shards=2, rows_per=30)
        spec = BatchBuildSpec(
            schema_file=schema_path,
            table=physical,
            input_files=inputs,
            out_dir=str(tmp_path / "out"),
            controller=f"http://127.0.0.1:{http.port}",
        )
        # workers=1 keeps the push in-process (the pool path is covered
        # above; pushes go through the same HTTP client either way)
        results = run_batch_build(spec, workers=1)
        assert all(r["pushed"] for r in results)
        assert cluster.query("SELECT count(*) FROM testTable").num_docs_scanned == 60
    finally:
        http.stop()
        cluster.stop()


# -- cross-machine fan-out (SegmentCreationJob parity) ---


def _spawn_worker(tmp_path, name):
    """A build worker as a real OS process; returns (proc, port)."""
    import subprocess
    import sys
    import time

    import os

    script = tmp_path / f"{name}.py"
    port_file = tmp_path / f"{name}.port"
    script.write_text(
        "import sys, time\n"
        "from pinot_tpu.tools.batch_build import serve_build_worker\n"
        "srv = serve_build_worker(host='127.0.0.1', port=0)\n"
        f"open({str(port_file)!r}, 'w').write(str(srv.port))\n"
        "time.sleep(600)\n"
    )
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, str(script)],
        env={
            **os.environ,
            "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
        },
    )
    for _ in range(100):
        if port_file.exists() and port_file.read_text().strip():
            return proc, int(port_file.read_text())
        time.sleep(0.1)
    proc.kill()
    raise RuntimeError(f"worker {name} did not start")


def test_distributed_build_two_process_workers_and_push(tmp_path, schema_file):
    """N shards across 2 real OS-process workers, pushed to a live
    controller, queryable after — plus per-shard retry when one worker
    dies mid-run."""
    from pinot_tpu.tools.batch_build import run_distributed_build
    from pinot_tpu.tools.cluster_harness import InProcessCluster

    schema, schema_path = schema_file
    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path / "ctrl"))
    physical = cluster.add_offline_table(schema)
    http = ControllerHttpServer(cluster.controller)
    http.start()
    w1 = w2 = None
    try:
        w1, p1 = _spawn_worker(tmp_path, "w1")
        w2, p2 = _spawn_worker(tmp_path, "w2")
        inputs = _write_inputs(tmp_path, schema, shards=4, rows_per=25)
        spec = BatchBuildSpec(
            schema_file=schema_path,
            table=physical,
            input_files=inputs,
            out_dir=str(tmp_path / "out"),
            controller=f"http://127.0.0.1:{http.port}",
        )
        results = run_distributed_build(
            spec, [("127.0.0.1", p1), ("127.0.0.1", p2)], timeout_s=120.0
        )
        assert [r["segment"] for r in results] == [f"{physical}_{i}" for i in range(4)]
        assert all(r["pushed"] for r in results)
        assert cluster.query("SELECT count(*) FROM testTable").num_docs_scanned == 100

        # kill one worker: every shard still completes via retry on the
        # survivor (Hadoop mapper re-execution analog)
        w1.terminate()
        w1.wait(timeout=30)
        spec2 = BatchBuildSpec(
            schema_file=schema_path,
            table=physical,
            input_files=inputs[:2],
            out_dir=str(tmp_path / "out2"),
            segment_name_prefix="bb2",
        )
        results2 = run_distributed_build(
            spec2, [("127.0.0.1", p1), ("127.0.0.1", p2)], timeout_s=120.0
        )
        assert [r["segment"] for r in results2] == ["bb2_0", "bb2_1"]
    finally:
        for w in (w1, w2):
            if w is not None:
                w.terminate()
        http.stop()
        cluster.stop()
