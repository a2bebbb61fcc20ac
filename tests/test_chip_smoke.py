"""The rules a program meant for the chip keeps, held on the CPU: it
checks the platform it got and refuses the wrong one before doing any
work, and the smoke's own logic (upload, HTTP query, numpy reference,
tier and heal assertions) runs in seconds at a cut size so it cannot rot
between chip runs."""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, env_changes, timeout=300):
    env = dict(os.environ)
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, *argv],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    return out, time.monotonic() - t0


def test_chip_smoke_refuses_the_cpu_before_any_work():
    out, took = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert out.returncode not in (0, None)
    assert "platform is 'cpu'" in out.stderr and "need 'tpu'" in out.stderr
    assert out.stdout == ""  # no result line, no data generated
    assert took < 60


def test_chip_smoke_whole_logic_at_a_cut_size():
    out, _ = _run(
        [
            "chip_smoke.py", "--allow-cpu", "--seed", "3",
            "--segments", "2", "--rows-per-segment", "20000",
            "--realtime-events", "5000", "--realtime-rows-per-segment", "2000",
        ],
        {"JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True and set(last) == {"ok", "device"}
    # the device as jax reports it (the child inherits the virtual CPU mesh)
    assert last["device"]["platform"] == "cpu" and last["device"]["kind"] == "cpu"
    assert last["device"]["count"] >= 1
    report = json.loads(next(l for l in lines if l.startswith("# report: "))[len("# report: "):])
    assert report["lineitem"]["rows"] == 40000
    assert report["realtime"] == {
        **report["realtime"], "events": 5000, "sealedSegments": 2, "consumingRows": 1000,
    }
    tiers = {name: q["tier"] for name, q in report["queries"].items()}
    # the tier accounting tells the host postings tier from the device tiers
    assert tiers["point_lookup"] == "Postings"
    assert tiers["q1"] == "FullScan"
    assert len(tiers) == 10
    assert all(v == 0 for v in report["zeroMeters"].values())
    assert report["stagedBytes"] > 0
    assert "CUT: 2 x 20,000 rows" in out.stdout


def test_chip_smoke_fails_when_a_phase_fails(tmp_path):
    """A wrong answer is a non-zero exit and no result line: break the
    reference (not the system) by importing the smoke with a tighter
    tolerance than HLL can meet."""
    driver = tmp_path / "broken_reference.py"
    driver.write_text(
        "import sys, chip_smoke\n"
        "chip_smoke.HLL_RTOL = 0.0\n"
        "sys.exit(chip_smoke.main(['--allow-cpu', '--segments', '1',\n"
        "    '--rows-per-segment', '20000', '--realtime-events', '3000',\n"
        "    '--realtime-rows-per-segment', '2000']))\n"
    )
    out, _ = _run([str(driver)], {"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    assert out.returncode not in (0, None)
    assert "SmokeFailure: hll" in out.stderr
    assert '"ok"' not in out.stdout


def test_controller_broker_and_quickstart_parent_never_initialize_a_backend(tmp_path):
    """One process for each chip: on a machine with a chip only the server
    process may bring a backend up.  The controller, the broker and the
    networked quickstart's parent import jax at most; they never
    initialize it (their /health reports the same ``backend_state``)."""
    script = tmp_path / "roles.py"
    script.write_text(
        "import json\n"
        "import pinot_tpu.tools.quickstart  # what the quickstart's parent imports\n"
        "from pinot_tpu.tools.cluster_harness import InProcessCluster\n"
        "from pinot_tpu.tools.datagen import lineitem_schema\n"
        "from pinot_tpu.utils.platform import backend_state\n"
        "cluster = InProcessCluster(num_servers=0, http=True)  # controller + broker\n"
        "cluster.add_offline_table(lineitem_schema())\n"
        "resp = cluster.query('SELECT sum(l_quantity) FROM lineitem GROUP BY l_shipmode TOP 3')\n"
        "assert resp.exceptions[0].error_code == 410  # parsed, planned, nothing to route to\n"
        "cluster.stop()\n"
        "print(json.dumps(backend_state()))\n"
    )
    out, _ = _run([str(script)], {"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    assert out.returncode == 0, out.stderr[-2000:]
    state = json.loads(out.stdout.strip().splitlines()[-1])
    assert state["backendInitialized"] is False and state["platform"] is None
