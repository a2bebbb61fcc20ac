"""The per-layer readers that read the program's own spans and counters
(PR 25): a rehearsal of a whole traced run on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

On the CPU the run prints no time under a metric's name, so the printed
line carries none of them; what each reader returned is taken where the
run calls it.  A reader finds its counters (a number) or, on a program
that lacks the span, nothing (``None``): it never raises.
"""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run  # noqa: E402

NEW = ["plan_build_ms_mean", "lane_queue_ms_mean", "lane_launch_ms_mean", "device_wait_ms_mean",
       "d2h_unpack_ms_mean", "transport_ms_mean", "render_ms_mean", "bookkeeping_ms_mean",
       "host_unattributed_ms_mean", "lane_busy_share"]


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory) -> str:
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    manifest["configs"] = [{"name": "tiny", "source": "tests only", "file": "benchmark/tests/tiny_config.json",
                            "reduced": ["segments", "rows_per_segment"], "why": "a rehearsal on the CPU"}]
    for w in manifest["workloads"]:
        w["config"] = "tiny"
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def test_manifest_lists_the_new_readers_last_and_for_every_cell():
    """PR 25's ten are there, in their order, each for every cell.  Not
    last: a PR that adds a reader appends its entry after them (PR 26's
    stands there).  A PR that adds a cell appends it to their
    ``workloads`` lists, the one edit such a PR makes to entries that
    are there."""
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [n for n in entries if n in NEW] == NEW
    cells = [w["name"] for w in manifest["workloads"]]
    for m in (entries[n] for n in NEW):
        assert m["moves"] == "latency_p50_ms" and m["source"] in ("program_span", "program_counter")
        assert m.get("workloads", cells) == cells
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    # retired in PR 28, each the sum of readers listed on their own
    assert "launch_fetch_ms_mean" not in entries
    assert not os.path.exists(os.path.join(BENCH, "layer_metrics", "launch_fetch_ms_mean.py"))


@pytest.mark.parametrize("workload", ["lineitem_suite_open", "lineitem_groupby_closed"])
def test_rehearsal_reads_every_new_metric_and_prints_no_time(capsys, monkeypatch, tiny_manifest, workload):
    returned = {}
    load_module = run.load_module

    def recording(path):
        module = load_module(path)
        name = os.path.basename(path)[:-3]
        if name not in NEW:
            return module

        def read(r):
            returned[name] = module.read(r)
            return returned[name]

        return types.SimpleNamespace(read=read)

    monkeypatch.setattr(run, "load_module", recording)
    assert run.main(["--workload", workload, "--seed", str(2**31 + 25), "--seconds", "2", "--trace", "1"],
                    allow_cpu=True, manifest_path=tiny_manifest) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert not set(out["metrics"]) & set(NEW)  # times and shares: none printed without the chip
    assert sorted(returned) == sorted(NEW)
    # under allow_cpu every one of them found its counters
    for name, value in returned.items():
        assert isinstance(value, float) and value >= 0, (name, value)
    assert returned["host_unattributed_ms_mean"] < returned["device_wait_ms_mean"] + 50
    assert 0 < returned["lane_busy_share"] <= 100


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_on_a_program_without_the_span(name):
    """The parent's counters: ``phase.laneDispatch`` is there, the rest
    is not.  Nothing raises, every reader returns ``None``."""
    old = {"server.timer.phase.laneDispatch.ms": 5.0, "server.timer.phase.laneDispatch.n": 10,
           "broker.timer.queryTotal.ms": 100.0, "broker.timer.queryTotal.n": 10,
           "server.timer.queryExecution.n": 10}
    r = types.SimpleNamespace(delta=lambda key: old.get(key, 0), window_s=45.0, samples=[], trace=None)
    assert run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read(r) is None


def test_scheduler_wait_reads_the_schedulers_queue_alone():
    """``phase.laneWait`` (the lane's queue, launch, delivery and wake-up,
    each listed on its own) is no part of it since PR 28."""
    d = {"server.timer.phase.schedulerWait.ms": 4.0, "server.timer.phase.schedulerWait.n": 10,
         "server.timer.phase.laneWait.ms": 300.0, "server.timer.phase.laneWait.n": 10,
         "server.timer.queryExecution.n": 10}
    read = run.load_module(os.path.join(BENCH, "layer_metrics", "scheduler_wait_ms_mean.py")).read
    assert read(types.SimpleNamespace(delta=lambda key: d.get(key, 0))) == 0.4
    assert read(types.SimpleNamespace(delta=lambda key: 0)) is None
