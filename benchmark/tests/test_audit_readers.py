"""The four readers of the audit plane (PR 29) and their cell,
``lineitem_suite_audited``: a rehearsal of the cell on the CPU at a tiny
size with the auditor sampling often enough that passes fall in a window
of 2 s, and each reader on the counters of programs that lack the series.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
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
from test_layer_readers import tiny_manifest  # noqa: E402,F401  (the real manifest with every cell on the tiny configuration)

CELL = "lineitem_suite_audited"
READERS = ["audit_coverage_share", "audit_oracle_ms_mean", "audit_step_max_ms", "audit_divergences_in_window"]
SERIES = ["server.meter.audit.offered", "server.meter.audit.samples", "server.meter.audit.dropped",
          "server.meter.audit.errors", "server.meter.audit.divergences", "server.timer.audit.shadowMs.n",
          "server.timer.audit.stepMs.n", "server.gauge.audit.stepMaxMs"]


def reader(name):
    return run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read


def fake_run(before: dict, after: dict, samples: int = 0):
    return types.SimpleNamespace(before=before, after=after, samples=[None] * samples,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def test_manifest_adds_one_configuration_one_cell_and_four_readers_last():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = manifest["configs"][-1]
    assert config["name"] == "tpch_lineitem_audited_1chip" and config["reduced"] == [] and len(config["source"]) <= 200
    on_file = json.load(open(os.path.join(ROOT, config["file"])))
    assert on_file["env"] == {} and on_file["source"] == config["source"] and on_file["reduced"] == []
    base = json.load(open(os.path.join(BENCH, "configs", "tpch_lineitem_1chip.json")))
    for key in ("table", "schema", "generator", "segments", "rows_per_segment", "chips", "assumed", "scale"):
        assert on_file[key] == base[key], key
    promised = dict(on_file["guarantees"])
    audit = promised.pop("audit")
    assert promised == base["guarantees"]  # nothing weakened, one promise more
    assert "1 in 64" in audit["sample"] and "float64" in audit["oracle"] and "all rows" in audit["oracle"]
    assert "5e-4" in audit["compared"] and "1e-3" in audit["compared"] and "quarantined" in audit["on_divergence"]
    cell = manifest["workloads"][-1]
    assert cell == {"name": CELL, "config": config["name"], "traffic": "suite_open", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "knee" in cell["why"]
    assert [m["name"] for m in manifest["per_layer"][-4:]] == READERS
    for m in manifest["per_layer"][-4:]:
        assert m["layer"] == "audit plane" and m["moves"] == "latency_p50_ms" and m["workloads"] == [CELL]
    # the cell reports what the open cell reports but the tail: the parent's p95 in this cell spread more
    # than the bound admits (PERF.md section 7), so latency_p95_ms and the reader that moves it keep the
    # lists they had; nothing was taken from a list that was there
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            if "lineitem_suite_open" in m.get("workloads", []):
                tail = m["name"] == "latency_p95_ms" or m.get("moves") == "latency_p95_ms"
                assert (CELL not in m["workloads"]) if tail else (m["workloads"][-1] == CELL), m["name"]


def test_rehearsal_of_the_audited_cell_finds_every_readers_counters(capsys, monkeypatch, tiny_manifest):
    monkeypatch.setenv("PINOT_TPU_AUDIT_SAMPLE_N", "8")  # some twenty passes in the rehearsal and the window
    returned, seen = {}, {}
    load_module = run.load_module

    def recording(path):
        module = load_module(path)
        name = os.path.basename(path)[:-3]
        if name not in READERS:
            return module

        def read(r):
            seen.update(after=r.after, delta={k: r.delta(k) for k in SERIES})
            returned[name] = module.read(r)
            return returned[name]

        return types.SimpleNamespace(read=read)

    monkeypatch.setattr(run, "load_module", recording)
    assert run.main(["--workload", CELL, "--seed", str(2**31 + 29), "--seconds", "2", "--trace", "1"],
                    allow_cpu=True, manifest_path=tiny_manifest) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["device"]["platform"] == "cpu"
    assert sorted(returned) == sorted(READERS)
    for key in SERIES:
        assert key in seen["after"], key
    delta = seen["delta"]
    assert delta["server.meter.audit.offered"] >= 4 and delta["server.meter.audit.samples"] >= 1
    assert delta["server.timer.audit.stepMs.n"] >= 2 * delta["server.timer.audit.shadowMs.n"]  # two segments a pass
    assert delta["server.meter.audit.errors"] == 0
    for name, value in returned.items():
        assert isinstance(value, float) and value >= 0, (name, value)
    assert 0 < returned["audit_coverage_share"] <= 100 * (1 + 16 / delta["server.meter.audit.offered"])
    assert returned["audit_step_max_ms"] > 0 and returned["audit_oracle_ms_mean"] > 0
    # three derivations agree: device, the program's float64 oracle, the benchmark's reference
    assert returned["audit_divergences_in_window"] == 0
    # without the chip only counts are printed: the one count among the four
    assert out["metrics"]["audit_divergences_in_window"] == {"value": 0.0, "unit": "count"}
    assert not set(out["metrics"]) & set(READERS[:3])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_on_a_program_without_the_series(name):
    """No auditor's counters at all, or an auditor that is off (its
    meters stand at 0): nothing raises, every reader returns ``None``."""
    assert reader(name)(fake_run({}, {}, samples=1392)) is None
    off = {key: 0 for key in SERIES}
    assert reader(name)(fake_run(off, dict(off), samples=1392)) is None


def test_readers_on_the_parents_counters(monkeypatch):
    """Before PR 29 the program has ``audit.samples``, ``audit.shadowMs``
    and ``audit.divergences`` and neither ``audit.offered`` nor steps:
    coverage is taken over the window's queries / 64, the step reader
    finds nothing."""
    monkeypatch.delenv("PINOT_TPU_AUDIT_SAMPLE_N", raising=False)
    before = {"server.meter.audit.samples": 1, "server.meter.audit.divergences": 0,
              "server.timer.audit.shadowMs.n": 1, "server.timer.audit.shadowMs.ms": 9000.0}
    after = {"server.meter.audit.samples": 6, "server.meter.audit.divergences": 0,
             "server.timer.audit.shadowMs.n": 6, "server.timer.audit.shadowMs.ms": 54000.0}
    parent = fake_run(before, after, samples=1392)  # 21 offers
    assert reader("audit_coverage_share")(parent) == pytest.approx(100.0 * 5 / 21)
    assert reader("audit_oracle_ms_mean")(parent) == 9000.0
    assert reader("audit_divergences_in_window")(parent) == 0.0
    assert reader("audit_step_max_ms")(parent) is None


def test_readers_on_the_changes_counters():
    before = {"server.meter.audit.offered": 2, "server.meter.audit.samples": 1, "server.meter.audit.divergences": 0,
              "server.timer.audit.shadowMs.n": 1, "server.timer.audit.shadowMs.ms": 1300.0,
              "server.timer.audit.stepMs.n": 128, "server.gauge.audit.stepMaxMs": 30.0}
    after = {"server.meter.audit.offered": 23, "server.meter.audit.samples": 21, "server.meter.audit.divergences": 1,
             "server.timer.audit.shadowMs.n": 21, "server.timer.audit.shadowMs.ms": 27300.0,
             "server.timer.audit.stepMs.n": 2688, "server.gauge.audit.stepMaxMs": 35.5}
    change = fake_run(before, after, samples=1392)
    assert reader("audit_coverage_share")(change) == pytest.approx(100.0 * 20 / 21)
    assert reader("audit_oracle_ms_mean")(change) == 1300.0
    assert reader("audit_step_max_ms")(change) == 35.5
    assert reader("audit_divergences_in_window")(change) == 1.0
