"""The readers of a connection's life (PR 39): ``http_accept_ms_mean``,
``http_head_ms_mean``, ``http_close_ms_mean`` and
``http_outside_program_ms_mean``, and of the two timers that were read
by hand, ``staging_window_ms_mean`` and ``kernel_prep_ms_mean``: their
arithmetic on a hand-made run, nothing where the program has no such
timer (the parent of PR 39), their entries in the manifest, looked up by
name, and a rehearsal on the CPU that ends with the new timers counted.

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

# reader: (the timer whose window mean it is, layer, source)
SPANS = {
    "http_accept_ms_mean": ("broker.timer.phase.httpAccept", "client and HTTP"),
    "http_head_ms_mean": ("broker.timer.phase.httpHead", "client and HTTP"),
    "http_close_ms_mean": ("broker.timer.phase.httpClose", "client and HTTP"),
    "staging_window_ms_mean": ("server.timer.phase.staging", "staging, H2D"),
    "kernel_prep_ms_mean": ("server.timer.phase.kernelPrep", "plan build"),
}
OUTSIDE = "http_outside_program_ms_mean"
READERS = {name: run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py")) for name in (*SPANS, OUTSIDE)}
ACCEPT, HEAD, TOTAL = "broker.timer.phase.httpAccept", "broker.timer.phase.httpHead", "broker.timer.httpTotal"


def _run(before, after, samples=()):
    return types.SimpleNamespace(before=before, after=after, samples=list(samples), window_s=45.0, trace=None,
                                 delta=lambda key: after.get(key, 0) - before.get(key, 0))


def _sample(sent, wait_ms, ok=True):
    return {"sent": sent, "done": sent + wait_ms / 1000.0, "ok": ok, "reply": {"timeUsedMs": 1.0} if ok else None}


@pytest.mark.parametrize("name", sorted(SPANS))
def test_a_span_reader_is_the_timers_mean_over_the_window(name):
    timer = SPANS[name][0]
    # set-up and the rehearsal left 90 updates, the window added 1,350 of 0.25 ms each
    before = {timer + ".n": 90, timer + ".ms": 400.0}
    after = {timer + ".n": 1440, timer + ".ms": 400.0 + 1350 * 0.25}
    assert READERS[name].read(_run(before, after)) == pytest.approx(0.25)
    # a program without the timer (the parent), and a window that did not move it
    assert READERS[name].read(_run({}, {})) is None
    assert READERS[name].read(_run(before, before)) is None


def test_outside_the_program_is_the_clients_wait_less_accept_head_and_handler():
    before = {k + s: v for k in (ACCEPT, HEAD, TOTAL) for s, v in ((".n", 10), (".ms", 50.0))}
    after = {ACCEPT + ".n": 14, ACCEPT + ".ms": 50.0 + 4 * 0.3, HEAD + ".n": 14, HEAD + ".ms": 50.0 + 4 * 0.4,
             TOTAL + ".n": 14, TOTAL + ".ms": 50.0 + 4 * 6.0,
             "broker.timer.phase.httpClose.n": 14, "broker.timer.phase.httpClose.ms": 4 * 100.0}  # no part of it
    waits = [_sample(0.0, 7.0), _sample(0.1, 8.0), _sample(0.2, 9.0), _sample(0.3, 8.0)]
    # mean wait 8.0, inside the program 0.3 + 0.4 + 6.0
    assert READERS[OUTSIDE].read(_run(before, after, waits)) == pytest.approx(8.0 - 6.7)
    # a reply that was not sound is no part of the mean, whatever it waited
    assert READERS[OUTSIDE].read(_run(before, after, waits + [_sample(0.4, 900.0, ok=False)])) == pytest.approx(1.3)


def test_outside_the_program_finds_nothing_without_the_accept_timer_or_a_sound_reply():
    old = {TOTAL + ".n": 4, TOTAL + ".ms": 24.0}  # the parent: the handler's timer alone
    waits = [_sample(0.0, 7.0), _sample(0.1, 9.0)]
    assert READERS[OUTSIDE].read(_run({}, old, waits)) is None
    new = dict(old, **{ACCEPT + ".n": 4, ACCEPT + ".ms": 1.2, HEAD + ".n": 4, HEAD + ".ms": 1.6})
    assert READERS[OUTSIDE].read(_run({}, new, waits)) == pytest.approx(8.0 - 6.7)
    assert READERS[OUTSIDE].read(_run({}, new, [])) is None
    assert READERS[OUTSIDE].read(_run({}, new, [_sample(0.0, 7.0, ok=False)])) is None
    assert READERS[OUTSIDE].read(_run({}, {}, waits)) is None


@pytest.mark.parametrize("name", [*sorted(SPANS), OUTSIDE])
def test_the_manifest_lists_it_for_every_cell_under_its_layer(name):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    entry = by_name[name]
    assert "workloads" not in entry  # every cell has an HTTP front and an executor: a later cell gets it without an edit
    assert (entry["unit"], entry["better"], entry["moves"]) == ("ms", "lower", "latency_p50_ms")
    if name == OUTSIDE:
        assert (entry["layer"], entry["source"]) == ("client and HTTP", "host_clock")  # it reads the client's clock too
    else:
        assert (entry["layer"], entry["source"]) == (SPANS[name][1], "program_span")
    # the layer is one the manifest already names, letter for letter
    older = {"client and HTTP": "http_overhead_p50_ms", "staging, H2D": "staging_s", "plan build": "plan_build_ms_mean"}
    assert by_name[older[entry["layer"]]]["layer"] == entry["layer"]
    assert "workloads" not in by_name["http_overhead_p50_ms"] and "workloads" not in by_name["render_ms_mean"]
    every = [w["name"] for w in manifest["workloads"]]
    assert [m.get("workloads", every) for m in manifest["end_to_end"] if m["name"] == "latency_p50_ms"] == [every]
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))


def test_rehearsal_ends_with_the_connection_timers_counted(capsys, monkeypatch, tmp_path):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    manifest["configs"] = [{"name": "tiny", "source": "tests only", "file": "benchmark/tests/tiny_config.json",
                            "reduced": ["segments", "rows_per_segment"], "why": "a rehearsal on the CPU"}]
    for w in manifest["workloads"]:
        w["config"] = "tiny"
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    returned, seen = {}, {}
    load_module = run.load_module

    def recording(path):
        module = load_module(path)
        name = os.path.basename(path)[:-3]
        if name not in READERS:
            return module

        def read(r):
            seen["run"] = r
            returned[name] = module.read(r)
            return returned[name]

        return types.SimpleNamespace(read=read)

    monkeypatch.setattr(run, "load_module", recording)
    assert run.main(["--workload", "lineitem_suite_open", "--seed", str(2**31 + 39), "--seconds", "2", "--trace", "1"],
                    allow_cpu=True, manifest_path=str(path)) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert not set(out["metrics"]) & set(READERS)  # times: none printed without the chip
    assert sorted(returned) == sorted(READERS)
    for name, value in returned.items():
        assert isinstance(value, float) and value > 0, (name, value)
    r = seen["run"]
    # a client has its reply before the handler's boundary ends: a count can be one query off at either edge
    queries = r.delta(TOTAL + ".n")
    assert len(r.samples) > 10 and abs(queries - len(r.samples)) <= 1
    for timer in (ACCEPT, HEAD, "broker.timer.phase.httpClose", "broker.timer.httpConnection"):
        assert r.after[timer + ".ms"] > 0 and abs(r.delta(timer + ".n") - len(r.samples)) <= 1, timer
    # the three intervals follow one another inside the client's wait
    inside = sum(r.delta(k + ".ms") for k in (ACCEPT, HEAD, TOTAL)) / queries
    waits = [(s["done"] - s["sent"]) * 1000.0 for s in r.samples if s["ok"]]
    assert returned[OUTSIDE] == pytest.approx(sum(waits) / len(waits) - inside)
    assert r.delta("broker.timer.httpConnection.ms") >= r.delta(TOTAL + ".ms") + r.delta(HEAD + ".ms") - 1.0
