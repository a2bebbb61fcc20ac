#!/usr/bin/env python3
"""One run of one benchmark cell, a new process each time.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Generates the table from ``--seed``, stands up controller, broker and one
server in this process (which owns the chips), loads every segment
through the controller with a real CRC, warms the cell's own shapes,
rehearses the schedule's first seconds, measures for ``--seconds`` from
the client's side of the broker's HTTP port, then holds every reply of
the window to the numpy reference and prints one JSON line.

Nothing here knows a cell by name.  ``BENCHMARK.json`` names the cell's
configuration (``configs/<name>.json``) and traffic
(``traffic/<name>.json``); every metric listed there for the cell is a
reader of its own, ``end_to_end/<metric>.py`` or
``layer_metrics/<metric>.py``, with one function ``read(run)`` that
returns the number, or ``None`` where it finds nothing to read.
``--trace 0`` prints the end-to-end metrics, ``--trace 1`` the per-layer
ones, with a few seconds of device trace taken after the window.  A
traffic file's ``keep_awake`` is the number of processes that spin
beside the server while traffic is offered (``kept_awake``; 0 if absent).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse
import contextlib
import gc
import importlib
import importlib.util
import json
import os
import subprocess
import sys
import tempfile
import threading
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 4.0  # of steady traffic under the profiler, after the window
# a process that only spins, and ends when the parent whose id it is given does
SPINNER = "import os, sys\nwhile os.getppid() == int(sys.argv[1]):\n    for _ in range(1000000):\n        pass\n"


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location("bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(dotted: str):
    module, name = dotted.split(":")
    return getattr(importlib.import_module(module), name)


def cell_metrics(manifest: dict, kind: str, cell: str) -> list:
    """The metrics of ``kind`` that the manifest lists for this cell."""
    return [m for m in manifest[kind] if cell in m.get("workloads", [cell])]


def counters(cluster) -> dict:
    """Every meter, gauge and timer of the server and the broker, flat:
    ``server.meter.<n>``, ``server.gauge.<n>``, ``server.timer.<n>.ms``
    (the total) and ``.n`` (the count)."""
    out = {}
    for role, registry in (("server", cluster.servers[0].metrics), ("broker", cluster.broker.metrics)):
        snap = registry.snapshot()
        for name, m in snap["meters"].items():
            out[f"{role}.meter.{name}"] = m["count"]
        for name, v in snap["gauges"].items():
            out[f"{role}.gauge.{name}"] = v
        for name in snap["timers"]:
            t = registry.timer(name)
            out[f"{role}.timer.{name}.ms"] = t.total_ms
            out[f"{role}.timer.{name}.n"] = t.count
    return out


def device_info(chips: int, allow_cpu: bool) -> dict:
    import jax

    devices = jax.devices()
    if not allow_cpu and (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"benchmark: need {chips} tpu chip(s), JAX has {len(devices)} x {devices[0].platform!r}",
              file=sys.stderr)
        sys.exit(2)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


GENERATORS = 3  # segments generated side by side; numpy releases the interpreter lock


def load_table(cluster, config: dict, seed: int, references: list) -> dict:
    """Generate, upload and reference as a pipeline: ``GENERATORS``
    threads make the segments ahead, this thread uploads them in order,
    and one thread answers each with the references after its upload.
    ``load_s`` is the wall clock spent inside store write + server load."""
    from concurrent.futures import ThreadPoolExecutor

    make_segment = resolve(config["generator"])
    physical = cluster.add_offline_table(resolve(config["schema"])())

    def generate(i: int):
        seg = make_segment(config["rows_per_segment"], seed=seed * 1000 + i, name=f"seg{i}")
        # a real data CRC, so that the load path's verification has a
        # byte-level claim to hold the stored copy to
        seg.metadata.crc = seg.compute_crc()
        seg.metadata.custom["dataCrc"] = True
        return seg

    def refer(seg) -> None:
        for ref in references:
            ref.add(seg)

    n, load_s = config["segments"], 0.0
    with ThreadPoolExecutor(GENERATORS) as makers, ThreadPoolExecutor(1) as referee:
        made = [makers.submit(generate, i) for i in range(min(GENERATORS, n))]
        answered = []
        for i in range(n):
            seg = made[i].result()
            if i + GENERATORS < n:
                made.append(makers.submit(generate, i + GENERATORS))
            t0 = time.perf_counter()
            cluster.upload(physical, seg)
            load_s += time.perf_counter() - t0
            answered.append(referee.submit(refer, seg))
            made[i] = None
            if i >= 2:
                answered[i - 2].result()  # at most two segments wait for the reference
        t0 = time.perf_counter()
        for f in answered:
            f.result()
        reference_wait_s = time.perf_counter() - t0
    loaded = cluster.servers[0].data_manager.table(physical)
    if loaded is None or len(loaded.segment_names()) != n:
        raise RuntimeError(f"server loaded {loaded and len(loaded.segment_names())} of {n} segments")
    return {"load_s": load_s, "reference_wait_s": reference_wait_s}


def judge(samples: list, shapes: dict, reference, compare, limits: dict) -> dict:
    """Hold every reply to the reference.  Returns the numbers compared
    (the worst over the replies) and marks each sample ``ok``."""
    worst = {"sum_gap": 0.0, "count_errors": 0, "key_errors": 0, "reply_errors": 0, "sum_gap_by_shape": {},
             "first_faults": []}
    for s in samples:
        if s["reply"] is None:
            s["ok"] = False
            worst["reply_errors"] += 1
            worst["first_faults"].append(f"{s['shape']}: no reply ({s['error']})")
            continue
        got = compare(s["reply"], shapes[s["shape"]], reference.answers[s["shape"]], reference.rows)
        s["ok"] = all(got[k] <= limits[k] for k in limits)
        if not s["ok"]:
            r = s["reply"]
            worst["first_faults"].append(f"{s['shape']}: {got} exceptions={r.get('exceptions')} "
                                         f"partial={r.get('partialResponse')} cost={r.get('cost')}")
        worst["sum_gap"] = max(worst["sum_gap"], got["sum_gap"])
        by_shape = worst["sum_gap_by_shape"]
        by_shape[s["shape"]] = max(by_shape.get(s["shape"], 0.0), got["sum_gap"])
        for k in ("count_errors", "key_errors", "reply_errors"):
            worst[k] += got[k]
    return worst


def by_shape(samples: list, percentile) -> dict:
    """What a median of the whole window hides: each shape's own median,
    how many replies it had, and how many took over three times that
    median (the host's stalls)."""
    out = {}
    for shape in sorted({s["shape"] for s in samples}):
        ms = [s["latency_ms"] for s in samples if s["shape"] == shape]
        p50 = percentile(ms, 50)
        out[shape] = {"n": len(ms), "p50_ms": round(p50, 3), "over_3x_p50": sum(1 for x in ms if x > 3 * p50)}
    return out


class CompileLog:
    """When JAX compiled a program or loaded one from its cache: the
    benchmark's own count, beside the program's ``compile.*`` meters."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.times: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


@contextlib.contextmanager
def serving(config: dict, seed: int, references: list, pql_of: dict, post):
    """The deployment, loaded and warm on the shapes of ``pql_of``: yields
    the cluster, the broker's address and the set-up's wall clocks."""
    from pinot_tpu.tools.cluster_harness import InProcessCluster

    with tempfile.TemporaryDirectory(prefix="benchmark_") as data_dir:
        cluster = InProcessCluster(num_servers=1, data_dir=data_dir, http=True, timeout_ms=900_000.0)
        try:
            address = (cluster.http.host, cluster.http.port)
            setup = load_table(cluster, config, seed, references)
            if counters(cluster)["server.meter.crcFailures"]:
                raise RuntimeError("crcFailures during load")
            # warm-up: the first call of each shape stages its columns,
            # builds its host indexes and compiles or loads its program
            for name, pql in pql_of.items():
                t0 = time.perf_counter()
                body = post(*address, pql)
                if not isinstance(body, bytes):
                    raise RuntimeError(f"warm-up of {name}: {body!r}")
                setup[f"first_call_s.{name}"] = time.perf_counter() - t0
            yield types.SimpleNamespace(cluster=cluster, address=address, setup=setup)
        finally:
            cluster.stop()
            for server in cluster.servers:
                server.shutdown()


@contextlib.contextmanager
def kept_awake(n: int):
    """``n`` processes that spin beside the server while traffic is offered
    (a traffic file's ``keep_awake``).  One closed-loop client that waits
    27 ms a query for the device leaves the host's other cores idle, and
    the machine then serves every crossing into the system and the runtime
    slower, in some processes and not in others (``PERF.md`` section 2: two
    levels 9% apart).  A deployment's host is not idle beside its server;
    one busy core stands for that.  They touch neither JAX nor the chip."""
    spinners = [subprocess.Popen([sys.executable, "-I", "-S", "-c", SPINNER, str(os.getpid())], stdin=subprocess.DEVNULL,
                                 stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL) for _ in range(n)]
    try:
        yield
    finally:
        for p in spinners:
            p.kill()
        for p in spinners:
            p.wait()


def take_trace(run_pass, trace_reduce) -> tuple:
    """Run ``run_pass(span)`` under the profiler; the pass and its reduced
    trace, clipped to the client's first send and last reply."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the interpreter's own tracer slows the host it measures
    options.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="benchmark_trace_") as trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            traced = run_pass(lambda shape: jax.profiler.TraceAnnotation("client:" + shape))
        finally:
            jax.profiler.stop_trace()
        loaded = trace_reduce.load(trace_reduce.newest_xplane(trace_dir))
    spans = loaded["client"]
    window = (min(s[1] for s in spans), max(s[1] + s[2] for s in spans))
    return traced, trace_reduce.reduce(loaded, window)


def main(argv=None, allow_cpu: bool = False, manifest_path: str = "") -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", default="", help="also answer the shapes with the reference in this lower "
                   "precision and print the gap it would show (bfloat16); not part of a benchmark run")
    args = p.parse_args(argv)

    manifest = load_json(manifest_path or os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in manifest["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"benchmark: no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    config_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, config_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    peaks_table = load_json(HERE, "peaks.json")
    reference_module = load_module(os.path.join(HERE, traffic.get("reference", "reference") + ".py"))
    loadgen = load_module(os.path.join(HERE, "loadgen.py"))

    # the deployment's settings reach the program the way an operator sets
    # them, before the program is imported
    os.environ.update(config.get("env", {}))
    sys.path.insert(0, ROOT)
    info = device_info(cell["chips"], allow_cpu)
    if info["kind"] not in peaks_table and not allow_cpu:
        print(f"benchmark: no peaks for device kind {info['kind']!r} in peaks.json", file=sys.stderr)
        return 2
    import jax

    compiles = CompileLog()
    shapes = {s["name"]: s for s in traffic["shapes"]}
    pql_of = {name: reference_module.render_pql(config["table"], shape) for name, shape in shapes.items()}
    limits = {"sum_gap": config["guarantees"]["sum_rtol"], "count_errors": 0, "key_errors": 0, "reply_errors": 0}
    reference = reference_module.Reference(shapes)
    control = reference_module.Reference(shapes, control=args.control) if args.control else None

    with serving(config, args.seed, [r for r in (reference, control) if r], pql_of, loadgen.post) as s:
        setup = s.setup
        offer = lambda seconds, span=contextlib.nullcontext: loadgen.run_traffic(
            s.address, pql_of, traffic, seconds, span)
        # rehearsal: the schedule's first seconds untimed, so that
        # connections, thread pools, lanes and batched programs are hot
        with kept_awake(traffic.get("keep_awake", 0)):
            rehearsal = offer(traffic["rehearse_s"])
            after_setup = counters(s.cluster)
            gc.collect()
            gc.freeze()
            before = counters(s.cluster)
            t_window = time.perf_counter()
            setup["setup_s"] = t_window - T_START - setup["reference_wait_s"]
            window = offer(args.seconds)
            compiled = compiles.between(t_window, time.perf_counter())
            after = counters(s.cluster)
            traced = trace = None
            if args.trace:
                traced, trace = take_trace(
                    lambda span: offer(min(TRACE_SECONDS, args.seconds), span),
                    load_module(os.path.join(HERE, "trace_reduce.py")))
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices())

    # timing has stopped: every reply of the rehearsal, the window and the
    # traced pass is held to the reference, each number beside its limit
    every = rehearsal["samples"] + window["samples"] + (traced["samples"] if traced else [])
    compared = judge(every, shapes, reference, reference_module.compare, limits)
    failed = sum(1 for s in window["samples"] if not s["ok"])
    correct = all(compared[k] <= limits[k] for k in limits)
    print("# set-up: " + json.dumps({k: round(v, 3) for k, v in setup.items()}))
    print(f"# sum_gap by shape: {json.dumps(compared['sum_gap_by_shape'])}")
    for fault in compared["first_faults"][:5]:
        print(f"# fault: {fault}"[:600])
    print("# window by shape: " + json.dumps(by_shape(window["samples"], loadgen.percentile)))
    slowest = sorted(window["samples"], key=lambda x: -x["latency_ms"])[:5]
    print("# slowest: " + json.dumps([[x["shape"], round(x["due"], 3), round(x["latency_ms"], 1),
                                       round(x["late_ms"], 1)] for x in slowest]))
    if control is not None:
        gaps = reference_module.control_gaps(reference, control)
        print(f"# control {args.control} sum_gap by shape: {json.dumps(gaps)} limit {limits['sum_gap']!r}")

    run = types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic, samples=window["samples"], window_s=window["window_s"],
        compiles=compiled, setup=setup, rows=reference.rows, after_setup=after_setup, before=before,
        after=after, delta=lambda key: after.get(key, 0) - before.get(key, 0),  # a counter's change over the window
        trace=trace, peaks=peaks_table.get(info["kind"]), percentile=loadgen.percentile,
        shape_bytes=reference.shape_bytes,
    )
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(manifest, kind, cell["name"]):
        value = load_module(os.path.join(HERE, "layer_metrics" if args.trace else "end_to_end",
                                         m["name"] + ".py")).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if info["platform"] != "tpu":
        # a rehearsal without the chip: counts only, no time under a metric's name
        metrics = {k: v for k, v in metrics.items() if v["unit"] in ("count", "B/row")}
    device = dict(info, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": len(window["samples"]), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        print("# idle with a query in flight, by span: " + json.dumps(trace["idle_by_span"]))
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    # each number compared beside its limit: the result line's last key, and the last lines of standard error
    result["compared"] = {k: {"value": compared[k], "limit": limits[k]} for k in limits}
    print(json.dumps(result), flush=True)
    for k, v in result["compared"].items():
        print(f"compared {k}: {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
