"""95th percentile of the same latencies as ``latency_p50_ms``."""


def read(run):
    ok = [s["latency_ms"] for s in run.samples if s["ok"]]
    return run.percentile(ok, 95) if ok else None
