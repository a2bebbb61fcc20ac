"""Median latency of the window's correct replies at the client; in an
open loop from when the query was due."""


def read(run):
    ok = [s["latency_ms"] for s in run.samples if s["ok"]]
    return run.percentile(ok, 50) if ok else None
