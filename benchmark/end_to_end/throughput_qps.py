"""Correct replies per second of window (closed loop: whole rounds)."""


def read(run):
    ok = sum(1 for s in run.samples if s["ok"])
    return ok / run.window_s if ok else None
