"""Client and HTTP: what the client waited from send to reply, less the
broker's own ``timeUsedMs``; median."""


def read(run):
    over = [(s["done"] - s["sent"]) * 1000.0 - s["reply"]["timeUsedMs"] for s in run.samples if s["ok"]]
    return run.percentile(over, 50) if over else None
