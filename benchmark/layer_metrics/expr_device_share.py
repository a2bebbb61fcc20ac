"""The share of the window's queries with arithmetic inside an aggregate
(``sum(a*(1-b))``) that a device program answered, the expression
evaluated in the kernel's row loop: the server's ``agg.expr.device``
marks over ``device`` + ``host``, one mark a query whose plan holds an
expression (``engine/executor.py _execute_engine``).  Under 100 a host
tier answered some (the postings tier, the forced host path, the
failover), in float64 and at the host's speed.  Nothing where the
program has no such counters, or no query of the window held an
expression."""

PLACES = ("device", "host")


def read(run):
    keys = {p: f"server.meter.agg.expr.{p}" for p in PLACES}
    if not all(key in run.after for key in keys.values()):
        return None
    marks = {p: run.delta(key) for p, key in keys.items()}
    total = sum(marks.values())
    return 100.0 * marks["device"] / total if total else None
