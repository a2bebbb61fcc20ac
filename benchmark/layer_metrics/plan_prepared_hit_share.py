"""The share of the window's queries that found their tier verdicts, static
plan, query inputs and block ids kept from an earlier query of the same
text over the same segments, placement and settings: the server's
``plan.prepared.hit`` marks over ``hit`` + ``miss`` + ``stale``, one mark a
query that reached the tier ladder (``engine/executor.py _Prepared``).
Under 100 a query of the window derived them anew: a literal not seen
before, a segment loaded or a table staged anew.  Nothing where the
program has no such counters, or no query of the window reached the ladder."""

OUTCOMES = ("hit", "miss", "stale")


def read(run):
    keys = {o: f"server.meter.plan.prepared.{o}" for o in OUTCOMES}
    if not all(key in run.after for key in keys.values()):
        return None
    marks = {o: run.delta(key) for o, key in keys.items()}
    total = sum(marks.values())
    return 100.0 * marks["hit"] / total if total else None
