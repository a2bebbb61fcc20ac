"""Registers to estimates, per group-by query with a
``distinctcounthll``: the server's ``phase.hllEstimate`` timer (inside
``phase.finalize`` and its ``phase.groupTrim``: every live group's 256
registers to clearspring's estimate, which the per-server trim orders
by and the state's digest sums).  Nothing where the program has no such
timer, or the window estimated nothing."""


def read(run):
    n = run.delta("server.timer.phase.hllEstimate.n")
    return run.delta("server.timer.phase.hllEstimate.ms") / n if n else None
