"""From a group-by's fetched state to its kept keys, per group-by query:
the server's ``phase.groupTrim`` timer (inside ``phase.finalize``: the
non-zero test over the occupancy, the order values, the trim to
``max(5 x TOP, 100)`` an aggregate).  Nothing where the program has no
such timer, or the window finalized no group-by on the device."""


def read(run):
    n = run.delta("server.timer.phase.groupTrim.n")
    return run.delta("server.timer.phase.groupTrim.ms") / n if n else None
