"""Groups a group-by's finalize kept after the per-server trim
(``max(5 x TOP, 100)`` an aggregate, and ties at the boundary), per
group-by query of the window: the server's ``groupby.groups.kept`` meter
(marked by the count) over ``phase.groupTrim``'s count.  What the server
renders, encodes and sends to the broker's reduce.  Nothing where the
program has no such counter, or the window finalized no group-by on the
device."""

METER = "server.meter.groupby.groups.kept"


def read(run):
    n = run.delta("server.timer.phase.groupTrim.n")
    return run.delta(METER) / n if n and METER in run.after else None
