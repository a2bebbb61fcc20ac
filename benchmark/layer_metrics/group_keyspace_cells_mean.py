"""Cells the plan sized its group space at, per device group-by of the
window: the server's ``groupby.keySpaceCells`` meter (marked by
``plan.group_by.capacity``, the product of the group columns' table
cardinalities whatever the filter leaves, in ``executor._finalize``) over
the queries whose finalize trimmed (``phase.groupTrim``'s count), beside
``groups_live_mean``: a key space of 1,750,000 cells for 800 live groups
is what chose the lowering.  Nothing where the program has no such
counter, or the window finalized no group-by on the device."""

METER = "server.meter.groupby.keySpaceCells"


def read(run):
    n = run.delta("server.timer.phase.groupTrim.n")
    return run.delta(METER) / n if n and METER in run.after else None
