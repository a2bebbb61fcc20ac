"""Groups with a row in a group-by's fetched state, before the trim, per
group-by query of the window: the server's ``groupby.groups.live`` meter
(marked by the count) over the queries whose finalize trimmed
(``phase.groupTrim``'s count).  Every reply's own count is held to the
reference's (``numGroupsLive`` under ``count_errors``); this is the
window's mean.  Nothing where the program has no such counter, or the
window finalized no group-by on the device."""

METER = "server.meter.groupby.groups.live"


def read(run):
    n = run.delta("server.timer.phase.groupTrim.n")
    return run.delta(METER) / n if n and METER in run.after else None
