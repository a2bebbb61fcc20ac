"""What a group-by's finalize was handed of the group state from the
chip, per group-by query of the window, in kilobytes of 1,000 B: the
server's ``groupby.stateFetchBytes`` meter (marked by the bytes a reply,
whatever lowering made the state) over the queries whose finalize trimmed
(``phase.groupTrim``'s count).  A dense holder comes back whole (4 or 8 B
a key an aggregate, and the occupancy: megabytes at 220,000 keys); the
runs lowering's candidates are kilobytes whatever the key count is, and
this is the reader that would show a dense state coming back.  Nothing
where the program has no such counter, or the window finalized no
group-by on the device."""

METER = "server.meter.groupby.stateFetchBytes"


def read(run):
    n = run.delta("server.timer.phase.groupTrim.n")
    return run.delta(METER) / n / 1000.0 if n and METER in run.after else None
