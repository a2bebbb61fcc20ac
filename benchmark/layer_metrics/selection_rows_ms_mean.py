"""From a selection's fetched candidates to its rows, per selection
query: the server's ``phase.selectionRows`` timer (inside
``phase.finalize``: each valid candidate's row gathered from its segment
and decoded, the sort values and the selected columns).  Nothing where
the program has no such timer, or the window finalized no selection on
the device."""


def read(run):
    n = run.delta("server.timer.phase.selectionRows.n")
    return run.delta("server.timer.phase.selectionRows.ms") / n if n else None
