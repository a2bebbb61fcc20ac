"""The valid candidate rows the device handed the host, per selection
query of the window: the server's ``selection.candidates`` meter (marked
by the count, in ``_finalize``) over the queries whose finalize gathered
rows (``phase.selectionRows``'s count).  Segments x k as the program
stands (120 in ``hits_search_selection_closed``); what pruning segments
by a sort column's min and max would lower.  Nothing where the program
has no such counter, or the window finalized no selection on the
device."""

METER = "server.meter.selection.candidates"


def read(run):
    n = run.delta("server.timer.phase.selectionRows.n")
    return run.delta(METER) / n if n and METER in run.after else None
