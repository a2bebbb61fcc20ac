"""What building the table-level dictionaries cost up to the window, in
seconds: the server's ``phase.globalDictBuild`` timer (inside
``phase.staging``: a key column's sorted union of the segments'
dictionaries and each segment's remap into it, once a table, by the
first query that needs the column's global ids).  Nothing where the
program has no such timer."""


def read(run):
    key = "server.timer.phase.globalDictBuild.ms"
    return run.after_setup[key] / 1000.0 if key in run.after_setup else None
