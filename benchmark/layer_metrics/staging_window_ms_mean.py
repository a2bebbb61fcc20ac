"""The executor's first stretch per query once the table is staged: the
plan's shape digest, the lane's choice, the prepared-query look-up and
the residency look-up that finds the columns in HBM, from the server's
``phase.staging`` over the window (span ``staging``,
``engine/executor.py _execute_engine``; ``staging_s`` reads the same
timer up to the window, where it is the encode and the H2D copy).  A
leaf of ``host_unattributed_ms_mean``.  ``None`` where no query reached
the executor."""


def read(run):
    n = run.delta("server.timer.phase.staging.n")
    return run.delta("server.timer.phase.staging.ms") / n if n else None
