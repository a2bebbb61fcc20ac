"""Wall time of one whole pass of the host oracle, the mean over the
passes that ended in the window: ``audit.shadowMs`` (``utils/audit.py``;
since PR 29 all the steps of a block-streamed pass and the worker's
sleeps between them).  Nothing where no pass ended in the window."""


def read(run):
    n = run.delta("server.timer.audit.shadowMs.n")
    return run.delta("server.timer.audit.shadowMs.ms") / n if n else None
