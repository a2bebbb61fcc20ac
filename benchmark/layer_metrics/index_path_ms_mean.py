"""Milliseconds a query that a host index tier answered spent from the
ladder's entry to its answer, over the window: the server's
``phase.indexPath`` + ``phase.bitslicedPath`` timers (``host_index_build_s``
reads the same two up to the window: the postings built by the shapes'
first queries) over the window's ``tier.answered.postings`` +
``tier.answered.bitsliced`` marks.  The timer is the reply's first
stretch relabelled when the tier answers: the prepared decision's
look-up, the driving leaf's rows resolved from the postings, the
residual predicates over them, the host's group-by of the rows left.
Nothing where the program has no such counters, or no query of the
window was answered there."""


def read(run):
    marks = [f"server.meter.tier.answered.{t}" for t in ("postings", "bitsliced")]
    if not any(key in run.after for key in marks):
        return None
    n = sum(run.delta(key) for key in marks)
    ms = run.delta("server.timer.phase.indexPath.ms") + run.delta("server.timer.phase.bitslicedPath.ms")
    return ms / n if n else None
