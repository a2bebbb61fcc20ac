"""``planstats.record``, ``tail.observe``, ``slo.observe`` and
``querylog.observe`` per query, which run before the reply is written,
from the broker's ``phase.bookkeeping``.  Counted as "HTTP" by
``http_overhead_p50_ms`` too."""


def read(run):
    n = run.delta("broker.timer.phase.bookkeeping.n")
    return run.delta("broker.timer.phase.bookkeeping.ms") / n if n else None
