"""``resp.to_json()`` + ``json.dumps`` + writing the reply, per query,
from the broker's ``phase.render`` (``BrokerHttpServer``).  Counted as
"HTTP" by ``http_overhead_p50_ms``, which is taken from outside."""


def read(run):
    n = run.delta("broker.timer.phase.render.n")
    return run.delta("broker.timer.phase.render.ms") / n if n else None
