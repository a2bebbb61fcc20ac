"""D2H unpack and finalize per query, from ``phase.finalize``."""


def read(run):
    n = run.delta("server.timer.phase.finalize.n")
    return run.delta("server.timer.phase.finalize.ms") / n if n else None
