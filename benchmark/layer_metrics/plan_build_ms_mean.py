"""Plan build per query: static plan, query inputs, inputs digest and
block ids, from the server's ``phase.planBuild`` (span ``planBuild``,
``engine/executor.py``).  ``None`` where the program has no such timer."""


def read(run):
    n = run.delta("server.timer.phase.planBuild.n")
    return run.delta("server.timer.phase.planBuild.ms") / n if n else None
