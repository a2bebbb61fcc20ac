"""What a query waited for the server's scheduler and for its lane."""


def read(run):
    n = run.delta("server.timer.queryExecution.n")
    if not n:
        return None
    return (run.delta("server.timer.phase.schedulerWait.ms") + run.delta("server.timer.phase.laneWait.ms")) / n
