"""What a query waited for the server's scheduler per query: submit to a
worker's dequeue, from ``phase.schedulerWait`` (``server/scheduler.py``).
The wait for the lane is ``lane_queue_ms_mean`` and ``lane_launch_ms_mean``."""


def read(run):
    n = run.delta("server.timer.phase.schedulerWait.n")
    return run.delta("server.timer.phase.schedulerWait.ms") / n if n else None
