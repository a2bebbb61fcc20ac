"""Lane submit to dequeue per dispatch: queue and batch-formation wait
only, from ``phase.laneQueue`` (``engine/dispatch.py``).  The launch
call is ``lane_launch_ms_mean``; the scheduler's own queue is
``scheduler_wait_ms_mean``."""


def read(run):
    n = run.delta("server.timer.phase.laneQueue.n")
    return run.delta("server.timer.phase.laneQueue.ms") / n if n else None
