"""The launch call on the lane thread per dispatch: jit dispatch and
input H2D, from ``phase.laneDispatch`` (``engine/dispatch.py``).  A
compile inside the window would show here and in
``compiles_in_window``.  ``None`` on a program whose lane does not time
its queue apart (before PR 25 the timer was there and the span not: the
number is read only where ``phase.laneQueue`` exists too, so that the
two always split the same thing)."""


def read(run):
    if not run.delta("server.timer.phase.laneQueue.n"):
        return None
    n = run.delta("server.timer.phase.laneDispatch.n")
    return run.delta("server.timer.phase.laneDispatch.ms") / n if n else None
