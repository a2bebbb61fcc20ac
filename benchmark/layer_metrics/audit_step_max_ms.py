"""The longest the auditor's thread can have kept the interpreter in
one step of the oracle's pass (one block of rows): the gauge
``audit.stepMaxMs``, the largest of the last 4,096 steps that
``audit.stepMs`` retains, read at the window's end.  The program times
a step on the thread's own processor clock, so a stall of the whole
machine is no part of it; on the chip's host that clock ticks in 10 ms.
Nothing where the program does not stream its oracle (before PR 29: one
numpy call a segment, as long as it takes) or no step fell in the
window."""


def read(run):
    if not run.delta("server.timer.audit.stepMs.n"):
        return None
    return run.after.get("server.gauge.audit.stepMaxMs")
