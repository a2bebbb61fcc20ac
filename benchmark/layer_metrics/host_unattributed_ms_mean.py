"""What no leaf span covers of a query's time inside the broker's HTTP
handler: ``httpTotal`` (handler entry to last byte written) less the
leaf timers on the one-server critical path, per query.  The leaves:

broker  ``phase.httpRead``, ``phase.parse``, ``phase.route``,
        ``phase.attemptSubmit``, ``phase.poolQueue``, ``phase.serializeRequest``,
        ``phase.deserializeResult``, ``phase.gatherWake``, ``reduce``,
        ``phase.bookkeeping``, ``phase.render``
server  ``phase.deserializeRequest``, ``phase.schedulerWait``,
        ``phase.serverParse``, ``phase.segmentAcquire``, ``phase.prune``,
        ``phase.staging``, ``phase.planBuild``, ``phase.kernelPrep``,
        ``phase.laneQueue``, ``phase.laneDispatch``, ``phase.laneDeliver``,
        ``phase.laneWake``, ``phase.deviceWait``, ``phase.d2hUnpack``,
        ``phase.finalize``, ``phase.workerWake``,
        ``phase.serverBookkeeping``, ``phase.serializeResult``

Five of them are a thread handing work to the next (``poolQueue``,
``schedulerWait``, ``laneQueue``) or back (``laneWake``, ``workerWake``,
``gatherWake``).  What is left is the self time of the containers
(``query``, ``scatterGather``, ``serverAttempt``, ``serverQuery``,
``planAndExecute``, ``laneWait``, ``planExec``): the few statements
between two boundaries.  A query that a host tier
answers spends its time in ``phase.indexPath``, ``phase.bitslicedPath``
or ``phase.hostPath`` instead of the device leaves; they are counted."""

BROKER = ("phase.httpRead", "phase.parse", "phase.route", "phase.attemptSubmit", "phase.poolQueue",
          "phase.serializeRequest",
          "phase.deserializeResult", "phase.gatherWake", "reduce", "phase.bookkeeping", "phase.render")
SERVER = ("phase.deserializeRequest", "phase.schedulerWait", "phase.serverParse", "phase.segmentAcquire",
          "phase.prune", "phase.staging", "phase.planBuild", "phase.kernelPrep", "phase.laneQueue",
          "phase.laneDispatch", "phase.laneDeliver", "phase.laneWake", "phase.deviceWait",
          "phase.d2hUnpack", "phase.finalize", "phase.workerWake", "phase.serverBookkeeping",
          "phase.serializeResult",
          "phase.indexPath", "phase.bitslicedPath", "phase.hostPath", "phase.hostFailover")


def read(run):
    n = run.delta("broker.timer.httpTotal.n")
    if not n:
        return None
    leaves = sum(run.delta(f"broker.timer.{k}.ms") for k in BROKER)
    leaves += sum(run.delta(f"server.timer.{k}.ms") for k in SERVER)
    return (run.delta("broker.timer.httpTotal.ms") - leaves) / n
