"""D2H copy and unpack per query: ``np.asarray`` of the packed buffer
and the slicing back into arrays, from ``phase.d2hUnpack``
(``engine/executor.py _run_kernel``)."""


def read(run):
    n = run.delta("server.timer.phase.d2hUnpack.n")
    return run.delta("server.timer.phase.d2hUnpack.ms") / n if n else None
