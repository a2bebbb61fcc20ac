"""Between the plan and the launch, per query: the kernel's handle
looked up, the batch spec, and where the launch does not carry them the
upload of the query's inputs and of a zone-tier launch's block ids,
from the server's ``phase.kernelPrep`` (span ``kernelPrep``,
``engine/executor.py``).  A leaf of ``host_unattributed_ms_mean``.
``None`` where the program has no such timer."""


def read(run):
    n = run.delta("server.timer.phase.kernelPrep.n")
    return run.delta("server.timer.phase.kernelPrep.ms") / n if n else None
