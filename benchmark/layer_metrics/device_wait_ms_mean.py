"""What a query waited for the device per wait: ``block_until_ready`` on
the launch's output, from ``phase.deviceWait`` (``engine/executor.py
_run_kernel``).  The kernel starts while the launch call is still
returning, so this reads a little under ``kernel_ms_per_query``."""


def read(run):
    n = run.delta("server.timer.phase.deviceWait.n")
    return run.delta("server.timer.phase.deviceWait.ms") / n if n else None
