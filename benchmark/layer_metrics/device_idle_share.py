"""1 less the union of device operation intervals over the traced pass."""


def read(run):
    t = run.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t and t["busy_s"] else None
