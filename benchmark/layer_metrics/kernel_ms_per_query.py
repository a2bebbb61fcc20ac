"""Device time of the jitted programs per query of the traced pass."""


def read(run):
    t = run.trace
    return t["busy_s"] * 1000.0 / t["queries"] if t and t["queries"] and t["busy_s"] else None
