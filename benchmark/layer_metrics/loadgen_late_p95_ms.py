"""How late a query left against its due time; open loops only.  A
starved generator is not a fast server."""


def read(run):
    if run.traffic["loop"] != "open":
        return None
    return run.percentile([s["late_ms"] for s in run.samples], 95)
