"""The share of the window's group-by launches that went through the
runs lowering (a group-by over more keys than a dense holder takes: the
table's rows sorted by group id once, a run a group, the trim's
candidates made on the device): the server's ``groupby.lowering.runs``
marks over all four ``groupby.lowering.*`` marks, one mark a launch
(``engine/kernel.py groupby_lowering``).  100 says every group-by of the
window was answered that way; 0 that none was: at most 2^20 keys.
Nothing where the program has no such counters, or the window launched
no group-by."""

LOWERINGS = ("onehot", "radix", "scatter", "runs")


def read(run):
    keys = [f"server.meter.groupby.lowering.{k}" for k in LOWERINGS]
    if not any(key in run.after for key in keys):
        return None
    launches = sum(run.delta(key) for key in keys)
    return 100.0 * run.delta(keys[-1]) / launches if launches else None
