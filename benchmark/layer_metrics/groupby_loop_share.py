"""The share of the window's group-by launches whose filter mask, key and
weight columns were built inside the group-by's row loop, so that no
segment-sized intermediate reached HBM: the server's
``groupby.operands.loop`` marks over all ``groupby.lowering.*`` marks,
one mark a launch (``engine/kernel.py groupby_operands`` and
``groupby_lowering``).  100 says every group-by of the window rode the
row loop; 0 that none did: the staged one-hot contraction (a group-by of
more than 64 cells), or on the CPU the scatter.  Nothing where the
program has no such counters, or the window launched no group-by."""

LOWERINGS = ("onehot", "radix", "scatter")


def read(run):
    loop = "server.meter.groupby.operands.loop"
    keys = [f"server.meter.groupby.lowering.{k}" for k in LOWERINGS]
    if loop not in run.after and not any(key in run.after for key in keys):
        return None
    launches = sum(run.delta(key) for key in keys)
    return 100.0 * run.delta(loop) / launches if launches else None
