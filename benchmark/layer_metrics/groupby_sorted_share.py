"""The share of the window's group-by launches whose rows were put in
key order before the contraction (a group-by over more keys than
``RADIX_GROUP_CAP``: one sort by group id carrying the weight columns,
then the two-level contraction over a window of keys a block): the
server's ``groupby.operands.sorted`` marks over all
``groupby.lowering.*`` marks, one mark a launch (``engine/kernel.py
groupby_operands`` and ``groupby_lowering``).  100 says every group-by
of the window was summed that way; 0 that none was: at most 65,536
groups, or on the CPU the scatter.  Nothing where the program has no
such counters, or the window launched no group-by."""

LOWERINGS = ("onehot", "radix", "scatter")


def read(run):
    in_order = "server.meter.groupby.operands.sorted"
    keys = [f"server.meter.groupby.lowering.{k}" for k in LOWERINGS]
    if in_order not in run.after and not any(key in run.after for key in keys):
        return None
    launches = sum(run.delta(key) for key in keys)
    return 100.0 * run.delta(in_order) / launches if launches else None
