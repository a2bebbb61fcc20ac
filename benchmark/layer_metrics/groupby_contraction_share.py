"""The share of the window's group-by launches whose occupancy and sums
rode a one-hot contraction on the matrix unit, one level (``onehot``) or
two (``radix``), and not the serialised scatter: the server's
``groupby.lowering.*`` marks, one a launch, from the function the kernel
builder asks (``engine/kernel.py groupby_lowering``).  100 where no
group-by of the window fell to the scatter.  Nothing where the program
has no such counters, or the window launched no group-by."""

LOWERINGS = ("onehot", "radix", "scatter")


def read(run):
    keys = {k: f"server.meter.groupby.lowering.{k}" for k in LOWERINGS}
    if not any(key in run.after for key in keys.values()):
        return None
    marks = {k: run.delta(key) for k, key in keys.items()}
    total = sum(marks.values())
    if not total:
        return None
    return 100.0 * (marks["onehot"] + marks["radix"]) / total
