"""The share of the window's launches with a ``distinctcounthll`` whose
registers came from the sort lowering (one packed int32 key a row,
sorted in the reduce, which reads each (group, register) cell's largest
key): the server's ``hll.lowering.sort`` marks over all
``hll.lowering.*`` marks, one mark a launch that carries such an
aggregate, the answer of the function the kernel builder and the reduce
spec ask (``engine/kernel.py hll_lowering``).  66.7 in
``hits_distinct_users_closed``: two shapes of three group by 9,040
regions, the third is ungrouped and rides the contraction.  Nothing
where the program has no such counters, or the window launched no such
aggregate."""

LOWERINGS = ("matmul", "sort", "scatter", "pairs")


def read(run):
    keys = {k: f"server.meter.hll.lowering.{k}" for k in LOWERINGS}
    if not any(key in run.after for key in keys.values()):
        return None
    launches = sum(run.delta(key) for key in keys.values())
    return 100.0 * run.delta(keys["sort"]) / launches if launches else None
