"""The share of the segments the window's queries were handed that the
value pruner left out of the work (``engine/pruner.py value_dead``): the
server's ``prune.segments.value`` marks over ``prune.segments.offered``,
as a percentage.  A query marks ``offered`` by the segments it was given
and ``value`` by those among them whose own dictionaries its filter
empties, which stay among the table's segments (``totalDocs``, the staged
table) and are scanned by no tier (``engine/executor.py _execute_tiers``).
By the traffic file, 42 of a round's 7 x 16 (query, segment) pairs of
``ssb_flat_drilldown_closed`` are dead: 37.5.  Nothing where the program
has no such meters (the parent of PR 48), or the window offered no
segment."""

OFFERED, VALUE = "server.meter.prune.segments.offered", "server.meter.prune.segments.value"


def read(run):
    if OFFERED not in run.after:
        return None
    offered = run.delta(OFFERED)
    return 100.0 * run.delta(VALUE) / offered if offered else None
