"""The share of the window's queries that the host answered: the server's
``tier.answered.host`` marks over the marks of all four rungs of the
ladder (``tier_postings_share`` has the family), one mark a query the
ladder answered.  A forced host answer (a group-by the device declines,
``engine/plan.py group_runs_host_reason``) and a failover's both mark
``host``; every such reply also carries ``segmentsHost`` and fails the
run's ``reply_errors``.  Must read 0.  Nothing where the program has no
such counters, or no query of the window reached the ladder."""

TIERS = ("postings", "bitsliced", "host", "device")


def read(run):
    keys = {t: f"server.meter.tier.answered.{t}" for t in TIERS}
    if not any(key in run.after for key in keys.values()):
        return None
    total = sum(run.delta(key) for key in keys.values())
    return 100.0 * run.delta(keys["host"]) / total if total else None
