"""The share of the window's queries that the postings tier answered
(host postings in O(matches), ``engine/invindex_path.py``): the server's
``tier.answered.postings`` marks over the marks of all four rungs of the
ladder (``engine/ladder.py TIERS``: ``postings``, ``bitsliced``, ``host``,
``device``), one mark a query the ladder answered
(``engine/executor.py _finish_tier``).  Which rung answers a shape is the
ladder's decision from the query and the table; this is what it decided
over the window.  Nothing where the program has no such counters, or no
query of the window reached the ladder."""

TIERS = ("postings", "bitsliced", "host", "device")


def read(run):
    keys = {t: f"server.meter.tier.answered.{t}" for t in TIERS}
    if not any(key in run.after for key in keys.values()):
        return None
    total = sum(run.delta(key) for key in keys.values())
    return 100.0 * run.delta(keys["postings"]) / total if total else None
