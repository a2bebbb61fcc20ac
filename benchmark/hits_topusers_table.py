"""The table of ``clickbench_hits_topusers_1chip``: the ``hits`` segment of
``clickbench_hits_users_1chip``, from the program's own generator and
unchanged, for a program that answers the deployment's query from the
chip.

The deployment is ClickBench's line 16, ``COUNT(*)`` by ``UserID``, TOP
10: a group-by over 17.6M keys.  A program whose planner sends a group-by
over more keys than a dense holder takes to the host would load the
table and then answer the warm-up on the host's numpy path, which at
this volume is no answer: ``run.py``'s warm-up waits up to 900 s for a
reply and takes any status 200 for one, so the run would hold its machine
a quarter of an hour and end ``correct: false`` by ``segmentsHost``.  So
the precondition is held here, before the first segment is made: the
program is asked, by the name of its own function
(``engine/plan.py group_runs_host_reason``), whether a plan of the query
at the deployment's key count is forced to the host, and a program that
says so, or has no such function, fails the cell at once and with its
reason (``PERF.md`` section 7 asks a ``benchmark`` PR to hold the
warm-up's replies to ``exceptions`` and ``segmentsHost`` instead, after
which this check can go).
"""
QUERY = "SELECT COUNT(*) FROM hits GROUP BY UserID TOP 10"
KEYS = 17_630_976  # the source's distinct users: what the table's dictionary will hold, give or take 0.02%


def segment(num_rows: int, seed: int = 7, name: str = "hits0"):
    from pinot_tpu.engine import plan
    from pinot_tpu.pql import parse_pql
    from pinot_tpu.tools.datagen import synthetic_hits_users_segment

    ask = getattr(plan, "group_runs_host_reason", None)
    if ask is None:
        raise RuntimeError(f"clickbench_hits_topusers_1chip needs a group-by over {KEYS:,} keys answered from the chip, "
                           f"and this program's planner has no group_runs_host_reason: above MAX_GROUP_CAPACITY it "
                           f"sends {QUERY!r} to the host")
    reason = ask(parse_pql(QUERY), KEYS)
    if reason is not None:
        raise RuntimeError(f"clickbench_hits_topusers_1chip needs {QUERY!r} over {KEYS:,} keys answered from the chip, "
                           f"and this program's planner sends it to the host: {reason}")
    return synthetic_hits_users_segment(num_rows, seed=seed, name=name)
