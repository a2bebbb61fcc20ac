"""The share of the answers the shadow auditor should have re-derived
that it did: passes of the host oracle finished in the window
(``audit.samples``) over every N-th eligible answer of the window
(``audit.offered``, counted before the sampler's budget and its queue).
Under 100 the auditor dropped answers or fell behind.  On a program with
the auditor but without ``audit.offered`` (before PR 29) the offers are
the window's queries over the sample rate.  Nothing where the program
has no auditor's counters, or the window offered none."""
import os


def read(run):
    if "server.meter.audit.samples" not in run.after:
        return None
    if "server.meter.audit.offered" in run.after:
        offered = run.delta("server.meter.audit.offered")
    else:
        every = int(os.environ.get("PINOT_TPU_AUDIT_SAMPLE_N") or 64)
        offered = len(run.samples) // every if every > 0 else 0
    if not offered:
        return None
    return 100.0 * run.delta("server.meter.audit.samples") / offered
