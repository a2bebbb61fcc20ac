"""Answers of the window that the host oracle contradicted
(``audit.divergences``): must be 0, since every reply of the window is
also held to the benchmark's own reference.  Nothing where the program
has no auditor's counters or no pass ended in the window."""


def read(run):
    if "server.meter.audit.divergences" not in run.after or not run.delta("server.meter.audit.samples"):
        return None
    return float(run.delta("server.meter.audit.divergences"))
