"""Broker parse + route + reduce per query, from its phase timers."""


def read(run):
    n = run.delta("broker.timer.queryTotal.n")
    if not n:
        return None
    return sum(run.delta(f"broker.timer.{k}.ms") for k in ("phase.parse", "phase.route", "reduce")) / n
