"""``hbm.stagedBytes`` after the window over the table's rows."""


def read(run):
    return run.after["server.gauge.hbm.stagedBytes"] / run.rows
