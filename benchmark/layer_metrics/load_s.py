"""Wall clock inside store write + server load, all segments."""


def read(run):
    return run.setup["load_s"]
