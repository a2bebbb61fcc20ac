"""Process start to the first timed query, less any wait for the
benchmark's own reference."""


def read(run):
    return run.setup["setup_s"]
