"""The least time the traced queries' bytes need at the chip's HBM
bandwidth, over their kernel time.  Bytes bound it (an aggregation needs
an add a row); the bytes are ``reference.shape_bytes``, a lower bound."""


def read(run):
    t = run.trace
    if not (t and t["busy_s"] and run.peaks):
        return None
    chips = run.cell["chips"]
    need = sum(run.shape_bytes(shape) * n for shape, n in t["queries_by_shape"].items())
    return 100.0 * need / (run.peaks["hbm_bytes_per_s"] * chips) / t["busy_s"]
