"""Host index builds inside the shapes' first queries: bit-sliced plane
encode and postings."""


def read(run):
    c = run.after_setup
    return (c.get("server.timer.phase.bitslicedPath.ms", 0.0) + c.get("server.timer.phase.indexPath.ms", 0.0)) / 1000.0
