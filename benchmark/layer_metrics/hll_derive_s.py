"""What deriving the per-row HLL streams cost up to the window, in
seconds: the server's ``phase.hllDerive`` timer (inside
``phase.staging``: every segment's dictionary of the counted column
hashed once, and the (register, rank) of each row fanned out through
the forward index, before the upload).  Nothing where the program has
no such timer."""


def read(run):
    key = "server.timer.phase.hllDerive.ms"
    return run.after_setup[key] / 1000.0 if key in run.after_setup else None
