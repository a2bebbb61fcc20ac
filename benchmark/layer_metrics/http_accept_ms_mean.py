"""A connection's thread made, started and woken, per query: from
``accept()`` returning the socket on the accept loop's thread to the
connection thread's first statement, the broker's ``phase.httpAccept``
(span ``httpAccept``, ``broker/broker.py _Connection``).  ``None`` where
the program has no such timer."""


def read(run):
    n = run.delta("broker.timer.phase.httpAccept.n")
    return run.delta("broker.timer.phase.httpAccept.ms") / n if n else None
