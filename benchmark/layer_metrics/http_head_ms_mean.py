"""What a connection's thread does before the query handler's entry, per
query: the handler object, the request line read from the socket, the
header parse and the route, the broker's ``phase.httpHead`` (span
``httpHead``, ``broker/broker.py _Connection``).  Ends where
``httpTotal`` begins.  ``None`` where the program has no such timer."""


def read(run):
    n = run.delta("broker.timer.phase.httpHead.n")
    return run.delta("broker.timer.phase.httpHead.ms") / n if n else None
