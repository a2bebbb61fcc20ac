"""What a connection costs after the reply's last byte is with the
socket, per query: the flush, ``shutdown_request`` and the close, the
broker's ``phase.httpClose`` (span ``httpClose``, ``broker/broker.py
_Connection``).  The client may have its reply by then, so this is no
part of its wait; in a closed loop it runs beside the next query's
connect.  ``None`` where the program has no such timer."""


def read(run):
    n = run.delta("broker.timer.phase.httpClose.n")
    return run.delta("broker.timer.phase.httpClose.ms") / n if n else None
