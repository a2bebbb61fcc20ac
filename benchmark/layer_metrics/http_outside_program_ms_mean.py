"""What no span of the program can cover of a client's wait: the mean
over the window's sound replies of send to reply, less the three
intervals that follow one another inside the program from ``accept()``
returning to the reply's last byte written (``phase.httpAccept``,
``phase.httpHead``, ``httpTotal``), a query over the window.  What is
left is the client library's connect, send and read, the kernel's
loopback, the wait in the listen queue, and the load generator's share
of the interpreter, which runs in the server's process.
``phase.httpClose`` is left out on purpose: it begins when the reply is
out.  ``None`` where the program has no ``phase.httpAccept``."""

INSIDE = ("phase.httpAccept", "phase.httpHead", "httpTotal")


def read(run):
    n = run.delta("broker.timer.httpTotal.n")
    waits = [(s["done"] - s["sent"]) * 1000.0 for s in run.samples if s["ok"]]
    if not n or not waits or not run.delta("broker.timer.phase.httpAccept.n"):
        return None
    return sum(waits) / len(waits) - sum(run.delta(f"broker.timer.{k}.ms") for k in INSIDE) / n
