"""The four codecs between broker and server per query: the broker's
``phase.serializeRequest`` and ``phase.deserializeResult``, the server's
``phase.deserializeRequest`` and ``phase.serializeResult``."""

CODECS = ("broker.timer.phase.serializeRequest", "server.timer.phase.deserializeRequest",
          "server.timer.phase.serializeResult", "broker.timer.phase.deserializeResult")


def read(run):
    n = run.delta("broker.timer.queryTotal.n")
    if not n or not run.delta(CODECS[0] + ".n"):
        return None
    return sum(run.delta(c + ".ms") for c in CODECS) / n
