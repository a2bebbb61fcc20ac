"""Programs that JAX compiled, or loaded from its cache, inside the
window (its own compile event), or the server's count of first launches
(``compile.cold`` + ``compile.persistentHit``) where that is larger.
Has to read 0.  (``compile.warm`` counts launches of a program that was
already compiled, so it is no part of this.)"""


def read(run):
    first = sum(run.delta(f"server.meter.compile.{k}") for k in ("cold", "persistentHit"))
    return max(run.compiles, first)
