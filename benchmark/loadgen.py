"""The one load generator: a traffic file in, timed replies out.

A traffic file (``traffic/<name>.json``) states the loop (``open`` with a
``rate_qps``, or ``closed`` with ``clients``), its ``schedule_seed`` and
its shapes with their ``share``.  The schedule is built from that seed
alone, never from ``--seed``: every run of a cell offers the same
queries in the same order, and in an open loop at the same instants.

Open loop: blocks that hold each shape exactly ``share`` times, each
shuffled by the schedule's generator, with Poisson due times at the
stated rate.  A window of ``seconds`` is the whole blocks that are due
inside it, so the mix behind a percentile is exact.  A query is timed
from when it was due; how late it left is kept beside it.

Closed loop: each client sends the shapes in file order, round after
round, the next query when the last one is answered.  The window is the
whole rounds that fit into ``seconds``.

The broker's HTTP server speaks HTTP/1.0 and closes after each reply, so
a query is one connection; ``PERF.md`` lists that for the program.
"""
from __future__ import annotations

import contextlib
import http.client
import json
import random
import threading
import time

OPEN_WORKERS = 32  # each owns every 32nd query, so none waits on another's reply
REPLY_TIMEOUT_S = 900.0  # above the broker's own, so that a first call's compile is the program's to time out


def percentile(values, p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of nothing")
    k = (len(s) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def open_schedule(traffic: dict, seconds: float) -> list:
    """``[(shape name, due seconds)]`` for the whole blocks due inside
    ``seconds``; a prefix of the same list for any shorter ``seconds``."""
    rng = random.Random(traffic["schedule_seed"])
    block = [s["name"] for s in traffic["shapes"] for _ in range(s["share"])]
    out, due = [], 0.0
    while True:
        names = block[:]
        rng.shuffle(names)
        dues = []
        for _ in names:
            due += rng.expovariate(traffic["rate_qps"])
            dues.append(due)
        if dues[-1] > seconds:
            return out
        out.extend(zip(names, dues))


def post(host: str, port: int, pql: str):
    """One query over one connection: the reply's bytes, or the error."""
    conn = http.client.HTTPConnection(host, port, timeout=REPLY_TIMEOUT_S)
    try:
        conn.request("POST", "/query", json.dumps({"pql": pql}), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        return body if resp.status == 200 else RuntimeError(f"HTTP {resp.status}")
    except (OSError, http.client.HTTPException) as e:
        return e
    finally:
        conn.close()


def _sample(shape, due, sent, done, body) -> dict:
    reply = None
    if isinstance(body, bytes):
        try:
            reply = json.loads(body)
        except ValueError:
            pass
    return {"shape": shape, "due": due, "sent": sent, "done": done, "reply": reply,
            "error": None if reply is not None else repr(body)[:200],
            "latency_ms": (done - due) * 1000.0, "late_ms": (sent - due) * 1000.0}


def run_open(address, pql_of: dict, schedule: list, span=contextlib.nullcontext) -> dict:
    """Offer ``schedule`` and wait for every reply.  ``span(name)`` is a
    context manager put around each query (the traced run's annotation)."""
    raw = [None] * len(schedule)
    t0 = time.perf_counter() + 0.05

    def worker(w: int) -> None:
        for i in range(w, len(schedule), OPEN_WORKERS):
            shape, due = schedule[i]
            wait = t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent = time.perf_counter()
            with span(shape):
                body = post(*address, pql_of[shape])
            raw[i] = (shape, due, sent - t0, time.perf_counter() - t0, body)

    threads = [threading.Thread(target=worker, args=(w,), daemon=True) for w in range(OPEN_WORKERS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    samples = [_sample(*r) for r in raw]
    return {"samples": samples, "window_s": max(s["done"] for s in samples) if samples else 0.0}


def run_closed(address, pql_of: dict, traffic: dict, seconds: float, span=contextlib.nullcontext) -> dict:
    """Each client runs whole rounds of the shapes in file order while
    another round is expected to end inside ``seconds`` (one at least)."""
    order = [s["name"] for s in traffic["shapes"] for _ in range(s["share"])]
    raw: list = []
    lock = threading.Lock()
    t0 = time.perf_counter()

    def client() -> None:
        rounds = 0
        while True:
            mine = []
            for shape in order:
                sent = time.perf_counter()
                with span(shape):
                    body = post(*address, pql_of[shape])
                mine.append((shape, sent - t0, sent - t0, time.perf_counter() - t0, body))
            with lock:
                raw.extend(mine)
            rounds += 1
            elapsed = time.perf_counter() - t0
            if elapsed + elapsed / rounds > seconds:
                return

    threads = [threading.Thread(target=client, daemon=True) for _ in range(traffic["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    samples = [_sample(*r) for r in raw]
    return {"samples": samples, "window_s": max(s["done"] for s in samples)}


def run_traffic(address, pql_of: dict, traffic: dict, seconds: float, span=contextlib.nullcontext) -> dict:
    if traffic["loop"] == "open":
        return run_open(address, pql_of, open_schedule(traffic, seconds), span)
    if traffic["loop"] == "closed":
        return run_closed(address, pql_of, traffic, seconds, span)
    raise ValueError(f"traffic loop {traffic['loop']!r}: open or closed")
