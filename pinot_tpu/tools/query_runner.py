"""Query perf runner: singleThread / multiThreads / targetQPS modes.

The ``pinot-perf`` harness analog (``QueryRunner.java:42``, modes
:45-53): replays a list of PQL queries against a query function or a
broker URL, reporting throughput and latency percentiles (:115-117).
"""
from __future__ import annotations

import concurrent.futures
import json
import threading
import time
import urllib.request
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence


@dataclass
class RunnerReport:
    mode: str
    num_queries: int
    wall_s: float
    qps: float
    latencies_ms: List[float] = field(default_factory=list)

    def percentile(self, p: float) -> float:
        if not self.latencies_ms:
            return 0.0
        s = sorted(self.latencies_ms)
        return s[min(int(len(s) * p / 100.0), len(s) - 1)]

    def to_json(self) -> dict:
        return {
            "mode": self.mode,
            "numQueries": self.num_queries,
            "wallSeconds": round(self.wall_s, 3),
            "qps": round(self.qps, 1),
            "avgMs": round(sum(self.latencies_ms) / max(len(self.latencies_ms), 1), 3),
            "p50Ms": round(self.percentile(50), 3),
            "p90Ms": round(self.percentile(90), 3),
            "p95Ms": round(self.percentile(95), 3),
            "p99Ms": round(self.percentile(99), 3),
        }


def http_query_fn(broker_url: str, timeout_s: float = 60.0) -> Callable[[str], None]:
    endpoint = broker_url.rstrip("/") + "/query"

    def run(pql: str) -> None:
        body = json.dumps({"pql": pql}).encode()
        req = urllib.request.Request(endpoint, data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            r.read()

    return run


class QueryRunner:
    def __init__(self, query_fn: Callable[[str], None]) -> None:
        self.query_fn = query_fn

    def _timed(self, pql: str) -> float:
        t0 = time.perf_counter()
        self.query_fn(pql)
        return (time.perf_counter() - t0) * 1000.0

    def single_thread(self, queries: Sequence[str], rounds: int = 1) -> RunnerReport:
        lat: List[float] = []
        t0 = time.perf_counter()
        for _ in range(rounds):
            for q in queries:
                lat.append(self._timed(q))
        wall = time.perf_counter() - t0
        return RunnerReport("singleThread", len(lat), wall, len(lat) / wall, lat)

    def multi_threads(self, queries: Sequence[str], num_threads: int = 4, rounds: int = 1) -> RunnerReport:
        work = [q for _ in range(rounds) for q in queries]
        lat: List[float] = []
        lock = threading.Lock()

        def one(q: str) -> None:
            ms = self._timed(q)
            with lock:
                lat.append(ms)

        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(max_workers=num_threads) as pool:
            list(pool.map(one, work))
        wall = time.perf_counter() - t0
        return RunnerReport("multiThreads", len(lat), wall, len(lat) / wall, lat)

    def target_qps(self, queries: Sequence[str], qps: float, duration_s: float = 10.0) -> RunnerReport:
        interval = 1.0 / qps
        lat: List[float] = []
        lock = threading.Lock()
        start = time.perf_counter()
        stop = start + duration_s
        futures = []
        i = 0
        with concurrent.futures.ThreadPoolExecutor(max_workers=32) as pool:
            next_t = time.perf_counter()
            while time.perf_counter() < stop:
                q = queries[i % len(queries)]
                i += 1

                def one(q=q):
                    ms = self._timed(q)
                    with lock:
                        lat.append(ms)

                futures.append(pool.submit(one))
                next_t += interval
                delay = next_t - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
            concurrent.futures.wait(futures, timeout=60)
        # wall covers the DRAIN too: a backlogged system finishing its
        # queue after the submission window must not report the backlog
        # as achieved throughput
        wall = max(time.perf_counter() - start, 1e-9)
        return RunnerReport("targetQPS", len(lat), wall, len(lat) / wall, lat)
