"""Parallel realtime ingest benchmark: N partition consumers against
the stream broker, plus query latency DURING sustained ingest.

The reference measures realtime consumption as rows/s through one
segment's ``index()`` loop (``BenchmarkRealtimeConsumptionSpeed.java:38``).
Production ingest is N partition consumers spread across server
processes, each pulling batches from the stream broker by offset and
indexing into its partition's mutable segment — so this bench runs the
REAL consumer path (TCP fetch -> JSON decode -> encode -> commit) with
one OS process per partition:

  1. a ``StreamBrokerServer`` (realtime/netstream.py) holds an
     N-partition numeric-heavy topic, pre-produced;
  2. N-1 consumer subprocesses each drain one partition into a
     ``MutableSegment`` and report their own rows/s;
  3. partition 0 is consumed IN-PROCESS on a thread while a broker
     serves its live mutable segment — query p50/p99 is measured
     against it during the sustained ingest window.

Aggregate rows/s = total rows / slowest consumer's drain time (the
honest cluster-level number: ingestion finishes when the last
partition catches up).

Usage:
  python -m pinot_tpu.tools.ingest_bench -partitions 4 -rows 1000000

``--ladder`` (r15) runs the partition-parallel consumer ladder instead:
1/2/4 consumers — each a REAL ``RealtimeSegmentDataManager`` driven by
an ``IngestConsumerPool`` (realtime/pool.py) in its own OS process,
the production shape of consumers spread across server processes —
draining pre-produced partitions, reporting per-rung aggregate rows/s
and lag drain.  The 1-consumer baseline pins broker AND consumer to a
single core: that is the single-consumer LLC ceiling as INGEST_r5
committed it (``cpu_cores: 1``), and the number partition-parallel
aggregate ingest must beat.  Emits a perf-gateable document
(``metric: ingest_parallel_rows_per_sec``; see
``tools/perf_gate.py INGEST_METRIC_SPECS`` / ``INGEST_r15.json``).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
from typing import List

import numpy as np

from pinot_tpu.common.schema import (
    DataType,
    FieldSpec,
    FieldType,
    Schema,
    TimeFieldSpec,
)

TOPIC = "adclicks"
FETCH_ROWS = 4096
BLOCK_ROWS = 65536  # columnar block size: amortizes RTT, keeps encode batches fat

# the committed single-consumer LLC ceiling this arc set out to beat:
# INGEST_r5.json llc_consumer_columnar_rows_per_sec (the production
# RealtimeSegmentDataManager measured through its own consume_step
# loop, cpu_cores=1).  The ladder reports its aggregate against this
# reference alongside the same-host parallel_vs_single ratio.
R5_SINGLE_CONSUMER_CEILING = 1_288_021.0


def adclick_schema() -> Schema:
    """Numeric-heavy schema (the reference's consumption benchmark uses
    a numeric-dominated row too)."""
    return Schema(
        "adclicks",
        dimensions=[
            FieldSpec("campaign_id", DataType.INT, FieldType.DIMENSION),
            FieldSpec("site_id", DataType.INT, FieldType.DIMENSION),
            FieldSpec("user_id", DataType.LONG, FieldType.DIMENSION),
        ],
        metrics=[
            FieldSpec("clicks", DataType.INT, FieldType.METRIC),
            FieldSpec("cost", DataType.FLOAT, FieldType.METRIC),
        ],
        time_field=TimeFieldSpec("ts", DataType.LONG, time_unit="MILLISECONDS"),
    )


def gen_columns(n: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {
        "campaign_id": rng.integers(0, 1024, n, dtype=np.int64),
        "site_id": rng.integers(0, 128, n, dtype=np.int64),
        "user_id": rng.integers(0, 1 << 22, n, dtype=np.int64),
        "clicks": rng.integers(0, 16, n, dtype=np.int64),
        "cost": np.round(rng.random(n) * 10, 3),
        "ts": 1_700_000_000_000 + np.arange(n, dtype=np.int64),
    }


def drain_partition(host: str, port: int, partition: int, expect_rows: int, seg=None):
    """The real consumer loop: offset-addressed columnar TCP fetch +
    vectorized dictionary encode.  Returns (rows, seconds, segment)."""
    from pinot_tpu.realtime.mutable import MutableSegment
    from pinot_tpu.realtime.netstream import NetworkStreamProvider

    provider = NetworkStreamProvider(host, port, TOPIC)
    if seg is None:
        seg = MutableSegment(adclick_schema(), f"rt{partition}", "adclicks")
    offset = 0
    total = 0
    t0 = time.perf_counter()
    while total < expect_rows:
        cols, n, offset = provider.fetch_columns(partition, offset)
        if n == 0:
            time.sleep(0.001)
            continue
        seg.index_columns(cols)
        total += n
    return total, time.perf_counter() - t0, seg


def worker_main() -> None:
    host, port, partition, expect = (
        sys.argv[2],
        int(sys.argv[3]),
        int(sys.argv[4]),
        int(sys.argv[5]),
    )
    total, secs, _seg = drain_partition(host, port, partition, expect)
    print(json.dumps({"partition": partition, "rows": total, "seconds": round(secs, 3)}), flush=True)


def broker_main() -> None:
    """The stream broker as its OWN process: serving byte-splice fetches
    must not share a GIL with the query engine or a consumer.
    ``PINOT_TPU_LADDER_BROKER_CORE`` pins the WHOLE process (set before
    any serving thread spawns, so every thread inherits it) — the
    ladder's single-core baseline rung uses this."""
    from pinot_tpu.realtime.netstream import StreamBrokerServer

    core = os.environ.get("PINOT_TPU_LADDER_BROKER_CORE")
    if core:
        os.sched_setaffinity(0, {int(core)})
    partitions = int(sys.argv[2])
    srv = StreamBrokerServer()
    srv.start()
    srv.create_topic(TOPIC, partitions)
    print(json.dumps({"port": srv.address[1]}), flush=True)
    try:
        time.sleep(3600)
    except KeyboardInterrupt:
        pass


def ladder_worker_main() -> None:
    """One ladder consumer process: the real r15 consumer machinery —
    ``RealtimeSegmentDataManager`` (columnar fetch path) registered
    with an ``IngestConsumerPool`` — draining one partition.  argv:
    --ladder-worker host port partition rows core(-1=unpinned)."""
    host, port, partition, rows, core = (
        sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
        int(sys.argv[5]), int(sys.argv[6]),
    )
    if core >= 0:
        os.sched_setaffinity(0, {core})
    from pinot_tpu.realtime.llc import RealtimeSegmentDataManager
    from pinot_tpu.realtime.netstream import NetworkStreamProvider
    from pinot_tpu.realtime.pool import IngestConsumerPool

    class _BenchServer:  # the attrs the DM reads; no metrics/governor
        name = f"ladder{partition}"
        metrics = None
        ingest_backpressure = None
        result_cache = None

    stream = NetworkStreamProvider(host, port, TOPIC)
    dm = RealtimeSegmentDataManager(
        server=_BenchServer(),
        manager=None,  # no commits: rows_per_segment is never reached
        table="adclicks",
        segment_name=f"adclicks__{partition}__0",
        schema=adclick_schema(),
        stream=stream,
        partition=partition,
        start_offset=0,
        rows_per_segment=rows + 1,
    )
    dm.step_rows = BLOCK_ROWS  # consume whole columnar blocks per step
    pool = IngestConsumerPool(workers=1, name=f"ladder{partition}")
    # start barrier: every rung sibling finishes its (CPU-heavy)
    # interpreter startup BEFORE any of them drains, or the measured
    # window of one consumer overlaps another's imports
    print("READY", flush=True)
    sys.stdin.readline()
    t0 = time.perf_counter()
    pool.add(dm, key=partition)
    while dm.offset < rows:
        time.sleep(0.002)
    secs = time.perf_counter() - t0
    lag = dm.lag()
    pool.stop()
    print(
        json.dumps(
            {
                "partition": partition,
                "rows": dm.mutable.num_docs,
                "seconds": round(secs, 3),
                "lagFinal": lag,
            }
        ),
        flush=True,
    )


def ladder_main(args) -> None:
    """The 1/2/4-consumer partition-parallel ladder (r15)."""
    from pinot_tpu.realtime.netstream import NetworkStreamProvider

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    cores = sorted(os.sched_getaffinity(0))
    partitions = max(args.partitions, max(args.ladder_rungs))
    host = "127.0.0.1"

    def start_broker(n_partitions: int, pin_core=None):
        broker_env = dict(env)
        if pin_core is not None:
            broker_env["PINOT_TPU_LADDER_BROKER_CORE"] = str(pin_core)
        proc = subprocess.Popen(
            [sys.executable, "-m", "pinot_tpu.tools.ingest_bench",
             "--broker", str(n_partitions)],
            stdout=subprocess.PIPE, text=True, env=broker_env,
        )
        return proc, int(json.loads(proc.stdout.readline())["port"])

    def produce_all(port: int, n_partitions: int) -> None:
        def produce(p: int) -> None:
            provider = NetworkStreamProvider(host, port, TOPIC)
            cols = gen_columns(args.rows, seed=17 + p)
            for i in range(0, args.rows, BLOCK_ROWS):
                provider.produce_columns(
                    {c: a[i : i + BLOCK_ROWS] for c, a in cols.items()},
                    partition=p,
                )

        producers = [
            threading.Thread(target=produce, args=(p,))
            for p in range(n_partitions)
        ]
        for t in producers:
            t.start()
        for t in producers:
            t.join()

    def rung(port: int, consumers: int, pin_core=None):
        """Drain ``consumers`` partitions concurrently, one consumer
        process per partition (fetches are non-destructive, so rungs
        against the shared broker re-drain from offset 0)."""
        procs = [
            subprocess.Popen(
                [sys.executable, "-m", "pinot_tpu.tools.ingest_bench",
                 "--ladder-worker", host, str(port), str(p), str(args.rows),
                 str(pin_core if pin_core is not None else -1)],
                stdout=subprocess.PIPE, stdin=subprocess.PIPE,
                text=True, env=env,
            )
            for p in range(consumers)
        ]
        for pr in procs:  # wait for every sibling's READY, then GO
            assert pr.stdout.readline().strip() == "READY"
        for pr in procs:
            pr.stdin.write("GO\n")
            pr.stdin.flush()
        outs = [
            json.loads(pr.communicate(timeout=900)[0].splitlines()[-1])
            for pr in procs
        ]
        wall = max(o["seconds"] for o in outs)
        total = sum(o["rows"] for o in outs)
        return {
            "consumers": consumers,
            "rows": total,
            "rows_per_sec": round(total / wall, 1),
            # the pre-produced backlog IS the lag: draining it to 0 is
            # the lag-drain measurement
            "lag_drain_rows": total,
            "lag_drain_s": round(wall, 3),
            "lag_final": max(int(o.get("lagFinal") or 0) for o in outs),
        }

    ladder = {}
    # single-consumer baseline: broker AND consumer confined to ONE
    # core — the single-consumer LLC ceiling as INGEST_r5 committed it
    # (a cpu_cores=1 capture).  A dedicated broker process is used so
    # the affinity is set before any serving thread spawns.
    if 1 in args.ladder_rungs:
        pin_broker, pin_port = start_broker(1, pin_core=cores[0])
        produce_all(pin_port, 1)
        ladder["c1"] = rung(pin_port, 1, pin_core=cores[0])
        pin_broker.terminate()
        print(json.dumps({"rung": ladder["c1"]}), file=sys.stderr, flush=True)
    broker_proc, port = start_broker(partitions)
    produce_all(port, partitions)
    for c in args.ladder_rungs:
        if c == 1:
            continue
        ladder[f"c{c}"] = rung(port, c)
        print(json.dumps({"rung": ladder[f"c{c}"]}), file=sys.stderr, flush=True)
    broker_proc.terminate()

    # c1 only exists when rung 1 was requested; ratios degrade to None
    single = (ladder.get("c1") or {}).get("rows_per_sec")
    best = max(r["rows_per_sec"] for r in ladder.values())
    doc = {
        "metric": "ingest_parallel_rows_per_sec",
        "value": best,
        "bench": "partition_parallel_ingest_ladder",
        "path": "RealtimeSegmentDataManager (columnar TCP fetch -> "
        "np.frombuffer decode -> vectorized dictionary encode) driven "
        "by IngestConsumerPool, one consumer process per partition",
        "platform": "cpu",
        "cpu_cores": len(cores),
        "partitions": partitions,
        "rows_per_partition": args.rows,
        "ladder": ladder,
        "single_consumer_rows_per_sec": single,
        "parallel_vs_single": round(best / single, 3) if single else None,
        "r5_single_consumer_ceiling_rows_per_sec": R5_SINGLE_CONSUMER_CEILING,
        "vs_r5_single_consumer_ceiling": round(
            best / R5_SINGLE_CONSUMER_CEILING, 3
        ),
        "note": "c1 pins broker+consumer to ONE core (the single-"
        "consumer LLC ceiling as INGEST_r5 committed it, cpu_cores=1); "
        "parallel rungs use every core.  2-core CI caveat: the "
        "vectorized dictionary encode is MEMORY-BANDWIDTH-bound on "
        "this container (two pure-encode processes with no broker "
        "measure the same ~1.3-1.4x wall), so parallel_vs_single "
        "saturates near 1.3x here — re-capture on a many-core host "
        "for the full partition-parallel curve.  vs_r5_single_"
        "consumer_ceiling is the arc's headline: aggregate ingest vs "
        "the committed INGEST_r5 single-consumer LLC ceiling.",
    }
    out = json.dumps(doc, indent=1)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-partitions", type=int, default=4)
    ap.add_argument("-rows", type=int, default=1_000_000, help="rows per partition")
    ap.add_argument("-out", type=str, default="")
    ap.add_argument(
        "--ladder", action="store_true",
        help="run the r15 partition-parallel consumer ladder instead",
    )
    ap.add_argument(
        "--ladder-rungs", type=int, nargs="+", default=[1, 2, 4],
        help="consumer counts per ladder rung",
    )
    args = ap.parse_args()
    if args.ladder:
        return ladder_main(args)

    from pinot_tpu.realtime.netstream import NetworkStreamProvider

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    broker_proc = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "pinot_tpu.tools.ingest_bench",
            "--broker",
            str(args.partitions),
        ],
        stdout=subprocess.PIPE,
        text=True,
        env=env,
    )
    host = "127.0.0.1"
    port = int(json.loads(broker_proc.stdout.readline())["port"])

    # pre-produce every partition (setup, not measured): one producer
    # thread per partition overlaps the JSON encode
    t0 = time.perf_counter()

    def produce(p: int) -> None:
        provider = NetworkStreamProvider(host, port, TOPIC)
        cols = gen_columns(args.rows, seed=17 + p)
        for i in range(0, args.rows, BLOCK_ROWS):
            block = {c: a[i : i + BLOCK_ROWS] for c, a in cols.items()}
            provider.produce_columns(block, partition=p)

    producers = [threading.Thread(target=produce, args=(p,)) for p in range(args.partitions)]
    for t in producers:
        t.start()
    for t in producers:
        t.join()
    produce_s = time.perf_counter() - t0
    print(json.dumps({"produce_s": round(produce_s, 1)}), flush=True)

    # solo phase FIRST (nothing else consuming): one consumer, no query
    # load — the peak per-core consumer rate (fetches are
    # offset-addressed and non-destructive, so partition 0 re-drains in
    # the parallel phase)
    solo_rows, solo_s, _ = drain_partition(host, port, 0, args.rows)
    solo_rate = round(solo_rows / solo_s, 1)
    print(json.dumps({"solo_consumer_rows_per_sec": solo_rate}), flush=True)

    # consumers: partition 0 in-process (query target), 1..N-1 as procs
    procs = [
        subprocess.Popen(
            [
                sys.executable,
                "-m",
                "pinot_tpu.tools.ingest_bench",
                "--worker",
                host,
                str(port),
                str(p),
                str(args.rows),
            ],
            stdout=subprocess.PIPE,
            text=True,
            env=env,
        )
        for p in range(1, args.partitions)
    ]

    # partition 0's live mutable segment exists BEFORE its consumer
    # starts, so a broker can serve it while rows stream in
    from pinot_tpu.realtime.mutable import MutableSegment
    from pinot_tpu.tools.cluster_harness import single_server_broker

    live_seg = MutableSegment(adclick_schema(), "rt0", "adclicks")
    qbroker = single_server_broker("adclicks", [live_seg])
    local: dict = {}

    def local_consume() -> None:
        total, secs, _ = drain_partition(host, port, 0, args.rows, seg=live_seg)
        local.update({"rows": total, "seconds": secs})

    t_local = threading.Thread(target=local_consume)
    t_local.start()

    # query p50/p99 measured DURING the sustained ingest window: every
    # query sees the consumer's latest snapshot watermark advance
    pql = (
        "SELECT count(*), sum(clicks) FROM adclicks "
        "GROUP BY campaign_id TOP 10"
    )
    while live_seg.num_docs == 0 and t_local.is_alive():
        time.sleep(0.02)
    for _ in range(3):
        qbroker.handle_pql(pql)  # warm + compile
    during: List[float] = []
    docs_seen: List[int] = []
    while t_local.is_alive():
        q0 = time.perf_counter()
        resp = qbroker.handle_pql(pql)
        assert not resp.exceptions, resp.exceptions
        during.append((time.perf_counter() - q0) * 1000)
        docs_seen.append(resp.num_docs_scanned)
        # ~1 QPS probe cadence: measure live-query latency without the
        # query loop itself stealing the (single) core from ingest
        time.sleep(max(0.0, 1.0 - (time.perf_counter() - q0)))
    t_local.join()

    results = [json.loads(p.communicate(timeout=600)[0].splitlines()[-1]) for p in procs]
    results.append(
        {"partition": 0, "rows": local["rows"], "seconds": round(local["seconds"], 3)}
    )
    broker_proc.terminate()

    total_rows = sum(r["rows"] for r in results)
    slowest = max(r["seconds"] for r in results)
    doc = {
        "bench": "parallel_realtime_ingest",
        "schema": "numeric-heavy (3 int/long dims, 2 numeric metrics, time)",
        "path": "columnar TCP stream fetch -> np.frombuffer decode -> "
        "vectorized dictionary encode -> commit",
        "cpu_cores": len(os.sched_getaffinity(0)),
        "partitions": args.partitions,
        "rows_per_partition": args.rows,
        "total_rows": total_rows,
        "per_consumer": results,
        "solo_consumer_rows_per_sec": solo_rate,
        "aggregate_rows_per_sec": round(total_rows / slowest, 1),
        "queries_during_ingest": len(during),
        "query_during_ingest_p50_ms": round(sorted(during)[len(during) // 2], 2) if during else None,
        "query_during_ingest_p99_ms": (
            round(sorted(during)[min(len(during) - 1, int(len(during) * 0.99))], 2)
            if during
            else None
        ),
        "docs_growing_during_queries": bool(docs_seen and docs_seen[-1] > docs_seen[0]),
    }
    out = json.dumps(doc, indent=1)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        worker_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--broker":
        broker_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--ladder-worker":
        ladder_worker_main()
    else:
        main()
