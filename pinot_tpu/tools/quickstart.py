"""Quickstarts: offline (baseballStats), realtime (meetupRsvp-shaped
stream), hybrid — the ``Quickstart.java:33`` / ``RealtimeQuickStart.java``
/ ``HybridQuickstart.java`` analogs: stand up an in-process cluster,
load data, run sample queries, optionally keep an HTTP broker running.
"""
from __future__ import annotations

import json
import time
from typing import List, Optional

from pinot_tpu.common.schema import DataType, FieldSpec, FieldType, Schema, TimeFieldSpec
from pinot_tpu.realtime.stream import MemoryStreamProvider
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.startree.builder import StarTreeBuilderConfig
from pinot_tpu.tools.cluster_harness import InProcessCluster
from pinot_tpu.tools.datagen import baseball_rows, baseball_schema

# demo clusters serve interactively after the samples print, so the
# timeout only caps the worst case; it must cover a cold-chip compile
_COLD_TIMEOUT_MS = 300_000.0


def drain_stream(cluster: InProcessCluster, physical: str, max_rows: int = 10_000) -> int:
    """Consume/seal/roll partition 0 until the stream is dry (the
    background consume loop a deployment runs); returns sealed count."""
    from pinot_tpu.realtime.llc import make_segment_name

    seq = 0
    while True:
        seg = make_segment_name(physical, 0, seq)
        dms = cluster.controller.realtime_manager.consumers_of(seg)
        if not dms:
            break
        dm = dms[0]
        consumed = dm.consume_step(max_rows=max_rows)
        if dm.threshold_reached:
            dm.try_commit()
            seq += 1
        elif consumed == 0:
            break
    return seq

OFFLINE_SAMPLE_QUERIES = [
    "SELECT count(*) FROM baseballStats",
    "SELECT sum(runs) FROM baseballStats GROUP BY playerName TOP 5",
    "SELECT sum(hits), sum(homeRuns) FROM baseballStats WHERE teamID = 'BOS'",
    "SELECT avg(runs) FROM baseballStats GROUP BY league",
    "SELECT playerName, runs FROM baseballStats ORDER BY runs DESC LIMIT 5",
]


def run_offline_quickstart(
    num_rows: int = 10_000,
    num_segments: int = 4,
    startree: bool = False,
    http: bool = False,
    verbose: bool = True,
) -> InProcessCluster:
    """baseballStats offline quickstart: CSV-shaped data -> segments ->
    cluster -> PQL over HTTP (the minimum end-to-end slice, SURVEY §7)."""
    schema = baseball_schema()
    rows = baseball_rows(num_rows)
    # each demo query is a fresh plan shape: on a cold accelerator the
    # first compile takes 20-40s, so the serving default (15s) would
    # time out every sample query
    cluster = InProcessCluster(num_servers=2, http=http, timeout_ms=_COLD_TIMEOUT_MS)
    physical = cluster.add_offline_table(schema)

    chunk = max(1, len(rows) // num_segments)
    cfg = StarTreeBuilderConfig(max_leaf_records=100) if startree else None
    for i in range(num_segments):
        part = rows[i * chunk : (i + 1) * chunk if i < num_segments - 1 else len(rows)]
        seg = build_segment(
            schema, part, physical, f"baseballStats_{i}", startree_config=cfg
        )
        cluster.upload(physical, seg)

    if verbose:
        for pql in OFFLINE_SAMPLE_QUERIES:
            resp = cluster.query(pql)
            print(f"\n>>> {pql}")
            print(json.dumps(resp.to_json(), indent=2)[:1200])
        if http:
            print(f"\nbroker listening on http://127.0.0.1:{cluster.http.port}/query")
    return cluster


def meetup_schema() -> Schema:
    return Schema(
        "meetupRsvp",
        dimensions=[
            FieldSpec("venue_name", DataType.STRING),
            FieldSpec("event_name", DataType.STRING),
            FieldSpec("group_city", DataType.STRING),
        ],
        metrics=[FieldSpec("rsvp_count", DataType.INT, FieldType.METRIC)],
        time_field=TimeFieldSpec("mtime", DataType.LONG, time_unit="MILLISECONDS"),
    )


def run_realtime_quickstart(
    num_events: int = 2000, http: bool = False, verbose: bool = True
) -> InProcessCluster:
    """meetupRsvp realtime quickstart: stream -> consuming segment ->
    live windowed count queries (RealtimeQuickStart.java analog)."""
    import random

    rng = random.Random(1)
    schema = meetup_schema()
    cluster = InProcessCluster(num_servers=1, http=http, timeout_ms=_COLD_TIMEOUT_MS)
    stream = MemoryStreamProvider(num_partitions=1)
    physical = cluster.add_realtime_table(schema, stream, rows_per_segment=500)

    cities = ["sf", "nyc", "seattle", "austin", "chicago"]
    now = int(time.time() * 1000)
    for i in range(num_events):
        stream.produce(
            {
                "venue_name": f"venue{rng.randrange(20)}",
                "event_name": f"event{rng.randrange(8)}",
                "group_city": rng.choice(cities),
                "rsvp_count": rng.randint(1, 5),
                "mtime": now + i,
            }
        )

    drain_stream(cluster, physical)

    if verbose:
        for pql in [
            "SELECT count(*) FROM meetupRsvp",
            "SELECT sum(rsvp_count) FROM meetupRsvp GROUP BY group_city",
            "SELECT count(*) FROM meetupRsvp GROUP BY event_name TOP 3",
        ]:
            resp = cluster.query(pql)
            print(f"\n>>> {pql}")
            print(json.dumps(resp.to_json(), indent=2)[:900])
    return cluster


def run_hybrid_quickstart(
    num_offline: int = 1500, num_realtime: int = 800, http: bool = False, verbose: bool = True
) -> InProcessCluster:
    """Hybrid quickstart (``HybridQuickstart.java`` analog): the SAME
    logical table served by an OFFLINE side (historical segments) and a
    REALTIME side (live stream), federated at query time by the offline
    max-time boundary — offline answers <= boundary, realtime answers
    the fresh tail, each row counted exactly once."""
    import random

    rng = random.Random(3)
    schema = meetup_schema()
    cluster = InProcessCluster(num_servers=2, http=http, timeout_ms=_COLD_TIMEOUT_MS)
    cities = ["sf", "nyc", "seattle", "austin", "chicago"]
    base = int(time.time() * 1000) - 86_400_000  # yesterday

    def event(i: int) -> dict:
        return {
            "venue_name": f"venue{rng.randrange(20)}",
            "event_name": f"event{rng.randrange(8)}",
            "group_city": rng.choice(cities),
            "rsvp_count": rng.randint(1, 5),
            "mtime": base + i * 1000,
        }

    # offline side: two historical segments
    offline = cluster.add_offline_table(schema, table_name="meetupRsvp")
    rows = [event(i) for i in range(num_offline)]
    half = num_offline // 2
    for name, part in (("hist0", rows[:half]), ("hist1", rows[half:])):
        cluster.upload(offline, build_segment(schema, part, offline, name))

    # realtime side: the live tail STARTS BEFORE the boundary to prove
    # overlap dedup, then extends past it
    stream = MemoryStreamProvider(num_partitions=1)
    rt_physical = cluster.add_realtime_table(schema, stream, rows_per_segment=10_000)
    for i in range(num_offline - 100, num_offline + num_realtime):
        stream.produce(event(i))
    # consume/seal/roll until dry, so row counts past one segment's
    # budget still land
    drain_stream(cluster, rt_physical, max_rows=1_000_000)

    if verbose:
        for pql in [
            "SELECT count(*) FROM meetupRsvp",
            "SELECT max(mtime) FROM meetupRsvp",
            "SELECT sum(rsvp_count) FROM meetupRsvp GROUP BY group_city TOP 5",
        ]:
            resp = cluster.query(pql)
            print(f"\n>>> {pql}")
            print(json.dumps(resp.to_json(), indent=2)[:900])
        if http:
            print(f"\nbroker listening on http://127.0.0.1:{cluster.http.port}/query")
    return cluster


def run_network_realtime_quickstart(
    num_events: int = 2000,
    verbose: bool = True,
    data_dir: Optional[str] = None,
    consumer_type: str = "lowlevel",
    stream_protocol: str = "native",
):
    """Networked realtime quickstart: a real TCP stream-broker process
    boundary (realtime/netstream.py), a controller + server + broker as
    separate OS processes, REALTIME table created over REST, rows
    produced over TCP, counts queried through the broker HTTP port —
    the full reference deployment shape with the stream broker playing
    Kafka's role.

    ``stream_protocol="kafka"`` fronts the stream broker with the Kafka
    v0 wire-protocol shim (realtime/kafka.py) and creates the table
    with ``stream_type="kafka"``: the server processes then consume
    through the Kafka binary protocol (Metadata/ListOffsets/Fetch),
    exactly as they would against a real Kafka 0.8+ deployment
    (``SimpleConsumerWrapper.java`` parity)."""
    import random
    import subprocess
    import sys
    import tempfile
    import urllib.request

    from pinot_tpu.common.tableconfig import StreamConfig, TableConfig
    from pinot_tpu.realtime.netstream import NetworkStreamProvider, StreamBrokerServer

    root = data_dir or tempfile.mkdtemp(prefix="pinot_tpu_netrt_")
    stream_broker = StreamBrokerServer(log_dir=f"{root}/streamlog")
    stream_broker.start()
    host, port = stream_broker.address
    producer = NetworkStreamProvider(host, port, "meetupRsvp")
    producer.create_topic(1 if consumer_type == "lowlevel" else 2)
    kafka_shim = None
    if stream_protocol == "kafka":
        from pinot_tpu.realtime.kafka import KafkaProtocolShim

        kafka_shim = KafkaProtocolShim(stream_broker).start()

    def spawn(args, prefix="READY"):
        import select

        # every child inherits the environment: the server child takes
        # the platform JAX_PLATFORMS names (the chip, where there is
        # one), and the controller and broker children never initialize
        # a backend at all (their /health says so)
        proc = subprocess.Popen(
            [sys.executable, "-m", "pinot_tpu.tools.admin", *args],
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
            text=True,
        )
        deadline = time.time() + 90
        while time.time() < deadline:
            ready, _, _ = select.select([proc.stdout], [], [], 1.0)
            if ready:
                line = proc.stdout.readline()
                if line.startswith(prefix):
                    return proc, line.split()[-1]
            if proc.poll() is not None:
                raise RuntimeError(f"process exited early: {args}")
        proc.kill()
        raise RuntimeError(f"no READY from {args}")

    procs = []
    try:
        ctrl, ctrl_url = spawn(["StartController", "-port", "0", "-data-dir", f"{root}/store"])
        procs.append(ctrl)
        srv, _ = spawn(["StartServer", "-controller", ctrl_url, "-name", "qs0",
                        "-data-dir", f"{root}/cache"])
        procs.append(srv)
        brk, broker_url = spawn(["StartBroker", "-controller", ctrl_url, "-port", "0"])
        procs.append(brk)

        def post(url, payload):
            req = urllib.request.Request(
                url, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())

        schema = meetup_schema()
        post(ctrl_url + "/schemas", schema.to_json())
        if kafka_shim is not None:
            k_host, k_port = kafka_shim.address
            stream_cfg = StreamConfig(
                stream_type="kafka",
                topic="meetupRsvp",
                rows_per_segment=500,
                consumer_type=consumer_type,
                properties={"host": k_host, "port": k_port},
            )
        else:
            stream_cfg = StreamConfig(
                stream_type="network",
                topic="meetupRsvp",
                rows_per_segment=500,
                consumer_type=consumer_type,
                properties={"host": host, "port": port},
            )
        config = TableConfig(
            table_name="meetupRsvp",
            table_type="REALTIME",
            stream=stream_cfg,
        )
        post(ctrl_url + "/tables", config.to_json())

        rng = random.Random(1)
        now = int(time.time() * 1000)
        producer.produce_batch(
            [
                {
                    "venue_name": f"venue{rng.randrange(20)}",
                    "event_name": f"event{rng.randrange(8)}",
                    "group_city": rng.choice(["sf", "nyc", "seattle", "austin"]),
                    "rsvp_count": rng.randint(1, 5),
                    "mtime": now + i,
                }
                for i in range(num_events)
            ]
        )

        deadline = time.time() + 120
        count = 0
        while time.time() < deadline:
            resp = post(broker_url + "/query", {"pql": "SELECT count(*) FROM meetupRsvp"})
            count = resp.get("numDocsScanned", 0)
            if count >= num_events and not resp.get("exceptions"):
                break
            time.sleep(0.5)
        if verbose:
            for pql in [
                "SELECT count(*) FROM meetupRsvp",
                "SELECT sum(rsvp_count) FROM meetupRsvp GROUP BY group_city",
            ]:
                resp = post(broker_url + "/query", {"pql": pql})
                print(f"\n>>> {pql}")
                print(json.dumps(resp, indent=2)[:900])
            # one process for each chip: the server child is the only
            # one that may have a backend up, this parent included
            from pinot_tpu.utils.platform import backend_state

            def get(url):
                with urllib.request.urlopen(url, timeout=30) as r:
                    return json.loads(r.read())

            util = get(ctrl_url + "/debug/utilization")
            print("\n>>> who holds a device: " + json.dumps({
                "server": util["servers"]["qs0"]["device"]["platform"],
                "controller": get(ctrl_url + "/health")["jax"],
                "broker": get(broker_url + "/health")["jax"],
                "parent": backend_state(),
            }))
        return count
    finally:
        if kafka_shim is not None:
            kafka_shim.stop()
        stream_broker.stop()
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
