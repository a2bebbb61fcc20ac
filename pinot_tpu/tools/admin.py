"""Admin CLI — the ``PinotAdministrator`` analog (pinot-tools, 30+
commands).  Usage: ``python -m pinot_tpu.tools.admin <command> [args]``.

Commands:
  Quickstart            offline baseballStats demo (Quickstart.java:33)
  RealtimeQuickstart    streaming meetupRsvp demo
  HybridQuickstart      offline history + live stream, one logical table
  NetworkRealtimeQuickstart  same, across real processes + TCP stream broker
  StartCluster          in-process cluster with HTTP broker+controller
  StartController       standalone controller process (networked cluster)
  StartServer           standalone server process joining a controller
  StartBroker           standalone broker process joining a controller
  StartStreamBroker     standalone TCP stream broker (realtime ingest)
  CreateSegment         build a segment from CSV/JSONL + schema JSON
  UploadSegment         POST a segment file to a controller
  AddSchema / AddTable  controller CRUD
  PostQuery             run PQL against a broker
  QueryRunner           perf modes singleThread/multiThreads/targetQPS
  ShowSegment           print a segment file's metadata
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request


def _post(url: str, payload: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def cmd_quickstart(args) -> None:
    from pinot_tpu.tools.quickstart import run_offline_quickstart

    cluster = run_offline_quickstart(
        num_rows=args.rows, startree=args.startree, http=not args.no_http
    )
    if not args.no_http:
        print("Ctrl-C to exit.")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            cluster.stop()


def cmd_network_realtime_quickstart(args) -> None:
    from pinot_tpu.tools.quickstart import run_network_realtime_quickstart

    count = run_network_realtime_quickstart(
        num_events=args.events,
        consumer_type=args.consumer_type,
        stream_protocol=args.stream_protocol,
    )
    print(f"\nDONE networked realtime quickstart ({args.consumer_type}, "
          f"{args.stream_protocol} stream): {count} events ingested")


def cmd_realtime_quickstart(args) -> None:
    from pinot_tpu.tools.quickstart import run_realtime_quickstart

    cluster = run_realtime_quickstart(num_events=args.events, http=not args.no_http)
    if not args.no_http:
        print("Ctrl-C to exit.")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            cluster.stop()


def cmd_hybrid_quickstart(args) -> None:
    from pinot_tpu.tools.quickstart import run_hybrid_quickstart

    cluster = run_hybrid_quickstart(
        num_offline=args.offline_rows,
        num_realtime=args.realtime_rows,
        http=not args.no_http,
    )
    if not args.no_http:
        print("Ctrl-C to exit.")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            cluster.stop()


def cmd_start_cluster(args) -> None:
    from pinot_tpu.broker.broker import BrokerHttpServer
    from pinot_tpu.controller.controller import ControllerHttpServer
    from pinot_tpu.tools.cluster_harness import InProcessCluster

    cluster = InProcessCluster(num_servers=args.servers, data_dir=args.data_dir)
    broker_http = BrokerHttpServer(cluster.broker, port=args.broker_port)
    broker_http.start()
    cluster.broker_starter.url = f"http://127.0.0.1:{broker_http.port}"
    controller_http = ControllerHttpServer(cluster.controller, port=args.controller_port)
    controller_http.start()
    # register broker url for client discovery
    inst = cluster.controller.resources.instances.get("broker0")
    if inst is not None:
        inst.url = f"http://127.0.0.1:{broker_http.port}"
    print(f"controller: http://127.0.0.1:{controller_http.port}")
    print(f"broker:     http://127.0.0.1:{broker_http.port}/query")
    print("Ctrl-C to exit.")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        broker_http.stop()
        controller_http.stop()
        cluster.stop()


def _serve_forever(stoppers) -> None:
    print("Ctrl-C to exit.", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        for s in stoppers:
            s()


def cmd_start_controller(args) -> None:
    """Standalone controller process (ControllerStarter.java:47 analog)."""
    from pinot_tpu.controller.controller import Controller, ControllerHttpServer

    ctrl = Controller(args.data_dir, start_managers=True)
    ctrl.gateway.heartbeat_timeout_s = args.heartbeat_timeout
    http = ControllerHttpServer(ctrl, port=args.port)
    http.start()
    print(f"READY controller http://127.0.0.1:{http.port}", flush=True)
    _serve_forever([http.stop, ctrl.stop])


def cmd_start_server(args) -> None:
    """Standalone server process joining a remote controller
    (HelixServerStarter.java:63 analog)."""
    from pinot_tpu.server.network_starter import NetworkedServerStarter

    starter = NetworkedServerStarter(
        args.controller, args.name, port=args.port, data_dir=args.data_dir
    )
    starter.start()
    print(f"READY server {starter.tcp.address[0]}:{starter.tcp.address[1]}", flush=True)
    _serve_forever([starter.stop])


def cmd_start_broker(args) -> None:
    """Standalone broker process joining a remote controller
    (HelixBrokerStarter.java:57 analog)."""
    from pinot_tpu.broker.network_starter import NetworkedBrokerStarter

    starter = NetworkedBrokerStarter(args.controller, args.name, port=args.port)
    starter.start()
    print(f"READY broker http://127.0.0.1:{starter.http.port}", flush=True)
    _serve_forever([starter.stop])


def cmd_start_stream_broker(args) -> None:
    """Standalone TCP stream-broker process (the Kafka-broker role for
    realtime ingestion; realtime/netstream.py)."""
    from pinot_tpu.realtime.netstream import StreamBrokerServer

    broker = StreamBrokerServer(port=args.port, log_dir=args.log_dir)
    broker.start()
    print(f"READY streambroker {broker.address[0]}:{broker.address[1]}", flush=True)
    _serve_forever([broker.stop])


def cmd_create_segment(args) -> None:
    from pinot_tpu.common.schema import Schema
    from pinot_tpu.segment.builder import build_segment
    from pinot_tpu.segment.columnar import build_segment_from_csv
    from pinot_tpu.segment.format import write_segment
    from pinot_tpu.segment.readers import read_jsonl
    from pinot_tpu.startree.builder import StarTreeBuilderConfig

    with open(args.schema_file) as f:
        schema = Schema.from_json(json.load(f))
    cfg = StarTreeBuilderConfig() if args.startree else None
    if args.data_file.endswith(".csv"):
        # columnar path (native one-pass parse when available)
        seg = build_segment_from_csv(
            schema, args.data_file, args.table, args.segment_name, startree_config=cfg
        )
    else:
        from pinot_tpu.segment.readers import read_for_path

        rows = read_for_path(args.data_file, schema)  # avro / jsonl
        seg = build_segment(
            schema, rows, args.table, args.segment_name, startree_config=cfg
        )
    path = write_segment(seg, args.out_dir)
    print(f"built segment {seg.segment_name}: {seg.num_docs} docs -> {path}")


def cmd_batch_create_segments(args) -> None:
    """pinot-hadoop analog: one segment build per input file on a
    worker-process pool, optional push (SegmentCreationJob.java)."""
    import glob as _glob

    from pinot_tpu.tools.batch_build import BatchBuildSpec, run_batch_build

    inputs = sorted(
        f
        for pat in args.inputs
        for f in _glob.glob(pat)
        if os.path.isfile(f)
    )
    if not inputs:
        raise SystemExit(f"no input files matched {args.inputs}")
    spec = BatchBuildSpec(
        schema_file=args.schema_file,
        table=args.table,
        input_files=inputs,
        out_dir=args.out_dir,
        controller=args.controller,
        startree=args.startree,
        segment_name_prefix=args.segment_name_prefix,
    )
    if args.remote_workers:
        from pinot_tpu.tools.batch_build import run_distributed_build

        addrs = []
        for part in args.remote_workers.split(","):
            part = part.strip()
            if not part:
                continue  # tolerate trailing commas
            host, sep, port = part.rpartition(":")
            if not sep or not host or not port.isdigit():
                raise SystemExit(
                    f"-remote-workers: {part!r} is not host:port "
                    "(expected e.g. 10.0.0.5:9600,10.0.0.6:9600)"
                )
            addrs.append((host, int(port)))
        if not addrs:
            raise SystemExit("-remote-workers: no worker addresses given")
        results = run_distributed_build(spec, addrs)
    else:
        results = run_batch_build(spec, workers=args.workers)
    for r in results:
        print(json.dumps(r))


def cmd_start_build_worker(args) -> None:
    """Serve segment-build jobs over TCP (SegmentCreationJob mapper
    analog) until interrupted."""
    import time as _time

    from pinot_tpu.tools.batch_build import serve_build_worker

    server = serve_build_worker(host=args.host, port=args.port)
    print(f"build worker listening on {server.host}:{server.port}")
    try:
        while True:
            _time.sleep(3600)
    except KeyboardInterrupt:
        pass


def cmd_upload_segment(args) -> None:
    with open(args.segment_file, "rb") as f:
        data = f.read()
    url = args.controller.rstrip("/") + f"/segments/{args.table}"
    req = urllib.request.Request(url, data=data, headers={"Content-Type": "application/octet-stream"})
    with urllib.request.urlopen(req, timeout=120) as r:
        print(json.loads(r.read()))


def cmd_add_schema(args) -> None:
    with open(args.schema_file) as f:
        payload = json.load(f)
    print(_post(args.controller.rstrip("/") + "/schemas", payload))


def cmd_add_table(args) -> None:
    with open(args.config_file) as f:
        payload = json.load(f)
    print(_post(args.controller.rstrip("/") + "/tables", payload))


def cmd_post_query(args) -> None:
    out = _post(args.broker.rstrip("/") + "/query", {"pql": args.query, "trace": args.trace})
    print(json.dumps(out, indent=2))


def cmd_query_runner(args) -> None:
    from pinot_tpu.tools.query_runner import QueryRunner, http_query_fn

    with open(args.query_file) as f:
        queries = [q.strip() for q in f if q.strip()]
    runner = QueryRunner(http_query_fn(args.broker))
    if args.mode == "singleThread":
        report = runner.single_thread(queries, rounds=args.rounds)
    elif args.mode == "multiThreads":
        report = runner.multi_threads(queries, num_threads=args.threads, rounds=args.rounds)
    else:
        report = runner.target_qps(queries, qps=args.qps, duration_s=args.duration)
    print(json.dumps(report.to_json(), indent=2))


def cmd_rebalance_table(args) -> None:
    url = args.controller.rstrip("/") + f"/tables/{args.table}/rebalance"
    if args.dry_run:
        url += "?dryRun=true"
    print(json.dumps(_post(url, {}), indent=2))


def cmd_add_tenant(args) -> None:
    print(
        _post(
            args.controller.rstrip("/") + "/tenants",
            {"name": args.name, "role": args.role, "count": args.count},
        )
    )


def cmd_list_tenants(args) -> None:
    with urllib.request.urlopen(args.controller.rstrip("/") + "/tenants", timeout=30) as r:
        print(json.dumps(json.loads(r.read()), indent=2))


def cmd_show_segment(args) -> None:
    from pinot_tpu.segment.format import read_segment

    seg = read_segment(args.segment_dir)
    print(json.dumps(seg.metadata.to_json(), indent=2, default=str))


def cmd_convert_segment(args) -> None:
    from pinot_tpu.tools.converters import segment_to_csv, segment_to_jsonl

    if args.format == "csv":
        n = segment_to_csv(args.segment_dir, args.out_file)
    else:
        n = segment_to_jsonl(args.segment_dir, args.out_file)
    print(f"exported {n} rows -> {args.out_file}")


def cmd_show_star_tree(args) -> None:
    from pinot_tpu.tools.converters import star_tree_summary

    print(json.dumps(star_tree_summary(args.segment_dir, max_nodes=args.max_nodes), indent=2))


def cmd_generate_data(args) -> None:
    from pinot_tpu.common.schema import Schema
    from pinot_tpu.tools.datagen import random_rows

    with open(args.schema_file) as f:
        schema = Schema.from_json(json.load(f))
    rows = random_rows(schema, args.num_rows, seed=args.seed)
    with open(args.out_file, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print(f"generated {len(rows)} rows -> {args.out_file}")


def main(argv=None) -> None:
    import logging
    import os

    lvl = os.environ.get("PINOT_TPU_LOGLEVEL", "WARNING").upper()
    if not isinstance(getattr(logging, lvl, None), int):
        lvl = "WARNING"  # unknown names must not kill a role process
    logging.basicConfig(
        level=lvl,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )

    p = argparse.ArgumentParser(prog="pinot_tpu-admin", description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    q = sub.add_parser("Quickstart")
    q.add_argument("-rows", type=int, default=10_000)
    q.add_argument("-startree", action="store_true")
    q.add_argument("-no-http", action="store_true")
    q.set_defaults(fn=cmd_quickstart)

    rq = sub.add_parser("RealtimeQuickstart")
    rq.add_argument("-events", type=int, default=2000)
    rq.add_argument("-no-http", action="store_true")
    rq.set_defaults(fn=cmd_realtime_quickstart)

    hq = sub.add_parser("HybridQuickstart")
    hq.add_argument("-offline-rows", type=int, default=1500, dest="offline_rows")
    hq.add_argument("-realtime-rows", type=int, default=800, dest="realtime_rows")
    hq.add_argument("-no-http", action="store_true")
    hq.set_defaults(fn=cmd_hybrid_quickstart)

    nrq = sub.add_parser("NetworkRealtimeQuickstart")
    nrq.add_argument("-events", type=int, default=2000)
    nrq.add_argument("-consumer-type", default="lowlevel",
                     choices=["lowlevel", "highlevel"], dest="consumer_type")
    nrq.add_argument("-stream-protocol", default="native",
                     choices=["native", "kafka"], dest="stream_protocol")
    nrq.set_defaults(fn=cmd_network_realtime_quickstart)

    sc = sub.add_parser("StartCluster")
    sc.add_argument("-servers", type=int, default=2)
    sc.add_argument("-data-dir", default=None)
    sc.add_argument("-broker-port", type=int, default=8099)
    sc.add_argument("-controller-port", type=int, default=9000)
    sc.set_defaults(fn=cmd_start_cluster)

    stc = sub.add_parser("StartController")
    stc.add_argument("-port", type=int, default=9000)
    stc.add_argument("-data-dir", required=True, dest="data_dir")
    stc.add_argument("-heartbeat-timeout", type=float, default=6.0, dest="heartbeat_timeout")
    stc.set_defaults(fn=cmd_start_controller)

    sts = sub.add_parser("StartServer")
    sts.add_argument("-controller", default="http://127.0.0.1:9000")
    sts.add_argument("-name", default="server0")
    sts.add_argument("-port", type=int, default=0)
    sts.add_argument("-data-dir", default=None, dest="data_dir")
    sts.set_defaults(fn=cmd_start_server)

    stb = sub.add_parser("StartBroker")
    stb.add_argument("-controller", default="http://127.0.0.1:9000")
    stb.add_argument("-name", default="broker0")
    stb.add_argument("-port", type=int, default=8099)
    stb.set_defaults(fn=cmd_start_broker)

    ssb = sub.add_parser("StartStreamBroker")
    ssb.add_argument("-port", type=int, default=0)
    ssb.add_argument("-log-dir", default=None, dest="log_dir")
    ssb.set_defaults(fn=cmd_start_stream_broker)

    cs = sub.add_parser("CreateSegment")
    cs.add_argument("-schema-file", required=True, dest="schema_file")
    cs.add_argument("-data-file", required=True, dest="data_file")
    cs.add_argument("-table", required=True)
    cs.add_argument("-segment-name", required=True, dest="segment_name")
    cs.add_argument("-out-dir", required=True, dest="out_dir")
    cs.add_argument("-startree", action="store_true")
    cs.set_defaults(fn=cmd_create_segment)

    bcs = sub.add_parser("BatchCreateSegments")
    bcs.add_argument("-schema-file", required=True, dest="schema_file")
    bcs.add_argument("-inputs", required=True, nargs="+", help="input files/globs (csv/jsonl/avro), one segment each")
    bcs.add_argument("-table", required=True)
    bcs.add_argument("-out-dir", required=True, dest="out_dir")
    bcs.add_argument("-controller", default=None, help="push built segments here when set")
    bcs.add_argument("-workers", type=int, default=0)
    bcs.add_argument(
        "-remote-workers",
        default=None,
        dest="remote_workers",
        help="comma-separated host:port build workers (StartBuildWorker); "
        "fans shards out over TCP instead of the local process pool",
    )
    bcs.add_argument("-startree", action="store_true")
    bcs.add_argument("-segment-name-prefix", default=None, dest="segment_name_prefix")
    bcs.set_defaults(fn=cmd_batch_create_segments)

    sbw = sub.add_parser(
        "StartBuildWorker",
        help="long-lived remote segment-build worker (Hadoop-mapper analog)",
    )
    sbw.add_argument("-host", default="0.0.0.0")
    sbw.add_argument("-port", type=int, default=9600)
    sbw.set_defaults(fn=cmd_start_build_worker)

    us = sub.add_parser("UploadSegment")
    us.add_argument("-controller", default="http://127.0.0.1:9000")
    us.add_argument("-table", required=True)
    us.add_argument("-segment-file", required=True, dest="segment_file")
    us.set_defaults(fn=cmd_upload_segment)

    asch = sub.add_parser("AddSchema")
    asch.add_argument("-controller", default="http://127.0.0.1:9000")
    asch.add_argument("-schema-file", required=True, dest="schema_file")
    asch.set_defaults(fn=cmd_add_schema)

    at = sub.add_parser("AddTable")
    at.add_argument("-controller", default="http://127.0.0.1:9000")
    at.add_argument("-config-file", required=True, dest="config_file")
    at.set_defaults(fn=cmd_add_table)

    pq = sub.add_parser("PostQuery")
    pq.add_argument("-broker", default="http://127.0.0.1:8099")
    pq.add_argument("-query", required=True)
    pq.add_argument("-trace", action="store_true")
    pq.set_defaults(fn=cmd_post_query)

    qr = sub.add_parser("QueryRunner")
    qr.add_argument("-broker", default="http://127.0.0.1:8099")
    qr.add_argument("-query-file", required=True, dest="query_file")
    qr.add_argument("-mode", choices=["singleThread", "multiThreads", "targetQPS"], default="singleThread")
    qr.add_argument("-rounds", type=int, default=1)
    qr.add_argument("-threads", type=int, default=4)
    qr.add_argument("-qps", type=float, default=10.0)
    qr.add_argument("-duration", type=float, default=10.0)
    qr.set_defaults(fn=cmd_query_runner)

    rb = sub.add_parser("RebalanceTable")
    rb.add_argument("-controller", default="http://127.0.0.1:9000")
    rb.add_argument("-table", required=True)
    rb.add_argument("-dry-run", action="store_true", dest="dry_run")
    rb.set_defaults(fn=cmd_rebalance_table)

    ate = sub.add_parser("AddTenant")
    ate.add_argument("-controller", default="http://127.0.0.1:9000")
    ate.add_argument("-name", required=True)
    ate.add_argument("-role", choices=["server", "broker"], default="server")
    ate.add_argument("-count", type=int, default=1)
    ate.set_defaults(fn=cmd_add_tenant)

    lt = sub.add_parser("ListTenants")
    lt.add_argument("-controller", default="http://127.0.0.1:9000")
    lt.set_defaults(fn=cmd_list_tenants)

    ss = sub.add_parser("ShowSegment")
    ss.add_argument("-segment-dir", required=True, dest="segment_dir")
    ss.set_defaults(fn=cmd_show_segment)

    cv = sub.add_parser("ConvertSegment")
    cv.add_argument("-segment-dir", required=True, dest="segment_dir")
    cv.add_argument("-format", choices=["csv", "jsonl"], default="jsonl")
    cv.add_argument("-out-file", required=True, dest="out_file")
    cv.set_defaults(fn=cmd_convert_segment)

    sst = sub.add_parser("ShowStarTree")
    sst.add_argument("-segment-dir", required=True, dest="segment_dir")
    sst.add_argument("-max-nodes", type=int, default=50, dest="max_nodes")
    sst.set_defaults(fn=cmd_show_star_tree)

    gd = sub.add_parser("GenerateData")
    gd.add_argument("-schema-file", required=True, dest="schema_file")
    gd.add_argument("-num-rows", type=int, default=1000, dest="num_rows")
    gd.add_argument("-seed", type=int, default=0)
    gd.add_argument("-out-file", required=True, dest="out_file")
    gd.set_defaults(fn=cmd_generate_data)

    args = p.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
