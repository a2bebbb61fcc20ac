#!/usr/bin/env python3
"""The quickest proof that pinot_tpu still serves on the chip.

One process that owns the chip stands up what ``admin StartCluster``
stands up (controller + one server + broker with its HTTP port), uploads
TPC-H lineitem through ``Controller.upload_segment`` (store write ->
server load with a real data CRC), then answers PQL over ``POST /query``
and checks every answer against plain numpy over the decoded columns.
A second phase ingests the meetupRsvp realtime table on the same server
so sealed and consuming segments are both queried on the device.

The answers being right is not enough: the engine heals a device failure
by answering from the host, so every device-tier reply must also show
``segmentsHost == 0`` and ``deviceMs > 0`` in its cost vector, and at
the end every ``heal.*`` meter, lane restart and shed must be zero.

    python chip_smoke.py                      # 16 x 8,388,608 rows, needs a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --allow-cpu \
        --segments 2 --rows-per-segment 20000 --realtime-events 5000 \
        --realtime-rows-per-segment 2000      # the same logic, for tests

Exits non-zero on the first phase that fails, and before any work when
the platform is not ``tpu``.  Set-up figures (generate, store+load,
staging, cold and warm ms per shape) are printed as a report, not as
metrics.  The last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

SEGMENTS = 16  # BASELINE.json config 3: TPC-H lineitem, 16 x 8,388,608 = 134,217,728 rows
ROWS_PER_SEGMENT = 8_388_608
REALTIME_EVENTS = 1_100_000  # 4 sealed segments + a consuming tail
REALTIME_ROWS_PER_SEGMENT = 250_000
SUM_RTOL = 1e-4  # float32 accumulation (tests/test_tpu_platform.py RTOL)
HLL_RTOL = 3 * 1.04 / 16.0  # 3 sigma of HLL at m = 2^8 registers (engine/config.py HLL_LOG2M)
DEVICE_TIERS = ("segmentsFullScan", "segmentsZonemap", "segmentsBitsliced")

Q1 = (
    "SELECT sum(l_quantity), sum(l_extendedprice), sum(l_discount), count(*) "
    "FROM lineitem WHERE l_shipdate <= '1998-09-02' "
    "GROUP BY l_returnflag, l_linestatus TOP 10"
)
Q6 = (
    "SELECT sum(l_extendedprice) FROM lineitem "
    "WHERE l_shipmode IN ('RAIL','FOB') AND "
    "l_receiptdate BETWEEN '1997-01-01' AND '1997-12-31' "
    "GROUP BY l_shipmode TOP 10"
)
HLL = "SELECT distinctcounthll(l_shipdate) FROM lineitem GROUP BY l_returnflag TOP 10"
FILTERED = "SELECT sum(l_quantity), count(*) FROM lineitem WHERE l_quantity > 25"
DISTINCT = "SELECT distinctcount(l_shipmode), percentile50(l_quantity) FROM lineitem"
SELECTION = (
    "SELECT l_extendedprice, l_quantity, l_shipmode FROM lineitem "
    "ORDER BY l_extendedprice DESC LIMIT 10"
)
LOOKUP_DATE = "1995-06-14"
LOOKUP = (
    "SELECT sum(l_extendedprice), count(*) FROM lineitem "
    f"WHERE l_shipdate = '{LOOKUP_DATE}'"
)


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def close(got: float, want: float, rtol: float) -> bool:
    return abs(got - want) <= rtol * max(1.0, abs(want))


# ---------------------------------------------------------------------------
# The plain reference: numpy over the segment's dictionaries and forward
# indexes, one segment at a time, accumulated in float64 / python ints.
# Numeric columns are decoded in full (``dictionary.values[fwd]``); a string
# predicate is evaluated on the dictionary's values and carried to the rows
# through ``fwd``, and group keys are decoded from their ids at the end, so
# no 8M-row string array is ever built.  Touches neither engine/kernel.py nor
# engine/host_fallback.py.
# ---------------------------------------------------------------------------


class LineitemReference:
    def __init__(self) -> None:
        self.rows = 0
        self.q1: dict = {}  # (returnflag, linestatus) -> [qty, price, disc, count]
        self.q6: dict = {}  # shipmode -> price
        self.dates_by_flag: dict = {}  # returnflag -> set of shipdates
        self.filtered = [0.0, 0]
        self.shipmodes: set = set()
        self.qty_hist: dict = {}  # quantity -> rows
        self.max_price = float("-inf")
        self.max_price_rows: set = set()  # (qty, shipmode) of rows at max_price
        self.lookup = [0.0, 0]

    def add(self, segment) -> None:
        import numpy as np

        def values(name):
            return np.asarray(segment.column(name).dictionary.values)

        def ids(name):
            return segment.column(name).fwd

        flags, statuses, modes, dates = (
            values(c) for c in ("l_returnflag", "l_linestatus", "l_shipmode", "l_shipdate")
        )
        receipts = values("l_receiptdate")
        qty = values("l_quantity")[ids("l_quantity")].astype(np.float64)
        price = values("l_extendedprice")[ids("l_extendedprice")].astype(np.float64)
        disc = values("l_discount")[ids("l_discount")].astype(np.float64)
        self.rows += len(qty)

        # Q1: WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag, l_linestatus
        m = (dates <= "1998-09-02")[ids("l_shipdate")]
        code = (ids("l_returnflag").astype(np.int64) * len(statuses) + ids("l_linestatus"))[m]
        size = len(flags) * len(statuses)
        sums = [np.bincount(code, weights=v[m], minlength=size) for v in (qty, price, disc)]
        counts = np.bincount(code, minlength=size)
        for c in np.nonzero(counts)[0]:
            key = (str(flags[c // len(statuses)]), str(statuses[c % len(statuses)]))
            acc = self.q1.setdefault(key, [0.0, 0.0, 0.0, 0])
            for i, v in enumerate(sums):
                acc[i] += float(v[c])
            acc[3] += int(counts[c])

        # Q6: WHERE l_shipmode IN (...) AND l_receiptdate BETWEEN ... GROUP BY l_shipmode
        m = np.isin(modes, ["RAIL", "FOB"])[ids("l_shipmode")] & (
            (receipts >= "1997-01-01") & (receipts <= "1997-12-31")
        )[ids("l_receiptdate")]
        by_mode = np.bincount(ids("l_shipmode")[m], weights=price[m], minlength=len(modes))
        seen = np.bincount(ids("l_shipmode")[m], minlength=len(modes))
        for c in np.nonzero(seen)[0]:
            self.q6[str(modes[c])] = self.q6.get(str(modes[c]), 0.0) + float(by_mode[c])

        # exact distinct l_shipdate per l_returnflag
        pair = ids("l_returnflag").astype(np.int64) * len(dates) + ids("l_shipdate")
        present = np.bincount(pair, minlength=len(flags) * len(dates)).reshape(len(flags), -1)
        for f in range(len(flags)):
            self.dates_by_flag.setdefault(str(flags[f]), set()).update(
                dates[np.nonzero(present[f])[0]].tolist()
            )

        m = qty > 25
        self.filtered[0] += float(qty[m].sum())
        self.filtered[1] += int(m.sum())

        self.shipmodes.update(modes[np.unique(ids("l_shipmode"))].tolist())
        hist = np.bincount(ids("l_quantity"), minlength=len(values("l_quantity")))
        for v, n in zip(values("l_quantity").tolist(), hist.tolist()):
            self.qty_hist[v] = self.qty_hist.get(v, 0) + n

        top = float(price.max())
        if top > self.max_price:
            self.max_price, self.max_price_rows = top, set()
        if top == self.max_price:
            at = price == top
            self.max_price_rows.update(
                zip(qty[at].tolist(), modes[ids("l_shipmode")[at]].tolist())
            )

        m = (dates == LOOKUP_DATE)[ids("l_shipdate")]
        self.lookup[0] += float(price[m].sum())
        self.lookup[1] += int(m.sum())

    def percentile50(self) -> float:
        # the reference formula: sorted[min(int(n * p / 100), n - 1)]
        n = sum(self.qty_hist.values())
        idx = min(int(n * 50 / 100.0), n - 1)
        acc = 0
        for v in sorted(self.qty_hist):
            acc += self.qty_hist[v]
            if acc > idx:
                return float(v)
        raise SmokeFailure("percentile50: empty histogram")


# ---------------------------------------------------------------------------
# HTTP client + per-reply checks
# ---------------------------------------------------------------------------


def post_query(url: str, pql: str) -> tuple:
    req = urllib.request.Request(
        url,
        data=json.dumps({"pql": pql}).encode(),
        headers={"Content-Type": "application/json"},
    )
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=900) as r:
        body = json.loads(r.read())
    return body, (time.perf_counter() - t0) * 1000.0


def check_reply(name: str, reply: dict, tiers: tuple) -> str:
    """Every reply is complete and clean, and ran where it was meant to:
    ``tiers`` are the cost-vector keys allowed to hold the segments."""
    check(not reply.get("exceptions"), f"{name}: exceptions {reply.get('exceptions')}")
    check(not reply.get("partialResponse"), f"{name}: partialResponse")
    cost = reply.get("cost") or {}
    queried = reply["numSegmentsQueried"]
    check(queried > 0, f"{name}: no segment queried")
    check(cost.get("segmentsHost", 0) == 0, f"{name}: segmentsHost={cost.get('segmentsHost')} cost={cost}")
    used = {k: int(cost.get(k, 0)) for k in tiers if cost.get(k, 0)}
    check(
        sum(used.values()) == queried,
        f"{name}: tiers {tiers} hold {used}, numSegmentsQueried={queried}, cost={cost}",
    )
    if tiers == ("segmentsPostings",):
        check(cost.get("deviceMs", 0) == 0, f"{name}: postings tier with deviceMs={cost.get('deviceMs')}")
    else:
        check(cost.get("deviceMs", 0) > 0, f"{name}: deviceMs={cost.get('deviceMs')} cost={cost}")
    return "+".join(sorted(k[len("segments"):] for k in used))


def agg(reply: dict, i: int) -> dict:
    return reply["aggregationResults"][i]


def groups(reply: dict, i: int) -> dict:
    return {tuple(g["group"]): float(g["value"]) for g in agg(reply, i)["groupByResult"]}


def check_sum_count(name: str, reply: dict, want_sum: float, want_count: int) -> None:
    """An ungrouped ``sum(x), count(*)`` reply: the sum within float32
    tolerance, the count and numDocsScanned exact."""
    got_sum, got_count = float(agg(reply, 0)["value"]), int(float(agg(reply, 1)["value"]))
    check(close(got_sum, want_sum, SUM_RTOL), f"{name} sum: {got_sum} != {want_sum}")
    check(got_count == want_count, f"{name} count: {got_count} != {want_count}")
    check(reply["numDocsScanned"] == want_count,
          f"{name} numDocsScanned: {reply['numDocsScanned']} != {want_count}")


def verify_q1(reply: dict, ref: LineitemReference) -> None:
    for i, rtol in ((0, SUM_RTOL), (1, SUM_RTOL), (2, SUM_RTOL), (3, 0.0)):
        got = groups(reply, i)
        check(set(got) == set(ref.q1), f"q1 agg {i}: keys {sorted(got)} != {sorted(ref.q1)}")
        for key, want in ref.q1.items():
            check(close(got[key], want[i], rtol), f"q1 agg {i} {key}: {got[key]} != {want[i]}")
    matched = sum(want[3] for want in ref.q1.values())
    check(reply["numDocsScanned"] == matched, f"q1 numDocsScanned: {reply['numDocsScanned']} != {matched}")


def verify_q6(reply: dict, ref: LineitemReference) -> None:
    got = groups(reply, 0)
    check(set(got) == {(k,) for k in ref.q6}, f"q6: keys {sorted(got)} != {sorted(ref.q6)}")
    for key, want in ref.q6.items():
        check(close(got[(key,)], want, SUM_RTOL), f"q6 {key}: {got[(key,)]} != {want}")


def verify_hll(reply: dict, ref: LineitemReference) -> None:
    got = groups(reply, 0)
    check(set(got) == {(k,) for k in ref.dates_by_flag}, f"hll: keys {sorted(got)}")
    for key, dates in ref.dates_by_flag.items():
        check(
            close(got[(key,)], len(dates), HLL_RTOL),
            f"hll {key}: estimate {got[(key,)]} vs exact {len(dates)}",
        )


def verify_filtered(reply: dict, ref: LineitemReference) -> None:
    check_sum_count("filtered", reply, *ref.filtered)


def verify_distinct(reply: dict, ref: LineitemReference) -> None:
    check(int(float(agg(reply, 0)["value"])) == len(ref.shipmodes),
          f"distinctcount: {agg(reply, 0)['value']} != {len(ref.shipmodes)}")
    check(float(agg(reply, 1)["value"]) == ref.percentile50(),
          f"percentile50: {agg(reply, 1)['value']} != {ref.percentile50()}")


def verify_selection(reply: dict, ref: LineitemReference) -> None:
    sel = reply["selectionResults"]
    check(sel["columns"] == ["l_extendedprice", "l_quantity", "l_shipmode"], f"selection: {sel['columns']}")
    check(len(sel["results"]) == 10, f"selection: {len(sel['results'])} rows")
    # the price dictionary has 16,384 values, so at any real size the ten
    # best rows all carry the maximum; which of the tied rows come back is
    # free, but each must exist
    check(len(ref.max_price_rows) >= 1, "selection: reference saw no row")
    prices = [float(r[0]) for r in sel["results"]]
    check(prices[0] == ref.max_price, f"selection: top price {prices[0]} != max {ref.max_price}")
    check(prices == sorted(prices, reverse=True), f"selection: not descending {prices}")
    for row in sel["results"]:
        if float(row[0]) == ref.max_price:
            check((float(row[1]), row[2]) in ref.max_price_rows, f"selection: no such row {row}")


def verify_lookup(reply: dict, ref: LineitemReference) -> None:
    check_sum_count("lookup", reply, *ref.lookup)


LINEITEM_QUERIES = (
    # name, pql, verify, cost-vector tiers allowed to hold the segments
    ("q1", Q1, verify_q1, ("segmentsFullScan",)),
    ("q6", Q6, verify_q6, ("segmentsFullScan", "segmentsZonemap")),
    ("hll_groupby", HLL, verify_hll, ("segmentsFullScan",)),
    ("filtered_sum", FILTERED, verify_filtered, ("segmentsBitsliced", "segmentsFullScan")),
    ("distinct_percentile", DISTINCT, verify_distinct, ("segmentsFullScan",)),
    ("selection_topn", SELECTION, verify_selection, ("segmentsFullScan",)),
    ("point_lookup", LOOKUP, verify_lookup, ("segmentsPostings",)),
)


# where a first call's time goes, from the server's own phase timers:
# staging (encode + H2D), laneDispatch (trace + compile, or cache load, +
# first launch), and the whole of the two tiers that keep their own clock
FIRST_CALL_PHASES = ("staging", "laneDispatch", "bitslicedPath", "indexPath")


def phase_totals(server) -> dict:
    return {p: server.metrics.timer(f"phase.{p}").total_ms for p in FIRST_CALL_PHASES}


def run_queries(url: str, server, queries, ref, report: dict) -> None:
    for name, pql, verify, tiers in queries:
        before = phase_totals(server)
        cold, cold_ms = post_query(url, pql)
        first = {p: round(v - before[p]) for p, v in phase_totals(server).items() if v > before[p]}
        tier = check_reply(name, cold, tiers)
        verify(cold, ref)
        warm, warm_ms = post_query(url, pql)
        check(check_reply(name, warm, tiers) == tier, f"{name}: tier changed between calls")
        verify(warm, ref)
        report["queries"][name] = {
            "tier": tier,
            "coldMs": round(cold_ms, 1),
            "coldPhasesMs": first,
            "warmMs": round(warm_ms, 1),
            "deviceMsWarm": warm.get("cost", {}).get("deviceMs", 0),
        }
        print(f"# {name}: tier={tier} cold={cold_ms:.0f}ms {first} warm={warm_ms:.1f}ms", flush=True)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def lineitem_phase(cluster, url: str, args, report: dict) -> None:
    from pinot_tpu.tools.datagen import lineitem_schema, synthetic_lineitem_segment

    physical = cluster.add_offline_table(lineitem_schema())
    ref = LineitemReference()
    gen_s = ref_s = load_s = 0.0
    for i in range(args.segments):
        t0 = time.perf_counter()
        seg = synthetic_lineitem_segment(
            args.rows_per_segment, seed=args.seed * 1000 + i, name=f"li{i}"
        )
        # a real data CRC, so the load path's verify_segment_crc has a
        # byte-level claim to hold the stored copy to
        seg.metadata.crc = seg.compute_crc()
        seg.metadata.custom["dataCrc"] = True
        t1 = time.perf_counter()
        ref.add(seg)
        t2 = time.perf_counter()
        cluster.upload(physical, seg)  # store write -> server load
        t3 = time.perf_counter()
        gen_s, ref_s, load_s = gen_s + t1 - t0, ref_s + t2 - t1, load_s + t3 - t2
        del seg
    server = cluster.servers[0]
    loaded = server.data_manager.table(physical)
    check(loaded is not None and len(loaded.segment_names()) == args.segments,
          f"server loaded {loaded and len(loaded.segment_names())} of {args.segments} segments")
    snap = server.metrics.snapshot()
    check(snap["meters"]["crcFailures"]["count"] == 0, "crcFailures during load")
    report["lineitem"] = {
        "rows": ref.rows,
        "segments": args.segments,
        "rowsPerSegment": args.rows_per_segment,
        "generateS": round(gen_s, 2),
        "referenceS": round(ref_s, 2),
        "storeLoadS": round(load_s, 2),
    }
    print(f"# lineitem: {ref.rows:,} rows in {args.segments} segments; generate "
          f"{gen_s:.1f}s, numpy reference {ref_s:.1f}s, store+load {load_s:.1f}s", flush=True)
    run_queries(url, server, LINEITEM_QUERIES, ref, report)


def realtime_phase(cluster, url: str, args, report: dict) -> None:
    import numpy as np

    from pinot_tpu.realtime.stream import MemoryStreamProvider
    from pinot_tpu.tools.quickstart import drain_stream, meetup_schema

    n = args.realtime_events
    rng = np.random.default_rng(args.seed)
    cities = np.array(["sf", "nyc", "seattle", "austin", "chicago"])
    city = cities[rng.integers(0, len(cities), n)]
    event = rng.integers(0, 8, n)
    venue = rng.integers(0, 20, n)
    rsvp = rng.integers(1, 6, n)
    now = int(time.time() * 1000)

    stream = MemoryStreamProvider(num_partitions=1)
    physical = cluster.add_realtime_table(
        meetup_schema(), stream, rows_per_segment=args.realtime_rows_per_segment
    )
    t0 = time.perf_counter()
    for i in range(n):
        stream.produce(
            {
                "venue_name": f"venue{venue[i]}",
                "event_name": f"event{event[i]}",
                "group_city": str(city[i]),
                "rsvp_count": int(rsvp[i]),
                "mtime": now + i,
            }
        )
    sealed = drain_stream(cluster, physical, max_rows=50_000)
    ingest_s = time.perf_counter() - t0
    check(sealed == n // args.realtime_rows_per_segment,
          f"realtime: sealed {sealed} segments of {n // args.realtime_rows_per_segment}")
    consuming = n - sealed * args.realtime_rows_per_segment
    check(consuming > 0, "realtime: no consuming tail; pick events not divisible by rows per segment")
    report["realtime"] = {
        "events": n, "sealedSegments": sealed, "consumingRows": consuming,
        "ingestS": round(ingest_s, 2),
    }
    print(f"# meetupRsvp: {n:,} events, {sealed} sealed segments + {consuming:,} consuming rows, "
          f"ingest {ingest_s:.1f}s", flush=True)

    def verify_count(reply, _):
        check(int(float(agg(reply, 0)["value"])) == n, f"rt count: {agg(reply, 0)['value']} != {n}")
        check(reply["totalDocs"] == n, f"rt totalDocs: {reply['totalDocs']} != {n}")

    def verify_city(reply, _):
        got = groups(reply, 0)
        want = {(str(c),): int(rsvp[city == c].sum()) for c in cities}
        check({k: int(v) for k, v in got.items()} == want, f"rt sum by city: {got} != {want}")

    def verify_event(reply, _):
        got = groups(reply, 0)
        want = {(f"event{e}",): int((event == e).sum()) for e in range(8)}
        check({k: int(v) for k, v in got.items()} == want, f"rt count by event: {got} != {want}")

    # sum(rsvp_count) <= 5.5M < 2^24, so float32 holds every sum exactly
    run_queries(
        url,
        cluster.servers[0],
        (
            ("rt_count", "SELECT count(*) FROM meetupRsvp WHERE rsvp_count >= 1",
             verify_count, DEVICE_TIERS),
            ("rt_sum_by_city", "SELECT sum(rsvp_count) FROM meetupRsvp GROUP BY group_city TOP 10",
             verify_city, DEVICE_TIERS),
            ("rt_count_by_event", "SELECT count(*) FROM meetupRsvp GROUP BY event_name TOP 10",
             verify_event, DEVICE_TIERS),
        ),
        None,
        report,
    )


def final_checks(cluster, report: dict) -> None:
    """The chip did the work: nothing healed, restarted, shed or fell back."""
    import jax

    server = cluster.servers[0]
    snap = server.metrics.snapshot()
    meters = {k: v["count"] for k, v in snap["meters"].items()}
    heal = {k: v for k, v in meters.items() if k.startswith("heal.")}
    heal.setdefault("heal.bitslicedFallbacks", 0)  # registered on its first mark
    heal["poisonedPlans"] = server.executor.healing_stats()["poisonedPlans"]
    zeros = dict(heal)
    for name in ("lane.restarts", "lane.deviceFailures", "lane.shed", "queriesShed", "crcFailures"):
        zeros[name] = meters[name]
    report["zeroMeters"] = zeros
    staged = int(snap["gauges"]["hbm.stagedBytes"])
    timers = snap["timers"]
    report["stagedBytes"] = staged
    report["stagingS"] = round(
        timers["phase.staging"]["count"] * timers["phase.staging"]["meanMs"] / 1000.0, 2
    )
    report["compile"] = {
        k: meters[f"compile.{k}"] for k in ("cold", "warm", "persistentHit", "persistentMiss")
    }
    report["compile"]["cacheDir"] = server.lane.persistent_cache_dir if server.lane else None
    per_device = [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()]
    report["deviceBytesInUse"] = per_device
    print(f"# staged {staged:,} bytes; staging {report['stagingS']}s; compile {report['compile']}", flush=True)
    print(f"# zero meters: {zeros}", flush=True)
    bad = {k: v for k, v in zeros.items() if v}
    check(not bad, f"the device path healed, restarted or shed: {bad}")
    check(staged > 0, "hbm.stagedBytes == 0: nothing was staged on the device")
    mesh_devices = int(snap["gauges"]["mesh.devices"])
    report["meshDevices"] = mesh_devices
    if mesh_devices > 1 and jax.devices()[0].platform == "tpu":
        # a mesh placement shards the segment axis: every chip holds its
        # share of the staged table, none holds it all (the CPU keeps no
        # per-device memory_stats to check this against)
        check(all(b is not None for b in per_device), f"no memory_stats: {per_device}")
        check(min(per_device) * 2 > max(per_device),
              f"staged bytes are not spread over the mesh: {per_device}")


def device_report(allow_cpu: bool) -> dict:
    import importlib.metadata as md

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not allow_cpu:
        print(f"chip_smoke: platform is {dev.platform!r}, need 'tpu' "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})", file=sys.stderr)
        sys.exit(2)
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    stats = dev.memory_stats() or {}
    info = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "versions": versions,
        "hbmLimitBytes": stats.get("bytes_limit"),
    }
    return info


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--segments", type=int, default=SEGMENTS,
                   help="cut scale here only; rows per segment and the column set stay")
    p.add_argument("--rows-per-segment", type=int, default=ROWS_PER_SEGMENT)
    p.add_argument("--realtime-events", type=int, default=REALTIME_EVENTS)
    p.add_argument("--realtime-rows-per-segment", type=int, default=REALTIME_ROWS_PER_SEGMENT)
    p.add_argument("--allow-cpu", action="store_true",
                   help="tests only: run the whole logic on whatever platform JAX has")
    args = p.parse_args(argv)

    t_start = time.perf_counter()
    info = device_report(args.allow_cpu)

    import tempfile

    from pinot_tpu.segment import native
    from pinot_tpu.tools.cluster_harness import InProcessCluster

    print(f"# device: {info}", flush=True)
    report: dict = {"device": info, "seed": args.seed, "queries": {}}
    report["codec"] = "native" if native.available() else "numpy"
    print(f"# bit-pack codec: {report['codec']}", flush=True)
    if args.segments != SEGMENTS or args.rows_per_segment != ROWS_PER_SEGMENT:
        print(f"# CUT: {args.segments} x {args.rows_per_segment:,} rows "
              f"(full size {SEGMENTS} x {ROWS_PER_SEGMENT:,})", flush=True)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as data_dir:
        # what `admin StartCluster` starts; the timeout covers a cold compile
        cluster = InProcessCluster(num_servers=1, data_dir=data_dir, http=True, timeout_ms=900_000.0)
        try:
            url = f"http://{cluster.http.host}:{cluster.http.port}/query"
            lineitem_phase(cluster, url, args, report)
            realtime_phase(cluster, url, args, report)
            final_checks(cluster, report)
        finally:
            cluster.stop()
            for server in cluster.servers:
                server.shutdown()
    report["totalS"] = round(time.perf_counter() - t_start, 1)
    print("# report: " + json.dumps(report, sort_keys=True), flush=True)
    print(json.dumps({"ok": True, "device": {k: info[k] for k in ("platform", "kind", "count")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
