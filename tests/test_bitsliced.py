"""Bit-sliced filter/aggregate tier (engine/bitsliced.py, r17):
encode/decode round-trips, kernel vs numpy oracle, tier selection +
EXPLAIN honesty, env-tunable crossovers, and end-to-end bit-exactness
against the scan tier."""
import numpy as np
import pytest

from pinot_tpu.engine.packing import (
    bit_width,
    bitslice_decode,
    bitslice_encode,
    integral_dictionary_values,
)

jax = pytest.importorskip("jax")


# ------------------------------------------------------- encode/decode
def _roundtrip(values, width, n_rows=None):
    n = len(values) if n_rows is None else n_rows
    n_words = (max(n, 1) + 31) // 32
    planes = bitslice_encode(np.asarray(values), width, n_words)
    assert planes.shape == (width, n_words) and planes.dtype == np.uint32
    out = bitslice_decode(planes, len(values))
    np.testing.assert_array_equal(out, np.asarray(values, dtype=np.int64))
    return planes


def test_roundtrip_widths_and_word_edges():
    rng = np.random.default_rng(3)
    for width in (1, 2, 5, 12, 31, 32):
        hi = (1 << width) - 1 if width < 32 else (1 << 32) - 1
        # non-multiple-of-32 row counts cross word boundaries
        for n in (1, 31, 32, 33, 97):
            vals = rng.integers(0, hi, size=n, endpoint=True, dtype=np.uint64)
            _roundtrip(vals.astype(np.int64), width)


def test_roundtrip_extremes_width1_width32():
    _roundtrip([0, 1, 1, 0, 1], 1)
    hi = (1 << 32) - 1
    planes = _roundtrip([0, hi, 12345, hi - 1], 32)
    assert planes.shape[0] == 32


def test_encode_out_of_range_raises():
    with pytest.raises(ValueError):
        bitslice_encode(np.array([4]), width=2, n_words=1)
    with pytest.raises(ValueError):
        bitslice_encode(np.array([-1]), width=4, n_words=1)


def test_signed_values_roundtrip_via_offset():
    # signed domains are encoded as offsets from the per-segment min
    # (StagedColumn.bsiv_min) — the encoder itself is unsigned
    vals = np.array([-7, -3, 0, 12, 40], dtype=np.int64)
    off = vals - vals.min()
    width = bit_width(int(off.max()))
    planes = bitslice_encode(off, width, 1)
    back = bitslice_decode(planes, len(vals)) + vals.min()
    np.testing.assert_array_equal(back, vals)


def test_bit_width():
    assert bit_width(0) == 1
    assert bit_width(1) == 1
    assert bit_width(2) == 2
    assert bit_width(255) == 8
    assert bit_width(256) == 9


def test_integral_dictionary_values():
    ok = integral_dictionary_values(np.array([1.0, 50.0, 3.0]))
    assert ok is not None and ok.dtype == np.int64
    np.testing.assert_array_equal(ok, [1, 50, 3])
    assert integral_dictionary_values(np.array([1.5, 2.0])) is None
    assert integral_dictionary_values(np.array([np.nan, 1.0])) is None
    assert integral_dictionary_values(np.array([2.0**53, 1.0])) is None
    assert integral_dictionary_values(np.array(["a", "b"])) is None
    ints = integral_dictionary_values(np.array([3, 9], dtype=np.int32))
    np.testing.assert_array_equal(ints, [3, 9])


# ------------------------------------------------- kernel vs numpy oracle
def _encode_seg(ids, n_pad, width):
    return bitslice_encode(ids, width, n_pad // 32)


def test_kernel_matches_numpy_oracle():
    """Interval/points/negated-points leaves under an AND/OR tree with
    fused count/sum/min/max, across segments with UNEVEN doc counts
    (the validity mask must clip padding rows)."""
    from pinot_tpu.engine.kernel import make_packed_bitsliced_kernel

    rng = np.random.default_rng(11)
    n_pad, width, vwidth = 1024, 5, 6
    docs = [1000, 737]  # second segment ends mid-word
    ids = [rng.integers(0, 32, size=n_pad).astype(np.int64) for _ in docs]
    vals = [(i * 2) % 61 for i in ids]  # integral "values" per dict id

    spec = (
        (("interval", "c", width, 0), ("points", "c", width, 4)),
        ("or", ("leaf", 0), ("leaf", 1)),
        (("c", vwidth),),
        (("c", width, True), ("c", width, False)),
    )
    kern = make_packed_bitsliced_kernel(spec)

    segs = {
        "nd": np.array(docs, dtype=np.int32),
        "p:c": np.stack([_encode_seg(i, n_pad, width) for i in ids]),
        "v:c": np.stack([_encode_seg(v, n_pad, vwidth) for v in vals]),
    }
    q = {
        # kernel bounds are half-open [lo, hi): 3 <= id <= 9
        "bounds:0": np.array([[3, 10]] * 2, dtype=np.int32),
        "pts:1": np.array([[20, 25, -1, -1]] * 2, dtype=np.int32),
    }
    outs = kern(segs, q)

    for s, nd in enumerate(docs):
        i, v = ids[s][:nd], np.asarray(vals[s][:nd])
        m = ((i >= 3) & (i <= 9)) | np.isin(i, [20, 25])
        assert int(outs["count"][s]) == int(m.sum())
        got_sum = sum(
            (1 << b) * int(outs["psum:c"][s][b]) for b in range(vwidth)
        )
        assert got_sum == int(v[m].sum())
        if m.any():
            assert int(outs["ext:mx:c"][s]) == int(i[m].max())
            assert int(outs["ext:mn:c"][s]) == int(i[m].min())


def test_kernel_negated_points_and_full_interval():
    from pinot_tpu.engine.kernel import make_packed_bitsliced_kernel

    rng = np.random.default_rng(5)
    n_pad, width = 1024, 4
    nd = 990
    ids = rng.integers(0, 16, size=n_pad).astype(np.int64)
    spec = (
        (("points_none", "c", width, 2),),
        ("leaf", 0),
        (),
        (),
    )
    kern = make_packed_bitsliced_kernel(spec)
    segs = {
        "nd": np.array([nd], dtype=np.int32),
        "p:c": _encode_seg(ids, n_pad, width)[None],
    }
    q = {"pts:0": np.array([[7, 9]], dtype=np.int32)}
    outs = kern(segs, q)
    ref = int((~np.isin(ids[:nd], [7, 9])).sum())
    assert int(outs["count"][0]) == ref

    # hi >= 2^width must select every live row, not wrap
    spec2 = ((("interval", "c", width, 0),), ("leaf", 0), (), ())
    kern2 = make_packed_bitsliced_kernel(spec2)
    q2 = {"bounds:0": np.array([[0, 1 << width]], dtype=np.int32)}
    outs2 = kern2(segs, q2)
    assert int(outs2["count"][0]) == nd


# ----------------------------------------------- end-to-end + selection
@pytest.fixture(scope="module")
def lineitem():
    from pinot_tpu.engine.executor import QueryExecutor
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    segs = [
        synthetic_lineitem_segment(20000, seed=7, name="bsl0"),
        synthetic_lineitem_segment(15000, seed=11, name="bsl1"),
    ]
    return QueryExecutor(), segs


def _run(ex, segs, pql):
    from pinot_tpu.engine.reduce import reduce_to_response
    from pinot_tpu.pql import parse_pql, optimize_request

    req = optimize_request(parse_pql(pql))
    res = ex.execute(segs, req)
    return res, reduce_to_response(req, [res])


BIT_EXACT_CASES = [
    "SELECT sum(l_quantity), count(*), min(l_quantity), max(l_quantity), "
    "avg(l_quantity) FROM lineitem WHERE l_extendedprice BETWEEN 10000 AND 50000",
    "SELECT count(*), sum(l_quantity) FROM lineitem "
    "WHERE l_quantity IN (5, 10, 15) AND l_extendedprice > 30000",
    "SELECT count(*) FROM lineitem "
    "WHERE l_quantity NOT IN (1, 2) OR l_extendedprice < 20000",
    "SELECT min(l_extendedprice), max(l_extendedprice) FROM lineitem "
    "WHERE l_quantity = 25",
]


@pytest.mark.parametrize("pql", BIT_EXACT_CASES)
def test_bit_exact_vs_scan(lineitem, monkeypatch, pql):
    """The fused path must return byte-identical answers to the scan
    tier — fused SUM in exact integer arithmetic, extremes round-
    tripped through the device value dtype."""
    ex, segs = lineitem
    monkeypatch.setenv("PINOT_TPU_BITSLICED", "force")
    res, resp = _run(ex, segs, pql)
    assert res.cost.get("segmentsBitsliced") == len(segs), res.cost
    monkeypatch.setenv("PINOT_TPU_BITSLICED", "0")
    res2, resp2 = _run(ex, segs, pql)
    assert not res2.cost.get("segmentsBitsliced"), res2.cost
    assert [a.value for a in resp.aggregation_results] == [
        a.value for a in resp2.aggregation_results
    ]


def test_empty_match_and_disable(lineitem, monkeypatch):
    ex, segs = lineitem
    monkeypatch.setenv("PINOT_TPU_BITSLICED", "force")
    # a 0-match filter is legitimately postings turf; pin it off so the
    # empty-bitmap edge (garbage extreme ids, zero psum) is exercised.
    # Two leaves that each pass some value of every dictionary and no
    # row together: a leaf no value passes leaves the segment out of the
    # work altogether (the value pruner, PR 48)
    monkeypatch.setenv("PINOT_TPU_INVINDEX", "0")
    pql = (
        "SELECT count(*), sum(l_quantity), min(l_quantity) FROM lineitem "
        "WHERE l_quantity < 5 AND l_quantity > 45"
    )
    res, resp = _run(ex, segs, pql)
    assert res.cost.get("segmentsBitsliced") == len(segs)
    vals = [a.value for a in resp.aggregation_results]
    monkeypatch.setenv("PINOT_TPU_BITSLICED", "0")
    _, resp2 = _run(ex, segs, pql)
    assert vals == [a.value for a in resp2.aggregation_results]


def test_restaging_after_segment_set_change(lineitem, monkeypatch):
    """Staging-token participation: adding a segment (or reloading one
    under a fresh token) re-stages the bit planes and the answers
    track the new data — no stale-plane serving."""
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    ex, segs = lineitem
    monkeypatch.setenv("PINOT_TPU_BITSLICED", "force")
    pql = "SELECT count(*) FROM lineitem WHERE l_quantity > 10"
    res1, resp1 = _run(ex, segs[:1], pql)
    assert res1.cost.get("segmentsBitsliced") == 1
    # grow the serving set past the staged watermark
    res2, resp2 = _run(ex, segs, pql)
    assert res2.cost.get("segmentsBitsliced") == 2
    assert resp2.aggregation_results[0].value > resp1.aggregation_results[0].value
    # a RE-LOADED twin (same name, fresh staging token, different rows)
    # must not alias the old planes
    twin = synthetic_lineitem_segment(9000, seed=23, name="bsl0")
    res3, resp3 = _run(ex, [twin], pql)
    assert res3.cost.get("segmentsBitsliced") == 1
    monkeypatch.setenv("PINOT_TPU_BITSLICED", "0")
    _, ref3 = _run(ex, [twin], pql)
    assert resp3.aggregation_results[0].value == ref3.aggregation_results[0].value


def test_ineligible_shapes_fall_through(lineitem, monkeypatch):
    """force skips the cost model, never structural eligibility:
    group-by, selection, and unfiltered queries serve from the other
    tiers."""
    ex, segs = lineitem
    monkeypatch.setenv("PINOT_TPU_BITSLICED", "force")
    for pql in (
        "SELECT count(*) FROM lineitem",  # no filter
        "SELECT sum(l_quantity) FROM lineitem WHERE l_quantity > 5 "
        "GROUP BY l_returnflag",
        "SELECT l_quantity FROM lineitem WHERE l_quantity > 5 LIMIT 3",
    ):
        res, _ = _run(ex, segs, pql)
        assert not res.cost.get("segmentsBitsliced"), (pql, res.cost)


def test_cost_model_and_knobs(lineitem, monkeypatch):
    """Auto mode takes the tier exactly when the cost model picks it,
    and the PINOT_TPU_TIER_COST_* knobs move the crossover."""
    ex, segs = lineitem
    pql = (
        "SELECT sum(l_quantity), count(*) FROM lineitem "
        "WHERE l_extendedprice BETWEEN 10000 AND 60000"
    )
    monkeypatch.delenv("PINOT_TPU_BITSLICED", raising=False)
    res, _ = _run(ex, segs, pql)
    assert res.cost.get("segmentsBitsliced") == len(segs), res.cost
    # price the plane pass absurdly high: the model must hand the
    # query back to the scan
    monkeypatch.setenv("PINOT_TPU_TIER_COST_BSI_NS_PER_ROW_PER_PLANE", "1000")
    res2, _ = _run(ex, segs, pql)
    assert not res2.cost.get("segmentsBitsliced"), res2.cost


def test_tiercost_env_knobs_defaults_unchanged(monkeypatch):
    from pinot_tpu.engine import tiercost

    monkeypatch.delenv("PINOT_TPU_TIER_COST_POSTINGS_MATCH_FRACTION", raising=False)
    # the default reproduces the historical total_docs // 64 exactly
    for n in (0, 63, 64, 6400, 16_777_216):
        assert tiercost.postings_max_matches(n) == n // 64
    monkeypatch.setenv("PINOT_TPU_TIER_COST_POSTINGS_MATCH_FRACTION", "0.5")
    assert tiercost.postings_max_matches(100) == 50
    monkeypatch.setenv("PINOT_TPU_TIER_COST_BSI_MAX_PLANES", "3")
    assert tiercost.bsi_max_planes() == 3


def test_explain_reports_bitsliced_tier(monkeypatch):
    """EXPLAIN must say 'bitsliced' exactly when the executor would
    take it, with plane counts + fused-agg flags, and launch nothing."""
    from pinot_tpu.tools.cluster_harness import single_server_broker
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    segs = [synthetic_lineitem_segment(20000, seed=3, name="bsix0")]
    broker = single_server_broker("lineitem", segs)
    monkeypatch.delenv("PINOT_TPU_BITSLICED", raising=False)
    pql = (
        "EXPLAIN SELECT sum(l_quantity), count(*) FROM lineitem "
        "WHERE l_extendedprice BETWEEN 10000 AND 60000"
    )
    resp = broker.handle_pql(pql)
    assert not resp.exceptions, resp.exceptions
    node = resp.to_json()["explain"]["servers"][0]
    tiers = {s["segment"]: s for s in node["segments"]}
    seg = tiers["bsix0"]
    assert seg["tier"] == "bitsliced", seg
    assert seg["planes"] > 0 and seg["planeCounts"]
    assert any(a.startswith("sum") for a in seg["fusedAggs"])
    assert node["tierCounts"].get("segmentsBitsliced") == 1

    # flip the cost model off: EXPLAIN must agree with the executor
    monkeypatch.setenv("PINOT_TPU_BITSLICED", "0")
    resp2 = broker.handle_pql(pql)
    node2 = resp2.to_json()["explain"]["servers"][0]
    assert all(s["tier"] != "bitsliced" for s in node2["segments"])
    broker.local_servers[0].shutdown()


def test_batched_bsi_dispatches_match_serial(monkeypatch):
    """Lane micro-batching on the bit-sliced tier (r18): same-spec
    distinct-literal BSI queries queued on a blocked lane gather into
    one batched plane launch, and every member's payload is identical
    to the serial (no-lane) executor's — the counters prove real
    batches formed on the BSI path, not the scan tier."""
    import json
    import threading
    import time

    from pinot_tpu.tools.cluster_harness import single_server_broker
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    monkeypatch.setenv("PINOT_TPU_BITSLICED", "force")
    segs = [
        synthetic_lineitem_segment(8000, seed=7, name="bbat0"),
        synthetic_lineitem_segment(6000, seed=11, name="bbat1"),
    ]
    serial = single_server_broker("lineitem", segs, pipeline=False)
    pipelined = single_server_broker("lineitem", segs, pipeline=True)

    def payload(resp):
        return json.dumps(
            {
                k: v
                for k, v in resp.to_json().items()
                if k not in ("timeUsedMs", "requestId", "cost")
            },
            sort_keys=True,
        )

    queries = [
        "SELECT count(*), sum(l_quantity) FROM lineitem "
        f"WHERE l_extendedprice BETWEEN 10000 AND {t}"
        for t in (30000, 35000, 40000, 45000)
    ]
    # warm staging + plane compile so formation isn't skewed by a cold
    # compile holding the lane
    r = pipelined.handle_pql(queries[0])
    assert not r.exceptions, r.exceptions
    assert r.cost.get("segmentsBitsliced") == len(segs), r.cost

    server = pipelined.local_servers[0]
    gate = threading.Event()
    server.lane.submit(("blocker", time.monotonic()), lambda: gate.wait(15))
    time.sleep(0.05)
    results = {}
    errs = []

    def run(q):
        try:
            results[q] = pipelined.handle_pql(q)
        except Exception as e:  # pragma: no cover - fail loudly below
            errs.append((q, e))

    threads = [threading.Thread(target=run, args=(q,)) for q in queries]
    for t in threads:
        t.start()
    time.sleep(0.8)  # let every PREP finish and queue on the lane
    gate.set()
    for t in threads:
        t.join()
    assert not errs, errs[:1]

    stats = server.lane.stats()
    assert stats["batchLaunches"] >= 1, stats
    assert stats["batchedQueries"] >= 2, stats
    batched_hits = 0
    for q in queries:
        resp = results[q]
        assert not resp.exceptions, (q, resp.exceptions)
        # every member really served from the bit-sliced tier
        assert resp.cost.get("segmentsBitsliced") == len(segs), (q, resp.cost)
        assert payload(serial.handle_pql(q)) == payload(resp), q
        batched_hits += int(resp.cost.get("batchHits", 0))
    assert batched_hits >= 2  # the differential exercised real batches
    serial.local_servers[0].shutdown()
    pipelined.local_servers[0].shutdown()
