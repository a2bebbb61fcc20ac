"""Tools tests: quickstarts, client library, query runner, admin
CreateSegment/ShowSegment, controller segment upload over HTTP."""
import json
import urllib.request

import pytest

from pinot_tpu.api.client import Connection, ConnectionFactory, PinotClientError
from pinot_tpu.broker.broker import BrokerHttpServer
from pinot_tpu.controller.controller import ControllerHttpServer
from pinot_tpu.segment.builder import build_segment
from pinot_tpu.segment.format import SEGMENT_FILE_NAME, write_segment
from pinot_tpu.tools.datagen import baseball_rows, baseball_schema, make_test_schema, random_rows
from pinot_tpu.tools.query_runner import QueryRunner
from pinot_tpu.tools.quickstart import run_offline_quickstart, run_realtime_quickstart


def test_offline_quickstart():
    cluster = run_offline_quickstart(num_rows=2000, num_segments=3, verbose=False)
    resp = cluster.query("SELECT count(*) FROM baseballStats")
    assert resp.num_docs_scanned == 2000
    resp = cluster.query("SELECT sum(runs) FROM baseballStats GROUP BY playerName TOP 5")
    assert len(resp.aggregation_results[0].group_by_result) == 5
    cluster.stop()


def test_offline_quickstart_startree():
    cluster = run_offline_quickstart(num_rows=2000, num_segments=2, startree=True, verbose=False)
    resp = cluster.query("SELECT sum(runs), count(*) FROM baseballStats")
    assert int(resp.aggregation_results[1].value) == 2000
    # star-tree answers from pre-agg rows, far fewer than 2000
    assert resp.num_docs_scanned < 1000
    cluster.stop()


def test_realtime_quickstart():
    cluster = run_realtime_quickstart(num_events=1200, verbose=False)
    resp = cluster.query("SELECT count(*) FROM meetupRsvp")
    assert resp.num_docs_scanned == 1200
    cluster.stop()


def test_client_library():
    cluster = run_offline_quickstart(num_rows=500, num_segments=1, http=True, verbose=False)
    try:
        conn = ConnectionFactory.from_host_list([f"http://127.0.0.1:{cluster.http.port}"])
        rg = conn.execute("SELECT count(*) FROM baseballStats")
        rs = rg.get_result_set(0)
        assert rs.get_int(0) == 500
        assert rg.execution_stats["numDocsScanned"] == 500

        rg = conn.execute("SELECT sum(runs) FROM baseballStats GROUP BY teamID TOP 3")
        rs = rg.get_result_set(0)
        assert rs.kind == "groupby"
        assert rs.get_row_count() == 3
        assert len(rs.get_group_key(0)) == 1

        rg = conn.execute("SELECT playerName, runs FROM baseballStats LIMIT 4")
        rs = rg.get_result_set(0)
        assert rs.kind == "selection"
        assert rs.get_row_count() == 4
        assert rs.get_column_names() == ["playerName", "runs"]

        stmt = conn.prepare_statement("SELECT count(*) FROM baseballStats WHERE teamID = ?")
        stmt.set_string(0, "BOS")
        rg2 = stmt.execute()
        assert rg2.get_result_set(0).get_int(0) > 0
    finally:
        cluster.stop()


def test_query_runner_modes():
    calls = []

    def fake_query(pql):
        calls.append(pql)

    runner = QueryRunner(fake_query)
    rep = runner.single_thread(["q1", "q2"], rounds=3)
    assert rep.num_queries == 6 and rep.qps > 0
    rep = runner.multi_threads(["q1", "q2", "q3"], num_threads=2, rounds=2)
    assert rep.num_queries == 6
    assert rep.to_json()["p99Ms"] >= 0


def test_admin_create_and_show_segment(tmp_path, capsys):
    from pinot_tpu.tools.admin import main

    schema = make_test_schema(with_mv=False)
    schema_file = tmp_path / "schema.json"
    schema_file.write_text(json.dumps(schema.to_json()))
    data_file = tmp_path / "data.jsonl"
    rows = random_rows(schema, 50, seed=1)
    data_file.write_text("\n".join(json.dumps(r) for r in rows))

    out_dir = tmp_path / "seg_out"
    main([
        "CreateSegment",
        "-schema-file", str(schema_file),
        "-data-file", str(data_file),
        "-table", "t",
        "-segment-name", "cli_seg",
        "-out-dir", str(out_dir),
    ])
    captured = capsys.readouterr()
    assert "50 docs" in captured.out

    main(["ShowSegment", "-segment-dir", str(out_dir)])
    captured = capsys.readouterr()
    assert '"segmentName": "cli_seg"' in captured.out


def test_http_segment_upload(tmp_path):
    from pinot_tpu.tools.cluster_harness import InProcessCluster

    cluster = InProcessCluster(num_servers=1, data_dir=str(tmp_path / "ctrl"))
    schema = make_test_schema(with_mv=False)
    physical = cluster.add_offline_table(schema)
    http = ControllerHttpServer(cluster.controller)
    http.start()
    try:
        seg = build_segment(schema, random_rows(schema, 120, seed=3), physical, "up1")
        seg_dir = tmp_path / "up1"
        write_segment(seg, str(seg_dir))
        data = (seg_dir / SEGMENT_FILE_NAME).read_bytes()
        req = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/segments/{physical}",
            data=data,
            headers={"Content-Type": "application/octet-stream"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert out["status"] == "ok" and out["servers"]
        assert cluster.query("SELECT count(*) FROM testTable").num_docs_scanned == 120
    finally:
        http.stop()
        cluster.stop()


def test_segment_converters_roundtrip(tmp_path):
    """Export a segment to CSV/JSONL and rebuild an identical segment
    from the export (the pinot-tools segment-converter contract)."""
    from pinot_tpu.common.schema import Schema
    from pinot_tpu.segment.readers import read_csv, read_jsonl
    from pinot_tpu.tools.converters import segment_to_csv, segment_to_jsonl

    schema = make_test_schema(with_mv=True)
    rows = random_rows(schema, 150, seed=5)
    seg = build_segment(schema, rows, "t_OFFLINE", "conv")

    jl = str(tmp_path / "out.jsonl")
    assert segment_to_jsonl(seg, jl) == 150
    back = read_jsonl(jl, schema)
    seg2 = build_segment(schema, back, "t_OFFLINE", "conv2")
    assert seg2.num_docs == seg.num_docs
    assert seg2.rows() == seg.rows()

    cv = str(tmp_path / "out.csv")
    assert segment_to_csv(seg, cv) == 150
    back_csv = read_csv(cv, schema)
    seg3 = build_segment(schema, back_csv, "t_OFFLINE", "conv3")
    assert seg3.rows() == seg.rows()


def test_star_tree_viewer(tmp_path):
    from pinot_tpu.startree.builder import StarTreeBuilderConfig
    from pinot_tpu.tools.converters import star_tree_summary

    schema = baseball_schema()
    rows = baseball_rows(500, seed=9)
    seg = build_segment(
        schema, rows, "bb_OFFLINE", "st1",
        startree_config=StarTreeBuilderConfig(max_leaf_records=50),
    )
    summary = star_tree_summary(seg)
    assert summary["hasStarTree"]
    assert summary["splitOrder"]
    assert summary["numAggRecords"] > 0
    assert summary["numStarNodes"] > 0
    assert summary["numLeaves"] > 0
    assert summary["nodes"][0]["path"] == "(root)"
    # a plain segment reports no star tree
    plain = build_segment(schema, rows, "bb_OFFLINE", "plain1")
    assert star_tree_summary(plain) == {"hasStarTree": False}


def test_admin_convert_and_generate(tmp_path, capsys):
    from pinot_tpu.segment.format import write_segment
    from pinot_tpu.tools.admin import main as admin_main

    schema = make_test_schema(with_mv=False)
    schema_file = tmp_path / "schema.json"
    schema_file.write_text(json.dumps(schema.to_json()))

    out_data = tmp_path / "gen.jsonl"
    admin_main([
        "GenerateData", "-schema-file", str(schema_file),
        "-num-rows", "120", "-out-file", str(out_data),
    ])
    assert len(out_data.read_text().splitlines()) == 120

    seg_dir = tmp_path / "seg"
    admin_main([
        "CreateSegment", "-schema-file", str(schema_file),
        "-data-file", str(out_data), "-table", "testTable_OFFLINE",
        "-segment-name", "g1", "-out-dir", str(seg_dir),
    ])
    out_csv = tmp_path / "export.csv"
    admin_main([
        "ConvertSegment", "-segment-dir", str(seg_dir),
        "-format", "csv", "-out-file", str(out_csv),
    ])
    assert "exported 120 rows" in capsys.readouterr().out
    assert len(out_csv.read_text().splitlines()) == 121  # header + rows


def test_hybrid_quickstart():
    """Offline history + realtime tail on ONE logical table: the time
    boundary federates so overlap rows count exactly once
    (HybridQuickstart.java analog)."""
    from pinot_tpu.tools.quickstart import run_hybrid_quickstart

    cluster = run_hybrid_quickstart(num_offline=600, num_realtime=300, verbose=False)
    resp = cluster.query("SELECT count(*) FROM meetupRsvp")
    assert not resp.exceptions
    # 600 offline + 300 realtime past the boundary; the 100-row overlap
    # ingested on the realtime side is excluded by the boundary filter
    assert resp.num_docs_scanned == 900
    resp = cluster.query("SELECT sum(rsvp_count) FROM meetupRsvp GROUP BY group_city TOP 3")
    assert not resp.exceptions and resp.to_json()["aggregationResults"][0]["groupByResult"]


