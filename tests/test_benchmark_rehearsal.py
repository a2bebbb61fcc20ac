"""Tier-1's rehearsal of the benchmark: every cell of ``BENCHMARK.json``
through ``benchmark/run.py``'s own entry, on the CPU, with ``--trace`` 0
and 1.

Each cell keeps its own configuration's ``env``, ``chips``,
``guarantees`` and traffic file; only ``segments`` and
``rows_per_segment`` are cut, and the zone tier's block with them, so
that a cut segment is still 128 blocks and the queries that ride the
zone tier on the chip (Q5, TPC-H Q6, Q15) ride it here.  So the mesh4 cell runs
``PINOT_TPU_MESH_SHAPE=1x4`` on conftest's virtual devices and the
audited cell runs with the shadow auditor at its default.  A span or a
counter that a listed ``per_layer`` reader needs and no longer finds
fails here, where the driver would say ``output_malformed`` after the
chip time is spent.  A run on the CPU prints counts and no time under a
metric's name (``benchmark/tests`` hold the benchmark's own arithmetic).
"""
import gc
import importlib.util
import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
SEGMENTS, ROWS_PER_SEGMENT = 4, 10_000  # one segment a chip of the 1x4 mesh
ZONE_BLOCK = 128  # 10,000 rows are staged as 16,384: 128 blocks, as 8,388,608 rows are in blocks of 65,536
# windows as short as the readers allow, to keep tier-1's wall-clock tests undisturbed.  The
# auditor takes every 64th device answer: after the warm-up and the rehearsal's 3 s at 30
# queries/s that is the window's 34th query, 1.1 s in, and its pass has to end inside the window
SECONDS, SECONDS_AUDITED = 1, 3
PROGRAM_SOURCES = ("program_span", "program_counter")


@pytest.fixture(scope="module")
def run():
    spec = importlib.util.spec_from_file_location("benchmark_run", os.path.join(BENCH, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def cut_manifest(tmp_path_factory) -> str:
    """The real manifest, each configuration's file copied with its two
    sizes cut and nothing else touched."""
    out = tmp_path_factory.mktemp("rehearsal")
    manifest = json.loads(json.dumps(MANIFEST))
    for entry in manifest["configs"]:
        config = json.load(open(os.path.join(ROOT, entry["file"])))
        config.update(segments=SEGMENTS, rows_per_segment=ROWS_PER_SEGMENT)
        path = out / os.path.basename(entry["file"])
        path.write_text(json.dumps(config))
        entry["file"] = str(path)  # absolute: run.py joins it to the checkout's root
    path = out / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def cell_config(cell: str) -> dict:
    name = next(w["config"] for w in MANIFEST["workloads"] if w["name"] == cell)
    return json.load(open(os.path.join(ROOT, next(c["file"] for c in MANIFEST["configs"] if c["name"] == name))))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_on_the_cpu(capsys, monkeypatch, run, cut_manifest, cell, trace):
    config = cell_config(cell)
    # the deployment's settings as run.py will set them, so that they go with the test
    for name in [k for k in os.environ if k.startswith("PINOT_TPU_")]:
        monkeypatch.delenv(name)
    for name, value in config.get("env", {}).items():
        monkeypatch.setenv(name, value)
    monkeypatch.setenv("PINOT_TPU_ZONE_BLOCK", str(ZONE_BLOCK))
    read, after = {}, {}  # every reader's answer, before run.py drops the times of a CPU run; the counters it read
    load_module = run.load_module

    def recording(path: str):
        module = load_module(path)
        if os.path.basename(os.path.dirname(path)) not in ("layer_metrics", "end_to_end"):
            return module
        name = os.path.basename(path)[:-3]

        def read_and_record(r):
            after.update(r.after)
            read[name] = module.read(r)
            return read[name]

        return types.SimpleNamespace(read=read_and_record)

    monkeypatch.setattr(run, "load_module", recording)
    audited = "audit" in config["guarantees"]
    seconds = SECONDS_AUDITED if audited else SECONDS
    try:
        rc = run.main(["--workload", cell, "--seed", str(2**31 + 31), "--seconds", str(seconds), "--trace", str(trace)],
                      allow_cpu=True, manifest_path=cut_manifest)
    finally:
        gc.unfreeze()  # run.py freezes what set-up left, for its window's sake
    assert rc == 0
    printed = capsys.readouterr().out
    out = json.loads(printed.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0, printed[-2000:]
    assert out["device"]["platform"] == "cpu"
    assert all(m["unit"] in ("count", "B/row") for m in out["metrics"].values())
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())
    # the deployment was the cell's own: the mesh of its file, the auditor on where it guarantees one
    assert after["server.gauge.mesh.devices"] == config["chips"]
    assert (after["server.meter.audit.offered"] > 0) == audited
    if not trace:
        assert out["metrics"]["hbm_bytes_per_row"]["value"] > 0
        return
    listed = [m for m in run.cell_metrics(MANIFEST, "per_layer", cell) if m["source"] in PROGRAM_SOURCES]
    assert len(listed) >= 15
    missing = [m["name"] for m in listed if read.get(m["name"]) is None]
    assert not missing, f"per_layer readers of {cell} that found nothing to read: {missing}"
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    # Q15 groups by 220,000 keys: on the CPU the scatter (on the chip the rows in key order for the
    # contraction, PR 38: both shares read 100 there), and a segment's state (occupancy and one sum) over
    # kernel._INPLACE_STATE_CELLS, so the zone tier's gathered view; the product under the sum in the kernel
    top_supplier = cell == "lineitem_topsupplier_closed"
    if "zone_inplace_share" in read:  # the other closed cells: Q5 and TPC-H Q6 read their blocks in place
        assert read["zone_inplace_share"] == (0.0 if top_supplier else 100.0)
    if top_supplier:
        assert read["groupby_contraction_share"] == 0.0 and read["groupby_sorted_share"] == 0.0
        assert read["expr_device_share"] == 100.0
        assert read["groups_kept_mean"] == 100 and 0 < read["groups_live_mean"] <= SEGMENTS * ROWS_PER_SEGMENT


def test_every_file_the_manifest_names_exists_and_every_reader_imports(run):
    assert os.path.isfile(os.path.join(ROOT, *MANIFEST["command"][1:]))
    for entry in MANIFEST["configs"]:
        config = json.load(open(os.path.join(ROOT, entry["file"])))
        assert config["name"] == entry["name"]
    for w in MANIFEST["workloads"]:
        assert any(c["name"] == w["config"] for c in MANIFEST["configs"]), w
        traffic = json.load(open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(BENCH, traffic.get("reference", "reference") + ".py"))
    for kind, folder in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        listed = {m["name"] for m in MANIFEST[kind]}
        found = {f[:-3] for f in os.listdir(os.path.join(BENCH, folder)) if f.endswith(".py")}
        assert listed <= found, sorted(listed - found)
        for name in sorted(found):  # a reader no cell lists is still the benchmark's, and has to import, as run.py imports it
            assert callable(run.load_module(os.path.join(BENCH, folder, name + ".py")).read), name
    for name in ("loadgen.py", "reference.py", "trace_reduce.py", "peaks.json"):
        assert os.path.isfile(os.path.join(BENCH, name))
