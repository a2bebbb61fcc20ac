"""Disaster-recovery plane (ISSUE 20): journaled metadata, torn-write
crash battery, deep-store scrubbing, and full cluster restore.

The reference survives a controller loss because metadata lives in
ZooKeeper's transaction log + snapshots and segments in the deep store.
Our analogs — the CRC-framed ``MetadataJournal`` behind the
``PropertyStore`` and the ``tools/backup.py`` archive path — must keep
the same promises:

- a crash at ANY byte offset of a journal append or record write is
  recoverable (torn tail truncated, never fatal);
- replay is idempotent across a crash between snapshot and log
  truncation;
- a garbled record file heals from the journal (or surfaces as a typed
  ``CorruptRecordError`` with the damage quarantined aside);
- a backup taken while serving restores byte-for-byte, with epoch
  fencing still rejecting pre-disaster zombie writers;
- a corrupt deep-store copy is detected and re-replicated from a live
  server (scrubber), and CRC-failing fetches report the store suspect.
"""
from __future__ import annotations

import json
import os
import shutil
import tarfile
import threading

import pytest

from pinot_tpu.controller.journal import MetadataJournal, apply_op
from pinot_tpu.controller.property_store import CorruptRecordError, PropertyStore

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


# ------------------------------------------------------------ journal


def test_journal_append_recover_roundtrip(tmp_path):
    j = MetadataJournal(str(tmp_path / "j"), fsync=False)
    assert j.recover() == {}
    j.append({"op": "put", "ns": "tables", "key": "t1", "record": {"a": 1}})
    j.append({"op": "put", "ns": "tables", "key": "t2", "record": {"b": 2}})
    j.append({"op": "delete", "ns": "tables", "key": "t1"})
    j.append({"op": "put", "ns": "cluster", "key": "epoch", "record": {"epoch": 3}})
    j.close()

    j2 = MetadataJournal(str(tmp_path / "j"), fsync=False)
    state = j2.recover()
    assert state == {
        "tables": {"t2": {"b": 2}},
        "cluster": {"epoch": {"epoch": 3}},
    }
    assert j2.seq == 4  # appends continue past the recovered seq
    assert j2.append({"op": "delete", "ns": "cluster", "key": "epoch"}) == 5


def test_journal_torn_tail_battery(tmp_path):
    """Truncate the log at EVERY byte offset: recovery must never raise
    and must yield exactly the ops whose frames survived whole."""
    j = MetadataJournal(str(tmp_path / "j"), fsync=False)
    frame_ends = []
    for i in range(5):
        j.append({"op": "put", "ns": "ns", "key": f"k{i}", "record": {"v": i}})
        j.close()  # flush the fd so the size below is the true frame end
        frame_ends.append(os.path.getsize(j.log_path))
    full = open(j.log_path, "rb").read()

    for cut in range(len(full) + 1):
        d = tmp_path / f"cut{cut}"
        jdir = d / "j"
        os.makedirs(jdir)
        with open(jdir / "journal.log", "wb") as f:
            f.write(full[:cut])
        state = MetadataJournal(str(jdir), fsync=False).recover()
        whole = sum(1 for end in frame_ends if end <= cut)
        assert state.get("ns", {}) == {
            f"k{i}": {"v": i} for i in range(whole)
        }, f"cut at {cut}"
        # the torn remainder was truncated off, so a SECOND recovery
        # sees a clean log ending at the last whole frame
        assert os.path.getsize(jdir / "journal.log") == (
            frame_ends[whole - 1] if whole else 0
        )


def test_journal_garbage_tail_and_bit_flip(tmp_path):
    """Non-truncation damage: flipped bytes inside the last frame, or
    pure garbage appended — replay stops at the last good frame."""
    j = MetadataJournal(str(tmp_path / "j"), fsync=False)
    j.append({"op": "put", "ns": "ns", "key": "good", "record": {"v": 1}})
    j.close()
    keep = os.path.getsize(j.log_path)
    j2 = MetadataJournal(str(tmp_path / "j"), fsync=False)
    j2.recover()
    j2.append({"op": "put", "ns": "ns", "key": "bad", "record": {"v": 2}})
    j2.close()
    with open(j2.log_path, "r+b") as f:  # flip a payload byte of frame 2
        f.seek(keep + 10)
        b = f.read(1)
        f.seek(keep + 10)
        f.write(bytes([b[0] ^ 0xFF]))
    state = MetadataJournal(str(tmp_path / "j"), fsync=False).recover()
    assert state == {"ns": {"good": {"v": 1}}}

    with open(tmp_path / "j" / "journal.log", "ab") as f:
        f.write(b"\xff" * 37)  # garbage tail (absurd length word)
    state = MetadataJournal(str(tmp_path / "j"), fsync=False).recover()
    assert state == {"ns": {"good": {"v": 1}}}


def test_journal_snapshot_replay_idempotent_across_crash(tmp_path):
    """Crash between the snapshot replace and the log truncate: the
    snapshot says seq N while the log still holds frames 1..N — replay
    must skip them (seq <= snapshot.seq), not double-apply."""
    j = MetadataJournal(str(tmp_path / "j"), fsync=False)
    for i in range(3):
        j.append({"op": "put", "ns": "ns", "key": f"k{i}", "record": {"v": i}})
    j.append({"op": "delete", "ns": "ns", "key": "k0"})
    j.close()
    log_bytes = open(j.log_path, "rb").read()
    j2 = MetadataJournal(str(tmp_path / "j"), fsync=False)
    state = j2.recover()
    j2.write_snapshot(state)
    # simulate the crash: the pre-snapshot log reappears in full
    with open(j2.log_path, "wb") as f:
        f.write(log_bytes)
    recovered = MetadataJournal(str(tmp_path / "j"), fsync=False).recover()
    assert recovered == state == {"ns": {"k1": {"v": 1}, "k2": {"v": 2}}}
    # delete of k0 replayed on top of a snapshot that already folded it
    # in would be a no-op; a REPLAYED put of k0 would be the bug
    assert "k0" not in recovered["ns"]


def test_journal_corrupt_snapshot_quarantined(tmp_path):
    events = []
    j = MetadataJournal(str(tmp_path / "j"), fsync=False, on_event=events.append)
    j.append({"op": "put", "ns": "ns", "key": "k", "record": {"v": 9}})
    j.close()
    with open(j.snapshot_path, "w") as f:
        f.write("{not json")
    state = MetadataJournal(
        str(tmp_path / "j"), fsync=False, on_event=events.append
    ).recover()
    assert state == {"ns": {"k": {"v": 9}}}  # journal alone recovers
    assert "corruptSnapshot" in events
    assert any(".corrupt." in fn for fn in os.listdir(tmp_path / "j"))


# ----------------------------------------------------- property store


def test_property_store_kill_restart_mid_write(tmp_path):
    """Crash-at-every-offset at the PropertyStore level: commit some
    puts, tear the journal tail at arbitrary points, reopen — every
    committed record must come back, reads must never crash."""
    d = str(tmp_path / "ps")
    ps = PropertyStore(d)
    for i in range(6):
        ps.put("tables", f"t{i}", {"i": i})
    ps.delete("tables", "t0")
    ps.close()
    log = os.path.join(d, ".journal", "journal.log")
    full_size = os.path.getsize(log)

    for cut in range(0, full_size + 1, max(1, full_size // 23)):
        d2 = str(tmp_path / f"ps_cut{cut}")
        shutil.copytree(d, d2)
        with open(os.path.join(d2, ".journal", "journal.log"), "r+b") as f:
            f.truncate(cut)
        ps2 = PropertyStore(d2)
        # mirror files survive the torn journal, so every committed
        # record is still readable whatever the cut
        for i in range(1, 6):
            assert ps2.get("tables", f"t{i}") == {"i": i}
        ps2.close()


def test_record_corruption_heals_from_journal(tmp_path):
    ps = PropertyStore(str(tmp_path / "ps"))
    ps.put("schemas", "s1", {"cols": [1, 2, 3]})
    path = ps._path("schemas", "s1")
    with open(path, "w") as f:
        f.write('{"cols": [1,')  # torn mirror write
    assert ps.get("schemas", "s1") == {"cols": [1, 2, 3]}  # healed
    assert json.load(open(path)) == {"cols": [1, 2, 3]}  # rewritten
    ns_dir = os.path.dirname(path)
    assert any(".corrupt." in fn for fn in os.listdir(ns_dir))  # quarantined
    assert ps.metrics.meter("durability.recordsHealed").count >= 1
    assert ps.metrics.meter("durability.corruptRecords").count >= 1
    # a DELETED mirror file also heals (restore path)
    os.unlink(path)
    assert ps.get("schemas", "s1") == {"cols": [1, 2, 3]}
    ps.close()


def test_unjournaled_corrupt_record_raises_typed_error(tmp_path):
    ps = PropertyStore(str(tmp_path / "ps"))
    ps.put("tables", "anchor", {"x": 1})  # materialize the ns dir
    rogue = os.path.join(os.path.dirname(ps._path("tables", "anchor")), "rogue.json")
    with open(rogue, "w") as f:
        f.write("not json at all")
    with pytest.raises(CorruptRecordError) as ei:
        ps.get("tables", "rogue")
    assert ei.value.namespace == "tables" and ei.value.key == "rogue"
    assert not os.path.exists(rogue)  # quarantined aside, not left in place
    ns_dir = os.path.dirname(rogue)
    assert any(fn.startswith("rogue.json.corrupt.") for fn in os.listdir(ns_dir))
    assert "rogue" not in ps.list_keys("tables")
    ps.close()


def test_snapshot_while_mutating_consistent(tmp_path):
    """snapshot_now racing a writer thread: a reopened store must see
    every record the writer committed, with no torn/partial state."""
    d = str(tmp_path / "ps")
    ps = PropertyStore(d)
    stop = threading.Event()
    written = []

    def writer():
        i = 0
        while not stop.is_set():
            ps.put("segments/t", f"seg{i}", {"n": i})
            written.append(i)
            i += 1

    t = threading.Thread(target=writer)
    t.start()
    try:
        for _ in range(12):
            ps.snapshot_now()
    finally:
        stop.set()
        t.join()
    ps.close()
    ps2 = PropertyStore(d)
    for i in written:
        assert ps2.get("segments/t", f"seg{i}") == {"n": i}
    ps2.close()


def test_epoch_claims_journaled_mirror_loss_survivable(tmp_path):
    """Wipe every mirror file (keep only the journal): a reopened store
    recovers records AND the epoch, so fencing still rejects the old
    incarnation — the restore-from-journal invariant."""
    from pinot_tpu.common.fencing import StaleEpochError

    d = str(tmp_path / "ps")
    ps_a = PropertyStore(d)
    assert ps_a.claim_epoch() == 1
    ps_a.put("tables", "t", {"kept": True})
    ps_a.snapshot_now()
    ps_a.put("tables", "t2", {"post-snapshot": True})
    # destroy every record mirror; only .journal survives
    for entry in os.listdir(d):
        if entry in (".journal", ".fence.lock"):
            continue
        full = os.path.join(d, entry)
        shutil.rmtree(full) if os.path.isdir(full) else os.unlink(full)

    ps_b = PropertyStore(d)
    assert ps_b.get("tables", "t") == {"kept": True}
    assert ps_b.get("tables", "t2") == {"post-snapshot": True}
    assert ps_b.stored_epoch() == 1
    assert ps_b.claim_epoch() == 2
    with pytest.raises(StaleEpochError):
        ps_a.put("tables", "zombie", {"x": 1})
    ps_a.close()
    ps_b.close()


# ---------------------------------------------------- backup/restore


def _populated_data_dir(root):
    from pinot_tpu.controller.store import SegmentStore
    from pinot_tpu.segment.format import write_segment
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    data_dir = os.path.join(root, "cluster")
    ps = PropertyStore(os.path.join(data_dir, "property_store"))
    ps.claim_epoch()
    ps.put("schemas", "s", {"dims": ["a"]})
    ps.put("tables", "t_OFFLINE", {"replication": 2})
    ps.put("idealstates", "t_OFFLINE", {"seg0": {"server0": "ONLINE"}})
    ps.put("segments/t_OFFLINE", "seg0", {"crc": 123})
    store = SegmentStore(os.path.join(data_dir, "segments"))
    seg = synthetic_lineitem_segment(200, seed=7, name="seg0")
    write_segment(seg, store.segment_dir("t_OFFLINE", "seg0"))
    return data_dir, ps, store


def test_backup_restore_roundtrip_equality(tmp_path):
    from pinot_tpu.tools.backup import create_backup, restore_backup

    data_dir, ps, store = _populated_data_dir(str(tmp_path))
    archive = str(tmp_path / "b.tar.gz")
    info = create_backup(data_dir, archive)
    assert info["segments"] == 1 and info["epoch"] == 1
    assert os.path.exists(archive)

    # restore into a SECOND data dir that has only the deep store
    # (archive + deep store alone rebuild the cluster)
    data_dir2 = str(tmp_path / "cluster2")
    shutil.copytree(
        os.path.join(data_dir, "segments"), os.path.join(data_dir2, "segments")
    )
    out = restore_backup(archive, data_dir2)
    assert out["restored"] and out["segmentsVerified"] == 1
    assert out["segmentsMissing"] == [] and out["segmentsCorrupt"] == []
    ps2 = PropertyStore(os.path.join(data_dir2, "property_store"))
    for ns, key in (
        ("schemas", "s"),
        ("tables", "t_OFFLINE"),
        ("idealstates", "t_OFFLINE"),
        ("segments/t_OFFLINE", "seg0"),
    ):
        assert ps2.get(ns, key) == ps.get(ns, key), (ns, key)
    assert ps2.stored_epoch() == 1  # fencing token restored
    ps.close()
    ps2.close()


def test_restore_refuses_nonempty_and_reports_damage(tmp_path):
    from pinot_tpu.segment.format import SEGMENT_FILE_NAME
    from pinot_tpu.tools.backup import create_backup, restore_backup

    data_dir, ps, store = _populated_data_dir(str(tmp_path))
    archive = str(tmp_path / "b.tar.gz")
    create_backup(data_dir, archive)
    ps.close()
    with pytest.raises(FileExistsError):
        restore_backup(archive, data_dir)  # live store present, no overwrite
    # damage the deep store, then restore with overwrite: damage is
    # REPORTED (scrubber's job to heal), never fatal
    seg_path = store.segment_file_path("t_OFFLINE", "seg0")
    with open(seg_path, "r+b") as f:
        f.seek(-8, os.SEEK_END)
        f.write(b"\x00" * 8)
    out = restore_backup(archive, data_dir, overwrite=True)
    assert out["segmentsCorrupt"] == ["t_OFFLINE/seg0"]
    os.unlink(seg_path)
    out = restore_backup(archive, data_dir, overwrite=True)
    assert out["segmentsMissing"] == ["t_OFFLINE/seg0"]
    assert SEGMENT_FILE_NAME  # silence linters about the unused import


def test_restore_rejects_traversal_archive(tmp_path):
    from pinot_tpu.tools.backup import restore_backup

    evil = str(tmp_path / "evil.tar.gz")
    payload = tmp_path / "x"
    payload.write_text("boom")
    with tarfile.open(evil, "w:gz") as tar:
        tar.add(str(payload), arcname="../../escape")
    with pytest.raises(ValueError, match="unsafe archive member"):
        restore_backup(evil, str(tmp_path / "out"))


# -------------------------------------------- scrubbing & suspects


class _NoTableResources:
    def tables(self):
        return []

    def get_ideal_state(self, table):
        return {}

    def get_segment_metadata(self, table, segment):
        return {}


def test_scrubber_budget_denied_requeues_suspect(tmp_path):
    from pinot_tpu.controller.managers import DeepStoreScrubber
    from pinot_tpu.utils.audit import SamplerBudget

    scrub = DeepStoreScrubber(
        _NoTableResources(), store=None, budget=SamplerBudget(per_s=0.0)
    )
    scrub.report_suspect("t", "seg0", source="server1")
    scrub.run_once()
    snap = scrub.snapshot()
    assert snap["budgetDenied"] == 1
    assert snap["copiesChecked"] == 0
    # the server-reported suspect was requeued, not dropped
    assert snap["suspectsPending"] == 1


def test_scrubber_detects_and_repairs_from_donor(tmp_path):
    """Unit twin of the harness scrub leg: seed rot into the store
    copy; the scrubber detects it and re-replicates verified bytes via
    ``copy_fn`` from a 'server' holding a good copy."""
    from pinot_tpu.controller.managers import DeepStoreScrubber
    from pinot_tpu.controller.store import SegmentStore
    from pinot_tpu.segment.format import SEGMENT_FILE_NAME, write_segment
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment
    from pinot_tpu.utils.audit import SamplerBudget

    store = SegmentStore(str(tmp_path / "segments"))
    seg = synthetic_lineitem_segment(300, seed=11, name="seg0")
    # stamp a verifiable byte-level claim (the builder/commit path does
    # this; synthetic segments skip it and would pass CRC trivially)
    seg.metadata.custom["dataCrc"] = True
    seg.metadata.crc = seg.compute_crc()
    write_segment(seg, store.segment_dir("t_OFFLINE", "seg0"))
    good_bytes = open(store.segment_file_path("t_OFFLINE", "seg0"), "rb").read()
    with open(store.segment_file_path("t_OFFLINE", "seg0"), "r+b") as f:
        f.seek(-16, os.SEEK_END)
        f.write(b"\xde\xad\xbe\xef" * 4)

    class _Resources(_NoTableResources):
        def tables(self):
            return ["t_OFFLINE"]

        def get_ideal_state(self, table):
            return {"seg0": {"server0": "ONLINE"}}

        def get_external_view(self, table):
            return {"seg0": {"server0": "ONLINE"}}

        def instances_snapshot(self):
            class _I:
                name, url, role, alive = "server0", "inproc://server0", "server", True

            return [_I()]

    scrub = DeepStoreScrubber(
        _Resources(),
        store,
        budget=SamplerBudget(per_s=1000.0, burst=100.0),
        copy_fn=lambda name, url, table, segment: good_bytes,
    )
    scrub.run_once()
    snap = scrub.snapshot()
    assert snap["corruptCopies"] == 1 and snap["repairs"] == 1, snap
    assert snap["evidence"][0]["repairedFrom"] == "server0"
    store.verify_copy("t_OFFLINE", "seg0")  # healed copy passes CRC
    assert SEGMENT_FILE_NAME


def test_fetch_failing_crc_reports_store_suspect(tmp_path):
    from pinot_tpu.segment.fetcher import SegmentFetcherFactory
    from pinot_tpu.segment.format import SegmentIntegrityError

    src = tmp_path / "rotten"
    src.write_bytes(b"this is not a segment file")
    fired = []
    with pytest.raises(SegmentIntegrityError):
        SegmentFetcherFactory().fetch(
            str(src),
            str(tmp_path / "dest.pnt"),
            expected_crc=42,
            suspect_cb=lambda uri, exc: fired.append((uri, exc)),
        )
    assert fired and fired[0][0] == str(src)
    assert isinstance(fired[0][1], SegmentIntegrityError)
    assert not os.path.exists(tmp_path / "dest.pnt")  # bad bytes not installed


# --------------------------------------------------- chaos twin (e2e)


def test_disaster_recovery_scenario_chaos_twin(tmp_path):
    """Tier-1 twin of ``--scenario disaster-recovery``: consistent
    online backup under load, seeded store-copy rot scrubbed + repaired
    from a live server, then the property store DESTROYED mid-load and
    the cluster restored from archive + deep store — byte-identical
    answers, drain flag + fencing preserved, realtime resumes from the
    committed offset with zero lost/duplicate rows, ZERO failed
    queries throughout."""
    from pinot_tpu.tools.cluster_harness import run_disaster_recovery_scenario

    res = run_disaster_recovery_scenario(
        window_s=0.3, data_dir=str(tmp_path)
    )
    assert res["failedQueries"] == 0, res
    assert res["restore"]["byteIdentical"]
    assert res["restore"]["drainFlagPreserved"]
    assert res["restore"]["fencingPreserved"]
    assert res["restore"]["rtCommittedPreserved"] and res["restore"]["rtResumed"]
    assert res["scrub"]["detected"] and res["scrub"]["repaired"]
    assert res["restore"]["restoreToFirstQuerySeconds"] < 30.0
