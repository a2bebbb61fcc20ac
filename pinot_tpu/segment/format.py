"""On-disk segment format: single file + index map (v3-style).

The reference's v3 format stores all indexes in one blob with an index
map (``core/segment/store/SingleFileIndexDirectory.java``); v2 bit-packs
forward indexes (``SegmentVersion.java:23-30``).  This format does both:

    [0:8]    magic  b"PNTPUSEG"
    [8:16]   uint64 little-endian header JSON length H
    [16:16+H] header JSON: segment metadata + index map
              (per-buffer: offset, length, codec, dtype, shape)
    [16+H:]  concatenated buffers

Buffer codecs:
  raw      — dtype bytes as-is
  bitpack  — fixed-bit packed dictIds (see ``bitpack.py``)
  strings  — utf-8, '\\x00'-separated sorted dictionary entries

Everything is mmap-friendly: buffers are loaded with np.frombuffer over
a single read (the PinotDataBuffer analog is the OS page cache + numpy
views; device staging copies straight into HBM).
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Tuple

import numpy as np

from pinot_tpu.common.schema import DataType
from pinot_tpu.segment.bitpack import bits_required, pack_bits, unpack_bits
from pinot_tpu.segment.dictionary import Dictionary
from pinot_tpu.segment.immutable import ColumnData, ImmutableSegment, SegmentMetadata

MAGIC = b"PNTPUSEG"

SEGMENT_FILE_NAME = "columns.pnt"  # analog of v3's columns.psf


class SegmentIntegrityError(RuntimeError):
    """A segment's bytes do not match their metadata CRC claim — a
    corrupt download or bit-rotted disk copy.  The load paths quarantine
    the copy and re-fetch from the controller's durable store instead of
    serving wrong data (SegmentFetcherAndLoader.java:84 semantics)."""


class SegmentStaleError(SegmentIntegrityError):
    """An internally-CONSISTENT copy whose CRC is simply a different
    version than the ideal state asked for (replication lag during a
    segment refresh).  Not corruption: no quarantine, no crcFailures —
    the load is retried on the next transition once the source catches
    up."""


def verify_segment_crc(segment: ImmutableSegment, source: str = "") -> None:
    """Recompute the column-data CRC and compare against the metadata
    claim.

    Only producers that actually computed a data CRC mark the claim
    verifiable (``custom["dataCrc"]``: segment/builder.py and the
    realtime commit conversion).  Synthetic (datagen) segments and consuming
    snapshots reuse the crc field as a cheap cache-identity token —
    those (and crc == 0) pass trivially: there is no byte-level claim to
    hold them to."""
    claimed = segment.metadata.crc
    if not claimed or not segment.metadata.custom.get("dataCrc"):
        return
    actual = segment.compute_crc()
    if actual != claimed:
        where = f" ({source})" if source else ""
        raise SegmentIntegrityError(
            f"segment {segment.segment_name!r}{where}: computed CRC {actual} != "
            f"metadata CRC {claimed} — corrupt copy"
        )


def write_segment(segment: ImmutableSegment, directory: str) -> str:
    """Write a segment directory: one data file (index map inside)."""
    os.makedirs(directory, exist_ok=True)
    buffers: List[bytes] = []
    index_map: Dict[str, Dict[str, Any]] = {}
    offset = 0

    def add(key: str, data: bytes, codec: str, **extra: Any) -> None:
        nonlocal offset
        index_map[key] = {"offset": offset, "length": len(data), "codec": codec, **extra}
        buffers.append(data)
        offset += len(data)

    for name, col in segment.columns.items():
        d = col.dictionary
        if d.is_string:
            blob = "\x00".join(d.values).encode("utf-8")
            add(f"{name}.dict", blob, "strings", count=len(d))
        else:
            arr = np.ascontiguousarray(d.values)
            add(f"{name}.dict", arr.tobytes(), "raw", dtype=str(arr.dtype), count=len(d))

        nbits = bits_required(max(d.cardinality, 1))
        if col.fwd is not None:
            add(
                f"{name}.fwd",
                pack_bits(col.fwd, nbits).tobytes(),
                "bitpack",
                nbits=nbits,
                count=int(col.fwd.size),
            )
        if col.mv_values is not None:
            add(
                f"{name}.mv",
                pack_bits(col.mv_values, nbits).tobytes(),
                "bitpack",
                nbits=nbits,
                count=int(col.mv_values.size),
            )
            off = np.ascontiguousarray(col.mv_offsets, dtype=np.int32)
            add(f"{name}.mvoff", off.tobytes(), "raw", dtype="int32", count=int(off.size))

    # zone maps: per-block dictId min/max per SV column, persisted at
    # build/write time so selective-query pruning (engine/zonemap.py)
    # never pays an O(n) first-query scan (the inverted-index artifact
    # of the reference's segment files, re-derived)
    from pinot_tpu.engine.zonemap import column_zones, zone_block_rows

    zblock = zone_block_rows()
    for name, col in segment.columns.items():
        if col.fwd is None or col.fwd.size <= zblock:
            continue
        z = column_zones(segment, name, zblock)  # single source of truth
        if z is None:
            continue
        zmin, zmax = (a.astype(np.int32) for a in z)
        add(f"{name}.zmin", zmin.tobytes(), "raw", dtype="int32", count=int(zmin.size))
        add(f"{name}.zmax", zmax.tobytes(), "raw", dtype="int32", count=int(zmax.size))

    star_tree = getattr(segment, "star_tree", None)
    star_header = None
    if star_tree is not None:
        add("__startree__.dims", np.ascontiguousarray(star_tree.dims).tobytes(), "raw",
            dtype=str(star_tree.dims.dtype), count=int(star_tree.dims.size))
        add("__startree__.sums", np.ascontiguousarray(star_tree.sums).tobytes(), "raw",
            dtype=str(star_tree.sums.dtype), count=int(star_tree.sums.size))
        add("__startree__.counts", np.ascontiguousarray(star_tree.counts).tobytes(), "raw",
            dtype=str(star_tree.counts.dtype), count=int(star_tree.counts.size))
        for hcol, regs in star_tree.hll_registers.items():
            add(f"__startree__.hll.{hcol}", np.ascontiguousarray(regs).tobytes(), "raw",
                dtype=str(regs.dtype), count=int(regs.size))
        star_header = {
            "splitOrder": star_tree.split_order,
            "metricColumns": star_tree.metric_columns,
            "maxLeafRecords": star_tree.max_leaf_records,
            "numRecords": star_tree.num_records,
            "hllColumns": list(star_tree.hll_columns),
            "root": star_tree.root.to_json(),
        }

    header = {
        "metadata": segment.metadata.to_json(),
        "indexMap": index_map,
        "zoneBlock": zblock,
    }
    if star_header is not None:
        header["starTree"] = star_header
    hdr = json.dumps(header).encode("utf-8")
    path = os.path.join(directory, SEGMENT_FILE_NAME)
    with open(path, "wb") as f:
        f.write(MAGIC)
        f.write(len(hdr).to_bytes(8, "little"))
        f.write(hdr)
        for b in buffers:
            f.write(b)
    return path


def _decode(entry: Dict[str, Any], blob: bytes) -> Any:
    codec = entry["codec"]
    if codec == "raw":
        return np.frombuffer(blob, dtype=np.dtype(entry["dtype"]), count=entry["count"]).copy()
    if codec == "bitpack":
        packed = np.frombuffer(blob, dtype=np.uint8)
        return unpack_bits(packed, entry["nbits"], entry["count"])
    if codec == "strings":
        if entry["count"] == 0:
            return []
        return blob.decode("utf-8").split("\x00")
    raise ValueError(f"unknown codec {codec}")


def read_segment(directory: str) -> ImmutableSegment:
    path = os.path.join(directory, SEGMENT_FILE_NAME) if os.path.isdir(directory) else directory
    with open(path, "rb") as f:
        data = f.read()
    if data[:8] != MAGIC:
        raise ValueError(f"{path}: not a pinot_tpu segment file")
    hlen = int.from_bytes(data[8:16], "little")
    header = json.loads(data[16 : 16 + hlen].decode("utf-8"))
    base = 16 + hlen
    index_map = header["indexMap"]
    metadata = SegmentMetadata.from_json(header["metadata"])

    def load(key: str) -> Any:
        e = index_map[key]
        blob = data[base + e["offset"] : base + e["offset"] + e["length"]]
        return _decode(e, blob)

    columns: Dict[str, ColumnData] = {}
    for name, cmeta in metadata.columns.items():
        dict_values = load(f"{name}.dict")
        dictionary = Dictionary(cmeta.data_type.stored_type, dict_values)
        col = ColumnData(metadata=cmeta, dictionary=dictionary)
        if f"{name}.fwd" in index_map:
            col.fwd = load(f"{name}.fwd")
        if f"{name}.mv" in index_map:
            col.mv_values = load(f"{name}.mv")
            col.mv_offsets = load(f"{name}.mvoff")
        columns[name] = col
    segment = ImmutableSegment(metadata=metadata, columns=columns)

    # preload persisted zone maps into the segment's zone cache
    zblock = header.get("zoneBlock")
    if zblock:
        cache = {}
        for name in metadata.columns:
            if f"{name}.zmin" in index_map:
                cache[(name, int(zblock))] = (
                    load(f"{name}.zmin").astype(np.int64),
                    load(f"{name}.zmax").astype(np.int64),
                )
        if cache:
            object.__setattr__(segment, "_zone_cache", cache)

    st = header.get("starTree")
    if st is not None:
        from pinot_tpu.startree.index import StarTreeIndex, StarTreeNode

        n_rec = st["numRecords"]
        k = len(st["splitOrder"])
        m = len(st["metricColumns"])
        hll_cols = list(st.get("hllColumns", []))
        segment.star_tree = StarTreeIndex(
            split_order=list(st["splitOrder"]),
            metric_columns=list(st["metricColumns"]),
            dims=load("__startree__.dims").reshape(n_rec, k),
            sums=load("__startree__.sums").reshape(n_rec, m),
            counts=load("__startree__.counts"),
            root=StarTreeNode.from_json(st["root"]),
            max_leaf_records=st["maxLeafRecords"],
            hll_columns=hll_cols,
            hll_registers={
                c: load(f"__startree__.hll.{c}").reshape(n_rec, -1) for c in hll_cols
            },
        )
    return segment
