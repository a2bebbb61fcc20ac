"""HyperLogLog sketch shared by the TPU engine and the scan oracle.

The reference uses clearspring's HyperLogLog with ``log2m = 8``
(pinot-core ``startree/hll/HllConstants.java`` DEFAULT_LOG2M) for
``distinctcounthll`` / ``fasthll``.  Here the sketch is a plain
``uint8[m]`` register array — a representation that maps directly onto
TPU ops: per-row (bucket, rho) pairs are precomputed per dictionary
entry host-side, the device does a scatter-max into registers, and
cross-segment / cross-chip merge is an elementwise ``maximum`` (instead
of the reference's Java-serialized sketch objects,
``DataTableCustomSerDe.java:49``).

Hashing is deterministic (NOT Python's salted ``hash()``), so oracle and
engine agree bit-for-bit, and has two branches by the value's kind:

- an integral value (a Python or numpy integer that fits 64 signed bits, or
  a float that holds one: 5.0 and 5 hash alike, so INT, LONG and FLOAT
  columns agree): the splitmix64 finalizer of its two's-complement 64 bits,
  ``x += 0x9E3779B97F4A7C15; x ^= x >> 30; x *= 0xBF58476D1CE4E5B9;
  x ^= x >> 27; x *= 0x94D049BB133111EB; x ^= x >> 31`` (mod 2^64), which
  numpy does over a whole dictionary at once (``hash64_integers``: a
  dictionary of millions of ids is hashed in a fraction of a second where
  a call an entry took two microseconds each);
- anything else (a string, a float with a fraction): ``blake2b`` of the
  value's ``repr``, 8 bytes little-endian, one call a value.

The register index is the hash's low ``log2m`` bits, the rank the trailing
zeros of the other ``64 - log2m`` bits plus one (``64 - log2m + 1`` where
they are all zero).
"""
from __future__ import annotations

import functools
import hashlib
import math
import struct
from typing import Any, Iterable

import numpy as np

DEFAULT_LOG2M = 8  # HllConstants.java DEFAULT_LOG2M
M = 1 << DEFAULT_LOG2M
_MASK64 = (1 << 64) - 1


def hash64_integers(values: np.ndarray) -> np.ndarray:
    """``value_hash64`` of every entry of an integer array, as uint64:
    the splitmix64 finalizer of the values' two's-complement 64 bits."""
    x = np.asarray(values).astype(np.int64).view(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def value_hash64(value: Any) -> int:
    """Deterministic 64-bit hash of an ingest value."""
    if isinstance(value, (float, np.floating)) and float(value).is_integer():
        # Hash 5.0 and 5 identically so INT/LONG/FLOAT columns agree.
        value = int(value)
    if isinstance(value, (int, np.integer)) and not isinstance(value, (bool, np.bool_)) and -(1 << 63) <= value < 1 << 63:
        x = (int(value) + 0x9E3779B97F4A7C15) & _MASK64
        x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
        x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
        return x ^ (x >> 31)
    data = repr(value).encode("utf-8")
    return struct.unpack("<Q", hashlib.blake2b(data, digest_size=8).digest())[0]


def bucket_and_rho(h: int, log2m: int = DEFAULT_LOG2M) -> tuple:
    """Split a 64-bit hash into (register index, rank of first set bit)."""
    m = 1 << log2m
    bucket = h & (m - 1)
    rest = h >> log2m
    # rho = position of least-significant 1 bit in the remaining bits + 1
    width = 64 - log2m
    if rest == 0:
        rho = width + 1
    else:
        rho = (rest & -rest).bit_length()
    return bucket, rho


def buckets_and_rhos(hashes: np.ndarray, log2m: int = DEFAULT_LOG2M) -> tuple:
    """``bucket_and_rho`` of every entry of a uint64 array, as two uint8
    arrays."""
    hashes = np.asarray(hashes, dtype=np.uint64)
    bucket = (hashes & np.uint64((1 << log2m) - 1)).astype(np.uint8 if log2m <= 8 else np.uint16)
    rest = hashes >> np.uint64(log2m)
    lowest = rest & (~rest + np.uint64(1))  # a power of two (or 0): exact in float64
    rho = np.where(rest == 0, 64 - log2m + 1, np.log2(np.maximum(lowest, 1).astype(np.float64)) + 1)
    return bucket, rho.astype(np.uint8)


def registers_from_values(values: Iterable[Any], log2m: int = DEFAULT_LOG2M) -> np.ndarray:
    m = 1 << log2m
    regs = np.zeros(m, dtype=np.uint8)
    if isinstance(values, np.ndarray) and values.dtype.kind in "iu":
        b, r = buckets_and_rhos(hash64_integers(values), log2m)
        np.maximum.at(regs, b, r)
        return regs
    for v in values:
        b, r = bucket_and_rho(value_hash64(v), log2m)
        if r > regs[b]:
            regs[b] = r
    return regs


def _alpha(m: int) -> float:
    if m == 16:
        return 0.673
    if m == 32:
        return 0.697
    if m == 64:
        return 0.709
    return 0.7213 / (1.0 + 1.079 / m)


_POW2_NEG = np.ldexp(1.0, -np.arange(256))  # a rank is at most 64 - log2m + 1


@functools.lru_cache(maxsize=None)
def _linear_counting(m: int) -> np.ndarray:
    """``m ln(m / V)`` by the count ``V`` of empty registers, 0 to m."""
    return np.array([0.0] + [m * math.log(m / float(z)) for z in range(1, m + 1)])


def estimate_from_registers(regs: np.ndarray) -> int:
    """Standard HLL estimator with small/large-range corrections
    (the clearspring ``HyperLogLog.cardinality()`` algorithm), over the
    last axis: an int for one register array, an int64 array for a
    stack of them (one numpy pass: a group-by's trim estimates every
    live group of its fetched state)."""
    regs = np.asarray(regs)
    m = regs.shape[-1]
    rsum = np.sum(_POW2_NEG[regs], axis=-1)  # 2^-rank through a table by the register's byte
    estimate = np.asarray(_alpha(m) * m * m / rsum, dtype=np.float64)
    zeros = np.asarray(np.count_nonzero(regs == 0, axis=-1))
    # linear counting where the raw estimate is small and a register is
    # still empty, through one table by the count of empty registers
    linear = _linear_counting(m)
    two64 = 2.0**64
    with np.errstate(invalid="ignore", divide="ignore"):
        large = -two64 * np.log1p(-np.minimum(estimate, two64 * 0.999) / two64)
    out = np.where((estimate <= 2.5 * m) & (zeros > 0), linear[zeros], np.where(estimate > two64 / 30.0, large, estimate))
    out = np.rint(out).astype(np.int64)
    return int(out) if out.ndim == 0 else out


def merge_registers(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.maximum(a, b)


def hll_estimate_exact_values(values: Iterable[Any], log2m: int = DEFAULT_LOG2M) -> int:
    """Estimate cardinality of a concrete value set through the sketch
    (used by the oracle so engine and oracle agree exactly)."""
    return int(estimate_from_registers(registers_from_values(values, log2m)))


def dictionary_tables(dictionary):
    """Per-dictId (register index, rank) uint8 tables for a column
    dictionary — the ONE place a dictionary's HLL hashing lives (shared
    by the staging stream builder and the planner's table fallback,
    which must agree bit-for-bit).  An integral dictionary is hashed in
    bulk (``hash64_integers``); the entries of any other, and a float
    dictionary's entries with a fraction, one call each.  Cached on the
    dictionary: high-cardinality dictionaries (millions of entries at
    north-star scale) are re-staged per role augmentation."""
    cached = getattr(dictionary, "_hll_tables", None)
    if cached is not None:
        return cached
    card = max(dictionary.cardinality, 1)
    bt = np.zeros(card, dtype=np.uint8)
    rt = np.zeros(card, dtype=np.uint8)
    n = dictionary.cardinality
    values = None if dictionary.is_string else np.asarray(dictionary.values)
    if values is not None and values.dtype.kind in "iu":
        bulk = np.ones(n, dtype=bool)
    elif values is not None and values.dtype.kind == "f":
        bulk = (values == np.floor(values)) & (np.abs(values) < 2.0**63)
    else:
        bulk = np.zeros(n, dtype=bool)
    if bulk.any():
        bt[:n][bulk], rt[:n][bulk] = buckets_and_rhos(hash64_integers(values[bulk]))
    for j in np.nonzero(~bulk)[0]:
        bt[j], rt[j] = bucket_and_rho(value_hash64(dictionary.get(int(j))))
    dictionary._hll_tables = (bt, rt)
    return bt, rt
