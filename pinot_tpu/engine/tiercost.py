"""Per-tier filter cost model — the measured crossover constants that
pick between the four filter/aggregate tiers, in ONE env-tunable place.

The tiers (engine/invindex_path.py, engine/zonemap.py,
engine/bitsliced.py, engine/kernel.py) each win a region of the
(selectivity, layout) plane as measured on a CPU before the chip round
(record gone; not judged on the chip: ROADMAP S6, D3).  The constants
below encode the crossovers; every one is
overridable via ``PINOT_TPU_TIER_COST_*`` so the model can be
recalibrated per host (a TPU host, a fat CPU dev box) without code
edits.  Defaults reproduce the pre-knob behavior bit-for-bit: the
postings bound ``total_docs * (1/64.0)`` floors to exactly
``total_docs // 64`` (a power-of-two reciprocal is fp-exact).
"""
from __future__ import annotations

import os

# name -> default; read fresh per call so tests can flip them
# without cache invalidation ceremony
_DEFAULTS = {
    # postings/scan crossover: host fancy-index aggregation costs
    # ~10 ns/row vs the device scan's ~0.35 ns/row + dispatch floor;
    # the 1/64-of-table bound keeps postings an order of magnitude
    # under the scan at any size (invindex_path.py)
    "POSTINGS_MATCH_FRACTION": 1.0 / 64.0,
    "POSTINGS_NS_PER_ROW": 10.0,
    "SCAN_NS_PER_ROW": 0.35,
    # fixed per-query device overhead (dispatch + result fetch), ns
    "DISPATCH_FLOOR_NS": 200_000.0,
    # bit-sliced tier: the bitwise pass touches W packed planes of
    # n/32 words each, so its per-row cost scales with planes/32 of
    # the scan's (0.35 / 32 ~= 0.011) — plus the same dispatch floor
    # (engine/bitsliced.py)
    "BSI_NS_PER_ROW_PER_PLANE": 0.011,
    # eligibility cap on total planes a bit-sliced evaluation may
    # touch (filter + fused-agg planes); above it the encoding stops
    # paying for itself against the plain scan
    "BSI_MAX_PLANES": 24.0,
    # host->device reload cost (engine/residency.py victim scoring):
    # per-byte PCIe transfer plus the same dispatch floor — a
    # demotion candidate's score is touch-frequency x THIS, so evicting
    # a big table is charged what re-promoting it will actually cost
    "H2D_NS_PER_BYTE": 0.0625,  # ~16 GB/s effective H2D
    # exponential-decay halflife (seconds) of the residency heat signal
    "RESIDENCY_HALFLIFE_S": 30.0,
}


def _knob(name: str) -> float:
    env = os.environ.get(f"PINOT_TPU_TIER_COST_{name}")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    return _DEFAULTS[name]


def postings_max_matches(total_docs: int) -> int:
    """Postings/scan crossover in rows (invindex_path._max_matches)."""
    return int(total_docs * _knob("POSTINGS_MATCH_FRACTION"))


def scan_cost_ns(total_docs: int) -> float:
    """Full device scan: per-row stream cost + the dispatch floor."""
    return total_docs * _knob("SCAN_NS_PER_ROW") + _knob("DISPATCH_FLOOR_NS")


def postings_cost_ns(matches: int) -> float:
    return matches * _knob("POSTINGS_NS_PER_ROW")


def bitsliced_cost_ns(total_docs: int, planes: int) -> float:
    """Bit-sliced pass over ``planes`` packed bit-planes of the table."""
    return (
        total_docs * planes * _knob("BSI_NS_PER_ROW_PER_PLANE")
        + _knob("DISPATCH_FLOOR_NS")
    )


def bsi_max_planes() -> int:
    return int(_knob("BSI_MAX_PLANES"))


def h2d_cost_ns(nbytes: int) -> float:
    """Cost of re-promoting ``nbytes`` from host to device — the
    reload-cost half of the residency heat score."""
    return nbytes * _knob("H2D_NS_PER_BYTE") + _knob("DISPATCH_FLOOR_NS")


def residency_halflife_s() -> float:
    """Heat-decay halflife for tier victim selection."""
    return _knob("RESIDENCY_HALFLIFE_S")
