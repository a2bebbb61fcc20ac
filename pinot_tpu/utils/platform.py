"""The process's view of its JAX backend: the virtual CPU mesh the tests
and the multi-chip dry run ask for, the declared roofline peaks, and a
look at what this process has done with JAX so far.

The environment chooses the platform (``JAX_PLATFORMS``); serving code
never changes it.  ``virtual_cpu_mesh`` is for ``tests/conftest.py`` and
``__graft_entry__.dryrun_multichip``, which validate multi-chip sharding
on virtual CPU devices (``--xla_force_host_platform_device_count``) and
have to arrange that before the first backend initialization.
"""
from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def virtual_cpu_mesh(n_devices: int) -> bool:
    """Arrange for jax to come up on the CPU platform with at least
    ``n_devices`` virtual devices.

    Must run before the first jax backend initialization in this
    process. Returns True if the platform config was (or already is)
    CPU-forcible; False if backends already initialized on another
    platform (too late — the caller should fail with a clear message).
    """
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        # Too late to change platform or device count; don't touch the
        # env either (subprocesses should inherit the true state).
        return jax.default_backend() == "cpu" and len(jax.devices()) >= n_devices

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{re.escape(_COUNT_FLAG)}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = re.sub(
            rf"{re.escape(_COUNT_FLAG)}=\d+", f"{_COUNT_FLAG}={n_devices}", flags
        )
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    return True


def backend_state() -> dict:
    """What this process has done with JAX, found without doing any of
    it: a controller or a broker shares a machine with the server that
    owns the chip, and must never initialize a backend of its own."""
    import sys

    if "jax" not in sys.modules:
        return {"jaxImported": False, "backendInitialized": False, "platform": None}
    import jax
    from jax._src import xla_bridge

    initialized = xla_bridge.backends_are_initialized()
    return {
        "jaxImported": True,
        "backendInitialized": initialized,
        "platform": jax.default_backend() if initialized else None,
    }


# ---------------------------------------------------------------------------
# Declared per-platform roofline peaks.
#
# The utilization plane divides MEASURED achieved FLOP/s and bytes/s by
# these DECLARED peaks to get a roofline fraction (PAPERS.md's
# bulk-bitwise PIM line argues from exactly this achieved-vs-peak
# framing).  Values are per-chip datasheet numbers: dense bf16/fp
# peak FLOP/s and HBM bandwidth.  Matching is by ``device_kind``
# substring (longest match wins) so "TPU v5 lite" and "TPU v5e" both
# land on the v5e row.  The CPU has no declared peaks — the roofline
# fraction is then "unavailable", not a made-up number.  A TPU whose
# ``device_kind`` is not in the table is an error: add its row.
# ---------------------------------------------------------------------------

# lowercase device_kind substring -> (peak FLOP/s, peak HBM bytes/s)
_PLATFORM_PEAKS = {
    "v5 lite": (197e12, 819e9),  # v5e: 197 TFLOP/s bf16, 819 GB/s
    "v5litepod": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v6e": (918e12, 1640e9),
    "v4": (275e12, 1228e9),
    "v3": (123e12, 900e9),
    "v2": (45e12, 700e9),
}

_peaks_cache = None


def platform_peaks(refresh: bool = False) -> dict:
    """Declared roofline peaks for this process's default device.

    Returns ``{"platform", "deviceKind", "peakFlopsPerSec",
    "peakBytesPerSec", "source"}``.  Peaks are None on the CPU (source
    "unknown") and while jax backends have not initialized yet (source
    "uninitialized" — metric scrapes call through here, and a scrape
    must never be what brings a backend up).  A TPU whose
    ``device_kind`` matches no row raises ``LookupError``."""
    global _peaks_cache
    if not refresh and _peaks_cache is not None:
        return dict(_peaks_cache)
    out = {
        "platform": None,
        "deviceKind": None,
        "peakFlopsPerSec": None,
        "peakBytesPerSec": None,
        "source": "unknown",
    }
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        out["source"] = "uninitialized"  # transient: never cached
        return out
    import jax

    dev = jax.devices()[0]
    out["platform"] = dev.platform
    kind = (dev.device_kind or "").lower()
    out["deviceKind"] = kind
    best = None
    for sub, peaks in _PLATFORM_PEAKS.items():
        if sub in kind and (best is None or len(sub) > len(best[0])):
            best = (sub, peaks)
    if best is not None:
        out["peakFlopsPerSec"], out["peakBytesPerSec"] = best[1]
        out["source"] = "declared"
    elif dev.platform == "tpu":
        raise LookupError(
            f"no declared peaks for TPU device_kind {dev.device_kind!r}: "
            "add its datasheet row to utils/platform._PLATFORM_PEAKS"
        )
    _peaks_cache = dict(out)
    return out


def roofline_fractions(
    achieved_bytes_per_sec,
    achieved_flops_per_sec=None,
    peaks: "dict | None" = None,
) -> dict:
    """Per-resource achieved-vs-peak fractions — the ONE place the
    roofline verdict rule lives (PlanStatsStore per-shape entries and
    the server-wide recent window both call through here).

    Returns ``{"bandwidthFraction"?, "flopsFraction"?,
    "rooflineFraction"}``: a per-resource key is present only when its
    peak is declared AND the achieved rate is positive; a kernel is "at
    the roofline" when its BEST-utilized resource is, so
    ``rooflineFraction`` is the max of the present fractions — or the
    explicit None (never an invented 0) when no peak is declared."""
    if peaks is None:
        peaks = platform_peaks()
    out: dict = {}
    fractions = []
    if peaks.get("peakBytesPerSec") and achieved_bytes_per_sec:
        f = achieved_bytes_per_sec / peaks["peakBytesPerSec"]
        out["bandwidthFraction"] = round(f, 6)
        fractions.append(f)
    if peaks.get("peakFlopsPerSec") and achieved_flops_per_sec:
        f = achieved_flops_per_sec / peaks["peakFlopsPerSec"]
        out["flopsFraction"] = round(f, 6)
        fractions.append(f)
    out["rooflineFraction"] = round(max(fractions), 6) if fractions else None
    return out
