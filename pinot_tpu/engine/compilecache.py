"""Persistent compile cache: warm restarts for the device lanes.

Every plan shape pays a cold XLA compile on its first launch, so a
server restart, rollout, or rebalance destination is a p99 cliff until
the whole working set has recompiled.  jax already ships a persistent
compilation cache (keyed on the serialized HLO, compile options, jax
version, backend and topology); this module places it and adds the one
property jax's cache cannot give by itself:

- **A plan ledger.**  jax's cache is opaque: a lane cannot ask "is this
  plan-shape digest warm on disk?" before paying the compile.  The
  ledger records one tiny JSON file per (plan digest, topology
  fingerprint) after each successful compile, so the first launch of a
  shape can be *classified* — ``persistent`` (ledger hit: the XLA cache
  will serve the binary) vs genuinely ``cold`` — and the
  ``compile.cold`` meter stays honest across restarts.  Corrupt or alien
  ledger entries are a miss, never a crash: the ledger is advisory
  accounting, the XLA cache is the actual store.

Where the cache lives.  ``JAX_COMPILATION_CACHE_DIR`` places it from
outside: jax has already taken that directory at import, so this module
makes no ``jax_compilation_cache_dir`` update of its own and only keeps
the ledger in ``<that dir>/plans/``.  Unset, the cache goes to
``<checkout>/.jax_cache`` — resolved from this package's own path, a
fixed name, because a cache that moves never hits.  An explicit
``root=`` to ``configure_jax_cache`` is for tests and harnesses that
need a cache of their own.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from typing import Optional

logger = logging.getLogger(__name__)

_DEFAULT_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_lock = threading.Lock()
# root most recently configured (idempotence guard: lanes call
# configure_jax_cache() per construction, jax.config once)
_configured_root: Optional[str] = None
# explicit root= from a test or harness; wins over the environment
_root_override: Optional[str] = None


def _env_root() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()


def cache_root() -> str:
    """The directory holding jax's cache files and the ``plans/`` ledger."""
    return _root_override or _env_root() or _DEFAULT_ROOT


def wiped_subroot(name: str) -> str:
    """``<root>/<name>``, emptied: a cache of its own for a harness whose
    first phase has to compile cold.  A fixed name under the resolved
    root, never a temporary one."""
    import shutil

    path = os.path.join(cache_root(), name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def topology_fingerprint(
    jax_version: Optional[str] = None,
    platform: Optional[str] = None,
    device_count: Optional[int] = None,
    device_kind: Optional[str] = None,
    x64: Optional[bool] = None,
) -> str:
    """Short stable digest of everything that must invalidate a ledger
    entry.

    A compiled executable is only reusable on the same jax version,
    backend platform, device count (mesh shape), device kind, and
    float-width mode — any of these changing must produce a different
    fingerprint so the old entries become unreachable, not wrong.  All
    parameters are overridable so tests can prove each axis separates
    keys without owning a second topology.
    """
    import jax

    if jax_version is None:
        jax_version = jax.__version__
    if platform is None or device_count is None or device_kind is None:
        devices = jax.devices()
        if platform is None:
            platform = devices[0].platform
        if device_count is None:
            device_count = len(devices)
        if device_kind is None:
            device_kind = devices[0].device_kind
    if x64 is None:
        x64 = bool(jax.config.jax_enable_x64)
    payload = json.dumps(
        {
            "jax": jax_version,
            "platform": platform,
            "devices": int(device_count),
            "kind": device_kind,
            "x64": bool(x64),
        },
        sort_keys=True,
    )
    return hashlib.blake2b(payload.encode(), digest_size=8).hexdigest()


def configure_jax_cache(root: Optional[str] = None) -> Optional[str]:
    """Make sure jax's persistent compilation cache is on, and say where.

    Returns the cache root in use, or None when the directory cannot be
    created (the lanes then compile cold, they do not fail).  With
    ``root`` given the cache is re-pointed there and the ledger follows;
    otherwise see the module docstring.  Idempotent: repeat calls with
    the same root are free.
    """
    global _configured_root, _root_override
    with _lock:
        if root is not None:
            _root_override = root
        target = cache_root()
        if _configured_root == target:
            return target
        try:
            os.makedirs(target, exist_ok=True)
        except OSError:
            logger.warning("compile cache dir unusable: %s", target, exc_info=True)
            return None
        import jax

        if _root_override is not None or not _env_root():
            jax.config.update("jax_compilation_cache_dir", target)
        # jax only persists compiles that took over a second by default;
        # the ledger calls a shape "persistent" after ANY compile, so the
        # floors go to zero to keep the two in step
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _configured_root = target
        return target


# -- plan ledger ------------------------------------------------------------


def _plan_path(root: str, digest: str, fingerprint: str) -> str:
    # digest and fingerprint are short hex; sanitize anyway so a hostile
    # digest string can never escape the ledger directory
    safe = "".join(c for c in f"{digest}-{fingerprint}" if c.isalnum() or c == "-")
    return os.path.join(root, "plans", f"{safe}.json")


def record_plan(
    digest: str,
    fingerprint: Optional[str] = None,
    root: Optional[str] = None,
) -> bool:
    """Mark a plan-shape digest as compiled under this topology.

    Atomic (tmp + rename) so a crash mid-write leaves either a valid
    entry or none — never a truncated file another process would have
    to tolerate (it would anyway: see ``known_plan``).
    """
    if root is None:
        root = cache_root()
    if not digest:
        return False
    if fingerprint is None:
        fingerprint = topology_fingerprint()
    path = _plan_path(root, digest, fingerprint)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # unique per writer: two lanes of one process compile the same
        # digest at once, and each must rename a file of its own
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "w") as f:
            json.dump(
                {
                    "digest": digest,
                    "fingerprint": fingerprint,
                    "jaxVersion": __import__("jax").__version__,
                    "recordedAtMs": int(time.time() * 1000),
                },
                f,
            )
        os.replace(tmp, path)
        return True
    except OSError:
        logger.warning("plan ledger write failed: %s", path, exc_info=True)
        return False


def known_plan(
    digest: str,
    fingerprint: Optional[str] = None,
    root: Optional[str] = None,
) -> bool:
    """True when the ledger proves this digest compiled on THIS topology.

    Every failure mode — missing file, unreadable file, corrupt JSON,
    an alien entry whose recorded digest/fingerprint disagrees with its
    filename — is a miss.  The ledger only reclassifies accounting; a
    wrong False costs one cold-meter tick, a crash would cost the lane.
    """
    if root is None:
        root = cache_root()
    if not digest:
        return False
    if fingerprint is None:
        fingerprint = topology_fingerprint()
    path = _plan_path(root, digest, fingerprint)
    try:
        with open(path) as f:
            entry = json.load(f)
    except (OSError, ValueError):
        return False
    return (
        isinstance(entry, dict)
        and entry.get("digest") == digest
        and entry.get("fingerprint") == fingerprint
    )


def _reset_for_tests() -> None:
    """Forget the configured root and any ``root=`` override."""
    global _configured_root, _root_override
    with _lock:
        _configured_root = None
        _root_override = None
