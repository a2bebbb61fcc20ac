"""Test config: an 8-device virtual CPU mesh and 64-bit mode.

Multi-chip sharding is validated on a virtual CPU mesh
(``xla_force_host_platform_device_count=8``); x64 is enabled so CPU test
runs reproduce the reference's double-precision aggregation semantics
exactly.  Tier-1 is run with ``JAX_PLATFORMS=cpu``; ``virtual_cpu_mesh``
also adds the device-count flag, which has to be in place before the
first backend initialization.

``PINOT_TPU_TESTS=tpu`` is the on-device gate (``pytest -m tpu``): the
backend the environment chose is kept, and it has to be a TPU.
"""
import os

import pytest

if os.environ.get("PINOT_TPU_TESTS") == "tpu":
    # keep the real TPU backend and its native float32 semantics —
    # tolerance assertions live in the tests
    import jax  # noqa: F401
else:
    from pinot_tpu.utils.platform import virtual_cpu_mesh

    if not virtual_cpu_mesh(8):  # not an assert: must survive PYTHONOPTIMIZE
        raise RuntimeError(
            "jax backends initialized before conftest; tests must come up on a "
            "virtual 8-device CPU mesh"
        )

    import jax

    jax.config.update("jax_enable_x64", True)


@pytest.fixture(scope="session", autouse=True)
def _session_compile_cache(tmp_path_factory):
    """The persistent compile cache is on by default.  Processes the
    tests spawn take it from JAX_COMPILATION_CACHE_DIR: a pytest
    directory, never ``<checkout>/.jax_cache``."""
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.mktemp("jax_cache")))
    yield
    mp.undo()


@pytest.fixture(autouse=True)
def _own_compile_cache(tmp_path_factory, monkeypatch):
    """Every test gets a cache root of its own (shared with the
    processes it spawns), so a ``compile.cold`` assertion depends neither
    on what an earlier run left behind nor on which test ran before."""
    from pinot_tpu.engine import compilecache

    root = str(tmp_path_factory.mktemp("jax_cache"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", root)
    compilecache.configure_jax_cache(root=root)
    yield
    compilecache._reset_for_tests()


# ---------------------------------------------------------------------------
# Thread-leak guard for the device-lane supervision path: a watchdog
# restart abandons the wedged lane thread, and a bug there would leak
# one thread per wedge.  After every test, any lane that was CLOSED must
# have no surviving lane/watchdog threads (lanes left open by
# module-scoped fixtures are exempt — they are still serving).
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _no_leaked_lane_threads():
    yield
    from pinot_tpu.engine.dispatch import leaked_lane_threads

    leaked = leaked_lane_threads(grace_s=2.0)
    assert not leaked, (
        f"device-lane threads leaked past lane close: "
        f"{[t.name for t in leaked]}"
    )


@pytest.fixture(autouse=True)
def _no_leaked_scheduler_threads():
    """Fair-share scheduler workers (server/scheduler.py): a shut-down
    scheduler's workers must drain their queues and exit — this guard
    catches any worker that survived shutdown().  Workers of schedulers
    still serving (module fixtures) are exempt."""
    yield
    from pinot_tpu.server.scheduler import leaked_scheduler_threads

    # grace covers a worker still draining a query whose client already
    # timed out (e.g. the 2s sleep in test_scheduler_run_timeout)
    leaked = leaked_scheduler_threads(grace_s=4.0)
    assert not leaked, (
        f"scheduler worker threads leaked past shutdown(): "
        f"{[t.name for t in leaked]}"
    )


@pytest.fixture(autouse=True)
def _no_leaked_recorder_threads():
    """History recorders (utils/timeseries.py): one daemon thread per
    role snapshots metrics on a cadence; ``stop()`` must actually end
    it.  Recorders still running (module fixtures, live roles) are
    exempt — a STOPPED recorder whose thread survives is the leak."""
    yield
    from pinot_tpu.utils.timeseries import leaked_recorder_threads

    leaked = leaked_recorder_threads(grace_s=2.0)
    assert not leaked, (
        f"history-recorder threads leaked past stop(): "
        f"{[t.name for t in leaked]}"
    )


@pytest.fixture(autouse=True)
def _no_leaked_ingest_pool_threads():
    """Ingest consumer pools (realtime/pool.py): bounded workers
    multiplexing realtime consumers; ``stop()`` must end every worker.
    Pools still running (live servers) are exempt — a STOPPED pool
    whose workers survive is the leak."""
    yield
    from pinot_tpu.realtime.pool import leaked_pool_threads

    leaked = leaked_pool_threads(grace_s=2.0)
    assert not leaked, (
        f"ingest-pool worker threads leaked past stop(): "
        f"{[t.name for t in leaked]}"
    )


@pytest.fixture(autouse=True)
def _no_leaked_prewarm_threads():
    """Prewarm workers (server/prewarm.py): the background compile
    driver is one daemon thread per server, started lazily on the
    first prewarm request; ``stop()`` (via ``ServerInstance.shutdown``)
    must actually end it.  Workers still serving (live servers held by
    fixtures) are exempt — a STOPPED worker whose thread survives is
    the leak."""
    yield
    from pinot_tpu.server.prewarm import leaked_prewarm_threads

    leaked = leaked_prewarm_threads(grace_s=2.0)
    assert not leaked, (
        f"prewarm worker threads leaked past stop(): {leaked}"
    )


@pytest.fixture(autouse=True)
def _no_leaked_manager_threads():
    """Controller periodic managers (retention/validation/status/
    stabilizer): a stopped manager's worker must actually exit —
    ``_PeriodicManager.stop()`` joins it with a bounded timeout, and
    this guard catches any manager loop that shrugged off the stop
    event.  Still-running managers (module fixtures) are exempt."""
    yield
    from pinot_tpu.controller.managers import leaked_manager_threads

    leaked = leaked_manager_threads(grace_s=2.0)
    assert not leaked, (
        f"controller-manager threads leaked past stop(): "
        f"{[t.name for t in leaked]}"
    )


@pytest.fixture(autouse=True)
def _no_leaked_audit_threads():
    """Audit samplers (utils/audit.py): shadow/replica auditor workers
    are lazy daemon threads started on the first enqueued sample;
    ``stop()`` (via ServerInstance.shutdown / Broker.shutdown) must
    actually end them.  Still-enabled auditors on live fixtures are
    exempt — a STOPPED auditor whose worker survives is the leak."""
    yield
    from pinot_tpu.utils.audit import leaked_audit_threads

    leaked = leaked_audit_threads(grace_s=2.0)
    assert not leaked, (
        f"audit worker threads leaked past stop(): {leaked}"
    )
