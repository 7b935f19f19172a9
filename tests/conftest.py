"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the reference's tier-3 strategy (SURVEY.md §4): Trino boots a
multi-node cluster inside one JVM (DistributedQueryRunner); we boot a
multi-device mesh inside one process via XLA's host-platform device
partitioning. The chip is driven by chip_smoke.py and chipbench/, never
by the test suite; tests/test_chip_compile.py asks the chip's compiler
about the main-path kernels without a chip attached.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

# Persistent XLA compilation cache: the suite compiles 1000+ programs
# and the per-module clear_caches() below (segfault workaround) forces
# recompiles of shared kernels — with the disk cache those recompiles
# become cache hits (keyed by HLO hash, so code changes invalidate
# naturally). TRINO_TPU_NO_COMPILE_CACHE=1 disables for experiments.
# JAX_COMPILATION_CACHE_DIR, when set, places the cache (JAX reads it
# itself; no directory is set in code then).
if os.environ.get("TRINO_TPU_NO_COMPILE_CACHE") != "1":
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import tempfile

        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(
                tempfile.gettempdir(),
                f"trino_tpu_test_xla_cache_{os.getuid()}",  # per-user
            ),
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # bound the on-disk cache (LRU-evicted by jax past this size)
    jax.config.update("jax_compilation_cache_max_size", 2 * 1024**3)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long soaks (chaos soak, full mesh TPC-H sweep) excluded "
        "from the tier-1 run (-m 'not slow'); run in the dev loop",
    )


# -- shared read-only runners (tier-1 wall trim) -----------------------
# Many modules used to build identical tpch/tpcds-tiny runners — and
# 2-worker distributed clusters — once per module, or even once per
# parametrized case. These session-scoped fixtures build each exactly
# once per run. Tests using them MUST be read-only: no DML/DDL, no SET
# SESSION, no session-attribute mutation; a test that mutates state
# builds its own runner.


@pytest.fixture(scope="session")
def tpch_local():
    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import LocalQueryRunner, Session

    r = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    r.register_catalog("tpch", create_tpch_connector())
    return r


@pytest.fixture(scope="session")
def tpcds_local():
    from trino_tpu.connectors.tpcds import create_tpcds_connector
    from trino_tpu.engine import LocalQueryRunner, Session

    r = LocalQueryRunner(Session(catalog="tpcds", schema="tiny"))
    r.register_catalog("tpcds", create_tpcds_connector())
    return r


@pytest.fixture(scope="session")
def tpch_cluster():
    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.runtime import DistributedQueryRunner

    r = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny"),
        n_workers=2, hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())
    return r


@pytest.fixture(scope="session")
def tpcds_cluster():
    from trino_tpu.connectors.tpcds import create_tpcds_connector
    from trino_tpu.engine import Session
    from trino_tpu.runtime import DistributedQueryRunner

    r = DistributedQueryRunner(
        Session(catalog="tpcds", schema="tiny"),
        n_workers=2, hash_partitions=2,
    )
    r.register_catalog("tpcds", create_tpcds_connector())
    return r


@pytest.fixture(scope="session")
def tpch_cluster_mesh_off():
    """Page-plane (mesh_execution=False) 2-worker cluster. The chunk /
    recovery / replica modules each need the page plane's answers as a
    byte-identity oracle, and test_local_exchange needs a
    task_concurrency=2 cluster (2 is the session default) — one shared
    runner serves all of them."""
    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.runtime import DistributedQueryRunner

    r = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny", mesh_execution=False),
        n_workers=2, hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())
    return r


@pytest.fixture(autouse=True, scope="module")
def _concurrency_sanitizer(request):
    """Thread-leak and held-lock sanitizer: after each module, every
    registered background thread must have exited (or be daemon) and no
    witness lock may still be held. Session-scoped servers (statement
    server, proxy, worker HTTP) are daemon threads, so they pass; a test
    that forgets to stop a non-daemon worker fails its module here with
    the thread's registered name and owner."""
    yield
    import time as _time

    from trino_tpu.analysis import threadreg, witness

    _t0 = _time.monotonic()
    leaks = threadreg.THREADS.non_daemon_leaks()
    if leaks:
        # grace for threads mid-exit (target returned, join pending)
        deadline = _time.monotonic() + 2.0
        while leaks and _time.monotonic() < deadline:
            _time.sleep(0.02)
            leaks = threadreg.THREADS.non_daemon_leaks()
    assert not leaks, (
        "non-daemon threads leaked by this module: " + ", ".join(leaks)
    )

    held = witness.held_locks()
    if held:
        # a background daemon may transiently hold a lock; retry briefly
        deadline = _time.monotonic() + 1.0
        while held and _time.monotonic() < deadline:
            _time.sleep(0.01)
            held = witness.held_locks()
    assert not held, f"locks still held after module: {held}"
    assert witness.violation_count() == 0, (
        f"{witness.violation_count()} lock-witness violations recorded "
        "(a LockOrderError was raised and swallowed somewhere)"
    )
    dbg = os.environ.get("TRINO_TPU_SANITIZER_DEBUG")
    if dbg:
        with open(dbg, "a") as fh:
            fh.write(
                "[sanitizer] %s teardown=%.3fs locks=%d threads=%d t=%.1f\n"
                % (request.module.__name__, _time.monotonic() - _t0,
                   witness.lock_count(), threadreg.THREADS.spawned_total,
                   _time.monotonic())
            )


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """The full suite compiles 1000+ XLA programs in one process; this
    environment's XLA CPU compiler segfaults under that accumulated
    load (re-confirmed in r3: disabling this clearing crashed the run
    inside backend_compile — it is NOT the associative_scan issue,
    which r3 removed separately). Dropping compiled executables between
    modules bounds compiler state at the cost of per-module recompiles;
    TRINO_TPU_NO_CLEAR_CACHES=1 disables it for experiments."""
    yield
    if os.environ.get("TRINO_TPU_NO_CLEAR_CACHES") != "1":
        jax.clear_caches()
