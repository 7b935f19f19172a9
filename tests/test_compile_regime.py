"""Compile regime (trino_tpu/compile/): capacity ladder, shape
stabilization, census-driven warmup, program/persistent caches, and the
zero-recompile guarantees the regime exists to provide — dynamic-filter
retries, FTE re-attempts, and simulated worker restarts must all re-land
on already-compiled (operator, capacity, dtype-sig) lowerings."""

import os
from types import SimpleNamespace

import pytest

from trino_tpu import types as T
from trino_tpu.block import RelBatch
from trino_tpu.compile.cache import PersistentCompileCache
from trino_tpu.compile.shapes import CapacityLadder, ShapeStabilizer
from trino_tpu.compile.warmup import (
    WarmupEntry,
    WarmupService,
    classes_warm,
    note_classes_warm,
    reset_warm_classes,
    zeros_batch,
)
from trino_tpu.connectors.tpch import create_tpch_connector
from trino_tpu.engine import LocalQueryRunner, Session
from trino_tpu.runtime.metrics import METRICS


# ---------------------------------------------------------------------------
# capacity ladder (compile/shapes.py)
# ---------------------------------------------------------------------------


def test_ladder_rungs_monotone_and_idempotent():
    ladder = CapacityLadder()
    prev = 0
    for n in [1, 2, 15, 16, 17, 100, 1000, 65535, 65536, 65537, 1 << 20]:
        r = ladder.rung(n)
        assert r >= n
        assert r >= prev  # nondecreasing in n
        assert ladder.rung(r) == r  # rungs are fixed points
        prev = r


def test_ladder_base4_coarsens_base2():
    b2, b4 = CapacityLadder(base=2), CapacityLadder(base=4)
    # every base-4 rung is a base-2 rung (stays on the pow2 grid) ...
    assert set(b4.rungs(1 << 20)) <= set(b2.rungs(1 << 20))
    # ... and there are fewer of them (coarser = fewer distinct classes)
    assert len(b4.rungs(1 << 20)) < len(b2.rungs(1 << 20))
    assert b4.rung(100) == 256  # 16, 64, 256, ...
    assert b2.rung(100) == 128


def test_ladder_validation():
    with pytest.raises(ValueError):
        CapacityLadder(base=3)  # not a power of two
    with pytest.raises(ValueError):
        CapacityLadder(base=1)  # degenerate: every n its own class
    with pytest.raises(ValueError):
        CapacityLadder(min_capacity=24)


def test_scan_classes_main_and_tail():
    st = ShapeStabilizer(CapacityLadder(), batch_rows=49152)
    # tpch tiny lineitem: 60175 rows at batch_rows=49152 → one full
    # chunk (rung 65536) plus an 11023-row tail (rung 16384)
    assert st.scan_classes(60175) == (65536, 16384)
    assert st.scan_classes(1000) == (1024,)  # fits in one chunk: no tail
    assert st.scan_classes(2 * 49152) == (65536,)  # even split: no tail
    # pruned chunks re-land on the unpruned span's class
    assert st.chunk_capacity(60175) == st.chunk_capacity(60175) == 65536


# ---------------------------------------------------------------------------
# warmup service (compile/warmup.py)
# ---------------------------------------------------------------------------


def test_warmup_failure_degrades_not_fails():
    def boom(batch):
        raise RuntimeError("lowering exploded")

    entry = WarmupEntry(
        operator="FilterProjectOperator",
        fn=boom,
        in_schema=[(T.BIGINT, None)],
        out_dtypes=("bigint",),
        capacities=(16,),
    )
    svc = WarmupService([entry], mode="block").start()
    assert svc.wait(timeout=30.0)  # service completes despite the raise
    assert entry.status == "failed"
    assert "exploded" in entry.detail
    assert svc.warmed_keys() == set()
    line = svc.report_line()
    assert "failed=1" in line and "compiled=0" in line


def test_warmup_nested_schema_skipped():
    nested = SimpleNamespace(is_nested=True)
    with pytest.raises(NotImplementedError):
        zeros_batch([(nested, None)], 16)
    entry = WarmupEntry(
        operator="FilterProjectOperator",
        fn=lambda b: b,
        in_schema=[(nested, None)],
        out_dtypes=("array(bigint)",),
        capacities=(16,),
    )
    svc = WarmupService([entry], mode="block").start()
    svc.wait(timeout=30.0)
    assert entry.status == "skipped"


def test_warmup_success_marks_classes_warm():
    reset_warm_classes()
    try:
        keys = {("FilterProjectOperator", c, ("bigint",)) for c in (16, 64)}
        assert not classes_warm(keys)
        assert not classes_warm(set())  # vacuous truth is not warmth
        entry = WarmupEntry(
            operator="FilterProjectOperator",
            fn=lambda b: b,
            in_schema=[(T.BIGINT, None)],
            out_dtypes=("bigint",),
            capacities=(16, 64),
        )
        svc = WarmupService([entry], mode="block").start()
        svc.wait(timeout=30.0)
        assert entry.status == "compiled"
        assert svc.warmed_keys() == keys
        assert classes_warm(keys)
        # a superset with an un-warmed class is not all-warm
        assert not classes_warm(keys | {("HashAggregationOperator", 16, ("bigint",))})
    finally:
        reset_warm_classes()


def test_warmup_off_mode_is_immediate():
    svc = WarmupService([], mode="off").start()
    assert svc.wait(timeout=0)


# ---------------------------------------------------------------------------
# persistent cache management (compile/cache.py)
# ---------------------------------------------------------------------------


def test_persistent_cache_scrub_and_evict(tmp_path):
    cache = PersistentCompileCache(root=str(tmp_path), max_bytes=250)
    os.makedirs(cache.dir, exist_ok=True)

    def put(name, size, mtime):
        p = os.path.join(cache.dir, name)
        with open(p, "wb") as f:
            f.write(b"x" * size)
        os.utime(p, (mtime, mtime))
        return p

    put("dead", 0, 100)  # zero-byte: writer died pre-write
    put("entry.tmp", 50, 100)  # orphaned temp: writer died mid-rename
    put("tmp_orphan", 50, 100)
    oldest = put("xla_a", 100, 100)
    put("xla_b", 100, 200)
    put("xla_c", 100, 300)

    cache.prepare()  # scrub + evict, as a restarted worker would
    assert cache.scrubbed == 3
    # 300 bytes of real entries > max_bytes=250: oldest mtime goes first
    assert cache.evicted == 1
    assert not os.path.exists(oldest)
    assert cache.entry_count() == 2
    assert cache.total_bytes() == 200
    stats = cache.stats()
    assert stats["scrubbed"] == 3 and stats["evicted"] == 1
    # the salt dir is versioned: a jax upgrade or schema rev change must
    # not serve stale executables
    assert "jax" in cache.salt and "schema" in cache.salt
    assert cache.dir.endswith(cache.salt)


def test_persistent_cache_prepare_is_idempotent(tmp_path):
    cache = PersistentCompileCache(root=str(tmp_path), max_bytes=1 << 20)
    cache.prepare()
    cache.prepare()  # fresh dir, nothing to scrub or evict
    assert cache.scrubbed == 0 and cache.evicted == 0


@pytest.mark.parametrize("case", ["env_set", "env_unset", "cpu_process"])
def test_persistent_cache_location(case, monkeypatch, tmp_path):
    """Where the cache lands: JAX_COMPILATION_CACHE_DIR when set (and no
    directory set in code), else one fixed path inside the checkout; a
    CPU process gets none. jax.config.update is recorded, not applied,
    and the real cache directory is never created."""
    import subprocess
    import sys

    import jax

    from trino_tpu.compile import cache as cc

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append((name, value))
    )
    monkeypatch.setattr(cc, "ACTIVE_PERSISTENT_CACHE", None)
    monkeypatch.setattr(cc, "install_cache_event_listener", lambda: True)
    made = []
    monkeypatch.setattr(
        cc.os, "makedirs", lambda d, exist_ok=False: made.append(d)
    )
    monkeypatch.delenv("TRINO_TPU_NO_COMPILE_CACHE", raising=False)

    if case == "cpu_process":
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setenv(cc.CACHE_DIR_ENV, str(tmp_path))
        assert cc.configure_persistent_cache() is None
        assert updates == [] and made == []
        return

    # a TPU-targeted process: JAX_PLATFORMS unset, or the CPU not first
    monkeypatch.delenv("JAX_PLATFORMS")
    if case == "env_set":
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # the chip machine's
        monkeypatch.setenv(cc.CACHE_DIR_ENV, str(tmp_path))
        cache = cc.configure_persistent_cache()
        assert cache.dir == str(tmp_path) and made == [str(tmp_path)]
        assert "jax_compilation_cache_dir" not in dict(updates)
    else:
        monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
        cache = cc.configure_persistent_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cache.dir.startswith(os.path.join(repo, ".cache", "xla") + os.sep)
        assert dict(updates)["jax_compilation_cache_dir"] == cache.dir
        # fixed: the same path on a second call and in another process
        assert cc.PersistentCompileCache().dir == cache.dir
        env = {k: v for k, v in os.environ.items() if k != cc.CACHE_DIR_ENV}
        env["JAX_PLATFORMS"] = "cpu"  # the child only computes the path
        other = subprocess.run(
            [sys.executable, "-c",
             "from trino_tpu.compile.cache import PersistentCompileCache as P;"
             "print(P().dir)"],
            env=env, cwd=repo, capture_output=True, text=True, check=True,
        ).stdout.strip().splitlines()[-1]
        assert other == cache.dir
    # TPU compiles of about a second are cached too
    assert dict(updates)["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert cc.ACTIVE_PERSISTENT_CACHE is cache


def test_persistent_cache_activation_failure_raises(monkeypatch, tmp_path):
    """A cache that cannot be prepared is an error on a TPU process, not
    a silent downgrade to compiling everything again."""
    from trino_tpu.compile import cache as cc

    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.setattr(cc, "ACTIVE_PERSISTENT_CACHE", None)
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.delenv("TRINO_TPU_NO_COMPILE_CACHE", raising=False)
    monkeypatch.setenv(cc.CACHE_DIR_ENV, str(blocker / "cache"))
    with pytest.raises(OSError):
        cc.configure_persistent_cache()
    assert cc.ACTIVE_PERSISTENT_CACHE is None


# ---------------------------------------------------------------------------
# spill re-read capacity restore (exec/spill.py)
# ---------------------------------------------------------------------------


def test_spiller_restores_spill_time_capacity():
    from trino_tpu.exec.spill import FileSpiller

    b = RelBatch.from_pydict(
        [("a", T.BIGINT)], {"a": [1, 2, 3, 4, 5]}, capacity=64
    )
    assert b.capacity == 64
    sp = FileSpiller()
    try:
        sp.spill(b)
        (out,) = list(sp.unspill())
        # serialization compacts to live rows; the re-read must re-enter
        # the operator on the class it was first compiled for
        assert out.capacity == 64
        assert out.to_pylists() == b.to_pylists()
    finally:
        sp.close()


# ---------------------------------------------------------------------------
# warm watchdog threshold (runtime/worker.py)
# ---------------------------------------------------------------------------


class _FakeTask:
    def __init__(self, warm):
        self.shapes_warm = warm
        self.state = "running"
        self.seen = []
        self.spec = SimpleNamespace(task_id=f"t-{warm}")

    def interrupt_if_stuck(self, timeout, now=None):
        self.seen.append(timeout)
        return None


def _worker(**kw):
    from trino_tpu.connectors.spi import CatalogManager
    from trino_tpu.runtime.worker import Worker

    return Worker("w-watchdog", CatalogManager(), **kw)


def test_watchdog_warm_threshold_selection():
    w = _worker(stuck_task_interrupt_s=5.0, stuck_task_interrupt_warm_s=0.5)
    warm, cold = _FakeTask(True), _FakeTask(False)
    w._tasks = {"a": warm, "b": cold}
    w.watchdog_once()
    assert warm.seen == [0.5]  # all predicted classes warm → tight leash
    assert cold.seen == [5.0]  # cold compiles still get the slow path


def test_watchdog_warm_only_skips_cold_tasks():
    w = _worker(stuck_task_interrupt_warm_s=0.5)  # no conservative limit
    warm, cold = _FakeTask(True), _FakeTask(False)
    w._tasks = {"a": warm, "b": cold}
    w.watchdog_once()
    assert warm.seen == [0.5]
    assert cold.seen == []  # no threshold applies → never interrupted


def test_watchdog_disabled_without_thresholds():
    w = _worker()
    w._tasks = {"a": _FakeTask(True)}
    assert w.watchdog_once() == []


# ---------------------------------------------------------------------------
# end-to-end: stabilized execution, zero-recompile replay, warmup modes
# ---------------------------------------------------------------------------


FP_Q = "select l_orderkey + 1 from lineitem where l_quantity * 2 < 10"
AGG_Q = (
    "select l_returnflag, sum(l_quantity), count(*) from lineitem"
    " group by l_returnflag order by l_returnflag"
)
JOIN_Q = (
    "select count(*) from lineitem, orders"
    " where l_orderkey = o_orderkey and o_totalprice < 50000"
)
REPLAY_QUERIES = (FP_Q, AGG_Q, JOIN_Q)


@pytest.fixture(scope="module")
def runner():
    r = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    r.register_catalog("tpch", create_tpch_connector())
    return r


def _compiles_this_query(runner, sql):
    text = runner.execute("explain analyze " + sql).rows[0][0]
    assert "xla_compiles_this_query=" in text, text
    return int(text.split("xla_compiles_this_query=")[1].split()[0])


def test_stabilized_results_match_unstabilized_oracle(runner):
    oracle = {}
    runner.execute("SET SESSION shape_stabilization = false")
    try:
        for q in REPLAY_QUERIES:
            oracle[q] = runner.execute(q).rows
    finally:
        runner.execute("SET SESSION shape_stabilization = true")
    for q in REPLAY_QUERIES:
        assert runner.execute(q).rows == oracle[q]
    # a coarser ladder pads harder but must not change results
    runner.execute("SET SESSION capacity_ladder_base = 4")
    try:
        for q in REPLAY_QUERIES:
            assert runner.execute(q).rows == oracle[q]
    finally:
        runner.execute("SET SESSION capacity_ladder_base = 2")


def test_second_execution_compiles_nothing(runner):
    """The regime's core guarantee: once a query shape has executed,
    re-running it (dynamic-filter pruned re-scans included — JOIN_Q
    plans a dynamic filter) mints zero new XLA lowerings."""
    for q in REPLAY_QUERIES:
        first = _compiles_this_query(runner, q)
        second = _compiles_this_query(runner, q)
        assert second == 0, f"{q!r}: first={first} second={second}"


def test_restarted_runner_replays_warm(runner):
    """Simulated worker restart: a fresh runner (fresh plan cache,
    fresh shape ledger) replaying queries this process already executed
    reports zero compiles — program cache and jitted kernels are
    process-global, standing in for the persistent cache on TPU."""
    baseline = {}
    for q in REPLAY_QUERIES:  # ensure this process is warm
        baseline[q] = runner.execute(q).rows
    fresh = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    fresh.register_catalog("tpch", create_tpch_connector())
    for q in REPLAY_QUERIES:
        assert _compiles_this_query(fresh, q) == 0, q
        assert fresh.execute(q).rows == baseline[q]


def test_warmup_modes(runner):
    try:
        runner.execute("SET SESSION warmup_mode = off")
        text = runner.execute("explain analyze " + FP_Q).rows[0][0]
        assert "warmup:" not in text

        runner.execute("SET SESSION warmup_mode = block")
        text = runner.execute("explain analyze " + FP_Q).rows[0][0]
        assert "warmup: mode=block" in text, text
        tail = text.split("warmup: mode=block ")[1].splitlines()[0]
        stats = dict(kv.split("=") for kv in tail.split())
        assert int(stats["entries"]) >= 1
        assert int(stats["failed"]) == 0, text
        # the FP stage was warmed and then executed → counted as a hit
        assert int(stats["hits"]) >= 1, text

        runner.execute("SET SESSION warmup_mode = background")
        text = runner.execute("explain analyze " + FP_Q).rows[0][0]
        assert "warmup: mode=background" in text, text
    finally:
        runner.execute("SET SESSION warmup_mode = off")


def test_warmup_mode_validated(runner):
    with pytest.raises(Exception, match="warmup_mode"):
        runner.execute("SET SESSION warmup_mode = sideways")


# ---------------------------------------------------------------------------
# FTE re-attempt: retries re-land on compiled classes
# ---------------------------------------------------------------------------


FTE_Q = (
    "SELECT l_returnflag, sum(l_quantity), count(*) FROM lineitem"
    " GROUP BY l_returnflag ORDER BY l_returnflag"
)


def test_fte_reattempt_compiles_nothing():
    from trino_tpu.connectors.spi import CatalogManager
    from trino_tpu.runtime import DistributedQueryRunner
    from trino_tpu.runtime.failure import FailureInjector
    from trino_tpu.runtime.worker import Worker

    inj = FailureInjector()
    cats = CatalogManager()
    cats.register("tpch", create_tpch_connector())
    workers = [Worker(f"w{i}", cats, failure_injector=inj) for i in range(2)]
    r = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny", retry_policy="task"),
        worker_handles=workers,
        hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())

    baseline = r.execute(FTE_Q).rows  # clean run compiles everything
    before = METRICS.counter("xla_compiles")
    inj.inject(fragment_id=0, partition=0, attempts=(0,), where="start")
    assert r.execute(FTE_Q).rows == baseline
    delta = METRICS.counter("xla_compiles") - before
    assert delta == 0, f"FTE re-attempt minted {delta} new lowerings"
