"""The concurrency soundness plane (trino_tpu/analysis/).

Three layers under test:

* the static analyzer — deliberately broken in-memory fixture modules
  must each produce the right typed finding at the right file:line, and
  the committed package must produce none;
* the runtime lock witness — order violations and non-reentrant
  re-entry raise typed LockOrderError naming both locks and both sites;
* the thread registry — named ownership, leak reporting, join_all.

Plus the regression tests for the races the analyzer surfaced in the
seed tree (LAST_RUN_INFO, MESH_COUNTERS, _GLOBAL_FN_CACHE).
"""

import threading
import time

import pytest

from trino_tpu.analysis import analyze_package, analyze_sources
from trino_tpu.analysis.witness import (
    LockOrderError,
    named_condition,
    named_lock,
    named_rlock,
    reset_witness_for_tests,
    seed_order,
    violation_count,
)
from trino_tpu.analysis import threadreg


@pytest.fixture(autouse=True)
def _fresh_witness():
    """Tests here deliberately trip the witness; reset its order graph
    and violation counter around each one so the module-scoped
    sanitizer fixture (conftest) sees a clean slate afterwards."""
    reset_witness_for_tests()
    yield
    reset_witness_for_tests()


# -- static analyzer: broken fixtures ---------------------------------

CYCLE_SRC = """\
from trino_tpu.analysis.witness import named_lock

_lock_a = named_lock("fix._lock_a")
_lock_b = named_lock("fix._lock_b")


def forward():
    with _lock_a:
        with _lock_b:
            pass


def backward():
    with _lock_b:
        with _lock_a:
            pass
"""


def test_static_lock_order_cycle_reported_with_both_paths():
    rep = analyze_sources({"fix": ("fix.py", CYCLE_SRC)})
    cycles = [f for f in rep.findings if f.kind == "lock-cycle"]
    assert len(cycles) == 1
    f = cycles[0]
    assert f.file == "fix.py"
    # both lock ids and both witness sites must appear in the report
    assert "fix._lock_a" in f.message and "fix._lock_b" in f.message
    assert "fix.py:9" in f.message  # forward's inner acquire
    assert "fix.py:15" in f.message  # backward's inner acquire


def test_static_cycle_through_call_edge():
    # the cycle closes through a function call, not a nested with:
    # holder_a holds A and calls helper, which takes B; holder_b does
    # the reverse. Neither function nests both locks syntactically.
    src = """\
from trino_tpu.analysis.witness import named_lock

_a = named_lock("m._a")
_b = named_lock("m._b")


def take_b():
    with _b:
        pass


def take_a():
    with _a:
        pass


def holder_a():
    with _a:
        take_b()


def holder_b():
    with _b:
        take_a()
"""
    rep = analyze_sources({"m": ("m.py", src)})
    cycles = [f for f in rep.findings if f.kind == "lock-cycle"]
    assert len(cycles) == 1
    assert "m._a" in cycles[0].message and "m._b" in cycles[0].message


BARE_WRITE_SRC = """\
from trino_tpu.analysis.witness import named_lock

_cache_lock = named_lock("bw._cache_lock")
CACHE = {}  # guarded_by: _cache_lock


def good(key, value):
    with _cache_lock:
        CACHE[key] = value


def bad(key, value):
    CACHE[key] = value
"""


def test_static_bare_guarded_write_flagged_at_line():
    rep = analyze_sources({"bw": ("bw.py", BARE_WRITE_SRC)})
    hits = [f for f in rep.findings if f.kind == "guarded-field"]
    assert len(hits) == 1
    assert hits[0].file == "bw.py"
    assert hits[0].line == 13  # the write inside bad(), not good()
    assert "_cache_lock" in hits[0].message


UNLOCKED_GLOBAL_SRC = """\
REGISTRY = {}


def record(key, value):
    REGISTRY[key] = value
"""


def test_static_unlocked_mutable_global_write_flagged():
    rep = analyze_sources({"ug": ("ug.py", UNLOCKED_GLOBAL_SRC)})
    hits = [f for f in rep.findings if f.kind == "unlocked-global-write"]
    assert len(hits) == 1
    assert hits[0].file == "ug.py" and hits[0].line == 5


LEAKED_THREAD_SRC = """\
import threading


def spawn_worker(target):
    t = threading.Thread(target=target)
    t.start()
    return t
"""


def test_static_raw_thread_spawn_flagged():
    rep = analyze_sources({"lt": ("lt.py", LEAKED_THREAD_SRC)})
    hits = [f for f in rep.findings if f.kind == "unregistered-thread"]
    assert len(hits) == 1
    assert hits[0].file == "lt.py" and hits[0].line == 5


REENTRY_SRC = """\
from trino_tpu.analysis.witness import named_lock

_mu = named_lock("re._mu")


def recurse():
    with _mu:
        with _mu:
            pass
"""


def test_static_nonreentrant_reentry_flagged():
    rep = analyze_sources({"re_fix": ("re_fix.py", REENTRY_SRC)})
    hits = [f for f in rep.findings if f.kind == "lock-reentry"]
    assert len(hits) == 1
    assert hits[0].line == 8


WAIT_HOLDING_SRC = """\
from trino_tpu.analysis.witness import named_condition, named_lock

_outer = named_lock("wh._outer")
_cv = named_condition("wh._cv")


def stall():
    with _outer:
        with _cv:
            _cv.wait()
"""


def test_static_wait_while_holding_flagged():
    rep = analyze_sources({"wh": ("wh.py", WAIT_HOLDING_SRC)})
    hits = [f for f in rep.findings if f.kind == "wait-while-holding"]
    assert len(hits) == 1
    assert "wh._outer" in hits[0].message


def test_full_package_is_clean():
    """The committed tree must analyze clean (this test is the gate)."""
    rep = analyze_package()
    assert rep.files > 100
    assert len(rep.graph.locks) > 40
    assert rep.graph.sites > 200
    assert rep.ok, "\n".join(
        f"[{f.kind}] {f.file}:{f.line}: {f.message}" for f in rep.findings
    )


# -- runtime witness ---------------------------------------------------

def test_witness_order_violation_raises_typed_error():
    a = named_lock("t16.order_a")
    b = named_lock("t16.order_b")
    with a:
        with b:
            pass  # establishes a -> b
    with b:
        with pytest.raises(LockOrderError) as ei:
            a.acquire()
    err = ei.value
    assert err.lock_a == "t16.order_b"
    assert err.lock_b == "t16.order_a"
    assert err.stack_a and err.stack_b  # both sites captured
    assert violation_count() == 1


def test_witness_transitive_violation_detected():
    a = named_lock("t16.tr_a")
    b = named_lock("t16.tr_b")
    c = named_lock("t16.tr_c")
    with a:
        with b:
            pass
    with b:
        with c:
            pass
    # a -> b -> c witnessed; c before a contradicts transitively
    with c:
        with pytest.raises(LockOrderError):
            a.acquire()


def test_witness_same_thread_reentry_raises():
    mu = named_lock("t16.reentry")
    with mu:
        with pytest.raises(LockOrderError) as ei:
            mu.acquire()
    assert ei.value.lock_a == ei.value.lock_b == "t16.reentry"
    # the failed re-entry must not have corrupted the held stack
    assert not mu.locked()


def test_witness_rlock_reentry_allowed():
    mu = named_rlock("t16.rlock")
    with mu:
        with mu:
            assert mu._is_owned()
    assert not mu._is_owned()


def test_witness_condition_wait_releases_recursion():
    cv = named_condition("t16.cv")
    hits = []

    def waiter():
        with cv:
            hits.append("waiting")
            cv.wait(timeout=5.0)
            hits.append("woke")

    t = threadreg.spawn("t16-cv-waiter", waiter, daemon=False)
    for _ in range(500):
        if hits:
            break
        time.sleep(0.01)
    with cv:
        cv.notify_all()
    t.join(timeout=5.0)
    assert hits == ["waiting", "woke"]


def test_witness_seed_order_preloads_static_edges():
    added = seed_order([("t16.seed_a", "t16.seed_b")])
    assert added == 1
    a = named_lock("t16.seed_a")
    b = named_lock("t16.seed_b")
    with b:
        with pytest.raises(LockOrderError):
            a.acquire()


def test_witness_distinct_instances_same_name_no_false_positive():
    # per-replica locks share a name; no instance-level order exists
    r0 = named_lock("t16.replica._lock")
    r1 = named_lock("t16.replica._lock")
    with r0:
        with r1:
            pass


# -- thread registry ---------------------------------------------------

def test_threadreg_spawn_tracks_name_and_owner():
    done = threading.Event()
    t = threadreg.spawn(
        "t16-worker", done.wait, args=(5.0,), daemon=False, owner="t16"
    )
    live = threadreg.THREADS.live()
    assert ("t16-worker", "t16", False) in live
    done.set()
    t.join(timeout=5.0)
    assert not any(n == "t16-worker" for n, _o, _d in threadreg.THREADS.live())


def test_threadreg_non_daemon_leak_reported_then_cleared():
    stop = threading.Event()
    t = threadreg.spawn(
        "t16-leak", stop.wait, args=(10.0,), daemon=False, owner="t16"
    )
    leaks = threadreg.THREADS.non_daemon_leaks()
    assert any(s.startswith("t16-leak ") for s in leaks)
    stop.set()
    t.join(timeout=5.0)
    assert not any(
        s.startswith("t16-leak ")
        for s in threadreg.THREADS.non_daemon_leaks()
    )


def test_threadreg_join_all_by_owner():
    evs = [threading.Event() for _ in range(3)]
    for i, ev in enumerate(evs):
        threadreg.spawn(
            f"t16-ja-{i}", ev.wait, args=(10.0,), daemon=False, owner="t16ja"
        )
    for ev in evs:
        ev.set()
    assert not threadreg.THREADS.join_all(timeout=5.0, owner="t16ja")


# -- regression tests for the analyzer-surfaced races ------------------

def test_last_run_info_publish_is_atomic():
    """Seed race: run() did LAST_RUN_INFO.clear() then .update() —
    a concurrent reader could observe the empty dict. The accessor
    pair must never expose a half-published snapshot."""
    from trino_tpu.parallel import mesh_chunk

    payload = {"chunks": 4, "resumes": 0, "chunked": True}
    mesh_chunk.publish_run_info(dict(payload))
    stop = threading.Event()
    bad = []

    def reader():
        while not stop.is_set():
            snap = mesh_chunk.last_run_info()
            if snap and set(snap) != set(payload):
                bad.append(snap)

    threads = [
        threadreg.spawn(f"t16-lri-{i}", reader, daemon=False, owner="t16lri")
        for i in range(2)
    ]
    for _ in range(300):
        mesh_chunk.publish_run_info(dict(payload))
    stop.set()
    assert not threadreg.THREADS.join_all(timeout=5.0, owner="t16lri")
    assert not bad, f"reader saw a torn snapshot: {bad[:3]}"
    del threads


def test_mesh_counters_concurrent_bumps_all_land():
    """Seed race: MESH_COUNTERS[...] += 1 from concurrent query
    threads could drop increments (read-modify-write)."""
    from trino_tpu.parallel.mesh_plan import bump_mesh_counter, mesh_counter

    before = mesh_counter("queries")
    N, PER = 4, 500

    def bump():
        for _ in range(PER):
            bump_mesh_counter("queries")

    ts = [
        threadreg.spawn(f"t16-mc-{i}", bump, daemon=False, owner="t16mc")
        for i in range(N)
    ]
    assert not threadreg.THREADS.join_all(timeout=10.0, owner="t16mc")
    assert mesh_counter("queries") == before + N * PER
    del ts


def test_global_fn_cache_returns_one_identity():
    """Seed race: the unlocked check-then-insert in _global_update_fn
    could mint two jitted callables for one agg spec; every caller must
    get the same object (dispatch caches key on identity)."""
    from trino_tpu.exec.operators import (
        _GLOBAL_FN_CACHE,
        AggSpec,
        _global_update_fn,
    )
    from trino_tpu import types as T

    spec = (AggSpec("count", None, T.BIGINT),)
    _GLOBAL_FN_CACHE.pop((spec, ()), None)
    got = []

    def fetch():
        got.append(_global_update_fn(spec))

    ts = [
        threadreg.spawn(f"t16-fc-{i}", fetch, daemon=False, owner="t16fc")
        for i in range(4)
    ]
    assert not threadreg.THREADS.join_all(timeout=30.0, owner="t16fc")
    assert len(got) == 4
    assert all(g is got[0] for g in got)
    del ts
