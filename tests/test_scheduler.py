"""Preemptive mesh multi-tenancy (runtime/scheduler.py, PR 18).

The MeshScheduler arbitrates one mesh resource at chunk granularity:
weighted-fair virtual-time accounting between resource groups, a fast
lane whose arrivals preempt the running analytic at the next chunk
boundary, and park/resume — the preempted query's device carries
snapshot to the host checkpoint store and the query later resumes from
chunk k warm. These tests pin the scheduler invariants:

  - weighted-fair share convergence: two contending groups' completed
    chunk counts converge to their weight ratio;
  - no starvation: the lowest-weight group still progresses under a
    much heavier competitor, and an idle group REJOINS at the current
    global pass (sleeping never banks catch-up credit);
  - park byte-identity at every chunk index: wherever the fast-lane
    arrival lands, the parked-and-resumed analytic answers exactly the
    uninterrupted run's rows, with zero re-executed chunk-steps and
    zero new XLA lowerings;
  - a deadline firing WHILE PARKED kills typed (EXCEEDED_TIME_LIMIT,
    parked context in the message), the snapshot is discarded, and the
    query never resumes;
  - park-budget refusal degrades to run-to-completion — never query
    failure — and the fast waiter is served via an in-place yield;
  - drain-failover work stealing: a draining replica's unstarted chunk
    range splits across two siblings and merges byte-identically.
"""

import threading
import time

import pytest

from trino_tpu.connectors.tpch import create_tpch_connector
from trino_tpu.engine import Session
from trino_tpu.parallel import mesh_chunk
from trino_tpu.recovery import CHECKPOINTS
from trino_tpu.runtime import DistributedQueryRunner
from trino_tpu.runtime.metrics import METRICS
from trino_tpu.runtime.query_tracker import (
    EXCEEDED_TIME_LIMIT,
    QueryDeadlineError,
)
from trino_tpu.runtime.scheduler import MeshScheduler, parse_group_weights

# exact-valued aggregates only: park/resume and steal-merge must be
# byte-identical to the uninterrupted run
ANALYTIC = (
    "select l_returnflag, count(*) c, sum(l_quantity) q from lineitem "
    "group by l_returnflag order by l_returnflag"
)
# dimension-decorated point lookup: serving/admission.is_fast_lane
POINT = (
    "select n_name, r_name from nation join region "
    "on n_regionkey = r_regionkey where n_nationkey = 3"
)


def mk_runner(**session_kw):
    # tiny-SF lineitem is ~7.5k rows/shard on the full-width mesh:
    # 2048-row chunks -> K=4 boundaries to preempt at
    kw = dict(mesh_chunk_rows=2048)
    kw.update(session_kw)
    r = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny", **kw),
        n_workers=2, hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())
    return r


@pytest.fixture(autouse=True)
def _clean_scheduler_state():
    CHECKPOINTS.clear()
    mesh_chunk.MESH_FAULT_HOOK = None
    yield
    CHECKPOINTS.clear()
    mesh_chunk.MESH_FAULT_HOOK = None


# -- weighted fairness (pure scheduler, synthetic chunk clock) ----------


def contend(weights, total_chunks, dt=0.01, min_slice=1):
    """Drive one MeshScheduler with one thread per group, each charging
    `dt` per synthetic chunk, until `total_chunks` complete across all
    groups. Returns per-group completed-chunk counts (only chunks run
    while the contention was live)."""
    sched = MeshScheduler(name="unit", min_slice_chunks=min_slice)
    counts = {g: 0 for g in weights}
    stop = threading.Event()
    barrier = threading.Barrier(len(weights))

    def drive(group, weight):
        job = sched.submit(f"q-{group}", group=group, weight=weight)
        # synthetic-clock harness: mark the seat ready at submit so it
        # exerts fair-share pressure even before this thread is
        # scheduled into its acquire (real queries flip ready when
        # their host prep finishes and acquire blocks)
        job.ready = True
        barrier.wait()  # all seats queued before anyone runs
        sched.acquire(job)
        try:
            done = 0
            while not stop.is_set():
                done += 1
                counts[group] += 1
                if sum(counts.values()) >= total_chunks:
                    stop.set()
                    return
                job.boundary(done, 1 << 30, dt)
        finally:
            sched.finish(job)

    threads = [
        threading.Thread(target=drive, args=(g, w), daemon=True)
        for g, w in weights.items()
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
        assert not t.is_alive(), "scheduler unit thread wedged"
    return counts, sched


def test_weighted_fair_share_converges_to_weight_ratio():
    """Two groups at weight 2:1 contending for 600 chunks complete
    chunks in ~2:1 — each chunk charges dt/weight to the holder's
    virtual-time account and the laggard preempts at the boundary."""
    counts, _ = contend({"heavy": 2.0, "light": 1.0}, 600)
    ratio = counts["heavy"] / max(counts["light"], 1)
    assert 1.6 <= ratio <= 2.6, f"expected ~2:1, got {counts}"


def test_no_starvation_of_lowest_weight_group():
    """A 50:1 weight split still grants the light group its
    proportional slices — weighted fairness shares, it never excludes."""
    counts, _ = contend({"hog": 50.0, "mouse": 1.0}, 400)
    assert counts["mouse"] >= 2, f"lowest-weight group starved: {counts}"
    assert counts["hog"] > counts["mouse"]


def test_idle_group_rejoins_at_current_pass():
    """A group that slept through 50 chunks joins at the current global
    pass — equal virtual time, no banked credit to monopolize the mesh
    paying back history."""
    sched = MeshScheduler(name="unit")
    a = sched.submit("q-busy", group="busy")
    sched.acquire(a)
    for i in range(1, 51):
        a.boundary(i, 100, 0.01)  # uncontended: keeps the grant
    b = sched.submit("q-late", group="late")
    v = sched.stats()["vtime"]
    assert v["late"] >= v["busy"] - 1e-9, (
        f"late group banked credit while idle: {v}"
    )
    sched.finish(a)
    sched.finish(b)


def test_parse_group_weights_skips_malformed_entries():
    assert parse_group_weights("etl=1,serving=4") == {
        "etl": 1.0, "serving": 4.0,
    }
    # typos must never fail dispatch: bad entries drop, good ones stay
    assert parse_group_weights("etl=x,=3,serving=2,loner") == {
        "serving": 2.0,
    }
    assert parse_group_weights("") == {}


# -- park/resume on the real mesh ---------------------------------------


def spawn_point_at(r, sched, target, state):
    """MESH_FAULT_HOOK: at analytic chunk `target`, start POINT on a
    side thread and hold the boundary until its fast-lane seat is
    visible in the run queue — the NEXT boundary then parks
    deterministically."""
    main = threading.current_thread()

    def hook(k, K):
        if threading.current_thread() is not main:
            return  # the point lookup's own chunk loop
        if state["fired"] or k != target:
            return
        state["fired"] = True

        def run_point():
            state["point_rows"] = r.execute(POINT).rows

        threading.Thread(target=run_point, daemon=True).start()
        deadline = time.monotonic() + 10.0
        while (
            sched.waiting_count(fast=True) < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.002)

    return hook


def await_point(state, timeout=10.0):
    deadline = time.monotonic() + timeout
    while state["point_rows"] is None and time.monotonic() < deadline:
        time.sleep(0.002)
    return state["point_rows"]


def test_park_byte_identity_at_every_chunk_index():
    """Wherever the fast-lane lookup lands (park at chunk 1..K-1), the
    preempted analytic resumes to exactly the uninterrupted rows, with
    zero re-executed chunk-steps and zero new XLA lowerings."""
    r = mk_runner()
    clean = r.execute(ANALYTIC).rows  # warm analytic
    K = int(mesh_chunk.LAST_RUN_INFO["chunks"])
    assert K >= 3, f"query too small to exercise every index ({K})"
    point_clean = r.execute(POINT).rows  # warm point shape
    sched = r._mesh_scheduler
    assert sched is not None, "scheduled dispatch did not engage"
    compiles0 = METRICS.snapshot().get("xla_compiles", 0.0)

    for target in range(K - 1):  # hook at k parks at boundary k+1
        state = {"fired": False, "point_rows": None}
        parks0, resumes0 = sched.parks, sched.resumes
        steps0 = METRICS.snapshot().get("mesh.chunk_steps", 0.0)
        mesh_chunk.MESH_FAULT_HOOK = spawn_point_at(
            r, sched, target, state
        )
        try:
            rows = r.execute(ANALYTIC).rows
        finally:
            mesh_chunk.MESH_FAULT_HOOK = None
        assert state["fired"], f"hook never fired at chunk {target}"
        assert rows == clean, f"park at chunk {target + 1} changed rows"
        info = mesh_chunk.LAST_RUN_INFO
        assert info["parks"] == 1 and info["unparks"] == 1, info
        assert info["executed_chunk_steps"] == K, (
            f"re-executed chunk-steps after park at {target + 1}: {info}"
        )
        assert sched.parks == parks0 + 1
        assert sched.resumes == resumes0 + 1
        assert await_point(state) == point_clean
        # analytic K steps + the point lookup's own single chunk
        steps = METRICS.snapshot().get("mesh.chunk_steps", 0.0) - steps0
        assert steps == K + 1, f"unexpected step ledger delta {steps:g}"

    compiles = METRICS.snapshot().get("xla_compiles", 0.0) - compiles0
    assert compiles == 0, (
        f"park/resume cycles lowered {compiles:g} new XLA programs"
    )
    assert CHECKPOINTS.parked_count() == 0, "leaked parked snapshot"


def test_deadline_while_parked_kills_typed_and_never_resumes():
    """A wall deadline expiring while the query sits PARKED raises the
    typed EXCEEDED_TIME_LIMIT error out of the parked wait — with the
    parked context in the message — discards the snapshot, and the
    query never resumes. The occupying fast seat is synthetic, so the
    park wait provably outlives the budget."""
    r = mk_runner()
    clean = r.execute(ANALYTIC).rows  # warm
    sched = r._mesh_scheduler
    main = threading.current_thread()
    state = {"fake": None}

    def hook(k, K):
        if threading.current_thread() is not main:
            return
        if state["fake"] is None and k == 1:
            # a fast seat that never runs: the analytic parks at the
            # next boundary and stays parked until the deadline fires
            state["fake"] = sched.submit("fake-point", fast=True)
            # synthetic waiter: never calls acquire, so mark it ready
            # by hand — only ready waiters exert preemption pressure
            state["fake"].ready = True

    # slow the tracker tick so the park-wait poll — not the background
    # enforcement thread — is what kills the query
    r.query_tracker.tick_interval_s = 60.0
    r.session.query_max_execution_time_s = 0.5
    parks0, resumes0 = sched.parks, sched.resumes
    mesh_chunk.MESH_FAULT_HOOK = hook
    try:
        with pytest.raises(QueryDeadlineError) as ei:
            r.execute(ANALYTIC)
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
        if state["fake"] is not None:
            sched.finish(state["fake"])
    msg = str(ei.value)
    assert EXCEEDED_TIME_LIMIT in msg
    assert "parked" in msg, f"no parked context in kill message: {msg}"
    assert sched.parks == parks0 + 1
    assert sched.resumes == resumes0, "a dead query must never resume"
    assert CHECKPOINTS.parked_count() == 0, "kill must discard the park"

    # the rerun starts FRESH — no resume from the dead query's state
    r.session.query_max_execution_time_s = 0.0
    assert r.execute(ANALYTIC).rows == clean
    info = mesh_chunk.LAST_RUN_INFO
    assert info["resumes"] == 0 and info["parks"] == 0, info


def test_park_budget_refusal_degrades_to_run_to_completion():
    """park_max_bytes too small for the snapshot: the park is REFUSED,
    the analytic keeps its carries and completes correctly (degradation
    is never query failure), and the fast waiter is served via an
    in-place yield instead."""
    r = mk_runner(park_max_bytes=1)
    clean = r.execute(ANALYTIC).rows  # warm
    K = int(mesh_chunk.LAST_RUN_INFO["chunks"])
    assert K >= 4, f"need a boundary after the refusal to yield at ({K})"
    point_clean = r.execute(POINT).rows
    sched = r._mesh_scheduler
    state = {"fired": False, "point_rows": None}
    refusals0, yields0, parks0 = (
        sched.park_refusals, sched.yields, sched.parks,
    )
    mesh_chunk.MESH_FAULT_HOOK = spawn_point_at(r, sched, 1, state)
    try:
        rows = r.execute(ANALYTIC).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    assert state["fired"]
    assert rows == clean, "budget refusal must not change the answer"
    info = mesh_chunk.LAST_RUN_INFO
    assert info["parks"] == 0 and info["unparks"] == 0, info
    assert sched.park_refusals == refusals0 + 1
    assert sched.parks == parks0
    assert sched.yields >= yields0 + 1, (
        "fast waiter not served via in-place yield after refusal"
    )
    assert await_point(state) == point_clean
    assert CHECKPOINTS.parked_count() == 0


def test_batched_lookup_text_takes_the_fast_lane():
    """The micro-batcher sends ONE combined IN-list statement for a
    group of point lookups (serving/batcher.py `_run_group`); on the
    mesh that statement is a fast-lane submission and an analytic is
    not, so coalesced lookups preempt like single ones."""
    r = mk_runner()
    r.execute(ANALYTIC)
    sched = r._mesh_scheduler
    assert (sched.submitted, sched.fast_submitted) == (1, 0)
    rows = r.execute(
        "SELECT o_orderkey, o_custkey FROM orders "
        "WHERE o_orderkey IN (1, 2, 3, 3)"
    ).rows
    assert r._last_data_plane == "mesh", r.last_mesh_fallback
    assert sorted(row[0] for row in rows) == [1, 2, 3]
    assert (sched.submitted, sched.fast_submitted) == (2, 1)


# -- drain-failover work stealing ---------------------------------------


def test_drain_steal_splits_unstarted_chunks_across_siblings():
    """A replica draining mid-run on an all-append-carry query: the
    coordinator splits the unstarted chunk range across TWO siblings —
    the primary resumes [k0, mid) from the portable checkpoint while a
    helper computes [mid, K) from zero carries — and the merge is
    byte-identical with nothing re-executed."""
    r = mk_runner(
        mesh_replicas=4, mesh_chunk_rows=64,
        mesh_checkpoint_interval_chunks=1,
    )
    # scan-filter: every carry is an append accumulator ("out"), the
    # steal-eligible shape (group carries cannot merge byte-identically)
    q = ("select l_orderkey, l_linenumber from lineitem "
         "where l_quantity < 4")
    rows0 = None
    for _ in range(4):  # round-robin placement: warm all four replicas
        rows = r.execute(q).rows
        assert r._last_data_plane == "mesh", r.last_mesh_fallback
        if rows0 is None:
            rows0 = rows
        else:
            assert rows == rows0
    K = int(mesh_chunk.LAST_RUN_INFO["chunks"])
    assert K >= 6, f"query too small to split ({K})"
    rm = r._replicas
    assert rm is not None and rm.n_replicas == 4
    state = {"victim": None, "requested": False}

    def hook(k, K_):
        rep = mesh_chunk.active_replica()
        if rep is None:
            return
        if state["victim"] is None:
            state["victim"] = rep
        if (
            not state["requested"]
            and rep == state["victim"]
            and k >= max(1, K_ // 2)
        ):
            state["requested"] = True
            rm.request_drain(rep)

    steals0 = METRICS.snapshot().get("scheduler.steals", 0.0)
    steps0 = METRICS.snapshot().get("mesh.chunk_steps", 0.0)
    mesh_chunk.MESH_FAULT_HOOK = hook
    try:
        rows = r.execute(q).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    assert state["requested"]
    assert rows == rows0, "steal merge changed the answer"
    assert rm.failovers == 1
    info = mesh_chunk.LAST_RUN_INFO
    assert info["steals"] == 1, f"steal did not complete: {info}"
    assert (
        METRICS.snapshot().get("scheduler.steals", 0.0) == steals0 + 1
    )
    # victim [0, k0) + primary [k0, mid) + helper [mid, K): the whole
    # query executes exactly K chunk-steps across three replicas
    steps = METRICS.snapshot().get("mesh.chunk_steps", 0.0) - steps0
    assert steps == K, f"steal re-executed {steps - K:g} chunk-steps"
    out = r.execute(f"EXPLAIN ANALYZE {q}").rows[0][0]
    assert "steals=1" in out
