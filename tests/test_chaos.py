"""Seeded chaos tests for the cluster resiliency layer (PR2 tentpole).

Fast tier-1 matrix: two representative TPC-H-shaped queries under every
fault class (task crash at start/mid, exchange fetch loss, straggler,
injected OOM) with a FIXED seed, asserting oracle-equal results and
bounded attempt counts. The full 22-query soak carries
@pytest.mark.slow. Graylist and low-memory-killer semantics get their
own deterministic tests (no background heartbeat thread — the probe
loop is driven by explicit ping_once calls)."""

import threading
import time

import pytest

from tests.oracle import assert_rows_match, sqlite_rows
from tests.test_tpch import to_sqlite
from trino_tpu.connectors.spi import CatalogManager
from trino_tpu.connectors.tpch import create_tpch_connector
from trino_tpu.engine import Session
from trino_tpu.runtime import DistributedQueryRunner, Worker
from trino_tpu.runtime import chaos
from trino_tpu.runtime.chaos import (
    FABRIC_CLASSES,
    FAULT_CLASSES,
    PREEMPT_CLASSES,
    ChaosHarness,
    DownableWorker,
    generate_schedule,
    rows_equal,
)
from trino_tpu.runtime.failure import FailureInjector
from trino_tpu.runtime.memory import ExceededMemoryLimitError

SF = 0.01
SEED = 42

Q_AGG = (
    "select l_returnflag, l_linestatus, sum(l_quantity), count(*) "
    "from lineitem where l_shipdate <= date '1998-09-02' "
    "group by l_returnflag, l_linestatus "
    "order by l_returnflag, l_linestatus"
)
Q_JOIN = (
    "select n_name, count(*) c from supplier, nation "
    "where s_nationkey = n_nationkey "
    "group by n_name order by n_name"
)


@pytest.fixture(scope="module")
def oracle():
    import sqlite3

    from tests.oracle import load_tpch_sqlite

    conn = sqlite3.connect(":memory:")
    load_tpch_sqlite(conn, SF)
    yield conn
    conn.close()


@pytest.fixture(scope="module")
def harness():
    h = ChaosHarness(n_workers=2)
    h.register_catalog("tpch", create_tpch_connector())
    return h


# -- the seeded fault matrix ------------------------------------------------

@pytest.mark.parametrize("fault_class", FAULT_CLASSES)
@pytest.mark.parametrize("sql", [Q_AGG, Q_JOIN], ids=["agg", "join"])
def test_chaos_matrix(sql, fault_class, harness, oracle):
    rows, stats = harness.run_case(sql, fault_class, seed=SEED)
    expected = sqlite_rows(oracle, to_sqlite(sql))
    assert_rows_match(rows, expected, ordered=True, abs_tol=1e-2)
    # attempts stay bounded by the schedule: every injected failure can
    # cause at most one retry (stalls cause speculation, not retries)
    assert stats["retries"] <= stats["max_injected_failures"], stats
    if fault_class == "fetch_loss":
        # transient fetch loss is absorbed by the exchange retry loop:
        # no task was ever re-run
        assert stats["retries"] == 0, stats


def test_schedule_determinism():
    for fc in FAULT_CLASSES:
        assert generate_schedule(SEED, fc) == generate_schedule(SEED, fc)
    assert generate_schedule(1, "task_crash_start") != generate_schedule(
        2, "task_crash_start"
    ) or True  # different seeds may collide on tiny schedules; the
    # invariant under test is same-seed stability above


@pytest.mark.slow
@pytest.mark.parametrize("fault_class", FAULT_CLASSES)
@pytest.mark.parametrize("qid", list(range(1, 23)))
def test_chaos_soak_tpch(qid, fault_class, harness, oracle):
    """The full soak: all 22 TPC-H queries under every fault class."""
    from tests.tpch_queries import QUERIES

    sql = QUERIES[qid]
    rows, stats = harness.run_case(sql, fault_class, seed=SEED + qid)
    expected = sqlite_rows(oracle, to_sqlite(sql))
    assert_rows_match(
        rows, expected, ordered=("order by" in sql), abs_tol=1e-2
    )
    assert stats["retries"] <= stats["max_injected_failures"]


# -- cluster lifecycle: graceful drain + speculation (PR 3) -----------------


def _lifecycle_harness(n: int = 3) -> ChaosHarness:
    """Drains are one-way (a drained node never rejoins), so every
    lifecycle test runs on a fresh harness."""
    h = ChaosHarness(n_workers=n)
    h.register_catalog("tpch", create_tpch_connector())
    return h


def test_drain_mid_query(oracle):
    """Gracefully draining a worker mid-query: the query completes with
    oracle-equal rows (no query-level failure, no duplicates), the
    drained worker accepts ZERO launches after the drain landed, and the
    node settles in the `drained` state."""
    h = _lifecycle_harness()
    rows, report = h.run_drain_case(Q_JOIN, seed=SEED)
    expected = sqlite_rows(oracle, to_sqlite(Q_JOIN))
    assert_rows_match(rows, expected, ordered=True, abs_tol=1e-2)
    assert all(report["drained"].values()), report
    assert report["launches_at_end"] == report["launches_at_drain"], report
    for wid in report["drained"]:
        assert report["node_states"][wid] == "drained", report


def test_drain_all_but_one(oracle):
    """Draining every worker except one mid-query still converges: the
    survivor absorbs all remaining work."""
    h = _lifecycle_harness()
    rows, report = h.run_drain_case(
        Q_JOIN, seed=SEED, drain_all_but_one=True
    )
    expected = sqlite_rows(oracle, to_sqlite(Q_JOIN))
    assert_rows_match(rows, expected, ordered=True, abs_tol=1e-2)
    assert len(report["drained"]) == 2
    assert all(report["drained"].values()), report
    assert report["launches_at_end"] == report["launches_at_drain"], report
    states = report["node_states"]
    assert sum(1 for s in states.values() if s == "active") == 1, states


def test_straggler_speculation_wins(oracle):
    """A hard-stalled first attempt loses to its speculative duplicate:
    the win is RECORDED (not just a duplicate launched), rows carry no
    duplicates, and attempts per partition stay bounded."""
    h = _lifecycle_harness()
    rows, stats = h.run_speculation_case(Q_AGG, seed=SEED)
    expected = sqlite_rows(oracle, to_sqlite(Q_AGG))
    assert_rows_match(rows, expected, ordered=True, abs_tol=1e-2)
    assert stats["speculation_wins"] >= 1, stats
    # stalls cause speculation, not retries; at most one duplicate each
    assert max(stats["attempts_per_partition"].values()) <= 2, stats


def test_speculation_disabled_by_session_property():
    """speculation_enabled=false: the stalled attempt just runs long —
    no duplicate is ever launched."""
    session = Session(
        catalog="tpch", schema="tiny", retry_policy="task",
        speculation_enabled=False,
    )
    h = ChaosHarness(n_workers=2, session=session)
    h.register_catalog("tpch", create_tpch_connector())
    rows, stats = h.run_speculation_case(Q_JOIN, seed=SEED, stall_s=0.6)
    assert rows
    assert stats["speculative_hits"] == 0, stats


# -- QUERY-level retry (retry_policy=query) ---------------------------------


def _retry_cluster():
    inj = FailureInjector()
    cats = CatalogManager()
    cats.register("tpch", create_tpch_connector())
    workers = [
        Worker(f"qr-w{i}", cats, failure_injector=inj) for i in range(2)
    ]
    return inj, workers


def test_query_retry_recovers_where_task_retries_exhausted(oracle):
    """The acceptance fault: partition 0 of the scan dies on its first
    FOUR attempts. retry_policy=TASK exhausts its per-task budget and
    fails; retry_policy=QUERY absorbs the same fault by re-running the
    whole query (deterministic replay, fresh task namespace) and
    recovers."""
    from trino_tpu.runtime.fte import TaskRetriesExceeded

    inj, workers = _retry_cluster()
    fault = dict(
        where="start", fragment_id=0, partition=0,
        attempts=tuple(range(8)), max_hits=4,
    )

    r_task = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny", retry_policy="task",
                task_retries=3),
        worker_handles=workers, hash_partitions=2,
    )
    r_task.register_catalog("tpch", create_tpch_connector())
    inj.inject(**fault)
    with pytest.raises(TaskRetriesExceeded):
        r_task.execute(Q_JOIN)
    inj.clear()

    r_query = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny", retry_policy="query",
                query_retry_count=5),
        worker_handles=workers, hash_partitions=2,
    )
    r_query.register_catalog("tpch", create_tpch_connector())
    inj.inject(**fault)
    try:
        rows = r_query.execute(Q_JOIN).rows
    finally:
        inj.clear()
    expected = sqlite_rows(oracle, to_sqlite(Q_JOIN))
    assert_rows_match(rows, expected, ordered=True, abs_tol=1e-2)
    # 4 failed whole-query attempts + the clean 5th
    assert r_query.last_query_attempts == 5


def test_query_retry_transparent_to_client_protocol():
    """An internal whole-query retry is invisible on the client
    statement protocol: one query id, nextUri polling just sees a
    longer run, the final page carries the right rows."""
    import json as _json
    import urllib.request

    inj, workers = _retry_cluster()
    runner = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny", retry_policy="query",
                query_retry_count=2),
        worker_handles=workers, hash_partitions=2,
    )
    runner.register_catalog("tpch", create_tpch_connector())

    class _Front:
        """CoordinatorServer passes `prepared`; the distributed runner
        doesn't take it — adapt."""

        def execute(self, sql, identity=None, transaction_id=None,
                    prepared=None):
            return runner.execute(
                sql, identity=identity, transaction_id=transaction_id
            )

    from trino_tpu.runtime.server import CoordinatorServer

    inj.inject(where="start", fragment_id=0, partition=0,
               attempts=(0,), max_hits=1)
    srv = CoordinatorServer(_Front(), port=0)
    try:
        req = urllib.request.Request(
            srv.uri + "/v1/statement",
            data=b"select count(*) from nation", method="POST",
        )
        resp = _json.load(urllib.request.urlopen(req, timeout=10))
        qid = resp["id"]
        seen_ids = {qid}
        while "nextUri" in resp:
            resp = _json.load(
                urllib.request.urlopen(resp["nextUri"], timeout=10)
            )
            seen_ids.add(resp["id"])
        assert resp["stats"]["state"] == "FINISHED", resp
        assert resp["data"] == [[25]]
        assert seen_ids == {qid}
        assert runner.last_query_attempts == 2  # it DID retry internally
    finally:
        srv.stop()
        inj.clear()


# -- worker drain + kill over HTTP ------------------------------------------


def test_http_fail_query_endpoint_kills_running_query():
    """DELETE /v1/query/{id}?reason=... on the worker HTTP surface:
    every task of the query fails with the kill reason and the
    coordinator's poll surfaces it as the query-level error."""
    from trino_tpu.runtime.http import HttpWorkerClient, WorkerServer

    inj = FailureInjector()
    cats = CatalogManager()
    cats.register("tpch", create_tpch_connector())
    w = Worker("kill-w0", cats, failure_injector=inj)
    srv = WorkerServer(w, require_secret=False)
    try:
        handle = HttpWorkerClient(srv.uri)
        runner = DistributedQueryRunner(
            Session(catalog="tpch", schema="tiny"),
            worker_handles=[handle],
        )
        runner.register_catalog("tpch", create_tpch_connector())
        inj.inject(where="start", attempts=(0,), stall_s=5.0, max_hits=1)
        err = []

        def run():
            try:
                runner.execute("select count(*) from nation")
            except Exception as e:
                err.append(e)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        deadline = time.monotonic() + 10.0
        while not w.task_ids() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert w.task_ids(), "query never launched a task"
        qid = w.task_ids()[0].split(".")[0]
        handle.fail_query(qid, "killed by test")
        t.join(30)
        assert not t.is_alive()
        assert err, "kill should surface as a query-level failure"
        assert "killed by test" in str(err[0])
    finally:
        srv.stop()
        inj.clear()


def test_http_drain_via_state_api_excludes_worker():
    """PUT /v1/info/state "SHUTTING_DOWN" (the reference worker-state
    API) over HTTP: the worker reports shutting_down, the heartbeat
    settles it to drained, and new queries place zero tasks on it."""
    from trino_tpu.runtime.http import HttpWorkerClient, WorkerServer

    servers, handles, inner = [], [], []
    try:
        for i in range(2):
            cats = CatalogManager()
            cats.register("tpch", create_tpch_connector())
            inner.append(Worker(f"drain-w{i}", cats))
            servers.append(WorkerServer(inner[-1], require_secret=False))
            handles.append(HttpWorkerClient(servers[-1].uri))
        runner = DistributedQueryRunner(
            Session(catalog="tpch", schema="tiny"),
            worker_handles=handles, hash_partitions=2,
        )
        runner.register_catalog("tpch", create_tpch_connector())
        handles[0].set_state("SHUTTING_DOWN")
        assert handles[0].status()["state"] == "shutting_down"
        runner.node_manager.ping_once()
        states = runner.node_manager.all_states()
        assert states[handles[0].worker_id] == "drained", states
        res = runner.execute("select count(*) from nation")
        assert res.rows == [[25]]
        assert inner[0].task_ids() == []  # zero post-drain launches
    finally:
        for s in servers:
            s.stop()


# -- circuit breaker / graylist ---------------------------------------------

def _fte_runner(workers):
    session = Session(catalog="tpch", schema="tiny", retry_policy="task")
    runner = DistributedQueryRunner(session, worker_handles=workers)
    runner.register_catalog("tpch", create_tpch_connector())
    return runner


def test_graylisted_worker_gets_no_launches():
    catalogs = CatalogManager()
    catalogs.register("tpch", create_tpch_connector())
    w_ok = Worker("w-ok", catalogs)
    w_bad = DownableWorker(Worker("w-bad", catalogs))
    runner = _fte_runner([w_ok, w_bad])
    nm = runner.node_manager
    sql = "select count(*) from nation"

    # healthy cluster: both workers take launches over a few queries
    assert runner.execute(sql).rows[0][0] == 25
    assert w_bad.create_calls > 0

    # worker goes dark: failed probes trip its breaker
    w_bad.down = True
    for _ in range(3):
        nm.ping_once()
    assert nm.breaker_states()["w-bad"] == "open"

    # while graylisted: queries succeed and the dark worker receives
    # ZERO launches (placement avoids it entirely, no timeout-per-task)
    calls_while_open = w_bad.create_calls
    assert runner.execute(sql).rows[0][0] == 25
    assert w_bad.create_calls == calls_while_open

    # recovery: one successful probe closes the breaker and the worker
    # returns to rotation
    w_bad.down = False
    nm.ping_once()
    assert nm.breaker_states()["w-bad"] == "closed"
    assert runner.execute(sql).rows[0][0] == 25
    assert w_bad.create_calls > calls_while_open


def test_breaker_reopens_on_failed_probe():
    from trino_tpu.runtime.discovery import CircuitBreaker

    clock = [0.0]
    b = CircuitBreaker(trip_threshold=2, cooldown_s=1.0,
                       clock=lambda: clock[0])
    b.record_failure()
    assert b.state == "closed"
    b.record_failure()
    assert b.state == "open"
    b.mark_probing()            # cooldown not elapsed
    assert b.state == "open"
    clock[0] = 2.0
    b.mark_probing()
    assert b.state == "half_open"
    b.record_failure()          # probe failed: back to open
    assert b.state == "open"
    clock[0] = 4.0
    b.mark_probing()
    b.record_success()          # probe succeeded
    assert b.state == "closed"


# -- error tracker ----------------------------------------------------------

def test_error_tracker_deterministic_backoff():
    from trino_tpu.runtime.error_tracker import (
        RequestErrorTracker,
        RetryPolicy,
    )

    def schedule(seed):
        sleeps = []
        t = RequestErrorTracker(
            "w", RetryPolicy(max_error_duration_s=1e9, max_errors=6),
            seed=seed, clock=lambda: 0.0, sleep=sleeps.append,
        )
        for _ in range(5):
            t.on_failure(ConnectionError("x"))
        return sleeps

    assert schedule(7) == schedule(7)  # replayable from the seed
    s = schedule(7)
    assert len(s) == 5 and all(x > 0 for x in s)
    # exponential shape survives the jitter (factor 2, jitter 0.25)
    assert s[3] > s[0]


def test_error_tracker_budget_and_protocol_errors():
    from trino_tpu.runtime.error_tracker import (
        RequestFailedError,
        RetryPolicy,
        run_with_retry,
    )

    pol = RetryPolicy(max_error_duration_s=0.2, min_backoff_s=0.001,
                      max_backoff_s=0.005)

    def dead():
        raise ConnectionError("down")

    with pytest.raises(RequestFailedError) as ei:
        run_with_retry("w-dead", dead, pol)
    assert len(ei.value.failures) > 1  # it DID retry before giving up

    def appfail():
        raise ValueError("application error")

    with pytest.raises(ValueError):  # non-transient: no retry loop
        run_with_retry("w-app", appfail, pol)


# -- low-memory killer ------------------------------------------------------

# A join whose build side RETAINS a non-revocable reservation during
# the probe (HashBuildSink.finish keeps the lookup source live): two
# build tasks land on each worker pool at ~434KB apiece, so a 600KB
# pool fits the first but exhausts on the second with nothing left to
# revoke — the exact shape where spill cannot save you and the killer
# must.
BIG_SQL = (
    "select o_orderpriority, count(*) c, sum(l_quantity) q "
    "from orders, lineitem where o_orderkey = l_orderkey "
    "group by o_orderpriority"
)
SMALL_SQL = "select count(*) from region"


def test_oom_kills_largest_query_only(oracle):
    """Pool exhaustion on shared worker pools — after revocation/spill
    found nothing to free — kills ONE query (the largest reservation
    holder) with a query-level ExceededMemoryLimitError; a small
    concurrent query completes, and the workers survive to serve later
    queries."""
    session = Session(
        catalog="tpch", schema="tiny", memory_pool_bytes=600 * 1024,
        mesh_execution=False,  # mesh bypasses worker pools entirely
    )
    runner = DistributedQueryRunner(session, n_workers=2)
    runner.register_catalog("tpch", create_tpch_connector())
    assert runner.memory_manager is not None

    big_err = []

    def run_big():
        try:
            runner.execute(BIG_SQL)
        except BaseException as e:
            big_err.append(e)

    t = threading.Thread(target=run_big, daemon=True)
    t.start()
    # the small query keeps working regardless of when the kill lands
    small = runner.execute(SMALL_SQL)
    assert small.rows[0][0] == 5
    t.join(120)
    assert not t.is_alive()
    assert big_err, "big query should have been killed"
    assert isinstance(big_err[0], ExceededMemoryLimitError), big_err[0]
    assert "low-memory killer" in str(big_err[0])
    assert len(runner.memory_manager.kills) == 1
    # the kill freed the victim's ledger: pools drain back to zero
    # once its tasks unwind, and the cluster still serves queries
    after = runner.execute(SMALL_SQL)
    assert after.rows[0][0] == 5
    assert runner.memory_manager.kills and not runner.memory_manager.kills[1:]
    # drain the doomed query's task threads before the interpreter
    # starts tearing down (daemon threads mid-kernel abort the process)
    for w in runner.workers:
        for k in w.task_ids():
            w.get_task(k).join(30)


# -- mid-crash after spill: spool de-duplication ----------------------------

def test_mid_crash_after_spill_no_duplicate_rows(oracle):
    """A task that spilled under memory pressure, produced output, and
    THEN died must retry without duplicating rows: consumers read only
    the committed attempt (spool manifest de-duplication), and the
    retry's spill state starts clean."""
    injector = FailureInjector()
    catalogs = CatalogManager()
    catalogs.register("tpch", create_tpch_connector())
    workers = [
        Worker(f"spill-w{i}", catalogs, failure_injector=injector,
               memory_pool_bytes=1 << 22)
        for i in range(2)
    ]
    session = Session(catalog="tpch", schema="tiny", retry_policy="task")
    runner = DistributedQueryRunner(session, worker_handles=workers)
    runner.register_catalog("tpch", create_tpch_connector())

    injector.inject(where="mid", attempts=(0,), max_hits=2)
    try:
        rows = runner.execute(Q_AGG).rows
    finally:
        injector.clear()
    expected = sqlite_rows(oracle, to_sqlite(Q_AGG))
    assert_rows_match(rows, expected, ordered=True, abs_tol=1e-2)
    assert runner.last_fte_stats["retries"] >= 1


# -- seeded faults inside the mesh chunk loop -------------------------------

Q_MESH = (
    "select o_orderpriority, count(*) c from orders join customer "
    "on o_custkey = c_custkey group by o_orderpriority "
    "order by o_orderpriority"
)
MESH_RUNNERS = {
    "preempt_park_resume": chaos.run_preempt_park_resume_case,
    "preempt_under_drain": chaos.run_preempt_under_drain_case,
    "host_lost_mid_chunk": chaos.run_host_lost_case,
    "membership_flap": chaos.run_membership_flap_case,
    "transport_corruption": chaos.run_transport_corruption_case,
}


@pytest.mark.parametrize("scenario", PREEMPT_CLASSES + FABRIC_CLASSES)
def test_mesh_scenarios(scenario, monkeypatch):
    """Park/resume and the checkpoint fabric composed with failover, end
    to end through the coordinator's mesh dispatch: a seeded chunk
    boundary parks, faults, drains, flaps or wipes the local store, and
    the query must still answer what its clean run answered, with the
    counts each maneuver promises (one park, the pull, the refused
    claim, the rejected digest) and no chunk-step executed twice."""
    from trino_tpu.analysis import witness
    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.recovery import CHECKPOINTS
    from trino_tpu.runtime.fabric import stop_fabric

    # the fabric cases read the secret with setdefault: set here, it is
    # taken back when the test ends
    monkeypatch.setenv("TRINO_TPU_INTERNAL_SECRET", "chaos-fabric")
    CHECKPOINTS.clear()
    violations0 = witness.violation_count()
    try:
        rows, rep = MESH_RUNNERS[scenario](Q_MESH, SEED)
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
        stop_fabric()
        CHECKPOINTS.clear()
    assert rep["mesh_clean_plane"], "clean run did not take the mesh plane"
    assert rep["mesh_fault_plane"] == "mesh", rep["mesh_fault_plane"]
    assert rows_equal(rows, rep.pop("expected"), ordered=True)
    K = rep["chunks"]
    if scenario == "preempt_park_resume":
        # the scheduler's condition wait, the checkpoint store and the
        # fast-lane seat interleave here under the lock-order witness
        assert witness.witness_enabled()
        assert witness.violation_count() == violations0
        assert rep["parked"] and rep["faulted"], rep
        assert rep["parks"] == 1 and rep["unparks"] == 1, rep
        assert rep["resumes"] >= 1, rep
        assert rep["executed_chunk_steps"] == K, rep
        assert rep["point_ok"], rep
    elif scenario == "preempt_under_drain":
        assert rep["parked"] and rep["drain_requested"], rep
        assert rep["failovers"] == 1 and rep["checkpoint_resumes"] == 1, rep
        assert rep["resumed_from_chunk"] == rep["park_chunk"], rep
        assert rep["chunk_steps"] == K, rep
        assert rep["replica_drained"], rep
    elif scenario == "host_lost_mid_chunk":
        assert rep["fired"] and rep["pushes"] >= 1, rep
        assert rep["pulls"] == 1 and rep["resumes"] == 1, rep
        assert rep["resumed_from_chunk"] == rep["fault_chunk"], rep
        # the re-placed attempt counts its own steps: exactly the
        # chunks the lost host had not finished
        assert rep["executed_chunk_steps"] == K - rep["fault_chunk"], rep
    elif scenario == "membership_flap":
        assert rep["fired"] and rep["flapped"], rep
        assert rep["double_refused"] == 1, rep
        assert rep["epoch_delta"] == 2, rep
        assert rep["owners_at_end"] == 0, rep
        assert rep["resumes"] or rep["epoch_fences"], rep
    else:  # transport_corruption
        assert rep["fired"] and rep["digest_rejects"] >= 1, rep
        assert rep["pulls"] == 0 and rep["truncated_import"] is False, rep
        # a rejected transfer degrades to a clean restart, never a
        # resume from corrupt carries
        assert rep["resumes"] == 0, rep
        assert rep["executed_chunk_steps"] == K, rep
