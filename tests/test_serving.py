"""Serving tier (PR 8): prepared-statement plan cache, typed EXECUTE
parameter binding, lane-based admission with overload shedding,
inter-query micro-batching, and the worker-local deadline check.

The plan-cache tests assert BOTH halves of the contract: a hit must be
observable in the counters (or the cache is decorative) AND the reused
plan must produce oracle-equal rows (or the cache is wrong). Property
flips and DML must miss/invalidate — a stale physical plan captures
split listings, i.e. a data snapshot.
"""

import threading
import time
import urllib.error

import pytest

from tests.oracle import assert_rows_match, oracle_rows
from trino_tpu.connectors.memory import create_memory_connector
from trino_tpu.connectors.tpch import create_tpch_connector
from trino_tpu.engine import LocalQueryRunner, Session
from trino_tpu.serving.admission import (
    AdmissionPipeline,
    OverloadSheddedError,
    fast_path_probe,
)
from trino_tpu.serving.batcher import MicroBatcher, classify
from trino_tpu.serving.params import ParameterBindingError
from trino_tpu.serving.plan_cache import PlanCache

SF = 0.01

Q_POINT = "select o_custkey, o_totalprice from orders where o_orderkey = 7"
Q_AGG = (
    "select l_returnflag, count(*) c from lineitem "
    "group by l_returnflag order by l_returnflag"
)


@pytest.fixture()
def runner():
    r = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    r.register_catalog("tpch", create_tpch_connector())
    return r


# -- plan cache -------------------------------------------------------------


def test_plan_cache_hit_is_oracle_equal(runner):
    cache = runner._plan_cache
    first = runner.execute(Q_AGG).rows
    h0 = cache.hits
    # a whitespace/case variant must canonicalize onto the same entry
    variant = Q_AGG.replace("select", "SELECT  ").replace("  c ", " c ")
    again = runner.execute(variant).rows
    assert cache.hits > h0, cache.stats()
    expected = oracle_rows(SF, Q_AGG)
    assert_rows_match(first, expected, ordered=True)
    assert_rows_match(again, expected, ordered=True)


def test_plan_cache_property_change_misses(runner):
    runner.execute(Q_POINT)
    cache = runner._plan_cache
    m0, h0 = cache.misses, cache.hits
    runner.execute(Q_POINT)
    assert cache.hits == h0 + 1 and cache.misses == m0
    # flipping a plan-affecting session property must MISS, not serve
    # the stale shape (SET SESSION never needs to invalidate)
    runner.session.enable_dynamic_filtering = (
        not runner.session.enable_dynamic_filtering
    )
    rows = runner.execute(Q_POINT).rows
    assert cache.misses == m0 + 1, cache.stats()
    assert_rows_match(rows, oracle_rows(SF, Q_POINT), ordered=False)


def test_plan_cache_invalidated_by_dml():
    r = LocalQueryRunner(Session(catalog="memory", schema="default"))
    r.register_catalog("memory", create_memory_connector())
    r.execute("CREATE TABLE t (a bigint)")
    r.execute("INSERT INTO t VALUES (1), (2)")
    assert r.execute("SELECT count(*) FROM t").only_value() == 2
    inv0 = r._plan_cache.invalidations
    r.execute("INSERT INTO t VALUES (3)")
    assert r._plan_cache.invalidations > inv0
    # the recount must NOT come from a plan that captured the old splits
    assert r.execute("SELECT count(*) FROM t").only_value() == 3


def test_plan_cache_lru_bound():
    c = PlanCache(max_entries=2)
    s = Session(catalog="tpch", schema="tiny")
    keys = [c.key(f"select {i}", s) for i in range(3)]
    for i, k in enumerate(keys):
        c.store(k, ("plan", i))
    assert len(c) == 2 and c.evictions == 1
    assert c.lookup(keys[0]) is None  # oldest evicted
    assert c.lookup(keys[2]) == ("plan", 2)
    # lookup refreshes recency: storing a 4th now evicts keys[1]
    c.store(c.key("select 3", s), ("plan", 3))
    assert c.lookup(keys[2]) == ("plan", 2)
    assert c.lookup(keys[1]) is None


def test_plan_cache_stale_generation_not_stored():
    c = PlanCache()
    s = Session(catalog="tpch", schema="tiny")
    k = c.key("select 1", s)
    gen = c.generation
    c.invalidate()  # DDL lands while the planner is mid-flight
    c.store(k, "stale-plan", generation=gen)
    assert c.contains(k) is False


# -- typed EXECUTE ... USING binding ----------------------------------------


def test_execute_using_repeat_binding_hits_cache(runner):
    runner.execute(
        "PREPARE pq FROM select o_custkey from orders where o_orderkey = ?"
    )
    cache = runner._plan_cache
    first = runner.execute("EXECUTE pq USING 7").rows
    h0 = cache.hits
    again = runner.execute("EXECUTE pq USING 7").rows
    assert cache.hits > h0, cache.stats()
    assert first == again
    assert_rows_match(
        first,
        oracle_rows(SF, "select o_custkey from orders where o_orderkey = 7"),
        ordered=False,
    )


def test_execute_using_arity_error(runner):
    runner.execute(
        "PREPARE p1 FROM select o_custkey from orders where o_orderkey = ?"
    )
    with pytest.raises(ParameterBindingError, match="expects 1 parameter"):
        runner.execute("EXECUTE p1 USING 1, 2")


def test_execute_using_dtype_error(runner):
    runner.execute(
        "PREPARE p2 FROM select o_custkey from orders where o_orderkey = ?"
    )
    with pytest.raises(
        ParameterBindingError, match="expected bigint, got varchar"
    ):
        runner.execute("EXECUTE p2 USING 'not-a-key'")


# -- admission + shedding ---------------------------------------------------


def test_admission_sheds_past_depth():
    p = AdmissionPipeline(None, fast_depth=1, general_depth=2,
                          retry_after_s=0.75)
    held = [p.reserve(fast=False), p.reserve(fast=False)]
    with pytest.raises(OverloadSheddedError) as ei:
        p.reserve(fast=False)
    assert ei.value.retry_after_s == 0.75
    # the fast lane is independent capacity: still admits
    f = p.reserve(fast=True)
    with pytest.raises(OverloadSheddedError):
        p.reserve(fast=True)
    for r in held + [f]:
        p.release(r)
        p.release(r)  # idempotent
    assert p.reserve(fast=False).lane == "general"


def test_server_sheds_with_429_and_retry_after(runner):
    from trino_tpu.client import Client
    from trino_tpu.runtime.server import CoordinatorServer

    server = CoordinatorServer(
        runner,
        max_concurrent=6,
        admission=AdmissionPipeline(None, fast_depth=1, general_depth=2,
                                    retry_after_s=0.5),
    )
    codes = []
    lock = threading.Lock()

    def go():
        c = Client(server.uri, timeout=30.0, poll_interval=0.005)
        try:
            c.execute(Q_AGG)
            with lock:
                codes.append("ok")
        except urllib.error.HTTPError as e:
            with lock:
                codes.append((e.code, e.headers.get("Retry-After")))

    try:
        ts = [threading.Thread(target=go) for _ in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
    finally:
        server.stop()
    shed = [c for c in codes if c != "ok"]
    assert codes.count("ok") >= 2, codes  # admitted work still finishes
    assert shed and all(c == (429, "0.5") for c in shed), codes


def test_fast_path_probe_requires_warm_plan(runner):
    assert fast_path_probe(runner, Q_POINT, None) is False  # cold
    runner.execute(Q_POINT)
    assert fast_path_probe(runner, Q_POINT, None) is True  # warm
    assert fast_path_probe(runner, Q_AGG, None) is False  # not a point


# -- micro-batching ---------------------------------------------------------


def test_classify_is_strict(runner):
    ok = classify(Q_POINT)
    assert ok is not None and ok.value == 7 and ok.key_col == "o_orderkey"
    for sql in (
        Q_AGG,  # aggregate
        "select o_custkey from orders where o_orderkey = 1.5",  # float key
        "select o_custkey from orders where o_orderkey > 7",  # range
        "select o_custkey from orders where o_orderkey = 1 limit 1",
        "select o_custkey c from orders where o_orderkey = 1",  # alias
        "select o_custkey from orders o where o_orderkey = 1",  # table alias
    ):
        assert classify(sql) is None, sql
    # EXECUTE resolves through the request-prepared dict
    look = classify(
        "EXECUTE pp USING 9",
        prepared={
            "pp": "select o_custkey from orders where o_orderkey = ?"
        },
    )
    assert look is not None and look.value == 9


def test_batcher_demux_interleaved_clients(runner):
    keys = [1, 2, 3, 7, 7, 32, 33, 2]  # duplicates on purpose
    expected = {
        k: runner.execute(
            f"select o_custkey, o_totalprice from orders "
            f"where o_orderkey = {k}"
        ).rows
        for k in set(keys)
    }
    b = MicroBatcher(runner, window_s=0.25, max_batch=len(keys))
    results: dict = {}
    errors: list = []

    def go(i, k):
        try:
            res = b.submit(
                f"select o_custkey, o_totalprice from orders "
                f"where o_orderkey = {k}"
            )
            results[i] = (k, res.rows)
        except Exception as e:  # pragma: no cover - diagnostic
            errors.append(e)

    ts = [
        threading.Thread(target=go, args=(i, k))
        for i, k in enumerate(keys)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errors
    assert len(results) == len(keys)
    # every client got exactly ITS key's rows, not a neighbor's
    for i, k in enumerate(keys):
        got_k, rows = results[i]
        assert got_k == k and rows == expected[k], (i, k, rows)
    st = b.stats()
    assert st["batched_queries"] == len(keys)
    assert 1 <= st["batches"] < len(keys), st  # coalescing happened
    assert st["open_groups"] == 0


def test_batcher_propagates_shared_failure(runner):
    b = MicroBatcher(runner, window_s=0.01, max_batch=4)
    with pytest.raises(Exception):
        b.submit("select no_such_col from orders where o_orderkey = 1")
    assert b.stats()["open_groups"] == 0


# -- worker-local deadline --------------------------------------------------


def test_on_batch_enforces_local_deadline():
    from trino_tpu import types as T
    from trino_tpu.runtime.task import TaskExecution, TaskId, TaskSpec
    from trino_tpu.sql.fragmenter import PlanFragment
    from trino_tpu.sql.plan import Field, ValuesNode

    node = ValuesNode((Field("a", T.BIGINT),), ((1,), (2,)))
    frag = PlanFragment(0, node, "single", "single")

    def spec(deadline):
        return TaskSpec(
            task_id=TaskId("q0", 0, 0),
            fragment=frag,
            n_output_partitions=1,
            remote_schemas={},
            scan_slice=None,
            input_locations={},
            deadline_epoch_s=deadline,
        )

    # expired deadline: the batch-boundary check fails the task itself,
    # with the typed code in the travelled message
    t = TaskExecution(spec(time.time() - 5.0), None)
    t._on_batch("scan", True)
    assert t.state == "failed"
    assert "EXCEEDED_TIME_LIMIT" in (t.failure or "")
    assert "worker-local deadline" in t.failure
    # live deadline: no effect
    t2 = TaskExecution(spec(time.time() + 60.0), None)
    t2._on_batch("scan", True)
    assert t2.state != "failed"
    # no deadline: no effect
    t3 = TaskExecution(spec(None), None)
    t3._on_batch("scan", True)
    assert t3.state != "failed"

