"""Client protocol, CLI, session properties, config, resource groups
(SURVEY.md §2.11, §5.6, §2.3)."""

import dataclasses
import threading
import time

import pytest

from trino_tpu.client import Client, QueryError
from trino_tpu.cli import format_table
from trino_tpu.config import (
    SYSTEM_PROPERTIES,
    bind_session,
    load_properties_file,
)
from trino_tpu.connectors.tpch import create_tpch_connector
from trino_tpu.engine import LocalQueryRunner, Session
from trino_tpu.runtime.resource_groups import (
    QueryQueueFullError,
    ResourceGroupManager,
    ResourceGroupSpec,
    Selector,
)
from trino_tpu.runtime.server import CoordinatorServer


@pytest.fixture(scope="module")
def server():
    lq = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    lq.register_catalog("tpch", create_tpch_connector())
    srv = CoordinatorServer(lq)
    yield srv
    srv.stop()


def test_client_roundtrip(server):
    c = Client(server.uri)
    r = c.execute(
        "select n_regionkey, count(*) c from nation group by n_regionkey order by 1"
    )
    assert r.column_names == ["n_regionkey", "c"]
    assert r.rows == [[i, 5] for i in range(5)]


def test_client_error_propagates(server):
    c = Client(server.uri)
    with pytest.raises(QueryError, match="does not exist"):
        c.execute("select * from tpch.tiny.nope")


def test_client_pagination(server):
    c = Client(server.uri)
    r = c.execute("select o_orderkey from orders order by o_orderkey")
    assert len(r.rows) == 15000
    assert r.rows[0] == [1]


def test_cli_format_table():
    out = format_table(["a", "bb"], [[1, None], [22, "x"]])
    lines = out.splitlines()
    assert lines[0].split("|")[0].strip() == "a"
    assert "NULL" in out
    assert "(2 rows)" in out


# -- session properties / config --


def test_set_show_session():
    lq = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    lq.register_catalog("tpch", create_tpch_connector())
    lq.execute("SET SESSION batch_rows = 8192")
    assert lq.session.batch_rows == 8192
    lq.execute("SET SESSION enable_dynamic_filtering = false")
    assert lq.session.enable_dynamic_filtering is False
    rows = lq.execute("SHOW SESSION").rows
    names = [r[0] for r in rows]
    assert "batch_rows" in names and "retry_policy" in names
    with pytest.raises(Exception):
        lq.execute("SET SESSION no_such_prop = 1")


def test_property_registry_validation():
    assert SYSTEM_PROPERTIES.validate("batch_rows", "4096") == 4096
    assert SYSTEM_PROPERTIES.validate("enable_dynamic_filtering", "false") is False
    with pytest.raises(ValueError):
        SYSTEM_PROPERTIES.validate("retry_policy", 7)


def test_removed_mesh_scheduler_property_is_unknown():
    # the MeshScheduler seat is the only guard of a mesh; the switch
    # that selected a bare lock instead went with the lock
    with pytest.raises(ValueError, match="unknown session property"):
        SYSTEM_PROPERTIES.validate("mesh_scheduler", False)
    lq = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    with pytest.raises(Exception, match="unknown session property"):
        lq.execute("SET SESSION mesh_scheduler = false")
    assert not hasattr(lq.session, "mesh_scheduler")


def test_registry_and_session_declare_the_same_properties():
    # every property is declared twice (a registry row and a Session
    # field); until one declaration derives the other this holds them
    # to the same names and defaults
    fields = {f.name for f in dataclasses.fields(Session)}
    props = SYSTEM_PROPERTIES.all()
    assert fields - {m.name for m in props} == {
        "catalog", "schema", "user", "timezone"
    }
    bound = Session()
    bind_session(bound, {m.name: m.default for m in props})
    assert bound == Session()


def test_load_properties_file(tmp_path):
    p = tmp_path / "config.properties"
    p.write_text("# comment\nbatch_rows=1024\nretry_policy = task\n\n")
    props = load_properties_file(str(p))
    assert props == {"batch_rows": "1024", "retry_policy": "task"}


# -- resource groups --


def test_resource_group_concurrency_and_queue():
    mgr = ResourceGroupManager(
        ResourceGroupSpec("global", max_concurrency=1, max_queued=1)
    )
    lease1 = mgr.acquire()
    assert mgr.stats()["global"][0] == 1
    # second query queues; third is rejected (queue full)
    entered = threading.Event()
    released = []

    def second():
        entered.set()
        lease = mgr.acquire(timeout=10)
        released.append(lease)

    t = threading.Thread(target=second, daemon=True)
    t.start()
    entered.wait()
    time.sleep(0.05)  # let it enter the queue
    with pytest.raises(QueryQueueFullError):
        mgr.acquire(timeout=0.01)
    mgr.release(lease1)
    t.join(5)
    assert released
    mgr.release(released[0])
    assert mgr.stats()["global"] == (0, 0)


def test_resource_group_selectors():
    spec = ResourceGroupSpec(
        "global",
        max_concurrency=10,
        sub_groups=[ResourceGroupSpec("etl", max_concurrency=1)],
    )
    mgr = ResourceGroupManager(
        spec, [Selector(("global", "etl"), user_pattern="etl-.*")]
    )
    lease = mgr.acquire(user="etl-nightly")
    assert mgr.stats()["global.etl"][0] == 1
    # non-matching user routes to the root group
    lease2 = mgr.acquire(user="alice")
    assert mgr.stats()["global"][0] == 2
    mgr.release(lease)
    mgr.release(lease2)

# -- query TTL tracking (QueryTracker analogue) --


def test_abandoned_query_expires(server):
    import urllib.request

    # submit directly so we control polling
    req = urllib.request.Request(
        f"{server.uri}/v1/statement",
        data=b"select count(*) from nation",
        method="POST",
    )
    import json as _json

    resp = _json.loads(urllib.request.urlopen(req).read())
    qid = resp["id"]
    job = server._jobs[qid]
    # wait for it to finish but never drain the results
    for _ in range(100):
        if job.state == "finished":
            break
        time.sleep(0.05)
    assert job.state == "finished"
    # simulate client silence past the TTL, then trigger the sweep
    old = server.CLIENT_TTL_S
    server.CLIENT_TTL_S = 0.0
    try:
        time.sleep(0.01)
        server._evict_completed()
    finally:
        server.CLIENT_TTL_S = old
    assert job.abandoned and job.state == "failed"
    assert "abandoned" in job.error
    assert job.rows == []


def test_completed_job_evicted_after_ttl(server):
    c = Client(server.uri)
    c.execute("select 1")
    # every fully-drained job older than the completed TTL is evicted
    old = server.COMPLETED_TTL_S
    server.COMPLETED_TTL_S = 0.0
    try:
        time.sleep(0.01)
        server._evict_completed()
    finally:
        server.COMPLETED_TTL_S = old
    assert all(j.finished_at is None for j in server._jobs.values())


class TestPreparedStatements:
    """PREPARE/EXECUTE/DEALLOCATE + the prepared-statement protocol
    headers (VERDICT r3 item #8; tree/Prepare.java:25, StatementClientV1
    X-Trino-Prepared-Statement / addedPrepare threading)."""

    def test_prepare_execute_deallocate_roundtrip(self, server):
        c = Client(server.uri)
        c.execute("prepare q1 from select n_name from nation where n_nationkey = ?")
        # PREPARE travels back as addedPrepare and the client resends
        # it per request, so EXECUTE works on this stateless server
        assert "q1" in c.prepared
        r = c.execute("execute q1 using 3")
        assert r.rows == [["CANADA"]]
        r = c.execute("execute q1 using 0")
        assert r.rows == [["ALGERIA"]]
        c.execute("deallocate prepare q1")
        assert "q1" not in c.prepared

    def test_two_parameters(self, server):
        c = Client(server.uri)
        c.execute(
            "prepare q2 from select count(*) from nation "
            "where n_regionkey = ? and n_nationkey > ?"
        )
        r = c.execute("execute q2 using 1, 2")
        want = server.runner.execute(
            "select count(*) from nation where n_regionkey = 1 and n_nationkey > 2"
        ).rows
        assert r.rows == want

    def test_dbapi_server_side_binding(self, server):
        import trino_tpu.dbapi as dbapi

        conn = dbapi.Connection(Client(server.uri))
        cur = conn.cursor()
        cur.execute(
            "SELECT n_name FROM nation WHERE n_nationkey = ?", (3,)
        )
        assert cur.fetchall() == [["CANADA"]]
        # the statement body traveled via the prepared header, not by
        # splicing the parameter into the SQL text
        assert "stmt" in conn._client.prepared
        cur.execute(
            "SELECT count(*) FROM nation WHERE n_name = ?", ("CANADA",)
        )
        assert cur.fetchall() == [[1]]
