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
    # a property is declared once, as a field of Session made by
    # config._prop; the registry is read off those fields at import
    plain = {"catalog", "schema", "user", "timezone"}
    fields = {f.name: f for f in dataclasses.fields(Session)}
    props = SYSTEM_PROPERTIES.all()
    assert set(fields) - {m.name for m in props} == plain
    assert len(props) == 48
    for m in props:
        f = fields[m.name]
        assert m.description == f.metadata["description"]
        assert m.allowed == f.metadata["allowed"]
        assert m.default == f.metadata.get("registered_default", f.default)
        assert m.type is type(m.default)
    assert not any(fields[name].metadata for name in plain)
    # every default round-trips through bind_session
    bound = Session()
    bind_session(bound, {m.name: m.default for m in props})
    assert bound == Session()
    bind_session(bound, {m.name: str(m.default) for m in props})
    assert bound == Session()


# the twenty-one properties nothing ever set (PR 47): four switches
# that went with their off-branches, seventeen thresholds that are now
# constants of the module that owns the mechanism
REMOVED_PROPERTIES = {
    "preemption_enabled": False,
    "replica_failover_enabled": False,
    "mesh_steal_enabled": False,
    "low_memory_killer_enabled": False,
    "speculation_quantile": 3.0,
    "request_max_error_duration_s": 10.0,
    "node_breaker_threshold": 5,
    "node_breaker_cooldown_s": 2.0,
    "replica_breaker_threshold": 5,
    "replica_breaker_cooldown_s": 2.0,
    "compile_churn_warn_threshold": 8,
    "plan_cache_entries": 16,
    "micro_batch_max": 4,
    "admission_fast_depth": 8,
    "admission_general_depth": 8,
    "admission_retry_after_s": 2.0,
    "mesh_scheduler_min_slice_chunks": 2,
    "mesh_park_max_bytes": 1024,
    "fabric_queue_depth": 2,
    "fabric_max_error_duration_s": 1.0,
    "hash_partition_count": 2,
}


@pytest.mark.parametrize("name", sorted(REMOVED_PROPERTIES))
def test_removed_property_is_refused_as_unknown(name):
    value = REMOVED_PROPERTIES[name]
    with pytest.raises(ValueError, match="unknown session property"):
        SYSTEM_PROPERTIES.validate(name, value)
    lq = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    before = dataclasses.replace(lq.session)
    literal = str(value).lower() if isinstance(value, bool) else value
    with pytest.raises(Exception, match="unknown session property"):
        lq.execute(f"SET SESSION {name} = {literal}")
    assert lq.session == before and not hasattr(lq.session, name)
    with pytest.raises(TypeError):
        Session(**{name: value})


def test_no_reader_carries_a_default_of_its_own():
    # `getattr(<expr>, "<property>", <default>)` was the third place a
    # property's default was written, and two had drifted; a reader
    # reads `session.<name>`
    import ast
    import pathlib

    import trino_tpu

    names = {m.name for m in SYSTEM_PROPERTIES.all()}
    root = pathlib.Path(trino_tpu.__file__).parent
    found = []
    for path in sorted(root.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "getattr"
                and len(node.args) == 3
                and isinstance(node.args[1], ast.Constant)
                and node.args[1].value in names
            ):
                found.append(f"{path.relative_to(root)}:{node.lineno}")
    assert found == []


def test_show_session_lists_the_registry_with_a_fresh_sessions_defaults():
    lq = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    result = lq.execute("SHOW SESSION")
    assert result.column_names == ["Name", "Value", "Default", "Description"]
    props = SYSTEM_PROPERTIES.all()
    assert [r[0] for r in result.rows] == [m.name for m in props]
    assert len(result.rows) == 48
    fresh = Session()
    for (name, value, default, description), m in zip(result.rows, props):
        assert value == default == str(m.default)
        assert description == m.description
        # what the session holds is what a properties file would bind
        bound = Session()
        bind_session(bound, {name: value})
        assert getattr(bound, name) == getattr(fresh, name)
    lq.execute("SET SESSION memory_pool_bytes = 4096")
    shown = {r[0]: r[1:3] for r in lq.execute("SHOW SESSION").rows}
    assert shown["memory_pool_bytes"] == ["4096", "0"]


def test_a_property_is_a_plain_attribute_of_a_plain_dataclass():
    # no __getattr__ fallback and no per-read lookup: a session is read
    # once a statement on the host-bound path
    assert "__getattr__" not in vars(Session)
    assert "__getattribute__" not in vars(Session)
    assert not any(
        isinstance(v, property) for v in vars(Session).values()
    )
    s = Session()
    with pytest.raises(AttributeError):
        s.no_such_property
    with pytest.raises(AttributeError):
        s.plan_cache_entries
    assert vars(s)["batch_rows"] == s.batch_rows == 1 << 20
    assert set(vars(s)) == {f.name for f in dataclasses.fields(Session)}


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
