"""The program's spans as events of a `jax.profiler` trace (CPU backend:
the profiler's host plane works there). `tiny` TPC-H in the memory
connector, served by `CoordinatorServer`, asked by `Client`: what a
traced statement writes (`tpusql.<kind>.<name>`, runtime/tracing.py),
how the events nest, what the served span tree and the response's
`stats` carry, and that nothing of it exists while no trace runs. And
the statement's own account, which is kept trace or no trace: what it
counts, that it is the statement's alone, and the one `tpusql.stmt.done`
event a statement that carries it, whenever the statement began."""

import glob
import json
import os
import sys
import threading
import time
import urllib.request

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from trino_tpu.client import Client  # noqa: E402
from trino_tpu.runtime import tracing  # noqa: E402
from trino_tpu.runtime.server import CoordinatorServer  # noqa: E402

STATEMENTS = dict(chip_smoke.STATEMENTS)
P = tracing.PROFILE_PREFIX


def build_runner():
    from trino_tpu.connectors.memory import create_memory_connector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.connectors.tpch import TABLES
    from trino_tpu.engine import LocalQueryRunner, Session

    mem = create_memory_connector()
    for table, cols in chip_smoke.generate_tables(0.01).items():
        types = dict(TABLES[table])
        mem.load_table(
            "tiny", table, [ColumnMetadata(n, types[n]) for n in cols],
            [data for data, _ in cols.values()], None,
            [d for _, d in cols.values()],
        )
    runner = LocalQueryRunner(
        Session(catalog="memory", schema="tiny", batch_rows=16384))
    runner.register_catalog("memory", mem)
    return runner


@pytest.fixture(scope="module")
def served():
    runner = build_runner()
    server = CoordinatorServer(runner, port=0)
    client = Client(server.uri, poll_interval=0.002)
    for name in ("q1", "q3"):
        client.execute(STATEMENTS[name])      # compile, plan, cache
    yield runner, server, client
    server.stop()


def host_lines(trace_dir):
    """[[(name, start_ns, end_ns, stats)]] per thread line of the host
    plane, the program's events only, in order of start."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = sorted(
                (e.start_ns, -e.duration_ns, e.name, dict(e.stats))
                for e in line.events if e.name.startswith(P)
            )
            if events:
                lines.append([(n, s, s - d, st) for s, d, n, st in events])
    return lines


def start_trace(trace_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    """One trace over a Q1 and a Q3: (thread lines, {name: ClientResult},
    {name: the served span tree})."""
    runner, server, client = served
    trace_dir = tmp_path_factory.mktemp("trace")
    results, trees = {}, {}
    start_trace(trace_dir)
    try:
        for name in ("q1", "q3"):
            results[name] = client.execute(STATEMENTS[name])
            with urllib.request.urlopen(
                f"{server.uri}/v1/query/{results[name].query_id}/trace",
                timeout=10,
            ) as r:
                trees[name] = json.load(r)
    finally:
        jax.profiler.stop_trace()
    return host_lines(trace_dir), results, trees


def inside(event, line):
    """Events of `line` that lie inside `event`, itself left out."""
    _, lo, hi, _ = event
    return [e for e in line if e is not event and lo <= e[1] and e[2] <= hi]


@pytest.mark.parametrize("index, name", [(0, "q1"), (1, "q3")])
def test_a_statement_is_one_query_event_with_its_children_nested(
        traced, index, name):
    lines, _results, _trees = traced
    queries = [(e, line) for line in lines for e in line
               if e[0].startswith(P + "query.")]
    assert len(queries) == 2                   # one per statement
    queries.sort(key=lambda q: q[0][1])
    query, line = queries[index]
    query_id = query[3]["query_id"]
    children = inside(query, line)
    names = [c[0] for c in children]
    for phase in ("plan", "instantiate", "execute", "finalize"):
        assert names.count(f"{P}phase.{phase}") == 1, phase
    # spans made from a Span, and the phases, carry the statement's id
    for c in children:
        if c[0].startswith(P + "phase."):
            assert c[3]["query_id"] == query_id, c
    # on one thread line events nest: no two overlap without containing
    for a in children:
        for b in children:
            if a[1] < b[1] < a[2]:
                assert b[2] <= a[2], (a, b)
    execute = next(c for c in children if c[0] == P + "phase.execute")
    assert execute[3]["parent_id"] == query[3]["span_id"]
    assert 0 < execute[3]["cpu_ns"] <= execute[2] - execute[1]
    plan = next(c for c in children if c[0] == P + "phase.plan")
    assert plan[3]["hit"] == 1                 # warmed: the plan cache answers
    ops = [c for c in inside(execute, line) if c[0].startswith(P + "op.")]
    assert {c[0].rsplit(".", 1)[1] for c in ops} == {
        "get_output", "add_input", "finish"}
    assert any(c[0].startswith(P + "op.TableScanOperator.") for c in ops)
    # the query span's own numbers ride on its event
    assert {"queued_ms", "plan_ms", "cpu_ms"} <= set(query[3])
    # the parse comes before the statement has an id: just outside
    parse = [e for e in line if e[0] == P + "phase.parse" and e[2] <= query[1]]
    assert parse and query[1] - parse[-1][2] < 5e6


def test_every_sync_lies_inside_an_operator_call_or_the_result_fetch(traced):
    lines, _results, _trees = traced
    syncs = [(e, line) for line in lines for e in line
             if e[0].startswith(P + "sync.")]
    assert len(syncs) >= 4       # each result, and the join's readbacks
    for sync, line in syncs:
        around = [e for e in line
                  if e[1] <= sync[1] and sync[2] <= e[2] and e is not sync]
        assert any(e[0].startswith((P + "op.", P + "result."))
                   for e in around), sync
        assert sync[3]["nbytes"] >= 1
    sites = {s[0][len(P + "sync."):] for s, _ in syncs}
    assert {"result", "join.match_total"} <= sites
    for line in lines:
        for e in line:
            if e[0] == P + "result.fetch":
                got = {c[0] for c in inside(e, line)}
                assert got == {P + "sync.result", P + "result.to_rows"}


def test_the_server_spans_and_the_replayed_scan(traced):
    lines, results, _trees = traced
    events = [e for line in lines for e in line]
    queued = [e for e in events if e[0] == P + "server.queued"]
    # per statement: the handler thread's part and the pool thread's
    assert len(queued) == 4
    assert sum("handoff_us" in e[3] for e in queued) == 2
    last_pages = [e for e in events if e[0] == P + "server.respond"
                  and "since_finished_us" in e[3]]
    assert len(last_pages) == 2
    assert sorted(e[3]["rows"] for e in last_pages) == sorted(
        len(r.rows) for r in results.values())
    # both statements were warmed: every scan finds its batches on the
    # device, none filters on the host or copies
    scans = [e for e in events if e[0] == P + "scan.batches"]
    assert scans and all(e[3]["cached"] == 1 for e in scans)
    assert not [e for e in events if e[0] in (
        P + "scan.host_filter", P + "scan.to_device")]


def test_a_fresh_predicate_pays_the_host_filter_and_the_copy(served, tmp_path):
    _runner, _server, client = served
    sql = "select count(*) from lineitem where l_quantity < 7"
    start_trace(tmp_path)
    try:
        first = client.execute(sql).rows
        again = client.execute(sql).rows
    finally:
        jax.profiler.stop_trace()
    assert first == again
    events = [e for line in host_lines(tmp_path) for e in line]
    assert sum(e[0] == P + "scan.host_filter" for e in events) == 1
    assert sum(e[0] == P + "scan.to_device" for e in events) >= 1
    assert sorted(e[3]["cached"] for e in events
                  if e[0] == P + "scan.batches") == [0, 1]
    hits = [e[3]["hit"] for e in events if e[0] == P + "phase.plan"]
    assert hits == [0, 1]
    # on a miss the old phase spans lie inside `phase.plan`
    plan = next(e for e in events if e[0] == P + "phase.plan")
    assert {e[0] for e in events if plan[1] <= e[1] and e[2] <= plan[2]} >= {
        P + "phase.analyze", P + "phase.optimize"}


def test_the_served_tree_has_one_operator_span_per_operator(traced, served):
    runner, _server, _client = served
    _lines, _results, trees = traced
    for name in ("q1", "q3"):
        spans = [e for e in trees[name]["traceEvents"] if e["ph"] == "X"]
        operators = [e for e in spans if e["cat"] == "operator"]
        assert operators, name
        for op in operators:
            assert {"calls", "batches", "host_syncs", "host_sync_ms",
                    "busy_ms"} <= set(op["args"])
            assert op["args"]["calls"] >= 1
        assert sum(op["args"]["host_syncs"] for op in operators) >= 1
        query = next(e for e in spans if e["cat"] == "query")
        assert {"queued_ms", "plan_ms", "cpu_ms"} <= set(query["args"])
        assert query["args"]["queued_ms"] > 0
    join = [e["args"] for e in trees["q3"]["traceEvents"]
            if e["ph"] == "X" and e["name"] == "LookupJoinOperator"]
    assert join and all(a["host_syncs"] >= 1 and a["host_sync_ms"] > 0
                        for a in join)
    # the tree the endpoint serves is the runner's, and it is sound
    assert tracing.check_span_invariants(runner.query_trace_export()) == []


def test_the_response_stats_and_the_client_keeps_the_last(served):
    _runner, server, client = served
    result = client.execute(STATEMENTS["q1"])
    assert result.stats["state"] == "FINISHED"
    for key in ("queuedTimeMillis", "elapsedTimeMillis", "cpuTimeMillis"):
        assert isinstance(result.stats[key], int) and result.stats[key] >= 0
    assert result.stats["queuedTimeMillis"] <= result.stats["elapsedTimeMillis"]
    assert result.stats["cpuTimeMillis"] <= result.stats["elapsedTimeMillis"]
    # the first response already carries the times, counting up
    request = urllib.request.Request(
        f"{server.uri}/v1/statement", data=b"select 1", method="POST")
    with urllib.request.urlopen(request, timeout=10) as r:
        first = json.load(r)
    assert {"state", "queuedTimeMillis", "elapsedTimeMillis",
            "cpuTimeMillis"} == set(first["stats"])


def test_without_a_trace_a_leaf_span_is_the_shared_noop(served):
    assert not tracing.profiling()
    assert tracing.host_span("op.X.get_output") is tracing.OFF
    assert tracing.host_sync("scan.rows_scanned", 8) is tracing.OFF
    assert tracing.phase_span(None, "plan", hit=0) is tracing.OFF
    with tracing.host_span("anything") as span:
        span.set_metadata(rows=1)              # accepted, dropped


def test_without_a_trace_a_statement_makes_no_more_spans_than_before(
        served, monkeypatch):
    """Two `Span`s a statement, as before this file existed (the query
    and its `execute` phase; a plan-cache hit has no other phase), each
    registered under the trace's lock once; the per-batch path makes no
    tally, no annotation, and takes no lock."""
    runner, _server, _client = served
    made, tallies, annotations = [], [], []
    init = tracing.Span.__init__

    here = threading.get_ident()

    def counting(self, *args, **kwargs):
        # (this statement's thread alone: a worker that ran another
        # file's statements before may still be ending one)
        if threading.get_ident() == here:
            made.append(args[1])
        init(self, *args, **kwargs)

    monkeypatch.setattr(tracing.Span, "__init__", counting)
    monkeypatch.setattr(tracing, "OpTally",
                        lambda *a: tallies.append(a) or pytest.fail("tally"))
    monkeypatch.setattr(tracing, "TraceAnnotation", type(
        "Spy", (), {"is_enabled": staticmethod(lambda: False),
                    "__init__": lambda self, *a, **k: annotations.append(a)}))
    rows = runner.execute(STATEMENTS["q1"]).rows
    assert len(rows) == 4
    assert made == [f"query {runner.query_trace_export()['query_id']}",
                    "execute"]
    assert not tallies and not annotations
    export = runner.query_trace_export()
    assert {s["kind"] for s in export["spans"]} == {"query", "phase"}
    assert tracing.check_span_invariants(export) == []


# -- the statement's own account ------------------------------------------------


def begun_before_the_trace(runner, sql, trace_dir):
    """Start `sql` on a thread of its own and hold it inside its first
    scan call, so that its pipeline HAS begun; start a profiler trace in
    `trace_dir`; return the function that lets the statement go and
    returns its result. The caller stops the trace."""
    from trino_tpu.exec import operators

    entered, go, out = threading.Event(), threading.Event(), {}
    inner = operators.TableScanOperator.get_output

    def held(self):
        if not entered.is_set():
            entered.set()
            assert go.wait(60)
        return inner(self)

    def run():
        try:
            out["result"] = runner.execute(sql)
        except BaseException as e:          # handed to the caller below
            out["error"] = e

    thread = threading.Thread(target=run)
    operators.TableScanOperator.get_output = held
    try:
        thread.start()
        assert entered.wait(60)
        start_trace(trace_dir)
    except BaseException:
        operators.TableScanOperator.get_output = inner
        go.set()
        raise

    def finish():
        try:
            go.set()
            thread.join(120)
            assert not thread.is_alive()
        finally:
            operators.TableScanOperator.get_output = inner
        if "error" in out:
            raise out["error"]
        return out["result"]

    return finish


COUNTS = ("syncs", "sync_bytes", "plan_hit")


def counts_of(account):
    """What of an account repeats exactly from run to run."""
    return {k: v for k, v in account.items()
            if k in COUNTS or k.endswith(".n") or k.startswith("c.")}


def test_the_account_is_kept_without_a_trace_and_served(served):
    runner, server, client = served
    assert not tracing.profiling()
    result = runner.execute(STATEMENTS["q3"])
    account = result.stats["account"]
    assert {"wall_us", "parse_us", "plan_us", "plan_hit", "instantiate_us",
            "execute_us", "release_us", "cpu_us", "syncs", "sync_us",
            "sync_bytes"} <= set(account)
    assert account["plan_hit"] == 1
    phases = sum(account[k] for k in (
        "parse_us", "plan_us", "instantiate_us", "execute_us", "release_us"))
    assert 0 < account["execute_us"] < phases <= account["wall_us"]
    assert 0 < account["cpu_us"] <= account["execute_us"]
    assert 0 < account["sync_us"] <= account["execute_us"]
    # every readback by its site, and they add up
    sites = {k[2:-2] for k in account if k.startswith("s.") and k.endswith(".n")}
    assert {"result", "join.match_total", "scan.rows_scanned"} <= sites
    assert sum(account[f"s.{s}.n"] for s in sites) == account["syncs"] >= 4
    assert sum(account[f"s.{s}.us"] for s in sites) == pytest.approx(
        account["sync_us"])
    # the counters its thread moved: the rows it scanned, the paths taken
    assert account["c.rows_scanned"] > 0
    assert account["c.plan_cache.hits"] == 1
    assert account["c.join_probe_path.sorted"] >= 1
    # Q3's keys are an order key, a date and a priority: no exact range
    # bounds such a table, so its batches sort as they did (issue 39)
    assert account["c.agg_ingest_path.sort"] == account["c.agg_ingest_batches"]
    assert account["c.agg_key_bound.none"] == 1
    assert "c.agg_key_bound.range" not in account
    assert not [k for k in account if "by_query" in k]
    # the same statement again counts the same
    again = runner.execute(STATEMENTS["q3"]).stats["account"]
    assert counts_of(again) == counts_of(account)
    # the span tree's export and the endpoint serve it, with no profiler
    assert runner.query_trace_export()["account"] == again
    served_result = client.execute(STATEMENTS["q3"])
    with urllib.request.urlopen(
        f"{server.uri}/v1/query/{served_result.query_id}/trace", timeout=10,
    ) as r:
        tree = json.load(r)
    assert counts_of(tree["account"]) == counts_of(account)
    assert tree["account"]["wall_us"] > 0 and tree["traceEvents"]


def test_a_thread_with_no_statement_gets_the_shared_noop():
    assert tracing.running_statement() is None
    assert tracing.host_sync("scan.rows_scanned", 8) is tracing.OFF
    account = tracing.StmtAccount("by-hand")
    seen = {}

    def elsewhere():
        seen["sync"] = tracing.host_sync("scan.rows_scanned", 8)
        seen["phase"] = tracing.phase_span(None, "plan", hit=0)
        seen["statement"] = tracing.running_statement()

    with tracing.statement(account, time.perf_counter_ns(), 7):
        assert tracing.running_statement() is account
        with tracing.host_sync("a.site", 16) as sync:
            sync.set_metadata(rows=1)           # accepted, dropped
            assert sync is not tracing.OFF
        with tracing.host_sync("a.site", 4):
            pass
        with tracing.phase_span(None, "plan", hit=0) as plan:
            plan.set_metadata(hit=1)
        assert tracing.phase_span(None, "finalize") is tracing.OFF
        from trino_tpu.runtime.metrics import METRICS

        METRICS.increment("test_tracing_profiler.by_hand", 3)
        thread = threading.Thread(target=elsewhere)
        thread.start()
        thread.join(30)
        assert not thread.is_alive()
    # another thread, while the statement ran on this one: nothing
    assert seen == {"sync": tracing.OFF, "phase": tracing.OFF, "statement": None}
    assert tracing.running_statement() is None
    assert tracing.host_sync("a.site", 16) is tracing.OFF
    stats = account.stats()
    assert (stats["syncs"], stats["sync_bytes"], stats["s.a.site.n"]) == (2, 20, 2)
    assert stats["s.a.site.us"] == stats["sync_us"] > 0
    assert stats["plan_hit"] == 1 and stats["plan_us"] > 0
    assert stats["parse_us"] == 0.007 and stats["wall_us"] > stats["plan_us"]
    assert stats["c.test_tracing_profiler.by_hand"] == 3
    METRICS.remove("test_tracing_profiler.by_hand")


def test_a_statement_begun_before_the_trace_leaves_its_whole_account(
        served, tmp_path):
    """Held inside its first scan call while the trace starts: its
    pipeline began before the trace, its query and `execute` spans were
    entered before it. It still leaves `op.*` and `sync.*` events from
    the trace's start on and one `stmt.done` with the numbers of its
    whole life; and no `stmt.begin`, no `query.query`."""
    runner, _server, _client = served
    alone = runner.execute(STATEMENTS["q3"]).stats["account"]
    finish = begun_before_the_trace(runner, STATEMENTS["q3"], tmp_path)
    try:
        result = finish()
        after = runner.execute(STATEMENTS["q1"])
    finally:
        jax.profiler.stop_trace()
    lines = host_lines(tmp_path)
    events = [e for line in lines for e in line]
    done = {e[3]["query_id"]: e for e in events if e[0] == P + "stmt.done"}
    assert set(done) == {result.stats["query_id"], after.stats["query_id"]}
    assert [e[3]["query_id"] for e in events if e[0] == P + "stmt.begin"] == [
        after.stats["query_id"]]
    assert [e[3]["query_id"] for e in events if e[0] == P + "query.query"] == [
        after.stats["query_id"]]
    event = done[result.stats["query_id"]]
    stats = dict(event[3])
    del stats["query_id"]
    # the event is the account the result carries, number for number
    account = result.stats["account"]
    assert set(stats) == set(account)
    for key, value in account.items():
        assert stats[key] == pytest.approx(value, rel=1e-6), key
    # and what the same statement counts untraced: counts exactly
    assert counts_of(stats) == counts_of(alone)
    assert stats["syncs"] == alone["syncs"]
    assert stats["c.rows_scanned"] == alone["c.rows_scanned"]
    assert stats["execute_us"] > 0 and stats["wall_us"] > stats["execute_us"]
    # it began before the trace did: its wall reaches back past the
    # first event of the trace
    first_ns = min(e[1] for e in events)
    assert event[2] - 1e3 * stats["wall_us"] < first_ns
    # operator calls and readbacks from the trace's start on, on its line
    line = next(line for line in lines if event in line)
    mine = [e for e in line if e[2] <= event[1]]
    ops = [e for e in mine if e[0].startswith(P + "op.")]
    syncs = [e for e in mine if e[0].startswith(P + "sync.")]
    assert {e[0].rsplit(".", 1)[1] for e in ops} == {
        "get_output", "add_input", "finish"}
    assert any(e[0].startswith(P + "op.LookupJoinOperator.") for e in ops)
    assert syncs and len(syncs) <= stats["syncs"]
    # (a readback of the held call itself lies in no span: that call
    # began before the trace)
    first_op_ns = min(o[1] for o in ops)
    for sync in syncs:
        assert sync[1] < first_op_ns or any(
            o[1] <= sync[1] and sync[2] <= o[2] for o in mine
            if o[0].startswith((P + "op.", P + "result."))), sync
    assert sum(sync[1] > first_op_ns for sync in syncs) >= stats["syncs"] - 2
    # the served tree still has its operator spans, made at the first
    # call that found the profiler on
    export = runner.query_trace_export(result.stats["query_id"])
    assert export is None or tracing.check_span_invariants(export) == []


def test_two_statements_at_once_count_each_its_own(served):
    """Two threads, one runner, both statements' accounts open before
    either runs: each counts what it counts alone, and so does its
    completion event (which was the process's count over its life)."""
    runner, _server, _client = served
    alone = {name: runner.execute(STATEMENTS[name]).stats["account"]
             for name in ("q1", "q3")}
    both_open = threading.Barrier(2, timeout=60)
    completed, results, errors = {}, {}, []

    class Listener:
        def query_created(self, event):
            both_open.wait()

        def query_completed(self, event):
            completed[event.query_id] = event

    listener = Listener()
    runner.event_listeners.add(listener)

    def run(name):
        try:
            results[name] = runner.execute(STATEMENTS[name])
        except BaseException as e:
            errors.append(e)

    threads = [threading.Thread(target=run, args=(n,)) for n in ("q1", "q3")]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive()
    finally:
        runner.event_listeners._listeners.remove(listener)
    assert not errors
    for name in ("q1", "q3"):
        account = results[name].stats["account"]
        assert counts_of(account) == counts_of(alone[name]), name
        event = completed[results[name].stats["query_id"]]
        assert event.rows_scanned == alone[name]["c.rows_scanned"]
    assert alone["q1"]["c.rows_scanned"] != alone["q3"]["c.rows_scanned"]
    assert alone["q1"]["syncs"] != alone["q3"]["syncs"]
