"""`chipbench/stmt_account.py` and its seven `stmt_*` metrics, on the
CPU: the statement-equivalents and the medians on traces small enough to
do by hand (a statement straddling either edge of the window, one begun
and not ended), the same on a trace recorded here with the program's
`tpusql.stmt.done` events in it (the profiler's host plane works on the
CPU), and what `read` answers when the run's trace cannot be found, is
another run's, or holds no `stmt.done`."""

import json
import os
import shutil
import statistics
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

from chipbench import harness, spans, stats, stmt_account, trace, traffic  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = traffic.load_json(os.path.join(ROOT, "BENCHMARK.json"))
READERS = ["stmt_execute_ms", "stmt_syncs", "stmt_sync_ms", "stmt_cpu_ms",
           "stmt_offcpu_ms", "stmt_device_ms", "stmt_launches"]
# recorded on the v5e before the account existed: spans, no `stmt.done`
BEFORE_THE_ACCOUNT = os.path.join(HERE, "trace_spans_small.xplane.pb")
P = spans.PROGRAM


def ev(name, start, end, **stats_):
    return spans.Event(name if name.startswith("PjitFunction") else P + name,
                       start, end, stats_)


def done(query_id, at, wall_s, **account):
    record = {"wall_us": wall_s * 1e6, "execute_us": wall_s * 0.9e6,
              "cpu_us": wall_s * 0.2e6, "syncs": 4, "sync_us": wall_s * 0.5e6,
              **account}
    return ev("stmt.done", at - 1e-7, at, query_id=query_id, **record)


def hand_trace(lines, window=(10.0, 13.0)):
    yardstick = trace.Trace({}, {}, {}, [trace.Annotation(trace.WINDOW, *window, {})])
    return spans.SpanTrace(yardstick, lines, [])


def synthetic_run(reduced):
    mix = traffic.load_traffic("scan_agg.2streams")
    plan = traffic.plan(mix, 1)
    samples = [stats.Sample(0, 0, 10.0, 10.030, [[1]]),
               stats.Sample(1, 3, 10.0, 10.012, [[2]])]
    return harness.RunData(
        mix, {}, plan.instances, stats.account(samples, 10.0, 1.0), [],
        {"xla_compiles": 0.0, "plan_cache.hits": 3.0, "plan_cache.misses": 1.0},
        [1000.0] * len(plan.instances), [16] * len(plan.instances),
        10.0, {"hbm_bytes_per_s": 819e9}, reduced, samples,
    )


def loud_lines(capsys):
    return [line for line in map(json.loads, capsys.readouterr().out.splitlines())
            if line["phase"] == "NO_STMT_ACCOUNT"]


def put_trace(root, source, cell="some.cell"):
    there = os.path.join(root, cell, "plugins", "profile", "2026_09_30")
    os.makedirs(there)
    shutil.copy(source, os.path.join(there, "host.xplane.pb"))


# -- the entries ------------------------------------------------------------------------


def test_the_seven_wait_for_a_benchmark_pr():
    """`test_dispatch_readers.py` holds the last two entries of
    `per_layer`, and the driver takes an entry put before them for a
    change to them: the seven are `stmt_account.METRICS` and no entry,
    and no file under `layer_metrics/` (`test_chipbench.py` wants one
    for every entry and no other)."""
    assert list(stmt_account.METRICS) == READERS
    assert not {m["name"] for m in BENCHMARK["per_layer"]} & set(READERS)
    there = os.listdir(os.path.join(ROOT, "chipbench", "layer_metrics"))
    assert not [f for f in there if f.startswith("stmt_")]


# -- by hand ------------------------------------------------------------------------------


@pytest.mark.parametrize("lines, equivalents, inside", [
    # wholly inside: one statement, whatever its length
    ([[done("a", 12.0, 1.5)]], 1.0, ["a"]),
    # began 1 s before the window's start, ended inside: 1.5 of its 2.5 s
    ([[done("a", 11.5, 2.5)]], 1.5 / 2.5, ["a"]),
    # ended after the window's end, inside the trace: 2 of its 2.5 s lie
    # in the window, and it is no statement of the medians
    ([[done("a", 13.5, 2.5)]], 2.0 / 2.5, []),
    # covers the window from before its start to after its end
    ([[done("a", 13.5, 4.0)]], 3.0 / 4.0, []),
    # one ended inside, the next begun inside and never ended in the
    # trace: (13 - 12.2) over the median wall of those that ended
    ([[ev("stmt.begin", 10.1, 10.1, query_id="a"), done("a", 12.1, 2.0),
       ev("stmt.begin", 12.2, 12.2, query_id="b")]], 1.0 + 0.8 / 2.0, ["a"]),
    # two threads: their statements add up
    ([[done("a", 11.0, 2.0), done("c", 12.9, 1.0)],
      [done("b", 12.0, 2.0)]], 0.5 + 1.0 + 1.0, ["a", "b", "c"]),
    # a statement begun inside and not ended, and none that ended: no
    # wall to measure it by
    ([[ev("stmt.begin", 10.5, 10.5, query_id="a")]], 0.0, []),
    # begun and ended before the window: not of this window
    ([[ev("stmt.begin", 8.0, 8.0, query_id="a"), done("a", 9.5, 1.5)]], 0.0, []),
])
def test_statement_equivalents_by_hand(lines, equivalents, inside):
    reduced = stmt_account.reduce(hand_trace(lines))
    assert reduced["equivalents"] == pytest.approx(equivalents)
    assert [s["query_id"] for s in reduced["statements"]] == inside
    assert reduced["window_s"] == pytest.approx(3.0)


def test_the_benchmarks_own_clock_around_the_same_statement():
    """Two streams: each statement's `chipbench.runner.execute` call is
    the one that holds its `stmt.done` and began nearest before it did;
    a call that began before the trace is not there to compare."""
    st = hand_trace([[done("a", 11.0, 1.5), done("c", 12.9, 1.0)],
                     [done("b", 12.0, 2.0)]])
    st.yardstick.annotations += [
        trace.Annotation(trace.ENGINE, 9.999, 12.003, {}),       # b's
        trace.Annotation(trace.ENGINE, 11.8995, 12.9015, {}),    # c's
    ]
    got = {s["query_id"]: s["outside_us"]
           for s in stmt_account.reduce(st)["statements"]}
    assert got["a"] is None
    assert got["b"] == pytest.approx(2.004e6) and got["c"] == pytest.approx(1.002e6)
    text = stmt_account.table(stmt_account.reduce(st))
    assert "minus wall_us: median 3.000 ms, most 4.000 ms, over 2 statements" in text


def test_a_trace_without_the_window_is_refused():
    with pytest.raises(ValueError, match="chipbench.window"):
        stmt_account.reduce(spans.SpanTrace(trace.Trace({}, {}, {}, []), [], []))


def test_the_seven_metrics_by_hand():
    """Three statements end in a window of 3 s, the first begun 0.5 s
    before it; a fourth is begun and not ended. Launches: four outermost
    `PjitFunction` events lie in the window (one twin nested in its
    outer, one clipped at the window's start), one lies before it."""
    lines = [[
        ev("PjitFunction(f)", 9.0, 9.5),
        ev("PjitFunction(f)", 9.9, 10.2),
        done("a", 11.0, 1.5, execute_us=1.4e6, cpu_us=0.3e6, syncs=7, sync_us=0.9e6),
        ev("PjitFunction(f)", 11.1, 11.3), ev("PjitFunction(f)", 11.15, 11.25),
        done("b", 12.0, 1.0, execute_us=0.9e6, cpu_us=0.5e6, syncs=7, sync_us=0.6e6),
        ev("PjitFunction(g)", 12.1, 12.2),
        done("c", 12.8, 0.8, execute_us=0.7e6, cpu_us=0.1e6, syncs=9, sync_us=0.1e6),
        ev("stmt.begin", 12.8, 12.8, query_id="d"),
        ev("PjitFunction(g)", 12.9, 12.95),
    ]]
    reduced = stmt_account.reduce(hand_trace(lines))
    equivalents = 1.0 / 1.5 + 1.0 + 1.0 + 0.2 / 1.0
    assert reduced["equivalents"] == pytest.approx(equivalents)
    assert reduced["launches"] == 4 and reduced["unfinished"] == 1
    got = stmt_account.metrics(reduced, busy_s=2.4)
    assert list(got) == READERS
    assert got["stmt_execute_ms"] == pytest.approx(900.0)
    assert got["stmt_syncs"] == 7
    assert got["stmt_sync_ms"] == pytest.approx(600.0)
    assert got["stmt_cpu_ms"] == pytest.approx(300.0)
    # per statement 1.4 - 0.3 - 0.9, 0.9 - 0.5 - 0.6 (not under 0), 0.7 - 0.1 - 0.1
    assert got["stmt_offcpu_ms"] == pytest.approx(200.0)
    assert got["stmt_device_ms"] == pytest.approx(2400.0 / equivalents)
    assert got["stmt_launches"] == pytest.approx(4 / equivalents)
    text = stmt_account.table(reduced, busy_s=2.4)
    assert f"statement-equivalents in the window: {equivalents:.6f}" in text
    assert "launches: 4 in the window" in text
    assert f"{2400.0 / equivalents:16.4f}  stmt_device_ms" in text
    # no device plane: the device's metric is left out, the others stay
    assert set(READERS) - set(stmt_account.metrics(reduced, None)) == {
        "stmt_device_ms"}


# -- on a trace recorded here ----------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace with the program's own events in it: a Q3 begun before
    the trace and ended inside the window, then a Q1 and a Q3 inside it.
    (trace root, the statements' `result.stats` in order of ending)."""
    import test_tracing_profiler as profiler_tests

    runner = profiler_tests.build_runner()
    q1, q3 = (profiler_tests.STATEMENTS[n] for n in ("q1", "q3"))
    for sql in (q1, q3):
        runner.execute(sql)
    root = tmp_path_factory.mktemp("traces")
    trace_dir = os.path.join(str(root), "some.cell")
    straddling = profiler_tests.begun_before_the_trace(runner, q3, trace_dir)
    results = []
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            results.append(straddling())
            results.append(runner.execute(q1))
            results.append(runner.execute(q3))
    finally:
        jax.profiler.stop_trace()
    return str(root), [r.stats for r in results]


def test_the_metrics_on_a_recorded_trace(recorded, monkeypatch, capsys):
    root, accounts = recorded
    monkeypatch.setattr(spans, "TRACE_ROOT", root)
    st = spans.load(spans.newest_xplane(root))
    reduced = stmt_account.reduce(st)
    window_s = reduced["window_s"]
    # every statement's event carries what its `result.stats` carries
    assert [s["query_id"] for s in reduced["statements"]] == [
        a["query_id"] for a in accounts]
    for got, want in zip(reduced["statements"], accounts):
        want = want["account"]
        assert set(want) <= set(got)
        for key, value in want.items():
            assert got[key] == pytest.approx(value, rel=1e-6), key
    # the first began before the window: under one statement of the sum
    first = reduced["statements"][0]
    assert first["done_s"] < 1e-6 * first["wall_us"]
    assert 2.0 < reduced["equivalents"] < 3.0 and reduced["unfinished"] == 0
    run = synthetic_run({"window_s": window_s, "busy_s": 0.5 * window_s})
    got = stmt_account.read(run)
    assert loud_lines(capsys) == [] and list(got) == READERS
    walls = [a["account"] for a in accounts]
    assert got["stmt_syncs"] == statistics.median(a["syncs"] for a in walls)
    assert got["stmt_syncs"] == walls[0]["syncs"] == walls[2]["syncs"]   # both Q3
    assert got["stmt_execute_ms"] == pytest.approx(
        statistics.median(a["execute_us"] for a in walls) / 1e3, rel=1e-6)
    assert got["stmt_sync_ms"] == pytest.approx(
        statistics.median(a["sync_us"] for a in walls) / 1e3, rel=1e-6)
    assert got["stmt_cpu_ms"] == pytest.approx(
        statistics.median(a["cpu_us"] for a in walls) / 1e3, rel=1e-6)
    assert got["stmt_offcpu_ms"] >= 0
    assert got["stmt_device_ms"] == pytest.approx(
        1e3 * 0.5 * window_s / reduced["equivalents"])
    assert got["stmt_launches"] == pytest.approx(
        reduced["launches"] / reduced["equivalents"])
    assert reduced["launches"] > 0


def test_the_table_of_a_cells_last_traced_run(recorded, monkeypatch, capsys):
    root, accounts = recorded
    monkeypatch.setattr(spans, "TRACE_ROOT", root)
    assert stmt_account.main(["some.cell"]) == 0
    out = capsys.readouterr().out
    assert "statement-equivalents in the window" in out
    for a in accounts:
        assert a["query_id"] in out
    assert "join.match_total" in out and "rows_scanned" in out
    assert "join_probe_path.sorted" in out
    assert stmt_account.main(["no.such.cell"]) == 1
    assert stmt_account.main([]) == 2


# -- the three answers without data -------------------------------------------------------------


def test_read_answers_zero_loudly_when_the_runs_trace_is_not_found(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    run = synthetic_run({"window_s": 3.0, "busy_s": 1.0})
    assert stmt_account.read(run) == {name: 0.0 for name in READERS}
    lines = loud_lines(capsys)
    assert len(lines) == 1 and "no .xplane.pb" in lines[0]["why"]
    run.trace = run.trace_completed = None
    assert stmt_account.read(run) == {}
    run = synthetic_run({"window_s": 3.0, "busy_s": 1.0})
    run.trace_completed = []
    assert stmt_account.read(run) == {}
    assert loud_lines(capsys) == []


def test_another_runs_trace_is_not_read(recorded, monkeypatch, capsys):
    root, _accounts = recorded
    monkeypatch.setattr(spans, "TRACE_ROOT", root)
    run = synthetic_run({"window_s": 2.5, "busy_s": 1.0})
    assert stmt_account.read(run) == {name: 0.0 for name in READERS}
    lines = loud_lines(capsys)
    assert len(lines) == 1 and "not this run's trace" in lines[0]["why"]


def test_a_program_from_before_the_account_leaves_the_metrics_out(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    put_trace(str(tmp_path), BEFORE_THE_ACCOUNT)
    window_s = spans.reduce(spans.load(BEFORE_THE_ACCOUNT))["window_s"]
    run = synthetic_run({"window_s": window_s, "busy_s": 1.0})
    assert stmt_account.read(run) == {}
    lines = loud_lines(capsys)
    assert len(lines) == 1 and "stmt.done" in lines[0]["why"]
