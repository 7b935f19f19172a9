"""`chipbench/spans.py` and the nine `program_span` readers, on the CPU:
self time, per-statement sums and the three idle shares on traces small
enough to do by hand, the same on `trace_spans_small.xplane.pb` (recorded
on the v5e with the program's spans in it, PR 25), and what a reader
answers when the run's trace cannot be found, is another run's, or holds
no program span."""

import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, spans, stats, trace, traffic  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = traffic.load_json(os.path.join(ROOT, "BENCHMARK.json"))
READERS = [m["name"] for m in BENCHMARK["per_layer"]
           if m["source"] == "program_span"]
RECORDED = os.path.join(HERE, "trace_spans_small.xplane.pb")
P = spans.PROGRAM


def ev(name, start, end, **stats_):
    return spans.Event(name if name.startswith("PjitFunction") else P + name,
                       start, end, stats_)


def hand_trace(lines, device_ops, engine=((0.0, 10.0),), window=(0.0, 10.0)):
    """A SpanTrace with one chip whose operations are `device_ops`
    [(start, end)], no clock shift, and the benchmark's annotations."""
    names = [f"%op.{i}" for i in range(len(device_ops))]
    yardstick = trace.Trace(
        {"/device:TPU:0": (names, np.asarray([a for a, _ in device_ops], float),
                           np.asarray([b for _, b in device_ops], float))},
        {}, {},
        [trace.Annotation(trace.WINDOW, *window, {})]
        + [trace.Annotation(trace.ENGINE, a, b, {}) for a, b in engine],
    )
    return spans.SpanTrace(yardstick, lines, [])


def test_interval_sets_by_hand():
    a = spans.intervals([(0, 2), (1, 3), (5, 6)])
    b = spans.intervals([(2.5, 5.5)])
    assert list(zip(*a)) == [(0, 3), (5, 6)]
    assert list(zip(*spans.both(a, b))) == [(2.5, 3), (5, 5.5)]
    assert list(zip(*spans.minus(a, b))) == [(0, 2.5), (5.5, 6)]
    assert list(zip(*spans.either(a, b))) == [(0, 6)]
    assert spans.measure(spans.minus(b, a)) == pytest.approx(2.0)
    empty = spans.intervals([])
    assert spans.measure(spans.both(a, empty)) == 0.0
    assert spans.measure(spans.minus(a, empty)) == pytest.approx(4.0)


def test_self_time_is_wall_minus_what_children_on_the_line_cover():
    line = [
        ev("query.query", 1.0, 9.0, query_id="local-1"),
        ev("phase.plan", 1.5, 2.0, query_id="local-1"),
        ev("phase.execute", 2.0, 8.0, query_id="local-1", cpu_ns=2.5e9),
        ev("op.Scan.get_output", 2.0, 4.0),
        ev("sync.scan.rows_scanned", 3.0, 3.5, nbytes=8),
        ev("PjitFunction(f)", 4.5, 5.5), ev("PjitFunction(f)", 4.6, 5.4),
        ev("op.Agg.finish", 6.0, 11.0),         # runs past the window's end
        ev("sync.result", 7.0, 8.0, nbytes=64),
    ]
    clipped, uncovered = spans.nest(line, 0.0, 10.0)
    by_name = {}
    for e in clipped:
        by_name.setdefault(e.name, []).append(e)
    assert by_name[P + "query.query"][0].self_s == pytest.approx(8 - 0.5 - 6)
    assert by_name[P + "phase.execute"][0].self_s == pytest.approx(
        6 - 2 - 1 - 2)          # minus the scan, the dispatch, the clipped finish
    assert by_name[P + "op.Scan.get_output"][0].self_s == pytest.approx(1.5)
    # a child that outlasts its parent is cut to it: finish ends at 8
    finish = by_name[P + "op.Agg.finish"][0]
    assert finish.end == 8.0 and finish.self_s == pytest.approx(1.0)
    outer, inner = by_name["PjitFunction(f)"]
    assert outer.outermost and not inner.outermost
    assert outer.self_s == pytest.approx(0.2) and inner.self_s == pytest.approx(0.8)
    # every leaf knows its statement and whether `execute` encloses it
    assert all(e.query_id == "local-1" for e in clipped)
    assert by_name[P + "sync.result"][0].in_execute
    assert not by_name[P + "phase.plan"][0].in_execute
    assert uncovered[P + "phase.execute"] == [(4.0, 4.5), (5.5, 6.0)]
    # self times partition the line's covered time
    assert sum(e.self_s for e in clipped) == pytest.approx(8.0)

    reduced = spans.reduce(hand_trace([line], [(0.0, 10.0)]))
    assert reduced["spans"]["PjitFunction(f)"] == {
        "count": 1, "wall_s": pytest.approx(1.0), "self_s": pytest.approx(1.0)}
    assert reduced["dispatches"] == {"f": {"count": 1, "host_s": pytest.approx(1.0)}}
    totals = reduced["totals"]
    assert totals["syncs"] == 2 and totals["sync_s"] == pytest.approx(1.5)
    assert totals["plan_s"] == pytest.approx(0.5)
    assert totals["execute_s"] == pytest.approx(6.0)
    assert totals["execute_self_s"] == pytest.approx(1.0)
    # 6 s of wall - 2.5 s on the CPU - 1.5 s in readbacks
    assert totals["offcpu_s"] == pytest.approx(2.0)
    assert totals["scan_misses"] == 0
    assert reduced["idle"]["in_engine"] == 0.0          # the device never idles


def test_sums_per_statement_follow_the_enclosing_query_event():
    one = [
        ev("server.queued", 0.1, 0.3, handoff_us=50_000),
        ev("phase.parse", 0.3, 0.5),
        ev("query.query", 0.5, 3.0, query_id="local-1"),
        ev("phase.plan", 0.5, 0.7, query_id="local-1", hit=1),
        ev("op.Scan.get_output", 1.0, 2.0),
        ev("sync.scan.rows_scanned", 1.2, 1.4),
        ev("result.fetch", 2.0, 2.9), ev("sync.result", 2.0, 2.5),
        ev("query.query", 4.0, 6.0, query_id="local-3"),
        ev("sync.result", 5.0, 5.5),
    ]
    two = [
        ev("server.queued", 0.0, 0.1),
        ev("query.query", 1.0, 2.0, query_id="local-2"),
        ev("scan.host_filter", 1.1, 1.2), ev("scan.to_device", 1.2, 1.5),
        ev("server.respond", 6.0, 6.1, pages=1, rows=4, since_finished_us=300_000),
        ev("server.respond", 6.2, 6.3, pages=0, rows=0),
    ]
    reduced = spans.reduce(hand_trace([one, two], [(0.0, 10.0)]))
    s = reduced["statements"]
    assert set(s) == {"local-1", "local-2", "local-3"}
    assert s["local-1"] == {
        "wall_s": pytest.approx(2.5), "plan_s": pytest.approx(0.2), "syncs": 2,
        "sync_s": pytest.approx(0.7), "op_s": pytest.approx(1.0),
        "result_s": pytest.approx(0.9)}
    assert s["local-3"]["syncs"] == 1 and s["local-2"]["syncs"] == 0
    totals = reduced["totals"]
    assert totals["queued_s"] == pytest.approx(0.2 + 0.1 + 0.05)
    assert totals["result_wait_s"] == pytest.approx(0.3)
    assert totals["plan_s"] == pytest.approx(0.2 + 0.2)    # the parse counts
    assert totals["syncs"] == 3 and totals["scan_misses"] == 2
    assert reduced["program_events"] == len(one) + len(two)


def test_idle_inside_the_engine_is_split_three_ways_by_hand():
    """Device busy 0-2 and 8-10, so idle 2-8; the engine covers 1-7, so
    idle inside the engine is 2-7, 5 s. Thread A is in an operator call
    2-4 with a readback 3-4 inside; thread B is in an operator call
    2.5-6.5 with a readback 3-5.5 inside. So: 2-3 A works (B too from
    2.5); 3-4 both wait for a readback; 4-5.5 B still does; 5.5-6.5 B
    works; 6.5-7 nobody is inside anything but the containers."""
    a = [ev("query.query", 1.0, 7.0, query_id="local-1"),
         ev("phase.execute", 1.5, 7.0, query_id="local-1"),
         ev("op.Agg.add_input", 2.0, 4.0), ev("sync.agg.flag", 3.0, 4.0)]
    b = [ev("op.Join.finish", 2.5, 6.5), ev("sync.join.match_total", 3.0, 5.5)]
    st = hand_trace([a, b], [(0.0, 2.0), (8.0, 10.0)], engine=[(1.0, 7.0)])
    reduced = spans.reduce(st)
    idle = reduced["idle"]
    assert idle["in_engine"] == pytest.approx(5.0)
    assert idle[spans.HOST_WORKING] == pytest.approx(1.0 + 1.0)       # 2-3, 5.5-6.5
    assert idle[spans.IN_SYNC] == pytest.approx(2.5)                   # 3-5.5
    assert idle[spans.UNATTRIBUTED] == pytest.approx(0.5)              # 6.5-7
    assert (idle[spans.HOST_WORKING] + idle[spans.IN_SYNC]
            + idle[spans.UNATTRIBUTED]) == pytest.approx(idle["in_engine"])
    # and it is the yardstick's own in-engine idle time
    yard = dict(map(tuple, trace.reduce(st.yardstick)["idle_gaps"]))
    assert yard["total.in_engine"] == pytest.approx(idle["in_engine"])
    # by innermost covering span, each thread counted
    by_span = reduced["idle_by_span"]
    assert by_span[P + "op.Agg.add_input"] == pytest.approx(1.0)
    assert by_span[P + "sync.join.match_total"] == pytest.approx(2.5)
    assert by_span[P + "phase.execute"] == pytest.approx(3.0)    # 4-7; 1.5-2 is busy


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


def read_all(run):
    return {name: traffic.load_module(os.path.join(
        ROOT, "chipbench", "layer_metrics", f"{name}.py")).read(run)
        for name in READERS}


def no_span_lines(capsys):
    return [line for line in map(json.loads, capsys.readouterr().out.splitlines())
            if line["phase"] == "NO_PROGRAM_SPANS"]


def put_trace(root, source, cell="some.cell"):
    there = os.path.join(root, cell, "plugins", "profile", "2026_09_27")
    os.makedirs(there)
    shutil.copy(source, os.path.join(there, "host.xplane.pb"))


def test_the_nine_readers_are_the_program_span_entries():
    assert len(READERS) == 9
    by_name = {m["name"]: m for m in BENCHMARK["per_layer"]}
    assert all(by_name[n]["better"] == "lower" for n in READERS)
    assert {by_name[n]["moves"] for n in READERS} == {"stmt_p50_ms", "stmts_per_s"}


def test_readers_answer_zero_loudly_when_the_runs_trace_is_not_found(
        tmp_path, monkeypatch, capsys):
    """As `test_layer_metric_readers` builds it: the reduction of a trace
    whose file lies nowhere a reader can look."""
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    small = trace.reduce(trace.load(os.path.join(HERE, "trace_small.xplane.pb")))
    run = synthetic_run(small)
    assert read_all(run) == {name: 0.0 for name in READERS}
    lines = no_span_lines(capsys)
    assert len(lines) == 1 and "no .xplane.pb" in lines[0]["why"]    # once for all nine
    run.trace = run.trace_completed = None
    assert read_all(run) == {name: None for name in READERS}
    assert no_span_lines(capsys) == []


def test_readers_refuse_a_stale_trace(tmp_path, monkeypatch, capsys):
    """The newest trace on disk is another run's: its window is not the
    length of this run's."""
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    put_trace(str(tmp_path), RECORDED)
    small = trace.reduce(trace.load(os.path.join(HERE, "trace_small.xplane.pb")))
    run = synthetic_run(small)
    assert read_all(run) == {name: 0.0 for name in READERS}
    lines = no_span_lines(capsys)
    assert len(lines) == 1 and "not this run's trace" in lines[0]["why"]


def test_readers_leave_out_a_program_without_spans(tmp_path, monkeypatch, capsys):
    """The run's own trace, found and read, from a program that writes no
    span (the parent of PR 25): nothing to report, said once."""
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    put_trace(str(tmp_path), os.path.join(HERE, "trace_small.xplane.pb"))
    small = trace.reduce(trace.load(os.path.join(HERE, "trace_small.xplane.pb")))
    run = synthetic_run(small)
    assert read_all(run) == {name: None for name in READERS}
    lines = no_span_lines(capsys)
    assert len(lines) == 1 and "no tpusql.* event" in lines[0]["why"]
    got = harness.read_layer_metrics(BENCHMARK, "sf1.scan_agg", synthetic_run(small))
    assert not set(got) & set(READERS) and "device_idle_pct" in got


def test_the_recorded_trace_reduces_and_its_idle_shares_sum(
        tmp_path, monkeypatch, capsys):
    """`trace_spans_small.xplane.pb`: 100 ms of `tiny` Q6 and Q1 through
    the HTTP path on the v5e, two client threads, the benchmark's own
    annotations (my chip run, PR 25). Of the 4.7 MB the profiler wrote,
    the file keeps what `trace.py` and `spans.py` read (the host's
    `tpusql.*`, `chipbench.*`, `PjitFunction(*)` and `DoEnqueueProgram`
    events, the device's `XLA Ops` and `XLA Modules` lines) and drops the
    rest, mostly the HLO of every program the process had loaded; both
    reductions give the same numbers on either file."""
    st = spans.load(RECORDED)
    reduced = spans.reduce(st)
    yard = trace.reduce(st.yardstick)
    assert reduced["window_s"] == yard["window_s"]
    assert reduced["clock_shift_s"] == yard["clock_shift_s"]
    assert reduced["program_events"] > 50 and len(st.lines) >= 3
    names = set(reduced["spans"])
    assert {P + "query.query", P + "phase.execute", P + "phase.plan",
            P + "sync.result", P + "result.to_rows", P + "server.queued",
            P + "server.respond", P + "scan.batches"} <= names
    assert any(n.startswith(P + "op.TableScanOperator.") for n in names)
    assert any(n.startswith("PjitFunction(") for n in names)
    assert reduced["totals"]["scan_misses"] == 0
    assert reduced["programs"] and reduced["dispatches"]
    idle = reduced["idle"]
    in_engine = dict(map(tuple, yard["idle_gaps"]))["total.in_engine"]
    assert idle["in_engine"] == pytest.approx(in_engine, rel=1e-6)
    assert in_engine > 0
    assert (idle[spans.HOST_WORKING] + idle[spans.IN_SYNC]
            + idle[spans.UNATTRIBUTED]) == pytest.approx(in_engine, rel=1e-6)
    for row in reduced["spans"].values():
        assert -1e-9 <= row["self_s"] <= row["wall_s"] + 1e-9 or row["count"] == 0
    for s in reduced["statements"].values():
        assert s["sync_s"] <= s["wall_s"] + 1e-9
    # device time by program is the yardstick's busy time, seen by program
    assert sum(r["device_s"] for r in reduced["programs"].values()) == pytest.approx(
        yard["busy_s"], rel=0.2)

    # and through the readers, as the harness calls them
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    put_trace(str(tmp_path), RECORDED)
    run = synthetic_run(yard)
    got = harness.read_layer_metrics(BENCHMARK, "sf1.scan_agg", run)
    assert set(READERS) <= set(got)
    value = {k: v["value"] for k, v in got.items()}
    assert value["host_syncs_per_stmt"] == reduced["totals"]["syncs"] / 2
    assert value["plan_ms"] == pytest.approx(1e3 * reduced["totals"]["plan_s"] / 2)
    assert (value["idle_host_working_pct"] + value["idle_in_sync_pct"]
            + value["idle_unattributed_pct"]) == pytest.approx(
                100 * in_engine / yard["window_s"])
    assert all(value[name] >= 0 for name in READERS)
    assert no_span_lines(capsys) == []


def test_the_table_of_the_last_traced_run(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    assert spans.main(["sf1.scan_agg"]) == 1            # no traced run yet
    put_trace(str(tmp_path), RECORDED, cell="sf1.scan_agg")
    assert spans.main(["sf1.scan_agg"]) == 0
    out = capsys.readouterr().out
    for title in ("spans by self time", "device idle inside the engine",
                  "host dispatches", "device time by program",
                  "totals over the window"):
        assert title in out
    assert "tpusql.sync.result" in out and spans.HOST_WORKING in out
    assert spans.main([]) == 2
