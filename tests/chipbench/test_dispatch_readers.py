"""The two readers of issue 26, `dispatches_per_stmt` and
`dispatch_ms_per_stmt`, on the CPU: the recorded trace
(`trace_spans_small.xplane.pb`, the v5e, PR 25) by hand, and the answers
where there is nothing to read, which are `spans.read_total`'s."""

import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, spans, stats, trace, traffic  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = traffic.load_json(os.path.join(ROOT, "BENCHMARK.json"))
RECORDED = os.path.join(HERE, "trace_spans_small.xplane.pb")
WITHOUT_SPANS = os.path.join(HERE, "trace_small.xplane.pb")
READERS = ["dispatches_per_stmt", "dispatch_ms_per_stmt"]


def run_over(reduced):
    """Two statements completed in the traced window, as the other
    readers' tests build it."""
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


def put_trace(root, source):
    there = os.path.join(root, "some.cell", "plugins", "profile", "2026_09_27")
    os.makedirs(there)
    shutil.copy(source, os.path.join(there, "host.xplane.pb"))


def read(name, run):
    return traffic.load_module(os.path.join(
        ROOT, "chipbench", "layer_metrics", f"{name}.py")).read(run)


def test_the_entries_as_the_issue_gives_them():
    by_name = {m["name"]: m for m in BENCHMARK["per_layer"]}
    assert [m["name"] for m in BENCHMARK["per_layer"]][-2:] == READERS
    for name, moves in zip(READERS, ("stmt_p50_ms", "stmts_per_s")):
        m = by_name[name]
        assert (m["layer"], m["better"], m["moves"]) == ("operators, host loop", "lower", moves)
        assert "workloads" not in m          # every cell launches programs


@pytest.mark.parametrize("name", READERS)
def test_on_the_recorded_trace_by_hand(name, tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    put_trace(str(tmp_path), RECORDED)
    st = spans.load(RECORDED)
    reduced = spans.reduce(st)
    # by hand: every outermost `PjitFunction(` event of a host line that
    # starts inside the window, and its wall clipped to the window
    windows = [a for a in st.yardstick.annotations if a.name == trace.WINDOW]
    lo, hi = windows[0].start, windows[0].end
    events = [e for line in st.lines for e in line
              if e.name.startswith(spans.DISPATCH) and e.end > lo and e.start < hi]
    count = sum(row["count"] for row in reduced["dispatches"].values())
    assert 0 < count <= len(events)
    wall = sum(min(e.end, hi) - max(e.start, lo) for e in events)
    host_s = sum(row["host_s"] for row in reduced["dispatches"].values())
    assert 0 < host_s <= wall + 1e-9
    run = run_over(trace.reduce(st.yardstick))
    want = {"dispatches_per_stmt": count / 2, "dispatch_ms_per_stmt": 1e3 * host_s / 2}
    assert read(name, run) == pytest.approx(want[name])
    # and through the harness, with every other per-layer metric
    got = harness.read_layer_metrics(BENCHMARK, "sf1.join", run_over(trace.reduce(st.yardstick)))
    assert got[name]["value"] == pytest.approx(want[name])
    assert "NO_PROGRAM_SPANS" not in capsys.readouterr().out


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read(name, tmp_path, monkeypatch, capsys):
    """None without a trace, and from a program that writes no span
    (what a parent from before PR 25 gives); 0.0, said loudly, where the
    run's own trace cannot be found."""
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    small = trace.reduce(trace.load(WITHOUT_SPANS))
    assert read(name, run_over(small)) == 0.0
    assert "no .xplane.pb" in capsys.readouterr().out
    put_trace(str(tmp_path), WITHOUT_SPANS)
    assert read(name, run_over(small)) is None
    assert "no tpusql.* event" in capsys.readouterr().out
    run = run_over(small)
    run.trace = run.trace_completed = None
    assert read(name, run) is None
    run = run_over(small)
    run.trace_completed = []
    assert read(name, run) is None
