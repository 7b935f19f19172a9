"""The benchmark's own checks, on the CPU: the yardstick in `chipbench/`
(traffic draws, window accounting, trace reduction on a small trace
recorded on the chip, the names in BENCHMARK.json), the cells' phases at
`tiny` through the HTTP path, the plain references against
`chip_smoke.py`'s, and the proof that `correct` can come out false: a
wrong unit, a wrong last digit, a tampered timed path, and the control
(the same sums in float32). The chip runs are `python3 chipbench/run.py`
on the machine with the chip."""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from chipbench import data, harness, stats, trace, traffic  # noqa: E402
from chipbench.references import _common  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = traffic.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
TINY = 0.01
STATEMENTS = ("q1", "q6", "q3", "g3")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A stand-in for the checkout's root: the data cache and a traced
    run's files go under it."""
    return str(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="module")
def tables():
    return chip_smoke.generate_tables(TINY)


def validation(name):
    path = os.path.join(ROOT, "chipbench", "statements", f"{name}.json")
    return traffic.load_json(path)["validation"]


# -- traffic ----------------------------------------------------------------


def test_traffic_repeats_for_a_seed_and_differs_across_seeds():
    mix = traffic.load_traffic("scan_agg.2streams")
    a, b = traffic.plan(mix, 3_000_000_001), traffic.plan(mix, 3_000_000_001)
    assert [i.sql for i in a.instances] == [i.sql for i in b.instances]
    assert a.schedule == b.schedule
    c = traffic.plan(mix, 7)
    assert [i.sql for i in a.instances] != [i.sql for i in c.instances]
    assert a.schedule != c.schedule
    # the amount of work does not depend on the seed
    for p in (a, c):
        names = [i.name for i in p.instances]
        assert {n: names.count(n) for n in names} == {
            n: mix["params_per_statement"] for n in mix["statements"]
        }
        assert len(p.schedule) == mix["streams"]
        assert all(sorted(o) == list(range(len(names))) for o in p.schedule)
        # the pattern of statement kinds is the same for every seed:
        # stream k starts at slot k of q1, q6, g3, q1, ...
        for k, order in enumerate(p.schedule):
            kinds = [names[i] for i in order]
            assert kinds == [mix["statements"][(k + j) % 3] for j in range(9)]
    # a traffic file holds the mix and nothing of the harness's windows
    assert set(mix) == {"statements", "loop", "streams", "params_per_statement",
                        "client_poll_ms", "why"}
    # the draws stay inside the ranges the statement files give
    for inst in a.instances + c.instances:
        for key, spec in inst.statement.draws.items():
            if spec["draw"] == "int":
                assert spec["lo"] <= inst.params[key] <= spec["hi"]
            elif spec["draw"] == "choice":
                assert inst.params[key] in spec["values"]
            else:
                assert spec["lo"] <= inst.params[key] <= spec["hi"]
        assert "{" not in inst.sql


# -- statistics ---------------------------------------------------------------


def test_percentile_and_window_accounting():
    assert stats.exact_percentile(list(range(1, 101)), 0.95) == 95
    assert stats.exact_percentile([5.0], 0.95) == 5.0
    with pytest.raises(ValueError):
        stats.exact_percentile([], 0.5)
    t0 = 100.0
    samples = [
        stats.Sample(0, 0, t0 + i, t0 + i + 0.5, [[i]]) for i in range(9)
    ]
    samples.append(stats.Sample(1, 0, t0 + 9.2, t0 + 10.4, [[9]]))  # in flight
    samples.append(stats.Sample(1, 0, t0 + 2.0, t0 + 4.0, None, "boom"))
    acc = stats.account(samples, t0, 10.0)
    assert (len(acc.completed), len(acc.failed), acc.in_flight) == (9, 1, 1)
    assert acc.attempted == 10
    e2e = stats.end_to_end(acc)
    assert e2e["stmts_per_s"] == pytest.approx(0.9)
    assert e2e["stmt_p50_ms"] == pytest.approx(500.0)
    assert e2e["stmt_p95_ms"] == pytest.approx(500.0)
    with pytest.raises(ValueError):
        stats.account([stats.Sample(0, 0, t0 - 1, t0, [])], t0, 10.0)


# -- trace reduction ------------------------------------------------------------


def test_trace_reduction_on_a_recorded_trace():
    """`trace_small.xplane.pb`: recorded on the v5e in PR 24. Inside a
    22 ms `chipbench.window`, two statements (a 65,536-row int64
    sort and an elementwise program inside `runner.execute`, 3 ms of
    protocol after it, 5 ms of nothing between) and a matmul."""
    t = trace.load(os.path.join(HERE, "trace_small.xplane.pb"))
    assert len(t.device_ops) == 1 and len(t.modules) == 5
    r = trace.reduce(t)
    assert r["chips"] == 1 and r["statements_in_window"] == 2
    assert r["window_s"] == pytest.approx(0.022302, rel=1e-4)
    assert r["busy_s"] == pytest.approx(144.7e-6, rel=1e-3)
    assert r["idle_pct"] == pytest.approx(99.351, abs=1e-3)
    assert r["sort_seconds"] / r["op_seconds"] == pytest.approx(0.8688, abs=1e-3)
    assert r["device_ops"][0][0] == "%sort.11"
    assert r["clock_shift_s"] == pytest.approx(1.3158e-3, rel=1e-3)
    totals = dict(r["idle_gaps"][:3])
    assert sum(totals.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert totals["total.in_engine"] == pytest.approx(3.02e-3, rel=0.01)
    assert totals["total.in_protocol"] == pytest.approx(7.68e-3, rel=0.01)
    assert totals["total.no_statement_in_flight"] == pytest.approx(11.46e-3, rel=0.01)
    assert r["idle_gaps"][3][0] == "gap1.no_statement_in_flight"
    assert len(r["idle_gaps"]) <= 10 and len(r["device_ops"]) <= 10


def test_trace_reduction_by_hand():
    """Busy union, shares and gap attribution on a trace small enough to
    work out on paper; two ops overlap, the clocks are 1 s apart."""
    ops = (["%sort.1 = (u32[8]) sort(u32[8] %x)", "%fusion.2 = u32[8] fusion()",
            "%add.3 = u32[8] add()"],
           np.array([1.0, 1.5, 4.0]) - 1.0, np.array([2.0, 3.0, 5.0]) - 1.0)
    t = trace.Trace(
        {"/device:TPU:0": ops},
        {"/device:TPU:0/1": (0.0, 2.0)}, {"/device:TPU:0/1": 1.0},
        [trace.Annotation(trace.WINDOW, 0.0, 10.0, {}),
         trace.Annotation(trace.CLIENT, 0.5, 6.0, {}),
         trace.Annotation(trace.ENGINE, 0.8, 3.5, {})],
    )
    assert trace.clock_shift(t) == 1.0
    r = trace.reduce(t)
    assert r["busy_s"] == pytest.approx(3.0)          # [1,3] and [4,5]
    assert r["idle_pct"] == pytest.approx(70.0)
    assert r["sort_seconds"] == pytest.approx(1.0)
    assert r["op_seconds"] == pytest.approx(3.5)
    totals = dict(r["idle_gaps"][:3])
    # idle: [0,1] [3,4] [5,10]; engine covers [0.8,1] and [3,3.5]
    assert totals["total.in_engine"] == pytest.approx(0.7)
    assert totals["total.in_protocol"] == pytest.approx(0.3 + 0.5 + 1.0)
    assert totals["total.no_statement_in_flight"] == pytest.approx(0.5 + 4.0)
    assert r["idle_gaps"][3] == ["gap1.no_statement_in_flight", pytest.approx(5.0)]
    assert trace.is_sort("%fusion.9 = u32[8] fusion(), calls=%sort_comp") is False


# -- BENCHMARK.json against the files ----------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_every_name_resolves_to_its_files_and_every_file_is_named():
    bench_dir = os.path.join(ROOT, "chipbench")

    def stems(sub, ext):
        return {f[: -len(ext)] for f in os.listdir(os.path.join(bench_dir, sub))
                if f.endswith(ext) and not f.startswith("_")}

    assert BENCHMARK["command"] == ["python3", "chipbench/run.py"]
    assert set(BENCHMARK["paths"]) == {"chipbench", "tests/chipbench"}
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    assert {os.path.basename(c["file"])[:-5] for c in configs.values()} == stems("configs", ".json")
    used_configs, used_traffic, used_statements = set(), set(), set()
    for w in BENCHMARK["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        used_configs.add(w["config"])
        used_traffic.add(w["traffic"])
        cfg = traffic.load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
        assert cfg["name"] == w["config"] and cfg["chips"] == w["chips"]
        assert cfg["source"] == configs[w["config"]]["source"]
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        assert os.path.exists(os.path.join(bench_dir, "runners", cfg["runner"] + ".py"))
        mix = traffic.load_traffic(w["traffic"])
        assert os.path.exists(os.path.join(bench_dir, "loops", mix["loop"] + ".py"))
        used_statements.update(mix["statements"])
    assert used_configs == set(configs)
    assert used_traffic == stems("traffic", ".json")
    assert used_statements == stems("statements", ".json")
    assert {traffic.load_json(os.path.join(bench_dir, "statements", s + ".json"))["reference"]
            for s in used_statements} == stems("references", ".py")
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert {m["name"] for m in BENCHMARK["per_layer"]} == stems("layer_metrics", ".py")
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    layers = {m["layer"] for m in BENCHMARK["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(layer in perf for layer in layers)
    peaks = traffic.load_json(os.path.join(bench_dir, "peaks.json"))
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    for path in BENCHMARK["paths"]:
        for folder, _dirs, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in folder:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


# -- the cells at tiny, through the HTTP path ----------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_cell_at_tiny_answers_equal_the_references(cell, checkout, capsys):
    result = harness.run_cell(
        cell, seed=2_147_483_659, seconds=1.0, trace=False, cache_root=checkout,
        scale=TINY, require_tpu=False,
    )
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert set(result["metrics"]) == {m["name"] for m in BENCHMARK["end_to_end"]}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    phases = {line["phase"]: line for line in lines}
    assert {"data", "device", "warm", "memory", "setup", "window",
            "statements", "compare"} <= set(phases)
    assert phases["window"]["counters"]["xla_compiles"] == 0
    assert phases["window"]["counters"]["plan_cache.misses"] == 0
    setup = phases["setup"]
    assert setup["setup_s"] == pytest.approx(
        setup["data_s"] + setup["load_s"] + setup["warm_s"] + setup["other_s"])
    # the second cell on a configuration finds its columns in the cache
    again, generated = data.ensure_columns(checkout, TINY, {"lineitem": ["l_quantity"]})
    assert generated == 0 and os.path.isdir(again)


def test_layer_metric_readers(tables):
    """Every reader on a synthetic run: two statements, one engine call
    each inside the client's interval, the recorded trace's reduction."""
    mix = traffic.load_traffic("scan_agg.2streams")
    plan = traffic.plan(mix, 1)
    a, b = plan.instances[0], plan.instances[3]  # a q1 and a q6
    samples = [stats.Sample(0, 0, 10.0, 10.030, [[1]]),
               stats.Sample(1, 3, 10.0, 10.012, [[2]])]
    engine = [(a.sql, 10.004, 10.029), (b.sql, 10.003, 10.010),
              (b.sql, 9.0, 9.5)]  # the last: warm-up, outside the window
    reduced = trace.reduce(trace.load(os.path.join(HERE, "trace_small.xplane.pb")))
    run = harness.RunData(
        mix, {}, plan.instances, stats.account(samples, 10.0, 1.0), engine,
        {"xla_compiles": 0.0, "plan_cache.hits": 3.0, "plan_cache.misses": 1.0},
        [1000.0] * len(plan.instances),
        [harness.scan_row_bytes(i.statement, tables) for i in plan.instances],
        10.0, {"hbm_bytes_per_s": 819e9}, reduced, samples,
    )
    got = harness.read_layer_metrics(BENCHMARK, "sf1.scan_agg", run)
    assert set(got) == {m["name"] for m in BENCHMARK["per_layer"]}
    value = {k: v["value"] for k, v in got.items()}
    assert value["engine_ms"] == pytest.approx(16.0)       # median of 25 and 7
    assert value["protocol_ms"] == pytest.approx(5.0)      # both 5 ms
    assert value["plan_cache_hit_pct"] == 75.0 and value["compiles_in_window"] == 0
    assert value["device_idle_pct"] == pytest.approx(reduced["idle_pct"])
    assert value["device_busy_ms_per_stmt"] == pytest.approx(1e3 * reduced["busy_s"] / 2)
    assert run.row_bytes[0] == 4 * 8 + 2 * 4 and run.row_bytes[3] == 16
    bytes_read = 1000.0 * (40 + 16)
    assert value["scan_roofline_pct"] == pytest.approx(
        100 * bytes_read / 819e9 / reduced["busy_s"])
    # the join cell does not list the scan roofline; a run without a
    # trace leaves the trace metrics out
    assert "scan_roofline_pct" not in harness.read_layer_metrics(BENCHMARK, "sf1.join", run)
    run.trace = run.trace_completed = None
    assert set(harness.read_layer_metrics(BENCHMARK, "sf1.scan_agg", run)) == {
        "protocol_ms", "engine_ms", "plan_cache_hit_pct", "compiles_in_window"}


# -- the references, and `correct` coming out false ------------------------------------


@pytest.mark.parametrize("name", STATEMENTS)
def test_reference_agrees_with_chip_smoke_at_the_validation_parameters(
        name, tables, monkeypatch):
    statement = traffic.load_statement(name)
    inst = traffic.instantiate(statement, validation(name))
    want = chip_smoke.REFERENCES[name](tables)
    assert harness.same_rows(statement, statement.module.reference(tables, inst.params), want)
    # the references work block by block: `tiny` is one block as it
    # stands, and fifteen or more of these
    monkeypatch.setattr(_common, "BLOCK_ROWS", 4000)
    assert len(list(_common.blocks(len(tables["lineitem"]["l_quantity"][0])))) >= 15
    assert harness.same_rows(statement, statement.module.reference(tables, inst.params), want)
    # and the text is chip_smoke's statement, up to white space
    theirs = dict(chip_smoke.STATEMENTS)[name]
    assert inst.sql.split() == theirs.split()


@pytest.mark.parametrize("case", ["sum_off_by_one_unit", "double_off_in_last_digit",
                                  "rows_swapped"])
def test_a_wrong_answer_is_not_correct(case, tables):
    statement = traffic.load_statement("q1")
    want = statement.module.reference(tables, validation("q1"))
    got = [list(r) for r in want]
    assert harness.same_rows(statement, got, want)
    if case == "sum_off_by_one_unit":
        got[0][9] += 1                      # count_order
    elif case == "double_off_in_last_digit":
        got[0][5] = float(np.nextafter(got[0][5], np.inf))   # sum_charge
    else:
        got[0], got[1] = got[1], got[0]
    assert not harness.same_rows(statement, got, want)


@pytest.mark.parametrize("name", ["q1", "q6", "q3"])
def test_control_float32_sums_are_not_correct(name, tables, monkeypatch):
    """The control: the reference itself with its sums accumulated in
    float32, put in the program's place. At `tiny` it fails Q1, Q6 and
    Q3 (G3's groups are small enough here for float32 to be exact; at
    the cells' sizes it fails G3 too: PERF.md)."""
    statement = traffic.load_statement(name)
    params = validation(name)
    want = statement.module.reference(tables, params)
    for block_rows in (_common.BLOCK_ROWS, 4000):
        monkeypatch.setattr(_common, "BLOCK_ROWS", block_rows)
        control = statement.module.reference(
            tables, params, sums=_common.group_sums_float32)
        assert len(control) == len(want)
        assert not harness.same_rows(statement, control, want)


@pytest.mark.parametrize("case", ["every_fifth_execution", "one_instance_always"])
def test_a_tampered_timed_path_is_not_correct(case, checkout, monkeypatch, capsys):
    """Everything of a run but the look for a chip, with the timed path
    broken underneath, where the answer is produced: the runner alters
    one Q6 sum by one unit in every fifth execution, or answers one Q6
    text with another parameter's sum every time, warm-up included (a
    cache that hands back another predicate's copy): every instance is
    held against its own reference, so agreeing with itself is not
    enough."""
    real = harness.wrap_execute

    def tampering(runner, log):
        inner = runner.execute
        calls, q6_texts = [], []

        def execute(sql, *args, **kwargs):
            result = inner(sql, *args, **kwargs)
            calls.append(sql)
            if "sum(l_extendedprice * l_discount)" not in sql:
                return result
            if sql not in q6_texts:
                q6_texts.append(sql)
            if case == "every_fifth_execution" and len(calls) % 5 == 0:
                result.rows = [[result.rows[0][0] + 0.0001]]
            if case == "one_instance_always" and sql == q6_texts[0]:
                result.rows = [[12345.6789]]
            return result

        execute.__wrapped__ = inner
        runner.execute = execute
        real(runner, log)

    monkeypatch.setattr(harness, "wrap_execute", tampering)
    result = harness.run_cell(
        "sf10.scan_agg", seed=5, seconds=1.0, trace=False, cache_root=checkout,
        scale=TINY, require_tpu=False,
    )
    assert result["correct"] is False and result["failed"] >= 1
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    compare = {l["against"]: l for l in lines if l.get("against")}
    assert compare["q6.reference"]["mismatches"] >= 1
    assert compare["q1.reference"]["mismatches"] == 0 == compare["q1.reference"]["limit"]
    # 3 values of Q1, 3 of Q6, G3 has no parameter: 7 texts, 7 references
    assert [l["references"] for l in lines if "references" in l] == [7]


def test_main_refuses_a_cpu_backend(capsys):
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", "sf1.scan_agg", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None) and "no TPU" in str(e.value.code)
    assert '"correct"' not in capsys.readouterr().out
    for name in ("harness.py", "run.py"):
        with open(os.path.join(ROOT, "chipbench", name)) as f:
            source = f.read()
        for escape in ("JAX_PLATFORMS", "--allow-cpu", "interpret=True"):
            assert escape not in source, (name, escape)
