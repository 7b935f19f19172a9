"""TPC-H Q18 on the CPU at `tiny`, through the files of its cell
(`sf10.q18`, configuration `tpch-sf10-q18-1chip`, `chipbench/Q18.md`):
what `BENCHMARK.json` names, the engine against the plain reference and
against the sqlite oracle,
the reference coming out wrong when it should, the plan (the semi-join
below both joins, the lineitem probe seeing the filtered rows), the
aggregation's merges whatever the number of batches, the spans and
counters of `chipbench/Q18.md` in a traced run, and
`chipbench/agg_trace.py` by hand."""

import glob
import os
import re
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import agg_trace, harness, spans, trace, traffic  # noqa: E402
from chipbench.references import _common  # noqa: E402
from tests.oracle import assert_rows_match, oracle_rows  # noqa: E402
from trino_tpu.exec import operators as O  # noqa: E402
from trino_tpu.runtime.metrics import METRICS  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = traffic.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "sf10.q18"
TINY = 0.01
P = spans.PROGRAM
Q18_COLUMNS = {
    "lineitem": ["l_orderkey", "l_quantity"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
    "customer": ["c_custkey", "c_name"],
}
# rows the statement answers at `tiny` (the repo's generator)
ROWS_AT_TINY = {200: 100, 250: 63, 300: 0}


def load_config(name):
    return traffic.load_json(os.path.join(ROOT, "chipbench", "configs", f"{name}.json"))


def load_traffic():
    return traffic.load_json(os.path.join(ROOT, "chipbench", "traffic", "q18.1stream.json"))


def load_statement():
    return traffic.load_statement("q18")


@pytest.fixture(scope="module")
def tables():
    """Q18's columns at `tiny`, as `data.load_columns` hands them over."""
    from trino_tpu.connectors.tpch import base_row_count, generate_column

    return {
        table: {c: generate_column(table, c, TINY, 0, base_row_count(table, TINY))
                for c in columns}
        for table, columns in Q18_COLUMNS.items()
    }


def build_runner(tables, batch_rows):
    config = load_config("tpch-sf10-q18-1chip")
    runner_kind = traffic.load_module(
        os.path.join(ROOT, "chipbench", "runners", config["runner"] + ".py"))
    return runner_kind.build({**config, "batch_rows": batch_rows}, tables)


def q18(quantity):
    return traffic.instantiate(load_statement(), {"quantity": quantity})


# -- the configuration, the traffic, the statement ---------------------------------------


def test_the_configuration_states_the_deployment_and_its_guarantees():
    config, other = load_config("tpch-sf10-q18-1chip"), load_config("tpch-sf10-1chip")
    assert config["guarantees"] == other["guarantees"]      # word for word
    # (`local_q18`: the `local` runner behind one EXPLAIN, below)
    assert (config["scale"], config["runner"], config["batch_rows"], config["chips"]) == (
        10.0, "local_q18", 1 << 20, 1)
    assert config["reduced"] == ["scale", "columns", "streams"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert "2.4.18" in config["source"] and len(config["source"]) <= 200
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert entry["file"] == "chipbench/configs/tpch-sf10-q18-1chip.json"


def test_the_traffic_and_the_statement_are_the_issues():
    mix = load_traffic()
    assert (mix["statements"], mix["loop"], mix["streams"],
            mix["params_per_statement"], mix["client_poll_ms"]) == (
        ["q18"], "closed", 1, 1, 2)
    spec = traffic.load_json(os.path.join(ROOT, "chipbench", "statements", "q18.json"))
    assert spec["draws"] == {"quantity": {"draw": "int", "lo": 312, "hi": 315}}
    assert spec["validation"] == {"quantity": 300} and spec["ordered"] is True
    assert spec["tables"] == Q18_COLUMNS and spec["scan_columns"] == Q18_COLUMNS


def test_the_benchmark_names_the_configuration_and_the_cell():
    """One QUANTITY value a seed (`params_per_statement` 1): what the
    harness's residency check takes and what qgen draws for one stream
    (chipbench/Q18.md)."""
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch-sf10-q18-1chip", "q18.1stream", 1)
    assert "tpch-sf10-q18-1chip" in {c["name"] for c in BENCHMARK["configs"]}
    assert load_traffic()["params_per_statement"] == 1
    # the cell adds no per-layer entry: its readings are agg_trace.py's
    assert not [m for m in BENCHMARK["per_layer"] if CELL in m.get("workloads", ())]


@pytest.mark.parametrize("seed", [1, 2, 2_147_483_659, 3_300_000_001])
def test_any_seed_plans_one_quantity_of_the_specs_four(seed):
    plan = traffic.plan(load_traffic(), seed)
    drawn = [inst.params["quantity"] for inst in plan.instances]
    assert len(drawn) == 1 and 312 <= drawn[0] <= 315
    assert traffic.plan(load_traffic(), seed).instances[0].sql == plan.instances[0].sql


def test_the_seeds_draw_all_four_quantities():
    drawn = {traffic.plan(load_traffic(), seed).instances[0].params["quantity"]
             for seed in range(3_300_000_001, 3_300_000_033)}
    assert drawn == {312, 313, 314, 315}


def test_the_text_is_the_specs_statement():
    sql = q18(300).sql
    assert re.sub(r"\s+", " ", sql) == (
        "select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, sum(l_quantity) "
        "from customer, orders, lineitem where o_orderkey in ( select l_orderkey from "
        "lineitem group by l_orderkey having sum(l_quantity) > 300) and c_custkey = "
        "o_custkey and o_orderkey = l_orderkey group by c_name, c_custkey, o_orderkey, "
        "o_orderdate, o_totalprice order by o_totalprice desc, o_orderdate limit 100")


# -- the reference, the engine, the oracle -------------------------------------------


@pytest.mark.parametrize("quantity", sorted(ROWS_AT_TINY))
def test_engine_reference_and_oracle_agree_at_tiny(quantity, tables, monkeypatch):
    inst = q18(quantity)
    want = inst.statement.module.reference(tables, inst.params)
    assert len(want) == ROWS_AT_TINY[quantity]
    # block by block: `tiny` is one block as it stands, fifteen of these
    monkeypatch.setattr(_common, "BLOCK_ROWS", 4000)
    assert len(list(_common.blocks(len(tables["lineitem"]["l_quantity"][0])))) >= 15
    assert inst.statement.module.reference(tables, inst.params) == want
    got = build_runner(tables, 16384).execute(inst.sql).rows
    assert harness.same_rows(inst.statement, got, want)
    assert_rows_match(got, oracle_rows(TINY, inst.sql), ordered=True)


@pytest.mark.parametrize("case", ["ge_for_gt", "a_lineitem_dropped", "float32_prices"])
def test_a_wrong_reference_is_not_correct(case, tables):
    """What `correct` has to catch: the HAVING bound off by its edge, a
    row lost on the way into the sums, a price through float32. (The
    control of the other cells, the sums in float32, is exact here: no
    order's quantities pass 350.00, far under 2^24 hundredths.)"""
    statement = load_statement()
    reference = statement.module.reference
    # an order whose lineitems sum to exactly the bound, so that the
    # edge is in the data
    _keys, _rows, total, lines = statement.module.quantity_per_order(tables)
    on_edge = sorted({int(t) // 100 for t, n in zip(total, lines)
                      if n and t % 100 == 0 and 200 <= t // 100 <= 279})
    assert on_edge
    params = {"quantity": on_edge[-1]}
    want = reference(tables, params)
    assert want and harness.same_rows(
        statement, reference(tables, params, sums=_common.group_sums_float32), want)
    if case == "ge_for_gt":
        got = reference(tables, params, having=np.greater_equal)
    elif case == "a_lineitem_dropped":
        key = want[0][2]
        l_key = tables["lineitem"]["l_orderkey"][0]
        keep = np.ones(len(l_key), dtype=bool)
        keep[np.nonzero(l_key == key)[0][0]] = False
        fewer = dict(tables, lineitem={
            c: (a[keep], d) for c, (a, d) in tables["lineitem"].items()})
        got = reference(fewer, params)
    else:
        got = [r[:4] + [float(np.float32(r[4]))] + r[5:] for r in want]
    assert not harness.same_rows(statement, got, want)


def test_the_reference_refuses_a_tie_at_the_limit(tables):
    statement = load_statement()
    orders = dict(tables["orders"])
    price, d = orders["o_totalprice"]
    date, dd = orders["o_orderdate"]
    orders["o_totalprice"] = (np.full_like(price, 100), d)
    orders["o_orderdate"] = (np.full_like(date, 9000), dd)
    with pytest.raises(AssertionError, match="tie"):
        statement.module.reference(dict(tables, orders=orders), {"quantity": 250})


# -- the plan -----------------------------------------------------------------------------


def explain_analyze(runner, sql):
    return runner.execute("explain analyze " + sql).rows[0][0]


def test_the_semi_join_lies_below_both_joins_and_filters_the_lineitem_probe(tables):
    runner = build_runner(tables, 16384)
    text = explain_analyze(runner, q18(200).sql)
    plan = text[:text.index("Pipeline 0")].splitlines()
    depth = {kind: [len(line) - len(line.lstrip()) for line in plan
                    if line.lstrip().startswith(f"Join {kind}")]
             for kind in ("inner", "semi")}
    assert len(depth["inner"]) == 2 and len(depth["semi"]) == 1
    assert depth["semi"][0] > max(depth["inner"])
    below_semi = plan[[i for i, line in enumerate(plan)
                       if line.lstrip().startswith("Join semi")][0] + 1]
    assert "orders" in below_semi and below_semi.lstrip().startswith("Scan")
    # the last pipeline: what the dynamic filter lets into the lineitem
    # probe is the lineitems of the orders over 200 (over 64 of them, so
    # the scan gets their range and not their list), not
    # the table's 60,064 rows
    last = text[text.rindex("Pipeline "):].splitlines()
    scan = next(line for line in last if "TableScanOperator" in line)
    probe = next(line for line in last if "LookupJoinOperator" in line)
    # (the scan itself drops only what lies outside the set's range)
    assert int(re.search(r"out=(\d+) rows", scan).group(1)) > 50000
    rows_in = int(re.search(r"in=(\d+) rows", probe).group(1))
    _k, _r, total, lines = q18(200).statement.module.quantity_per_order(tables)
    assert rows_in == int(lines[total > 200 * 100].sum()) < 6000


def test_a_program_that_plans_the_semi_join_above_the_joins_is_refused_at_once(
        tables, monkeypatch):
    """The parent's plan (no `PushSemiJoinDown`): the runner kind says so
    and ends the run with an exit code before any statement runs; with
    the rule it hands over the `local` runner."""
    from trino_tpu.engine import LocalQueryRunner
    from trino_tpu.sql import optimizer

    assert isinstance(build_runner(tables, 16384), LocalQueryRunner)
    monkeypatch.setattr(optimizer.PushSemiJoinDown, "apply", lambda self, node, ctx: None)
    with pytest.raises(SystemExit, match="semi-join above its inner joins"):
        build_runner(tables, 16384)


def test_the_plan_check_reads_explain_by_indentation():
    kind = traffic.load_module(os.path.join(ROOT, "chipbench", "runners", "local_q18.py"))
    above = "Output\n  Join semi L[0]=R[0]\n    Join inner\n      Join inner\n"
    below = "Output\n  Join inner\n    Join inner\n      Join semi L[0]=R[0]\n"
    assert kind.semi_join_above_a_join(above)
    assert not kind.semi_join_above_a_join(below)
    assert not kind.semi_join_above_a_join("Output\n  Join inner\n    Scan t\n")


# -- the aggregation's merges, whatever the number of batches -------------------------


@pytest.mark.parametrize("quantity", [250])
def test_59_batches_and_15_batches_answer_alike_with_as_many_merge_programs(
        quantity, tables):
    inst = q18(quantity)
    want = inst.statement.module.reference(tables, inst.params)
    seen = {}
    for batch_rows in (1024, 4096):
        jax.clear_caches()
        before = {k: METRICS.counter(k) for k in (
            "agg_ingest_batches", "agg_merge_launches", "agg_merge_retries")}
        assert build_runner(tables, batch_rows).execute(inst.sql).rows == want
        moved = {k: METRICS.counter(k) - v for k, v in before.items()}
        seen[batch_rows] = (O._merge_group_states._cache_size(), moved)
    (few_programs, few), (many_programs, many) = seen[4096], seen[1024]
    # 59 + 1 and 15 + 1 launches of the ingest; folds of FOLD_STATES and
    # one last merge an aggregation
    assert many["agg_ingest_batches"] == 60 and few["agg_ingest_batches"] == 16
    assert many["agg_merge_launches"] == 59 // O.FOLD_STATES + 1
    assert few["agg_merge_launches"] == 15 // O.FOLD_STATES + 1
    assert many["agg_merge_retries"] == 0 and few["agg_merge_retries"] == 0
    # one program for the folds, one for the last merge: four times the
    # batches mint no further lowering
    assert many_programs == few_programs == 2


# -- spans and counters, in a traced run on the CPU -----------------------------------


@pytest.fixture(scope="module")
def traced(tables, tmp_path_factory):
    """One profiler trace over a warm Q18 at `tiny`, 15 batches a scan:
    (the SpanTrace, the METRICS deltas)."""
    runner = build_runner(tables, 4096)
    sql = q18(250).sql
    runner.execute(sql)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    names = ("agg_merge_launches", "agg_merge_retries", "agg_groups_out")
    before = {k: METRICS.counter(k) for k in names}
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            rows = runner.execute(sql).rows
    finally:
        jax.profiler.stop_trace()
    assert len(rows) == 63
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    return spans.load(path), {k: METRICS.counter(k) - v for k, v in before.items()}


def events_named(st, name):
    return [e for line in st.lines for e in line if e.name == name]


def test_every_merge_launch_is_a_span_with_its_stats(traced):
    st, counters = traced
    merges = events_named(st, P + "agg.merge")
    assert len(merges) == counters["agg_merge_launches"] == 2
    fold, last = sorted(merges, key=lambda e: e.start)
    assert int(fold.stats["states"]) == O.FOLD_STATES and int(fold.stats["retry"]) == 0
    assert int(fold.stats["cap"]) >= int(fold.stats["slots_in"])   # a fold takes the slots
    # the fold's state, and the 7 left made up to FOLD_STATES
    assert int(last.stats["states"]) == 1 + O.FOLD_STATES
    assert int(last.stats["cap"]) < int(last.stats["slots_in"])    # sized by the counts
    assert counters["agg_merge_retries"] == 0


def test_the_syncs_that_read_a_scalar_carry_it(traced, tables):
    st, _ = traced
    probes = events_named(st, spans.SYNC + "join.match_total")
    kept = events_named(st, spans.SYNC + "join.dynamic_filter")
    assert probes and all({"rows", "probe_slots"} <= set(e.stats) for e in probes)
    with_rows = [e for e in kept if "rows" in e.stats]
    # 63 orders survive the set filter into the semi-join, their 438
    # lineitems into the last join
    assert sum(int(e.stats["rows"]) for e in with_rows) >= 63 + 438
    assert sum(int(e.stats["probe_slots"]) for e in probes) < 60064
    builds = events_named(st, spans.SYNC + "join.build_rows")
    assert all("rows" in e.stats for e in builds)


def test_the_counters_are_metrics_counters_and_off_without_a_trace(tables):
    from trino_tpu.runtime import tracing

    assert tracing.host_span("agg.merge", states=8) is tracing.OFF
    before = METRICS.counter("agg_merge_launches")
    build_runner(tables, 4096).execute(q18(250).sql)
    assert METRICS.counter("agg_merge_launches") == before + 2


# -- agg_trace.py ---------------------------------------------------------------------------


def one_chip_trace(events, busy, programs):
    names = [f"%fusion.{j} = f(%x)" for j in range(len(busy))]
    device_ops = {"/device:TPU:0": (
        names, np.asarray([a for a, _ in busy], float),
        np.asarray([b for _, b in busy], float))}
    yardstick = trace.Trace(device_ops, {}, {}, [
        trace.Annotation(trace.WINDOW, 0.0, 10.0, {}),
        trace.Annotation(trace.ENGINE, 0.0, 10.0, {}),
        trace.Annotation(trace.CLIENT, 0.5, 4.0, {}),
        trace.Annotation(trace.CLIENT, 4.0, 9.0, {})])
    return spans.SpanTrace(yardstick, [events], programs)


def test_the_four_metrics_by_hand():
    events = [
        spans.Event(P + "phase.execute", 0.0, 8.0, {"cpu_ns": 1}),
        spans.Event(P + "op.HashAggregationOperator.add_input", 1.0, 4.0, {}),
        spans.Event(P + "agg.merge", 2.0, 2.5,
                    {"states": 8, "slots_in": 64, "cap": 64, "retry": 0}),
        spans.Event(P + "op.HashAggregationOperator.finish", 4.0, 6.0, {}),
        spans.Event(P + "agg.merge", 4.5, 5.0,
                    {"states": 3, "slots_in": 100, "cap": 32, "retry": 0}),
        spans.Event(P + "op.LookupJoinOperator.add_input", 6.0, 7.0, {}),
        spans.Event(P + "sync.join.dynamic_filter", 6.0, 6.1, {"rows": 40}),
        spans.Event(P + "sync.join.match_total", 6.5, 6.6,
                    {"rows": 40, "probe_slots": 64}),
        spans.Event(P + "sync.join.match_total", 6.8, 6.9,
                    {"rows": 7, "probe_slots": 16}),
        # ends after the window: not this window's
        spans.Event(P + "sync.join.match_total", 9.9, 10.5,
                    {"rows": 7, "probe_slots": 1 << 20}),
    ]
    programs = [("jit__agg_ingest(1)", 1.0, 3.0), ("jit__merge_group_states(2)", 3.0, 4.0),
                ("jit_probe_counts(3)", 6.0, 7.0)]
    got = agg_trace.metrics(one_chip_trace(events, [(1.0, 4.0), (6.0, 7.0)], programs))
    assert got["statements_in_window"] == 2
    assert got["agg_merge_ms_per_stmt"] == pytest.approx(1e3 * 1.0 / 2)
    assert got["agg_op_share_pct"] == pytest.approx(100 * 5.0 / 8.0)
    assert got["join_probe_rows_per_stmt"] == pytest.approx(80 / 2)
    assert got["agg_device_share_pct"] == pytest.approx(100 * 3.0 / 4.0)
    assert got["merges"][0] == {"states": 8, "slots_in": 64, "cap": 64, "retry": 0}
    assert got["dynamic_filter_rows_kept"] == 40 and got["probe_batches"] == 2
    # a statement that began before the trace did leaves no client
    # annotation in it: the program's `result.fetch` counts it
    long = one_chip_trace(events + [spans.Event(P + "result.fetch", 7.5, 7.6, {})],
                          [(1.0, 4.0), (6.0, 7.0)], programs)
    long.yardstick.annotations[:] = [a for a in long.yardstick.annotations
                                     if a.name != trace.CLIENT]
    assert agg_trace.metrics(long)["statements_in_window"] == 1
    assert agg_trace.metrics(long)["join_probe_rows_per_stmt"] == pytest.approx(80)
    assert agg_trace.metrics(long)["agg_op_share_of"] == "phase.execute"
    # nor does its `phase.execute` lie inside the trace: the operator's
    # wall is then a share of the traced window's seconds
    long.lines[0][:] = [e for e in long.lines[0] if e.name != P + "phase.execute"]
    assert agg_trace.metrics(long)["agg_op_share_pct"] == pytest.approx(100 * 5.0 / 10.0)
    assert agg_trace.metrics(long)["agg_op_share_of"] == "window"


@pytest.mark.parametrize("recorded", ["trace_spans_small.xplane.pb",
                                      "trace_small.xplane.pb"])
def test_a_program_from_before_the_spans_reads_none_and_nothing_raises(recorded):
    """The parent's traces: no `agg.merge`, no `probe_slots`."""
    got = agg_trace.metrics(spans.load(os.path.join(HERE, recorded)))
    assert got["agg_merge_ms_per_stmt"] is None
    assert got["join_probe_rows_per_stmt"] is None
    assert got["merges"] == [] and got["probe_batches"] == 0
    assert got["agg_device_share_pct"] is None or got["agg_device_share_pct"] >= 0.0


def test_the_command_reads_the_cells_last_traced_run(tmp_path, monkeypatch, capsys):
    import json
    import shutil

    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    assert agg_trace.main([CELL]) == 1 and "no traced run" in capsys.readouterr().err
    there = tmp_path / CELL / "plugins" / "profile" / "2026_09_28"
    there.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "trace_spans_small.xplane.pb"), there / "host.xplane.pb")
    assert agg_trace.main([CELL]) == 0
    line = json.loads(capsys.readouterr().out)
    assert {"agg_merge_ms_per_stmt", "agg_op_share_pct", "join_probe_rows_per_stmt",
            "agg_device_share_pct"} <= set(line)
    assert agg_trace.main([]) == 2
