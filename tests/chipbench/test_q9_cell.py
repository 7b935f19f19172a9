"""TPC-H Q9 on the CPU at `tiny`, through the files of its cell
(`sf10.q9`, configuration `tpch-sf10-q9-1chip`, `chipbench/Q9.md`):
what `BENCHMARK.json` names, the engine against the plain reference and
against the sqlite oracle, the reference coming out wrong when it
should, the plan (the fact table on the probe side of every join, the
filtered `part` first), the harness's phases, the dynamic filters'
spans and counters in a traced run, and `chipbench/join_trace.py` by
hand."""

import glob
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, join_trace, spans, trace, traffic  # noqa: E402
from chipbench.references import _common  # noqa: E402
from tests.oracle import assert_rows_match, oracle_rows  # noqa: E402
from tests.test_tpch import to_sqlite  # noqa: E402
from trino_tpu.exec import operators as O  # noqa: E402
from trino_tpu.runtime.metrics import METRICS  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = traffic.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "sf10.q9"
CONFIG = "tpch-sf10-q9-1chip"
TINY = 0.01
P = spans.PROGRAM
Q9_COLUMNS = {
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity", "l_extendedprice",
                 "l_discount"],
    "orders": ["o_orderkey", "o_orderdate"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
    "part": ["p_partkey", "p_name"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name"],
}
# (rows answered, lineitems that survive the colour) at `tiny`
AT_TINY = {"green": (168, 3282), "midnight": (168, 3165), "almond": (168, 3190),
           "red": (167, 3481)}


def load_config(name):
    return traffic.load_json(os.path.join(ROOT, "chipbench", "configs", f"{name}.json"))


def load_traffic():
    return traffic.load_json(os.path.join(ROOT, "chipbench", "traffic", "q9.1stream.json"))


def load_statement():
    return traffic.load_statement("q9")


@pytest.fixture(scope="module")
def tables():
    """Q9's columns at `tiny`, as `data.load_columns` hands them over."""
    from trino_tpu.connectors.tpch import base_row_count, generate_column

    return {
        table: {c: generate_column(table, c, TINY, 0, base_row_count(table, TINY))
                for c in columns}
        for table, columns in Q9_COLUMNS.items()
    }


def build_runner(tables, batch_rows):
    config = load_config(CONFIG)
    runner_kind = traffic.load_module(
        os.path.join(ROOT, "chipbench", "runners", config["runner"] + ".py"))
    return runner_kind.build({**config, "batch_rows": batch_rows}, tables)


def q9(color):
    return traffic.instantiate(load_statement(), {"color": color})


# -- the configuration, the traffic, the statement ---------------------------------------


def test_the_configuration_states_the_deployment_and_its_guarantees():
    config, other = load_config(CONFIG), load_config("tpch-sf10-q18-1chip")
    assert config["guarantees"] == other["guarantees"]      # word for word
    assert config["deployment"] == other["deployment"]
    # step 0: the parent fails its statement and exits by itself, so no
    # plan guard stands in front of the runner (chipbench/Q9.md)
    assert (config["scale"], config["runner"], config["batch_rows"], config["chips"],
            config["connector"]) == (10.0, "local", 1 << 20, 1, "memory")
    assert config["reduced"] == ["scale", "columns", "streams"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert "2.4.9" in config["source"] and len(config["source"]) <= 200
    assert "5,000" in config["assumed"]["generator"]
    assert "q72" in config["why"] and "BASELINE.json" in config["why"]
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert BENCHMARK["configs"][-1] is entry               # appended, nothing moved


def test_the_traffic_and_the_statement_are_the_issues():
    from trino_tpu.connectors.tpch import COLORS

    mix = load_traffic()
    assert (mix["statements"], mix["loop"], mix["streams"],
            mix["params_per_statement"], mix["client_poll_ms"]) == (
        ["q9"], "closed", 1, 1, 2)
    spec = traffic.load_json(os.path.join(ROOT, "chipbench", "statements", "q9.json"))
    assert spec["draws"] == {"color": {"draw": "choice", "values": list(COLORS)}}
    assert len(spec["draws"]["color"]["values"]) == 92
    assert spec["validation"] == {"color": "green"} and spec["ordered"] is True
    assert spec["tables"] == Q9_COLUMNS and spec["scan_columns"] == Q9_COLUMNS
    assert spec["reference"] == "q9"


def test_the_benchmark_names_the_configuration_and_the_cell():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "q9.1stream", 1)
    assert BENCHMARK["workloads"][-1] is cell and len(cell["why"]) <= 200
    assert load_traffic()["params_per_statement"] == 1
    # the cell adds no per-layer entry: its readings are join_trace.py's
    assert not [m for m in BENCHMARK["per_layer"] if CELL in m.get("workloads", ())]


@pytest.mark.parametrize("seed", [1, 2, 2_147_483_659, 3_500_000_001])
def test_any_seed_plans_one_colour_of_the_specs_92(seed):
    plan = traffic.plan(load_traffic(), seed)
    drawn = [inst.params["color"] for inst in plan.instances]
    assert len(drawn) == 1 and drawn[0] in load_statement().draws["color"]["values"]
    assert f"like '%{drawn[0]}%'" in plan.instances[0].sql
    assert traffic.plan(load_traffic(), seed).instances[0].sql == plan.instances[0].sql


def test_the_seeds_draw_many_colours():
    drawn = {traffic.plan(load_traffic(), seed).instances[0].params["color"]
             for seed in range(3_500_000_001, 3_500_000_201)}
    assert len(drawn) > 60


def test_the_text_is_the_specs_statement():
    sql = q9("green").sql
    assert re.sub(r"\s+", " ", sql) == (
        "select nation, o_year, sum(amount) as sum_profit from ( select n_name as nation, "
        "extract(year from o_orderdate) as o_year, l_extendedprice * (1 - l_discount) - "
        "ps_supplycost * l_quantity as amount from part, supplier, lineitem, partsupp, orders, "
        "nation where s_suppkey = l_suppkey and ps_suppkey = l_suppkey and ps_partkey = "
        "l_partkey and p_partkey = l_partkey and o_orderkey = l_orderkey and s_nationkey = "
        "n_nationkey and p_name like '%green%' ) as profit group by nation, o_year order by "
        "nation, o_year desc")


# -- the reference, the engine, the oracle -------------------------------------------


@pytest.mark.parametrize("color", sorted(AT_TINY))
def test_engine_reference_and_oracle_agree_at_tiny(color, tables, monkeypatch):
    inst = q9(color)
    want = inst.statement.module.reference(tables, inst.params)
    assert len(want) == AT_TINY[color][0]
    assert all(type(v) is t for row in want for v, t in zip(row, (str, int, float)))
    # block by block: `tiny` is one block as it stands, fifteen of these
    monkeypatch.setattr(_common, "BLOCK_ROWS", 4000)
    monkeypatch.setattr(inst.statement.module, "blocks", _common.blocks)
    assert len(list(_common.blocks(len(tables["lineitem"]["l_quantity"][0])))) >= 15
    assert inst.statement.module.reference(tables, inst.params) == want
    oracle = oracle_rows(TINY, to_sqlite(inst.sql))
    for batch_rows in (4096, 16384):                 # 15 and 4 batches of the fact table
        got = build_runner(tables, batch_rows).execute(inst.sql).rows
        assert harness.same_rows(inst.statement, got, want)
        assert_rows_match(got, oracle, ordered=True)


@pytest.mark.parametrize("case", ["float32_sums", "supply_cost_dropped", "year_of_shipdate",
                                  "a_lineitem_dropped", "prefix_for_substring"])
def test_a_wrong_reference_is_not_correct(case, tables):
    """What `correct` has to catch: sums in the precision below, a term
    of the expression lost, the year read from the wrong table's date,
    a row lost on the way into the sums, the pattern anchored."""
    from trino_tpu.connectors.tpch import base_row_count, generate_column

    statement = load_statement()
    reference = statement.module.reference
    params = {"color": "green"}
    want = reference(tables, params)
    if case == "float32_sums":
        got = reference(tables, params, sums=_common.group_sums_float32)
    elif case == "supply_cost_dropped":
        got = reference(tables, params, with_supply_cost=False)
    elif case == "year_of_shipdate":
        shipdate = generate_column("lineitem", "l_shipdate", TINY, 0,
                                   base_row_count("lineitem", TINY))
        with_date = dict(tables, lineitem=dict(tables["lineitem"], l_shipdate=shipdate))
        got = reference(with_date, params, year_from="l_shipdate")
    elif case == "a_lineitem_dropped":
        names = tables["part"]["p_name"][1].values
        green = tables["part"]["p_partkey"][0][
            np.asarray(["green" in names[c] for c in tables["part"]["p_name"][0]])]
        keep = np.ones(len(tables["lineitem"]["l_partkey"][0]), dtype=bool)
        keep[np.nonzero(np.isin(tables["lineitem"]["l_partkey"][0], green))[0][0]] = False
        fewer = dict(tables, lineitem={
            c: (a[keep], d) for c, (a, d) in tables["lineitem"].items()})
        got = reference(fewer, params)
    else:
        got = reference(tables, params, match=lambda name, color: name.startswith(color))
    assert want and got and not harness.same_rows(statement, got, want)


def test_the_colour_is_a_substring_of_the_dictionarys_values(tables):
    """5 of the 92 words a name: a colour is in about 5.4 % of the
    pool's names, and so of the parts."""
    names = tables["part"]["p_name"][1].values
    codes = tables["part"]["p_name"][0]
    module = load_statement().module
    share = np.mean([module.matches(names[c], "green") for c in codes])
    assert 0.03 < share < 0.08
    assert module.matches("dark green lace", "green") and not module.matches("grey", "green")
    assert module.year_of(np.asarray([0, 365, 9130, 10591], dtype=np.int32)).tolist() == [
        1970, 1971, 1994, 1998]


# -- the plan -----------------------------------------------------------------------------


def explain_analyze(runner, sql):
    return runner.execute("explain analyze " + sql).rows[0][0]


def test_the_fact_table_probes_every_build_and_meets_the_filtered_part_first(tables):
    runner = build_runner(tables, 16384)
    text = explain_analyze(runner, q9("green").sql)
    plan = text[:text.index("Pipeline 0")].splitlines()
    joins = [line.strip() for line in plan if line.strip().startswith("Join ")]
    assert len(joins) == 5 and all(j.startswith("Join inner") for j in joins)
    at = next(i for i, line in enumerate(plan) if ".lineitem " in line)
    assert plan[at + 1].strip().startswith("Filter like(") and ".part " in plan[at + 2]
    # the last pipeline is the fact table's: one scan, four filters and
    # probes, then the 175 groups; the survivors of the first filter are
    # what every join sees
    last = text[text.rindex("Pipeline "):].splitlines()
    last = [line for line in last if re.search(r"^\s+\w+: in=", line)]
    ops = [line.split(":")[0].strip() for line in last]
    assert ops[:9] == ["TableScanOperator"] + ["DynamicFilterOperator", "LookupJoinOperator"] * 4
    assert "HashAggregationOperator" in ops and "HashBuildSink" not in ops
    survivors = AT_TINY["green"][1]
    probes = [line for line in last if "LookupJoinOperator" in line]
    assert [int(re.search(r"in=(\d+) rows", p).group(1)) for p in probes] == [survivors] * 4
    assert [int(re.search(r"out=(\d+) rows", p).group(1)) for p in probes] == [survivors] * 4
    filters = [line for line in last if "DynamicFilterOperator" in line]
    assert int(re.search(r"out=(\d+) rows", filters[0]).group(1)) == survivors
    # every build is a base table's rows (or fewer), never the fact table's
    builds = [int(re.search(r"in=(\d+) rows", line).group(1))
              for line in text.splitlines() if "HashBuildSink" in line]
    assert sorted(builds) == [25, 100, 111, 8000, 15000]


# -- the harness's phases ---------------------------------------------------------------


def test_the_harness_runs_the_cell_at_tiny(tmp_path, capsys):
    result = harness.run_cell(CELL, seed=3_500_000_001, seconds=2.0, trace=False,
                              cache_root=str(tmp_path), scale=TINY, require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {m["name"] for m in BENCHMARK["end_to_end"]}
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    phases = {line["phase"]: line for line in lines if "phase" in line}
    assert phases["window"]["counters"]["xla_compiles"] == 0
    assert phases["window"]["counters"]["plan_cache.misses"] == 0
    assert phases["data"]["rows"] == {"lineitem": 60064, "orders": 15000, "partsupp": 8000,
                                      "part": 2000, "supplier": 100, "nation": 25}
    assert [l["references"] for l in lines if "references" in l] == [1]


# -- spans and counters, in a traced run on the CPU -----------------------------------


@pytest.fixture(scope="module")
def traced(tables, tmp_path_factory):
    """One profiler trace over a warm Q9 at `tiny`, 15 batches a scan of
    the fact table, with the set filter's limit lowered so that the 111
    green parts take the BITS: (the SpanTrace, the METRICS deltas)."""
    was = O.DF_SET_MAX_SLOTS
    O.DF_SET_MAX_SLOTS = 64
    try:
        runner = build_runner(tables, 4096)
        sql = q9("green").sql
        runner.execute(sql)
        trace_dir = str(tmp_path_factory.mktemp("trace"))
        names = ("df_filter_path.set", "df_filter_path.bits", "df_filter_path.range",
                 "df_rows_in", "df_rows_kept", "df_pack_batches_in", "df_pack_batches_out")
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
    finally:
        O.DF_SET_MAX_SLOTS = was
    assert len(rows) == AT_TINY["green"][0]
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    return spans.load(path), {k: METRICS.counter(k) - v for k, v in before.items()}


def events_named(st, name):
    return [e for line in st.lines for e in line if e.name == name]


def test_every_filter_says_which_it_took_and_what_it_kept(traced):
    st, counters = traced
    prepared = events_named(st, P + "df.prepare")
    # supplier's probe of nation (25 keys: the set), then the fact
    # table's four: the 111 green parts take the bits; the 15,000 orders
    # (a quarter of 1..60,000: too full for the bits to drop enough),
    # partsupp's two columns and the suppliers, who fill their domain,
    # the range
    assert sorted(str(e.stats["path"]) for e in prepared) == [
        "bits", "range", "range", "range", "set"]
    (bits,) = (e for e in prepared if str(e.stats["path"]) == "bits")
    assert int(bits.stats["keys"]) == 111 and 1500 < int(bits.stats["domain"]) <= 2000
    assert int(bits.stats["table_bytes"]) == 4 * 128
    totals = events_named(st, spans.SYNC + "join.dynamic_filter_totals")
    assert len(totals) == 5
    (fact,) = (e for e in totals if str(e.stats["path"]) == "bits")
    survivors = AT_TINY["green"][1]
    assert int(fact.stats["rows_kept"]) == survivors and int(fact.stats["batches"]) == 15
    assert 59000 < int(fact.stats["rows_in"]) <= 60064 and int(fact.stats["key_bytes"]) == 8
    assert int(fact.stats["slots"]) == 15 * 4096
    assert counters["df_filter_path.bits"] == 15
    assert counters["df_rows_kept"] == sum(int(e.stats["rows_kept"]) for e in totals)
    assert counters["df_rows_in"] == sum(int(e.stats["rows_in"]) for e in totals)
    # about 220 survivors a batch of 4,096: gathered (the special case),
    # no sort needed
    assert counters["df_pack_batches_in"] == 0


def test_join_trace_finds_the_traced_runs_spans(traced):
    """(A CPU trace has no device plane, so `join_trace.metrics` cannot
    reduce it: the six readings are computed by hand below.)"""
    st, _ = traced
    events = join_trace.window_events(st)
    totals = [e for e in events if e.name == join_trace.DF_TOTALS]
    assert len(totals) == 5 and all("rows_in" in e.stats for e in totals)
    assert len([e for e in events if e.name == join_trace.DF_PREPARE]) == 5
    probes = [e for e in events if e.name == join_trace.MATCH_TOTAL]
    assert probes and all("probe_slots" in e.stats for e in probes)
    with pytest.raises(ValueError):
        join_trace.metrics(st)


# -- join_trace.py ---------------------------------------------------------------------------


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


def test_the_six_readings_by_hand():
    events = [
        spans.Event(P + "phase.execute", 0.0, 8.0, {"cpu_ns": 1}),
        spans.Event(P + "op.HashBuildSink.finish", 0.5, 1.5, {}),
        spans.Event(P + "sync.join.build_rows", 0.6, 0.7, {"rows": 8000}),
        spans.Event(P + "op.DynamicFilterOperator.add_input", 2.0, 3.0, {}),
        spans.Event(P + "df.prepare", 2.0, 2.1, {
            "path": "bits", "keys": 111, "domain": 1990, "build_slots": 128,
            "table_bytes": 512}),
        spans.Event(P + "op.DynamicFilterOperator.finish", 3.0, 3.5, {}),
        spans.Event(P + "sync.join.dynamic_filter_totals", 3.1, 3.2, {
            "rows_in": 60000, "rows_kept": 3000, "batches": 15, "slots": 61440,
            "path": "bits", "key_bytes": 8}),
        spans.Event(P + "sync.join.dynamic_filter_totals", 3.3, 3.4, {
            "rows_in": 3000, "rows_kept": 3000, "batches": 1, "slots": 4096,
            "path": "range", "key_bytes": 8}),
        spans.Event(P + "op.LookupJoinOperator.add_input", 4.0, 6.0, {}),
        spans.Event(P + "sync.join.match_total", 4.5, 4.6, {"rows": 3000, "probe_slots": 4096}),
        spans.Event(P + "sync.join.match_total", 5.5, 5.6, {"rows": 3000, "probe_slots": 4096}),
        spans.Event(P + "op.HashAggregationOperator.add_input", 6.0, 7.0, {}),
        # ends after the window: not this window's
        spans.Event(P + "sync.join.build_rows", 9.9, 10.5, {"rows": 1 << 20}),
    ]
    programs = [("jit__df_filter_bits(1)", 2.0, 2.0 + 1e-6), ("jit_probe_counts(2)", 4.0, 5.0),
                ("jit__agg_ingest(3)", 6.0, 7.0), ("jit__pack_rows(4)", 3.0, 3.5)]
    got = join_trace.metrics(one_chip_trace(
        events, [(2.0, 2.0 + 1e-6), (3.0, 3.5), (4.0, 5.0), (6.0, 7.0)], programs))
    assert got["statements_in_window"] == 2
    assert got["join_op_share_pct"] == pytest.approx(100 * 4.5 / 8.0)
    assert got["join_device_share_pct"] == pytest.approx(100 * (1.5 + 1e-6) / (2.5 + 1e-6))
    assert got["join_build_rows_per_stmt"] == pytest.approx(8000 / 2)
    assert got["join_probe_rows_per_stmt"] == pytest.approx(8192 / 2)
    assert got["df_kept_pct"] == pytest.approx(5.0)
    # 61,440 slots x (8 + 1) bytes + 15 x 512 bytes of table, at 819 GB/s,
    # over the microsecond the program ran
    moved = join_trace.df_bits_bytes(61440, 8, 15, 512)
    assert moved == 61440 * 9 + 15 * 512
    assert got["df_bits_roofline_pct"] == pytest.approx(100 * (moved / 819e9) / 1e-6, rel=1e-3)
    assert got["filters"]["range"] == {"filters": 1, "batches": 1, "slots": 4096,
                                       "rows_in": 3000, "rows_kept": 3000}
    assert got["builds"] == [8000] and got["probe_batches"] == 2
    assert got["prepared"] == [{"path": "bits", "keys": 111, "domain": 1990,
                                "build_slots": 128, "table_bytes": 512}]


@pytest.mark.parametrize("recorded", ["trace_spans_small.xplane.pb",
                                      "trace_small.xplane.pb"])
def test_a_program_from_before_the_spans_reads_none_and_nothing_raises(recorded):
    """The parent's traces: no `df.prepare`, no totals."""
    got = join_trace.metrics(spans.load(os.path.join(HERE, recorded)))
    assert got["df_kept_pct"] is None and got["df_bits_roofline_pct"] is None
    assert got["join_build_rows_per_stmt"] is None
    assert got["filters"] == {} and got["prepared"] == []
    assert got["join_device_share_pct"] is None or got["join_device_share_pct"] >= 0.0


def test_the_command_reads_the_cells_last_traced_run(tmp_path, monkeypatch, capsys):
    import shutil

    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    assert join_trace.main([CELL]) == 1 and "no traced run" in capsys.readouterr().err
    there = tmp_path / CELL / "plugins" / "profile" / "2026_09_29"
    there.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "trace_spans_small.xplane.pb"), there / "host.xplane.pb")
    assert join_trace.main([CELL]) == 0
    line = json.loads(capsys.readouterr().out)
    assert {"join_op_share_pct", "join_device_share_pct", "join_build_rows_per_stmt",
            "join_probe_rows_per_stmt", "df_kept_pct", "df_bits_roofline_pct"} <= set(line)
    assert join_trace.main([]) == 2
