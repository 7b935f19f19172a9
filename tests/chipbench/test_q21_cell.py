"""TPC-H Q21 on the CPU at `tiny`, through the files of its cell
(`sf10.q21`, configuration `tpch-sf10-q21-1chip`, `chipbench/Q21.md`):
what `BENCHMARK.json` names, the engine against the plain reference and
against the sqlite oracle, the reference coming out wrong when it
should, the plan (the late lines of one nation's suppliers built twice,
the fact table probing them behind their keys' filter), the harness's
phases, the spans and counters of the two joins in a traced run, and
`chipbench/semi_trace.py` by hand."""

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

from chipbench import harness, semi_trace, spans, stmt_account, trace, traffic  # noqa: E402
from chipbench.references import _common  # noqa: E402
from tests.oracle import assert_rows_match, oracle_rows  # noqa: E402
from tests.test_tpch import to_sqlite  # noqa: E402
from trino_tpu.runtime.metrics import METRICS  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = traffic.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "sf10.q21"
CONFIG = "tpch-sf10-q21-1chip"
# what `BENCHMARK.json` held before this cell, in its order
OLDER_CONFIGS = ["tpch-sf1-1chip", "tpch-sf10-1chip", "tpch-sf30-4chip",
                 "tpch-sf10-q18-1chip", "tpch-sf10-q9-1chip"]
OLDER_CELLS = ["sf1.scan_agg", "sf1.join", "sf10.scan_agg", "sf30.mesh4", "sf10.q18",
               "sf10.q9"]
TINY = 0.01
P = spans.PROGRAM
Q21_COLUMNS = {
    "lineitem": ["l_orderkey", "l_suppkey", "l_commitdate", "l_receiptdate"],
    "orders": ["o_orderkey", "o_orderstatus"],
    "supplier": ["s_suppkey", "s_name", "s_nationkey"],
    "nation": ["n_nationkey", "n_name"],
}
# (rows answered, the first row) at `tiny`
AT_TINY = {"SAUDI ARABIA": (6, ["Supplier#000000065", 11]),
           "FRANCE": (3, ["Supplier#000000072", 11]),
           "CHINA": None, "UNITED STATES": None}
COUNTERS = ("join_semi_side.source", "join_semi_side.filtering",
            "join_expand_launches.first", "join_expand_launches.general",
            "semi_pairs_seen", "semi_pairs_kept", "semi_build_rows", "semi_build_flagged",
            "df_reverse_rows_in", "df_reverse_rows_kept", "filter_read_bytes")


def load_config(name):
    return traffic.load_json(os.path.join(ROOT, "chipbench", "configs", f"{name}.json"))


def load_traffic():
    return traffic.load_json(os.path.join(ROOT, "chipbench", "traffic", "q21.1stream.json"))


def load_statement():
    return traffic.load_statement("q21")


@pytest.fixture(scope="module")
def tables():
    """Q21's columns at `tiny`, as `data.load_columns` hands them over."""
    from trino_tpu.connectors.tpch import base_row_count, generate_column

    return {
        table: {c: generate_column(table, c, TINY, 0, base_row_count(table, TINY))
                for c in columns}
        for table, columns in Q21_COLUMNS.items()
    }


def build_runner(tables, batch_rows):
    config = load_config(CONFIG)
    runner_kind = traffic.load_module(
        os.path.join(ROOT, "chipbench", "runners", config["runner"] + ".py"))
    return runner_kind.build({**config, "batch_rows": batch_rows}, tables)


def q21(nation):
    return traffic.instantiate(load_statement(), {"nation": nation})


# -- the configuration, the traffic, the statement ---------------------------------------


def test_the_configuration_states_the_deployment_its_cuts_and_its_guarantees():
    config, other = load_config(CONFIG), load_config("tpch-sf10-q9-1chip")
    assert config["guarantees"] == other["guarantees"]      # word for word
    assert config["deployment"] == other["deployment"]
    assert (config["scale"], config["batch_rows"], config["chips"], config["connector"],
            config["schema"]) == (10.0, 1 << 20, 1, "memory", "chipbench")
    # step 0: the parent cannot end a run of the cell with exit code 0
    # (chipbench/Q21.md), so one EXPLAIN stands in front of the runner
    assert config["runner"] == "local_q21" and "step 0" in config["runner_why"]
    assert os.path.exists(os.path.join(ROOT, "chipbench", "runners", config["runner"] + ".py"))
    assert config["reduced"] == ["scale", "columns", "streams"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert "2.4.21" in config["source"] and len(config["source"]) <= 200
    assert "59,992,734" in config["reduced_why"]["scale"]
    assert "100,000" in config["assumed"]["generator"]
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    # the driver's limit on the entry's lines (it refused 207 characters)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert all(1 <= len(entry[k]) <= 200 and entry[k].isprintable()
               for k in ("source", "why"))
    # appended: the five configurations before it keep their places, and
    # whatever a later PR appends comes behind
    assert [c["name"] for c in BENCHMARK["configs"]][:len(OLDER_CONFIGS) + 1] == [
        *OLDER_CONFIGS, CONFIG]


def test_the_traffic_and_the_statement_are_the_issues():
    from trino_tpu.connectors.tpch import NATIONS

    mix = load_traffic()
    assert (mix["statements"], mix["loop"], mix["streams"],
            mix["params_per_statement"], mix["client_poll_ms"]) == (
        ["q21"], "closed", 1, 1, 2)
    spec = traffic.load_json(os.path.join(ROOT, "chipbench", "statements", "q21.json"))
    assert spec["draws"] == {"nation": {"draw": "choice", "values": [n for n, _ in NATIONS]}}
    assert len(spec["draws"]["nation"]["values"]) == 25
    assert spec["validation"] == {"nation": "SAUDI ARABIA"} and spec["ordered"] is True
    assert spec["tables"] == Q21_COLUMNS and spec["scan_columns"] == Q21_COLUMNS
    assert spec["reference"] == "q21"


def test_the_benchmark_names_the_configuration_and_the_cell():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "q21.1stream", 1)
    assert [w["name"] for w in BENCHMARK["workloads"]][:len(OLDER_CELLS) + 1] == [
        *OLDER_CELLS, CELL]
    assert len(cell["why"]) <= 200
    # the cell adds no per-layer entry: its readings are semi_trace.py's
    assert not [m for m in BENCHMARK["per_layer"] if CELL in m.get("workloads", ())]


@pytest.mark.parametrize("seed", [1, 2, 2_147_483_659, 4_000_000_001])
def test_any_seed_plans_one_of_the_25_nations(seed):
    plan = traffic.plan(load_traffic(), seed)
    drawn = [inst.params["nation"] for inst in plan.instances]
    assert len(drawn) == 1 and drawn[0] in load_statement().draws["nation"]["values"]
    assert f"n_name = '{drawn[0]}'" in plan.instances[0].sql
    assert traffic.plan(load_traffic(), seed).instances[0].sql == plan.instances[0].sql


def test_the_seeds_draw_many_nations():
    drawn = {traffic.plan(load_traffic(), seed).instances[0].params["nation"]
             for seed in range(4_000_000_001, 4_000_000_201)}
    assert len(drawn) == 25


def test_the_text_is_the_specs_statement():
    from tests.tpch_queries import QUERIES

    sql = q21("SAUDI ARABIA").sql
    assert re.sub(r"\s+", " ", sql).strip() == re.sub(
        r"\s+", " ", QUERIES[21]).strip().replace("( select", "(select")


# -- the reference, the engine, the oracle -------------------------------------------


@pytest.mark.parametrize("nation", sorted(AT_TINY))
def test_engine_reference_and_oracle_agree_at_tiny(nation, tables, monkeypatch):
    inst = q21(nation)
    want = inst.statement.module.reference(tables, inst.params)
    if AT_TINY[nation]:
        assert (len(want), want[0]) == AT_TINY[nation]
    assert want and all(type(v) is t for row in want for v, t in zip(row, (str, int)))
    # block by block: `tiny` is one block as it stands, fifteen of these
    monkeypatch.setattr(_common, "BLOCK_ROWS", 4000)
    monkeypatch.setattr(inst.statement.module, "blocks", _common.blocks)
    assert len(list(_common.blocks(len(tables["lineitem"]["l_suppkey"][0])))) >= 15
    assert inst.statement.module.reference(tables, inst.params) == want
    oracle = oracle_rows(TINY, to_sqlite(inst.sql))
    assert [list(r) for r in oracle] == want
    for batch_rows in (4096, 16384):                 # 15 and 4 batches of the fact table
        got = build_runner(tables, batch_rows).execute(inst.sql).rows
        assert harness.same_rows(inst.statement, got, want)
        assert_rows_match(got, oracle, ordered=True)


def test_the_reference_does_not_need_the_lines_in_key_order(tables):
    inst = q21("SAUDI ARABIA")
    want = inst.statement.module.reference(tables, inst.params)
    order = np.random.default_rng(21).permutation(len(tables["lineitem"]["l_suppkey"][0]))
    shuffled = dict(tables, lineitem={
        c: (a[order], d) for c, (a, d) in tables["lineitem"].items()})
    assert inst.statement.module.reference(shuffled, inst.params) == want


@pytest.mark.parametrize("case", [
    "ne_dropped_from_exists", "ne_dropped_from_not_exists", "lateness_dropped_from_not_exists",
    "orderstatus_dropped", "orders_counted_not_lines", "ties_by_name_reversed"])
def test_a_wrong_reference_is_not_correct(case, tables):
    """What `correct` has to catch: either subquery without its `<>`
    (EXISTS is then true of every line, NOT EXISTS of none), NOT EXISTS
    over every line of another supplier and not the late ones, the
    `o_orderstatus = 'F'` lost, `count(distinct l_orderkey)` for
    `count(*)`, and suppliers of equal `numwait` by name descending."""
    statement = load_statement()
    reference = statement.module.reference
    knobs = {
        "ne_dropped_from_exists": {"exists_other": False},
        "ne_dropped_from_not_exists": {"not_exists_other": False},
        "lateness_dropped_from_not_exists": {"not_exists_late": False},
        "orderstatus_dropped": {"with_status": False},
        "orders_counted_not_lines": {"count": "orders"},
        "ties_by_name_reversed": {"ties_ascending": False},
    }[case]
    # at `tiny` no nation's answer alone tells every case apart (few
    # suppliers have two late lines on one order): all 25 together do
    differs = 0
    for nation in statement.draws["nation"]["values"]:
        params = {"nation": nation}
        want, got = reference(tables, params), reference(tables, params, **knobs)
        differs += not harness.same_rows(statement, got, want)
    assert differs >= {"orders_counted_not_lines": 1, "ties_by_name_reversed": 10}.get(case, 20)


def test_count_star_counts_lines():
    """Two late lines of one supplier on one order are two waits."""
    from trino_tpu.block import Dictionary

    tables = {
        "nation": {"n_nationkey": (np.asarray([7]), None),
                   "n_name": (np.asarray([0], dtype=np.int32), Dictionary(["PERU"]))},
        "supplier": {"s_suppkey": (np.asarray([1, 2]), None),
                     "s_name": (np.asarray([0, 1], dtype=np.int32), Dictionary(["A", "B"])),
                     "s_nationkey": (np.asarray([7, 7]), None)},
        "orders": {"o_orderkey": (np.asarray([10]), None),
                   "o_orderstatus": (np.asarray([0], dtype=np.int32), Dictionary(["F", "O"]))},
        "lineitem": {"l_orderkey": (np.asarray([10, 10, 10]), None),
                     "l_suppkey": (np.asarray([1, 1, 2]), None),
                     "l_commitdate": (np.asarray([5, 5, 5], dtype=np.int32), None),
                     "l_receiptdate": (np.asarray([9, 8, 1], dtype=np.int32), None)},
    }
    reference = load_statement().module.reference
    assert reference(tables, {"nation": "PERU"}) == [["A", 2]]
    assert reference(tables, {"nation": "PERU"}, count="orders") == [["A", 1]]
    # the other supplier late too: nobody was the only one
    tables["lineitem"]["l_receiptdate"] = (np.asarray([9, 8, 7], dtype=np.int32), None)
    assert reference(tables, {"nation": "PERU"}) == []


# -- the plan -----------------------------------------------------------------------------


def explain_analyze(runner, sql):
    return runner.execute("explain analyze " + sql).rows[0][0]


def test_the_late_lines_are_built_twice_and_the_fact_table_probes_them(tables):
    runner = build_runner(tables, 16384)
    text = explain_analyze(runner, q21("SAUDI ARABIA").sql)
    plan = text[:text.index("Pipeline 0")].splitlines()
    joins = [line.strip() for line in plan if line.strip().startswith("Join ")]
    assert [j.split(" L[")[0] for j in joins] == [
        "Join anti", "Join semi", "Join inner", "Join inner", "Join inner"]
    assert all(j.endswith("+residual build=left") for j in joins[:2])
    assert not any("build=left" in j for j in joins[2:])
    # the side both joins preserve carries s_name, l_orderkey, l_suppkey
    at = next(i for i, line in enumerate(plan) if line.strip().startswith("Join semi"))
    assert plan[at + 1].strip().startswith("Project [") and plan[at + 1].count("$[") == 3
    # `select *` reads the key and what the residual names; the third
    # scan keeps the dates its filter compares
    scans = [line.strip().split(".lineitem ")[1] for line in plan if ".lineitem " in line]
    assert sorted(scans) == sorted([
        "['l_orderkey', 'l_suppkey', 'l_commitdate', 'l_receiptdate']",
        "['l_orderkey', 'l_suppkey']",
        "['l_orderkey', 'l_suppkey', 'l_commitdate', 'l_receiptdate']"])
    # the last two pipelines are the subqueries': a scan of all 60,064
    # lines, the filter of the build side's keys, the probe; the first
    # ends in the second's build
    pipelines = re.split(r"Pipeline \d+:", text[text.index("Pipeline 0"):])[1:]
    ops = [[line.split(":")[0].strip() for line in p.splitlines()
            if re.search(r"^\s+\w+: in=", line)] for p in pipelines]
    assert ops[-2] == ["TableScanOperator", "DynamicFilterOperator", "LookupJoinOperator",
                       "HashBuildSink"]
    assert ops[-1][:4] == ["TableScanOperator", "FilterProjectOperator",
                           "DynamicFilterOperator", "LookupJoinOperator"]
    assert "HashAggregationOperator" in ops[-1]
    for p in pipelines[-2:]:
        scan = next(line for line in p.splitlines() if "TableScanOperator" in line)
        assert int(re.search(r"out=(\d+) rows", scan).group(1)) == 60064
        probe = next(line for line in p.splitlines() if "LookupJoinOperator" in line)
        # what the filter let through of 60,064 (or of the late 38,080)
        assert int(re.search(r"in=(\d+) rows", probe).group(1)) < 6000
    # no build is fed by a whole scan of the fact table
    builds = [int(re.search(r"in=(\d+) rows", line).group(1))
              for line in text.splitlines() if "HashBuildSink" in line]
    assert max(builds) < 3000


def test_the_statement_counts_what_the_two_joins_did(tables):
    runner = build_runner(tables, 4096)
    sql = q21("SAUDI ARABIA").sql
    runner.execute(sql)
    before = {k: METRICS.counter(k) for k in COUNTERS}
    result = runner.execute(sql)
    moved = {k: METRICS.counter(k) - v for k, v in before.items()}
    assert moved["join_semi_side.source"] == 2 and moved["join_semi_side.filtering"] == 0
    assert moved["semi_pairs_seen"] > moved["semi_pairs_kept"] > 0
    assert moved["semi_build_rows"] > moved["semi_build_flagged"] > 0
    assert moved["df_reverse_rows_in"] == 60064 + 38080
    assert 0 < moved["df_reverse_rows_kept"] < 12000
    # the two scans' lateness filter reads two dates and writes a mask:
    # 15 batches of 4,096 slots each, 9 bytes a slot, twice (the other
    # filters read a dictionary code or nothing)
    assert moved["filter_read_bytes"] >= 2 * 15 * 4096 * 9
    account = result.stats["account"]
    for name in ("join_semi_side.source", "semi_pairs_seen", "semi_pairs_kept",
                 "df_reverse_rows_kept", "filter_read_bytes"):
        assert account["c." + name] == moved[name]
    assert account["s.join.semi_flags.n"] == 2
    # a second run counts the same
    again = {k: METRICS.counter(k) for k in COUNTERS}
    runner.execute(sql)
    assert {k: METRICS.counter(k) - v for k, v in again.items()} == moved


PARENT_PLAN = """\
Output ['s_name', 'numwait']
  TopN keys=[(1, 'desc'), (0, 'asc')] n=100
    Aggregate keys=[0] aggs=['count_star']
      Project ['$[1:varchar]']
        Join anti L[3]=R[0] +residual
          Join semi L[3]=R[0] +residual
            Project ['$[6:bigint]', '$[7:varchar]']
              Join inner L[0]=R[0]
                Scan memory.chipbench.orders ['o_orderkey', 'o_orderstatus']
                Scan memory.chipbench.supplier ['s_suppkey', 's_name', 's_nationkey']
            Scan memory.chipbench.lineitem ['l_orderkey', 'l_suppkey', 'l_commitdate']
          Filter gt($[3:date], $[2:date])
            Scan memory.chipbench.lineitem ['l_orderkey', 'l_suppkey', 'l_commitdate']
"""


def test_the_runner_kind_refuses_a_plan_that_builds_the_fact_table(tables):
    """`local_q21`: one EXPLAIN in front of the `local` runner (step 0 of
    `chipbench/Q21.md`: the parent builds both subqueries' scans)."""
    kind = traffic.load_module(os.path.join(ROOT, "chipbench", "runners", "local_q21.py"))
    refuses = kind.fact_table_built_under_a_semi_join
    assert refuses(PARENT_PLAN)
    # either subquery alone is enough, under a filter or bare
    assert refuses(PARENT_PLAN.replace("Join semi L[3]=R[0] +residual",
                                       "Join semi L[3]=R[0] +residual build=left"))
    assert refuses(PARENT_PLAN.replace("Join anti L[3]=R[0] +residual",
                                       "Join anti L[3]=R[0] +residual build=left"))
    assert not refuses(PARENT_PLAN.replace("+residual", "+residual build=left"))
    # a semi-join that builds something else (Q18's IN set) passes
    assert not refuses(PARENT_PLAN.replace(".lineitem ", ".orders "))
    runner = build_runner(tables, 16384)
    assert not refuses(runner.execute("explain " + q21("PERU").sql).rows[0][0])
    assert load_config(CONFIG)["runner"] == "local_q21"
    # a program that plans it the parent's way ends before its first statement
    import trino_tpu.sql.optimizer as Opt

    was = Opt._with_semi_join_sides
    Opt._with_semi_join_sides = lambda node, stats: node
    try:
        with pytest.raises(SystemExit, match="builds a whole scan of lineitem"):
            build_runner(tables, 16384)
    finally:
        Opt._with_semi_join_sides = was


# -- the harness's phases ---------------------------------------------------------------


def test_the_harness_runs_the_cell_at_tiny(tmp_path, capsys):
    result = harness.run_cell(CELL, seed=4_000_000_001, seconds=2.0, trace=False,
                              cache_root=str(tmp_path), scale=TINY, require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {m["name"] for m in BENCHMARK["end_to_end"]}
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    phases = {line["phase"]: line for line in lines if "phase" in line}
    assert phases["window"]["counters"]["xla_compiles"] == 0
    assert phases["window"]["counters"]["plan_cache.misses"] == 0
    assert phases["data"]["rows"] == {"lineitem": 60064, "orders": 15000,
                                      "supplier": 100, "nation": 25}
    assert [l["references"] for l in lines if "references" in l] == [1]


# -- spans and counters, in a traced run on the CPU -----------------------------------


@pytest.fixture(scope="module")
def traced(tables, tmp_path_factory):
    """One profiler trace over a warm Q21 at `tiny`, 15 batches a scan
    of the fact table: the SpanTrace."""
    runner = build_runner(tables, 4096)
    sql = q21("SAUDI ARABIA").sql
    runner.execute(sql)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            rows = runner.execute(sql).rows
    finally:
        jax.profiler.stop_trace()
    assert len(rows) == AT_TINY["SAUDI ARABIA"][0]
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    return spans.load(path)


def events_named(st, name):
    return [e for line in st.lines for e in line if e.name == name]


def test_the_two_joins_say_what_their_pairs_came_to(traced):
    flags = events_named(traced, semi_trace.SEMI_FLAGS)
    assert sorted(str(e.stats["kind"]) for e in flags) == ["anti", "semi"]
    for e in flags:
        assert int(e.stats["pairs_seen"]) > int(e.stats["pairs_kept"]) > 0
        assert int(e.stats["build_rows"]) > int(e.stats["build_flagged"]) > 0
    semi = next(e for e in flags if str(e.stats["kind"]) == "semi")
    anti = next(e for e in flags if str(e.stats["kind"]) == "anti")
    # what the semi-join flagged is what the anti-join was built of
    assert int(anti.stats["build_rows"]) == int(semi.stats["build_flagged"])
    reverse = [e for e in events_named(traced, semi_trace.DF_TOTALS)
               if int(e.stats.get("reverse", 0))]
    assert sorted(int(e.stats["rows_in"]) for e in reverse) == [38080, 60064]
    probes = [e for e in events_named(traced, semi_trace.MATCH_TOTAL)
              if "first_candidates" in e.stats]
    assert probes and all(
        int(e.stats["first_candidates"]) <= int(e.stats["rows"]) for e in probes)
    calls = [e for line in traced.lines for e in line
             if e.name.startswith(semi_trace.JOIN_OP) and "preserved" in e.stats]
    assert calls and any(e.name.endswith(".finish") for e in calls)
    assert [e for line in traced.lines for e in line
            if e.name.startswith(semi_trace.FILTER_OP) and "reverse" in e.stats]
    (done,) = events_named(traced, stmt_account.DONE)
    assert int(done.stats["c.join_semi_side.source"]) == 2
    assert int(done.stats["c.semi_pairs_seen"]) == sum(
        int(e.stats["pairs_seen"]) for e in flags)


def test_semi_trace_finds_the_traced_runs_spans(traced):
    """(A CPU trace has no device plane, so `semi_trace.metrics` cannot
    reduce it: the readings are computed by hand below.)"""
    events = semi_trace.window_events(traced)
    assert len([e for e in events if e.name == semi_trace.SEMI_FLAGS]) == 2
    with pytest.raises(ValueError):
        semi_trace.metrics(traced)


# -- semi_trace.py ---------------------------------------------------------------------------


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


def test_the_readings_by_hand():
    account = {"wall_us": 4e6, "execute_us": 4e6, "cpu_us": 1e6, "syncs": 3, "sync_us": 10.0,
               "c.filter_read_bytes": 819e3}
    events = [
        spans.Event(P + "phase.execute", 0.0, 8.0, {"cpu_ns": 1}),
        spans.Event(P + "op.DynamicFilterOperator.add_input", 1.0, 1.5, {"reverse": 1}),
        spans.Event(P + "op.DynamicFilterOperator.add_input", 1.5, 2.5, {}),
        spans.Event(P + "sync.join.dynamic_filter_totals", 2.6, 2.7, {
            "rows_in": 60000, "rows_kept": 3000, "batches": 15, "slots": 61440,
            "path": "bits", "key_bytes": 8, "reverse": 1}),
        spans.Event(P + "sync.join.dynamic_filter_totals", 2.8, 2.9, {
            "rows_in": 1000, "rows_kept": 1000, "batches": 1, "slots": 4096,
            "path": "range", "key_bytes": 8, "reverse": 0}),
        spans.Event(P + "op.LookupJoinOperator.add_input", 3.0, 4.0, {"preserved": 1}),
        spans.Event(P + "sync.join.match_total", 3.5, 3.6, {
            "rows": 5000, "probe_slots": 4096, "first_candidates": 3000}),
        spans.Event(P + "op.LookupJoinOperator.add_input", 4.0, 5.0, {}),
        spans.Event(P + "sync.join.match_total", 4.5, 4.6, {"rows": 3000, "probe_slots": 4096}),
        spans.Event(P + "op.LookupJoinOperator.finish", 5.0, 5.5, {"preserved": 1}),
        spans.Event(P + "sync.join.semi_flags", 5.1, 5.2, {
            "pairs_seen": 5000, "pairs_kept": 4000, "build_rows": 900, "build_flagged": 800,
            "kind": "semi"}),
        spans.Event(P + "stmt.done", 3.999, 4.0, account),
        spans.Event(P + "stmt.done", 7.999, 8.0, account),
        # ends after the window: not this window's
        spans.Event(P + "sync.join.semi_flags", 9.9, 10.5, {"pairs_seen": 1 << 20,
                                                            "pairs_kept": 1}),
    ]
    programs = [("jit__flag_build_rows(1)", 3.0, 3.0 + 2e-6), ("jit_probe_counts(2)", 4.0, 5.0),
                ("jit_FilterProjectOperator(3)", 1.0, 1.0 + 4e-6), ("jit__flagged_rows(4)", 5.0, 5.5)]
    got = semi_trace.metrics(one_chip_trace(
        events, [(1.0, 1.0 + 4e-6), (3.0, 3.0 + 2e-6), (4.0, 5.0), (5.0, 5.5)], programs))
    assert got["statements_in_window"] == 2 and got["statement_equivalents"] == pytest.approx(2.0)
    assert got["semi_op_share_pct"] == pytest.approx(100 * 2.0 / 8.0)
    assert got["semi_op_share_of"] == "phase.execute"
    assert got["semi_device_share_pct"] == pytest.approx(
        100 * (0.5 + 2e-6) / (1.5 + 6e-6), rel=1e-6)
    assert got["semi_pairs_per_stmt"] == pytest.approx(5000 / 2)
    assert got["semi_pairs_kept_pct"] == pytest.approx(80.0)
    assert got["reverse_filter_kept_pct"] == pytest.approx(5.0)
    # 819,000 bytes a statement at 819 GB/s are a microsecond; the filter's
    # program ran 4 microseconds over two statements
    assert got["scan_filter_roofline_pct"] == pytest.approx(100 * 1e-6 / 2e-6, rel=1e-3)
    moved = semi_trace.flag_rows_bytes(4096, 5000)
    assert moved == 4096 * 24 + 5000 * 21
    assert got["flag_rows_roofline_pct"] == pytest.approx(
        100 * (moved / 2 / 819e9) / 1e-6, rel=1e-3)
    assert got["joins"] == [{"kind": "semi", "pairs_seen": 5000, "pairs_kept": 4000,
                             "build_rows": 900, "build_flagged": 800}]
    assert got["probe_batches"] == [{"rows": 5000, "probe_slots": 4096,
                                     "first_candidates": 3000}]
    assert got["reverse_filters"] == [{"path": "bits", "rows_in": 60000, "rows_kept": 3000,
                                       "batches": 15, "slots": 61440}]


@pytest.mark.parametrize("recorded", ["trace_spans_small.xplane.pb",
                                      "trace_small.xplane.pb"])
def test_a_program_from_before_the_spans_reads_none_and_nothing_raises(recorded):
    """The parent's traces: no `semi_flags`, no `reverse`, no account."""
    got = semi_trace.metrics(spans.load(os.path.join(HERE, recorded)))
    for name in ("semi_op_share_pct", "semi_device_share_pct", "semi_pairs_per_stmt",
                 "semi_pairs_kept_pct", "reverse_filter_kept_pct", "scan_filter_roofline_pct",
                 "flag_rows_roofline_pct"):
        assert got[name] is None
    assert got["joins"] == [] and got["reverse_filters"] == [] and got["probe_batches"] == []


def test_the_command_reads_the_cells_last_traced_run(tmp_path, monkeypatch, capsys):
    import shutil

    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    assert semi_trace.main([CELL]) == 1 and "no traced run" in capsys.readouterr().err
    there = tmp_path / CELL / "plugins" / "profile" / "2026_10_01"
    there.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "trace_spans_small.xplane.pb"), there / "host.xplane.pb")
    assert semi_trace.main([CELL]) == 0
    line = json.loads(capsys.readouterr().out)
    assert {"semi_op_share_pct", "semi_device_share_pct", "semi_pairs_per_stmt",
            "semi_pairs_kept_pct", "reverse_filter_kept_pct", "scan_filter_roofline_pct",
            "flag_rows_roofline_pct"} <= set(line)
    assert semi_trace.main([]) == 2
