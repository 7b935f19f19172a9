"""TPC-H Q13 on the CPU at `tiny`, through the files of its cell
(`sf10.q13`, configuration `tpch-sf10-q13-1chip`, `chipbench/Q13.md`):
what `BENCHMARK.json` names, the engine against the plain reference and
against the sqlite oracle for all sixteen word pairs, the reference
coming out wrong when it should, the plan (the comment's filter and the
count a customer under the join, the customers probing what is left of
the orders), the runner kind that refuses the parent's plan, the
harness's phases, the spans and counters of the outer join in a traced
run, and `chipbench/outer_trace.py` by hand."""

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

from chipbench import harness, outer_trace, spans, stmt_account, trace, traffic  # noqa: E402
from chipbench.references import _common  # noqa: E402
from tests.oracle import oracle_rows  # noqa: E402
from tests.test_tpch import to_sqlite  # noqa: E402
from trino_tpu.runtime.metrics import METRICS  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = traffic.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "sf10.q13"
CONFIG = "tpch-sf10-q13-1chip"
# what `BENCHMARK.json` held before this cell, in its order
OLDER_CONFIGS = ["tpch-sf1-1chip", "tpch-sf10-1chip", "tpch-sf30-4chip",
                 "tpch-sf10-q18-1chip", "tpch-sf10-q9-1chip", "tpch-sf10-q21-1chip"]
OLDER_CELLS = ["sf1.scan_agg", "sf1.join", "sf10.scan_agg", "sf30.mesh4", "sf10.q18",
               "sf10.q9", "sf10.q21"]
TINY = 0.01
P = spans.PROGRAM
Q13_COLUMNS = {
    "customer": ["c_custkey"],
    "orders": ["o_orderkey", "o_custkey", "o_comment"],
}
WORD1 = ["special", "pending", "unusual", "express"]
WORD2 = ["packages", "requests", "accounts", "deposits"]
PAIRS = [(a, b) for a in WORD1 for b in WORD2]
COUNTERS = ("join_outer_side.build", "join_outer_side.probe", "join_outer_build_rows",
            "join_outer_unmatched_rows", "join_expand_launches.fanout1",
            "join_expand_launches.general", "agg_unordered_input.batches",
            "agg_ordered_input.batches", "agg_ingest_path.sort", "df_reverse_rows_in",
            "df_reverse_rows_kept")


def load_config(name):
    return traffic.load_json(os.path.join(ROOT, "chipbench", "configs", f"{name}.json"))


def load_traffic():
    return traffic.load_json(os.path.join(ROOT, "chipbench", "traffic", "q13.1stream.json"))


def load_statement():
    return traffic.load_statement("q13")


@pytest.fixture(scope="module")
def tables():
    """Q13's columns at `tiny`, as `data.load_columns` hands them over."""
    from trino_tpu.connectors.tpch import base_row_count, generate_column

    return {
        table: {c: generate_column(table, c, TINY, 0, base_row_count(table, TINY))
                for c in columns}
        for table, columns in Q13_COLUMNS.items()
    }


def build_runner(tables, batch_rows):
    config = load_config(CONFIG)
    runner_kind = traffic.load_module(
        os.path.join(ROOT, "chipbench", "runners", config["runner"] + ".py"))
    return runner_kind.build({**config, "batch_rows": batch_rows}, tables)


def q13(word1="special", word2="requests"):
    return traffic.instantiate(load_statement(), {"word1": word1, "word2": word2})


# -- the configuration, the traffic, the statement ---------------------------------------


def test_the_configuration_states_the_deployment_its_cuts_and_its_guarantees():
    config, other = load_config(CONFIG), load_config("tpch-sf10-q21-1chip")
    assert config["guarantees"] == other["guarantees"]      # word for word
    assert config["deployment"] == other["deployment"]
    assert (config["scale"], config["batch_rows"], config["chips"], config["connector"],
            config["schema"]) == (10.0, 1 << 20, 1, "memory", "chipbench")
    assert os.path.exists(os.path.join(ROOT, "chipbench", "runners", config["runner"] + ".py"))
    assert config["reduced"] == ["scale", "columns", "streams"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert "2.4.13" in config["source"] and 1 <= len(config["source"]) <= 200
    assert "15,000,000" in config["reduced_why"]["scale"]
    assert "1,500,000" in config["reduced_why"]["scale"]
    # the comments' pool is said, and that the LIKE is not what the cell measures
    assert "3,000" in config["assumed"]["o_comment"] and "NOT" in config["assumed"]["o_comment"]
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    # the driver's limits on the entry's lines (it refused 207 characters)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert all(1 <= len(entry[k]) <= 200 and entry[k].isprintable()
               for k in ("source", "why"))
    # appended: the six configurations before it keep their places, and
    # whatever a later PR appends comes behind
    assert [c["name"] for c in BENCHMARK["configs"]][:len(OLDER_CONFIGS) + 1] == [
        *OLDER_CONFIGS, CONFIG]


def test_the_pools_count_of_matching_comments_is_the_configurations():
    """`assumed.o_comment` states what each of the sixteen pairs keeps of
    the 3,000 comments, from a count: this is the count."""
    from trino_tpu.connectors.tpch import _comment_dict

    pool = list(_comment_dict("order").values)
    assert len(pool) == 3000
    like = load_statement().module.like
    said = load_config(CONFIG)["assumed"]["o_comment"]
    for w1 in WORD1:
        counts = [sum(like(v, w1, w2) for v in pool) for w2 in WORD2]
        assert f"{w1} " + " / ".join(map(str, counts)) in said
    assert sum(like(v, "special", "requests") for v in pool) == 60      # 2 % of the pool


def test_the_traffic_and_the_statement_are_the_issues():
    mix = load_traffic()
    assert (mix["statements"], mix["loop"], mix["streams"],
            mix["params_per_statement"], mix["client_poll_ms"]) == (
        ["q13"], "closed", 1, 1, 2)
    spec = traffic.load_json(os.path.join(ROOT, "chipbench", "statements", "q13.json"))
    assert spec["draws"] == {"word1": {"draw": "choice", "values": WORD1},
                             "word2": {"draw": "choice", "values": WORD2}}
    assert spec["validation"] == {"word1": "special", "word2": "requests"}
    assert spec["ordered"] is True
    assert spec["tables"] == Q13_COLUMNS and spec["scan_columns"] == Q13_COLUMNS
    assert spec["reference"] == "q13"


def test_the_benchmark_names_the_configuration_and_the_cell():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "q13.1stream", 1)
    assert [w["name"] for w in BENCHMARK["workloads"]][:len(OLDER_CELLS) + 1] == [
        *OLDER_CELLS, CELL]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(cell["why"]) <= 200 and cell["why"].isprintable()
    # the cell adds no per-layer entry: its readings are outer_trace.py's
    assert not [m for m in BENCHMARK["per_layer"] if CELL in m.get("workloads", ())]


@pytest.mark.parametrize("seed", [1, 2, 2_147_483_659, 4_300_000_001])
def test_any_seed_plans_one_pair_of_words(seed):
    plan = traffic.plan(load_traffic(), seed)
    (inst,) = plan.instances
    assert inst.params["word1"] in WORD1 and inst.params["word2"] in WORD2
    assert f"not like '%{inst.params['word1']}%{inst.params['word2']}%'" in inst.sql
    assert traffic.plan(load_traffic(), seed).instances[0].sql == inst.sql


def test_the_seeds_draw_all_sixteen_pairs():
    drawn = {tuple(traffic.plan(load_traffic(), seed).instances[0].params[k]
                   for k in ("word1", "word2"))
             for seed in range(4_300_000_001, 4_300_000_201)}
    assert drawn == set(PAIRS)


def test_the_text_is_the_specs_statement():
    from tests.tpch_queries import QUERIES

    def spaced(sql):
        return re.sub(r"\s+", " ", re.sub(r"([()])", r" \1 ", sql)).strip()

    assert spaced(q13().sql) == spaced(QUERIES[13])


# -- the reference, the engine, the oracle -------------------------------------------


@pytest.mark.parametrize("word1, word2", PAIRS)
def test_reference_and_oracle_agree_at_tiny_for_every_pair(word1, word2, tables):
    inst = q13(word1, word2)
    want = inst.statement.module.reference(tables, inst.params)
    assert want[0] == [0, 500]            # the customers without an order come first
    assert all(type(v) is int for row in want for v in row)
    assert sum(row[1] for row in want) == 1500          # every customer, once
    oracle = oracle_rows(TINY, to_sqlite(inst.sql))
    assert [list(r) for r in oracle] == want


@pytest.mark.parametrize("word1, word2", [("special", "requests"), ("pending", "deposits"),
                                          ("express", "accounts")])
def test_the_engine_answers_the_reference_whatever_the_batches(word1, word2, tables,
                                                               monkeypatch):
    inst = q13(word1, word2)
    want = inst.statement.module.reference(tables, inst.params)
    # block by block: `tiny` is one block as it stands, eight of these
    monkeypatch.setattr(_common, "BLOCK_ROWS", 2000)
    monkeypatch.setattr(inst.statement.module, "blocks", _common.blocks)
    assert len(list(_common.blocks(len(tables["orders"]["o_custkey"][0])))) == 8
    assert inst.statement.module.reference(tables, inst.params) == want
    for batch_rows in (1024, 4096, 16384):          # 15, 4 and 1 batches of orders
        got = build_runner(tables, batch_rows).execute(inst.sql).rows
        assert harness.same_rows(inst.statement, got, want)


def test_the_reference_does_not_need_the_tables_in_any_order(tables):
    inst = q13()
    want = inst.statement.module.reference(tables, inst.params)
    rng = np.random.default_rng(13)
    shuffled = {
        t: {c: (a[order], d) for c, (a, d) in cols.items()}
        for t, cols in tables.items()
        for order in [rng.permutation(len(next(iter(cols.values()))[0]))]
    }
    assert inst.statement.module.reference(shuffled, inst.params) == want


@pytest.mark.parametrize("case", [
    "an_inner_join", "count_star_for_count_of_the_key", "the_like_as_a_where",
    "the_filter_ignored", "the_sort_keys_swapped"])
def test_a_wrong_reference_is_not_correct(case, tables):
    """What `correct` has to catch: an inner join (the row of the
    customers without an order gone), `count(*)` for `count(o_orderkey)`
    (those customers counted as having one), the LIKE applied after the
    join as a WHERE (the NULL rows fail it), the filter ignored, and the
    rows ordered by c_count first."""
    statement = load_statement()
    reference = statement.module.reference
    knobs = {
        "an_inner_join": {"outer": False},
        "count_star_for_count_of_the_key": {"count": "*"},
        "the_like_as_a_where": {"like_in": "where"},
        "the_filter_ignored": {"with_filter": False},
        "the_sort_keys_swapped": {"order": ("c_count", "custdist")},
    }[case]
    for word1, word2 in PAIRS:
        params = {"word1": word1, "word2": word2}
        want, got = reference(tables, params), reference(tables, params, **knobs)
        assert not harness.same_rows(statement, got, want), (case, word1, word2)


def test_the_counts_by_hand():
    """Three customers: one with two orders of which one is refused, one
    whose only order is refused, one with none."""
    from trino_tpu.block import Dictionary

    comments = Dictionary(["plain words", "special deposits then requests"])
    tables = {
        "customer": {"c_custkey": (np.asarray([1, 2, 3]), None)},
        "orders": {"o_orderkey": (np.asarray([10, 11, 12]), None),
                   "o_custkey": (np.asarray([1, 1, 2]), None),
                   "o_comment": (np.asarray([0, 1, 1], dtype=np.int32), comments)},
    }
    reference = load_statement().module.reference
    params = {"word1": "special", "word2": "requests"}
    assert reference(tables, params) == [[0, 2], [1, 1]]
    assert reference(tables, params, outer=False) == [[1, 1]]
    assert reference(tables, params, count="*") == [[1, 3]]
    assert reference(tables, params, with_filter=False) == [[2, 1], [1, 1], [0, 1]]
    # WORD2 has to come after WORD1
    assert reference(tables, {"word1": "requests", "word2": "special"}) == [
        [2, 1], [1, 1], [0, 1]]
    like = load_statement().module.like
    assert like("a special b requests c", "special", "requests")
    assert not like("requests special", "special", "requests")
    assert not like("specialrequest", "special", "requests")
    assert like("specialrequests", "special", "requests")


# -- the plan -----------------------------------------------------------------------------


def explain_analyze(runner, sql):
    return runner.execute("explain analyze " + sql).rows[0][0]


def test_the_orders_are_counted_under_the_join_and_the_customers_probe_the_counts(tables):
    runner = build_runner(tables, 1024)
    text = explain_analyze(runner, q13().sql)
    plan = [line.strip() for line in text[:text.index("Pipeline 0")].splitlines()]
    (join,) = [line for line in plan if line.startswith("Join ")]
    assert join == "Join left L[0]=R[0]"                       # no +residual
    at = plan.index(join)
    assert plan[at - 2].startswith("Aggregate keys=[0] aggs=['sum']")
    assert plan[at - 1].startswith("Project ") and "coalesce(" in plan[at - 1]
    assert plan[at + 1] == "Scan memory.chipbench.customer ['c_custkey']"
    assert plan[at + 2].startswith("Aggregate keys=[1] aggs=['count']")
    assert plan[at + 3].startswith("Filter not(like(")
    assert plan[at + 4].startswith("Scan memory.chipbench.orders ")
    pipelines = re.split(r"Pipeline \d+:", text[text.index("Pipeline 0"):])[1:]
    ops = [[line.split(":")[0].strip() for line in p.splitlines()
            if re.search(r"^\s+\w+: in=", line)] for p in pipelines]
    # (the comment's filter runs inside the aggregation's ingest)
    assert ops[0][0] == "TableScanOperator" and ops[0][-2:] == [
        "HashAggregationOperator", "HashBuildSink"]
    assert ops[1][:2] == ["TableScanOperator", "LookupJoinOperator"]
    assert ops[1].count("HashAggregationOperator") == 2
    build = next(line for line in text.splitlines() if "HashBuildSink" in line)
    # the 1,000 customers that have an order left to count
    assert int(re.search(r"in=(\d+) rows", build).group(1)) == 1000
    probe = next(line for line in text.splitlines() if "LookupJoinOperator" in line)
    assert int(re.search(r"in=(\d+) rows", probe).group(1)) == 1500
    assert int(re.search(r"out=(\d+) rows", probe).group(1)) == 1500


def test_the_statement_counts_what_the_join_and_the_aggregations_did(tables):
    runner = build_runner(tables, 1024)
    sql = q13().sql
    runner.execute(sql)
    before = {k: METRICS.counter(k) for k in COUNTERS}
    result = runner.execute(sql)
    moved = {k: METRICS.counter(k) - v for k, v in before.items()}
    assert moved["join_outer_side.probe"] == 1 and moved["join_outer_side.build"] == 0
    assert moved["join_outer_build_rows"] == 1000
    assert moved["join_outer_unmatched_rows"] == 500
    # two batches of customers against the counts' unique keys
    assert moved["join_expand_launches.fanout1"] == 2
    assert moved["join_expand_launches.general"] == 0
    assert moved["df_reverse_rows_in"] == 0
    account = result.stats["account"]
    for name in ("join_outer_side.probe", "join_outer_build_rows",
                 "join_outer_unmatched_rows"):
        assert account["c." + name] == moved[name]
    assert account["s.join.outer_flags.n"] == 1
    # a second run counts the same
    again = {k: METRICS.counter(k) for k in COUNTERS}
    runner.execute(sql)
    assert {k: METRICS.counter(k) - v for k, v in again.items()} == moved


PARENT_PLAN = """\
Output ['c_count', 'custdist']
  Sort keys=[(1, 'desc'), (0, 'desc')]
    Project ['$[0:bigint]', '$[1:bigint]']
      Aggregate keys=[0] aggs=['count_star']
        Project ['$[1:bigint]']
          Aggregate keys=[0] aggs=['count']
            Project ['$[0:bigint]', '$[1:bigint]']
              Join left L[0]=R[1] +residual
                Scan memory.chipbench.customer ['c_custkey']
                Scan memory.chipbench.orders ['o_orderkey', 'o_custkey', 'o_comment']
"""


def test_the_runner_kind_refuses_a_plan_that_builds_the_orders(tables, monkeypatch):
    """`local_q13`: one EXPLAIN in front of the `local` runner (step 0 of
    `chipbench/Q13.md`: the parent builds all 15 M orders under the join
    and runs out of the chip's memory compiling the expansion)."""
    kind = traffic.load_module(os.path.join(ROOT, "chipbench", "runners", "local_q13.py"))
    refuses = kind.fact_table_built_under_a_left_join
    assert refuses(PARENT_PLAN)
    # under the comment's filter too
    assert refuses(PARENT_PLAN.replace(
        "                Scan memory.chipbench.orders",
        "                Filter not(like($[2:varchar], lit('%a%b%':varchar)))\n"
        "                  Scan memory.chipbench.orders").replace(" +residual", ""))
    # the customers built, or the orders counted under the join: run
    assert not refuses(PARENT_PLAN.replace("+residual", "build=left"))
    assert not refuses(PARENT_PLAN.replace(
        "                Scan memory.chipbench.orders",
        "                Aggregate keys=[1] aggs=['count']\n"
        "                  Scan memory.chipbench.orders"))
    # a left join that builds something else passes
    assert not refuses(PARENT_PLAN.replace(".orders ", ".nation "))
    runner = build_runner(tables, 16384)
    assert not refuses(runner.execute("explain " + q13("pending", "accounts").sql).rows[0][0])
    config = load_config(CONFIG)
    assert config["runner"] == "local_q13" and "step 0" in config["runner_why"]
    # a program that plans it the parent's way ends before its first statement
    import trino_tpu.sql.optimizer as Opt

    monkeypatch.setattr(Opt, "_with_aggregates_under_left_joins", lambda node, stats: node)
    monkeypatch.setattr(Opt, "_with_semi_join_sides", lambda node, stats: node)
    with pytest.raises(SystemExit, match="builds all of orders"):
        build_runner(tables, 16384)


def test_the_sort_path_counts_its_unordered_batches():
    """The first aggregation's key arrives in no order. Over the memory
    connector at `tiny` the customers' exact key range (1,500 slots)
    bounds its table; through the tpch connector, which declares no
    exact range, the batches take the sort path as at SF10 (1.5 M slots
    are past every bounded table) and count there."""
    from tests.tpch_queries import QUERIES
    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import LocalQueryRunner, Session

    runner = LocalQueryRunner(Session(catalog="tpch", schema="tiny", batch_rows=4096))
    runner.register_catalog("tpch", create_tpch_connector())
    before = {k: METRICS.counter(k) for k in COUNTERS}
    rows = runner.execute(QUERIES[13]).rows
    moved = {k: METRICS.counter(k) - v for k, v in before.items()}
    assert [list(r) for r in rows][0] == [0, 500]
    # four batches of orders into the aggregation under the join, the
    # join's batches into the one over it, its result into the last
    assert moved["agg_ingest_path.sort"] >= 6
    assert moved["agg_unordered_input.batches"] >= 4
    assert (moved["agg_unordered_input.batches"] + moved["agg_ordered_input.batches"]
            == moved["agg_ingest_path.sort"])


# -- the harness's phases ---------------------------------------------------------------


def test_the_harness_runs_the_cell_at_tiny(tmp_path, capsys):
    result = harness.run_cell(CELL, seed=4_300_000_001, seconds=2.0, trace=False,
                              cache_root=str(tmp_path), scale=TINY, require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {m["name"] for m in BENCHMARK["end_to_end"]}
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    phases = {line["phase"]: line for line in lines if "phase" in line}
    assert phases["window"]["counters"]["xla_compiles"] == 0
    assert phases["window"]["counters"]["plan_cache.misses"] == 0
    assert phases["data"]["rows"] == {"customer": 1500, "orders": 15000}
    assert [l["references"] for l in lines if "references" in l] == [1]


# -- spans and counters, in a traced run on the CPU -----------------------------------


@pytest.fixture(scope="module")
def traced(tables, tmp_path_factory):
    """One profiler trace over a warm Q13 at `tiny`, 15 batches of
    orders: the SpanTrace."""
    runner = build_runner(tables, 1024)
    sql = q13().sql
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
    assert [list(r) for r in rows][0] == [0, 500]
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    return spans.load(path)


def events_named(st, name):
    return [e for line in st.lines for e in line if e.name == name]


def test_the_join_says_what_came_out_with_nulls(traced):
    (flags,) = events_named(traced, outer_trace.OUTER_FLAGS)
    assert (int(flags.stats["preserved_rows"]), int(flags.stats["unmatched"])) == (1500, 500)
    assert str(flags.stats["preserved"]) == "probe"
    assert int(flags.stats["build_rows"]) == 1000 and int(flags.stats["build_slots"]) >= 1000
    calls = [e for line in traced.lines for e in line
             if e.name.startswith(outer_trace.JOIN_OP)]
    assert calls and all("outer" in e.stats for e in calls)
    assert any(e.name.endswith(".finish") for e in calls)
    assert not any("preserved" in e.stats for e in calls)       # semi_trace.py's stat
    (done,) = events_named(traced, stmt_account.DONE)
    assert int(done.stats["c.join_outer_side.probe"]) == 1
    assert int(done.stats["c.join_outer_unmatched_rows"]) == 500
    assert int(done.stats["c.join_outer_build_rows"]) == 1000
    assert int(done.stats["c.agg_unordered_input.batches"]) >= 1


def test_outer_trace_finds_the_traced_runs_spans(traced):
    """(A CPU trace has no device plane, so `outer_trace.metrics` cannot
    reduce it: the readings are computed by hand below.)"""
    events = outer_trace.window_events(traced)
    assert len([e for e in events if e.name == outer_trace.OUTER_FLAGS]) == 1
    assert len([e for e in events if e.name == outer_trace.MATCH_TOTAL]) == 2
    with pytest.raises(ValueError):
        outer_trace.metrics(traced)


# -- outer_trace.py --------------------------------------------------------------------------


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


def by_hand(other_join=False, ordered=0):
    account = {"wall_us": 4e6, "execute_us": 4e6, "cpu_us": 1e6, "syncs": 3, "sync_us": 10.0,
               "c.agg_unordered_input.batches": 16, "c.agg_ordered_input.batches": ordered}
    events = [
        spans.Event(P + "phase.execute", 0.0, 8.0, {"cpu_ns": 1}),
        spans.Event(P + "op.LookupJoinOperator.add_input", 1.0, 2.0, {"outer": 1}),
        spans.Event(P + "sync.join.match_total", 1.5, 1.6, {"rows": 4000, "probe_slots": 4096}),
        spans.Event(P + "op.LookupJoinOperator.add_input", 2.0, 2.5,
                    {} if other_join else {"outer": 1}),
        spans.Event(P + "sync.join.match_total", 2.2, 2.3, {"rows": 4000, "probe_slots": 4096}),
        spans.Event(P + "op.LookupJoinOperator.finish", 3.0, 3.5, {"outer": 1}),
        spans.Event(P + "sync.join.outer_flags", 3.1, 3.2, {
            "build_rows": 1500, "unmatched": 500, "preserved_rows": 1500,
            "build_slots": 2048, "preserved": "build"}),
        spans.Event(P + "agg.merge", 5.0, 5.5, {"states": 8, "slots_in": 64, "cap": 64,
                                                "retry": 0}),
        spans.Event(P + "stmt.done", 3.999, 4.0, account),
        spans.Event(P + "stmt.done", 7.999, 8.0, account),
        # ends after the window: not this window's
        spans.Event(P + "sync.join.outer_flags", 9.9, 10.5, {"preserved_rows": 1 << 20,
                                                             "unmatched": 1}),
    ]
    programs = [("jit_probe_counts(1)", 1.0, 1.5), ("jit__expand_pairs_fanout1(2)", 1.5, 2.0),
                ("jit__mark_build_rows(3)", 2.0, 2.0 + 1e-6),
                ("jit__mark_build_rows(3)", 2.5, 2.5 + 1e-6),
                ("jit__agg_ingest(4)", 4.0, 4.25), ("jit__agg_ingest(4)", 4.5, 4.75),
                ("jit__agg_ingest_train(5)", 6.0, 6.5),
                ("jit__merge_group_states(6)", 5.0, 5.5)]
    busy = [(s, e) for _, s, e in programs]
    return outer_trace.metrics(one_chip_trace(events, busy, programs))


def test_the_readings_by_hand():
    got = by_hand()
    busy = 0.5 + 0.5 + 2e-6 + 0.5 + 0.5 + 0.5
    assert got["statements_in_window"] == 2 and got["statement_equivalents"] == pytest.approx(2.0)
    assert got["outer_op_share_pct"] == pytest.approx(100 * 2.0 / 8.0)
    assert got["outer_op_share_of"] == "phase.execute"
    # the window's joins are all outer joins: the probes are theirs
    assert got["outer_device_share_of"] == "every join program"
    assert got["outer_device_share_pct"] == pytest.approx(100 * (1.0 + 2e-6) / busy, rel=1e-6)
    assert got["outer_unmatched_pct"] == pytest.approx(100 / 3)
    assert got["agg_unordered_ms_per_batch"] == pytest.approx(250.0)
    assert got["agg_merge_ms_per_stmt"] == pytest.approx(250.0)
    assert got["agg_merge_device_ms_per_stmt"] == pytest.approx(250.0)
    moved = outer_trace.mark_build_rows_bytes(2, 4096, 2048)
    assert moved == 2 * (4096 * 5 + 2048 * 2)
    assert got["mark_build_rows_roofline_pct"] == pytest.approx(
        100 * (moved / 819e9) / 2e-6, rel=1e-3)
    assert got["outer_joins"] == [{"build_rows": 1500, "unmatched": 500,
                                   "preserved_rows": 1500, "build_slots": 2048,
                                   "preserved": "build"}]
    assert got["probe_batches"] == 2 and got["unordered_batches"] == 32


def test_beside_another_join_only_the_outer_joins_own_program_counts():
    got = by_hand(other_join=True)
    assert got["outer_device_share_of"] == "the programs only an outer join runs"
    assert got["outer_device_share_pct"] == pytest.approx(100 * 2e-6 / (2.5 + 2e-6), rel=1e-6)
    assert got["outer_op_share_pct"] == pytest.approx(100 * 1.5 / 8.0)
    # mostly batches that skipped their key sort: the mean is not theirs
    assert by_hand(ordered=3)["agg_unordered_ms_per_batch"] == pytest.approx(250.0)
    assert by_hand(ordered=16)["agg_unordered_ms_per_batch"] is None


@pytest.mark.parametrize("recorded", ["trace_spans_small.xplane.pb",
                                      "trace_small.xplane.pb"])
def test_a_program_from_before_the_spans_reads_none_and_nothing_raises(recorded):
    """The parent's traces: no `outer_flags`, no `outer`, no account."""
    got = outer_trace.metrics(spans.load(os.path.join(HERE, recorded)))
    for name in ("outer_op_share_pct", "outer_device_share_pct", "outer_unmatched_pct",
                 "agg_unordered_ms_per_batch", "agg_merge_device_ms_per_stmt",
                 "mark_build_rows_roofline_pct"):
        assert got[name] is None
    assert got["outer_joins"] == [] and got["probe_batches"] == 0


def test_the_command_reads_the_cells_last_traced_run(tmp_path, monkeypatch, capsys):
    import shutil

    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    assert outer_trace.main([CELL]) == 1 and "no traced run" in capsys.readouterr().err
    there = tmp_path / CELL / "plugins" / "profile" / "2026_10_02"
    there.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "trace_spans_small.xplane.pb"), there / "host.xplane.pb")
    assert outer_trace.main([CELL]) == 0
    line = json.loads(capsys.readouterr().out)
    assert {"outer_op_share_pct", "outer_device_share_pct", "outer_unmatched_pct",
            "agg_unordered_ms_per_batch", "agg_merge_ms_per_stmt",
            "mark_build_rows_roofline_pct"} <= set(line)
    assert outer_trace.main([]) == 2
