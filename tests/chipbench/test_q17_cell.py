"""TPC-H Q17 on the CPU at `tiny`, through the files of its cell
(`sf10.q17`, configuration `tpch-sf10-q17-1chip`, `chipbench/Q17.md`):
what `BENCHMARK.json` names, the engine against the plain reference and
the reference against the sqlite oracle, the reference coming out wrong
when it should (on tables made by hand, where a cent of the average
decides, and at `tiny`), the plan (the join's key filter under the
aggregation by `l_partkey`), the harness's phases, the spans and
counters of a traced run, and `chipbench/corr_trace.py` by hand."""

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

from chipbench import corr_trace, harness, spans, stmt_account, trace, traffic  # noqa: E402
from chipbench.references import _common  # noqa: E402
from tests.oracle import oracle_rows  # noqa: E402
from tests.test_tpch import to_sqlite  # noqa: E402
from trino_tpu.runtime.metrics import METRICS  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = traffic.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "sf10.q17"
CONFIG = "tpch-sf10-q17-1chip"
# what `BENCHMARK.json` held before this cell, in its order
OLDER_CONFIGS = ["tpch-sf1-1chip", "tpch-sf10-1chip", "tpch-sf30-4chip",
                 "tpch-sf10-q18-1chip", "tpch-sf10-q9-1chip", "tpch-sf10-q21-1chip",
                 "tpch-sf10-q13-1chip"]
OLDER_CELLS = ["sf1.scan_agg", "sf1.join", "sf10.scan_agg", "sf30.mesh4", "sf10.q18",
               "sf10.q9", "sf10.q21", "sf10.q13"]
TINY = 0.01
P = spans.PROGRAM
Q17_COLUMNS = {
    "lineitem": ["l_partkey", "l_quantity", "l_extendedprice"],
    "part": ["p_partkey", "p_brand", "p_container"],
}
BRANDS = [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]
CONTAINERS = [f"{size} {kind}" for size in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for kind in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM")]
# a spread of the 1,000 pairs: every 83rd, and the validation pair
SPREAD = [(BRANDS[i // 40], CONTAINERS[i % 40]) for i in range(0, 1000, 83)] + [
    ("Brand#23", "MED BOX")]
COUNTERS = ("df_under_aggregate", "decorrelated_scalar_aggregates",
            "agg_filtered_input.batches", "df_reverse_rows_in", "df_reverse_rows_kept",
            "join_outer_side.build", "join_outer_side.probe", "agg_ingest_path.sort",
            "agg_unordered_input.batches", "agg_ordered_input.batches")


def load_config(name):
    return traffic.load_json(os.path.join(ROOT, "chipbench", "configs", f"{name}.json"))


def load_traffic():
    return traffic.load_json(os.path.join(ROOT, "chipbench", "traffic", "q17.1stream.json"))


def load_statement():
    return traffic.load_statement("q17")


@pytest.fixture(scope="module")
def tables():
    """Q17's columns at `tiny`, as `data.load_columns` hands them over."""
    from trino_tpu.connectors.tpch import base_row_count, generate_column

    return {
        table: {c: generate_column(table, c, TINY, 0, base_row_count(table, TINY))
                for c in columns}
        for table, columns in Q17_COLUMNS.items()
    }


def build_runner(tables, batch_rows):
    config = load_config(CONFIG)
    runner_kind = traffic.load_module(
        os.path.join(ROOT, "chipbench", "runners", config["runner"] + ".py"))
    return runner_kind.build({**config, "batch_rows": batch_rows}, tables)


def q17(brand="Brand#23", container="MED BOX"):
    return traffic.instantiate(load_statement(), {"brand": brand, "container": container})


# -- the configuration, the traffic, the statement ---------------------------------------


def test_the_configuration_states_the_deployment_its_cuts_and_its_guarantees():
    config, other = load_config(CONFIG), load_config("tpch-sf10-q13-1chip")
    assert config["guarantees"] == other["guarantees"]      # word for word
    assert config["deployment"] == other["deployment"]
    assert (config["scale"], config["batch_rows"], config["chips"], config["connector"],
            config["schema"]) == (10.0, 1 << 20, 1, "memory", "chipbench")
    assert os.path.exists(os.path.join(ROOT, "chipbench", "runners", config["runner"] + ".py"))
    assert config["reduced"] == ["scale", "columns", "streams"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert "2.4.17" in config["source"] and 1 <= len(config["source"]) <= 200
    assert "59,992,734" in config["reduced_why"]["scale"]
    assert "2,000,000" in config["reduced_why"]["scale"]
    # both copies of the fact table are said, and what they come to
    assert "2.43 GB" in config["reduced_why"]["scale"]
    # the counted selectivities: parts and lines a pair, least and most
    for number in ("1,872", "2,148", "56,181", "64,302", "1,953", "58,767"):
        assert number in config["assumed"]["selectivity"]
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    # the driver's limits on the entry's lines
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert all(1 <= len(entry[k]) <= 200 and entry[k].isprintable()
               for k in ("source", "why"))
    # appended: the seven configurations before it keep their places, and
    # whatever a later PR appends comes behind
    assert [c["name"] for c in BENCHMARK["configs"]][:len(OLDER_CONFIGS) + 1] == [
        *OLDER_CONFIGS, CONFIG]


def test_the_selectivities_at_tiny_are_counted_the_way_the_configurations_are(tables):
    """`assumed.selectivity` is a count over SF10's generated columns
    (`chipbench/Q17.md` has the script's lines); the same count at `tiny`:
    1,000 pairs over 2,000 parts, every line's part among them."""
    module = load_statement().module
    parts = lines = 0
    l_part = tables["lineitem"]["l_partkey"][0]
    for brand, container in SPREAD:
        keys = module.selected_parts(tables, brand, container)
        parts += len(keys)
        lines += len(module.lines_of(keys, l_part)[0])
    assert 13 <= parts <= 60 and 10 * parts <= lines <= 50 * parts
    assert len(tables["part"]["p_brand"][1].values) == 25
    assert len(tables["part"]["p_container"][1].values) == 40
    assert sorted(tables["part"]["p_brand"][1].values) == sorted(BRANDS)
    assert sorted(tables["part"]["p_container"][1].values) == sorted(CONTAINERS)


def test_the_traffic_and_the_statement_are_the_issues():
    mix = load_traffic()
    assert (mix["statements"], mix["loop"], mix["streams"],
            mix["params_per_statement"], mix["client_poll_ms"]) == (
        ["q17"], "closed", 1, 1, 2)
    spec = traffic.load_json(os.path.join(ROOT, "chipbench", "statements", "q17.json"))
    assert spec["draws"] == {"brand": {"draw": "choice", "values": BRANDS},
                             "container": {"draw": "choice", "values": CONTAINERS}}
    assert spec["validation"] == {"brand": "Brand#23", "container": "MED BOX"}
    assert spec["ordered"] is True
    assert spec["tables"] == Q17_COLUMNS and spec["scan_columns"] == Q17_COLUMNS
    assert spec["reference"] == "q17"


def test_the_benchmark_names_the_configuration_and_the_cell():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "q17.1stream", 1)
    assert [w["name"] for w in BENCHMARK["workloads"]][:len(OLDER_CELLS) + 1] == [
        *OLDER_CELLS, CELL]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(cell["why"]) <= 200 and cell["why"].isprintable()
    # the cell adds no per-layer entry: its readings are corr_trace.py's
    assert not [m for m in BENCHMARK["per_layer"] if CELL in m.get("workloads", ())]


@pytest.mark.parametrize("seed", [1, 2, 2_147_483_659, 4_800_000_001])
def test_any_seed_plans_one_pair(seed):
    plan = traffic.plan(load_traffic(), seed)
    (inst,) = plan.instances
    assert inst.params["brand"] in BRANDS and inst.params["container"] in CONTAINERS
    assert f"p_brand = '{inst.params['brand']}'" in inst.sql
    assert f"p_container = '{inst.params['container']}'" in inst.sql
    assert traffic.plan(load_traffic(), seed).instances[0].sql == inst.sql


def test_the_seeds_reach_all_thousand_pairs():
    """The generator's own draw (`traffic.draw`, the parameters in name
    order, as `traffic.plan` takes them), over 12,000 seeds."""
    draws = load_statement().draws
    drawn = set()
    for seed in range(4_800_000_001, 4_800_012_001):
        rng = np.random.default_rng(seed)
        params = {k: traffic.draw(rng, d) for k, d in sorted(draws.items())}
        drawn.add((params["brand"], params["container"]))
    assert drawn == {(b, c) for b in BRANDS for c in CONTAINERS}
    seed = 4_800_000_777
    rng = np.random.default_rng(seed)
    assert traffic.plan(load_traffic(), seed).instances[0].params == {
        k: traffic.draw(rng, d) for k, d in sorted(draws.items())}


def test_the_text_is_the_specs_statement():
    from tests.tpch_queries import QUERIES

    def spaced(sql):
        return re.sub(r"\s+", " ", re.sub(r"([()])", r" \1 ", sql)).strip()

    assert spaced(q17().sql) == spaced(QUERIES[17])


# -- the reference, the engine, the oracle -------------------------------------------


@pytest.mark.parametrize("brand, container", SPREAD)
def test_reference_and_oracle_agree_at_tiny_for_a_spread_of_pairs(brand, container, tables):
    """sqlite computes in doubles and rounds nowhere; with at most 62
    lines a part no average lies within a cent of a quantity it is not
    equal to, so it selects the same lines, and its quotient is within
    half a cent of the reference's decimal."""
    inst = q17(brand, container)
    want = inst.statement.module.reference(tables, inst.params)
    ((oracle,),) = oracle_rows(TINY, to_sqlite(inst.sql))
    if oracle is None:
        assert want == [[None]]
        return
    ((value,),) = want
    assert type(value) is float and abs(value - oracle) <= 0.005 + 1e-9
    # the revenue itself, to the cent
    ((revenue,),) = inst.statement.module.reference(tables, inst.params, yearly=False)
    assert abs(revenue - 7.0 * oracle) < 1e-6 * max(1.0, revenue)


def test_the_spread_holds_pairs_with_an_answer_and_without(tables):
    reference = load_statement().module.reference
    answers = [reference(tables, {"brand": b, "container": c}) for b, c in SPREAD]
    assert [[None]] in answers
    assert sum(a != [[None]] for a in answers) >= 4


@pytest.mark.parametrize("brand, container", [("Brand#23", "MED BOX"), SPREAD[0], SPREAD[7]])
def test_the_engine_answers_the_reference_whatever_the_batches(brand, container, tables,
                                                               monkeypatch):
    inst = q17(brand, container)
    want = inst.statement.module.reference(tables, inst.params)
    # block by block: `tiny` is one block as it stands, eight of these
    monkeypatch.setattr(_common, "BLOCK_ROWS", 8000)
    monkeypatch.setattr(inst.statement.module, "blocks", _common.blocks)
    assert len(list(_common.blocks(len(tables["lineitem"]["l_partkey"][0])))) == 8
    assert inst.statement.module.reference(tables, inst.params) == want
    for batch_rows in (1024, 4096, 16384, 65536):    # 59, 15, 4 and 1 batches of lines
        got = build_runner(tables, batch_rows).execute(inst.sql).rows
        assert harness.same_rows(inst.statement, got, want)


def test_the_reference_does_not_need_the_tables_in_any_order(tables):
    inst = q17()
    want = inst.statement.module.reference(tables, inst.params)
    rng = np.random.default_rng(17)
    shuffled = {
        t: {c: (a[order], d) for c, (a, d) in cols.items()}
        for t, cols in tables.items()
        for order in [rng.permutation(len(next(iter(cols.values()))[0]))]
    }
    assert inst.statement.module.reference(shuffled, inst.params) == want


# -- tables by hand: where a cent of the average decides -------------------------------

# hundredths; the first four parts are Brand#11 in SM BOX, the fifth is not
BY_HAND_LINES = {
    # avg 500.5 hundredths, half up 501, a fifth 1.002: the line of 1.00 is
    # below it; truncated (500, 1.000) it is not; averaged over the lines
    # kept (itself alone) it is not
    1: [100, 901],
    # avg 500.3 -> 500, a fifth 1.000: 1.00 is NOT below it; 0.2 applied
    # before the rounding, 1.0006 -> 1.001, it is; `<=` keeps it too
    2: [100] + [545] * 8 + [543],
    # avg exactly 5.00: 1.00 is not below a fifth of it, `<=` says it is
    3: [100, 900],
    # avg 1,000,000.01: 200,000.00 is below a fifth of it by 0.002; the
    # sum, 300,000,003 hundredths, is 300,000,000 in float32, and then not
    4: [20_000_000, 140_000_000, 140_000_003],
    # another brand's part: in nothing but the average over everything
    5: [1, 2, 3],
}
CONTROLS = {
    "a_float32_average": {"average": "float32"},
    "a_truncated_average": {"average": "truncated"},
    "le_for_lt": {"compare": np.less_equal},
    "the_average_over_the_lines_the_filter_keeps": {"over": "kept"},
    "one_global_average": {"over": "all"},
    "a_fifth_before_the_rounding": {"scale_first": True},
    "the_sum_not_divided_by_seven": {"yearly": False},
    "a_float32_sum_of_the_prices": {"revenue_dtype": np.float32},
}


@pytest.fixture(scope="module")
def by_hand():
    from trino_tpu.block import Dictionary

    brands, containers = Dictionary(["Brand#11", "Brand#12"]), Dictionary(["SM BAG", "SM BOX"])
    part, qty = [], []
    for key, lines in BY_HAND_LINES.items():
        part += [key] * len(lines)
        qty += lines
    # every line its own price, so that no two selections sum alike
    price = [100_000_000 * (i + 1) + 7 for i in range(len(qty))]
    return {
        "part": {"p_partkey": (np.asarray([1, 2, 3, 4, 5]), None),
                 "p_brand": (np.asarray([0, 0, 0, 0, 1], dtype=np.int32), brands),
                 "p_container": (np.asarray([1, 1, 1, 1, 0], dtype=np.int32), containers)},
        "lineitem": {"l_partkey": (np.asarray(part), None),
                     "l_quantity": (np.asarray(qty), None),
                     "l_extendedprice": (np.asarray(price), None)},
    }


HAND = {"brand": "Brand#11", "container": "SM BOX"}


def test_the_reference_by_hand(by_hand):
    reference = load_statement().module.reference
    # the lines kept: part 1's first and part 4's first
    kept = by_hand["lineitem"]["l_extendedprice"][0][[0, 14]]
    assert reference(by_hand, HAND, yearly=False) == [[_common.dec(int(kept.sum()), 2)]]
    assert reference(by_hand, HAND) == [[_common.dec((2 * int(kept.sum()) + 7) // 14, 2)]]
    assert reference(by_hand, {"brand": "Brand#12", "container": "SM BOX"}) == [[None]]
    assert reference(by_hand, {"brand": "Brand#99", "container": "SM BOX"}) == [[None]]


@pytest.mark.parametrize("case", sorted(CONTROLS))
def test_a_wrong_reference_is_not_correct(case, by_hand, tables):
    """What `correct` has to catch: the average in float32, truncated and
    not rounded half up, `<=` for `<`, the average taken over the lines
    the quantity filter keeps, one average for the whole table, the 0.2
    applied before the average is rounded, the sum not divided by 7.0,
    the prices summed in float32 (the precision below the decimal's).
    On the tables made by hand every one answers another number; at
    `tiny`, where no average lies within a cent of a quantity, the ones
    that select other lines or another quotient do."""
    statement = load_statement()
    reference = statement.module.reference
    want = reference(by_hand, HAND)
    assert not harness.same_rows(statement, reference(by_hand, HAND, **CONTROLS[case]), want)
    if case not in ("one_global_average", "the_average_over_the_lines_the_filter_keeps",
                    "the_sum_not_divided_by_seven"):
        return
    differ = answered = 0
    for brand, container in SPREAD:
        params = {"brand": brand, "container": container}
        want = reference(tables, params)
        if want == [[None]]:
            continue
        answered += 1
        differ += not harness.same_rows(
            statement, reference(tables, params, **CONTROLS[case]), want)
    # (a fifth of the whole table's average, 5.1, selects a part's lines
    # of 1 to 5 as a fifth of its own average mostly does: one part a
    # pair at `tiny`, so only some pairs tell; the other two always do)
    assert answered >= 4
    assert differ >= (1 if case == "one_global_average" else answered)


def test_the_engine_answers_the_tables_made_by_hand(by_hand):
    """The ties and the near-ties of the decimal average, through the
    engine: exact where the real tables never ask for it."""
    statement = load_statement()
    want = statement.module.reference(by_hand, HAND)
    for batch_rows in (16, 1024):
        runner = build_runner(by_hand, batch_rows)
        got = runner.execute(traffic.instantiate(statement, HAND).sql).rows
        assert harness.same_rows(statement, got, want)
        # part by part: the average the engine hands on is the decimal's
        rows = runner.execute(
            "select l_partkey, avg(l_quantity) from lineitem group by l_partkey "
            "order by l_partkey").rows
        assert [list(r) for r in rows] == [
            [1, 5.01], [2, 5.0], [3, 5.0], [4, 1000000.01], [5, 0.02]]


# -- the plan -----------------------------------------------------------------------------


def explain_analyze(runner, sql):
    return runner.execute("explain analyze " + sql).rows[0][0]


def test_the_key_filter_stands_under_the_aggregation(tables):
    runner = build_runner(tables, 4096)
    text = explain_analyze(runner, q17().sql)
    plan = [line.strip() for line in text[:text.index("Pipeline 0")].splitlines()]
    left, inner = [line for line in plan if line.startswith("Join ")]
    assert left == "Join left L[3]=R[0] build=left filter=under_aggregate"
    assert inner == "Join inner L[0]=R[0]"
    at = plan.index(left)
    assert plan[at - 1].startswith("Filter lt(")
    agg = next(i for i, line in enumerate(plan) if line.startswith("Aggregate keys=[0] aggs=['avg']"))
    assert plan[agg - 1].startswith("Project ") and "mul(lit(0.2" in plan[agg - 1]
    assert plan[agg + 2] == ("Scan memory.chipbench.lineitem ['l_partkey', 'l_quantity'] "
                             "key_filter=[0]")
    pipelines = re.split(r"Pipeline \d+:", text[text.index("Pipeline 0"):])[1:]
    ops = [[line.split(":")[0].strip() for line in p.splitlines()
            if re.search(r"^\s+\w+: in=", line)] for p in pipelines]
    # part's filter builds; the lines probe it and build; the second scan
    # is filtered by their keys, THEN aggregated, and probes them
    assert ops[0] == ["TableScanOperator", "FilterProjectOperator", "HashBuildSink"]
    assert ops[1] == ["TableScanOperator", "DynamicFilterOperator", "LookupJoinOperator",
                      "HashBuildSink"]
    assert ops[2][:4] == ["TableScanOperator", "DynamicFilterOperator",
                          "HashAggregationOperator", "FilterProjectOperator"]
    assert ops[2][4] == "LookupJoinOperator"
    last = [line for line in pipelines[2].splitlines() if "Operator:" in line]
    scan_out = int(re.search(r"out=(\d+) rows", last[0]).group(1))
    filter_out = int(re.search(r"out=(\d+) rows", last[1]).group(1))
    agg_out = int(re.search(r"out=(\d+) rows", last[2]).group(1))
    # 60,064 lines in, the one selected part's lines out, one average
    assert scan_out == 60064 and filter_out == 22 and agg_out == 1


def test_the_statement_counts_what_the_filter_and_the_aggregation_did(tables):
    runner = build_runner(tables, 4096)
    sql = q17().sql
    runner.execute(sql)
    before = {k: METRICS.counter(k) for k in COUNTERS}
    result = runner.execute(sql)
    moved = {k: METRICS.counter(k) - v for k, v in before.items()}
    assert moved["df_under_aggregate"] == 1
    assert moved["decorrelated_scalar_aggregates"] == 1
    assert moved["df_reverse_rows_in"] == 60064 and moved["df_reverse_rows_kept"] == 22
    assert moved["agg_filtered_input.batches"] == 1
    assert moved["join_outer_side.build"] == 1 and moved["join_outer_side.probe"] == 0
    account = result.stats["account"]
    for name in ("df_under_aggregate", "decorrelated_scalar_aggregates",
                 "agg_filtered_input.batches", "df_reverse_rows_in", "df_reverse_rows_kept"):
        assert account["c." + name] == moved[name]
    assert account["plan_hit"] == 1
    # a second run counts the same
    again = {k: METRICS.counter(k) for k in COUNTERS}
    runner.execute(sql)
    assert {k: METRICS.counter(k) - v for k, v in again.items()} == moved


def test_with_the_filter_over_the_aggregation_the_answer_is_the_same(tables, monkeypatch):
    """The parent's plan (step 0 of `chipbench/Q17.md`): every part's
    average, 2,000 of them, and the filter after."""
    import trino_tpu.sql.optimizer as Opt

    inst = q17()
    want = inst.statement.module.reference(tables, inst.params)
    monkeypatch.setattr(Opt, "_with_key_filters_under_aggregates", lambda node, stats: node)
    runner = build_runner(tables, 4096)
    text = explain_analyze(runner, inst.sql)
    assert "under_aggregate" not in text and "key_filter" not in text
    agg = next(line for line in text.splitlines() if "HashAggregationOperator: in=60064" in line)
    assert "out=2000 rows" in agg
    before = METRICS.counter("agg_filtered_input.batches")
    assert harness.same_rows(inst.statement, runner.execute(inst.sql).rows, want)
    assert METRICS.counter("agg_filtered_input.batches") == before


PARENT_PLAN = """\
Output ['avg_yearly']
  Project ['div($[0:decimal(38,2)], lit(7.0:decimal(2,1)))']
    Aggregate keys=[] aggs=['sum']
      Project ['$[2:decimal(12,2)]']
        Filter lt($[1:decimal(12,2)], $[7:decimal(14,3)])
          Join left L[3]=R[0] build=left
            Join inner L[0]=R[0]
              Scan memory.chipbench.lineitem ['l_partkey', 'l_quantity', 'l_extendedprice']
              Filter and(eq($[1:varchar], lit('Brand#23':varchar)), eq($[2:varchar], lit('MED BOX':varchar)))
                Scan memory.chipbench.part ['p_partkey', 'p_brand', 'p_container']
            Project ['$[0:bigint]', 'mul(lit(0.2:decimal(2,1)), $[1:decimal(12,2)])']
              Aggregate keys=[0] aggs=['avg']
                Project ['$[0:bigint]', '$[1:decimal(12,2)]']
                  Scan memory.chipbench.lineitem ['l_partkey', 'l_quantity']
"""


def test_the_cell_runs_under_the_plain_local_runner_whatever_the_plan(tables, monkeypatch):
    """No plan guard stands in front of the cell: the parent ends Q17 by
    itself under the cell's files (step 0 of `chipbench/Q17.md`: 2.76 s a
    statement, its warm-up inside the client's limit), so its numbers
    stand beside the change's. The change's plan is the parent's with
    the filter's place said on two lines."""
    config = load_config(CONFIG)
    assert config["runner"] == "local" and "runner_why" not in config
    runner = build_runner(tables, 16384)
    want = PARENT_PLAN.replace("build=left", "build=left filter=under_aggregate").replace(
        "lineitem ['l_partkey', 'l_quantity']", "lineitem ['l_partkey', 'l_quantity'] key_filter=[0]")
    ranged = " key_ranges=[(1, 2000)]"   # (the memory connector counts its keys' range)

    def plan_of(r):
        return r.execute("explain " + q17().sql).rows[0][0].replace(ranged, "").strip()

    assert plan_of(runner) == want.strip()
    # a program that plans it the parent's way is built and answers too
    import trino_tpu.sql.optimizer as Opt

    monkeypatch.setattr(Opt, "_with_key_filters_under_aggregates", lambda node, stats: node)
    parent = build_runner(tables, 16384)
    assert plan_of(parent) == PARENT_PLAN.strip()


# -- the harness's phases ---------------------------------------------------------------


def test_the_harness_runs_the_cell_at_tiny(tmp_path, capsys):
    """(counted in statements, not in a rate: one that completes and is
    compared is what the phases need, whatever the sandbox's load)"""
    result = harness.run_cell(CELL, seed=4_800_000_001, seconds=2.0, trace=False,
                              cache_root=str(tmp_path), scale=TINY, require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {m["name"] for m in BENCHMARK["end_to_end"]}
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    phases = {line["phase"]: line for line in lines if "phase" in line}
    assert phases["window"]["counters"]["xla_compiles"] == 0
    assert phases["window"]["counters"]["plan_cache.misses"] == 0
    assert phases["data"]["rows"] == {"lineitem": 60064, "part": 2000}
    # part, the fact table under the aggregation, and of the other scan
    # what the one or two selected keys let through as an IN-list (at
    # SF10 2,000 keys are no IN-list and the scan reads every row)
    scanned = phases["warm"]["instances"][0]["rows_scanned"]
    assert 60064 + 2000 <= scanned <= 60064 + 2000 + 200
    assert [l["references"] for l in lines if "references" in l] == [1]


# -- spans and counters, in a traced run on the CPU -----------------------------------


@pytest.fixture(scope="module")
def traced(tables, tmp_path_factory):
    """One profiler trace over a warm Q17 at `tiny`, 15 batches a scan of
    the fact table: the SpanTrace."""
    runner = build_runner(tables, 4096)
    sql = q17().sql
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
    assert [list(r) for r in rows] == [[1117.2]]
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    return spans.load(path)


def events_named(st, name):
    return [e for line in st.lines for e in line if e.name == name]


def test_the_filter_under_the_aggregation_says_so_in_the_trace(traced):
    totals = events_named(traced, corr_trace.DF_TOTALS)
    (under,) = [e for e in totals if "under_aggregate" in e.stats]
    (other,) = [e for e in totals if "under_aggregate" not in e.stats]
    assert (int(under.stats["rows_in"]), int(under.stats["rows_kept"])) == (60064, 22)
    assert int(under.stats["reverse"]) == 1 and int(under.stats["batches"]) == 15
    assert int(other.stats["reverse"]) == 0
    calls = [e for line in traced.lines for e in line
             if e.name.startswith(P + "op.DynamicFilterOperator.")]
    assert any("under_aggregate" in e.stats for e in calls)
    assert any("under_aggregate" not in e.stats for e in calls)
    (done,) = events_named(traced, stmt_account.DONE)
    assert int(done.stats["c.df_under_aggregate"]) == 1
    assert int(done.stats["c.decorrelated_scalar_aggregates"]) == 1
    assert int(done.stats["c.agg_filtered_input.batches"]) == 1
    assert int(done.stats["c.df_reverse_rows_kept"]) == 22


def test_corr_trace_finds_the_traced_runs_spans(traced):
    """(A CPU trace has no device plane, so `corr_trace.metrics` cannot
    reduce it: the readings are computed by hand below.)"""
    events = corr_trace.window_events(traced)
    assert len([e for e in events if e.name == corr_trace.DF_TOTALS]) == 2
    with pytest.raises(ValueError):
        corr_trace.metrics(traced)


# -- corr_trace.py --------------------------------------------------------------------------


def one_chip_trace(events, programs):
    names = [f"%fusion.{j} = f(%x)" for j in range(len(programs))]
    device_ops = {"/device:TPU:0": (
        names, np.asarray([s for _, s, _ in programs], float),
        np.asarray([e for _, _, e in programs], float))}
    yardstick = trace.Trace(device_ops, {}, {}, [
        trace.Annotation(trace.WINDOW, 0.0, 10.0, {}),
        trace.Annotation(trace.ENGINE, 0.0, 10.0, {}),
        trace.Annotation(trace.CLIENT, 0.5, 4.0, {}),
        trace.Annotation(trace.CLIENT, 4.0, 9.0, {})])
    return spans.SpanTrace(yardstick, [events], programs)


def by_hand_trace(other_path="set"):
    account = {"wall_us": 4e6, "execute_us": 4e6, "cpu_us": 1e6, "syncs": 3, "sync_us": 10.0,
               "c.df_under_aggregate": 1, "c.decorrelated_scalar_aggregates": 1,
               "c.agg_filtered_input.batches": 4, "c.df_reverse_rows_in": 3 << 20,
               "c.df_reverse_rows_kept": 3000}
    totals = P + "sync.join.dynamic_filter_totals"
    under = {"rows_in": 3 << 20, "rows_kept": 3000, "batches": 3, "slots": 3 << 20,
             "path": "bits", "key_bytes": 8, "reverse": 1, "under_aggregate": 1}
    other = {"rows_in": 1 << 20, "rows_kept": 1000, "batches": 1, "slots": 1 << 20,
             "path": other_path, "key_bytes": 8, "reverse": 0}
    events = [
        spans.Event(P + "phase.execute", 0.0, 8.0, {"cpu_ns": 1}),
        spans.Event(P + "df.prepare", 0.1, 0.2, {"path": "bits", "table_bytes": 1 << 18}),
        spans.Event(totals, 1.0, 1.1, other), spans.Event(totals, 2.0, 2.1, under),
        spans.Event(totals, 5.0, 5.1, other), spans.Event(totals, 6.0, 6.1, under),
        spans.Event(P + "stmt.done", 3.999, 4.0, account),
        spans.Event(P + "stmt.done", 7.999, 8.0, account),
        # ends after the window: not this window's
        spans.Event(totals, 9.9, 10.5, under),
    ]
    programs = [("jit__df_filter_set(1)", 1.0, 1.5), ("jit__df_filter_bits(2)", 2.0, 3.0),
                ("jit__pack_rows(3)", 3.0, 3.4), ("jit__front_rows(4)", 3.4, 3.8),
                ("jit__df_filter(5)", 4.0, 4.25), ("jit__agg_ingest(6)", 4.5, 4.75),
                ("jit__merge_group_states(7)", 5.0, 5.25),
                ("jit_probe_counts(8)", 6.0, 7.0)]
    return corr_trace.metrics(one_chip_trace(events, programs))


def test_the_readings_by_hand():
    got = by_hand_trace()
    busy = 0.5 + 1.0 + 0.4 + 0.4 + 0.25 + 0.25 + 0.25 + 1.0
    assert got["statements_in_window"] == 2 and got["statement_equivalents"] == pytest.approx(2.0)
    assert got["corr_filter_kept_pct"] == pytest.approx(100 * 3000 / (3 << 20))
    assert got["corr_agg_rows_per_stmt"] == pytest.approx(3000.0)
    # the bits are the filter's under the aggregation alone; the packers'
    # seconds go by slots, three batches of four; the set and the range
    # are other filters'
    assert got["corr_filter_device_share_pct"] == pytest.approx(
        100 * (1.0 + 0.75 * 0.8) / busy)
    assert got["corr_agg_device_share_pct"] == pytest.approx(100 * 0.5 / busy)
    moved = 2 * corr_trace.join_trace.df_bits_bytes(3 << 20, 8, 3, 1 << 18)
    assert got["df_bits_roofline_pct"] == pytest.approx(100 * (moved / 819e9) / 1.0, rel=1e-3)
    assert got["c.df_under_aggregate"] == 1 and got["c.decorrelated_scalar_aggregates"] == 1
    assert got["c.agg_filtered_input.batches"] == 4
    assert len(got["filters"]) == 4 and got["new_programs"] == []
    assert sorted(got["filter_programs_device_s"]) == [
        "jit__df_filter", "jit__df_filter_bits", "jit__df_filter_set", "jit__front_rows",
        "jit__pack_rows"]


def test_filters_that_share_a_path_share_its_seconds_by_slots():
    """Beside another filter that takes the bits too, the bits' seconds
    are divided by the slots each sent through them."""
    got = by_hand_trace(other_path="bits")
    busy = 0.5 + 1.0 + 0.4 + 0.4 + 0.25 + 0.25 + 0.25 + 1.0
    assert got["corr_filter_device_share_pct"] == pytest.approx(
        100 * 0.75 * (1.0 + 0.8) / busy)


@pytest.mark.parametrize("recorded", ["trace_spans_small.xplane.pb",
                                      "trace_small.xplane.pb"])
def test_a_program_from_before_the_spans_reads_none_and_nothing_raises(recorded):
    """The parent's traces: no `under_aggregate`, no such counters."""
    got = corr_trace.metrics(spans.load(os.path.join(HERE, recorded)))
    for name in ("corr_filter_kept_pct", "corr_agg_rows_per_stmt",
                 "corr_filter_device_share_pct", "corr_agg_device_share_pct",
                 "c.df_under_aggregate", "c.decorrelated_scalar_aggregates"):
        assert got[name] is None


def test_the_command_reads_the_cells_last_traced_run(tmp_path, monkeypatch, capsys):
    import shutil

    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    assert corr_trace.main([CELL]) == 1 and "no traced run" in capsys.readouterr().err
    there = tmp_path / CELL / "plugins" / "profile" / "2026_10_04"
    there.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "trace_spans_small.xplane.pb"), there / "host.xplane.pb")
    assert corr_trace.main([CELL]) == 0
    line = json.loads(capsys.readouterr().out)
    assert {"corr_filter_kept_pct", "corr_agg_rows_per_stmt", "corr_filter_device_share_pct",
            "corr_agg_device_share_pct", "df_bits_roofline_pct", "c.df_under_aggregate",
            "c.decorrelated_scalar_aggregates"} <= set(line)
    assert corr_trace.main([]) == 2
