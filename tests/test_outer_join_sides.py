"""Which side a LEFT join builds (issue 43): the plan's
`JoinNode.build_left` for `left`, decided from the two sides' estimated
rows; the operator that puts out the pairs of a build side it preserves
as they come and the build rows no pair flagged, with NULLs, at its
input's end (in grace mode a partition at a time); the `ON` conjunct
that reads the null-supplying side alone, applied under the join; and
the sort path's count of the batches that paid their key sort. CPU
counts and answers only; what any of it costs is a chip reading
(PERF.md section 6, PR 43)."""

import dataclasses
import sqlite3
import zlib

import numpy as np
import pytest

from tests.oracle import assert_rows_match, oracle_rows
from tests.test_semi_join_sides import batch, drain, rng_rows
from tests.test_tpch import to_sqlite
from tests.tpch_queries import QUERIES
from trino_tpu import types as T
from trino_tpu.exec import operators as O
from trino_tpu.expr import ir
from trino_tpu.expr.compile import ExprBinder
from trino_tpu.runtime.metrics import METRICS
from trino_tpu.sql import plan as P

SCHEMA = [(T.BIGINT, None), (T.BIGINT, None)]      # (key, payload)
COUNTERS = ("join_outer_side.build", "join_outer_side.probe", "join_outer_build_rows",
            "join_outer_unmatched_rows", "join_expand_launches.general",
            "join_expand_launches.fanout1", "df_reverse_rows_in", "df_reverse_rows_kept",
            "agg_ordered_input.batches", "agg_unordered_input.batches",
            "agg_ingest_path.sort")


def moved(fn):
    before = {c: METRICS.counter(c) for c in COUNTERS}
    out = fn()
    return out, {c: METRICS.counter(c) - before[c] for c in COUNTERS}


RESIDUALS = {
    None: None,
    # reads both sides / the preserved side alone, over (left, right)
    "both": ir.Call("ne", (ir.InputRef(1, T.BIGINT), ir.InputRef(3, T.BIGINT)), T.BOOLEAN),
    "preserved": ir.Call("eq", (ir.InputRef(1, T.BIGINT), ir.Literal(1, T.BIGINT)), T.BOOLEAN),
}
HOLDS = {None: lambda lp, rp: True, "both": lambda lp, rp: lp != rp,
         "preserved": lambda lp, rp: lp == 1}


def sort_rows(rows):
    return sorted(rows, key=lambda r: tuple((v is None, v) for v in r))


def run_left_join(left, right, residual, build_left, spill=False, with_filter=False):
    """The rows of `left LEFT JOIN right ON left.key = right.key [AND
    residual]`, sorted, with the side built that `build_left` says."""
    bridge = O.JoinBridge()
    built, probing = (left, right) if build_left else (right, left)
    sink = O.HashBuildSink(bridge, [0], SCHEMA, force_spill=spill)
    for rows in built:
        sink.add_input(batch(rows))
    sink.finish()
    fn = None
    if residual is not None:
        fn = O.make_residual_fn(
            ExprBinder([t for t, _ in SCHEMA * 2], [None] * 4).bind(RESIDUALS[residual]))
    ops = [O.LookupJoinOperator(bridge, [0], "left", SCHEMA, residual_fn=fn,
                                build_preserved=build_left)]
    if with_filter:
        ops.insert(0, O.DynamicFilterOperator(bridge, [0], reverse=True))
    out = []

    def push(op_at, b):
        if op_at == len(ops):
            out.append(b)
            return
        ops[op_at].add_input(b)
        for o in drain(ops[op_at]):
            push(op_at + 1, o)

    for rows in probing:
        push(0, batch(rows))
    for at, op in enumerate(ops):
        op.finish()
        for o in drain(op):
            push(at + 1, o)
    return sort_rows(tuple(r) for b in out for r in b.to_pylists())


def expected(left, right, residual):
    holds = HOLDS[residual]
    rights = [r for rows in right for r in rows if r[0] is not None]
    out = []
    for lk, lp in (r for rows in left for r in rows):
        pairs = [(lk, lp, rk, rp) for rk, rp in rights
                 if lk is not None and rk == lk and holds(lp, rp)]
        out.extend(pairs or [(lk, lp, None, None)])
    return sort_rows(out)


def sides(case):
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    if case == "null_keys_on_the_preserved_side":
        return [rng_rows(rng, 40, 12, 3, nulls=0.3)], [rng_rows(rng, 60, 12, 3)]
    if case == "null_keys_on_the_null_supplying_side":
        return [rng_rows(rng, 40, 12, 3)], [rng_rows(rng, 60, 12, 3, nulls=0.3)]
    if case == "repeated_keys_on_both_sides":
        return [rng_rows(rng, 64, 5, 2)], [rng_rows(rng, 64, 5, 2)]
    if case == "unmatched_rows_on_both_sides":
        return ([[(k, k % 3) for k in range(0, 40, 2)]],
                [[(k, k % 2) for k in range(0, 40, 3)], [(k, 1) for k in range(100, 140)]])
    if case == "an_empty_preserved_side":
        return [[]], [rng_rows(rng, 30, 8, 3)]
    if case == "an_empty_null_supplying_side":
        return [rng_rows(rng, 30, 8, 3)], [[]]
    if case == "several_batches_a_side":
        return ([rng_rows(rng, 32, 40, 3) for _ in range(3)],
                [rng_rows(rng, 64, 40, 3) for _ in range(4)])
    if case == "a_preserved_row_matched_only_by_a_later_batch":
        return [[(7, 1), (8, 1), (9, 1), (10, 1)]], [[(8, 1), (1, 1)], [(8, 2)], [(7, 1)],
                                                    [(7, 5), (9, 1)]]
    if case == "every_probe_row_has_one_candidate":
        # a unique key on the preserved side and a dense probe: the
        # fanout-one form when the preserved side is the build
        return ([[(k, k % 3) for k in range(48)]],
                [[(int(k), 1) for k in rng.integers(0, 32, 64)] for _ in range(2)])
    raise AssertionError(case)


CASES = ["null_keys_on_the_preserved_side", "null_keys_on_the_null_supplying_side",
         "repeated_keys_on_both_sides", "unmatched_rows_on_both_sides",
         "an_empty_preserved_side", "an_empty_null_supplying_side", "several_batches_a_side",
         "a_preserved_row_matched_only_by_a_later_batch", "every_probe_row_has_one_candidate"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("residual", [None, "both", "preserved"],
                         ids=["no_residual", "residual_on_both", "residual_on_the_preserved"])
def test_either_side_built_gives_the_same_rows(residual, case):
    left, right = sides(case)
    want = expected(left, right, residual)
    assert run_left_join(left, right, residual, build_left=False) == want
    assert run_left_join(left, right, residual, build_left=True) == want
    assert run_left_join(left, right, residual, build_left=True, with_filter=True) == want


@pytest.mark.parametrize("case", ["unmatched_rows_on_both_sides", "several_batches_a_side",
                                  "a_preserved_row_matched_only_by_a_later_batch",
                                  "null_keys_on_the_preserved_side",
                                  "an_empty_null_supplying_side"])
@pytest.mark.parametrize("residual", [None, "both"], ids=["no_residual", "residual"])
def test_a_spilled_preserved_side_answers_a_partition_at_a_time(residual, case):
    """The grace path: every partition flags its own build rows and
    emits the unflagged ones, a partition without a probe page too, and
    the flags start anew with each."""
    left, right = sides(case)
    want = expected(left, right, residual)
    got, counts = moved(lambda: run_left_join(left, right, residual, build_left=True,
                                              spill=True))
    assert got == want
    n_left = sum(len(rows) for rows in left)
    assert counts["join_outer_side.build"] == 1 and counts["join_outer_side.probe"] == 0
    # every partition counted its own build rows, once
    assert counts["join_outer_build_rows"] == n_left
    assert counts["join_outer_unmatched_rows"] == len([r for r in want if r[2] is None
                                                       and r[3] is None])
    assert run_left_join(left, right, residual, build_left=False, spill=True) == want


def test_the_counters_say_which_side_was_preserved_and_what_came_out_with_nulls():
    left, right = sides("every_probe_row_has_one_candidate")
    got, counts = moved(lambda: run_left_join(left, right, None, build_left=True))
    assert got == expected(left, right, None)
    assert counts["join_outer_side.build"] == 1 and counts["join_outer_side.probe"] == 0
    assert counts["join_outer_build_rows"] == 48
    assert counts["join_outer_unmatched_rows"] == len([r for r in got if r[2] is None])
    assert counts["join_outer_unmatched_rows"] >= 16          # keys 32..47 at least
    # a unique build key and a dense probe: the inner join's fanout-one
    # form, no general expansion
    assert counts["join_expand_launches.fanout1"] == 2
    assert counts["join_expand_launches.general"] == 0
    # the other way round the probe's rows leave with their batches, and
    # the same readback says how many of them went out with NULLs
    unmatched = counts["join_outer_unmatched_rows"]
    _, counts = moved(lambda: run_left_join(left, right, None, build_left=False))
    assert counts["join_outer_side.probe"] == 1 and counts["join_outer_side.build"] == 0
    assert counts["join_outer_build_rows"] == 128
    assert counts["join_outer_unmatched_rows"] == unmatched


def test_the_unmatched_rows_are_packed_where_they_are_few(monkeypatch):
    """The build rows that go out with NULLs take the power of two that
    holds them where that is at most half the lookup's slots."""
    seen = []
    real = O._unmatched_build_rows

    def spy(schema, build, flags, unmatched):
        out = real(schema, build, flags, unmatched)
        seen.append((build.capacity, unmatched, out.capacity))
        return out

    monkeypatch.setattr(O, "_unmatched_build_rows", spy)
    left = [[(k, 0) for k in range(200)]]
    right = [[(k, 1) for k in range(190)]]
    got = run_left_join(left, right, None, build_left=True)
    assert got == expected(left, right, None)
    assert seen == [(200, 10, 16)]
    seen.clear()
    run_left_join(left, [[(k, 1) for k in range(10)]], None, build_left=True)
    assert seen == [(200, 190, 200)]


def test_build_preserved_is_not_an_inner_or_a_full_joins():
    for kind in ("inner", "full"):
        with pytest.raises(ValueError):
            O.LookupJoinOperator(O.JoinBridge(), [0], kind, SCHEMA, build_preserved=True)


# -- the plan and the statements, against sqlite ------------------------------------------


def random_tables(case):
    """{table: [(k, v)]} of two tables `a` (smaller) and `b`, seeded:
    duplicate keys on both sides, NULL keys, unmatched rows on both."""
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    if case == "an_empty_null_supplying_side":
        return {"a": rng_rows(rng, 20, 10, 4, nulls=0.1), "b": []}
    if case == "an_empty_preserved_side":
        return {"a": [], "b": rng_rows(rng, 30, 10, 4)}
    if case == "unique_preserved_keys":
        return {"a": [(k, int(rng.integers(4))) for k in range(60)],
                "b": [(int(k), int(rng.integers(4))) for k in rng.integers(0, 90, 200)]}
    return {"a": rng_rows(rng, 50, 30, 4, nulls=0.1),
            "b": rng_rows(rng, 120, 40, 4, nulls=0.1)}


def memory_runner(tables, batch_rows=32):
    from trino_tpu.connectors.memory import create_memory_connector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.engine import LocalQueryRunner, Session

    mem = create_memory_connector()
    for name, rows in tables.items():
        k = np.asarray([0 if r[0] is None else r[0] for r in rows], dtype=np.int64)
        valid = np.asarray([r[0] is not None for r in rows], dtype=bool)
        v = np.asarray([r[1] for r in rows], dtype=np.int64)
        mem.load_table("s", name, [ColumnMetadata("k", T.BIGINT), ColumnMetadata("v", T.BIGINT)],
                       [k, v], [valid, None], [None, None])
    runner = LocalQueryRunner(Session(catalog="memory", schema="s", batch_rows=batch_rows))
    runner.register_catalog("memory", mem)
    return runner


def sqlite_rows(tables, sql):
    db = sqlite3.connect(":memory:")
    for name, rows in tables.items():
        db.execute(f"create table {name} (k integer, v integer)")
        db.executemany(f"insert into {name} values (?, ?)", rows)
    return [tuple(r) for r in db.execute(sql).fetchall()]


def forced(build_left, spill=False):
    """The optimizer's last pass with every LEFT join's side forced."""
    from trino_tpu.sql import optimizer as Opt

    def force(node, stats):
        node = Opt.with_children(node, [force(c, stats) for c in node.children()])
        if isinstance(node, P.JoinNode) and node.kind == "left" and node.left_keys:
            return dataclasses.replace(node, build_left=build_left, spill_build=spill)
        return node

    return force


STATEMENTS = {
    "no_filter": "select a.k, a.v, b.k, b.v from {l} a left join {r} b on a.k = b.k",
    "filter_on_the_null_supplying_side":
        "select a.k, a.v, b.k, b.v from {l} a left join {r} b on a.k = b.k and b.v <> 2",
    "filter_on_the_preserved_side":
        "select a.k, a.v, b.k, b.v from {l} a left join {r} b on a.k = b.k and a.v <> 2",
    "filter_on_both": "select a.k, a.v, b.k, b.v from {l} a left join {r} b "
                      "on a.k = b.k and a.v <> b.v",
    "counts_of_a_nullable_column":
        "select a.k, count(b.k), count(*), count(b.v) from {l} a left join {r} b "
        "on a.k = b.k and b.v <> 1 group by a.k",
    "a_right_join": "select a.k, a.v, b.k, b.v from {r} b right join {l} a on a.k = b.k "
                    "and b.v <> 2",
}


@pytest.mark.parametrize("spill", [False, True], ids=["in_memory", "spilled"])
@pytest.mark.parametrize("statement", list(STATEMENTS))
@pytest.mark.parametrize("case", ["random", "unique_preserved_keys",
                                  "an_empty_null_supplying_side", "an_empty_preserved_side"])
def test_both_plans_answer_what_sqlite_answers(monkeypatch, case, statement, spill):
    from trino_tpu.sql import optimizer as Opt

    tables = random_tables(case)
    for l, r in (("a", "b"), ("b", "a")):
        sql = STATEMENTS[statement].format(l=l, r=r)
        want = sqlite_rows(tables, sql)
        for build_left in (False, True):
            monkeypatch.setattr(Opt, "_with_semi_join_sides", forced(build_left, spill))
            runner = memory_runner(tables)
            text = runner.execute("explain " + sql).rows[0][0]
            assert ("build=left" in text) == build_left
            (rows, counts) = moved(lambda: runner.execute(sql).rows)
            assert_rows_match(rows, want, ordered=False)
            assert counts["join_outer_side.build"] == int(build_left)
            assert counts["join_outer_side.probe"] == int(not build_left)


def test_count_of_a_column_is_0_and_count_star_is_1_for_an_unmatched_row(monkeypatch):
    from trino_tpu.sql import optimizer as Opt

    tables = {"a": [(1, 0), (2, 0), (3, 0)], "b": [(1, 5), (1, 6), (3, 2)]}
    sql = ("select a.k, count(b.k), count(*) from a left join b on a.k = b.k and b.v <> 2 "
           "group by a.k order by a.k")
    for build_left in (False, True):
        monkeypatch.setattr(Opt, "_with_semi_join_sides", forced(build_left))
        rows = memory_runner(tables).execute(sql).rows
        assert [list(r) for r in rows] == [[1, 2, 2], [2, 0, 1], [3, 0, 1]]


@pytest.fixture(scope="module")
def runner():
    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import LocalQueryRunner, Session

    r = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    r.register_catalog("tpch", create_tpch_connector())
    return r


def explain(runner, sql):
    return runner.execute("explain " + sql).rows[0][0]


def joins_of(text):
    return [line.strip() for line in text.splitlines() if line.strip().startswith("Join ")]


SMALL_LEFT = "select n_name, l_orderkey, l_linenumber from nation left join lineitem " \
             "on n_nationkey = l_suppkey"
LARGE_LEFT = "select count(*), count(n_name) from lineitem left join nation " \
             "on n_nationkey = l_suppkey"


def test_the_preserved_side_is_built_exactly_where_the_other_is_estimated_larger(runner):
    (line,) = joins_of(explain(runner, SMALL_LEFT))
    assert line.startswith("Join left ") and line.endswith(" build=left")
    (line,) = joins_of(explain(runner, LARGE_LEFT))
    assert line.startswith("Join left ") and "build=left" not in line
    for sql in (SMALL_LEFT, LARGE_LEFT):
        (rows, counts) = moved(lambda: runner.execute(sql).rows)
        assert_rows_match(rows, oracle_rows(0.01, to_sqlite(sql)), ordered=False)
        assert counts["join_outer_side.build"] == int(sql is SMALL_LEFT)
        assert counts["join_outer_side.probe"] == int(sql is LARGE_LEFT)
    # the 25 nations are the lookup, all of them come out of it matched
    # or not, and the filter in front of the probe is counted apart
    (_, counts) = moved(lambda: runner.execute(SMALL_LEFT).rows)
    assert counts["join_outer_build_rows"] == 25
    assert counts["df_reverse_rows_in"] == 60064


def test_the_decision_is_the_estimates_alone():
    from trino_tpu.sql import optimizer as Opt
    from trino_tpu.sql.stats import PlanStats, StatsCalculator

    fa, fb = (P.Field("a", T.BIGINT),), (P.Field("b", T.BIGINT),)
    plan = P.JoinNode("left", P.ValuesNode(fa, ()), P.ValuesNode(fb, ()), (0,), (0,), None,
                      fa + fb)

    class Fixed(StatsCalculator):
        def __init__(self, left, right):
            super().__init__(None)
            self.rows = {id(plan.left): left, id(plan.right): right}

        def stats(self, node):
            return PlanStats(self.rows[id(node)])

    assert Opt._with_semi_join_sides(plan, Fixed(10.0, 11.0)).build_left is True
    assert Opt._with_semi_join_sides(plan, Fixed(11.0, 10.0)).build_left is False
    assert Opt._with_semi_join_sides(plan, Fixed(10.0, 10.0)).build_left is False
    keyless = dataclasses.replace(plan, left_keys=(), right_keys=())
    assert Opt._with_semi_join_sides(keyless, Fixed(10.0, 11.0)).build_left is False
    for kind in ("inner", "full"):
        other = dataclasses.replace(plan, kind=kind)
        assert Opt._with_semi_join_sides(other, Fixed(10.0, 11.0)).build_left is False


def test_q13_filters_and_counts_the_orders_under_the_join(runner, monkeypatch):
    text = explain(runner, QUERIES[13])
    lines = [line.strip() for line in text.splitlines()]
    (join,) = joins_of(text)
    assert join == "Join left L[0]=R[0]"                      # no +residual
    at = lines.index(join)
    assert lines[at - 1].startswith("Project ") and "coalesce(" in lines[at - 1]
    assert lines[at - 2] == "Aggregate keys=[0] aggs=['sum']"
    assert lines[at + 1].startswith("Scan tpch.tiny.customer ")
    assert lines[at + 2] == "Aggregate keys=[1] aggs=['count']"
    assert lines[at + 3].startswith("Filter not(like(") and "special%requests" in lines[at + 3]
    assert lines[at + 4].startswith("Scan tpch.tiny.orders ")
    (rows, counts) = moved(lambda: runner.execute(QUERIES[13]).rows)
    assert_rows_match(rows, oracle_rows(0.01, to_sqlite(QUERIES[13])), ordered=True)
    assert [list(r) for r in rows][0] == [0, 500]
    # 1,500 customers probe the 1,000 that have an order left to count
    assert counts["join_outer_side.probe"] == 1 and counts["join_outer_side.build"] == 0
    assert counts["join_outer_build_rows"] == 1000
    assert counts["join_outer_unmatched_rows"] == 500
    # with the aggregation left over the join the filter still goes under
    # it, and the customers, the smaller side, are the lookup
    from trino_tpu.sql import optimizer as Opt

    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import LocalQueryRunner, Session

    monkeypatch.setattr(Opt, "_with_aggregates_under_left_joins", lambda node, stats: node)
    fresh = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    fresh.register_catalog("tpch", create_tpch_connector())
    text = explain(fresh, QUERIES[13])
    lines = [line.strip() for line in text.splitlines()]
    (join,) = joins_of(text)
    assert join == "Join left L[0]=R[1] build=left"
    at = lines.index(join)
    assert lines[at + 2].startswith("Filter not(like(")
    (rows, counts) = moved(lambda: fresh.execute(QUERIES[13]).rows)
    assert [list(r) for r in rows][0] == [0, 500]
    assert counts["join_outer_side.build"] == 1
    assert counts["join_outer_build_rows"] == 1500
    assert counts["join_outer_unmatched_rows"] == 500
    # o_custkey arrives in no order: the batches pay their key sort
    assert counts["agg_unordered_input.batches"] >= 1
    assert (counts["agg_unordered_input.batches"] + counts["agg_ordered_input.batches"]
            == counts["agg_ingest_path.sort"])


def test_a_condition_on_the_preserved_side_stays_on_the_pairs(runner):
    sql = ("select n_name, r_name from nation left join region "
           "on n_regionkey = r_regionkey and n_name like 'A%' and r_name <> 'ASIA'")
    text = explain(runner, sql)
    (join,) = joins_of(text)
    assert join.startswith("Join left ") and "+residual" in join
    lines = [line.strip() for line in text.splitlines()]
    at = lines.index(join)
    assert lines[at + 1].startswith("Scan tpch.tiny.nation")
    assert lines[at + 2].startswith("Filter ") and "ASIA" in lines[at + 2]
    assert "like" not in lines[at + 2]
    rows = runner.execute(sql).rows
    assert len(rows) == 25                                    # every nation, once
    assert_rows_match(rows, oracle_rows(0.01, to_sqlite(sql)), ordered=False)


def test_the_rule_moves_only_what_reads_the_null_supplying_side_alone():
    from trino_tpu.sql import optimizer as Opt

    f = tuple(P.Field(f"c{i}", T.BIGINT) for i in range(2))
    a, b = P.ValuesNode(f, ()), P.ValuesNode(f, ())
    right_only = ir.Call("ne", (ir.InputRef(3, T.BIGINT), ir.Literal(2, T.BIGINT)), T.BOOLEAN)
    left_only = ir.Call("ne", (ir.InputRef(1, T.BIGINT), ir.Literal(2, T.BIGINT)), T.BOOLEAN)
    both = ir.Call("ne", (ir.InputRef(1, T.BIGINT), ir.InputRef(3, T.BIGINT)), T.BOOLEAN)
    join = P.JoinNode("left", a, b, (0,), (0,), ir.and_(right_only, left_only, both), f + f)
    out = Opt.IterativeOptimizer((Opt.PushOuterJoinConditionToNullSide(),)).optimize(
        join, Opt.StatsCalculator(None))
    assert isinstance(out, P.JoinNode) and isinstance(out.left, P.ValuesNode)
    assert isinstance(out.right, P.FilterNode)
    assert out.right.predicate == Opt.shift_refs(right_only, -2)
    assert out.residual == ir.and_(left_only, both)
    for kind in ("inner", "semi", "anti", "full"):
        same = Opt.IterativeOptimizer((Opt.PushOuterJoinConditionToNullSide(),)).optimize(
            dataclasses.replace(join, kind=kind,
                                fields=f if kind in ("semi", "anti") else f + f),
            Opt.StatsCalculator(None))
        assert not isinstance(same.right, P.FilterNode)


# -- an aggregation of the null-supplying side goes under the join ---------------------------


def many_a_key():
    """`b` holds 6 rows a key on average, `a` some keys twice, NULL keys
    and keys `b` has not."""
    rng = np.random.default_rng(43)
    return {"a": rng_rows(rng, 40, 30, 4, nulls=0.1) + [(3, 1), (3, 2), (29, 0)],
            "b": rng_rows(rng, 120, 20, 50, nulls=0.05)}


UNDER = ("select a.k, count(b.v), sum(b.v), min(b.v), max(b.v), count(b.k) "
         "from a left join b on a.k = b.k {on} group by a.k")


def under_the_join(text):
    """Whether the plan aggregates the left join's right side under it."""
    lines = [line.strip() for line in text.splitlines()]
    at = next(i for i, line in enumerate(lines) if line.startswith("Join left "))
    return lines[at + 2].startswith("Aggregate keys=[0] ")


@pytest.mark.parametrize("on", ["", "and b.v <> 7"], ids=["no_filter", "filter_on_b"])
def test_the_null_supplying_sides_aggregates_are_taken_under_the_join(on):
    tables = many_a_key()
    sql = UNDER.format(on=on)
    runner = memory_runner(tables)
    text = runner.execute("explain " + sql).rows[0][0]
    assert under_the_join(text) and "+residual" not in text
    lines = [line.strip() for line in text.splitlines()]
    at = next(i for i, line in enumerate(lines) if line.startswith("Join left "))
    assert lines[at - 2].startswith(
        "Aggregate keys=[0] aggs=['sum', 'sum', 'min', 'max', 'sum']")
    assert lines[at - 1].count("coalesce(") == 2             # the two counts
    assert_rows_match(runner.execute(sql).rows, sqlite_rows(tables, sql), ordered=False)
    # a key the preserved side holds several times is one group of so
    # many times its rows
    held = len([1 for k, _ in tables["a"] if k == 3])
    counted = len([1 for k, v in tables["b"] if k == 3 and (not on or v != 7)])
    assert held >= 2 and counted >= 1
    assert {r[0]: r for r in runner.execute(sql).rows}[3][1] == held * counted


@pytest.mark.parametrize("sql", [
    UNDER.format(on="and a.v <> b.v"),                                  # a residual
    "select a.k, avg(b.v) from a left join b on a.k = b.k group by a.k",
    "select a.k, count(*) from a left join b on a.k = b.k group by a.k",
    "select a.k, count(distinct b.v) from a left join b on a.k = b.k group by a.k",
    "select b.v, count(b.k) from a left join b on a.k = b.k group by b.v",
    "select a.k, sum(a.v), count(b.k) from a left join b on a.k = b.k group by a.k",
    "select a.k, count(b.k) from a join b on a.k = b.k group by a.k",   # not an outer join
], ids=["residual", "avg", "count_star", "distinct", "grouped_by_the_other_side",
        "an_argument_of_the_preserved_side", "inner"])
def test_what_the_split_would_not_compute_exactly_keeps_its_plan(sql):
    tables = many_a_key()
    runner = memory_runner(tables)
    text = runner.execute("explain " + sql).rows[0][0]
    lines = [line.strip() for line in text.splitlines()]
    at = next(i for i, line in enumerate(lines) if line.startswith("Join "))
    assert not lines[at + 2].startswith("Aggregate ")
    assert_rows_match(runner.execute(sql).rows, sqlite_rows(tables, sql), ordered=False)


def test_the_split_is_the_estimates_alone():
    """One row a key on the null-supplying side: the aggregation under
    the join would hand on as many rows as it read."""
    from trino_tpu.sql import optimizer as Opt
    from trino_tpu.sql.stats import PlanStats, StatsCalculator

    fa, fb = (P.Field("a", T.BIGINT),), (P.Field("b", T.BIGINT), P.Field("v", T.BIGINT))
    join = P.JoinNode("left", P.ValuesNode(fa, ()), P.ValuesNode(fb, ()), (0,), (0,), None,
                      fa + fb)
    agg = P.AggregateNode(join, (0,), (P.AggCall("count", 2, T.BIGINT),),
                          (fa[0], P.Field("n", T.BIGINT)))

    class Fixed(StatsCalculator):
        def __init__(self, rows, groups):
            super().__init__(None)
            self.rows, self.groups = rows, groups

        def stats(self, node):
            return PlanStats(self.groups if isinstance(node, P.AggregateNode) else self.rows)

    assert Opt._with_aggregates_under_left_joins(agg, Fixed(100.0, 100.0)) is agg
    assert Opt._with_aggregates_under_left_joins(agg, Fixed(100.0, 51.0)) is agg
    out = Opt._with_aggregates_under_left_joins(agg, Fixed(100.0, 50.0))
    assert out is not agg and out.fields == agg.fields
    assert out.aggs == (P.AggCall("sum", 1, T.BIGINT),)
    assert isinstance(out.child.child.right, P.AggregateNode)
    assert out.child.child.right.group_channels == (0,)
    # what came out is not split again
    assert Opt._with_aggregates_under_left_joins(out, Fixed(100.0, 10.0)) == out


# -- the mesh plane plans what it did --------------------------------------------------------


def test_the_mesh_plane_takes_a_left_join_whatever_side_the_local_plan_builds():
    from trino_tpu.parallel import mesh_plan

    f = (P.Field("k", T.BIGINT),)
    for build_left in (False, True):
        node = P.JoinNode("left", P.ValuesNode(f, ()), P.ValuesNode(f, ()), (0,), (0,), None,
                          f + f, build_left=build_left)
        mesh_plan._check_node(node)
