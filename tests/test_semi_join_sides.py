"""Which side a semi- or anti-join builds (issue 40): the plan's
`JoinNode.build_left`, decided from the two sides' estimated rows, the
operator that flags the rows of a build side it preserves, the filter of
the build side's keys in front of the side that filters, and the
projections that keep both sides narrow. CPU counts and answers only;
what any of it costs is a chip reading (PERF.md section 6, PR 40)."""

import itertools
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from tests.oracle import assert_rows_match, oracle_rows
from tests.test_tpch import to_sqlite
from tests.tpch_queries import QUERIES
from trino_tpu import types as T
from trino_tpu.block import Column, RelBatch
from trino_tpu.exec import operators as O
from trino_tpu.expr import ir
from trino_tpu.expr.compile import ExprBinder
from trino_tpu.runtime.metrics import METRICS
from trino_tpu.sql import plan as P

SCHEMA = [(T.BIGINT, None), (T.BIGINT, None)]      # (key, payload)
COUNTERS = ("join_semi_side.source", "join_semi_side.filtering",
            "join_expand_launches.first", "join_expand_launches.general",
            "join_expand_launches.fanout1", "semi_pairs_seen", "semi_pairs_kept",
            "semi_build_rows", "semi_build_flagged", "df_reverse_rows_in",
            "df_reverse_rows_kept")


def batch(rows, capacity=None):
    """[(key | None, payload)] as a batch; `capacity` pads it with dead slots."""
    n = len(rows)
    cap = max(capacity or n, 16)
    keys = np.zeros(cap, dtype=np.int64)
    valid = np.zeros(cap, dtype=bool)
    payload = np.zeros(cap, dtype=np.int64)
    for i, (k, p) in enumerate(rows):
        keys[i], valid[i], payload[i] = (0 if k is None else k), k is not None, p
    live = np.arange(cap) < n
    return RelBatch([Column(T.BIGINT, jnp.asarray(keys), jnp.asarray(valid), None),
                     Column(T.BIGINT, jnp.asarray(payload), None, None)], jnp.asarray(live))


def drain(op):
    out = []
    while (b := op.get_output()) is not None:
        out.append(b)
    return out


def payload_differs():
    """`source.payload <> filtering.payload` over the pair schema."""
    e = ir.Call("ne", (ir.InputRef(1, T.BIGINT), ir.InputRef(3, T.BIGINT)), T.BOOLEAN)
    return O.make_residual_fn(ExprBinder([t for t, _ in SCHEMA * 2], [None] * 4).bind(e))


def run_join(kind, source, filtering, residual, build_source, with_filter=False,
             spill=False):
    """The source rows a semi- or anti-join lets through, sorted, with
    the side built that `build_source` says. `source` and `filtering`
    are lists of batches' rows. `spill`: the build side goes to its
    grace partitions on disk and the probe runs a partition at a time."""
    bridge = O.JoinBridge()
    built, probing = (source, filtering) if build_source else (filtering, source)
    sink = O.HashBuildSink(bridge, [0], SCHEMA, force_spill=spill)
    for rows in built:
        sink.add_input(batch(rows))
    sink.finish()
    fn = payload_differs() if residual else None
    unread = (0, 2) if residual else (0, 1, 2, 3)
    join = O.LookupJoinOperator(bridge, [0], kind, SCHEMA, residual_fn=fn,
                                build_preserved=build_source,
                                unread=unread if build_source else ())
    ops = [join]
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
    rows = [tuple(r) for b in out for r in b.to_pylists()]
    return sorted(rows, key=lambda r: (r[0] is None, r))


def expected(kind, source, filtering, residual):
    f = [r for rows in filtering for r in rows if r[0] is not None]
    out = []
    for k, p in (r for rows in source for r in rows):
        hit = k is not None and any(fk == k and (not residual or fp != p) for fk, fp in f)
        if hit == (kind == "semi"):
            out.append((k, p))
    return sorted(out, key=lambda r: (r[0] is None, r))


def rng_rows(rng, n, keys, payloads, nulls=0.0):
    return [(None if rng.random() < nulls else int(rng.integers(keys)),
             int(rng.integers(payloads))) for _ in range(n)]


def sides(case):
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    if case == "null_keys_on_the_source":
        return [rng_rows(rng, 40, 12, 3, nulls=0.3)], [rng_rows(rng, 60, 12, 3)]
    if case == "null_keys_on_the_filtering_side":
        return [rng_rows(rng, 40, 12, 3)], [rng_rows(rng, 60, 12, 3, nulls=0.3)]
    if case == "repeated_keys_on_both_sides":
        return [rng_rows(rng, 64, 5, 2)], [rng_rows(rng, 64, 5, 2)]
    if case == "an_empty_source":
        return [[]], [rng_rows(rng, 30, 8, 3)]
    if case == "an_empty_filtering_side":
        return [rng_rows(rng, 30, 8, 3)], [[]]
    if case == "several_batches_a_side":
        return ([rng_rows(rng, 32, 40, 3) for _ in range(3)],
                [rng_rows(rng, 64, 40, 3) for _ in range(4)])
    if case == "a_source_row_matched_only_by_a_later_batch":
        return [[(7, 1), (8, 1), (9, 1)]], [[(8, 1), (1, 1)], [(8, 2)], [(7, 1)], [(7, 5), (9, 1)]]
    if case == "most_probe_rows_have_a_candidate":
        # every filtering row finds one source row at least, some two or
        # three: the first candidates and the rest take their two forms
        return ([[(k, k % 3) for k in range(20)] + [(k, 1) for k in range(0, 20, 4)]
                 + [(k, 2) for k in range(0, 20, 8)]],
                [rng_rows(rng, 64, 20, 3), rng_rows(rng, 64, 20, 3)])
    if case == "few_probe_rows_have_a_candidate":
        return [[(1000 + k, k % 2) for k in range(6)]], [
            rng_rows(rng, 120, 1000, 2) + [(1003, 0), (1003, 1), (1005, 1)]]
    raise AssertionError(case)


CASES = ["null_keys_on_the_source", "null_keys_on_the_filtering_side",
         "repeated_keys_on_both_sides", "an_empty_source", "an_empty_filtering_side",
         "several_batches_a_side", "a_source_row_matched_only_by_a_later_batch",
         "most_probe_rows_have_a_candidate", "few_probe_rows_have_a_candidate"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("residual", [False, True], ids=["no_residual", "residual"])
@pytest.mark.parametrize("kind", ["semi", "anti"])
def test_either_side_built_gives_the_same_rows(kind, residual, case):
    source, filtering = sides(case)
    want = expected(kind, source, filtering, residual)
    assert run_join(kind, source, filtering, residual, build_source=False) == want
    assert run_join(kind, source, filtering, residual, build_source=True) == want
    assert run_join(kind, source, filtering, residual, build_source=True,
                    with_filter=True) == want


def grace_sides(case):
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    if case == "partitions_without_a_row_of_either_side":
        # three keys: most of the partitions hold nothing, and key 2 is
        # the source's alone, so its partition has no filtering page
        return [[(0, 1), (1, 1), (2, 1), (None, 4)]], [[(0, 1), (0, 2)], [(1, 1), (None, 3)]]
    if case == "matched_only_by_a_later_batch_of_its_partition":
        return ([[(7, 1), (8, 1), (9, 1)], [(7, 5), (107, 1)]],
                [[(8, 1), (1, 1)], [(8, 2), (107, 1)], [(7, 1)], [(7, 5), (9, 1)]])
    if case == "many_keys_in_every_partition":
        return ([rng_rows(rng, 48, 200, 3, nulls=0.1) for _ in range(3)],
                [rng_rows(rng, 64, 200, 3, nulls=0.1) for _ in range(4)])
    raise AssertionError(case)


@pytest.mark.parametrize("case", ["partitions_without_a_row_of_either_side",
                                  "matched_only_by_a_later_batch_of_its_partition",
                                  "many_keys_in_every_partition"])
@pytest.mark.parametrize("residual", [False, True], ids=["no_residual", "residual"])
@pytest.mark.parametrize("kind", ["semi", "anti"])
def test_a_spilled_preserved_side_answers_a_partition_at_a_time(kind, residual, case):
    """The grace path under `build_preserved`: every partition flags its
    own build rows and emits them, an anti-join's partitions without a
    filtering page too, and the flags start anew with each."""
    source, filtering = grace_sides(case)
    want = expected(kind, source, filtering, residual)
    assert run_join(kind, source, filtering, residual, build_source=False) == want
    (got, counts) = moved(lambda: run_join(kind, source, filtering, residual,
                                           build_source=True, spill=True))
    assert got == want
    pairs = [(s, f) for rows in source for s in rows for page in filtering for f in page
             if s[0] is not None and s[0] == f[0]]
    assert counts["semi_pairs_seen"] == len(pairs)
    assert counts["semi_pairs_kept"] == len([1 for s, f in pairs
                                             if not residual or s[1] != f[1]])
    # every partition that emitted counted its own build rows, once
    assert counts["semi_build_flagged"] == (len(got) if kind == "semi" else
                                            sum(len(r) for r in source) - len(got))
    assert run_join(kind, source, filtering, residual, build_source=False, spill=True) == want


def test_the_spilled_partitions_flags_do_not_leak_into_the_next(monkeypatch):
    """Two keys that land in different partitions: the first's flagged
    slot must not read as the second's (the flags are a partition's)."""
    seen = []
    real = O.LookupJoinOperator._emit_preserved

    def spy(self, build):
        seen.append((build.capacity, self._build_matched is not None))
        real(self, build)
        assert self._build_matched is None and self._pair_totals is None

    monkeypatch.setattr(O.LookupJoinOperator, "_emit_preserved", spy)
    source = [[(k, 1) for k in range(64)]]
    filtering = [[(k, 2) for k in range(0, 64, 2)]]
    got = run_join("anti", source, filtering, True, build_source=True, spill=True)
    assert got == [(k, 1) for k in range(1, 64, 2)]
    assert len(seen) > 1 and any(flagged for _, flagged in seen)


@pytest.mark.parametrize("key_fill, path", [(None, "range"), (0.9, "range"), (0.02, "bits")],
                         ids=["the_plan_cannot_say", "planned_full", "planned_sparse"])
@pytest.mark.parametrize("reverse", [False, True], ids=["forward", "reverse"])
def test_a_large_build_side_takes_the_bits_on_the_plans_word_alone(
        monkeypatch, reverse, key_fill, path):
    """A build side of more slots than DF_BITS_MAX_SLOTS: where the plan
    expects it to fill its key's range, or cannot say, the filter keeps
    the range and reads nothing back (TPC-H Q18's customers); where the
    plan expects it sparse, the domain is read once and the bits taken,
    up to DF_BITS_PLANNED_MAX_SLOTS (TPC-H Q21's late lines)."""
    slots = 1 << 10
    monkeypatch.setattr(O, "DF_SET_MAX_SLOTS", slots // 8)
    monkeypatch.setattr(O, "DF_BITS_MAX_SLOTS", slots // 2)
    monkeypatch.setattr(O, "DF_BITS_PLANNED_MAX_SLOTS", slots)
    sites = []
    real = O.host_sync

    def sync(site, nbytes=0):
        sites.append(site)
        return real(site, nbytes)

    monkeypatch.setattr(O, "host_sync", sync)
    bridge = O.JoinBridge()
    sink = O.HashBuildSink(bridge, [0], SCHEMA)
    sink.add_input(batch([(k * 7, 0) for k in range(slots)]))
    sink.finish()
    df = O.DynamicFilterOperator(bridge, [0], reverse=reverse, key_fill=key_fill)
    df.add_input(batch([(k, 0) for k in range(200)]))
    df.finish()
    kept = sum(int(np.asarray(b.live_mask()).sum()) for b in drain(df))
    assert df._path == path
    assert sites.count("join.dynamic_filter_domain") == int(path == "bits")
    assert kept == (len(range(0, 200, 7)) if path == "bits" else 200)
    # the plan's word opens the larger limit and no more
    monkeypatch.setattr(O, "DF_BITS_PLANNED_MAX_SLOTS", slots // 2)
    larger = O.DynamicFilterOperator(bridge, [0], reverse=reverse, key_fill=key_fill)
    larger.add_input(batch([(k, 0) for k in range(200)]))
    assert larger._path == "range"
    # and at or under DF_BITS_MAX_SLOTS the domain is read whatever it says
    monkeypatch.setattr(O, "DF_BITS_MAX_SLOTS", slots)
    monkeypatch.setattr(O, "DF_BITS_PLANNED_MAX_SLOTS", 4 * slots)
    small = O.DynamicFilterOperator(bridge, [0], reverse=reverse, key_fill=key_fill)
    small.add_input(batch([(k, 0) for k in range(200)]))
    assert small._path == "bits"


def dynamic_filters(runner, sql):
    """The statement's `DynamicFilterOperator`s as the planner makes them."""
    from trino_tpu.sql import parser

    filters = []
    real = O.DynamicFilterOperator.__init__

    def spy(self, *args, **kwargs):
        real(self, *args, **kwargs)
        filters.append(self)

    O.DynamicFilterOperator.__init__ = spy
    try:
        runner.execute(sql)
    finally:
        O.DynamicFilterOperator.__init__ = real
    del parser
    return filters


def test_the_planner_hands_each_filter_its_build_sides_estimated_fill(runner):
    # every customer is built: a key range as wide as its rows
    (df,) = dynamic_filters(runner, "select count(*) from orders join customer "
                                    "on o_custkey = c_custkey")
    assert df._key_fill == pytest.approx(1.0, rel=0.01) and not df._reverse
    # Q21: the late lines of one nation's suppliers are a sliver of
    # l_orderkey's range, in front of `orders` and of both subqueries' scans
    fills = [(d._reverse, d._key_fill) for d in dynamic_filters(runner, QUERIES[21])]
    assert sorted(r for r, _ in fills) == [False, False, False, True, True]
    sparse = [f for _, f in fills if f is not None and f < O.DF_BITS_MAX_FILL]
    assert len(sparse) >= 3 and all(f is not None for r, f in fills if r)
    # two keys: the plan says nothing
    (df,) = dynamic_filters(runner, "select count(*) from lineitem join partsupp "
                                    "on l_partkey = ps_partkey and l_suppkey = ps_suppkey")
    assert df._key_fill is None


def moved(fn):
    before = {n: METRICS.counter(n) for n in COUNTERS}
    out = fn()
    return out, {n: METRICS.counter(n) - v for n, v in before.items()}


def test_the_counters_say_which_side_was_built_and_what_the_pairs_came_to():
    source, filtering = sides("most_probe_rows_have_a_candidate")
    pairs = [(s, f) for s in source[0] for rows in filtering for f in rows if s[0] == f[0]]
    kept = [1 for s, f in pairs if s[1] != f[1]]
    got, counts = moved(lambda: run_join("semi", source, filtering, True, build_source=True))
    assert counts["join_semi_side.source"] == 1 and counts["join_semi_side.filtering"] == 0
    assert counts["semi_pairs_seen"] == len(pairs) and counts["semi_pairs_kept"] == len(kept)
    assert counts["semi_build_rows"] == len(source[0])
    assert counts["semi_build_flagged"] == len(got)
    # two batches, each: every row's first candidate, then the rest
    assert counts["join_expand_launches.first"] == 2
    assert counts["join_expand_launches.general"] == 2
    _, counts = moved(lambda: run_join("anti", source, filtering, True, build_source=False))
    assert counts["join_semi_side.filtering"] == 1 and counts["join_semi_side.source"] == 0
    assert counts["semi_pairs_seen"] == 0 and counts["join_expand_launches.first"] == 0


def test_a_sparse_batch_takes_the_forms_a_dense_one_takes():
    source, filtering = sides("few_probe_rows_have_a_candidate")
    _, counts = moved(lambda: run_join("semi", source, filtering, True, build_source=True))
    # three of 123 rows have a candidate, one each: nothing after the first
    assert counts["join_expand_launches.first"] == 1
    assert counts["join_expand_launches.general"] == 0 and counts["semi_pairs_seen"] == 3
    twice = [source[0] + [(1003, 5)]]
    got, counts = moved(lambda: run_join("semi", twice, filtering, True, build_source=True))
    assert counts["join_expand_launches.first"] == 1
    assert counts["join_expand_launches.general"] == 1 and counts["semi_pairs_seen"] == 5
    assert got == expected("semi", twice, filtering, True)


def test_the_reverse_filter_counts_what_it_kept_apart():
    source, filtering = sides("few_probe_rows_have_a_candidate")
    _, counts = moved(lambda: run_join("semi", source, filtering, False, build_source=True,
                                       with_filter=True))
    assert counts["df_reverse_rows_in"] == len(filtering[0])
    assert counts["df_reverse_rows_kept"] == 3


def test_a_batch_without_a_candidate_launches_nothing():
    _, counts = moved(lambda: run_join("anti", [[(1, 1), (2, 2)]], [[(5, 1)], [(6, 1)]],
                                       True, build_source=True))
    assert counts["join_expand_launches.first"] + counts["join_expand_launches.general"] == 0
    assert counts["semi_build_flagged"] == 0 and counts["semi_build_rows"] == 2


def test_build_preserved_is_a_semi_or_anti_joins():
    with pytest.raises(ValueError):
        O.LookupJoinOperator(O.JoinBridge(), [0], "inner", SCHEMA, build_preserved=True)


# -- the plan ---------------------------------------------------------------------------------


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


SMALL_FILTERS_LARGE = ("select count(*) from lineitem where {neg} exists "
                       "(select * from nation where n_nationkey = l_suppkey)")
LARGE_FILTERS_SMALL = ("select count(*) from nation where {neg} exists "
                       "(select * from lineitem where l_suppkey = n_nationkey)")


@pytest.mark.parametrize("neg, kind", [("", "semi"), ("not", "anti")])
def test_the_preserved_side_is_built_exactly_where_the_filtering_side_is_estimated_larger(
        runner, neg, kind):
    (line,) = joins_of(explain(runner, LARGE_FILTERS_SMALL.format(neg=neg)))
    assert line.startswith(f"Join {kind} ") and line.endswith(" build=left")
    (line,) = joins_of(explain(runner, SMALL_FILTERS_LARGE.format(neg=neg)))
    assert line.startswith(f"Join {kind} ") and "build=left" not in line


@pytest.mark.parametrize("neg", ["", "not"])
@pytest.mark.parametrize("sql", [SMALL_FILTERS_LARGE, LARGE_FILTERS_SMALL])
def test_both_plans_answer_what_the_oracle_answers(runner, sql, neg):
    text = sql.format(neg=neg)
    (rows, counts) = moved(lambda: runner.execute(text).rows)
    assert_rows_match(rows, oracle_rows(0.01, to_sqlite(text)), ordered=False)
    built_source = "build=left" in explain(runner, text)
    assert counts["join_semi_side.source"] == int(built_source)
    assert counts["join_semi_side.filtering"] == int(not built_source)


def test_the_decision_is_the_estimates_alone(runner):
    """The same statement over the same tables, the estimates turned
    round: the side flips, and nothing else in the session says which."""
    from trino_tpu.sql import optimizer as Opt
    from trino_tpu.sql.stats import PlanStats, StatsCalculator

    text = explain(runner, LARGE_FILTERS_SMALL.format(neg=""))
    plan = P.JoinNode("semi", P.ValuesNode((P.Field("a", T.BIGINT),), ()),
                      P.ValuesNode((P.Field("b", T.BIGINT),), ()), (0,), (0,), None,
                      (P.Field("a", T.BIGINT),))

    class Fixed(StatsCalculator):
        def __init__(self, left, right):
            super().__init__(None)
            self.rows = {id(plan.left): left, id(plan.right): right}

        def stats(self, node):
            return PlanStats(self.rows[id(node)])

    assert "build=left" in text
    assert Opt._with_semi_join_sides(plan, Fixed(10.0, 11.0)).build_left is True
    assert Opt._with_semi_join_sides(plan, Fixed(11.0, 10.0)).build_left is False
    assert Opt._with_semi_join_sides(plan, Fixed(10.0, 10.0)).build_left is False
    inner = P.JoinNode("inner", plan.left, plan.right, (0,), (0,), None,
                       plan.left.fields + plan.right.fields)
    assert Opt._with_semi_join_sides(inner, Fixed(10.0, 11.0)).build_left is False


def test_q21_builds_the_late_lines_twice_and_carries_three_columns(runner):
    text = explain(runner, QUERIES[21])
    plan = text.splitlines()
    joins = joins_of(text)
    assert joins[0].startswith("Join anti ") and joins[0].endswith("+residual build=left")
    assert joins[1].startswith("Join semi ") and joins[1].endswith("+residual build=left")
    at = next(i for i, line in enumerate(plan) if line.strip().startswith("Join semi"))
    # under the two subqueries: s_name, l_orderkey, l_suppkey of the joins' 36 columns
    assert plan[at + 1].strip().startswith("Project [") and plan[at + 1].count("$[") == 3
    # `select *` from lineitem reads the key and what the residual names
    scans = [line.strip() for line in plan if ".lineitem " in line]
    assert "Scan tpch.tiny.lineitem ['l_orderkey', 'l_suppkey']" in scans


@pytest.mark.parametrize("q", [4, 18, 20, 21, 22])
def test_the_specs_subquery_statements_still_answer(runner, q):
    rows, counts = moved(lambda: runner.execute(QUERIES[q]).rows)
    assert_rows_match(rows, oracle_rows(0.01, to_sqlite(QUERIES[q])),
                      ordered="order by" in QUERIES[q].lower())
    assert counts["join_semi_side.source"] + counts["join_semi_side.filtering"] >= 1


def test_q18s_semi_join_keeps_its_side_and_its_place(runner):
    text = explain(runner, QUERIES[18])
    (semi,) = [j for j in joins_of(text) if j.startswith("Join semi")]
    assert "build=left" not in semi
    plan = text.splitlines()
    at = next(i for i, line in enumerate(plan) if line.strip().startswith("Join semi"))
    assert ".orders " in plan[at + 1]


# -- the projections under a semi-join -----------------------------------------------------


def test_a_semi_join_pushed_under_a_projection_is_left_to_that_rule(runner):
    """`in (subquery)` without a residual on one key is carried down by
    push_semi_join_down; prune_semi_join_inputs must not lift it again
    (the two would chase each other to the pass limit)."""
    text = explain(runner, "select o_orderpriority from orders where o_orderkey in "
                           "(select l_orderkey from lineitem where l_quantity > 49)")
    assert text.count("Join semi") == 1


@pytest.mark.parametrize("kind", ["semi", "anti"])
def test_the_rule_narrows_both_sides_and_keeps_the_residual(kind):
    from trino_tpu.sql import optimizer as Opt

    wide = tuple(P.Field(f"c{i}", T.BIGINT) for i in range(5))
    left, right = P.ValuesNode(wide, ()), P.ValuesNode(wide, ())
    residual = ir.Call("ne", (ir.InputRef(3, T.BIGINT), ir.InputRef(5 + 4, T.BIGINT)), T.BOOLEAN)
    join = P.JoinNode(kind, left, right, (1,), (2,), residual, wide)
    top = P.ProjectNode(join, (ir.InputRef(0, T.BIGINT),), (wide[0],))
    out = Opt.IterativeOptimizer((Opt.PruneSemiJoinInputs(), Opt.InlineProjections(),
                                  Opt.RemoveIdentityProject())).optimize(top, None)
    assert isinstance(out, P.ProjectNode) and isinstance(out.child, P.JoinNode)
    j = out.child
    assert [repr(e) for e in j.left.exprs] == ["$[0:bigint]", "$[1:bigint]", "$[3:bigint]"]
    assert [repr(e) for e in j.right.exprs] == ["$[2:bigint]", "$[4:bigint]"]
    assert j.left_keys == (1,) and j.right_keys == (0,) and len(j.fields) == 3
    assert Opt.expr_refs(j.residual) == {2, 3 + 1}
    assert Opt.expr_refs(out.exprs[0]) == {0}


def test_unread_outputs_follow_an_anti_join_and_the_filtering_side():
    from trino_tpu.sql.local_planner import unread_join_outputs

    f = tuple(P.Field(f"c{i}", T.BIGINT) for i in range(3))
    a, b, c = (P.ValuesNode(f, ()) for _ in range(3))
    under_left = P.JoinNode("inner", a, b, (0,), (0,), None, f + f)
    under_right = P.JoinNode("inner", b, c, (0,), (0,), None, f + f)
    residual = ir.Call("ne", (ir.InputRef(4, T.BIGINT), ir.InputRef(6 + 5, T.BIGINT)), T.BOOLEAN)
    anti = P.JoinNode("anti", under_left, under_right, (1,), (2,), residual, f + f)
    top = P.ProjectNode(anti, (ir.InputRef(0, T.BIGINT),), (f[0],))
    unread = unread_join_outputs(top)
    assert unread[id(under_left)] == frozenset(range(6)) - {0, 1, 4}
    assert unread[id(under_right)] == frozenset(range(6)) - {2, 5}


# -- the mesh plane plans what it did --------------------------------------------------------


def test_the_mesh_plane_takes_a_join_whatever_side_the_local_plan_builds():
    from trino_tpu.parallel import mesh_plan

    f = (P.Field("k", T.BIGINT),)
    for build_left, kind in itertools.product((False, True), ("semi", "anti")):
        node = P.JoinNode(kind, P.ValuesNode(f, ()), P.ValuesNode(f, ()), (0,), (0,), None, f,
                          build_left=build_left)
        mesh_plan._check_node(node)
    with pytest.raises(mesh_plan.MeshUnsupported):
        mesh_plan._check_node(P.JoinNode("asof", P.ValuesNode(f, ()), P.ValuesNode(f, ()),
                                         (0,), (0,), None, f))
