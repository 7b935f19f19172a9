"""Plan sanity-checker pipeline (sql/validate.py).

Each corrupted-plan case asserts the RIGHT checker fires and names the
RIGHT node — a validator that trips on the wrong checker would mask the
actual invariant. Plus: plan determinism over the full TPC-H suite, the
rules-mode regression (a rule mutated to mis-shift refs is caught and
NAMED), and the cost-based partial-aggregation gate.
"""

import dataclasses

import pytest

from trino_tpu import types as T
from trino_tpu.connectors.tpch import create_tpch_connector
from trino_tpu.engine import LocalQueryRunner, Session
from trino_tpu.expr import ir
from trino_tpu.sql import plan as P
from trino_tpu.sql.fragmenter import (
    PlanFragment,
    SubPlan,
    plan_distributed,
    push_partial_aggregation_through_exchange,
)
from trino_tpu.sql.optimizer import IterativeOptimizer, Rule
from trino_tpu.sql.parser import parse
from trino_tpu.sql.validate import (
    Lowering,
    PlanValidationError,
    check_plan_determinism,
    check_sql_stability,
    collect_subplan_violations,
    collect_violations,
    shape_census,
    validate_logical,
    validate_subplan,
)
from tests.tpch_queries import QUERIES


def _values(*fields):
    fs = tuple(P.Field(n, t) for n, t in fields)
    return P.ValuesNode(fs, ((0,) * len(fs),))


def _err(fn) -> PlanValidationError:
    with pytest.raises(PlanValidationError) as e:
        fn()
    return e.value


# -- corrupted plans: one per checker -----------------------------------------


def test_bad_ref_index_names_refs_checker():
    vals = _values(("a", T.BIGINT))
    bad = P.ProjectNode(
        vals, (ir.InputRef(5, T.BIGINT),), (P.Field("x", T.BIGINT),)
    )
    e = _err(lambda: validate_logical(bad))
    assert e.checker == "refs"
    assert "Project" in e.node_path
    assert "5" in str(e)


def test_wrong_field_dtype_names_types_checker():
    vals = _values(("a", T.BIGINT))
    bad = P.ProjectNode(
        vals, (ir.InputRef(0, T.BIGINT),), (P.Field("x", T.DOUBLE),)
    )
    e = _err(lambda: validate_logical(bad))
    assert e.checker == "types"
    assert "Project" in e.node_path


def test_duplicate_node_object_names_structure_checker():
    vals = _values(("a", T.BIGINT))
    proj = P.ProjectNode(
        vals, (ir.InputRef(0, T.BIGINT),), (P.Field("x", T.BIGINT),)
    )
    bad = P.UnionAllNode((proj, proj), proj.fields)
    e = _err(lambda: validate_logical(bad))
    assert e.checker == "structure"
    assert "duplicate" in str(e)
    assert "Project" in e.node_path


def test_mismatched_exchange_keys_names_exchange_checker():
    left_in = _values(("a", T.BIGINT))
    right_in = _values(("b", T.BIGINT), ("s", T.VARCHAR))
    left = P.ExchangeNode(left_in, "repartition", (0,), left_in.fields)
    # join keys agree (both bigint) but the right side repartitions on
    # the VARCHAR column — rows land on different tasks
    right = P.ExchangeNode(right_in, "repartition", (1,), right_in.fields)
    bad = P.JoinNode(
        "inner", left, right, (0,), (0,), None, left.fields + right.fields
    )
    e = _err(lambda: validate_logical(bad))
    assert e.checker == "exchange_keys"
    assert "Join" in e.node_path


def test_uncanonicalized_tstz_key_names_exchange_checker():
    vals = _values(("ts", T.TIMESTAMP_TZ))
    bad = P.ExchangeNode(vals, "repartition", (0,), vals.fields)
    e = _err(lambda: validate_logical(bad))
    assert e.checker == "exchange_keys"
    assert "Exchange" in e.node_path
    assert "$utc" in str(e)


def test_canonicalized_tstz_key_passes():
    vals = _values(("ts$utc", T.TIMESTAMP_TZ))
    ok = P.ExchangeNode(vals, "repartition", (0,), vals.fields)
    validate_logical(ok)


def test_dangling_remote_source_names_structure_checker():
    remote = P.RemoteSourceNode((99,), (P.Field("a", T.BIGINT),))
    frag = PlanFragment(0, remote, "single", "single")
    e = _err(lambda: validate_subplan(SubPlan(frag, [])))
    assert e.checker == "structure"
    assert "RemoteSource" in e.node_path
    assert "99" in str(e)


def test_remote_source_schema_disagreement():
    producer = PlanFragment(
        1, _values(("a", T.VARCHAR)), "single", "single"
    )
    remote = P.RemoteSourceNode((1,), (P.Field("a", T.BIGINT),))
    consumer = PlanFragment(0, remote, "single", "single")
    e = _err(
        lambda: validate_subplan(SubPlan(consumer, [SubPlan(producer, [])]))
    )
    assert e.checker == "structure"
    assert "producer" in str(e)


def test_aggregate_width_mismatch_names_refs_checker():
    vals = _values(("k", T.BIGINT), ("v", T.BIGINT))
    bad = P.AggregateNode(
        vals, (0,), (P.AggCall("sum", 1, T.BIGINT),),
        (P.Field("k", T.BIGINT),),  # missing the agg output field
    )
    e = _err(lambda: validate_logical(bad))
    assert e.checker == "refs"
    assert "Aggregate" in e.node_path


# -- determinism over the full TPC-H suite ------------------------------------


@pytest.fixture(scope="module")
def tpch_runner():
    r = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    r.register_catalog("tpch", create_tpch_connector())
    return r


def test_tpch_planning_is_deterministic(tpch_runner):
    for qid, sql in sorted(QUERIES.items()):
        stmt = parse(sql)
        q = stmt.query if hasattr(stmt, "query") else stmt
        check_plan_determinism(
            lambda: tpch_runner._analyze(q), what=f"tpch q{qid}"
        )


def test_tpch_sql_formatting_is_stable():
    # formatted text keys the prepared-statement plan cache, so
    # formatting must be a fixpoint
    for qid, sql in sorted(QUERIES.items()):
        check_sql_stability(sql, what=f"tpch q{qid}")


@pytest.mark.parametrize("corpus", ["tpch", "tpcds"])
def test_corpus_validates_in_rules_mode(corpus, tpch_runner):
    """Every TPC-H query and every TPC-DS template under test plans
    under `plan_validation = rules` (a check after every rule, and the
    plan made twice), fragments, and leaves no checker a finding on the
    logical plan or on the fragments. Planned, not executed."""
    if corpus == "tpch":
        runner, queries = tpch_runner, QUERIES
    else:
        from tests.test_tpcds import QUERIES as queries
        from trino_tpu.connectors.tpcds import create_tpcds_connector

        runner = LocalQueryRunner(Session(catalog="tpcds", schema="tiny"))
        runner.register_catalog("tpcds", create_tpcds_connector())
    runner.session.plan_validation = "rules"
    try:
        for qid, sql in sorted(queries.items(), key=lambda kv: str(kv[0])):
            check_sql_stability(sql, what=f"{corpus} {qid}")
            stmt = parse(sql)
            output = runner._analyze(
                stmt.query if hasattr(stmt, "query") else stmt
            )
            subplan = plan_distributed(
                output, runner.catalogs, target_splits=2, validation="off"
            )
            found = list(collect_violations(output))
            found += list(collect_subplan_violations(subplan))
            assert not found, (corpus, qid, [
                (v.checker, v.node_path, v.message) for v in found
            ])
    finally:
        runner.session.plan_validation = "passes"


# -- rules mode catches a mutated optimizer rule ------------------------------


class MisshiftProjectRefs(Rule):
    """A deliberately broken rewrite: shifts every Project InputRef up
    by one — the classic off-by-one a real pushdown rule can make."""

    name = "misshift_project_refs"

    def apply(self, node, ctx):
        if isinstance(node, P.ProjectNode):
            shifted = tuple(
                ir.InputRef(e.index + 1, e.type)
                if isinstance(e, ir.InputRef) else e
                for e in node.exprs
            )
            if shifted != node.exprs:
                return dataclasses.replace(node, exprs=shifted)
        return None


def test_rules_mode_catches_misshifted_rule():
    vals = _values(("a", T.BIGINT))
    root = P.ProjectNode(
        vals, (ir.InputRef(0, T.BIGINT),), (P.Field("x", T.BIGINT),)
    )
    opt = IterativeOptimizer((MisshiftProjectRefs(),))
    with pytest.raises(PlanValidationError) as e:
        opt.optimize(
            root,
            validator=lambda plan, rule: validate_logical(
                plan, stage="optimizer", rule=rule
            ),
        )
    assert e.value.checker == "refs"
    assert e.value.rule == "misshift_project_refs"


# -- cost-based partial aggregation (satellite: ROADMAP open item) ------------


class _FakeStats:
    """Stats stub with a KNOWN per-column NDV — the gate only trusts
    confident estimates (unknown NDV keeps the structural split)."""

    def __init__(self, in_rows, ndv):
        self._in, self._ndv = in_rows, ndv

    def stats(self, node):
        col = dataclasses.make_dataclass("C", ["ndv"])(float(self._ndv))
        return dataclasses.make_dataclass("S", ["row_count", "col"])(
            float(self._in), lambda ch: col
        )


def _agg_over_exchange():
    vals = _values(("k", T.BIGINT), ("v", T.BIGINT))
    ex = P.ExchangeNode(vals, "repartition", (0,), vals.fields)
    return P.AggregateNode(
        ex, (0,), (P.AggCall("sum", 1, T.BIGINT),),
        (P.Field("k", T.BIGINT), P.Field("s", T.BIGINT)),
    )


def test_partial_agg_fires_when_groups_reduce():
    # 1000 rows, NDV(k)=10 -> ~10 groups: the partial step shrinks the
    # wire 100x
    root = push_partial_aggregation_through_exchange(
        _agg_over_exchange(), _FakeStats(1000, 10)
    )
    assert isinstance(root, P.AggregateNode) and root.step == "final"
    assert isinstance(root.child, P.ExchangeNode)
    assert root.child.child.step == "partial"


def test_partial_agg_skips_when_keys_nearly_unique():
    # NDV(group keys) ~= input rows: pre-aggregation cannot reduce wire
    # volume, so the split is skipped
    root = push_partial_aggregation_through_exchange(
        _agg_over_exchange(), _FakeStats(1000, 990)
    )
    assert isinstance(root, P.AggregateNode) and root.step == "single"


def test_partial_agg_fires_when_ndv_unknown():
    # unknown NDV must NOT suppress the split — the structural
    # behaviour is the safe default (TPC-DS q72 regression)
    class _UnknownNdv(_FakeStats):
        def stats(self, node):
            s = super().stats(node)
            return dataclasses.make_dataclass("S", ["row_count", "col"])(
                s.row_count,
                lambda ch: dataclasses.make_dataclass("C", ["ndv"])(None),
            )

    root = push_partial_aggregation_through_exchange(
        _agg_over_exchange(), _UnknownNdv(1000, 0)
    )
    assert root.step == "final"


def test_partial_agg_stays_structural_without_stats():
    root = push_partial_aggregation_through_exchange(_agg_over_exchange())
    assert root.step == "final"


# -- compile-churn census -----------------------------------------------------


def test_shape_census_simple_aggregation(tpch_runner):
    stmt = parse(
        "select l_returnflag, sum(l_quantity) from lineitem "
        "group by l_returnflag"
    )
    out = tpch_runner._analyze(stmt.query if hasattr(stmt, "query") else stmt)
    classes = shape_census(out, tpch_runner.catalogs)
    ops = {c.operator for c in classes}
    assert "TableScanOperator" in ops
    assert "HashAggregationOperator" in ops
    # no joins -> no retry-variant (dynamic filter) classes
    assert not any(c.retry_variant for c in classes)


def test_shape_census_join_marks_retry_variant(tpch_runner):
    stmt = parse(
        "select n_name, count(*) from supplier, nation "
        "where s_nationkey = n_nationkey group by n_name"
    )
    out = tpch_runner._analyze(stmt.query if hasattr(stmt, "query") else stmt)
    classes = shape_census(out, tpch_runner.catalogs)
    variants = [c for c in classes if c.retry_variant]
    assert variants and all(
        c.operator == "DynamicFilterOperator" for c in variants
    )
    assert shape_census(
        out, tpch_runner.catalogs, dynamic_filtering=False
    ) == [c for c in classes if not c.retry_variant]


def test_explain_analyze_census_matches_observed(tpch_runner):
    res = tpch_runner.execute(
        "explain analyze select l_returnflag, sum(l_quantity) "
        "from lineitem group by l_returnflag"
    )
    text = res.rows[0][0]
    assert "expected_xla_lowerings=" in text
    assert "observed_shape_classes=" in text
    expected = int(
        text.split("expected_xla_lowerings=")[1].split()[0].rstrip(";")
    )
    observed = int(
        text.split("observed_shape_classes=")[1].split()[0].rstrip(";")
    )
    # the acceptance bound: static census within +-1 of what actually
    # ran (sinks compile no output program; estimate jitter rounds away
    # inside the power-of-two capacity classes)
    assert abs(expected - observed) <= 1, text


def test_explain_analyze_census_tail_classes(tpch_runner):
    """Tables larger than batch_rows scan in batch_rows chunks plus one
    smaller tail chunk; the census must count the tail capacity class
    (PR 5 carried a known miss here) and the ±1 acceptance bound must
    hold through it. batch_rows=49152 puts lineitem tiny (60175 rows)
    at main class 65536 + tail class 16384."""
    tpch_runner.execute("SET SESSION batch_rows = 49152")
    try:
        res = tpch_runner.execute(
            "explain analyze select l_returnflag, sum(l_quantity) "
            "from lineitem group by l_returnflag"
        )
        text = res.rows[0][0]
        expected = int(
            text.split("expected_xla_lowerings=")[1].split()[0].rstrip(";")
        )
        observed = int(
            text.split("observed_shape_classes=")[1].split()[0].rstrip(";")
        )
        assert abs(expected - observed) <= 1, text
        # both the main and the tail scan class are predicted
        assert "TableScanOperator cap=65536" in text, text
        assert "TableScanOperator cap=16384" in text, text
    finally:
        tpch_runner.execute(f"SET SESSION batch_rows = {1 << 20}")


def test_census_warns_above_threshold():
    classes = [
        Lowering(f"Op{i}", 16, ("bigint",)) for i in range(5)
    ]
    from trino_tpu.sql.validate import census_line

    assert "WARNING" in census_line(classes, warn_threshold=3)
    assert "WARNING" not in census_line(classes, warn_threshold=10)
