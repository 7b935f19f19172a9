"""Statistics propagation + cost-based decisions.

Analogue of main/cost/ (StatsCalculator rule set: FilterStatsCalculator,
JoinStatsRule, AggregationStatsRule; CostCalculatorUsingExchanges —
SURVEY.md §2.2) reduced to the estimates the planner consults: row
counts and per-channel (ndv, null_fraction, low, high). Consumers:
broadcast-vs-partitioned join choice and adaptive partition counts
(DeterminePartitionCount.java:90), plus EXPLAIN row estimates."""

from __future__ import annotations

import dataclasses
import datetime
import math
from typing import Callable, Dict, Optional

import numpy as np

from trino_tpu import types as T
from trino_tpu.expr import ir
from trino_tpu.sql import plan as P

UNKNOWN_FILTER_COEFFICIENT = 0.33  # fallback selectivity
# a scanned column with this share of its table's rows in distinct values
# is taken for a key (the memory connector's NDVs are sampled estimates)
_UNIQUE_NDV_SHARE = 0.98


@dataclasses.dataclass
class ColStats:
    ndv: Optional[float] = None
    null_fraction: Optional[float] = None
    low: Optional[float] = None
    high: Optional[float] = None
    # a dictionary-coded column's table-stable dictionary, asked for only
    # when a predicate over the column has no estimate of its own
    dictionary: Optional[Callable[[], object]] = None
    # no two rows share a value (a key of its table, and what filters and
    # joins that repeat no row of its side have left of one)
    unique: bool = False
    # every non-NULL value lies inside [low, high]: a connector computed
    # them over the whole column at this table version
    # (TableStatistics.exact_ranges), or they follow from such a range
    # (_projected). Filters, joins and aggregations keep it: the
    # survivors' range lies inside. An estimate or a declared range
    # never sets it; only an exact range may bound a group table
    # (group_key_ranges).
    exact: bool = False


@dataclasses.dataclass
class PlanStats:
    row_count: float
    columns: Dict[int, ColStats] = dataclasses.field(default_factory=dict)

    def col(self, ch: int) -> ColStats:
        return self.columns.get(ch, ColStats())


class StatsCalculator:
    def __init__(self, catalogs):
        self._catalogs = catalogs
        # id(node) -> (node, stats); the node reference keeps the id alive
        self._memo: Dict[int, tuple] = {}

    def stats(self, node: P.PlanNode) -> PlanStats:
        # memo holds the node itself: id() alone would collide once a
        # previously-estimated node is garbage collected
        key = id(node)
        hit = self._memo.get(key)
        if hit is not None and hit[0] is node:
            return hit[1]
        # adaptive execution substitutes materialized subtrees back into
        # the plan carrying their EXACT observed statistics — those beat
        # any estimate this calculator could derive
        ps = getattr(node, "plan_stats", None)
        if isinstance(ps, PlanStats):
            self._memo[key] = (node, ps)
            return ps
        m = getattr(self, f"_{type(node).__name__}", None)
        if m is None and isinstance(node, P.ValuesNode):
            m = self._ValuesNode
        out = m(node) if m is not None else self._default(node)
        self._memo[key] = (node, out)
        return out

    def _default(self, node: P.PlanNode) -> PlanStats:
        kids = node.children()
        if not kids:
            return PlanStats(1e6)
        # a node without a rule of its own may lay its channels out
        # otherwise than its child: an estimate survives that, a bound
        # must not
        child = self.stats(kids[0])
        return PlanStats(child.row_count, {
            ch: dataclasses.replace(cs, exact=False) if cs.exact else cs
            for ch, cs in child.columns.items()
        })

    # -- leaves --
    def _ScanNode(self, node: P.ScanNode) -> PlanStats:
        try:
            ts = self._catalogs.get(node.catalog).metadata.get_table_statistics(
                node.handle
            )
        except Exception:
            return PlanStats(1e9)
        rows = float(ts.row_count) if ts.row_count is not None else 1e9
        cols: Dict[int, ColStats] = {}
        for i, name in enumerate(node.columns):
            t = ts.columns.get(name)
            if t is not None:
                ndv, nf, lo, hi = t
                cols[i] = ColStats(
                    ndv,
                    nf,
                    _as_float(lo),
                    _as_float(hi),
                    unique=ndv is not None and ndv >= _UNIQUE_NDV_SHARE * rows,
                    exact=name in ts.exact_ranges
                    and lo is not None and hi is not None,
                )
            if node.fields[i].type.is_string:
                cols[i] = dataclasses.replace(
                    cols.get(i, ColStats()),
                    dictionary=self._dictionary_of(node, name),
                )
        return PlanStats(rows, cols)

    def _dictionary_of(self, node: P.ScanNode, name: str):
        def dictionary():
            try:
                return self._catalogs.get(node.catalog).metadata.column_dictionary(
                    node.handle, name
                )
            except Exception:
                return None

        return dictionary

    def _ValuesNode(self, node: P.ValuesNode) -> PlanStats:
        return PlanStats(float(len(node.rows)))

    # -- relational --
    def _FilterNode(self, node: P.FilterNode) -> PlanStats:
        child = self.stats(node.child)
        sel = _selectivity(node.predicate, child)
        rows = max(child.row_count * sel, 1.0)
        cols = {
            ch: dataclasses.replace(
                cs, ndv=min(cs.ndv, rows) if cs.ndv is not None else None
            )
            for ch, cs in child.columns.items()
        }
        return PlanStats(rows, cols)

    def _ProjectNode(self, node: P.ProjectNode) -> PlanStats:
        child = self.stats(node.child)
        cols: Dict[int, ColStats] = {}
        for i, e in enumerate(node.exprs):
            cs = _projected(e, child)
            if cs is not None:
                cols[i] = cs
        return PlanStats(child.row_count, cols)

    def _AggregateNode(self, node: P.AggregateNode) -> PlanStats:
        child = self.stats(node.child)
        if not node.group_channels:
            return PlanStats(1.0)
        ndv_prod = 1.0
        for c in node.group_channels:
            ndv = child.col(c).ndv
            ndv_prod *= ndv if ndv is not None else math.sqrt(child.row_count)
        rows = max(min(child.row_count, ndv_prod), 1.0)
        cols = {
            i: dataclasses.replace(
                child.col(c), unique=len(node.group_channels) == 1
            )
            for i, c in enumerate(node.group_channels)
        }
        return PlanStats(rows, cols)

    def _JoinNode(self, node: P.JoinNode) -> PlanStats:
        left = self.stats(node.left)
        right = self.stats(node.right)
        width_l = len(node.left.fields)
        if node.kind == "cross":
            cols = _repeated(left.columns)
            for ch, cs in _repeated(right.columns).items():
                cols[width_l + ch] = cs
            return PlanStats(left.row_count * right.row_count, cols)
        if node.kind in ("semi", "anti"):
            return PlanStats(
                max(left.row_count * 0.5, 1.0), dict(left.columns)
            )
        if node.kind in ("mark", "mark_exists"):
            # mark joins preserve probe cardinality exactly; the output
            # is the probe columns + one BOOLEAN channel (no stats)
            return PlanStats(left.row_count, dict(left.columns))
        # equi-join estimate: |L|*|R| / max(ndv of the key pair).
        # Unknown NDV defaults to the side's ROW COUNT (join keys are
        # near-unique on one side in analytic schemas — FK->PK). The old
        # sqrt(rows) default overestimated join output ~25x on TPC-H Q3
        # through the memory connector, which flipped the reorderer into
        # building the lookup on the 6M-row side.
        ndvs = [
            (
                max(_or(left.col(lk).ndv, left.row_count), 1.0),
                max(_or(right.col(rk).ndv, right.row_count), 1.0),
            )
            for lk, rk in zip(node.left_keys, node.right_keys)
        ]
        if len(ndvs) == 1:
            denom = max(ndvs[0])
        else:
            denom = _composite_key_ndv(ndvs, left.row_count, right.row_count)
        rows = max(left.row_count * right.row_count / denom, 1.0)
        if node.kind == "left":
            rows = max(rows, left.row_count)
        # a side's rows come out once each where the other side's key is
        # unique, and its unique columns stay so
        keeps_l = key_is_unique(right, node.right_keys)
        keeps_r = key_is_unique(left, node.left_keys) and node.kind == "inner"
        cols = dict(left.columns) if keeps_l else _repeated(left.columns)
        for ch, cs in right.columns.items():
            cols[width_l + ch] = cs if keeps_r else dataclasses.replace(cs, unique=False)
        if node.kind == "inner":
            # what is left of a key column holds no more distinct values
            # than its partner had
            for (lk, rk), (ndv_l, ndv_r) in zip(
                zip(node.left_keys, node.right_keys), ndvs
            ):
                for ch in (lk, width_l + rk):
                    cols[ch] = dataclasses.replace(
                        cols.get(ch, ColStats()), ndv=min(ndv_l, ndv_r)
                    )
        return PlanStats(rows, cols)

    def _WindowNode(self, node: P.WindowNode) -> PlanStats:
        return self.stats(node.child)

    def _SortNode(self, node: P.SortNode) -> PlanStats:
        return self.stats(node.child)

    def _TopNNode(self, node: P.TopNNode) -> PlanStats:
        child = self.stats(node.child)
        return PlanStats(min(child.row_count, float(node.count)), dict(child.columns))

    def _LimitNode(self, node: P.LimitNode) -> PlanStats:
        child = self.stats(node.child)
        if node.count is None:
            return child
        return PlanStats(
            min(child.row_count, float(node.count)), dict(child.columns)
        )

    def _UnionAllNode(self, node: P.UnionAllNode) -> PlanStats:
        return PlanStats(sum(self.stats(c).row_count for c in node.inputs))

    def _OutputNode(self, node: P.OutputNode) -> PlanStats:
        return self.stats(node.child)

    def _ExchangeNode(self, node: P.ExchangeNode) -> PlanStats:
        return self.stats(node.child)

    def _RemoteSourceNode(self, node: P.RemoteSourceNode) -> PlanStats:
        return PlanStats(1e6)


_INTEGER_KINDS = (
    T.TypeKind.TINYINT, T.TypeKind.SMALLINT, T.TypeKind.INTEGER,
    T.TypeKind.BIGINT,
)
# a date part's values, whatever the date
_DATE_PART_RANGE = {
    "extract_month": (1, 12), "extract_day": (1, 31), "quarter": (1, 4),
}


def _year_of(days: float) -> Optional[int]:
    """The civil year of a day number (days since 1970-01-01), None
    outside the calendar `datetime` knows."""
    try:
        return (datetime.date(1970, 1, 1) + datetime.timedelta(days=int(days))).year
    except (OverflowError, ValueError):
        return None


def _projected(e: ir.Expr, child: PlanStats) -> Optional[ColStats]:
    """What a projection hands on of its input's statistics. A column
    keeps all of its own. A RANGE goes through a closed list of
    expressions and nothing else, each either monotone in its one
    argument or bounded whatever it is: the year of a date whose exact
    range is known; the month, the day of the month and the quarter of
    any date or timestamp; a cast between integer kinds of a column
    whose exact range is known. These carry no NDV and no null
    fraction: the estimates above them stay what they were."""
    if isinstance(e, ir.InputRef):
        return child.columns.get(e.index)
    if isinstance(e, ir.Call) and len(e.args) == 1:
        arg = e.args[0]
        if e.name in _DATE_PART_RANGE and arg.type.kind in (
                T.TypeKind.DATE, T.TypeKind.TIMESTAMP):
            low, high = _DATE_PART_RANGE[e.name]
            return ColStats(low=float(low), high=float(high), exact=True)
        if e.name == "extract_year" and arg.type.kind == T.TypeKind.DATE:
            inner = _projected(arg, child)
            if inner is None or not inner.exact:
                return None
            low, high = _year_of(inner.low), _year_of(inner.high)
            if low is None or high is None:
                return None
            return ColStats(low=float(low), high=float(high), exact=True)
        return None
    if isinstance(e, ir.Cast) and e.type.kind in _INTEGER_KINDS \
            and e.arg.type.kind in _INTEGER_KINDS:
        inner = _projected(e.arg, child)
        if inner is None or not inner.exact:
            return None
        info = np.iinfo(e.type.dtype)
        if inner.low < info.min or inner.high > info.max:
            return None  # the cast fails on some row: no bound to state
        return ColStats(low=inner.low, high=inner.high, exact=True)
    return None


def group_key_ranges(node: P.AggregateNode, child: PlanStats):
    """Per group channel of `node`, the exact (low, high) of its values
    or None; None for the node where no group table can be bounded by
    them. A key of integer kind (exec/operators.RANGE_KEY_KINDS: TINYINT
    to BIGINT, DATE) whose range is exact gives its range; a string or a
    boolean gives None (the operator bounds those itself, by their
    dictionary); any other key, or ranges whose digits alone (a NULL
    digit a key) outgrow what a slot-addressed reduce takes, leaves the
    node without any. Two limits (ops/groupby.choose_bounded_reduce's):
    MXU_MAX_SLOTS for any aggregates, and SLOT_MAX_SLOTS where every
    aggregate is a plain count, the one reduce that addresses a table
    that large. This looks at the node's aggregates so that a SUM by a
    key of a million values (TPC-H Q13's over its join, Q18's by
    `l_orderkey`) carries no range its operator would drop: the plan
    says `key_ranges` only where a table can be bounded by them. Past
    MXU_MAX_SLOTS it also looks at the rows the node is estimated to
    read: a table of millions of slots is zeroed, folded and handed on
    slot by slot whatever it holds, so a count of a few rows (a
    selective filter under it) keeps the sort path, whose cost follows
    its rows; the table is taken where the estimate gives at least a
    row to four slots (what HashBuildSink takes for a build side worth
    packing). The operator asks the chooser again with what it alone
    knows (the dictionaries' sizes, the backend) and still drops a
    range whose answer is `sort`."""
    from trino_tpu.exec.operators import RANGE_KEY_KINDS
    from trino_tpu.ops.groupby import MXU_MAX_SLOTS, SLOT_MAX_SLOTS

    counts = bool(node.aggs) and all(
        a.kind in ("count", "count_star") and not a.distinct for a in node.aggs
    )
    limit = SLOT_MAX_SLOTS if counts else MXU_MAX_SLOTS
    ranges, slots = [], 1
    for c in node.group_channels:
        t = node.child.fields[c].type
        if t.is_string or t.kind == T.TypeKind.BOOLEAN:
            ranges.append(None)
            continue
        cs = child.col(c)
        if t.kind not in RANGE_KEY_KINDS \
                or not cs.exact or cs.low is None or cs.high is None \
                or not cs.low <= cs.high:
            return None
        low, high = int(cs.low), int(cs.high)
        if low != cs.low or high != cs.high:
            return None
        slots *= high - low + 2
        if slots > limit:
            return None
        ranges.append((low, high))
    if all(r is None for r in ranges):
        return None
    if slots > MXU_MAX_SLOTS and child.row_count * 4 < slots:
        return None
    return tuple(ranges)


def _as_float(v) -> Optional[float]:
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def key_is_unique(side: PlanStats, channels) -> bool:
    """Whether no two rows of `side` share a value of the key: one of
    its columns is unique already."""
    return any(side.col(c).unique for c in channels)


def _repeated(columns: Dict[int, ColStats]) -> Dict[int, ColStats]:
    return {ch: dataclasses.replace(cs, unique=False) for ch, cs in columns.items()}


def _or(value: Optional[float], default: float) -> float:
    return value if value is not None else default


def _composite_key_ndv(ndvs, left_rows: float, right_rows: float) -> float:
    """Distinct values of a multi-column equi-join key, for the join's
    denominator: max over the sides of each side's distinct tuples.

    A side's columns are rarely independent (TPC-H's `(l_partkey,
    l_suppkey)` has 8 M pairs at SF10 where the product of the columns'
    NDVs is 2 x 10^11), so the product is only a bound, and so are the
    side's rows. Where every column of one side has no more distinct
    values than its partner, containment (the assumption the one-column
    estimate already makes: the smaller value set lies inside the
    larger) is taken for the tuples too: that side's tuples are among
    the other's, so it has no more of them. A foreign key of several
    columns then joins its primary key at the foreign side's row count
    instead of at next to nothing."""
    left_rows, right_rows = max(left_rows, 1.0), max(right_rows, 1.0)
    # (a join's output keeps its inputs' column NDVs: no more than its rows)
    ndvs = [(min(l, left_rows), min(r, right_rows)) for l, r in ndvs]
    tuples_l = min(math.prod(l for l, _ in ndvs), left_rows)
    tuples_r = min(math.prod(r for _, r in ndvs), right_rows)
    if all(l <= r for l, r in ndvs):
        tuples_l = min(tuples_l, tuples_r)
    if all(r <= l for l, r in ndvs):
        tuples_r = min(tuples_r, tuples_l)
    return max(tuples_l, tuples_r, 1.0)


def _first_input_ref(e: ir.Expr) -> Optional[ir.InputRef]:
    if isinstance(e, ir.InputRef):
        return e
    for c in e.children():
        found = _first_input_ref(c)
        if found is not None:
            return found
    return None


def _host_device():
    """Plan-time arithmetic stays off the accelerator where the process
    has a CPU backend beside it."""
    import contextlib

    import jax

    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


def _dictionary_selectivity(e: ir.Expr, child: PlanStats) -> Optional[float]:
    """The share of a dictionary's VALUES that pass `e`, for a predicate
    over one dictionary-coded column (`p_name like '%green%'`): the
    expression compiler evaluates it over the dictionary, one code a
    value, on the host's CPU. Every value is taken to be as frequent as
    any other. None where the predicate reads another number of columns,
    the column has no dictionary in hand, or the compiler cannot bind
    it."""
    from trino_tpu.sql.optimizer import expr_refs, substitute

    refs = expr_refs(e)
    if len(refs) != 1:
        return None
    (channel,) = refs
    thunk = child.col(channel).dictionary
    dictionary = thunk() if thunk is not None else None
    if dictionary is None or not len(dictionary):
        return None
    try:
        from trino_tpu.expr.compile import bind_expr

        column = _first_input_ref(e)
        bound_to = substitute(e, {channel: ir.InputRef(0, column.type)})
        with _host_device():
            bound = bind_expr(bound_to, [column.type], [dictionary])
            data, valid = bound.fn(
                [np.arange(len(dictionary), dtype=np.int32)], [None]
            )
            passed = np.asarray(data, dtype=bool)
            if valid is not None:
                passed &= np.asarray(valid, dtype=bool)
    except Exception:
        return None
    return float(passed.sum()) / len(dictionary)


def _selectivity(e: ir.Expr, child: PlanStats) -> float:
    """FilterStatsCalculator-style predicate selectivity."""
    if isinstance(e, ir.Call):
        if e.name == "and":
            return _selectivity(e.args[0], child) * _selectivity(e.args[1], child)
        if e.name == "or":
            a = _selectivity(e.args[0], child)
            b = _selectivity(e.args[1], child)
            return min(a + b, 1.0)
        if e.name == "not":
            return max(1.0 - _selectivity(e.args[0], child), 0.05)
        if e.name in ("eq", "ne", "lt", "le", "gt", "ge") and len(e.args) == 2:
            col, lit = e.args
            op = e.name
            if isinstance(lit, ir.InputRef) and isinstance(col, ir.Literal):
                # normalizing `lit OP col` to `col OP' lit` flips the
                # comparison direction
                col, lit = lit, col
                op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}.get(op, op)
            if isinstance(col, ir.InputRef) and isinstance(lit, ir.Literal):
                cs = child.col(col.index)
                if op == "eq":
                    return 1.0 / cs.ndv if cs.ndv else 0.1
                if op == "ne":
                    return 1.0 - (1.0 / cs.ndv if cs.ndv else 0.1)
                lo, hi = cs.low, cs.high
                v = _as_float(lit.value)
                if lo is not None and hi is not None and v is not None and hi > lo:
                    frac = (v - lo) / (hi - lo)
                    frac = min(max(frac, 0.0), 1.0)
                    return frac if op in ("lt", "le") else 1.0 - frac
    share = _dictionary_selectivity(e, child)
    return UNKNOWN_FILTER_COEFFICIENT if share is None else share


def determine_partition_count(
    rows: float, max_partitions: int, rows_per_partition: float = 1e6
) -> int:
    """Adaptive stage parallelism from stats
    (DeterminePartitionCount.java:90)."""
    want = math.ceil(rows / rows_per_partition)
    return max(1, min(max_partitions, want))
