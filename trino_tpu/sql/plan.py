"""Logical plan IR.

Analogue of Trino's plan-node layer (main/sql/planner/plan/, 59 classes
— SURVEY.md §2.2), reduced to the relational core the executor runs.
Conventions that keep physical planning mechanical:

- Every node's output schema is an ordered list of Field(name, type);
  expressions inside nodes are typed IR (trino_tpu.expr.ir) whose
  InputRefs index the CHILD's output channels.
- Aggregate/Join key and argument expressions are always plain channel
  references — the analyzer inserts Project nodes to materialize
  anything more complex (the HashGenerationOptimizer discipline).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from trino_tpu import types as T
from trino_tpu.expr.ir import Expr, InputRef
from trino_tpu.ops.sort import SortKey


@dataclasses.dataclass(frozen=True)
class Field:
    name: Optional[str]
    type: T.DataType


class PlanNode:
    fields: Tuple[Field, ...]

    def children(self) -> Sequence["PlanNode"]:
        return ()


@dataclasses.dataclass(frozen=True)
class ScanNode(PlanNode):
    """Connector table scan (TableScanNode analogue). `columns` are the
    pruned connector column names, 1:1 with fields."""

    catalog: str
    handle: object  # connectors.spi.TableHandle
    columns: Tuple[str, ...]
    fields: Tuple[Field, ...]


@dataclasses.dataclass(frozen=True)
class ValuesNode(PlanNode):
    fields: Tuple[Field, ...]
    rows: Tuple[Tuple[object, ...], ...]  # python literal values


@dataclasses.dataclass(frozen=True)
class FilterNode(PlanNode):
    child: PlanNode
    predicate: Expr
    fields: Tuple[Field, ...]

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class ProjectNode(PlanNode):
    child: PlanNode
    exprs: Tuple[Expr, ...]
    fields: Tuple[Field, ...]

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class AggCall:
    """kind in {sum,count,count_star,avg,min,max,any} plus the holistic
    kinds {min_by,max_by,approx_percentile}; arg_channel indexes the
    child schema (None for count_star). arg2_channel is min_by/max_by's
    ordering argument; percentile is approx_percentile's fraction."""

    kind: str
    arg_channel: Optional[int]
    out_type: T.DataType
    distinct: bool = False
    arg2_channel: Optional[int] = None
    percentile: Optional[float] = None
    separator: Optional[str] = None  # listagg
    arg3_channel: Optional[int] = None  # pctl_merge bucket-max channel
    param: Optional[float] = None  # numeric_histogram/approx_most_frequent b
    post: Optional[str] = None  # fused sketch accessor: card | vq | qv


@dataclasses.dataclass(frozen=True)
class AggregateNode(PlanNode):
    """Output schema = [group key channels..., agg results...]
    (AggregationNode analogue). `step` is the AggregationNode.Step:
    single | partial (emits serialized accumulator state) | final
    (consumes state from the exchange). In partial/final steps the
    output/input layout follows operators.partial_output_schema.
    `key_ranges`: per group channel the exact (low, high) of its values,
    or None for a key that has none to give; None where no group table
    can be bounded by them (sql/stats.group_key_ranges, set by the
    optimizer's last pass; a partial and its final step carry the same)."""

    child: PlanNode
    group_channels: Tuple[int, ...]
    aggs: Tuple[AggCall, ...]
    fields: Tuple[Field, ...]
    step: str = "single"
    key_ranges: Optional[Tuple[Optional[Tuple[int, int]], ...]] = None

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class JoinNode(PlanNode):
    """kind in {inner,left,semi,anti,cross}. Left is the probe side.
    Output schema: left fields + right fields (inner/left/cross);
    left fields only (semi/anti). `residual` is typed over the
    concatenated left+right schema and runs inside the join, before
    match flags (JoinNode.filter analogue).

    `build_left` (semi, anti and left only; the optimizer's last pass
    sets it from the two sides' estimated rows): the side the join
    PRESERVES, the left, is the one built, the other side probes it,
    and a flag a build row says whether any pair held: a semi- or
    anti-join puts out the flagged rows or the others at its input's
    end, a LEFT join its pairs as they come and the rows no pair
    flagged, with NULLs, at the end. Shown in EXPLAIN as ` build=left`,
    only where set.

    `filter_under_aggregate` (the optimizer's last pass): the dynamic
    filter this join's built side gives its other side does not stand at
    that side's end, in front of the probe, but UNDER the aggregation
    that side ends in (`key_filter_target`): every filtered key is a
    group key of it, so a row the filter drops could only have made a
    group the join would drop whole. Shown in EXPLAIN as
    ` filter=under_aggregate` on the join's line and ` key_filter=[...]`
    on the line of the node whose output is filtered, only where set."""

    kind: str
    left: PlanNode
    right: PlanNode
    left_keys: Tuple[int, ...]
    right_keys: Tuple[int, ...]
    residual: Optional[Expr]
    fields: Tuple[Field, ...]
    # skew-aware execution annotations (adaptive/controller.py). Both
    # are declared fields, so they ride through dataclasses.replace and
    # appear in the repr — which is what plan fingerprints, spool keys
    # and the mesh program-cache key hash, keeping annotated and plain
    # plans distinct without any explicit key plumbing.
    #
    # skew_hot_keys: observed heavy-hitter values of the (single) join
    # key; the mesh plane replicates hot BUILD rows to every shard and
    # salts hot PROBE rows across the all_to_all. spill_build: observed
    # build rows overflowed the estimate — the local planner pre-opens
    # grace partitions (hybrid hash) instead of thrashing revocation.
    skew_hot_keys: Tuple = ()
    spill_build: bool = False
    build_left: bool = False
    filter_under_aggregate: bool = False

    def children(self):
        return (self.left, self.right)


def filter_sides(join: JoinNode):
    """(the side of `join` that its built side's keys filter, that side's
    key channels, the built side, its key channels), or None for a join
    that filters neither side: an inner or semi-join filters its probe
    (the left) by its build's keys; a semi-, anti- or LEFT join that
    builds the side it preserves (`build_left`) filters the other side,
    the right, by the preserved side's."""
    if not join.left_keys:
        return None
    left = (join.left, tuple(join.left_keys))
    right = (join.right, tuple(join.right_keys))
    if join.kind in ("semi", "anti", "left") and join.build_left:
        return right + left
    if join.kind in ("inner", "semi"):
        return left + right
    return None


def key_filter_target(join: JoinNode):
    """Where `join`'s dynamic filter can stand under an aggregation of
    the side it filters: (the node whose OUTPUT it filters, the key
    channels there, the outermost aggregation it went under), or None
    where that side does not end in such an aggregation. From the side's root down:
    a projection hands a key on where it is a plain column, a filter
    hands every column on, and a single-step aggregation hands a key on
    where it is one of its GROUP keys (never an aggregate's output, never
    a global aggregation: dropping a key's rows drops exactly that key's
    group, and a global aggregation has one group for every row). The
    walk ends at the first node that hands some key on no further; it
    never ends ON a filter (a filter's child takes it)."""
    sides = filter_sides(join)
    if sides is None:
        return None
    node, channels = sides[:2]
    under = None
    while True:
        if isinstance(node, FilterNode):
            node = node.child
        elif isinstance(node, ProjectNode):
            exprs = [node.exprs[c] for c in channels]
            if not all(isinstance(e, InputRef) for e in exprs):
                break
            node, channels = node.child, tuple(e.index for e in exprs)
        elif (isinstance(node, AggregateNode) and node.step == "single"
              and node.group_channels
              and all(c < len(node.group_channels) for c in channels)):
            under = under or node
            channels = tuple(node.group_channels[c] for c in channels)
            node = node.child
        else:
            break
    return None if under is None else (node, channels, under)


@dataclasses.dataclass(frozen=True)
class WindowFuncSpec:
    """One window function: kind in {row_number, rank, dense_rank,
    percent_rank, cume_dist, ntile,
    lead, lag, first_value, last_value, sum, avg, min, max, count,
    count_star}; arg_channel indexes the child schema (None for rank
    family / count_star); `offset` is lead/lag's offset or ntile's n."""

    kind: str
    arg_channel: Optional[int]
    out_type: T.DataType
    offset: int = 1


@dataclasses.dataclass(frozen=True)
class WindowNode(PlanNode):
    """Window functions over (partition, order) — WindowNode analogue.
    Output schema = child fields + one field per function. `frame`:
    "range" | "rows" | "partition" (ops/window.py semantics)."""

    child: PlanNode
    partition_channels: Tuple[int, ...]
    order_keys: Tuple[SortKey, ...]
    functions: Tuple[WindowFuncSpec, ...]
    frame: str
    fields: Tuple[Field, ...]

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class UnnestNode(PlanNode):
    """Lateral UNNEST over ARRAY-typed child columns (UnnestNode
    analogue, main/sql/planner/plan/UnnestNode.java + UnnestOperator).
    Output = child fields + one element field per array channel
    (+ ordinality). Multi-array zip pads short arrays with NULL; rows
    whose arrays are all empty produce no output (inner semantics)."""

    child: PlanNode
    array_channels: Tuple[int, ...]
    ordinality: bool
    fields: Tuple[Field, ...]

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class MeasureSpec:
    """One MATCH_RECOGNIZE measure. kind: "first" | "last" (value of
    `channel` at the first/last row tagged `var`; var None = the whole
    match) | "match_number" | "classifier"."""

    kind: str
    name: str
    out_type: T.DataType
    var: Optional[str] = None
    channel: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class MatchRecognizeNode(PlanNode):
    """Row pattern recognition (PatternRecognitionNode analogue,
    main/sql/planner/plan/PatternRecognitionNode.java). `defines` maps
    var -> typed predicate over the EXTENDED child schema (child
    channels + the shifted copies listed in `shifts`: channel c shifted
    by offset o appears at extended channel len(child.fields) + i).
    Output schema (ONE ROW PER MATCH): partition channels' fields +
    one field per measure."""

    child: PlanNode
    partition_channels: Tuple[int, ...]
    order_keys: Tuple[SortKey, ...]
    defines: Tuple[Tuple[str, Expr], ...]
    shifts: Tuple[Tuple[int, int], ...]  # (child channel, offset)
    pattern: object
    measures: Tuple[MeasureSpec, ...]
    after_match: str  # "past_last" | "next_row"
    fields: Tuple[Field, ...]

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class SortNode(PlanNode):
    child: PlanNode
    keys: Tuple[SortKey, ...]
    fields: Tuple[Field, ...]

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class TopNNode(PlanNode):
    child: PlanNode
    keys: Tuple[SortKey, ...]
    count: int
    fields: Tuple[Field, ...]

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class LimitNode(PlanNode):
    child: PlanNode
    count: Optional[int]
    offset: int
    fields: Tuple[Field, ...]

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class EnforceSingleRowNode(PlanNode):
    """Scalar-subquery cardinality guard (EnforceSingleRowOperator
    analogue): exactly one input row passes through; zero rows yield one
    all-NULL row; more than one raises at execution."""

    child: PlanNode
    fields: Tuple[Field, ...]

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class UnionAllNode(PlanNode):
    """Concatenation of same-width children (UNION ALL; distinct unions
    get an AggregateNode on top)."""

    inputs: Tuple[PlanNode, ...]
    fields: Tuple[Field, ...]

    def children(self):
        return self.inputs


@dataclasses.dataclass(frozen=True)
class OutputNode(PlanNode):
    """Root: names the result columns (OutputNode analogue)."""

    child: PlanNode
    names: Tuple[str, ...]
    fields: Tuple[Field, ...]

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class ExchangeNode(PlanNode):
    """Remote exchange in the distributed plan (ExchangeNode REMOTE scope
    + the SystemPartitioningHandle family, SURVEY.md §2.2/§2.7).
    kind: "gather" (to one task; with merge_keys = merging gather),
    "repartition" (FIXED_HASH on hash_channels), "broadcast"
    (FIXED_BROADCAST replication). Inserted by the AddExchanges pass;
    the fragmenter cuts the plan here."""

    child: PlanNode
    kind: str
    hash_channels: Tuple[int, ...]
    fields: Tuple[Field, ...]
    merge_keys: Tuple = ()

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class RemoteSourceNode(PlanNode):
    """Leaf of a fragment: pages arriving from producer fragments
    (RemoteSourceNode analogue)."""

    fragment_ids: Tuple[int, ...]
    fields: Tuple[Field, ...]
    merge_keys: Tuple = ()


def explain_text(node: PlanNode, indent: int = 0, marks=None) -> str:
    """EXPLAIN rendering (textual plan like Trino's PlanPrinter).
    `marks`: what the joins above wrote for the lines of nodes below
    them (by `id`), appended to the node's line."""
    marks = {} if marks is None else marks
    pad = "  " * indent
    name = type(node).__name__.replace("Node", "")
    detail = ""
    if isinstance(node, ScanNode):
        h = node.handle
        detail = f" {node.catalog}.{h.schema}.{h.table} {list(node.columns)}"
        pushed = getattr(h, "constraints", ())
        if pushed:
            def _ctext(c):
                if c.op == "or":  # multi-range: render the disjuncts
                    return c.column + " (" + " or ".join(
                        f"{op} {v!r}" for op, v in c.value
                    ) + ")"
                return f"{c.column} {c.op} {c.value!r}"

            detail += " pushed=[" + ", ".join(
                _ctext(c) for c in pushed
            ) + "]"
    elif isinstance(node, FilterNode):
        detail = f" {node.predicate!r}"
    elif isinstance(node, ProjectNode):
        detail = f" {[repr(e) for e in node.exprs]}"
    elif isinstance(node, AggregateNode):
        detail = f" keys={list(node.group_channels)} aggs={[a.kind for a in node.aggs]}"
        if node.step != "single":
            detail += f" step={node.step}"
        if node.key_ranges is not None:
            detail += f" key_ranges={list(node.key_ranges)}"
    elif isinstance(node, ExchangeNode):
        detail = f" {node.kind}"
        if node.hash_channels:
            detail += f" on={list(node.hash_channels)}"
        if node.merge_keys:
            detail += " merge"
    elif isinstance(node, RemoteSourceNode):
        detail = f" fragments={list(node.fragment_ids)}"
    elif isinstance(node, JoinNode):
        detail = (
            f" {node.kind} L{list(node.left_keys)}=R{list(node.right_keys)}"
            + (" +residual" if node.residual is not None else "")
            + (" build=left" if node.build_left else "")
        )
        target = key_filter_target(node) if node.filter_under_aggregate else None
        if target is not None:
            detail += " filter=under_aggregate"
            marks[id(target[0])] = f" key_filter={list(target[1])}"
        # skew annotations render only when present, so plans with no
        # skew stay byte-identical to the unannotated output
        if node.skew_hot_keys:
            detail += f" hot={list(node.skew_hot_keys)}"
        if node.spill_build:
            detail += " spill_build"
    elif isinstance(node, (SortNode, TopNNode)):
        detail = f" keys={[(k.channel, 'desc' if k.descending else 'asc') for k in node.keys]}"
        if isinstance(node, TopNNode):
            detail += f" n={node.count}"
    elif isinstance(node, LimitNode):
        detail = f" n={node.count} offset={node.offset}"
    elif isinstance(node, OutputNode):
        detail = f" {list(node.names)}"
    elif isinstance(node, ValuesNode) and getattr(node, "spool_key", ""):
        # adaptively materialized subtree riding along as a literal
        detail = (
            f" rows={len(node.rows)} spool={node.spool_key}"
            f" [{getattr(node, 'source_desc', '')}]"
        )
    lines = [f"{pad}{name}{detail}{marks.get(id(node), '')}"]
    for c in node.children():
        lines.append(explain_text(c, indent + 1, marks))
    return "\n".join(lines)
