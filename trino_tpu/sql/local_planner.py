"""Physical planner: logical plan -> reusable operator-factory pipelines.

Analogue of Trino's LocalExecutionPlanner + DriverFactory (main/sql/
planner/LocalExecutionPlanner.java:520 — the operator-selection
switchboard, visitTableScan:2124 / visitAggregation:1926 /
visitJoin:2487; operators are created per-driver from factories,
SqlTaskExecution.java:100). Expression binding and jit compilation
happen ONCE at plan time (the ExpressionCompiler/PageFunctionCompiler
cache discipline, §2.9); each execution instantiates fresh operator
state from the factories, sharing the compiled device programs — so
re-running a cached query never re-traces.

A factory is `ctx -> Operator`; `ctx` is the per-execution context that
materializes join bridges/buffers so concurrent executions never share
mutable state.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from trino_tpu import types as T
from trino_tpu.block import Dictionary, RelBatch
from trino_tpu.connectors.spi import CatalogManager
from trino_tpu.exec import (
    AggSpec,
    BufferSink,
    BufferSource,
    CrossJoinBuildSink,
    CrossJoinOperator,
    FilterProjectOperator,
    HashAggregationOperator,
    HashBuildSink,
    JoinBridge,
    LimitOperator,
    LookupJoinOperator,
    MxuJoinAggOperator,
    Operator,
    Pipeline,
    SortOperator,
    TableScanOperator,
    TopNOperator,
    ValuesOperator,
)
from trino_tpu.exec.operators import make_filter_project_fn, make_residual_fn
from trino_tpu.expr.compile import Bound, ExprBinder
from trino_tpu.expr.ir import Expr, InputRef
from trino_tpu.sql import plan as P

Schema = List[Tuple[T.DataType, Optional[Dictionary]]]
Factory = Callable[[dict], Operator]


def _mem_ctx(ctx: dict):
    """Per-operator MemoryContext when the execution context carries a
    pool (OperatorContext.newLocalUserMemoryContext analogue). Contexts
    carry the query id (the pool's per-query kill ledger) and register
    in ctx["memory_contexts"] so task teardown can close them — on a
    SHARED worker pool a failed task must not leak its reservation."""
    pool = ctx.get("memory_pool")
    if pool is None:
        return None
    from trino_tpu.runtime.memory import MemoryContext

    mc = MemoryContext(pool, query_id=ctx.get("query_id"))
    ctx.setdefault("memory_contexts", []).append(mc)
    return mc


class PhysicalPlan:
    """Cached executable form of one query: factory pipelines + the main
    chain; instantiate() stamps a fresh operator DAG."""

    def __init__(
        self,
        pipelines: List[List[Factory]],
        chain: List[Factory],
        schema: Schema,
        warmup_entries: Sequence = (),
        decorrelated_scalar_aggregates: int = 0,
    ):
        self.pipeline_factories = pipelines
        self.chain_factories = chain
        self.schema = schema
        # compile.warmup.WarmupEntry list: the fused filter/project
        # programs this plan will dispatch, with their census-predicted
        # capacity classes (the AOT warmup input)
        self.warmup_entries = list(warmup_entries)
        # correlated scalar aggregates the analysis decorrelated into
        # this plan (`LocalPlanner.plan`); counted once an execution, so
        # a plan-cache hit counts what its analysis found
        self.decorrelated_scalar_aggregates = decorrelated_scalar_aggregates

    def instantiate(
        self, ctx: Optional[dict] = None
    ) -> Tuple[List[Pipeline], List[Operator]]:
        """`ctx` seeds the per-execution context; the task runtime
        injects "make_remote_source" for RemoteSourceNode leaves."""
        ctx = {} if ctx is None else ctx
        if self.decorrelated_scalar_aggregates:
            from trino_tpu.runtime.metrics import METRICS

            METRICS.increment(
                "decorrelated_scalar_aggregates",
                self.decorrelated_scalar_aggregates,
            )
        pipelines = [
            Pipeline([f(ctx) for f in fs]) for fs in self.pipeline_factories
        ]
        chain = [f(ctx) for f in self.chain_factories]
        return pipelines, chain


class _AggWarmer:
    """WarmupEntry.fn adapter for aggregation kernels. The group-reduce
    programs (ops/groupby) are module-level jits keyed by shape and
    static config, so driving a throwaway operator instance over the
    dead batch seeds the very dispatch cache the real query hits."""

    def __init__(self, groups, specs, schema, step, key_ranges=None):
        self.groups = list(groups)
        self.specs = list(specs)
        self.schema = list(schema)
        self.step = step
        self.key_ranges = key_ranges

    def __call__(self, batch):
        op = HashAggregationOperator(
            self.groups, self.specs, self.schema, step=self.step,
            key_ranges=self.key_ranges,
        )
        op.add_input(batch)
        op.finish()
        for _ in range(8):
            if op.get_output() is None:
                break


def unread_join_outputs(root: P.PlanNode) -> Dict[int, frozenset]:
    """By `id` of each inner JoinNode without a residual: the channels
    of its output that nothing above it reads (a key a later join no
    longer needs, a column only a filter below looked at). The join
    hands those on as zeros and gathers nothing for them
    (`LookupJoinOperator`, `unread`). What a node reads of its child is
    followed through filters, projections, aggregations and joins; any
    other node reads all of it."""
    from trino_tpu.sql.optimizer import expr_refs

    unread: Dict[int, frozenset] = {}

    def walk(node: P.PlanNode, read: Optional[set]) -> None:
        """`read`: the channels of `node`'s output its parent reads,
        None for all of them."""
        if isinstance(node, P.JoinNode):
            width_l = len(node.left.fields)
            pairs = node.kind in ("inner", "left", "full")
            if read is None or not pairs and node.kind not in ("semi", "anti"):
                walk(node.left, None)
                walk(node.right, None)
                return
            if node.kind == "inner" and node.residual is None:
                mine = frozenset(range(len(node.fields))) - frozenset(read)
                seen = unread.get(id(node))
                unread[id(node)] = mine if seen is None else (seen & mine)
            refs = expr_refs(node.residual) if node.residual is not None else set()
            left = {c for c in read | refs if c < width_l} | set(node.left_keys)
            walk(node.left, left)
            # (a semi- or anti-join hands on the left's columns only: of
            # the right it reads its keys and what the residual names)
            walk(node.right, {c - width_l for c in (read if pairs else set()) | refs
                              if c >= width_l} | set(node.right_keys))
            return
        if isinstance(node, P.FilterNode):
            walk(node.child,
                 None if read is None else read | expr_refs(node.predicate))
            return
        if isinstance(node, P.ProjectNode):
            kept = range(len(node.exprs)) if read is None else sorted(read)
            walk(node.child, set().union(*[expr_refs(node.exprs[c]) for c in kept]))
            return
        if isinstance(node, P.AggregateNode) and node.step in ("single", "partial"):
            reads = set(node.group_channels)
            for a in node.aggs:
                reads |= {c for c in (a.arg_channel, a.arg2_channel, a.arg3_channel)
                          if c is not None}
            walk(node.child, reads)
            return
        for child in node.children():
            walk(child, None)

    walk(root, None)
    return unread


class _JoinWarmer:
    """Dead-batch join warmup: build an empty lookup source at the
    build side's predicted capacity, then probe it at the entry's
    capacity — the (probe_cap, build_cap) pair the real query
    dispatches."""

    def __init__(self, lkeys, rkeys, kind, probe_schema, build_schema,
                 build_cap, unread=()):
        self.lkeys, self.rkeys, self.kind = list(lkeys), list(rkeys), kind
        self.probe_schema = list(probe_schema)
        self.build_schema = list(build_schema)
        self.build_cap = int(build_cap)
        self.unread = tuple(unread)

    def __call__(self, batch):
        from trino_tpu.compile.warmup import zeros_batch

        bridge = JoinBridge()
        sink = HashBuildSink(bridge, self.rkeys, self.build_schema)
        sink.add_input(zeros_batch(self.build_schema, self.build_cap))
        sink.finish()
        op = LookupJoinOperator(
            bridge, self.lkeys, self.kind, self.probe_schema,
            unread=self.unread,
        )
        op.add_input(batch)
        op.finish()
        for _ in range(8):
            if op.get_output() is None:
                break


@dataclasses.dataclass
class _KeyFilterSink:
    """A join's dynamic filter on its way under an aggregation
    (`LocalPlanner._sink_key_filter`): the side of the join it filters,
    the node whose output it stands on, what appends it to that node's
    chain, and whether the visit of that side put it there."""

    side: P.PlanNode
    at: P.PlanNode
    place: Callable[[List[Factory]], None]
    placed: bool = False


class LocalPlanner:
    def __init__(
        self,
        catalogs: CatalogManager,
        batch_rows: int = 1 << 20,
        target_splits: int = 1,
        remote_schemas: Optional[Dict[int, "Schema"]] = None,
        scan_slice: Optional[Tuple[int, int]] = None,
        dynamic_filtering: bool = True,
        stabilizer=None,
        mxu_join: bool = False,
        mxu_join_min_work: float = 16.0,
    ):
        """`remote_schemas` maps producer fragment id -> output Schema
        (with dictionaries) for RemoteSourceNode leaves; `scan_slice`
        (task_index, task_count) restricts scans to this task's share of
        the connector splits (the SourcePartitionedScheduler assignment,
        collapsed to deterministic round-robin). `stabilizer`
        (compile.shapes.ShapeStabilizer) pads scan chunks onto the
        session's capacity ladder and enables warmup-entry collection."""
        self.catalogs = catalogs
        self.batch_rows = batch_rows
        self.target_splits = target_splits
        self.remote_schemas = remote_schemas or {}
        self.scan_slice = scan_slice
        self.dynamic_filtering = dynamic_filtering
        self.stabilizer = stabilizer
        self.mxu_join = mxu_join
        self.mxu_join_min_work = float(mxu_join_min_work)
        self.pipelines: List[List[Factory]] = []
        self._next_key = 0
        self._warmup_entries: List = []
        self._stats_calc = None
        self._unread: Dict[int, frozenset] = {}
        # by `id` of a plan node: what appends a join's key filter to
        # the chain that ends in that node (a filter that goes under an
        # aggregation: `_visit_side` leaves it, `_visit` takes it)
        self._key_filters: Dict[int, object] = {}

    # -- public --
    def plan(self, root: P.PlanNode, decorrelated: int = 0) -> PhysicalPlan:
        """`decorrelated`: the correlated scalar aggregates the analysis
        of this statement decorrelated (the engine passes what
        `analyzer.decorrelated_scalar_aggregates()` found)."""
        self._unread = unread_join_outputs(root)
        chain, schema = self._visit(root)
        return PhysicalPlan(
            self.pipelines, chain, schema,
            warmup_entries=self._warmup_entries,
            decorrelated_scalar_aggregates=decorrelated,
        )

    # -- helpers --
    def _key(self) -> int:
        self._next_key += 1
        return self._next_key

    def _bind(self, e: Expr, schema: Schema) -> Bound:
        return ExprBinder([t for t, _ in schema], [d for _, d in schema]).bind(e)

    def _identity(self, schema: Schema) -> List[Bound]:
        return [
            self._bind(InputRef(i, t), schema) for i, (t, _) in enumerate(schema)
        ]

    # -- dispatch --
    def _visit(self, node: P.PlanNode) -> Tuple[List[Factory], Schema]:
        m = getattr(self, f"_visit_{type(node).__name__}", None)
        if m is None:
            raise NotImplementedError(f"no physical plan for {type(node).__name__}")
        chain, schema = m(node)
        place = self._key_filters.pop(id(node), None)
        if place is not None:
            place(chain)
        return chain, schema

    def _visit_OutputNode(self, node: P.OutputNode):
        return self._visit(node.child)

    def _visit_ScanNode(self, node: P.ScanNode):
        conn = self.catalogs.get(node.catalog)
        splits = conn.split_manager.get_splits(node.handle, self.target_splits)
        if self.scan_slice is not None:
            idx, count = self.scan_slice
            splits = splits[idx::count]
        columns = list(node.columns)
        page_source = conn.page_source
        batch_rows = self.batch_rows
        stabilizer = self.stabilizer
        schema: Schema = [
            (f.type, conn.metadata.column_dictionary(node.handle, c))
            for c, f in zip(node.columns, node.fields)
        ]

        def factory(ctx):
            return TableScanOperator(
                page_source, splits, columns, batch_rows, stabilizer=stabilizer
            )

        # predicted output capacity classes (main + tail) — consumed by
        # _append_fp to build warmup entries for downstream fused stages
        factory.out_caps = self._scan_caps(node)
        return [factory], schema

    def _scan_caps(self, node: P.PlanNode) -> Optional[Tuple[int, ...]]:
        """Census-predicted capacity classes of a scan's output batches,
        None when stabilization is off or stats are unusable."""
        if self.stabilizer is None:
            return None
        try:
            if self._stats_calc is None:
                from trino_tpu.sql.stats import StatsCalculator

                self._stats_calc = StatsCalculator(self.catalogs)
            rows = self._stats_calc.stats(node).row_count
        except Exception:
            return None
        if not rows or rows != rows or rows >= 1e9:  # missing-stats fallback
            return None
        return self.stabilizer.scan_classes(rows)

    def _visit_ValuesNode(self, node: P.ValuesNode):
        keys = [f.name or f"_c{i}" for i, f in enumerate(node.fields)]
        if len(set(keys)) != len(keys):
            # spooled join subtrees repeat column names (k, name, k,
            # name); a name-keyed dict would silently drop channels
            keys = [f"{k}_{i}" for i, k in enumerate(keys)]
        data = {k: [] for k in keys}
        for row in node.rows:
            for k, v in zip(keys, row):
                data[k].append(v)
        schema_t = [(k, f.type) for k, f in zip(keys, node.fields)]
        batch = RelBatch.from_pydict(schema_t, data)
        schema: Schema = [(c.type, c.dictionary) for c in batch.columns]

        def factory(ctx):
            return ValuesOperator([batch])

        if self.stabilizer is not None and batch.columns:
            factory.out_caps = (batch.capacity,)
        return [factory], schema

    # adaptive execution: a materialized subtree IS a values source;
    # its batch pads to bucket_capacity like any other, so re-planned
    # programs land on existing capacity-ladder shape classes
    _visit_SpooledValuesNode = _visit_ValuesNode

    # -- fusion helpers (program-count reduction; see compose_batch_fns) --
    def _cached_fp(self, flt: Optional[Bound], bounds: List[Bound],
                   schema: Schema, fingerprint) -> object:
        """Build (or reuse from the process-wide ProgramCache) the fused
        filter/project jit for a structurally-identified stage. Cache
        keys combine the expr-IR fingerprint with the input schema
        signature (dictionary values included); anything uncacheable —
        runtime dictionaries, non-structural reprs — builds a private
        jit exactly as before."""
        from trino_tpu.compile.cache import (
            PROGRAM_CACHE,
            expr_fingerprint,
            schema_cache_key,
        )

        fp = expr_fingerprint(fingerprint) if fingerprint is not None else None
        skey = schema_cache_key(schema)
        if fp is None or skey is None:
            return make_filter_project_fn(flt, bounds, name="FilterProjectOperator")
        return PROGRAM_CACHE.get_or_create(
            ("fp", fp, skey),
            lambda: make_filter_project_fn(
                flt, bounds, name="FilterProjectOperator"
            ),
        )

    @staticmethod
    def _read_row_bytes(schema: Schema, exprs) -> int:
        """Bytes a row of the columns `exprs` read (a column handed on
        as it is reads nothing): what a filter/project stage cannot
        avoid moving, for `filter_read_bytes`."""
        from trino_tpu.sql.optimizer import expr_refs

        read = set()
        for e in exprs:
            if e is not None and not isinstance(e, InputRef):
                read |= expr_refs(e)
        return sum(
            schema[c][0].dtype.itemsize * schema[c][0].lanes
            for c in read if not schema[c][0].is_nested
        )

    def _append_fp(self, chain: List[Factory], fn,
                   in_schema: Optional[Schema],
                   out_schema: Optional[Schema], read_row_bytes: int = 0) -> None:
        """Append a filter/project stage, folding it into a directly
        preceding one so adjacent stages share a device program. Also
        records the stage's warmup entry: the (possibly composed) jit,
        the schema feeding it, and the capacity classes predicted for
        the chain's source."""
        from trino_tpu.compile.cache import PROGRAM_CACHE
        from trino_tpu.exec.operators import compose_batch_fns

        prev = chain[-1] if chain else None
        pf = getattr(prev, "fused_fn", None)
        caps = getattr(prev, "out_caps", None)
        if pf is not None:
            chain.pop()
            read_row_bytes += getattr(prev, "read_row_bytes", 0)
            prev_entry = getattr(prev, "warmup_entry", None)
            if prev_entry is not None:
                # the folded stage dispatches as one program; its parts
                # must not be warmed separately
                self._warmup_entries.remove(prev_entry)
                in_schema = prev_entry.in_schema
            inner = fn
            k1, k2 = PROGRAM_CACHE.key_of(pf), PROGRAM_CACHE.key_of(inner)
            if k1 is not None and k2 is not None:
                fn = PROGRAM_CACHE.get_or_create(
                    ("compose", k1, k2),
                    lambda: compose_batch_fns(
                        pf, inner, name="FilterProjectOperator"
                    ),
                )
            else:
                fn = compose_batch_fns(pf, inner, name="FilterProjectOperator")

        def factory(ctx, fn=fn):
            return FilterProjectOperator(
                None, (), fn=fn, read_row_bytes=read_row_bytes
            )

        factory.fused_fn = fn
        factory.read_row_bytes = read_row_bytes
        # filter/project preserves capacity, so the source classes flow
        # through for any further folding above this stage
        factory.out_caps = caps
        if caps and in_schema is not None and out_schema is not None:
            from trino_tpu.compile.warmup import WarmupEntry

            entry = WarmupEntry(
                operator="FilterProjectOperator",
                fn=fn,
                in_schema=list(in_schema),
                out_dtypes=tuple(str(t) for t, _ in out_schema),
                capacities=tuple(caps),
            )
            factory.warmup_entry = entry
            self._warmup_entries.append(entry)
        chain.append(factory)

    def _record_kernel_warmup(self, operator: str, warmer, in_schema,
                              out_schema, caps) -> None:
        """Warmup entry for a blocking kernel (aggregation / join):
        the census predicted `caps` input classes; the warmer drives a
        throwaway operator so the shared kernel jits compile ahead of
        first touch. No-op when the census has no prediction."""
        if not caps:
            return
        from trino_tpu.compile.warmup import WarmupEntry

        self._warmup_entries.append(WarmupEntry(
            operator=operator,
            fn=warmer,
            in_schema=list(in_schema),
            out_dtypes=tuple(str(t) for t, _ in out_schema),
            capacities=tuple(caps),
        ))

    @staticmethod
    def _take_fused(chain: List[Factory]):
        """Pop a trailing fused filter/project stage so a blocking
        consumer (agg/sort/topn) can run it inside its own kernel."""
        prev = chain[-1] if chain else None
        pf = getattr(prev, "fused_fn", None)
        if pf is not None:
            chain.pop()
        return pf

    def _visit_RemoteSourceNode(self, node: P.RemoteSourceNode):
        """Exchange client as a source operator (ExchangeOperator.java:44;
        with merge_keys, MergeOperator.java:46). The execution context
        provides "make_remote_source": (fragment_ids) -> page source."""
        from trino_tpu.exec.exchange_ops import RemoteSourceOperator

        schemas = [self.remote_schemas[fid] for fid in node.fragment_ids]
        assert schemas and all(
            [t for t, _ in s] == [t for t, _ in schemas[0]] for s in schemas
        ), "remote source fragments must share one schema"
        schema: Schema = schemas[0]
        fragment_ids = tuple(node.fragment_ids)
        merge_keys = list(node.merge_keys) if node.merge_keys else None
        ladder = self.stabilizer.ladder if self.stabilizer is not None else None
        return [
            lambda ctx: RemoteSourceOperator(
                ctx["make_remote_source"](fragment_ids), merge_keys,
                ladder=ladder,
            )
        ], schema

    def _visit_FilterNode(self, node: P.FilterNode):
        chain, schema = self._visit(node.child)
        flt = self._bind(node.predicate, schema)
        fn = self._cached_fp(
            flt, self._identity(schema), schema, ("flt", repr(node.predicate))
        )
        self._append_fp(chain, fn, schema, schema,
                        self._read_row_bytes(schema, [node.predicate]))
        return chain, schema

    def _visit_ProjectNode(self, node: P.ProjectNode):
        # fuse a Filter directly below (ScanFilterAndProject discipline)
        child = node.child
        flt = None
        if isinstance(child, P.FilterNode):
            chain, schema = self._visit(child.child)
            flt = self._bind(child.predicate, schema)
        else:
            chain, schema = self._visit(child)
        bounds = [self._bind(e, schema) for e in node.exprs]
        fingerprint = (
            "proj",
            repr(child.predicate) if flt is not None else None,
            tuple(repr(e) for e in node.exprs),
        )
        fn = self._cached_fp(flt, bounds, schema, fingerprint)
        out_schema: Schema = [(b.type, b.dictionary) for b in bounds]
        self._append_fp(
            chain, fn, schema, out_schema, self._read_row_bytes(
                schema,
                [child.predicate if flt is not None else None, *node.exprs],
            ),
        )
        return chain, out_schema

    def _visit_AggregateNode(self, node: P.AggregateNode):
        mxu = self._try_mxu_join_agg(node)
        if mxu is not None:
            return mxu
        chain, schema = self._visit(node.child)
        if any(a.distinct for a in node.aggs):
            return self._distinct_agg(node, chain, schema)
        specs = [
            AggSpec(a.kind, a.arg_channel, a.out_type,
                    arg2_channel=a.arg2_channel, percentile=a.percentile,
                    separator=a.separator, arg3_channel=a.arg3_channel,
                    param=a.param, post=a.post)
            for a in node.aggs
        ]
        groups = list(node.group_channels)
        step = node.step
        key_ranges = node.key_ranges
        # input capacity classes before the fused stage is absorbed
        # (filter/project preserves capacity, so they flow through)
        src_caps = getattr(chain[-1], "out_caps", None) if chain else None
        pre = self._take_fused(chain)
        chain.append(
            lambda ctx: HashAggregationOperator(
                groups, specs, schema, step=step, memory_context=_mem_ctx(ctx),
                deferred_checks=ctx.setdefault("deferred_checks", []),
                pre_fn=pre, key_ranges=key_ranges,
            )
        )
        if step == "partial":
            from trino_tpu.exec.operators import partial_output_schema

            out_schema = partial_output_schema(specs, groups, schema)
            self._record_kernel_warmup(
                "HashAggregationOperator",
                _AggWarmer(groups, specs, schema, step, key_ranges),
                schema, out_schema, src_caps,
            )
            return chain, out_schema
        # min/max/any and the holistic kinds return a value from the
        # argument column, so its dictionary must ride along (a string
        # result without its dictionary renders as raw codes)
        def _out_dict(a):
            if (
                a.kind in ("min", "max", "any", "min_by", "max_by",
                           "approx_percentile")
                and a.arg_channel is not None
            ):
                return schema[a.arg_channel][1]
            if a.kind == "listagg":
                # created at execution time; plan-time string ops over
                # it must fail loudly (expr/compile._null_of)
                from trino_tpu.block import RuntimeDictionary

                return RuntimeDictionary()
            return None

        out_schema: Schema = [schema[c] for c in node.group_channels] + [
            (a.out_type, _out_dict(a)) for a in node.aggs
        ]
        if step == "final":
            # keys and min/max/any results keep the dictionaries that
            # rode through the state wire format
            out_schema = [schema[c] for c in range(len(groups))] + [
                (a.out_type, schema[len(groups) + 2 * i][1])
                for i, a in enumerate(node.aggs)
            ]
        self._record_kernel_warmup(
            "HashAggregationOperator",
            _AggWarmer(groups, specs, schema, step, key_ranges),
            schema, out_schema, src_caps,
        )
        return chain, out_schema

    def _try_mxu_join_agg(self, node: P.AggregateNode):
        """MXU join-project selection (ops/mxu_join.py): a single-step
        grouped aggregate directly over an inner single-integer-key
        equi-join, all group columns build-side, all aggregate
        arguments probe-side (or COUNT(*)), kinds in sum/count — the
        shape where the pair sum factors through the key and the join
        never needs to expand. Returns (chain, schema) when selected,
        None to fall through to the standard agg-over-join plan."""
        if not self.mxu_join:
            return None
        # the column pruner routinely leaves an identity Project
        # (pure channel references) between the aggregate and the
        # join — look through it, composing the channel map
        join = node.child
        cmap: Optional[List[int]] = None
        while isinstance(join, P.ProjectNode) and all(
            isinstance(e, InputRef) for e in join.exprs
        ):
            m = [e.index for e in join.exprs]
            cmap = m if cmap is None else [m[c] for c in cmap]
            join = join.child
        if not isinstance(join, P.JoinNode):
            return None

        def tr(ch: int) -> int:
            return cmap[ch] if cmap is not None else ch

        if (
            join.kind != "inner"
            or join.residual is not None
            or len(join.left_keys) != 1
            or len(join.right_keys) != 1
            or getattr(join, "spill_build", False)
        ):
            return None
        if node.step != "single" or not node.group_channels or not node.aggs:
            return None
        probe_width = len(join.left.fields)
        if any(tr(ch) < probe_width for ch in node.group_channels):
            return None
        for side, ch in ((join.left, join.left_keys[0]),
                         (join.right, join.right_keys[0])):
            t = side.fields[ch].type
            if t.is_nested or t.lanes != 1 or not t.is_integerlike:
                return None
        for a in node.aggs:
            if a.kind not in ("sum", "count", "count_star") or a.distinct:
                return None
            if (
                a.arg2_channel is not None or a.arg3_channel is not None
                or a.post is not None or a.out_type != T.BIGINT
            ):
                return None
            if a.kind == "count_star":
                if a.arg_channel is not None:
                    return None
                continue
            if a.arg_channel is None or tr(a.arg_channel) >= probe_width:
                return None
            at = join.left.fields[tr(a.arg_channel)].type
            if at.is_nested or at.lanes != 1 or not at.is_integerlike:
                return None
        # work gate: expected pairs per probe row (fanout) x build key
        # NDV must clear the threshold — below it the expansion is
        # cheap and the standard join keeps its dynamic-filter and
        # warmup advantages
        try:
            if self._stats_calc is None:
                from trino_tpu.sql.stats import StatsCalculator

                self._stats_calc = StatsCalculator(self.catalogs)
            bs = self._stats_calc.stats(join.right)
            rows = float(bs.row_count or 0.0)
            ndv = float(bs.col(join.right_keys[0]).ndv or rows)
            fanout = rows / max(ndv, 1.0)
            if fanout * ndv < self.mxu_join_min_work:
                return None
        except Exception:
            return None

        build_chain, build_schema = self._visit(join.right)
        probe_chain, probe_schema = self._visit(join.left)
        key = self._key()

        def bridge_of(ctx) -> JoinBridge:
            return ctx.setdefault(key, JoinBridge())

        rkeys = [join.right_keys[0]]
        # no memory context: this path has no grace-mode probe, so the
        # build sink must never flip to spill under pool pressure
        build_chain.append(
            lambda ctx: HashBuildSink(bridge_of(ctx), rkeys, build_schema)
        )
        self.pipelines.append(build_chain)
        lkey = join.left_keys[0]
        aggs = [
            dataclasses.replace(a, arg_channel=tr(a.arg_channel))
            if a.arg_channel is not None else a
            for a in node.aggs
        ]
        groups_b = [tr(ch) - probe_width for ch in node.group_channels]
        probe_chain.append(
            lambda ctx: MxuJoinAggOperator(bridge_of(ctx), lkey, aggs, groups_b)
        )
        # final grouping over the per-build-row partials: SUM of each
        # partial column (NULL partials drop out, so SUM-over-only-NULLs
        # is NULL and COUNT partials — always valid — total exactly)
        g = len(groups_b)
        partial_schema: Schema = [build_schema[ch] for ch in groups_b] + [
            (a.out_type, None) for a in aggs
        ]
        specs = [
            AggSpec("sum", g + i, a.out_type) for i, a in enumerate(aggs)
        ]
        probe_chain.append(
            lambda ctx: HashAggregationOperator(
                list(range(g)), specs, partial_schema,
                memory_context=_mem_ctx(ctx),
            )
        )
        from trino_tpu.runtime.metrics import METRICS

        METRICS.increment("skew.mxu_join_selected")
        out_schema: Schema = partial_schema[:g] + [
            (a.out_type, None) for a in aggs
        ]
        return probe_chain, out_schema

    def _distinct_agg(self, node: P.AggregateNode, chain, schema: Schema):
        """DISTINCT aggregates via dedup-then-aggregate (the
        MarkDistinct/MultipleDistinctAggregationToMarkDistinct analogue,
        restricted to the single-distinct shape)."""
        if len(node.aggs) != 1:
            raise NotImplementedError(
                "DISTINCT aggregates must be the only aggregate"
            )
        a = node.aggs[0]
        if a.arg_channel is None:
            raise NotImplementedError("count(distinct *) is meaningless")
        dedup_channels = list(node.group_channels) + [a.arg_channel]
        chain.append(
            lambda ctx: HashAggregationOperator(dedup_channels, [], schema)
        )
        dedup_schema: Schema = [schema[c] for c in dedup_channels]
        k = len(node.group_channels)
        specs = [AggSpec(a.kind, k, a.out_type)]
        groups = list(range(k))
        chain.append(
            lambda ctx: HashAggregationOperator(groups, specs, dedup_schema)
        )
        out_schema: Schema = dedup_schema[:k] + [(a.out_type, None)]
        return chain, out_schema

    def _visit_JoinNode(self, node: P.JoinNode):
        # (called when the plan is instantiated: `key` is set by then)
        def bridge_of(ctx) -> JoinBridge:
            return ctx.setdefault(key, JoinBridge())

        sink = self._sink_key_filter(node, bridge_of)
        build_chain, build_schema = self._visit_side(node.right, sink)
        probe_chain, probe_schema = self._visit_side(node.left, sink)
        # whether the key filter is in its place under the aggregation
        # (else it stands where it always stood)
        sunk = sink is not None and sink.placed
        build_caps = (
            getattr(build_chain[-1], "out_caps", None) if build_chain
            else None
        )
        probe_caps = (
            getattr(probe_chain[-1], "out_caps", None) if probe_chain
            else None
        )
        key = self._key()
        if node.kind == "cross":
            build_chain.append(
                lambda ctx: CrossJoinBuildSink(bridge_of(ctx), build_schema)
            )
            self.pipelines.append(build_chain)
            probe_chain.append(lambda ctx: CrossJoinOperator(bridge_of(ctx)))
            return probe_chain, probe_schema + build_schema
        if node.kind in ("semi", "anti", "left") and node.build_left:
            return self._join_built_left(
                node, probe_chain, probe_schema, build_chain, build_schema,
                bridge_of, sunk,
            )
        rkeys = list(node.right_keys)
        self._end_in_build(node, build_chain, rkeys, build_schema, bridge_of)
        residual_fn = None
        if node.residual is not None:
            residual_fn = make_residual_fn(
                self._bind(node.residual, probe_schema + build_schema)
            )
        lkeys = list(node.left_keys)
        kind = node.kind
        if kind in ("inner", "semi") and self.dynamic_filtering and not sunk:
            from trino_tpu.exec.operators import DynamicFilterOperator

            # connector reuse: when the probe side is a bare scan, feed
            # the build-side key domains into the scan's split handles
            # (evaluated lazily at first probe page — the build pipeline
            # has completed by then) so parquet row-group pruning and
            # constraint masks apply to dynamic-filter bounds too. The
            # DynamicFilterOperator below still enforces, so an
            # unpopulated bridge only costs the pruning, never rows.
            if isinstance(node.left, P.ScanNode) and len(probe_chain) == 1:
                from trino_tpu.exec.operators import (
                    dynamic_filter_constraints,
                )

                scan = node.left
                key_names = [scan.columns[c] for c in lkeys]
                key_types = [scan.fields[c].type for c in lkeys]
                scan_factory = probe_chain[0]

                def df_scan_factory(ctx, _f=scan_factory):
                    op = _f(ctx)
                    if hasattr(op, "set_runtime_constraints"):
                        op.set_runtime_constraints(
                            lambda: dynamic_filter_constraints(
                                bridge_of(ctx), key_types, key_names
                            )
                        )
                    return op

                caps = getattr(scan_factory, "out_caps", None)
                if caps is not None:
                    df_scan_factory.out_caps = caps
                probe_chain[0] = df_scan_factory
            key_fill = self._build_key_fill(node.right, rkeys)
            key_ordered = self._scan_key_ordered(node.left, lkeys)
            probe_chain.append(
                lambda ctx: DynamicFilterOperator(
                    bridge_of(ctx), lkeys, key_fill=key_fill,
                    key_ordered=key_ordered,
                )
            )
        unread = tuple(sorted(self._unread.get(id(node), ())))
        probe_chain.append(
            lambda ctx: LookupJoinOperator(
                bridge_of(ctx), lkeys, kind, probe_schema,
                residual_fn=residual_fn, unread=unread,
            )
        )
        if node.kind in ("semi", "anti"):
            out_schema = probe_schema
        elif node.kind in ("mark", "mark_exists"):
            out_schema = probe_schema + [(T.BOOLEAN, None)]
        else:
            out_schema = probe_schema + build_schema
        # residual joins skip: the residual program binds to this plan's
        # expressions, which the dead-batch warmer does not replicate
        if probe_caps and build_caps and residual_fn is None:
            self._record_kernel_warmup(
                "LookupJoinOperator",
                _JoinWarmer(lkeys, rkeys, kind, probe_schema,
                            build_schema, build_caps[0], unread),
                probe_schema, out_schema, probe_caps,
            )
        return probe_chain, out_schema

    def _end_in_build(self, node: P.JoinNode, chain, keys, schema,
                      bridge_of) -> None:
        """`chain` as the pipeline that builds `node`'s lookup side."""
        # adaptive spill-mode annotation (skewed/oversized build side):
        # grace partitions open before the first batch arrives
        force_spill = bool(getattr(node, "spill_build", False))
        chain.append(
            lambda ctx: HashBuildSink(
                bridge_of(ctx), keys, schema,
                memory_context=_mem_ctx(ctx), force_spill=force_spill,
            )
        )
        self.pipelines.append(chain)

    def _build_key_fill(self, build: P.PlanNode, keys) -> Optional[float]:
        """The share of its ONE key's value range that a build side's
        rows are estimated to fill (rows over high - low + 1 of the
        statistics), for `DynamicFilterOperator(key_fill=)`; None where
        the statistics cannot say."""
        if len(keys) != 1:
            return None
        try:
            if self._stats_calc is None:
                from trino_tpu.sql.stats import StatsCalculator

                self._stats_calc = StatsCalculator(self.catalogs)
            stats = self._stats_calc.stats(build)
            key = stats.col(keys[0])
            width = float(key.high) - float(key.low) + 1.0
            fill = float(stats.row_count) / width
        except Exception:
            return None
        return fill if width >= 1.0 and fill == fill else None

    def _scan_key_ordered(self, side: P.PlanNode, keys) -> bool:
        """Whether the ONE key of a dynamic filter in front of `side` is a
        column its connector lists as stored in order
        (`TableStatistics.ordered`) of a scan with nothing between the
        scan and the filter that moves rows: filters (they mask, or the
        connector scans a filtered copy: a subsequence) and projections
        that hand the column on as it is. Anything else, a join among
        them, is not asked."""
        if len(keys) != 1:
            return False
        ch = keys[0]
        while not isinstance(side, P.ScanNode):
            if isinstance(side, P.ProjectNode):
                e = side.exprs[ch]
                if not isinstance(e, InputRef):
                    return False
                ch = e.index
            elif not isinstance(side, P.FilterNode):
                return False
            side = side.child
        # (a connector with no statistics lists nothing: connectors/spi;
        # a scan's `columns` are the connector's names, one a field)
        stats = self.catalogs.get(side.catalog).metadata.get_table_statistics(
            side.handle
        )
        return side.columns[ch] in stats.ordered

    def _sink_key_filter(self, node: P.JoinNode, bridge_of) -> Optional[_KeyFilterSink]:
        """Where the plan sends `node`'s dynamic filter under an
        aggregation of the side it filters
        (`JoinNode.filter_under_aggregate`, `plan.key_filter_target`):
        that side, the node whose output the filter stands on, and what
        appends it to a chain, for `_visit_side`. The same operator with
        the same hints as at the side's end: `key_fill` of the side that
        gives the keys, `key_ordered` of the scan it now stands on."""
        if not (node.filter_under_aggregate and self.dynamic_filtering):
            return None
        target = P.key_filter_target(node)
        if target is None:  # (the plan changed under the flag: exchanges)
            return None
        from trino_tpu.exec.operators import DynamicFilterOperator

        at, channels, _ = target
        side, _, source, source_keys = P.filter_sides(node)
        reverse = side is node.right
        channels = list(channels)
        key_fill = self._build_key_fill(source, list(source_keys))
        key_ordered = self._scan_key_ordered(at, channels)

        def place(chain: List[Factory]) -> None:
            chain.append(
                lambda ctx: DynamicFilterOperator(
                    bridge_of(ctx), channels, reverse=reverse,
                    key_fill=key_fill, key_ordered=key_ordered,
                    under_aggregate=True,
                )
            )

        return _KeyFilterSink(side, at, place)

    def _visit_side(self, side: P.PlanNode, sink: Optional[_KeyFilterSink]):
        """Visit one side of a join; where it is the side `sink` filters,
        with the filter left for the visit of the node it stands on
        (`_visit` takes it from `_key_filters`)."""
        if sink is None or sink.side is not side:
            return self._visit(side)
        self._key_filters[id(sink.at)] = sink.place
        out = self._visit(side)
        sink.placed = self._key_filters.pop(id(sink.at), None) is None
        return out

    def _join_built_left(self, node: P.JoinNode, left_chain, left_schema,
                         right_chain, right_schema, bridge_of, sunk=False):
        """A semi-, anti- or LEFT join whose PRESERVED side is the lookup
        (`JoinNode.build_left`): the left's pipeline ends in the build,
        the other side's scan is filtered by the left's keys (a row
        whose key no left row has decides nothing, for EXISTS and NOT
        EXISTS alike, and pairs with nothing under a LEFT join) and
        probes. A semi- or anti-join puts out the left rows some pair
        flagged (semi) or none did (anti) when its input ends, and of
        the pairs only what the residual reads is gathered; a LEFT join
        puts out its pairs as they come, left columns first, and the
        left rows no pair flagged, with NULLs, when its input ends."""
        from trino_tpu.exec.operators import DynamicFilterOperator
        from trino_tpu.sql.optimizer import expr_refs

        lkeys, rkeys = list(node.left_keys), list(node.right_keys)
        kind = node.kind
        self._end_in_build(node, left_chain, lkeys, left_schema, bridge_of)
        residual_fn = None
        read = frozenset()
        if node.residual is not None:
            residual_fn = make_residual_fn(
                self._bind(node.residual, left_schema + right_schema)
            )
            # pairs are laid out probe (right) first, then build (left)
            width_l = len(left_schema)
            read = frozenset(
                c + len(right_schema) if c < width_l else c - width_l
                for c in expr_refs(node.residual)
            )
        unread = () if kind == "left" else tuple(sorted(
            frozenset(range(len(left_schema) + len(right_schema))) - read
        ))
        if self.dynamic_filtering and not sunk:
            key_fill = self._build_key_fill(node.left, lkeys)
            key_ordered = self._scan_key_ordered(node.right, rkeys)
            right_chain.append(
                lambda ctx: DynamicFilterOperator(
                    bridge_of(ctx), rkeys, reverse=True, key_fill=key_fill,
                    key_ordered=key_ordered,
                )
            )
        right_chain.append(
            lambda ctx: LookupJoinOperator(
                bridge_of(ctx), rkeys, kind, right_schema,
                residual_fn=residual_fn, unread=unread, build_preserved=True,
            )
        )
        if kind == "left":
            return right_chain, left_schema + right_schema
        return right_chain, left_schema

    def _visit_WindowNode(self, node: P.WindowNode):
        from trino_tpu.exec.operators import WindowOperator

        chain, schema = self._visit(node.child)
        partition = list(node.partition_channels)
        order = list(node.order_keys)
        fns = list(node.functions)
        frame = node.frame
        chain.append(
            lambda ctx: WindowOperator(partition, order, fns, frame, schema)
        )
        out_schema: Schema = list(schema)
        for f in fns:
            d = None
            if f.arg_channel is not None and f.kind in (
                "lead", "lag", "first_value", "last_value", "min", "max"
            ):
                d = schema[f.arg_channel][1]
            out_schema.append((f.out_type, d))
        return chain, out_schema

    def _visit_EnforceSingleRowNode(self, node: P.EnforceSingleRowNode):
        from trino_tpu.exec.operators import EnforceSingleRowOperator

        chain, schema = self._visit(node.child)
        chain.append(lambda ctx: EnforceSingleRowOperator(schema))
        return chain, schema

    def _visit_UnnestNode(self, node: P.UnnestNode):
        from trino_tpu.exec.unnest import UnnestOperator

        chain, schema = self._visit(node.child)
        channels = list(node.array_channels)
        ordinality = node.ordinality
        chain.append(
            lambda ctx: UnnestOperator(channels, ordinality, schema)
        )
        out_schema: Schema = list(schema)
        for ch in channels:
            elem_t = schema[ch][0].element
            out_schema.append((elem_t, schema[ch][1]))
        if ordinality:
            out_schema.append((T.BIGINT, None))
        return chain, out_schema

    def _visit_MatchRecognizeNode(self, node: P.MatchRecognizeNode):
        from trino_tpu.exec.match_recognize import MatchRecognizeOperator

        chain, schema = self._visit(node.child)
        # bind DEFINE predicates over the extended schema (child +
        # shifted copies); evaluation is one device program per define,
        # fused by XLA (exec/match_recognize.py)
        ext_schema: Schema = list(schema) + [
            schema[ch] for ch, _off in node.shifts
        ]
        define_fns = [
            (var, self._bind(pred, ext_schema).fn)
            for var, pred in node.defines
        ]
        chain.append(
            lambda ctx: MatchRecognizeOperator(node, schema, define_fns)
        )
        out_schema: Schema = []
        for ch in node.partition_channels:
            out_schema.append(schema[ch])
        for m in node.measures:
            if m.kind == "classifier":
                out_schema.append((m.out_type, None))  # runtime dict
            elif m.channel is not None:
                out_schema.append((m.out_type, schema[m.channel][1]))
            else:
                out_schema.append((m.out_type, None))
        return chain, out_schema

    def _visit_SortNode(self, node: P.SortNode):
        chain, schema = self._visit(node.child)
        keys = list(node.keys)
        pre = self._take_fused(chain)
        chain.append(
            lambda ctx: SortOperator(
                keys, schema, memory_context=_mem_ctx(ctx), pre_fn=pre
            )
        )
        return chain, schema

    def _visit_TopNNode(self, node: P.TopNNode):
        chain, schema = self._visit(node.child)
        keys = list(node.keys)
        count = node.count
        pre = self._take_fused(chain)
        chain.append(lambda ctx: TopNOperator(keys, count, schema, pre_fn=pre))
        return chain, schema

    def _visit_LimitNode(self, node: P.LimitNode):
        chain, schema = self._visit(node.child)
        count, offset = node.count, node.offset
        chain.append(lambda ctx: LimitOperator(count, offset))
        return chain, schema

    def _visit_UnionAllNode(self, node: P.UnionAllNode):
        sink_keys = []
        schemas = []
        for child in node.inputs:
            chain, schema = self._visit(child)
            schemas.append(schema)
            key = self._key()
            sink_keys.append(key)
            chain.append(
                lambda ctx, key=key: ctx.setdefault(key, BufferSink())
            )
            self.pipelines.append(chain)
        # string columns must agree on dictionaries across inputs for the
        # shared buffer to be bindable downstream; an all-NULL input
        # (None/empty dictionary, e.g. grouping-set NULL keys) is
        # compatible with anything
        def _dict_rank(d):
            return 0 if d is None or len(d) == 0 else 1

        out_schema = list(schemas[0])
        for s in schemas[1:]:
            for i, ((t0, d0), (t1, d1)) in enumerate(zip(out_schema, s)):
                if not t0.is_string:
                    continue
                if _dict_rank(d0) == 0:
                    out_schema[i] = (t0, d1)
                elif _dict_rank(d1) == 0 or d0 == d1:
                    continue
                else:
                    raise NotImplementedError(
                        "UNION of string columns with differing dictionaries"
                    )
        return [
            lambda ctx: BufferSource([ctx[k] for k in sink_keys])
        ], out_schema
