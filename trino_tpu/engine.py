"""In-process query engine: SQL text in, rows out.

Analogue of Trino's LocalQueryRunner (main/testing/LocalQueryRunner.java:264
— plan and execute SQL fully in-process with real operators, SURVEY.md
§4.2) plus the session/catalog surface of Session + MetadataManager.
The distributed runner (coordinator/worker split over fragments) layers
on top of the same plans.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

from trino_tpu import types as T
# Session is declared in config.py and imported from here by its users
from trino_tpu.config import SYSTEM_PROPERTIES, Session
from trino_tpu.connectors.spi import CatalogManager, Connector
from trino_tpu.exec import CollectorSink, Driver, Pipeline
from trino_tpu.runtime.tracing import host_span, phase_span
from trino_tpu.sql import ast
from trino_tpu.sql.analyzer import AnalysisError, Analyzer
from trino_tpu.sql.local_planner import LocalPlanner
from trino_tpu.sql.parser import parse
from trino_tpu.sql.plan import OutputNode, explain_text


@dataclasses.dataclass
class MaterializedResult:
    """QueryAssertions' MaterializedResult analogue."""

    rows: List[list]
    column_names: List[str]
    column_types: List[T.DataType]
    # transaction protocol surface (StatementClientV1's
    # X-Trino-Started-Transaction-Id / Clear-Transaction-Id headers)
    started_transaction_id: Optional[str] = None
    cleared_transaction: bool = False
    # prepared-statement protocol surface (X-Trino-Added-Prepare /
    # X-Trino-Deallocated-Prepare response headers)
    added_prepare: Optional[tuple] = None
    deallocated_prepare: Optional[str] = None
    # which data plane executed the query: "local" (single-process),
    # "mesh" (ICI collectives), "http" (page exchange), "fte" (spooled).
    # Surfaces the silent mesh fallback (VERDICT r2 weak #4).
    data_plane: str = "local"
    # what the runner measured of a tracked query, from the stamps its
    # spans use: query_id, elapsed_ms, plan_ms, cpu_ms (the protocol's
    # StatementStats are filled from it)
    stats: Optional[dict] = None

    def only_value(self):
        assert len(self.rows) == 1 and len(self.rows[0]) == 1, self.rows
        return self.rows[0][0]


def _raise_deferred_checks(ctx: dict) -> None:
    """Assertions deferred to the end-of-query sync point (the results
    are already materialized, so these bools are cheap)."""
    for flag, msg in ctx.get("deferred_checks", ()):
        if bool(flag):
            raise RuntimeError(msg)


class LocalQueryRunner:
    def __init__(
        self,
        session: Optional[Session] = None,
        access_control=None,
    ):
        from trino_tpu.security import AllowAllAccessControl, Identity
        from trino_tpu.transaction import TransactionManager

        self.session = session or Session()
        self.catalogs = CatalogManager()
        # PREPARE store: name -> (ast statement, formatted text); the
        # HTTP protocol's prepared-statement headers mirror this
        self._prepared: Dict[str, tuple] = {}
        self._request_prepared: Optional[Dict[str, str]] = None
        # canonical text -> (OutputNode, PhysicalPlan): re-executing a
        # cached query reuses every jitted device program (the
        # reference's expression/operator caches keyed on expression,
        # §2.9); serving/plan_cache.py owns keying/LRU/counters
        from trino_tpu.serving.plan_cache import PlanCache

        self._plan_cache = PlanCache()
        # dtype vector of the current EXECUTE's bound parameters (part
        # of the plan-cache key; set around the re-dispatch). Thread-
        # local: the HTTP server runs concurrent statements on one
        # runner, and one thread's EXECUTE must not perturb another
        # thread's cache key.
        import threading as _threading

        self._bound_dtypes_tls = _threading.local()
        from trino_tpu.runtime.events import EventListenerManager

        self.event_listeners = EventListenerManager()
        self.event_listeners.register_metrics()
        # per-query compile attribution + the xla_compile_duration_s
        # histogram need the jax.monitoring hook from process start,
        # not just from the first EXPLAIN ANALYZE
        from trino_tpu.runtime.metrics import install_xla_compile_listener

        install_xla_compile_listener()
        self._query_seq = 0
        # observability surfaces filled per query: the execution ctx's
        # memory pool (peak watermark) and the last completed span tree
        self._last_pool = None
        self._last_trace: Optional[tuple] = None
        self.access_control = access_control or AllowAllAccessControl()
        self.transactions = TransactionManager(self.catalogs)
        self._current_txn: Optional[str] = None
        import threading as _threading

        # per-request identity override (HTTP front passes the
        # authenticated principal; the runner is shared across threads)
        self._identity_override = _threading.local()
        # per-statement active transaction (explicit protocol threading)
        self._stmt_txn = _threading.local()

    @property
    def identity(self):
        from trino_tpu.security import Identity

        override = getattr(self._identity_override, "value", None)
        return override or Identity(self.session.user)

    def _check_scans(self, plan) -> None:
        """AccessControl over every table the plan reads (the analyzer
        already resolved views/CTEs away, so ScanNodes are the full
        read set — StatementAnalyzer's table references)."""
        from trino_tpu.sql.plan import ScanNode

        def walk(node):
            if isinstance(node, ScanNode):
                h = node.handle
                self.access_control.check_can_select(
                    self.identity, h.catalog, h.schema, h.table,
                    node.columns,
                )
            for c in node.children():
                walk(c)

        walk(plan)

    def register_catalog(self, name: str, connector: Connector) -> None:
        self.catalogs.register(name, connector)

    # -- entry point --
    def execute(
        self, sql: str, identity=None, transaction_id: Optional[str] = None,
        prepared: Optional[Dict[str, str]] = None,
        queued_ns: Optional[int] = None,
    ) -> MaterializedResult:
        """`identity` overrides the session user for this statement (the
        HTTP front passes the authenticated principal). `queued_ns` is
        how long the caller held the statement before this call (the
        server's admission and hand-off); it goes onto the query span.

        `transaction_id` selects EXPLICIT transaction threading — the
        protocol model, where each client connection carries its own
        transaction id (X-Trino-Transaction-Id) and the shared runner
        holds no cross-client state. Pass the sentinel "NONE" for an
        autocommit statement in explicit mode. When None (embedded
        use), the runner's own session transaction applies."""
        t_parse = time.perf_counter_ns()
        with host_span("phase.parse"):
            stmt = parse(sql)
        # when the statement entered, and (parse, queued) nanoseconds
        # before the query span opens
        self._stmt_txn.entered_ns = t_parse
        self._stmt_txn.before_ns = (
            time.perf_counter_ns() - t_parse, queued_ns or 0
        )
        explicit = transaction_id is not None
        active = (
            None if transaction_id in (None, "NONE") else transaction_id
        )
        if not explicit:
            active = self._current_txn
        if identity is not None:
            self._identity_override.value = identity
        self._stmt_txn.value = active
        self._request_prepared = prepared
        try:
            return self._dispatch(stmt, sql, active, explicit)
        finally:
            self._stmt_txn.value = None
            self._request_prepared = None
            if identity is not None:
                self._identity_override.value = None

    def _active_txn(self) -> Optional[str]:
        return getattr(self._stmt_txn, "value", None)

    def _check_writable(self) -> None:
        txn = self._active_txn()
        if txn is not None and self.transactions.is_read_only(txn):
            from trino_tpu.transaction import TransactionError

            raise TransactionError(
                "READ_ONLY_VIOLATION: cannot write in a read-only transaction"
            )

    def _dispatch(
        self, stmt, sql: str, active: Optional[str], explicit: bool
    ) -> MaterializedResult:
        from trino_tpu.transaction import TransactionError

        self.access_control.check_can_execute_query(self.identity)
        if isinstance(stmt, ast.Prepare):
            # PREPARE name FROM stmt (tree/Prepare.java:25; the protocol
            # threads these via X-Trino-Prepared-Statement headers —
            # runtime/server mirrors this session store per request)
            from trino_tpu.sql.formatter import format_statement

            try:
                text = format_statement(stmt.statement)
            except Exception:
                text = stmt.sql or ""
            self._prepared[stmt.name] = (stmt.statement, text)
            res = MaterializedResult([[True]], ["result"], [T.BOOLEAN])
            res.added_prepare = (stmt.name, text)
            return res
        if isinstance(stmt, ast.ExecuteStmt):
            # request-carried statements (X-Trino-Prepared-Statement)
            # take precedence: they are CLIENT-session state, while the
            # instance store is shared across every caller
            hit = None
            if self._request_prepared:
                text = self._request_prepared.get(stmt.name)
                if text is not None:
                    hit = (parse(text), text)
            if hit is None:
                hit = self._prepared.get(stmt.name)
            if hit is None:
                raise ValueError(
                    f"Prepared statement not found: {stmt.name}"
                )
            # typed binding check BEFORE substitution: arity and dtype
            # mismatches fail here with position/expected/got instead of
            # surfacing as an analyzer error deep inside the spliced
            # statement (serving/params.py)
            from trino_tpu.serving.params import check_parameters

            dtypes = check_parameters(
                hit[0], stmt.parameters, self.catalogs,
                self.session.catalog, self.session.schema,
            )
            body = ast.substitute_parameters(hit[0], stmt.parameters)
            # the plan-cache key canonicalizes the BOUND statement, so
            # distinct bindings plan separately; the dtype vector rides
            # along as its own key component (serving/plan_cache.py)
            prior = getattr(self._bound_dtypes_tls, "value", None)
            self._bound_dtypes_tls.value = tuple(dtypes)
            try:
                return self._dispatch(body, sql, active, explicit)
            finally:
                self._bound_dtypes_tls.value = prior
        if isinstance(stmt, ast.Deallocate):
            if stmt.name not in self._prepared:
                raise ValueError(
                    f"Prepared statement not found: {stmt.name}"
                )
            del self._prepared[stmt.name]
            res = MaterializedResult([[True]], ["result"], [T.BOOLEAN])
            res.deallocated_prepare = stmt.name
            return res
        if isinstance(stmt, ast.StartTransaction):
            if active is not None:
                raise TransactionError("transaction already in progress")
            new_txn = self.transactions.begin(stmt.read_only)
            if not explicit:
                self._current_txn = new_txn
            return MaterializedResult(
                [[True]], ["result"], [T.BOOLEAN],
                started_transaction_id=new_txn,
            )
        if isinstance(stmt, ast.Commit):
            if active is None:
                raise TransactionError("NOT_IN_TRANSACTION: no transaction in progress")
            try:
                self.transactions.commit(active)
            finally:
                # a failed commit still ends the transaction (the
                # reference's semantics) — never wedge the session
                if not explicit:
                    self._current_txn = None
                self._invalidate_plans()
            return MaterializedResult(
                [[True]], ["result"], [T.BOOLEAN], cleared_transaction=True
            )
        if isinstance(stmt, ast.Rollback):
            if active is None:
                raise TransactionError("NOT_IN_TRANSACTION: no transaction in progress")
            try:
                self.transactions.rollback(active)
            finally:
                if not explicit:
                    self._current_txn = None
            return MaterializedResult(
                [[True]], ["result"], [T.BOOLEAN], cleared_transaction=True
            )
        if isinstance(stmt, ast.Query):
            return self._run_tracked(sql, stmt)
        if isinstance(stmt, ast.ExplainStatement):
            if stmt.analyze:
                return self._explain_analyze(stmt.query)
            plan = self._analyze(stmt.query)
            return MaterializedResult(
                [[explain_text(plan)]], ["Query Plan"], [T.VARCHAR]
            )
        if isinstance(stmt, ast.CreateTable):
            from trino_tpu.connectors.spi import ColumnMetadata
            from trino_tpu.sql.analyzer import resolve_type

            cat, conn, schema, table = self._resolve_target(stmt.table)
            self.access_control.check_can_create_table(
                self.identity, conn.name, schema, table
            )
            self._check_writable()
            cols = [
                ColumnMetadata(n, resolve_type(t)) for n, t in stmt.columns
            ]
            conn.metadata.create_table(schema, table, cols)
            self._invalidate_plans(table=(cat, schema, table))
            return MaterializedResult([[True]], ["result"], [T.BOOLEAN])
        if isinstance(stmt, ast.CreateTableAs):
            return self._execute_ctas(stmt)
        if isinstance(stmt, ast.Insert):
            return self._execute_insert(stmt.table, stmt.columns, stmt.query)
        if isinstance(stmt, ast.Delete):
            return self._execute_rewrite_dml(stmt.table, stmt.where, None)
        if isinstance(stmt, ast.Merge):
            return self._execute_merge(stmt)
        if isinstance(stmt, ast.Update):
            names = [c for c, _ in stmt.assignments]
            if len(set(names)) != len(names):
                raise AnalysisError("multiple assignments for the same column")
            return self._execute_rewrite_dml(
                stmt.table, stmt.where, dict(stmt.assignments)
            )
        if isinstance(stmt, ast.DropTable):
            cat, conn, schema, table = self._resolve_target(stmt.table)
            self.access_control.check_can_drop_table(
                self.identity, conn.name, schema, table
            )
            self._check_writable()
            handle = conn.metadata.get_table_handle(schema, table)
            if handle is None:
                raise AnalysisError(f"table {schema}.{table} does not exist")
            conn.metadata.drop_table(handle)
            self._invalidate_plans(table=(cat, schema, table))
            return MaterializedResult([[True]], ["result"], [T.BOOLEAN])
        if isinstance(stmt, ast.SetSession):
            self.access_control.check_can_set_session_property(
                self.identity, stmt.name
            )
            # plan-shaping properties are part of the plan-cache key, so
            # no explicit invalidation is needed
            self.session.set_property(stmt.name, stmt.value)
            return MaterializedResult([[True]], ["result"], [T.BOOLEAN])
        if isinstance(stmt, ast.ShowSession):
            rows = []
            for meta in SYSTEM_PROPERTIES.all():
                current = getattr(self.session, meta.name)
                if meta.name == "memory_pool_bytes":
                    current = current or 0
                rows.append(
                    [meta.name, str(current), str(meta.default), meta.description]
                )
            return MaterializedResult(
                rows,
                ["Name", "Value", "Default", "Description"],
                [T.VARCHAR] * 4,
            )
        if isinstance(stmt, ast.ShowFunctions):
            from trino_tpu.expr.registry import REGISTRY

            rows = []
            for m in REGISTRY.all():
                arity = (
                    str(m.min_arity)
                    if m.max_arity == m.min_arity
                    else f"{m.min_arity}..{m.max_arity or 'N'}"
                )
                # one row per callable name and per concrete overload —
                # aliases and per-type signatures are rows, the
                # reference's SHOW FUNCTIONS unit (ceiling, pow, dow;
                # abs listed once per numeric type)
                sigs = m.overloads or (m.returns,)
                for nm in (m.name, *m.aliases):
                    for sig in sigs:
                        rows.append(
                            [nm, sig, arity, m.category, m.description]
                        )
            rows.sort(key=lambda r: (r[3], r[0], r[1]))
            return MaterializedResult(
                rows,
                ["Function", "Return Type", "Arity", "Function Type",
                 "Description"],
                [T.VARCHAR] * 5,
            )
        if isinstance(stmt, ast.ShowSchemas):
            cat = stmt.catalog or self.session.catalog
            conn = self.catalogs.get(cat)
            rows = [[s] for s in conn.metadata.list_schemas()]
            return MaterializedResult(rows, ["Schema"], [T.VARCHAR])
        if isinstance(stmt, ast.ShowTables):
            cat, schema = self.session.catalog, self.session.schema
            if stmt.schema:
                if len(stmt.schema) == 2:
                    cat, schema = stmt.schema
                else:
                    schema = stmt.schema[0]
            conn = self.catalogs.get(cat)
            rows = [[t] for t in conn.metadata.list_tables(schema)]
            return MaterializedResult(rows, ["Table"], [T.VARCHAR])
        if isinstance(stmt, ast.ShowColumns):
            parts = stmt.table
            cat, schema = self.session.catalog, self.session.schema
            table = parts[-1]
            if len(parts) == 2:
                schema = parts[0]
            elif len(parts) == 3:
                cat, schema = parts[0], parts[1]
            conn, handle = self.catalogs.resolve_table(cat, schema, table)
            meta = conn.metadata.get_table_metadata(handle)
            rows = [[c.name, str(c.type)] for c in meta.columns]
            return MaterializedResult(rows, ["Column", "Type"], [T.VARCHAR, T.VARCHAR])
        raise AnalysisError(f"cannot execute {type(stmt).__name__}")

    def _analyze(self, q: ast.Query) -> OutputNode:
        from trino_tpu.sql.analyzer import (
            set_session_info,
            set_session_zone,
        )
        from trino_tpu.sql.optimizer import (
            canonicalize_tstz_keys,
            optimize,
        )

        set_session_zone(self.session.timezone)
        set_session_info(
            self.session.catalog, self.session.schema,
            self.identity.user,
        )
        analyzer = Analyzer(self.catalogs, self.session.catalog, self.session.schema)
        root = optimize(analyzer.plan(q), self.catalogs, self.session)
        # correctness pass: runs regardless of enable_optimizer
        root = canonicalize_tstz_keys(root)
        mode = self.session.plan_validation
        if mode != "off":
            from trino_tpu.sql.validate import validate_logical

            validate_logical(root, stage="canonicalize_tstz_keys")
        if mode == "rules":
            # PlanDeterminismChecker: replanning the same AST must yield
            # byte-identical EXPLAIN text (fresh analyzer per run — the
            # plan cache would otherwise mask nondeterminism)
            from trino_tpu.sql.validate import check_plan_determinism

            def plan_once():
                a = Analyzer(
                    self.catalogs, self.session.catalog, self.session.schema
                )
                return canonicalize_tstz_keys(
                    optimize(a.plan(q), self.catalogs, self.session)
                )

            check_plan_determinism(plan_once)
        return root

    def _invalidate_plans(self, table=None, appended: bool = False,
                          tap=None) -> None:
        """Cached physical plans capture split lists (data snapshots) at
        plan time, so any write/DDL invalidates them — the analogue of
        the reference re-planning every query against current metadata.

        When the write can name its target (`table` = (catalog, schema,
        table)), invalidation is table-granular: only plans reading the
        written table drop, the table's generation counter bumps (the
        resident-tier invalidation protocol), and pinned resident state
        over the table is evicted — or, for an INSERT whose rows a
        `DeltaTap` captured (`appended`/`tap`), re-keyed onto the delta
        side so the pin stays warm. Writes that cannot name a table
        (COMMIT) stay wholesale."""
        from trino_tpu.resident import GENERATIONS, RESIDENT
        from trino_tpu.resident import fastlane as _fastlane
        from trino_tpu.resident.manager import table_key

        from trino_tpu.recovery import CHECKPOINTS

        if table is None:
            self._plan_cache.invalidate()
            GENERATIONS.bump_all()
            RESIDENT.evict_all()
            CHECKPOINTS.clear()
            return
        tkey = table_key(*table)
        self._plan_cache.invalidate_tables([tkey])
        GENERATIONS.bump(tkey)
        _fastlane.table_written(*tkey, appended=appended, tap=tap)
        # mesh checkpoints over the written table are stale by
        # construction: the generation guard already makes them
        # unreachable — reclaim their host memory eagerly
        CHECKPOINTS.invalidate_table(*tkey)

    # -- DML (BeginTableWrite/TableWriter/TableFinish path) --
    def _resolve_target(self, parts):
        # returns the REGISTERED catalog name alongside the connector:
        # conn.name is the connector type ("file"), which need not match
        # the registration name ("files") that plan/resident table keys
        # are built from on the read side
        cat, schema = self.session.catalog, self.session.schema
        table = parts[-1]
        if len(parts) == 2:
            schema = parts[0]
        elif len(parts) == 3:
            cat, schema = parts[0], parts[1]
        return cat, self.catalogs.get(cat), schema, table

    def _execute_ctas(self, stmt: ast.CreateTableAs) -> MaterializedResult:
        from trino_tpu.connectors.spi import ColumnMetadata

        output = self._analyze(stmt.query)
        self._check_scans(output)
        cat, conn, schema, table = self._resolve_target(stmt.table)
        self.access_control.check_can_create_table(
            self.identity, conn.name, schema, table
        )
        self._check_writable()  # before the table is created
        cols = [
            ColumnMetadata(n or f"_col{i}", f.type)
            for i, (n, f) in enumerate(zip(output.names, output.fields))
        ]
        conn.metadata.create_table(schema, table, cols)
        return self._write_into(
            cat, conn, schema, table, output, list(output.names)
        )

    def _execute_insert(self, parts, columns, query: ast.Query) -> MaterializedResult:
        cat, conn, schema, table = self._resolve_target(parts)
        self.access_control.check_can_insert(
            self.identity, conn.name, schema, table
        )
        output = self._analyze(query)
        self._check_scans(output)
        return self._write_into(
            cat, conn, schema, table, output,
            list(columns) if columns else None,
        )

    def _execute_rewrite_dml(
        self, parts, where, assignments: Optional[dict]
    ) -> MaterializedResult:
        """DELETE (assignments=None) / UPDATE as a read-rewrite: scan
        the surviving/updated rows into device batches, truncate, and
        re-append — the memory-connector analogue of the reference's
        row-level delete/update pushdown. Affected-row count comes from
        a matched-rows count pass."""
        from trino_tpu.transaction import TransactionError

        cat, conn, schema, table = self._resolve_target(parts)
        check = (
            self.access_control.check_can_delete
            if assignments is None
            else self.access_control.check_can_update
        )
        check(self.identity, conn.name, schema, table)
        self._check_writable()
        if self._active_txn() is not None:
            raise TransactionError(
                "DELETE/UPDATE inside an explicit transaction is not supported"
            )
        handle = conn.metadata.get_table_handle(schema, table)
        if handle is None:
            raise AnalysisError(f"table {schema}.{table} does not exist")
        meta = conn.metadata.get_table_metadata(handle)
        if assignments is not None:
            known = {c.name for c in meta.columns}
            for col in assignments:
                if col not in known:
                    raise AnalysisError(f"unknown column {col} in UPDATE")
        rel = ast.TableRef(parts)
        matched = (
            where
            if where is not None
            else ast.BooleanLiteral(True)
        )
        count_q = ast.Query(
            ast.QuerySpec(
                (ast.SelectItem(ast.FunctionCall("count", (ast.Star(),))),),
                from_=rel,
                where=where,
            )
        )
        affected = self._execute_query(count_q).only_value()

        if assignments is None:
            # keep rows where the predicate is NOT TRUE
            keep = (
                ast.UnaryOp(
                    "not",
                    ast.FunctionCall(
                        "coalesce", (where, ast.BooleanLiteral(False))
                    ),
                )
                if where is not None
                else None
            )
            if keep is None:  # unconditional DELETE = truncate
                conn.metadata.truncate_table(handle)
                self._invalidate_plans(table=(cat, schema, table))
                return MaterializedResult([[affected]], ["rows"], [T.BIGINT])
            select = tuple(
                ast.SelectItem(ast.Identifier((c.name,))) for c in meta.columns
            )
            rewrite_q = ast.Query(
                ast.QuerySpec(select, from_=rel, where=keep)
            )
        else:
            # per column: CASE WHEN pred THEN new ELSE old END
            items = []
            for c in meta.columns:
                old = ast.Identifier((c.name,))
                if c.name in assignments:
                    new = assignments[c.name]
                    e = (
                        ast.Case(
                            None,
                            (ast.WhenClause(matched, new),),
                            old,
                        )
                        if where is not None
                        else new
                    )
                else:
                    e = old
                items.append(ast.SelectItem(e, c.name))
            rewrite_q = ast.Query(ast.QuerySpec(tuple(items), from_=rel))

        self._replace_table_from_queries(cat, conn, handle, meta, [rewrite_q])
        return MaterializedResult([[affected]], ["rows"], [T.BIGINT])

    def _replace_table_from_queries(
        self, cat, conn, handle, meta, queries
    ) -> List[int]:
        """Materialize each rewrite query, coerce onto the table
        schema, and swap the combined batches in as the table's new
        contents (shared by DELETE/UPDATE/MERGE read-rewrites; MERGE
        runs survivors and inserts as separate queries so their string
        columns keep independent dictionaries). Returns the per-query
        materialized row counts (MERGE reads the insert count)."""
        from trino_tpu.expr import ir
        from trino_tpu.sql import plan as P

        batches = []
        counts = []
        for rewrite_q in queries:
            output = self._analyze(rewrite_q)
            # rewrite subqueries may scan other tables: same SELECT
            # access checks as any query
            self._check_scans(output)
            # coerce rewritten columns back onto the table schema
            # (UPDATE expressions may widen types), as the INSERT path
            exprs = []
            for i, col in enumerate(meta.columns):
                e: ir.Expr = ir.InputRef(i, output.fields[i].type)
                if output.fields[i].type != col.type:
                    e = ir.Cast(e, col.type)
                exprs.append(e)
            fields = tuple(P.Field(c.name, c.type) for c in meta.columns)
            node = P.ProjectNode(output.child, tuple(exprs), fields)
            planner = LocalPlanner(
                self.catalogs,
                batch_rows=self.session.batch_rows,
                target_splits=self.session.target_splits,
                dynamic_filtering=self.session.enable_dynamic_filtering,
            )
            physical = planner.plan(node)
            ctx = self._execution_ctx()
            pipelines, chain = physical.instantiate(ctx)
            sink = CollectorSink()
            chain.append(sink)
            for p in pipelines:
                Driver(p).run()
            Driver(Pipeline(chain)).run()
            _raise_deferred_checks(ctx)
            counts.append(sum(int(b.row_count()) for b in sink.batches))
            batches.extend(sink.batches)
        # commit the rewrite: connectors with replace_rows do it
        # atomically (stage-then-swap); the fallback truncate+append is
        # NOT crash-atomic
        replace = getattr(conn, "replace_rows", None)
        if replace is not None:
            replace(handle, batches)
        else:
            conn.metadata.truncate_table(handle)
            writer_sink = conn.page_sink(handle)
            for b in batches:
                writer_sink.append(b)
            writer_sink.finish()
        self._invalidate_plans(
            table=(cat, handle.schema, handle.table)
        )
        return counts

    def _execute_merge(self, stmt: ast.Merge) -> MaterializedResult:
        """MERGE as a read-rewrite over the existing query machinery
        (parser/sql/tree/Merge.java; the reference plans MERGE onto its
        row-change paradigm — here the whole statement compiles to ONE
        survivors-UNION-ALL-inserts query that becomes the table's new
        contents, the same strategy as DELETE/UPDATE):

        - survivors: target LEFT JOIN source; per column a CASE chain
          applies the FIRST matching WHEN MATCHED arm; rows whose first
          arm is DELETE drop.
        - inserts: source rows with NO target match (NOT EXISTS) and a
          matching WHEN NOT MATCHED arm.
        - a target row matching >1 source rows is an error (Trino's
          MERGE cardinality rule), checked with a row_number-keyed
          grouped count before the rewrite."""
        from trino_tpu.transaction import TransactionError

        cat, conn, schema, table = self._resolve_target(stmt.table)
        # each privilege gates only on the arms actually present
        # (Trino checks UPDATE/DELETE/INSERT per MERGE case kind)
        if any(c.action == "update" for c in stmt.clauses):
            self.access_control.check_can_update(
                self.identity, conn.name, schema, table
            )
        if any(not c.matched for c in stmt.clauses):
            self.access_control.check_can_insert(
                self.identity, conn.name, schema, table
            )
        if any(c.action == "delete" for c in stmt.clauses):
            self.access_control.check_can_delete(
                self.identity, conn.name, schema, table
            )
        self._check_writable()
        if self._active_txn() is not None:
            raise TransactionError(
                "MERGE inside an explicit transaction is not supported"
            )
        handle = conn.metadata.get_table_handle(schema, table)
        if handle is None:
            raise AnalysisError(f"table {schema}.{table} does not exist")
        meta = conn.metadata.get_table_metadata(handle)
        known = {c.name for c in meta.columns}
        for cl in stmt.clauses:
            set_names = [c for c, _ in cl.assignments]
            if len(set(set_names)) != len(set_names):
                raise AnalysisError(
                    "multiple assignments for the same column in MERGE"
                )
            for col in set_names:
                if col not in known:
                    raise AnalysisError(f"unknown column {col} in MERGE")
            if cl.action == "insert":
                cols = cl.insert_columns or tuple(
                    c.name for c in meta.columns
                )
                if len(cols) != len(cl.insert_values):
                    raise AnalysisError(
                        "MERGE INSERT column/value count mismatch"
                    )
                for col in cols:
                    if col not in known:
                        raise AnalysisError(
                            f"unknown column {col} in MERGE INSERT"
                        )

        t_alias = stmt.target_alias or table
        s_alias = getattr(stmt.source, "alias", None)
        if s_alias is None and isinstance(stmt.source, ast.TableRef):
            s_alias = stmt.source.name[-1]
        if s_alias is None:
            raise AnalysisError("MERGE source requires an alias")
        target_rel = ast.TableRef(stmt.table, alias=t_alias)
        true_lit = ast.BooleanLiteral(True)
        false_lit = ast.BooleanLiteral(False)

        def tcol(name: str) -> ast.Identifier:
            return ast.Identifier((t_alias, name))

        # cardinality rule: no target row may match more than one
        # source row (io.trino MERGE_TARGET_ROW_MULTIPLE_MATCHES)
        rid_target = ast.SubqueryRelation(
            ast.Query(ast.QuerySpec(
                (ast.SelectItem(ast.Star()),
                 ast.SelectItem(
                     ast.WindowCall("row_number", (), ast.WindowSpec()),
                     "__merge_rid",
                 )),
                from_=ast.TableRef(stmt.table),
            )),
            alias=t_alias,
        )
        dup_q = ast.Query(ast.QuerySpec(
            (ast.SelectItem(ast.FunctionCall("count", (ast.Star(),))),),
            from_=ast.SubqueryRelation(
                ast.Query(ast.QuerySpec(
                    (ast.SelectItem(tcol("__merge_rid")),),
                    from_=ast.Join(
                        "inner", rid_target, stmt.source, stmt.on
                    ),
                    group_by=(tcol("__merge_rid"),),
                    having=ast.BinaryOp(
                        "gt",
                        ast.FunctionCall("count", (ast.Star(),)),
                        ast.NumberLiteral("1"),
                    ),
                )),
                alias="__merge_dups",
            ),
        ))
        if (
            any(c.matched for c in stmt.clauses)
            and self._execute_query(dup_q).only_value() > 0
        ):
            raise RuntimeError(
                "One MERGE target table row matched more than one "
                "source row"
            )

        # matched flag rides the source side of the LEFT JOIN
        flagged_source = ast.SubqueryRelation(
            ast.Query(ast.QuerySpec(
                (ast.SelectItem(ast.Star()),
                 ast.SelectItem(true_lit, "__merge_m")),
                from_=stmt.source,
            )),
            alias=s_alias,
        )
        matched = ast.FunctionCall(
            "coalesce",
            (ast.Identifier((s_alias, "__merge_m")), false_lit),
        )
        m_clauses = [c for c in stmt.clauses if c.matched]
        nm_clauses = [c for c in stmt.clauses if not c.matched]

        # survivors: per column, the FIRST matching arm's value. With
        # no WHEN MATCHED arm the target is untouched — and must NOT
        # join (a LEFT JOIN would fan out on multiple source matches,
        # which insert-only MERGE legally allows)
        if not m_clauses:
            survivors = ast.QuerySpec(
                tuple(
                    ast.SelectItem(tcol(c.name), c.name)
                    for c in meta.columns
                ),
                from_=target_rel,
            )
        else:
            items = []
            for col in meta.columns:
                old = tcol(col.name)
                whens = []
                for cl in m_clauses:
                    cond = cl.condition or true_lit
                    val = dict(cl.assignments).get(col.name, old) \
                        if cl.action == "update" else old
                    whens.append(ast.WhenClause(
                        ast.BinaryOp("and", matched, cond), val
                    ))
                items.append(ast.SelectItem(
                    ast.Case(None, tuple(whens), old), col.name
                ))
            # a row drops iff matched AND its first applicable arm is
            # DELETE
            del_whens = [
                ast.WhenClause(
                    cl.condition or true_lit,
                    true_lit if cl.action == "delete" else false_lit,
                )
                for cl in m_clauses
            ]
            drop = ast.BinaryOp(
                "and", matched, ast.Case(None, tuple(del_whens), false_lit)
            )
            survivors = ast.QuerySpec(
                tuple(items),
                from_=ast.Join("left", target_rel, flagged_source, stmt.on),
                where=ast.UnaryOp("not", drop),
            )

        # affected rows: matched pairs whose first arm applies + inserts
        m_any = None
        for cl in m_clauses:
            c = cl.condition or true_lit
            m_any = c if m_any is None else ast.BinaryOp("or", m_any, c)
        updated = 0
        if m_clauses:
            updated = self._execute_query(ast.Query(ast.QuerySpec(
                (ast.SelectItem(ast.FunctionCall("count", (ast.Star(),))),),
                from_=ast.Join("inner", target_rel, stmt.source, stmt.on),
                where=m_any,
            ))).only_value()

        if nm_clauses:
            anti = ast.Exists(ast.Query(ast.QuerySpec(
                (ast.SelectItem(ast.NumberLiteral("1")),),
                from_=target_rel,
                where=stmt.on,
            )), negated=True)
            nm_any = None
            for cl in nm_clauses:
                c = cl.condition or true_lit
                nm_any = c if nm_any is None else ast.BinaryOp("or", nm_any, c)
            ins_items = []
            for col in meta.columns:
                whens = []
                for cl in nm_clauses:
                    cols = cl.insert_columns or tuple(
                        c.name for c in meta.columns
                    )
                    vmap = dict(zip(cols, cl.insert_values))
                    val = vmap.get(col.name, ast.NullLiteral())
                    whens.append(ast.WhenClause(
                        cl.condition or true_lit, val
                    ))
                ins_items.append(ast.SelectItem(
                    ast.Case(None, tuple(whens), ast.NullLiteral()),
                    col.name,
                ))
            ins_where = ast.BinaryOp("and", anti, nm_any)
            insert_spec = ast.QuerySpec(
                tuple(ins_items), from_=stmt.source, where=ins_where,
            )

        queries = [ast.Query(survivors)]
        if nm_clauses:
            queries.append(ast.Query(insert_spec))
        counts = self._replace_table_from_queries(
            cat, conn, handle, meta, queries
        )
        # the insert rewrite IS the anti-join — its materialized row
        # count is the inserted count (no third join execution)
        inserted = counts[1] if nm_clauses else 0
        return MaterializedResult(
            [[updated + inserted]], ["rows"], [T.BIGINT]
        )

    def _write_into(
        self, cat: str, conn, schema: str, table: str, output: OutputNode,
        insert_columns: Optional[List[str]],
    ) -> MaterializedResult:
        """Coerce the source onto the table schema and stream it into
        the connector page sink (TableWriterOperator)."""
        from trino_tpu.expr import ir
        from trino_tpu.exec.operators import TableWriterOperator
        from trino_tpu.sql import plan as P

        handle = conn.metadata.get_table_handle(schema, table)
        if handle is None:
            raise AnalysisError(f"table {schema}.{table} does not exist")
        meta = conn.metadata.get_table_metadata(handle)
        src_fields = output.fields
        if insert_columns is None:
            insert_columns = [c.name for c in meta.columns[: len(src_fields)]]
        if len(insert_columns) != len(src_fields):
            raise AnalysisError(
                f"INSERT has {len(src_fields)} columns but {len(insert_columns)} targets"
            )
        if len(set(insert_columns)) != len(insert_columns):
            raise AnalysisError("duplicate target column names in INSERT/CTAS")
        src_of = {name: i for i, name in enumerate(insert_columns)}
        exprs = []
        for col in meta.columns:
            i = src_of.get(col.name)
            if i is None:
                exprs.append(ir.Cast(ir.Literal(None, T.UNKNOWN), col.type))
                continue
            e: ir.Expr = ir.InputRef(i, src_fields[i].type)
            if src_fields[i].type != col.type:
                e = ir.Cast(e, col.type)
            exprs.append(e)
        fields = tuple(P.Field(c.name, c.type) for c in meta.columns)
        node = P.ProjectNode(output.child, tuple(exprs), fields)
        planner = LocalPlanner(
            self.catalogs,
            batch_rows=self.session.batch_rows,
            target_splits=self.session.target_splits,
            dynamic_filtering=self.session.enable_dynamic_filtering,
        )
        physical = planner.plan(node)
        ctx = self._execution_ctx()
        pipelines, chain = physical.instantiate(ctx)
        self._check_writable()
        active = self._active_txn()
        txn_handle = (
            self.transactions.join(active, conn.name, conn)
            if active is not None
            else None
        )
        if txn_handle is None and self.session.task_concurrency > 1:
            # autocommit bulk writes scale out with observed volume
            # (ScaledWriterSink); transactional writes keep ONE sink so
            # the commit stays a single handshake
            from trino_tpu.exec.operators import ScaledWriterSink

            sink_impl = ScaledWriterSink(
                lambda: conn.page_sink(handle),
                max_writers=self.session.task_concurrency,
            )
        else:
            sink_impl = conn.page_sink(handle, transaction=txn_handle)
        # when a resident pin covers this table, tee the written rows
        # through a DeltaTap so the pin can absorb the insert on its
        # delta side instead of being evicted
        from trino_tpu.resident import fastlane as _fastlane

        tap = _fastlane.delta_tap(
            cat, schema, table, [c.name for c in meta.columns]
        )
        if tap is not None:
            sink_impl = _fastlane.TeeSink(sink_impl, tap)
        writer = TableWriterOperator(sink_impl)
        chain.append(writer)
        for p in pipelines:
            Driver(p).run()
        Driver(Pipeline(chain)).run()
        _raise_deferred_checks(ctx)
        self._invalidate_plans(
            table=(cat, schema, table), appended=True, tap=tap
        )
        return MaterializedResult([[writer.rows_written]], ["rows"], [T.BIGINT])

    def _run_tracked(self, sql: str, stmt: ast.Query) -> MaterializedResult:
        """Query lifecycle: span tree, the statement's own account and
        event listener dispatch around the actual execution
        (SqlQueryExecution's tracing shape)."""
        from trino_tpu.runtime.tracing import QueryTrace, statement

        self._query_seq += 1
        query_id = f"local-{self._query_seq}"
        trace = QueryTrace(query_id)
        parse_ns, queued_ns = getattr(self._stmt_txn, "before_ns", (0, 0))
        entered_ns = getattr(
            self._stmt_txn, "entered_ns", time.perf_counter_ns())
        # opened and closed on this, the executing thread: what it reads
        # back and counts below is this statement's
        with statement(trace.account, entered_ns, parse_ns):
            result = self._run_in_trace(
                sql, stmt, query_id, trace, parse_ns, queued_ns)
        result.stats["account"] = trace.account.stats()
        return result

    def _run_in_trace(self, sql, stmt, query_id, trace, parse_ns,
                      queued_ns) -> MaterializedResult:
        import time as _time

        from trino_tpu.runtime.events import QueryCreatedEvent
        from trino_tpu.runtime.tracing import KIND_QUERY

        qspan = trace.span(f"query {query_id}", KIND_QUERY, sql=sql[:500])
        self.event_listeners.query_created(
            QueryCreatedEvent(query_id, sql, _time.time())
        )
        status, failure, rows_n = "finished", None, 0
        qspan.set(queued_ms=queued_ns / 1e6, plan_ms=parse_ns / 1e6)
        # entered and left on this, the executing thread: in a profiler
        # trace the statement is one event with everything below inside
        with qspan:
            try:
                result = self._execute_query(
                    stmt, sql_key=sql, query_id=query_id,
                    trace=trace, query_span=qspan,
                )
                rows_n = len(result.rows)
                result.stats = {
                    "query_id": query_id,
                    "elapsed_ms": qspan.duration_s * 1e3,
                    "plan_ms": qspan.attributes["plan_ms"],
                    "cpu_ms": qspan.attributes.get("cpu_ms", 0.0),
                }
                return result
            except BaseException as e:
                status, failure = "failed", repr(e)
                if not qspan.ended:
                    qspan.event("exception", error=repr(e)[:300])
                    qspan.set(error=True)
                raise
            finally:
                with phase_span(qspan, "finalize"):
                    self._finalize_query(
                        query_id, sql, trace, qspan, status, failure, rows_n,
                    )

    def _finalize_query(self, query_id, sql, trace, qspan, status,
                        failure, rows_n):
        """Close the span tree, retire per-query compile counters, and
        fire the enriched completion event. Observability finalization
        must never mask the query's own verdict, so it swallows."""
        try:
            from trino_tpu.runtime.events import QueryCompletedEvent
            from trino_tpu.runtime.metrics import (
                METRICS,
                retire_query_compiles,
            )

            qspan.set(state=status)
            qspan.end()
            trace.end_open_spans(qspan.end_s)
            wall = qspan.duration_s
            METRICS.observe("query_wall_s", wall)
            compile_count = retire_query_compiles(query_id)
            # what this statement's own thread counted, not the process
            account = trace.account
            peak = 0
            if self._last_pool is not None:
                peaks = self._last_pool.query_peaks()
                peak = int(max(peaks.values(), default=0))
            self._last_trace = (query_id, trace)
            self.event_listeners.query_completed(
                QueryCompletedEvent(
                    query_id, sql, status, wall,
                    rows=rows_n, failure=failure,
                    peak_memory_bytes=peak,
                    rows_scanned=int(account.counter("rows_scanned")),
                    bytes_scanned=int(account.counter("bytes_scanned")),
                    rows_shuffled=int(account.counter("rows_shuffled")),
                    compile_count=compile_count,
                )
            )
        except Exception:
            import logging

            logging.getLogger(__name__).warning(
                "query finalization failed for %s", query_id, exc_info=True
            )

    def _plan(self, q: ast.Query, sql_key: Optional[str], query_span=None):
        """(logical, physical) plan of `q`, from the plan cache where it
        has one; `phase.plan` in a profiler trace, with `hit`."""
        with phase_span(query_span, "plan", hit=0) as span:
            return self._plan_in(span, q, sql_key, query_span)

    def _plan_in(self, plan_span, q: ast.Query, sql_key: Optional[str],
                 query_span=None):
        import contextlib

        self._last_adaptive_report = None  # set again if adaptive runs

        def phase(name):
            if query_span is None:
                return contextlib.nullcontext()
            from trino_tpu.runtime.tracing import KIND_PHASE

            return query_span.child(name, KIND_PHASE)

        # the key canonicalizes the statement through the formatter
        # (fixpoint-checked in PR 5) and folds in the plan-shaping
        # session properties + bound-parameter dtypes, so SET SESSION
        # and EXECUTE bindings take effect however they were invoked
        cache_key = None
        if sql_key is not None:
            try:
                from trino_tpu.sql.formatter import format_statement

                canonical = format_statement(q)
            except Exception:
                canonical = sql_key
            cache_key = self._plan_cache.key(
                canonical, self.session,
                getattr(self._bound_dtypes_tls, "value", None) or (),
            )
        cached = self._plan_cache.lookup(cache_key) if cache_key else None
        if cached is not None:
            # access control re-checks on every execution, cached or not
            self._check_scans(cached[0])
            plan_span.set_metadata(hit=1)
            return cached
        from trino_tpu.sql.analyzer import (
            decorrelated_scalar_aggregates, plan_is_volatile,
            reset_plan_marks,
        )

        # snapshot the generation BEFORE planning: a DDL landing while
        # we plan must win over our store below
        cache_generation = self._plan_cache.generation
        reset_plan_marks()
        with phase("analyze"):
            output = self._analyze(q)
        self._check_scans(output)
        # adaptive execution: observe materialization barriers and
        # re-plan the remainder BEFORE physical planning; transformed
        # plans embed data snapshots so they never enter the plan cache
        adaptive_report = None
        from trino_tpu.adaptive import AdaptiveController

        controller = AdaptiveController(
            self.catalogs, self.session, span=query_span,
            stabilizer=self._make_stabilizer(),
        )
        if controller.enabled():
            with phase("adaptive"):
                output = controller.prepare(output)
            adaptive_report = controller.report
        self._last_adaptive_report = adaptive_report
        with phase("optimize"):
            planner = LocalPlanner(
                self.catalogs,
                batch_rows=self.session.batch_rows,
                target_splits=self.session.target_splits,
                dynamic_filtering=self.session.enable_dynamic_filtering,
                stabilizer=self._make_stabilizer(),
                mxu_join=self.session.mxu_join_enabled,
                mxu_join_min_work=self.session.mxu_join_min_work,
            )
            physical = planner.plan(output, decorrelated_scalar_aggregates())
        # plans with analysis-time-folded volatile values (now(),
        # current_date, uuid()) re-analyze every execution
        if (
            cache_key
            and not plan_is_volatile()
            and not (adaptive_report is not None and adaptive_report.transformed)
        ):
            from trino_tpu.serving.plan_cache import plan_tables

            self._plan_cache.store(
                cache_key, (output, physical), generation=cache_generation,
                tables=plan_tables(output),
            )
        return output, physical

    def _execution_ctx(self) -> dict:
        ctx: dict = {}
        if self.session.memory_pool_bytes is not None:
            from trino_tpu.runtime.memory import MemoryPool

            ctx["memory_pool"] = MemoryPool(self.session.memory_pool_bytes)
            # register resident pins revocable in this query's pool: a
            # reservation that cannot fit reclaims warm state BEFORE the
            # exhaustion handler considers killing a query
            from trino_tpu.resident import RESIDENT

            RESIDENT.attach_pool(ctx["memory_pool"])
        return ctx

    def _make_stabilizer(self):
        """Session's capacity policy (compile/shapes.py); None when
        shape stabilization is off."""
        if not self.session.shape_stabilization:
            return None
        from trino_tpu.compile.shapes import CapacityLadder, ShapeStabilizer

        return ShapeStabilizer(
            CapacityLadder(base=self.session.capacity_ladder_base),
            batch_rows=self.session.batch_rows,
        )

    def _start_warmup(self, physical):
        """Kick off census-driven AOT warmup per warmup_mode; returns
        the (started) WarmupService or None. mode=block waits here, so
        execution starts with every predicted program compiled."""
        mode = self.session.warmup_mode
        entries = getattr(physical, "warmup_entries", ())
        if mode == "off" or not entries:
            return None
        from trino_tpu.compile.warmup import WarmupService

        svc = WarmupService(entries, mode=mode).start()
        if mode == "block":
            svc.wait()
        return svc

    def _attribution_id(self) -> str:
        self._query_seq += 1
        return f"local-{self._query_seq}"

    # -- observability surface (runtime/tracing.py) --
    def query_trace_export(self, query_id: Optional[str] = None):
        """Span tree of the most recent query (the local runner keeps
        only the last trace); None when the id does not match."""
        if self._last_trace is None:
            return None
        qid, trace = self._last_trace
        if query_id is not None and query_id != qid:
            return None
        return trace.export()

    def query_chrome_trace(self, query_id: Optional[str] = None):
        from trino_tpu.runtime.tracing import chrome_trace

        export = self.query_trace_export(query_id)
        if export is None:
            return None
        # Perfetto reads `traceEvents`; the statement's own numbers ride
        # beside it
        return {"traceEvents": chrome_trace(export),
                "account": export.get("account")}

    def _execute_query(
        self, q: ast.Query, sql_key: Optional[str] = None,
        query_id: Optional[str] = None, trace=None, query_span=None,
    ) -> MaterializedResult:
        import contextlib

        from trino_tpu.runtime.metrics import set_compile_attribution

        t_plan = time.perf_counter_ns()
        output, physical = self._plan(q, sql_key, query_span=query_span)
        with phase_span(query_span, "instantiate"):
            self._start_warmup(physical)
            ctx = self._execution_ctx()
            self._last_pool = ctx.get("memory_pool")
            pipelines, chain = physical.instantiate(ctx)
            sink = CollectorSink()
            chain.append(sink)
        if query_span is not None:
            query_span.set(plan_ms=query_span.attributes.get("plan_ms", 0.0)
                           + (time.perf_counter_ns() - t_plan) / 1e6)
        # compile attribution reuses the tracked query id, so the
        # per-query counter retired at finalization is the same one the
        # listener installed compiles under. Internal subqueries
        # (DELETE count rewrites, MERGE match checks) inherit the
        # enclosing statement's attribution so their compiles are
        # charged — and retired — with the user's query instead of
        # leaking one never-retired counter per helper
        from trino_tpu.runtime.metrics import compile_attribution

        prev_qid = set_compile_attribution(
            query_id or compile_attribution() or self._attribution_id()
        )
        exec_span = contextlib.nullcontext()
        if query_span is not None:
            from trino_tpu.runtime.tracing import KIND_PHASE

            exec_span = query_span.child("execute", KIND_PHASE)
        op_parent = exec_span if query_span is not None else None
        account = trace.account if trace is not None else None
        t_execute = time.perf_counter_ns()
        cpu0 = time.thread_time_ns()
        try:
            with exec_span:
                try:
                    for p in pipelines:
                        Driver(p, span=op_parent).run()
                    Driver(Pipeline(chain), span=op_parent).run()
                    checks = ctx.get("deferred_checks", ())
                    rows, flags = sink.rows_with(
                        tuple(f for f, _ in checks))
                    for v, (_, msg) in zip(flags, checks):
                        if v:
                            raise RuntimeError(msg)
                finally:
                    # this thread's CPU time inside the phase: wall
                    # minus it is waiting (the device, the GIL)
                    cpu_ns = time.thread_time_ns() - cpu0
                    if query_span is not None:
                        exec_span.set(cpu_ns=cpu_ns)
                        query_span.set(cpu_ms=cpu_ns / 1e6)
                    if account is not None:
                        account.cpu_ns += cpu_ns
                        account.execute_ns += (
                            time.perf_counter_ns() - t_execute)
        finally:
            set_compile_attribution(prev_qid)
        with phase_span(query_span, "release"):
            # the operators' state goes here and not as the frame ends:
            # dropping the last references frees their device buffers
            del pipelines, chain, sink, ctx
        return MaterializedResult(
            rows,
            list(output.names),
            [f.type for f in output.fields],
        )

    def _explain_analyze(self, q: ast.Query) -> MaterializedResult:
        """EXPLAIN ANALYZE: run with instrumented operators, render plan
        + per-operator stats (ExplainAnalyzeOperator analogue)."""
        from trino_tpu.exec.stats import (
            engine_counters_delta,
            instrument,
            render_stats,
        )
        from trino_tpu.runtime.metrics import (
            METRICS,
            install_xla_compile_listener,
            retire_query_compiles,
            set_compile_attribution,
        )
        from trino_tpu.sql.validate import census_text, shape_census

        install_xla_compile_listener()
        output, physical = self._plan(q, sql_key=None)
        stabilizer = self._make_stabilizer()
        classes = shape_census(
            output, self.catalogs,
            batch_rows=self.session.batch_rows,
            dynamic_filtering=self.session.enable_dynamic_filtering,
            ladder=stabilizer.ladder if stabilizer is not None else None,
        )
        warmup_svc = self._start_warmup(physical)
        qid = self._attribution_id()
        before = METRICS.snapshot()
        ctx = self._execution_ctx()
        pipelines, chain = physical.instantiate(ctx)
        sink = CollectorSink()
        chain.append(sink)
        groups = []
        wrapped_pipelines = []
        ledger = set()
        for p in pipelines:
            ops, stats = instrument(
                p.operators, device_sync=True, shape_ledger=ledger
            )
            groups.append(stats)
            wrapped_pipelines.append(Pipeline(ops))
        main_ops, main_stats = instrument(
            chain, device_sync=True, shape_ledger=ledger
        )
        groups.append(main_stats)
        prev_qid = set_compile_attribution(qid)
        try:
            for p in wrapped_pipelines:
                Driver(p).run()
            Driver(Pipeline(main_ops)).run()
        finally:
            set_compile_attribution(prev_qid)
        _raise_deferred_checks(ctx)
        for p in wrapped_pipelines:
            for op in p.operators:
                op.flush_counts()
        for op in main_ops:
            op.flush_counts()
        after = METRICS.snapshot()
        counters = engine_counters_delta(before, after)
        census = census_text(classes, observed=len(ledger))
        # compile-regime lines ride directly under the census: per-query
        # attributed compile count (satellite of the process-wide
        # xla_compiles engine counter), warmup hit/miss, cache stats
        qkey = f"xla_compiles_by_query.{qid}"
        compiled_here = int(after.get(qkey, 0.0) - before.get(qkey, 0.0))
        # EXPLAIN ANALYZE is this attribution id's terminal operation —
        # retire its counter so the registry stays bounded
        retire_query_compiles(qid)
        census += f"\nxla_compiles_this_query={compiled_here}"
        if warmup_svc is not None:
            if warmup_svc.mode == "background":
                # settle before reporting so entry statuses are final
                warmup_svc.wait(timeout=60.0)
            census += "\n" + warmup_svc.report_line(ledger)
        from trino_tpu.compile.cache import (
            ACTIVE_PERSISTENT_CACHE,
            PROGRAM_CACHE,
        )

        ps = PROGRAM_CACHE.stats()
        census += (
            f"\nprogram_cache: entries={ps['entries']} hits={ps['hits']} "
            f"misses={ps['misses']} evictions={ps['evictions']}"
        )
        if ACTIVE_PERSISTENT_CACHE is not None:
            cs = ACTIVE_PERSISTENT_CACHE.stats()
            census += (
                f"\npersistent_cache: entries={cs['entries']} "
                f"bytes={cs['bytes']} scrubbed={cs['scrubbed']} "
                f"evicted={cs['evicted']}"
            )
        # adaptive section: what the controller observed and did
        # (estimated_vs_observed per barrier, replan/spool counts)
        report = getattr(self, "_last_adaptive_report", None)
        if report is not None:
            census += "\n" + "\n".join(report.lines())
        # census goes AFTER the runtime stats: per-class lines name
        # operators too, and stats consumers grep for the first line
        # mentioning an operator
        text = (
            explain_text(output) + "\n\n"
            + render_stats(groups, counters) + "\n\n" + census
        )
        return MaterializedResult([[text]], ["Query Plan"], [T.VARCHAR])
