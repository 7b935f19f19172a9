"""Regenerate the committed EXPLAIN-diff corpus (PR 1 plan-quality passes).

Run from the repo root:

    JAX_PLATFORMS=cpu python explain_corpus/generate.py

Each emitted file pairs an EXPLAIN with the relevant pass disabled (or
the plan before the rule fires) against the same query with it enabled,
so reviewers can see exactly what each pass buys:

    01_transitive_predicate.txt   EqualityInference derives a join-key
                                  bound for the unfiltered side
    02_scan_pushdown.txt          conjuncts + column list land on the
                                  scan node (TPC-H Q6)
    03_partial_agg_exchange.txt   partial aggregation placed below the
                                  repartition exchange
    04_elided_exchange.txt        co-bucketed join/agg plan drops its
                                  repartition exchanges

The corpus is deterministic (fixed seeds, tiny inputs) — diffs in a
future PR mean the planner actually changed.
"""

import os

# corpus 11 exercises the chunked mesh plane, whose chunk count depends
# on the per-shard extent — force the same virtual 8-device CPU mesh the
# test suite runs under (tests/conftest.py) so standalone regeneration
# matches the corpus-diff gate
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
try:
    jax.config.update("jax_num_cpu_devices", 8)
except AttributeError:
    pass

import numpy as np

from trino_tpu import types as T
from trino_tpu.connectors.memory import create_memory_connector
from trino_tpu.connectors.spi import CatalogManager, ColumnMetadata
from trino_tpu.connectors.tpch import create_tpch_connector
from trino_tpu.engine import LocalQueryRunner, Session
from trino_tpu.runtime.metrics import METRICS
from trino_tpu.sql import plan as P
from trino_tpu.sql.analyzer import Analyzer
from trino_tpu.sql.fragmenter import (
    explain_distributed,
    plan_distributed,
    push_partial_aggregation_through_exchange,
)
from trino_tpu.sql.parser import parse

HERE = os.path.dirname(os.path.abspath(__file__))
# write_all() retargets this so the corpus-diff test can regenerate into
# a tmp dir and diff against the committed files
_OUT_DIR = [HERE]


def emit(name: str, *sections, out_dir: str = None):
    path = os.path.join(out_dir or _OUT_DIR[0], name)
    body = []
    for title, text in sections:
        body.append("=" * 72)
        body.append(title)
        body.append("=" * 72)
        body.append(text.rstrip())
        body.append("")
    with open(path, "w") as fh:
        fh.write("\n".join(body))
    print(f"wrote {path}")


def _mem_runner():
    r = LocalQueryRunner(Session(catalog="memory", schema="s"))
    r.register_catalog("memory", create_memory_connector())
    mem = r.catalogs.get("memory")
    rng = np.random.default_rng(7)
    n = 1000
    mem.load_table(
        "s", "a",
        [ColumnMetadata("k", T.BIGINT), ColumnMetadata("v", T.BIGINT)],
        [np.arange(n, dtype=np.int64), rng.integers(0, 9, n, dtype=np.int64)],
    )
    mem.load_table(
        "s", "b",
        [ColumnMetadata("k", T.BIGINT), ColumnMetadata("w", T.BIGINT)],
        [np.arange(n, dtype=np.int64), rng.integers(0, 9, n, dtype=np.int64)],
    )
    return r


def explain(runner, sql):
    return runner.execute("explain " + sql).rows[0][0]


def corpus_01_transitive():
    r = _mem_runner()
    # the subquery keeps `ak < 100` ABOVE the join at analysis time —
    # exactly the Filter(Join) shape InferTransitivePredicates rewrites
    sql = (
        "select v, w from (select a.k as ak, b.k as bk, a.v as v, "
        "b.w as w from a join b on a.k = b.k) j where ak < 100"
    )
    r.execute("SET SESSION enable_optimizer = false")
    off = explain(r, sql)
    r.execute("SET SESSION enable_optimizer = true")
    on = explain(r, sql)
    emit(
        "01_transitive_predicate.txt",
        (f"QUERY\n{sql}", ""),
        ("enable_optimizer = false  (bound stays on the filter above "
         "the join; both\ntables scanned in full)", off),
        ("enable_optimizer = true   (EqualityInference derives k < 100 "
         "for b via the\njoin equivalence ak = bk; BOTH scans now carry "
         "pushed=[k lt 100])", on),
    )


def corpus_02_scan_pushdown():
    r = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    r.register_catalog("tpch", create_tpch_connector())
    sql = (
        "select sum(l_extendedprice * l_discount) as revenue from lineitem "
        "where l_shipdate >= date '1994-01-01' "
        "and l_shipdate < date '1995-01-01' "
        "and l_discount between 0.05 and 0.07 and l_quantity < 24"
    )
    r.execute("SET SESSION enable_pushdown = false")
    off = explain(r, sql)
    r.execute("SET SESSION enable_pushdown = true")
    on = explain(r, sql)
    emit(
        "02_scan_pushdown.txt",
        (f"QUERY (TPC-H Q6)\n{sql}", ""),
        ("enable_pushdown = false  (FilterNode above a full-width scan)",
         off),
        ("enable_pushdown = true   (conjuncts in `pushed=[...]` on the "
         "scan, column list\nnarrowed to the four referenced columns, "
         "no residual Filter)", on),
    )


def corpus_03_partial_agg():
    c = CatalogManager()
    c.register("tpch", create_tpch_connector())
    sql = (
        "select l_returnflag, sum(l_quantity) from lineitem "
        "group by l_returnflag"
    )
    output = Analyzer(c, "tpch", "tiny").plan(parse(sql))
    # the rule's input: a single-step aggregate above the repartition
    # exchange that AddExchanges inserted
    scan = _scan_of(output)
    ex = P.ExchangeNode(scan, "repartition", (0,), scan.fields)
    naive = P.AggregateNode(
        ex, (0,), (P.AggCall("sum", 1, T.BIGINT),),
        (P.Field("l_returnflag", scan.fields[0].type),
         P.Field("sum", T.BIGINT)),
        step="single",
    )
    pushed = push_partial_aggregation_through_exchange(naive)
    sp = plan_distributed(output, c)
    # catalogs=... annotates each fragment header with its compile-churn
    # census (expected_xla_lowerings — sql/validate.py)
    distributed = explain_distributed(sp, catalogs=c)
    emit(
        "03_partial_agg_exchange.txt",
        (f"QUERY\n{sql}", ""),
        ("before push_partial_aggregation_through_exchange\n"
         "(single-step aggregate consumes the repartition exchange: "
         "every input row\ncrosses the wire)", P.explain_text(naive)),
        ("after push_partial_aggregation_through_exchange\n"
         "(partial aggregate runs scan-side below the exchange; only "
         "one row per\ngroup per producer is shuffled; final step "
         "merges)", P.explain_text(pushed)),
        ("full distributed plan (plan_distributed applies the rule; "
         "Aggregate[partial]\nsits in the scan fragment, "
         "Aggregate[final] above the remote source; each\nfragment "
         "header carries its compile-churn census)",
         distributed),
    )


def _scan_of(node):
    if isinstance(node, P.ScanNode):
        return node
    for ch in node.children():
        s = _scan_of(ch)
        if s is not None:
            return s
    return None


def corpus_04_elided_exchange():
    rng = np.random.default_rng(11)
    ka = rng.integers(0, 50, 300).astype(np.int64)
    va = rng.integers(0, 9, 300).astype(np.int64)
    kb = rng.integers(0, 50, 200).astype(np.int64)
    wb = rng.integers(0, 9, 200).astype(np.int64)
    sql = (
        "select ta.k, sum(ta.v + tb.w) from ta join tb on ta.k = tb.k "
        "group by ta.k"
    )

    def distributed_explain(bucketed):
        mem = create_memory_connector()
        bb = ("k",) if bucketed else None
        mem.load_table(
            "d", "ta",
            [ColumnMetadata("k", T.BIGINT), ColumnMetadata("v", T.BIGINT)],
            [ka, va], bucketed_by=bb,
        )
        mem.load_table(
            "d", "tb",
            [ColumnMetadata("k", T.BIGINT), ColumnMetadata("w", T.BIGINT)],
            [kb, wb], bucketed_by=bb,
        )
        c = CatalogManager()
        c.register("memory", mem)
        output = Analyzer(c, "memory", "d").plan(parse(sql))
        before = METRICS.snapshot().get("exchanges_elided", 0.0)
        sp = plan_distributed(output, c, broadcast_threshold=0)
        elided = METRICS.snapshot().get("exchanges_elided", 0.0) - before
        return explain_distributed(sp, catalogs=c), elided

    plain, e_plain = distributed_explain(False)
    bucketed, e_bucketed = distributed_explain(True)
    emit(
        "04_elided_exchange.txt",
        (f"QUERY\n{sql}", ""),
        (f"unbucketed tables  (exchanges_elided +{e_plain:.0f}: the "
         "final aggregate reuses the\njoin's hash distribution, but "
         "both join inputs still repartition)", plain),
        (f"bucketed_by=('k') on both tables  (exchanges_elided "
         f"+{e_bucketed:.0f}: declared\nco-bucketing satisfies the "
         "join and aggregate distribution requirements,\nso the "
         "repartition exchanges disappear and fragments collapse)",
         bucketed),
    )


def corpus_05_plan_validation():
    from trino_tpu.expr import ir
    from trino_tpu.sql.validate import (
        PlanValidationError,
        census_text,
        shape_census,
        validate_logical,
    )

    # a rule mis-shifting a Ref — the error names checker + node path
    vals = P.ValuesNode((P.Field("a", T.BIGINT),), ((0,),))
    bad_ref = P.ProjectNode(
        vals, (ir.InputRef(5, T.BIGINT),), (P.Field("x", T.BIGINT),)
    )
    try:
        validate_logical(bad_ref, stage="optimizer", rule="example_rule")
        ref_err = "NOT CAUGHT"
    except PlanValidationError as e:
        ref_err = str(e)
    # an un-canonicalized tstz repartition key (zone bits would reach
    # the hash) — the regression canonicalize_tstz_keys exists to stop
    tvals = P.ValuesNode((P.Field("ts", T.TIMESTAMP_TZ),), ((0,),))
    bad_tstz = P.ExchangeNode(tvals, "repartition", (0,), tvals.fields)
    try:
        validate_logical(bad_tstz)
        tstz_err = "NOT CAUGHT"
    except PlanValidationError as e:
        tstz_err = str(e)
    # census over a join plan: the dynamic filter's retry-variant class
    c = CatalogManager()
    c.register("tpch", create_tpch_connector())
    sql = (
        "select n_name, count(*) from supplier, nation "
        "where s_nationkey = n_nationkey group by n_name"
    )
    output = Analyzer(c, "tpch", "tiny").plan(parse(sql))
    census = census_text(shape_census(output, c))
    emit(
        "05_plan_validation.txt",
        ("corrupted plan: Project ref outside input width\n"
         "(PlanValidationError names the checker, node path, stage and "
         "last rule)", ref_err),
        ("corrupted plan: repartition on a raw TIMESTAMP_TZ key\n"
         "(exchange_keys checker demands the $utc zone-masked "
         "projection)", tstz_err),
        (f"QUERY\n{sql}", ""),
        ("compile-churn census (logical plan): one line per expected "
         "(operator,\ncapacity, dtype) XLA lowering; the "
         "DynamicFilterOperator class is marked\nretry-variant — its "
         "pruned probe capacity depends on which retry attempt's\n"
         "build side survives, so it compiles fresh shapes no warm run "
         "covers", census),
    )


def corpus_06_compile_regime():
    from trino_tpu.compile.shapes import CapacityLadder, ShapeStabilizer
    from trino_tpu.compile.warmup import WarmupService
    from trino_tpu.sql.validate import census_text, shape_census

    # 1. the capacity ladder: how pruned spans snap onto stable rungs
    lines = []
    for base in (2, 4):
        lad = CapacityLadder(base=base)
        rungs = ", ".join(str(r) for r in lad.rungs(1 << 20))
        lines.append(f"base={base}: {rungs}")
    stab = ShapeStabilizer(CapacityLadder(base=2))
    demo = []
    for span, pruned in ((60175, 60175), (60175, 1732), (60175, 0)):
        cap = stab.chunk_capacity(span)
        demo.append(
            f"span={span} rows_after_pruning={pruned} -> capacity={cap}"
        )
    ladder_text = (
        "\n".join(lines)
        + "\n\nchunk capacity is a function of the PRE-pruning span, so "
        "pushdown- or\ndynamic-filter-pruned chunks land on the same "
        "class as the unpruned scan:\n" + "\n".join(demo)
    )

    # 2. census with tail classes: a table larger than batch_rows scans
    # in batch_rows chunks plus one smaller tail chunk
    c = CatalogManager()
    c.register("tpch", create_tpch_connector())
    sql_tail = (
        "select l_returnflag, sum(l_quantity) from lineitem "
        "group by l_returnflag"
    )
    output = Analyzer(c, "tpch", "tiny").plan(parse(sql_tail))
    census = census_text(
        shape_census(
            output, c, batch_rows=49152, ladder=CapacityLadder(base=2)
        ),
    )

    # 3. the census-driven warmup plan: the fused filter/project stages
    # the planner registered for AOT compilation, with their predicted
    # capacity classes (plan-time artifact — no runtime counters)
    r = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
    r.register_catalog("tpch", create_tpch_connector())
    sql_warm = (
        "select l_orderkey + 1 from lineitem where l_quantity * 2 < 10"
    )
    stmt = parse(sql_warm)
    q = stmt.query if hasattr(stmt, "query") else stmt
    _, physical = r._plan(q, sql_key=None)
    svc = WarmupService(physical.warmup_entries, mode="block")
    emit(
        "06_compile_regime.txt",
        ("capacity ladder (compile/shapes.py): geometric rungs pruned "
         "scan chunks,\nspill re-reads and exchange pages pad up to; "
         "base is the session property\ncapacity_ladder_base",
         ladder_text),
        (f"QUERY\n{sql_tail}", ""),
        ("stabilized shape census at batch_rows=49152 (lineitem tiny = "
         "60175 rows\n> batch_rows, so the scan and its consumers carry "
         "a tail capacity class\nbeside the main one)", census),
        (f"QUERY\n{sql_warm}", ""),
        ("warmup plan (compile/warmup.py): the fused FilterProject "
         "stage the planner\nregistered, warmed once per predicted "
         "capacity on an all-dead zero batch\nbefore (block) or while "
         "(background) the query runs", svc.plan_text()),
    )


def corpus_07_distributed_analyze():
    """Distributed EXPLAIN ANALYZE through the TaskInfo aggregation
    path (runtime/queryinfo.py): merged per-stage operator lines,
    expected-vs-observed lowering counts, and per-task-attempt summary
    lines. Wall/cpu timings and the process-global query counter are
    nondeterministic, so they are redacted to `#` — the corpus pins the
    structure (fragments, operators, row/batch counts, lowerings), not
    the clock."""
    import re

    from trino_tpu.runtime import DistributedQueryRunner, Worker

    cats = CatalogManager()
    cats.register("tpch", create_tpch_connector())
    workers = [Worker(f"corpus-w{i}", cats) for i in range(2)]
    r = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny"),
        worker_handles=workers,
        hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())
    sql = (
        "select n_regionkey, count(*) from nation group by n_regionkey"
    )
    out = r.execute("EXPLAIN ANALYZE " + sql).rows[0][0]

    def redact(text):
        text = re.sub(r"\b(wall|cpu)=\d+(\.\d+)?ms", r"\1=#ms", text)
        text = re.sub(r"\b(add|get|finish)=\d+(\.\d+)?", r"\1=#", text)
        text = re.sub(r"\btask q\d+\.", "task q#.", text)
        # corpus 15 pins the real membership= line
        text = re.sub(r"membership= .*", "membership= #", text)
        text = re.sub(r"replicas= .*", "replicas= #", text)
        # process-global resident/recovery-tier counters depend on what
        # ran before this corpus fn — corpora 09 and 11 pin the real
        # numbers
        text = re.sub(r"resident= .*", "resident= #", text)
        text = re.sub(r"recovery= .*", "recovery= #", text)
        text = re.sub(r"skew= .*", "skew= #", text)
        # process-global witness registry: lock/thread counts depend
        # on what ran before — corpus 16 pins the analyzer itself
        text = re.sub(r"concurrency= .*", "concurrency= #", text)
        return text

    emit(
        "07_distributed_analyze.txt",
        (f"QUERY\n{sql}", ""),
        ("distributed EXPLAIN ANALYZE (runtime/queryinfo.py rollup: "
         "Driver -> Task ->\nStage; merged operator lines per fragment "
         "through the shared OperatorStats\nformatter, "
         "expected-vs-observed XLA lowerings from the census ledger,\n"
         "one summary line per task attempt; wall-clock values "
         "redacted to `#`)", redact(out)),
    )


def corpus_08_mesh_analyze():
    """Distributed EXPLAIN ANALYZE on the chunked mesh plane
    (parallel/mesh_plan.py + mesh_chunk.py): a colocated in-process
    cluster reports `data_plane=mesh` with the statically counted ICI
    collectives (all_to_all per hash exchange, all_gather per broadcast
    / single-row enforcement) and the session's chunk granularity; an
    ineligible plan reports the fallback reason instead. Timings
    redacted as in corpus 07."""
    import re

    from trino_tpu.runtime import DistributedQueryRunner

    r = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny"),
        n_workers=2,
        hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())
    r.session.mesh_chunk_rows = 256
    sql = (
        "select o_orderpriority, count(*) from orders join customer "
        "on o_custkey = c_custkey group by o_orderpriority"
    )
    out = r.execute("EXPLAIN ANALYZE " + sql).rows[0][0]
    sql_single = "select 1"
    out_single = r.execute("EXPLAIN ANALYZE " + sql_single).rows[0][0]

    def redact(text):
        text = re.sub(r"\b(wall|cpu)=\d+(\.\d+)?ms", r"\1=#ms", text)
        text = re.sub(r"\b(add|get|finish)=\d+(\.\d+)?", r"\1=#", text)
        text = re.sub(r"\btask q\d+\.", "task q#.", text)
        # corpus 15 pins the real membership= line
        text = re.sub(r"membership= .*", "membership= #", text)
        text = re.sub(r"replicas= .*", "replicas= #", text)
        text = re.sub(r"resident= .*", "resident= #", text)
        text = re.sub(r"recovery= .*", "recovery= #", text)
        text = re.sub(r"skew= .*", "skew= #", text)
        # process-global witness registry: lock/thread counts depend
        # on what ran before — corpus 16 pins the analyzer itself
        text = re.sub(r"concurrency= .*", "concurrency= #", text)
        return text

    emit(
        "08_mesh_analyze.txt",
        (f"QUERY\n{sql}", ""),
        ("mesh-eligible EXPLAIN ANALYZE: the trailing data_plane line "
         "shows where\nthe query's data plane runs — here the mesh, "
         "with the static collective\ncounts (the broadcast join rides "
         "all_gather, the partial->final agg\nexchange rides "
         "all_to_all) and mesh_chunk_rows=256 preemptible chunking\n"
         "(wall-clock values redacted to `#`)", redact(out)),
        (f"QUERY\n{sql_single}", ""),
        ("ineligible plan: a single-fragment query never reaches the "
         "mesh — the\ndata_plane line carries the static refusal "
         "reason", redact(out_single)),
    )


def corpus_09_resident_analyze():
    """The resident state tier (trino_tpu/resident/): a point lookup
    over a table named in `resident_tables` builds and pins a
    device-resident hash table on first touch (miss), probes it with a
    shape-stable jitted program thereafter (hit, zero rebuild), rides
    an INSERT on the append-only delta side (the pin survives under the
    table's NEW generation), and is evicted by non-append DML
    (generation bump -> rebuild on next touch, oracle-equal). The
    trailing `resident=` line of distributed EXPLAIN ANALYZE reports
    the pin population and lifetime counters; device byte counts are
    layout-dependent and redacted to `#`."""
    import re

    from trino_tpu.resident import GENERATIONS, RESIDENT
    from trino_tpu.resident.fastlane import (
        drain_compactions,
        try_resident_lookup,
    )
    from trino_tpu.runtime import DistributedQueryRunner

    RESIDENT.evict_all()
    RESIDENT.reset_stats()
    r = LocalQueryRunner(
        Session(catalog="memory", schema="s", resident_tables="s.kv")
    )
    r.register_catalog("memory", create_memory_connector())
    mem = r.catalogs.get("memory")
    n = 64
    mem.load_table(
        "s", "kv",
        [ColumnMetadata("k", T.BIGINT), ColumnMetadata("v", T.BIGINT)],
        [np.arange(n, dtype=np.int64), np.arange(n, dtype=np.int64) * 10],
    )
    events = []

    def look(k):
        res = try_resident_lookup(r, f"select v from kv where k = {k}")
        return None if res is None else res.rows

    events.append(f"lookup k=7        -> {look(7)}   (miss: build + pin)")
    events.append(f"lookup k=7        -> {look(7)}   (hit: device probe)")
    r.execute("insert into kv values (1000, 12345)")
    events.append(
        f"insert (1000, 12345); lookup k=1000 -> {look(1000)}   "
        "(delta append: pin survived re-keyed)"
    )
    drain_compactions()
    r.execute("update kv set v = 0 where k = 7")
    events.append(
        f"update k=7 -> v=0; lookup k=7       -> {look(7)}   "
        "(generation bump evicted the pin; rebuild, oracle-equal)"
    )
    stats = RESIDENT.stats()
    events.append(
        "counters: hits={hits} misses={misses} pins={pins} "
        "evictions={evictions} compactions={compactions}".format(**stats)
    )

    # the resident= line on a distributed EXPLAIN ANALYZE (stats are
    # process-global; the distributed runner reports the same tier)
    dr = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny"), n_workers=2,
        hash_partitions=2,
    )
    dr.register_catalog("tpch", create_tpch_connector())
    out = dr.execute(
        "EXPLAIN ANALYZE select count(*) from nation"
    ).rows[0][0]

    def redact(text):
        text = re.sub(r"\b(wall|cpu)=\d+(\.\d+)?ms", r"\1=#ms", text)
        text = re.sub(r"\b(add|get|finish)=\d+(\.\d+)?", r"\1=#", text)
        text = re.sub(r"\btask q\d+\.", "task q#.", text)
        # corpus 15 pins the real membership= line
        text = re.sub(r"membership= .*", "membership= #", text)
        text = re.sub(r"replicas= .*", "replicas= #", text)
        text = re.sub(r"pinned_bytes=\d+", "pinned_bytes=#", text)
        text = re.sub(r"recovery= .*", "recovery= #", text)
        text = re.sub(r"skew= .*", "skew= #", text)
        # process-global witness registry: lock/thread counts depend
        # on what ran before — corpus 16 pins the analyzer itself
        text = re.sub(r"concurrency= .*", "concurrency= #", text)
        return text

    emit(
        "09_resident_analyze.txt",
        ("resident fast-lane lifecycle (miss -> hit -> delta append -> "
         "DML eviction\n-> rebuild); every lookup answer is "
         "oracle-equal to the cold path", "\n".join(events)),
        ("distributed EXPLAIN ANALYZE: the trailing resident= line "
         "(process-global\npin population + lifetime counters; byte "
         "counts redacted to `#`)", redact(out)),
    )


def corpus_10_adaptive_analyze():
    """The adaptive execution tier (trino_tpu/adaptive/): the same
    distributed query analyzed with adaptive execution OFF (baseline —
    no estimate/observation deltas reported) and ON with a permissive
    re-plan threshold. The build side's modulo filter is exactly the
    shape the stats heuristics misestimate, so the adaptive run crosses
    the divergence gate at the build barrier, re-plans the remainder
    seeded with observed stats, and reports: per-fragment
    estimated_vs_observed lines in the stage rollup, the adaptive
    counters line, and the per-barrier observation that triggered the
    re-plan. Wall-clock values and the content-addressed spool key are
    redacted to `#`."""
    import re

    from trino_tpu.adaptive import SPOOL
    from trino_tpu.runtime import DistributedQueryRunner, Worker

    # the spool is process-wide; a leftover entry from an earlier run in
    # the same process would flip spool_stores=1 to spool_hits=1
    SPOOL.clear()
    cats = CatalogManager()
    cats.register("tpch", create_tpch_connector())
    workers = [Worker(f"corpus-aw{i}", cats) for i in range(2)]
    r = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny"),
        worker_handles=workers,
        hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())
    sql = (
        "select count(*) from supplier s "
        "join nation n on s_nationkey = n_nationkey "
        "where n_nationkey % 2 = 0"
    )
    off = r.execute("EXPLAIN ANALYZE " + sql).rows[0][0]

    workers_on = [Worker(f"corpus-aw{i+2}", cats) for i in range(2)]
    r_on = DistributedQueryRunner(
        Session(
            catalog="tpch", schema="tiny",
            adaptive_execution=True,
            adaptive_replan_threshold=1.3,
        ),
        worker_handles=workers_on,
        hash_partitions=2,
    )
    r_on.register_catalog("tpch", create_tpch_connector())
    on = r_on.execute("EXPLAIN ANALYZE " + sql).rows[0][0]

    def redact(text):
        text = re.sub(r"\b(wall|cpu)=\d+(\.\d+)?ms", r"\1=#ms", text)
        text = re.sub(r"\b(add|get|finish)=\d+(\.\d+)?", r"\1=#", text)
        text = re.sub(r"\btask q\d+\.", "task q#.", text)
        # corpus 15 pins the real membership= line
        text = re.sub(r"membership= .*", "membership= #", text)
        text = re.sub(r"replicas= .*", "replicas= #", text)
        text = re.sub(r"resident= .*", "resident= #", text)
        text = re.sub(r"recovery= .*", "recovery= #", text)
        text = re.sub(r"skew= .*", "skew= #", text)
        # process-global witness registry: lock/thread counts depend
        # on what ran before — corpus 16 pins the analyzer itself
        text = re.sub(r"concurrency= .*", "concurrency= #", text)
        text = re.sub(r"spool=[0-9a-f]+", "spool=#", text)
        return text

    emit(
        "10_adaptive_analyze.txt",
        (f"QUERY\n{sql}", ""),
        ("adaptive_execution = off  (estimates never checked against "
         "observations;\nthe misestimated build side rides through "
         "silently)", redact(off)),
        ("adaptive_execution = on, adaptive_replan_threshold = 1.3  "
         "(the build\nbarrier observes 13 rows against an estimate of "
         "8.25, crosses the\nthreshold, and re-plans the remainder with "
         "the completed build spooled\nas a literal source; "
         "per-fragment estimated_vs_observed lines land in\nthe stage "
         "rollup and the adaptive section closes the report)",
         redact(on)),
    )


def corpus_11_recovery_analyze():
    """The recovery tier (trino_tpu/recovery/): a chunked mesh query
    with `mesh_checkpoint_interval_chunks` set snapshots its device
    carries at checkpoint boundaries; an injected MeshDeviceLost
    mid-run resumes from the last checkpoint instead of chunk 0 (the
    already-accumulated chunks are never re-executed and the resumed
    stretch lands on the same warm ladder rungs), oracle-equal to the
    uninterrupted run. The trailing `recovery=` line of EXPLAIN ANALYZE
    pins the lifetime counters and the `resumed_from_chunk=k/K`
    position of the most recent mesh run (ANALYZE itself executes the
    task plane to collect per-operator stats, so the faulted run comes
    first). Counters are reset up front so the numbers are exact;
    timings redacted as in corpus 07."""
    import re

    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.recovery import CHECKPOINTS
    from trino_tpu.runtime import DistributedQueryRunner

    CHECKPOINTS.clear()
    CHECKPOINTS.reset_stats()
    METRICS.remove("recovery.spooled_stage_hits")
    r = DistributedQueryRunner(
        Session(
            catalog="tpch", schema="tiny",
            mesh_chunk_rows=1024, mesh_checkpoint_interval_chunks=2,
        ),
        n_workers=2,
        hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())
    sql = (
        "select l_returnflag, count(*), sum(l_quantity) from lineitem "
        "group by l_returnflag"
    )
    # one clean run to learn the chunk geometry (and warm the ladder)
    clean = r.execute(sql).rows
    clean_taken = CHECKPOINTS.taken
    n_chunks = mesh_chunk.LAST_RUN_INFO["chunks"]
    target = n_chunks - 2  # fault late: most chunks already settled
    state = {"fired": False}

    def fault_once(k, K):
        if not state["fired"] and k == target:
            state["fired"] = True
            raise mesh_chunk.MeshDeviceLost(
                f"injected device loss at chunk {k}/{K}"
            )

    mesh_chunk.MESH_FAULT_HOOK = fault_once
    try:
        faulted = r.execute(sql).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    assert state["fired"], "fault hook never reached its target chunk"
    info = mesh_chunk.LAST_RUN_INFO
    events = [
        f"clean run: chunks={n_chunks} "
        f"checkpoints_taken={clean_taken}",
        f"device loss injected at chunk {target}/{n_chunks}",
        f"resumed_from_chunk={info['resumed_from_chunk']} "
        f"resumes={info['resumes']} "
        f"executed_chunk_steps={info['executed_chunk_steps']} "
        "(completed chunks never re-executed)",
        f"rows oracle-equal to uninterrupted run: {faulted == clean}",
    ]
    out = r.execute("EXPLAIN ANALYZE " + sql).rows[0][0]

    def redact(text):
        text = re.sub(r"\b(wall|cpu)=\d+(\.\d+)?ms", r"\1=#ms", text)
        text = re.sub(r"\b(add|get|finish)=\d+(\.\d+)?", r"\1=#", text)
        text = re.sub(r"\btask q\d+\.", "task q#.", text)
        # corpus 15 pins the real membership= line
        text = re.sub(r"membership= .*", "membership= #", text)
        text = re.sub(r"replicas= .*", "replicas= #", text)
        text = re.sub(r"resident= .*", "resident= #", text)
        text = re.sub(r"skew= .*", "skew= #", text)
        # process-global witness registry: lock/thread counts depend
        # on what ran before — corpus 16 pins the analyzer itself
        text = re.sub(r"concurrency= .*", "concurrency= #", text)
        return text

    emit(
        "11_recovery_analyze.txt",
        (f"QUERY\n{sql}", ""),
        ("checkpointed mesh run under an injected device loss "
         "(mesh_chunk_rows=1024,\nmesh_checkpoint_interval_chunks=2): "
         "the run resumes from the last checkpoint\ninstead of chunk 0 "
         "and stays on the mesh plane", "\n".join(events)),
        ("EXPLAIN ANALYZE after the faulted run: the trailing "
         "recovery= line reports\nthe lifetime checkpoint/resume "
         "counters plus the resume position of the\nmost recent mesh "
         "run (wall-clock values redacted to `#`)", redact(out)),
    )


def corpus_12_skew_analyze():
    """The skew-aware join plane (ISSUE 16): a build side whose modal
    key holds 40% of its rows crosses skew_hot_key_threshold at the
    adaptive build barrier — the controller classifies the heavy hitter
    from OBSERVED stats (never estimates), annotates the join with
    skew_hot_keys (salted repartition on the mesh plane: hot build rows
    replicate over all_gather, hot probe rows salt across shards), and
    the adaptive report grows a `skew:` line. Separately the MXU
    join-project kernel (ops/mxu_join.py) takes a high-fanout
    agg-over-join on the local path without ever expanding the pair
    batch. The trailing `skew=` line of distributed EXPLAIN ANALYZE
    pins the lifetime counters; they are reset up front so the numbers
    are exact. Timings redacted as in corpus 07."""
    import re

    from trino_tpu.adaptive import SPOOL
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.runtime import DistributedQueryRunner, Worker

    SPOOL.clear()
    for c in ("heavy_hitters_detected", "salted_exchanges",
              "mxu_join_selected", "spill_mode_replans"):
        METRICS.remove(f"skew.{c}")

    def load(conn):
        rng = np.random.default_rng(23)
        n, nk = 2000, 40
        conn.load_table(
            "s", "facts",
            [ColumnMetadata("k1", T.BIGINT), ColumnMetadata("v", T.BIGINT)],
            [rng.integers(0, nk, n).astype(np.int64),
             rng.integers(0, 100, n).astype(np.int64)],
        )
        # build side with a 40% modal key (key 0): the heavy hitter
        bk = np.concatenate([
            np.zeros(160, dtype=np.int64),
            rng.integers(1, nk, 240).astype(np.int64),
        ])
        conn.load_table(
            "s", "hot_dim",
            [ColumnMetadata("k", T.BIGINT), ColumnMetadata("name", T.VARCHAR)],
            [bk, np.array([f"g{i % 6}" for i in range(bk.size)],
                          dtype=object)],
        )
        return conn

    sql = (
        "select d.name, sum(f.v), count(*) from facts f "
        "join hot_dim d on f.k1 = d.k group by d.name order by 1"
    )

    # 1. MXU join-project on the local path (fanout 10 x ndv 40)
    lr = LocalQueryRunner(Session(
        catalog="memory", schema="s",
        mxu_join_enabled=True, mxu_join_min_work=16.0,
    ))
    lr.register_catalog("memory", load(MemoryConnector()))
    mxu_rows = lr.execute(sql).rows
    events = [
        f"local MXU join-project: {len(mxu_rows)} groups, "
        f"mxu_join_selected="
        f"{int(METRICS.snapshot().get('skew.mxu_join_selected', 0.0))}",
    ]

    # 2. heavy-hitter classification at the adaptive build barrier
    cats = CatalogManager()
    cats.register("memory", load(MemoryConnector()))
    workers = [Worker(f"corpus-sw{i}", cats) for i in range(2)]
    r = DistributedQueryRunner(
        Session(
            catalog="memory", schema="s",
            adaptive_execution=True,
            skewed_join_salting=True,
            skew_hot_key_threshold=0.2,
        ),
        worker_handles=workers,
        hash_partitions=2,
    )
    r.register_catalog("memory", load(MemoryConnector()))
    out = r.execute("EXPLAIN ANALYZE " + sql).rows[0][0]

    def redact(text):
        text = re.sub(r"\b(wall|cpu)=\d+(\.\d+)?ms", r"\1=#ms", text)
        text = re.sub(r"\b(add|get|finish)=\d+(\.\d+)?", r"\1=#", text)
        text = re.sub(r"\btask q\d+\.", "task q#.", text)
        # corpus 15 pins the real membership= line
        text = re.sub(r"membership= .*", "membership= #", text)
        text = re.sub(r"replicas= .*", "replicas= #", text)
        text = re.sub(r"resident= .*", "resident= #", text)
        text = re.sub(r"recovery= .*", "recovery= #", text)
        text = re.sub(r"spool=[0-9a-f]+", "spool=#", text)
        # process-global witness registry: lock/thread counts depend
        # on what ran before — corpus 16 pins the analyzer itself
        text = re.sub(r"concurrency= .*", "concurrency= #", text)
        return text

    emit(
        "12_skew_analyze.txt",
        (f"QUERY\n{sql}", ""),
        ("MXU join-project selection (mxu_join_enabled=true): the "
         "grouped aggregate\nover the inner join lowers to the "
         "indicator-matmul kernel — per-key sums\non the systolic "
         "array, no pair expansion", "\n".join(events)),
        ("distributed EXPLAIN ANALYZE with adaptive_execution=true, "
         "skewed_join_salting\n=true (hot_dim's modal key holds 40% of "
         "build rows > skew_hot_key_threshold\n=0.2: the build barrier "
         "classifies it from observed stats, the adaptive\nsection "
         "grows its skew: line, and the join is annotated for salted "
         "mesh\nrepartition; the trailing skew= line pins the lifetime "
         "counters)", redact(out)),
    )


def corpus_13_replica_analyze():
    """The replicated serving plane (trino_tpu/runtime/replicas.py): the
    8-device corpus mesh carved into two 4-wide sub-meshes. Two warm
    runs alternate across the replicas (round-robin placement — each
    sub-mesh pays its device-set lowering once); an injected
    MeshDeviceLost on the replica serving the third run fails the query
    over to its sibling, which resumes from the host-portable
    checkpoint. The trailing `replicas=` line of EXPLAIN ANALYZE pins
    the grid shape, per-replica lifecycle states and THIS runner's
    placement/failover counters — instance-scoped, so the numbers are
    exact. Timings redacted as in corpus 07."""
    import re

    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.recovery import CHECKPOINTS
    from trino_tpu.runtime import DistributedQueryRunner

    CHECKPOINTS.clear()
    r = DistributedQueryRunner(
        Session(
            catalog="tpch", schema="tiny",
            mesh_replicas=2, mesh_chunk_rows=1024,
            mesh_checkpoint_interval_chunks=1, mesh_resume_attempts=0,
        ),
        n_workers=2,
        hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())
    sql = (
        "select l_returnflag, count(*), sum(l_quantity) from lineitem "
        "group by l_returnflag"
    )
    # two warm runs: sequential placements alternate replicas, so both
    # sub-meshes hold warm programs before the fault
    clean = r.execute(sql).rows
    r.execute(sql)
    n_chunks = mesh_chunk.LAST_RUN_INFO["chunks"]
    target = n_chunks - 2
    state = {"victim": None, "fired": False}

    def kill_victim(k, K):
        rep = mesh_chunk.active_replica()
        if rep is None:
            return
        if state["victim"] is None:
            state["victim"] = rep
        if not state["fired"] and rep == state["victim"] and k >= target:
            state["fired"] = True
            raise mesh_chunk.MeshDeviceLost(
                f"injected: replica {rep} lost at chunk {k}/{K}"
            )

    mesh_chunk.MESH_FAULT_HOOK = kill_victim
    try:
        faulted = r.execute(sql).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    assert state["fired"], "fault hook never reached its target chunk"
    info = mesh_chunk.LAST_RUN_INFO
    rm = r._replicas
    events = [
        f"grid: {rm.n_replicas} replicas x {rm.partition_width} devices "
        "(two 4-wide sub-meshes of the 8-device corpus mesh)",
        f"replica {state['victim']} lost at chunk "
        f"{target}/{n_chunks}",
        f"failover: resumed_from_chunk={info['resumed_from_chunk']} "
        f"on the sibling sub-mesh (failovers={rm.failovers})",
        f"rows oracle-equal to the uninterrupted run: {faulted == clean}",
    ]
    out = r.execute("EXPLAIN ANALYZE " + sql).rows[0][0]

    def redact(text):
        text = re.sub(r"\b(wall|cpu)=\d+(\.\d+)?ms", r"\1=#ms", text)
        text = re.sub(r"\b(add|get|finish)=\d+(\.\d+)?", r"\1=#", text)
        text = re.sub(r"\btask q\d+\.", "task q#.", text)
        # corpus 15 pins the real membership= line
        text = re.sub(r"membership= .*", "membership= #", text)
        text = re.sub(r"resident= .*", "resident= #", text)
        text = re.sub(r"recovery= .*", "recovery= #", text)
        text = re.sub(r"skew= .*", "skew= #", text)
        # process-global witness registry: lock/thread counts depend
        # on what ran before — corpus 16 pins the analyzer itself
        text = re.sub(r"concurrency= .*", "concurrency= #", text)
        return text

    emit(
        "13_replica_analyze.txt",
        (f"QUERY\n{sql}", ""),
        ("replica failover under an injected device loss "
         "(mesh_replicas=2): the\nquery resumes on the sibling sub-mesh "
         "from the host-portable checkpoint\ninstead of restarting at "
         "chunk 0", "\n".join(events)),
        ("EXPLAIN ANALYZE after the failover: the trailing replicas= "
         "line reports\nthe grid shape, per-replica lifecycle states "
         "(a=active) and this runner's\ninstance-scoped "
         "placement/failover counters (wall-clock values redacted\nto "
         "`#`)", redact(out)),
    )


def corpus_14_scheduler_analyze():
    """The preemptive mesh scheduler (trino_tpu/runtime/scheduler.py):
    a chunked analytic streams chunk-steps on the full-width mesh; a
    fast-lane point lookup (dimension-decorated, serving/admission.py
    `is_fast_lane`) arrives mid-stream and PREEMPTS it — the analytic
    parks (device carries snapshot to the host checkpoint store, device
    memory released), the lookup runs, and the analytic resumes from
    chunk k on the same warm rungs: zero re-executed chunk-steps,
    byte-identical rows. The trailing `scheduler=` line of EXPLAIN
    ANALYZE pins the park/resume/preemption counters — instance-scoped,
    so the numbers are exact. Timings redacted as in corpus 07."""
    import re
    import threading
    import time

    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.recovery import CHECKPOINTS
    from trino_tpu.runtime import DistributedQueryRunner

    CHECKPOINTS.clear()
    r = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny", mesh_chunk_rows=1024),
        n_workers=2,
        hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())
    analytic = (
        "select l_returnflag, count(*), sum(l_quantity) from lineitem "
        "group by l_returnflag"
    )
    point = (
        "select n_name, r_name from nation join region "
        "on n_regionkey = r_regionkey where n_nationkey = 3"
    )
    # warm both shapes solo: every program below re-dispatches cached
    # rungs, so the preempted run demonstrably mints zero new lowerings
    clean = r.execute(analytic).rows
    n_chunks = mesh_chunk.LAST_RUN_INFO["chunks"]
    point_clean = r.execute(point).rows
    state = {"fired": False, "point_rows": None}
    main_thread = threading.current_thread()

    def inject_point(k, K):
        # fire once, on the analytic's chunk loop only (the point
        # lookup is single-chunk, and its run is on another thread)
        if threading.current_thread() is not main_thread:
            return
        if state["fired"] or k < 1 or K < 3:
            return
        state["fired"] = True

        def run_point():
            state["point_rows"] = r.execute(point).rows

        threading.Thread(target=run_point, daemon=True).start()
        # hold this boundary until the fast submission reaches the run
        # queue, so the NEXT boundary deterministically parks
        sched = r._mesh_scheduler
        deadline = time.monotonic() + 10.0
        while (
            sched.waiting_count(fast=True) < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.002)

    mesh_chunk.MESH_FAULT_HOOK = inject_point
    try:
        parked_rows = r.execute(analytic).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    assert state["fired"], "preempt hook never fired"
    info = mesh_chunk.LAST_RUN_INFO
    assert info["parks"] == 1, f"expected exactly one park: {info}"
    deadline = time.monotonic() + 10.0
    while state["point_rows"] is None and time.monotonic() < deadline:
        time.sleep(0.002)
    events = [
        f"analytic: {n_chunks} chunk-steps on the full-width mesh; a "
        "fast-lane point lookup arrived at chunk 1",
        f"park: parks={info['parks']} — carries snapshotted to the "
        "host checkpoint store, device memory released, lookup granted "
        "the mesh",
        f"point lookup rows == warm solo run: "
        f"{state['point_rows'] == point_clean}",
        f"resume: unparks={info['unparks']}, "
        f"executed_chunk_steps={info['executed_chunk_steps']} "
        f"(== {n_chunks}: zero re-executed chunk-steps)",
        f"rows byte-identical to the uninterrupted run: "
        f"{parked_rows == clean}",
    ]
    out = r.execute("EXPLAIN ANALYZE " + analytic).rows[0][0]

    def redact(text):
        text = re.sub(r"\b(wall|cpu)=\d+(\.\d+)?ms", r"\1=#ms", text)
        text = re.sub(r"\b(add|get|finish)=\d+(\.\d+)?", r"\1=#", text)
        text = re.sub(r"\btask q\d+\.", "task q#.", text)
        # corpus 15 pins the real membership= line
        text = re.sub(r"membership= .*", "membership= #", text)
        text = re.sub(r"resident= .*", "resident= #", text)
        text = re.sub(r"recovery= .*", "recovery= #", text)
        text = re.sub(r"skew= .*", "skew= #", text)
        # process-global witness registry: lock/thread counts depend
        # on what ran before — corpus 16 pins the analyzer itself
        text = re.sub(r"concurrency= .*", "concurrency= #", text)
        return text

    emit(
        "14_scheduler_analyze.txt",
        (f"QUERY\n{analytic}", ""),
        ("checkpoint-backed preemption on one mesh: a fast-lane point "
         "lookup\narriving mid-stream parks the running analytic at the "
         "next chunk boundary\nand the analytic resumes from chunk k "
         "warm — zero re-executed chunk-steps,\nbyte-identical rows",
         "\n".join(events)),
        ("EXPLAIN ANALYZE after the park/resume cycle: the trailing "
         "scheduler=\nline reports this runner's instance-scoped "
         "park/resume/preemption\ncounters (wall-clock values redacted "
         "to `#`)", redact(out)),
    )


def corpus_15_fabric_analyze():
    """The multi-host replica fabric (trino_tpu/runtime/fabric.py).
    Two legs. Transport: a loopback FabricServer fronting a peer
    HostFabric takes a framed checkpoint push, serves it back
    byte-identical, and refuses a corrupted payload typed on its
    sha256 digest — instance-scoped endpoint counters pin the
    exchange. Membership: a replicated runner suffers a sibling
    membership flap (leave + rejoin, each bumping the monotonic
    epoch) immediately followed by a device loss on the serving
    replica; failover resumes on the rejoined sibling because its
    join_epoch equals the fault epoch, while a resume context
    captured BEFORE the flap is refused typed (MembershipEpochError).
    The trailing `membership=` line of EXPLAIN ANALYZE pins the epoch
    and join/leave/fence counters — instance-scoped, so the numbers
    are exact. Timings redacted as in corpus 07."""
    import re

    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.recovery import CHECKPOINTS
    from trino_tpu.recovery.checkpoint import (
        MeshCheckpoint,
        MeshCheckpointStore,
    )
    from trino_tpu.runtime import DistributedQueryRunner
    from trino_tpu.runtime.fabric import (
        HostFabric,
        MembershipEpochError,
        checkpoint_digest,
    )
    from trino_tpu.runtime.http import FabricClient, FabricServer

    # -- transport leg: push / pull / corrupt over a loopback endpoint
    peer_store = MeshCheckpointStore()
    peer = HostFabric(store=peer_store, host_id="peer")
    srv = FabricServer(peer, internal_secret=None, require_secret=False)
    client = FabricClient(srv.uri, internal_secret=None)
    key = ("corpus15", "fabric", 0)
    data = MeshCheckpoint(
        next_chunk=3, n_chunks=8, chunk_cap=64,
        resolved_caps={"rows": 64},
        carries_host=(
            np.arange(64, dtype=np.int64),
            np.linspace(0.0, 1.0, 64),
        ),
        tables=(), generations=(),
    ).to_bytes()
    pushed = client.push_checkpoint(key, data)
    back, digest = client.pull_checkpoint(key)
    corrupt = bytearray(data)
    corrupt[len(corrupt) // 2] ^= 0xFF
    # original digest over corrupted bytes: the endpoint must refuse
    rejected = client.push_checkpoint(
        key, bytes(corrupt), digest=checkpoint_digest(data)
    )
    stored = peer_store.export_bytes(key)
    srv.stop()
    transport = [
        "peer endpoint: HostFabric behind a loopback FabricServer "
        "(single-process\nembedding, require_secret=False; a networked "
        "fabric refuses to start\nwithout TRINO_TPU_INTERNAL_SECRET)",
        f"push accepted: imported={pushed.get('imported')} — the "
        "encoded checkpoint key\ntravels length-prefixed in the request "
        "BODY, never the request line",
        f"pull round-trip byte-identical: {back == data} (digest "
        f"match: {digest == checkpoint_digest(data)})",
        "corrupted payload under the original digest refused typed: "
        f"imported={rejected.get('imported')} "
        f"reason={rejected.get('reason')}",
        f"stored entry unpoisoned by the refused push: {stored == data}",
        f"endpoint counters: received={peer.received} "
        f"served={peer.served} digest_rejects={peer.digest_rejects}",
    ]

    # -- membership leg: flap + host loss on a replicated runner ------
    CHECKPOINTS.clear()
    r = DistributedQueryRunner(
        Session(
            catalog="tpch", schema="tiny",
            mesh_replicas=2, mesh_chunk_rows=1024,
            mesh_checkpoint_interval_chunks=1, mesh_resume_attempts=0,
        ),
        n_workers=2,
        hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())
    sql = (
        "select l_returnflag, count(*), sum(l_quantity) from lineitem "
        "group by l_returnflag"
    )
    # two warm runs: round-robin placement warms both sub-meshes
    clean = r.execute(sql).rows
    r.execute(sql)
    n_chunks = mesh_chunk.LAST_RUN_INFO["chunks"]
    target = n_chunks - 2
    state = {"victim": None, "fired": False, "pre_epoch": None}

    def flap_then_kill(k, K):
        rep = mesh_chunk.active_replica()
        if rep is None:
            return
        if state["victim"] is None:
            state["victim"] = rep
        if not state["fired"] and rep == state["victim"] and k >= target:
            state["fired"] = True
            rm_ = r._replicas
            state["pre_epoch"] = rm_.membership_epoch
            # sibling flaps (heartbeat loss + recovery) just before the
            # serving replica dies: two epoch bumps, then the fault
            rm_.leave(1 - rep)
            rm_.join(1 - rep)
            raise mesh_chunk.MeshDeviceLost(
                f"injected: replica {rep} lost at chunk {k}/{K} "
                "after a sibling membership flap"
            )

    mesh_chunk.MESH_FAULT_HOOK = flap_then_kill
    try:
        faulted = r.execute(sql).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    assert state["fired"], "fault hook never reached its target chunk"
    info = mesh_chunk.LAST_RUN_INFO
    rm = r._replicas
    sib = 1 - state["victim"]
    # a resume context captured BEFORE the flap is stale: the sibling's
    # join_epoch has moved past it, so the fence refuses it typed
    try:
        rm.require_epoch(rm.replicas[sib], state["pre_epoch"])
        fenced = False
    except MembershipEpochError:
        fenced = True
    events = [
        f"grid: {rm.n_replicas} replicas x {rm.partition_width} "
        f"devices; membership epoch starts at {state['pre_epoch']}",
        f"flap: replica {sib} left and rejoined mid-run (epoch "
        f"{state['pre_epoch']} -> {rm.membership_epoch}: every leave "
        "and join bumps it)",
        f"replica {state['victim']} lost at chunk {target}/{n_chunks}; "
        f"failover resumed_from_chunk={info['resumed_from_chunk']} on "
        "the rejoined sibling\n(its join_epoch equals the fault epoch, "
        "so the resume is admitted)",
        f"rows oracle-equal to the uninterrupted run: {faulted == clean}",
        f"stale resume context (epoch {state['pre_epoch']}, captured "
        "before the flap)\nrefused typed with MembershipEpochError: "
        f"{fenced}",
    ]
    out = r.execute("EXPLAIN ANALYZE " + sql).rows[0][0]

    def redact(text):
        text = re.sub(r"\b(wall|cpu)=\d+(\.\d+)?ms", r"\1=#ms", text)
        text = re.sub(r"\b(add|get|finish)=\d+(\.\d+)?", r"\1=#", text)
        text = re.sub(r"\btask q\d+\.", "task q#.", text)
        text = re.sub(r"replicas= .*", "replicas= #", text)
        text = re.sub(r"resident= .*", "resident= #", text)
        text = re.sub(r"recovery= .*", "recovery= #", text)
        text = re.sub(r"skew= .*", "skew= #", text)
        # process-global witness registry: lock/thread counts depend
        # on what ran before — corpus 16 pins the analyzer itself
        text = re.sub(r"concurrency= .*", "concurrency= #", text)
        return text

    emit(
        "15_fabric_analyze.txt",
        (f"QUERY\n{sql}", ""),
        ("checkpoint transport across the host boundary: framed "
         "push/pull with\nsha256 content digests; a corrupted payload "
         "is refused typed and never\npoisons the receiving store",
         "\n".join(transport)),
        ("heartbeat-driven membership under a flap + host loss "
         "(mesh_replicas=2):\nthe rejoined sibling resumes from the "
         "host-portable checkpoint; a\npre-flap resume context is "
         "fenced on the membership epoch",
         "\n".join(events)),
        ("EXPLAIN ANALYZE after the flap + failover: the trailing "
         "membership=\nline reports the monotonic epoch and this "
         "runner's instance-scoped\njoin/leave/fence counters "
         "(wall-clock values redacted to `#`)", redact(out)),
    )


# deliberately-broken fixture modules for corpus 16: a two-lock order
# cycle and a bare write to a guarded_by-annotated global. Analyzed
# in-memory (never imported), so the file:line coordinates are stable.
_CYCLE_FIXTURE = """\
from trino_tpu.analysis.witness import named_lock

_lock_a = named_lock("deadlock_fixture._lock_a")
_lock_b = named_lock("deadlock_fixture._lock_b")


def forward():
    with _lock_a:
        with _lock_b:
            pass


def backward():
    with _lock_b:
        with _lock_a:
            pass
"""

_BARE_WRITE_FIXTURE = """\
from trino_tpu.analysis.witness import named_lock

_cache_lock = named_lock("bare_write_fixture._cache_lock")
CACHE = {}  # guarded_by: _cache_lock


def bad_write(key, value):
    CACHE[key] = value
"""


def corpus_16_concurrency_analyze():
    """The concurrency soundness plane (trino_tpu/analysis/): the pinned
    output of the static lock-order / shared-state analyzer over the
    whole package — the lock inventory, the may-hold-while-acquiring
    order, and zero findings — plus the analyzer's findings on two
    deliberately broken fixture modules, showing what a violation report
    looks like (cycle with both witness paths; bare guarded write)."""
    from trino_tpu.analysis import analyze_package, analyze_sources

    rep = analyze_package()
    s = rep.summary()
    summary = "\n".join(f"{k}={v}" for k, v in s.items())
    order = "\n".join(
        f"{a} -> {b}" for a, b in sorted(rep.graph.edges)
    ) or "(no lock is ever acquired while another is held)"

    bad = analyze_sources({
        "deadlock_fixture": (
            "fixtures/deadlock_fixture.py", _CYCLE_FIXTURE),
        "bare_write_fixture": (
            "fixtures/bare_write_fixture.py", _BARE_WRITE_FIXTURE),
    })
    findings = "\n".join(
        f"[{f.kind}] {f.file}:{f.line}\n  {f.message}"
        for f in bad.findings
    )

    emit(
        "16_concurrency_analyze.txt",
        ("QUERY\nanalyze_package()  (trino_tpu/analysis/ static passes)",
         ""),
        ("whole-package summary (the CI gate's JSON, one key per line; "
         "a diff\nhere means the engine's locking structure actually "
         "changed)", summary),
        ("the may-hold-while-acquiring order — every (held, acquired) "
         "pair the\nstatic pass can prove, including through call "
         "edges; the runtime\nwitness seeds its partial order from "
         "these", order),
        ("analyzer findings on two deliberately broken fixture modules "
         "(the\nsame fixtures tests/test_concurrency_analysis.py "
         "asserts on): a\ntwo-lock acquisition cycle reported with "
         "both witness paths, and a\nbare write to a guarded_by-"
         "annotated global", findings),
    )


def write_all(out_dir=None):
    """Regenerate every corpus file (into `out_dir` when given — used
    by tests/test_explain_corpus.py to diff against committed files)."""
    if out_dir is not None:
        _OUT_DIR[0] = out_dir
    try:
        corpus_01_transitive()
        corpus_02_scan_pushdown()
        corpus_03_partial_agg()
        corpus_04_elided_exchange()
        corpus_05_plan_validation()
        corpus_06_compile_regime()
        corpus_07_distributed_analyze()
        corpus_08_mesh_analyze()
        corpus_09_resident_analyze()
        corpus_10_adaptive_analyze()
        corpus_11_recovery_analyze()
        corpus_12_skew_analyze()
        corpus_13_replica_analyze()
        corpus_14_scheduler_analyze()
        corpus_15_fabric_analyze()
        corpus_16_concurrency_analyze()
    finally:
        _OUT_DIR[0] = HERE


if __name__ == "__main__":
    write_all()
