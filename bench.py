"""Benchmark entry point — prints ONE JSON line.

North-star configs (BASELINE.md): TPC-H Q3 (SF1/SF10) and Q18 (SF10)
wall-clock through the full SQL engine (parse -> analyze -> plan ->
jitted device pipeline), steady state (prewarm + repeat, the benchto
methodology, SURVEY.md §6), plus hash-probe GB/s per chip. Headline
metric = Q18 SF10 (large-state aggregation + semi-join, BASELINE
config 3); the other measurements ride in "extra".

`vs_baseline` is the speedup of the default device (the TPU chip under
the driver) over this host's CPU backend running the IDENTICAL engine
in a subprocess — the reference publishes no absolute numbers
(BASELINE.md), so the same engine's CPU path is the comparison point,
standing in for the "32-vCPU Java worker" of the north star.

The headline JSON line is re-emitted after EVERY completed config, so
the last stdout line is always the best complete result no matter when
the process is killed (the driver runs this under a hard timeout; a
bench that loses finished measurements to a later config's overrun
ships nothing).

Env knobs:
  BENCH_FAST=1     -> only Q1 SF1 (smoke)
  BENCH_RUNS=N     -> steady-state repetitions (default 3)
  BENCH_SKIP_CPU=1 -> skip the CPU-subprocess baseline
  BENCH_SF_LARGE=N -> scale factor for the large configs (default 10)
  BENCH_DEADLINE=N -> global wall budget in seconds (default 2700);
                      remaining configs are skipped when short, SF-large
                      CPU baselines first (the driver's own timeout can
                      land anytime — the last emitted line always holds
                      the best complete result)
  BENCH_WIDESTR_ROWS=N -> rows for the wide-string GROUP BY config

Flags:
  --chaos-smoke [seed]  run the seeded chaos harness (runtime/chaos.py)
                     over representative TPC-H shapes under every fault
                     class, the lifecycle maneuvers, and the timebound
                     scenarios (hung operator vs the stuck-task
                     watchdog, abandoned client vs the reaper); exits
                     non-zero if any run diverges from the clean answer,
                     exceeds its injected-failure bound, leaks a
                     resource-group slot, or leaves memory reserved; no
                     device needed (runs before preflight)
  --warmup-smoke     run the q72-class plan cold-with-warmup vs
                     cold-without (compile/warmup.py) and print per-arm
                     compile counts + walls; exits non-zero if the
                     warmup-on run observes more distinct XLA shape
                     classes than the census predicted; no device
                     needed (runs before preflight)
  --trace-smoke      run a traced distributed TPC-H query plus one
                     chaos scenario (runtime/tracing.py), validate the
                     exported span tree and Chrome trace-event schema,
                     and measure tracing overhead on the Q1/Q6 pair;
                     exits non-zero on an invariant violation or >5%
                     wall overhead; no device needed (runs before
                     preflight)
  --mesh-smoke       run Q1/Q6 plus a hash join chunked over an
                     8-device CPU mesh (parallel/mesh_chunk.py):
                     answer-equality vs the page plane, >=1 all_to_all,
                     zero new XLA lowerings on second execution, and a
                     mid-query deadline kill preempting between chunks
                     with the typed EXCEEDED_TIME_LIMIT error and no
                     page fallback; re-execs itself with an 8-device
                     host platform, so no device needed
  --resident-smoke   exercise the resident state tier
                     (trino_tpu/resident/): warm point-lookup p50 at
                     device-probe latency (faster than the cold path,
                     resident.hits > 0, zero rebuilds), oracle-equality
                     through DML invalidation, the delta-append path
                     and background compaction, zero post-warmup XLA
                     lowerings for repeated pinned probes, and graceful
                     cold-path degradation under a zero pin budget; no
                     device needed (runs before preflight)
  --adaptive-smoke   exercise the adaptive execution tier
                     (trino_tpu/adaptive/): a q72-class join over
                     deliberately misestimated stats, two arms on the
                     same lying catalog; the adaptive arm must re-plan
                     >=1 time, stay oracle-equal with the non-adaptive
                     arm, beat its warm wall, and mint zero new XLA
                     lowerings in the warm loop; JSON re-plan counts,
                     exit 1 on violation; no device needed (runs before
                     preflight)
  --recovery-smoke   exercise the recovery tier (trino_tpu/recovery/):
                     a q72-class deep join chunked over an 8-device CPU
                     mesh takes an injected device loss at chunk k of K
                     twice — once with checkpointing off (the fault
                     discards every completed chunk and the page plane
                     recomputes from zero) and once with chunk
                     checkpointing on (the run resumes from the last
                     checkpoint); the resumed arm must stay oracle-
                     equal, re-execute fewer chunks than the restart,
                     beat the restart wall, and mint zero new XLA
                     lowerings; re-execs itself with an 8-device host
                     platform, so no device needed
  --skew-smoke       exercise the skew-aware join plane: a zipf-skewed
                     join whose build barrier detects the heavy hitter
                     from observed stats and salts the mesh exchange
                     (oracle-equal, salted counters advance, zero new
                     lowerings warm), plus a high-fanout join-aggregate
                     lowered to the MXU join-project kernel (oracle-
                     equal vs the gather path, beats its warm wall);
                     re-execs itself with an 8-device host platform,
                     so no device needed
  --preempt-smoke    exercise checkpoint-backed preemptive
                     multi-tenancy (runtime/scheduler.py): point-
                     lookup p99 under a streaming q72-class analytic
                     must stay within 5x the solo p99 (the fast lane
                     preempts at chunk boundaries), a mid-analytic
                     arrival parks the device carries and resumes them
                     byte-identical with zero re-executed chunks and
                     zero new lowerings, and the park/resume wall must
                     beat abandoning + rerunning the analytic;
                     re-execs itself with an 8-device host platform,
                     so no device needed
  --multihost-smoke  exercise the multi-host replica fabric
                     (runtime/fabric.py) across a REAL process
                     boundary: a victim coordinator subprocess streams
                     its chunk checkpoints to the survivor's fabric
                     endpoint and hard-kills itself (os._exit) at
                     chunk 3K/4; the survivor digest-rejects a
                     corrupted replay, then resumes the query from
                     exactly the fault chunk — oracle-equal, zero
                     re-executed chunk-steps, zero new lowerings,
                     beating its own warm full-length wall;
                     re-execs itself with an 8-device host platform,
                     so no device needed
  --analyze          run the static concurrency analyzer
                     (trino_tpu/analysis/) over the whole package:
                     lock-order cycle detection on the may-hold-while-
                     acquiring graph, guarded_by annotation checking,
                     unlocked-global-write lint, and the unregistered-
                     thread-spawn lint; prints a JSON summary plus one
                     ANALYZE-VIOLATION line per finding at file:line;
                     exits non-zero on any finding; no device needed
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from typing import Sequence

RUNS = int(os.environ.get("BENCH_RUNS", "3"))
SF_LARGE = float(os.environ.get("BENCH_SF_LARGE", "10"))
FAST = os.environ.get("BENCH_FAST") == "1"
if "BENCH_SF" in os.environ:  # pre-r2 knob: map onto the large configs
    print(
        "bench.py: BENCH_SF is superseded by BENCH_SF_LARGE; honoring it",
        file=sys.stderr,
    )
    SF_LARGE = float(os.environ["BENCH_SF"])

Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

# TPC-H Q6: the trace-smoke overhead pair partner to Q1 — a scan-heavy
# single-fragment aggregate where per-operator instrumentation cost has
# nowhere to hide behind join/shuffle work
Q6 = """
select sum(l_extendedprice * l_discount) as revenue from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07 and l_quantity < 24
"""

Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""

Q18 = """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
  sum(l_quantity)
from customer, orders, lineitem
where o_orderkey in (
    select l_orderkey from lineitem group by l_orderkey
    having sum(l_quantity) > 300)
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate
limit 100
"""

# BASELINE config 4: TPC-DS q72 (deep multi-build join tree;
# partitioned lookup) — template matches tests/test_tpcds.py
Q72 = """
select i_item_desc, w_warehouse_name, d1.d_week_seq,
  sum(case when p_promo_sk is null then 1 else 0 end) no_promo,
  sum(case when p_promo_sk is not null then 1 else 0 end) promo,
  count(*) total_cnt
from catalog_sales
join inventory on (cs_item_sk = inv_item_sk)
join warehouse on (w_warehouse_sk = inv_warehouse_sk)
join item on (i_item_sk = cs_item_sk)
join customer_demographics on (cs_bill_cdemo_sk = cd_demo_sk)
join household_demographics on (cs_bill_hdemo_sk = hd_demo_sk)
join date_dim d1 on (cs_sold_date_sk = d1.d_date_sk)
join date_dim d2 on (inv_date_sk = d2.d_date_sk)
join date_dim d3 on (cs_ship_date_sk = d3.d_date_sk)
left outer join promotion on (cs_promo_sk = p_promo_sk)
left outer join catalog_returns on (cr_item_sk = cs_item_sk
                                    and cr_order_number = cs_order_number)
where d1.d_week_seq = d2.d_week_seq
  and inv_quantity_on_hand < cs_quantity
  and d3.d_date > d1.d_date + 5
  and hd_buy_potential = '>10000'
  and d1.d_year = 1999
  and cd_marital_status = 'D'
group by i_item_desc, w_warehouse_name, d1.d_week_seq
order by total_cnt desc, i_item_desc, w_warehouse_name, d1.d_week_seq
limit 100
"""

# BASELINE config 5: synthetic wide-string GROUP BY (variable-width ->
# device dictionary encoding) over the memory connector
WIDESTR = """
select s, count(*) as cnt, sum(v) as total
from widestr group by s order by cnt desc, s limit 10
"""

WIDESTR_ROWS = int(os.environ.get("BENCH_WIDESTR_ROWS", str(1 << 21)))
WIDESTR_GROUPS = 512
WIDESTR_WIDTH = 64

# columns each config needs resident (pruned load keeps host+device RAM
# proportional to what the queries touch)
TABLE_COLUMNS = {
    "q1": {
        "lineitem": [
            "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
            "l_discount", "l_tax", "l_shipdate",
        ],
    },
    "q3": {
        "customer": ["c_custkey", "c_mktsegment"],
        "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
        "lineitem": ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"],
    },
    "q18": {
        "customer": ["c_custkey", "c_name"],
        "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
        "lineitem": ["l_orderkey", "l_quantity"],
    },
}
SQL = {"q1": Q1, "q3": Q3, "q18": Q18, "q72": Q72, "widestr": WIDESTR}


_TABLE_CACHE_DIR = os.path.expanduser(
    os.environ.get("BENCH_TABLE_CACHE", "~/.trino_tpu_bench_cache")
)


def _cached_column(table: str, name: str, sf: float, base: int):
    """Generated TPC-H columns cached as .npz on disk: SF10 generation
    costs minutes per config SUBPROCESS (each config is isolated), which
    alone could blow the driver's bench budget. The generator is
    deterministic, so the cache is exact."""
    import numpy as np

    from trino_tpu.connectors.tpch import generate_column

    path = os.path.join(
        _TABLE_CACHE_DIR, f"{table}.{name}.sf{sf:g}.npz"
    )
    if os.path.exists(path):
        try:
            with np.load(path, allow_pickle=False) as z:
                data = z["data"]
                dvals = z["dict"] if "dict" in z.files else None
            if dvals is not None:
                from trino_tpu.block import Dictionary

                d = Dictionary([str(v) for v in dvals])
            else:
                d = None
            return data, d
        except Exception:
            pass  # corrupt cache entry: regenerate below
    data, d = generate_column(table, name, sf, 0, base)
    try:
        os.makedirs(_TABLE_CACHE_DIR, exist_ok=True)
        tmp = path + ".tmp.npz"  # savez keeps a name already ending .npz
        if d is not None:
            np.savez(tmp, data=data, dict=np.asarray(list(d.values)))
        else:
            np.savez(tmp, data=data)
        os.replace(tmp, path)
    except Exception:
        pass  # cache is an optimization only
    return data, d


def _make_runner(sf: float, table_columns):
    """LocalQueryRunner over the memory connector with the needed
    columns preloaded (device-resident after the prewarm scan)."""
    from trino_tpu.connectors.memory import create_memory_connector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.connectors.tpch import TABLES, base_row_count
    from trino_tpu.engine import LocalQueryRunner, Session

    mem = create_memory_connector()
    for table, cols in table_columns.items():
        types = dict(TABLES[table])
        base = base_row_count(table, sf)
        arrays, dicts = [], []
        for name in cols:
            data, d = _cached_column(table, name, sf, base)
            arrays.append(data)
            dicts.append(d)
        mem.load_table(
            "bench", table,
            [ColumnMetadata(n, types[n]) for n in cols],
            arrays, None, dicts,
        )
    # 4M-row batches, not the engine's 1M default: fewer dispatches per
    # scan. Not re-measured on the current machine (ROADMAP D8 retunes
    # it from a measurement). The CPU baseline subprocess pins its own
    # batch size via _CPU_ENV.
    batch_rows = int(os.environ.get("BENCH_BATCH_ROWS", str(1 << 22)))
    r = LocalQueryRunner(
        Session(catalog="memory", schema="bench", batch_rows=batch_rows)
    )
    r.register_catalog("memory", mem)
    return r


def _median_wall(runner, sql: str, runs: int = RUNS) -> float:
    runner.execute(sql)  # prewarm: host->device + compile
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        runner.execute(sql)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _configs():
    only = os.environ.get("BENCH_ONLY")
    if only:
        name, sf = only.split(":")
        return [(name, float(sf))]
    if FAST:
        return [("q1", 1.0)]
    # q72/widestr (BASELINE configs 4-5) run LAST: the deadline logic
    # sheds them first, protecting the headline configs
    return [
        ("q1", 1.0), ("q3", 1.0), ("q3", SF_LARGE), ("q18", SF_LARGE),
        ("q72", SF_LARGE), ("widestr", 1.0),
    ]


def _make_tpcds_runner(sf: float):
    """LocalQueryRunner over the tpcds connector (BASELINE config 4).
    Generation is on-scan; the engine's plan cache snapshots splits, so
    steady-state repeats re-read generated pages, not re-plan."""
    from trino_tpu.connectors.tpcds import create_tpcds_connector
    from trino_tpu.engine import LocalQueryRunner, Session

    batch_rows = int(os.environ.get("BENCH_BATCH_ROWS", str(1 << 22)))
    r = LocalQueryRunner(
        Session(catalog="tpcds", schema=f"sf{sf:g}", batch_rows=batch_rows)
    )
    r.register_catalog("tpcds", create_tpcds_connector())
    return r


def _make_widestr_runner():
    """Memory-connector table for BASELINE config 5: wide dictionary
    strings (WIDESTR_WIDTH chars, WIDESTR_GROUPS distinct) + a value
    column, exercising variable-width -> device dictionary encoding in
    a skewed GROUP BY."""
    import hashlib

    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.block import Dictionary
    from trino_tpu.connectors.memory import create_memory_connector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.engine import LocalQueryRunner, Session

    vals = [
        hashlib.sha256(f"widestr-{i}".encode()).hexdigest()[:WIDESTR_WIDTH]
        .ljust(WIDESTR_WIDTH, "x")
        for i in range(WIDESTR_GROUPS)
    ]
    rng = np.random.default_rng(7)
    # zipf-ish skew: a few huge groups plus a long tail
    codes = (
        rng.zipf(1.3, WIDESTR_ROWS).astype(np.int64) % WIDESTR_GROUPS
    )
    v = rng.integers(0, 1_000_000, WIDESTR_ROWS, dtype=np.int64)
    mem = create_memory_connector()
    mem.load_table(
        "bench", "widestr",
        [ColumnMetadata("s", T.VARCHAR), ColumnMetadata("v", T.BIGINT)],
        [codes, v], None, [Dictionary(vals), None],
    )
    batch_rows = int(os.environ.get("BENCH_BATCH_ROWS", str(1 << 22)))
    r = LocalQueryRunner(
        Session(catalog="memory", schema="bench", batch_rows=batch_rows)
    )
    r.register_catalog("memory", mem)
    return r


def run_benches() -> dict:
    """All configs on this process's default jax platform. Returns
    {metric_name: seconds}. Runners are built per (sf, union-of-columns)
    so the two SF-large configs share one generation pass per table."""
    out = {}
    by_sf = {}
    for name, sf in _configs():
        if name not in TABLE_COLUMNS:
            continue  # q72/widestr build their own runners below
        by_sf.setdefault(sf, {})
        for table, cols in TABLE_COLUMNS[name].items():
            cur = by_sf[sf].setdefault(table, [])
            for c in cols:
                if c not in cur:
                    cur.append(c)
    runners = {}
    for sf, tables in by_sf.items():
        print(f"bench: generating sf={sf:g} tables...", file=sys.stderr, flush=True)
        runners[sf] = _make_runner(sf, tables)
    for name, sf in _configs():
        # SF-large configs trim one run, but never EXCEED the requested
        # count (the CPU baseline passes BENCH_RUNS=1 and means it)
        runs = RUNS if sf <= 1 else min(RUNS, max(2, RUNS - 1))
        print(f"bench: running {name} sf={sf:g}...", file=sys.stderr, flush=True)
        t0 = time.time()
        if name == "q72":
            runner = _make_tpcds_runner(sf)
        elif name == "widestr":
            runner = _make_widestr_runner()
        else:
            runner = runners[sf]
        out[f"{name}_sf{sf:g}"] = round(
            _median_wall(runner, SQL[name], runs), 4
        )
        print(
            f"bench: {name} sf={sf:g} wall={out[f'{name}_sf{sf:g}']}s "
            f"(total {time.time()-t0:.0f}s incl. prewarm)",
            file=sys.stderr, flush=True,
        )
    return out


PROBE_ROWS = 1_000_000

# env for the CPU-baseline subprocess: JAX_PLATFORMS demotes the child
# (and is the compile-cache opt-out in compile/cache.py). Each platform
# runs its own batch size — on CPU 1M batches are cache-friendlier
# (measured: SF1 CPU times got WORSE at 4M). Pinning also keeps the
# on-disk baseline cache consistent across device-side tuning changes.
_CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "BENCH_RUNS": "1",
    "BENCH_BATCH_ROWS": str(1 << 20),
}


def probe_gbs(n: int = PROBE_ROWS) -> float:
    """Hash-probe throughput in GB/s of probe-side key bytes (the
    BASELINE.json 'hash-probe GB/s per chip' metric). Measured with the
    marginal-device-time slope (benchmarks/devtime): a methodology
    that fetches the (lo, counts) outputs bills the host read-back, a
    synchronisation point, not the kernel."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.devtime import devtime as _measure
    from trino_tpu.ops import join as J

    rng = np.random.default_rng(0)
    build_n = n // 8
    bkeys = [jnp.asarray(np.arange(build_n, dtype=np.int64))]
    bvalids = [jnp.ones(build_n, dtype=jnp.bool_)]
    lookup = J.build_lookup(bkeys, bvalids, jnp.ones(build_n, dtype=jnp.bool_))
    pkeys = [jnp.asarray(rng.integers(0, build_n * 2, n).astype(np.int64))]
    pvalids = [jnp.ones(n, dtype=jnp.bool_)]
    plive = jnp.ones(n, dtype=jnp.bool_)

    def run():
        return J.probe_counts(lookup, pkeys, pvalids, plive)

    secs = _measure(run)
    return round(n * 8 / secs / 1e9, 2)


def _run_one_subprocess(name: str, sf: float, platform_env: dict,
                        timeout_s: int):
    """One config in an isolated subprocess (a first-compile that runs
    away must never wedge the whole bench — the driver runs this
    un-supervised at round end). Child stderr streams live to our
    stderr as it happens (buffering it until completion destroys the
    progress trail when a timeout kills the child). Returns
    (seconds, platform) or (None, None)."""
    env = dict(os.environ, BENCH_INNER="1", BENCH_ONLY=f"{name}:{sf:g}")
    env.update(platform_env)
    tag = "cpu" if platform_env.get("JAX_PLATFORMS") == "cpu" else "dev"
    out_lines: list = []
    err_tail: list = []

    def _pump_err(pipe):
        for line in pipe:
            line = line.rstrip("\n")
            err_tail.append(line)
            del err_tail[:-15]
            if line.startswith("bench:"):
                print(f"[{tag}] {line}", file=sys.stderr, flush=True)

    def _pump_out(pipe):
        for line in pipe:
            out_lines.append(line.rstrip("\n"))

    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except Exception as ex:
        print(f"bench: {name} sf={sf:g} [{tag}] skipped ({type(ex).__name__})",
              file=sys.stderr, flush=True)
        return None, None
    threads = [
        threading.Thread(target=_pump_err, args=(proc.stderr,), daemon=True),
        threading.Thread(target=_pump_out, args=(proc.stdout,), daemon=True),
    ]
    for t in threads:
        t.start()
    try:
        proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        print(f"bench: {name} sf={sf:g} [{tag}] skipped (timeout {timeout_s}s)",
              file=sys.stderr, flush=True)
        return None, None
    for t in threads:
        t.join(timeout=5)
    payload = [ln for ln in out_lines if ln.strip()]
    if not payload:
        # inner crash: surface the traceback tail, not an IndexError
        for line in err_tail:
            print(f"bench[inner/{tag}]: {line}", file=sys.stderr, flush=True)
        print(
            f"bench: {name} sf={sf:g} [{tag}] inner exited "
            f"rc={proc.returncode} with no result",
            file=sys.stderr, flush=True,
        )
        return None, None
    try:
        rec = json.loads(payload[-1])
        return rec[f"{name}_sf{sf:g}"], rec.get("_platform")
    except Exception as ex:
        print(f"bench: {name} sf={sf:g} [{tag}] unparseable result "
              f"({type(ex).__name__})", file=sys.stderr, flush=True)
        return None, None


def _preflight_device(timeouts: Sequence[int] = (45, 75)) -> tuple:
    """Initialize the backend once in a child before committing to the
    full config matrix, so a dead backend costs ~2 minutes and not the
    whole budget: escalating-timeout child attempts (a healthy-but-slow
    init that misses the first window gets a longer second one). The
    child exits before any config runs (one process per chip). Returns
    (platform | None, tail); the caller exits non-zero unless the
    platform is a TPU."""
    code = (
        "import jax, json, sys;"
        "d = jax.devices();"
        "print(json.dumps({'platform': d[0].platform, 'n': len(d)}))"
    )
    tail: list = []
    for i, timeout_s in enumerate(timeouts):
        if i:
            print("bench: preflight retry in 5s...",
                  file=sys.stderr, flush=True)
            time.sleep(5)
        try:
            proc = subprocess.run(
                [sys.executable, "-c", code],
                capture_output=True, text=True, timeout=timeout_s,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
        except subprocess.TimeoutExpired:
            tail.append(f"attempt {i + 1}: init timeout after {timeout_s}s")
            continue
        if proc.returncode == 0 and proc.stdout.strip():
            try:
                info = json.loads(proc.stdout.strip().splitlines()[-1])
                print(
                    f"bench: preflight ok — platform={info['platform']} "
                    f"n={info['n']} (attempt {i + 1})",
                    file=sys.stderr, flush=True,
                )
                return info["platform"], tail
            except Exception:
                pass
        err = [ln for ln in proc.stderr.splitlines() if ln.strip()][-4:]
        tail.append(f"attempt {i + 1}: rc={proc.returncode} " + " | ".join(err))
    return None, tail


_BASELINE_FILE = os.path.join(_TABLE_CACHE_DIR, "baselines.json")

# Cached CPU baselines are only comparable while the engine's CPU path
# and the baseline batch config stay fixed (VERDICT r3 weak #2: a stale
# cached baseline overstated Q3 SF10 by 1.6x after CPU batch tuning).
# Bump the epoch whenever engine changes could move CPU times.
_CPU_BASELINE_EPOCH = "r4-syncfree-join-agg"


def _baseline_cache_key(key: str) -> str:
    return f"{key}@{_CPU_BASELINE_EPOCH}@b{_CPU_ENV['BENCH_BATCH_ROWS']}"


def _load_cached_baselines() -> dict:
    try:
        with open(_BASELINE_FILE) as f:
            return json.load(f)
    except Exception:
        return {}


def _save_cached_baseline(key: str, secs: float) -> None:
    try:
        os.makedirs(_TABLE_CACHE_DIR, exist_ok=True)
        cur = _load_cached_baselines()
        cur[_baseline_cache_key(key)] = {
            "cpu_s": secs, "ts": time.strftime("%Y-%m-%d %H:%M"),
        }
        tmp = _BASELINE_FILE + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cur, f)
        os.replace(tmp, _BASELINE_FILE)
    except Exception:
        pass


def _emit(device: dict, baseline: dict, gbs, cached=None) -> None:
    """Print the driver's ONE JSON line reflecting everything measured
    so far (flushed). Called after every completed config: the LAST
    stdout line is the record, so each call supersedes the previous and
    a kill at any point still leaves a complete result behind.

    `cached` holds CPU baselines measured by a PREVIOUS bench run on
    this host (the SF10 CPU engine runs for many minutes and does not
    always fit the driver's budget); they fill gaps with explicit
    provenance (cpu_source) and fresh measurements always win."""
    extra = {}
    cached = cached or {}
    for k, v in device.items():
        extra[k] = {"wall_s": v}
        if k in baseline:
            extra[k]["cpu_s"] = baseline[k]
            extra[k]["vs_cpu"] = round(baseline[k] / v, 3)
        elif _baseline_cache_key(k) in cached:
            hit = cached[_baseline_cache_key(k)]
            extra[k]["cpu_s"] = hit["cpu_s"]
            extra[k]["vs_cpu"] = round(hit["cpu_s"] / v, 3)
            extra[k]["cpu_source"] = f"cached {hit['ts']}"
    if gbs is not None:
        extra["hash_probe"] = {"gb_s": gbs, "rows": PROBE_ROWS}

    if not device:
        # even total failure must emit the driver's one JSON line
        print(
            json.dumps(
                {"metric": "bench_failed", "value": 0.0, "unit": "s",
                 "vs_baseline": 0.0, "extra": {}}
            ),
            flush=True,
        )
        return
    # headline: the largest completed north-star config, preferring one
    # whose CPU baseline actually completed (a missing comparison must
    # not masquerade as a measured 1.0x)
    order = [f"q18_sf{SF_LARGE:g}", f"q3_sf{SF_LARGE:g}", "q3_sf1", "q1_sf1"]
    with_vs = [k for k in order if k in device and "vs_cpu" in extra[k]]
    candidates = with_vs or [k for k in order if k in device] or sorted(device)
    headline = candidates[0]
    value = device[headline]
    vs = extra[headline].get("vs_cpu", 1.0)
    if "vs_cpu" not in extra[headline]:
        extra["note"] = "cpu baseline missing for headline; vs_baseline unmeasured"
    elif headline in order:
        # demotion must be loud: a larger config completed on device but
        # lost its CPU baseline, so the headline metric name changed
        passed_over = [
            k for k in order[: order.index(headline)] if k in device
        ]
        if passed_over:
            extra["note"] = (
                f"headline demoted to {headline}; completed without cpu "
                f"baseline: {', '.join(passed_over)}"
            )
    print(
        json.dumps(
            {
                "metric": f"tpch_{headline}_wall",
                "value": value,
                "unit": "s",
                "vs_baseline": vs,
                "extra": extra,
            }
        ),
        flush=True,
    )


# chaos-smoke queries: the two plan shapes whose recovery paths differ
# most (scan->partial/final agg with an exchange in between, and a
# broadcast-join->agg with a build side worth losing mid-flight)
CHAOS_QUERIES = {
    "agg": (
        "select l_returnflag, l_linestatus, sum(l_quantity), count(*) "
        "from lineitem where l_shipdate <= date '1998-09-02' "
        "group by l_returnflag, l_linestatus "
        "order by l_returnflag, l_linestatus"
    ),
    "join": (
        "select n_name, count(*) c from supplier, nation "
        "where s_nationkey = n_nationkey group by n_name order by n_name"
    ),
}


def _chaos_smoke(argv) -> int:
    """--chaos-smoke [seed]: deterministic resiliency gate. Exit 0 iff
    every (query, fault class) run is answer-equal to the clean run and
    stays within its injected-failure bound; a failing run replays from
    the printed seed."""
    i = argv.index("--chaos-smoke")
    try:
        seed = int(argv[i + 1])
    except (IndexError, ValueError):
        seed = 42
    # the replica scenarios carve 2 sub-meshes from the device set —
    # make sure the host platform exposes enough devices before any
    # backend initializes (a real accelerator platform ignores this)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    from trino_tpu.runtime.chaos import (
        ADAPTIVE_CLASSES,
        FAULT_CLASSES,
        LIFECYCLE_CLASSES,
        RECOVERY_CLASSES,
        REPLICA_CLASSES,
        SERVING_CLASSES,
        TIMEBOUND_CLASSES,
        chaos_smoke,
    )

    print(f"bench: chaos smoke seed={seed} "
          f"fault_classes={','.join(FAULT_CLASSES)} "
          f"lifecycle={','.join(LIFECYCLE_CLASSES)} "
          f"timebound={','.join(TIMEBOUND_CLASSES)} "
          f"serving={','.join(SERVING_CLASSES)} "
          f"adaptive={','.join(ADAPTIVE_CLASSES)} "
          f"recovery={','.join(RECOVERY_CLASSES)},recovery_loaded_drain "
          f"replica={','.join(REPLICA_CLASSES)}")
    t0 = time.time()
    violations = chaos_smoke(seed, CHAOS_QUERIES)
    wall = time.time() - t0
    for v in violations:
        print(f"bench: chaos VIOLATION: {v}", file=sys.stderr)
    print(json.dumps({
        "chaos_smoke": {
            "seed": seed,
            "cases": len(CHAOS_QUERIES) * len(FAULT_CLASSES)
            + len(LIFECYCLE_CLASSES) + len(TIMEBOUND_CLASSES)
            + len(SERVING_CLASSES) + len(ADAPTIVE_CLASSES)
            + len(RECOVERY_CLASSES) + 1 + len(REPLICA_CLASSES),
            "violations": len(violations),
            "wall_s": round(wall, 2),
        }
    }))
    return 1 if violations else 0


# serve-smoke mix: the two analytic shapes the trace/chaos gates already
# exercise, plus point lookups — the statement class the plan cache,
# admission fast path, and micro-batcher were built for
SERVE_QUERIES = {"q1": Q1, "q6": Q6}


def _serve_flag(argv, name: str, default, cast=float):
    if name in argv:
        try:
            return cast(argv[argv.index(name) + 1])
        except (IndexError, ValueError):
            pass
    return default


def _serve_smoke(argv) -> int:
    """--serve-smoke [seed]: serving-tier gate. Drives the statement
    protocol open-loop with >=8 concurrent clients on a q1/q6/point mix
    and exits 0 iff every result is oracle-equal, nothing was shed,
    the plan-cache hit rate stays >=90%, zero XLA lowerings happen
    after warm-up, p99 <= 5x p50, and the batched phase coalesces
    while staying oracle-equal."""
    i = argv.index("--serve-smoke")
    try:
        seed = int(argv[i + 1])
    except (IndexError, ValueError):
        seed = 7
    from trino_tpu.serving.harness import serve_smoke

    n_clients = int(_serve_flag(argv, "--serve-clients", 8, int))
    duration_s = _serve_flag(argv, "--serve-duration", 6.0)
    print(f"bench: serve smoke seed={seed} clients={n_clients} "
          f"duration={duration_s:g}s mix=q1,q6,point")
    t0 = time.time()
    report, violations = serve_smoke(
        SERVE_QUERIES, n_clients=n_clients, duration_s=duration_s,
        seed=seed,
    )
    for v in violations:
        print(f"bench: serve VIOLATION: {v}", file=sys.stderr)
    report["violations"] = len(violations)
    report["wall_total_s"] = round(time.time() - t0, 2)
    print(json.dumps({"serve_smoke": report}))
    return 1 if violations else 0


def _serve(argv) -> int:
    """--serve: tunable open-loop load run (no gates, just the report).
    Knobs: --serve-clients N --serve-duration S --serve-rate QPS
    --serve-util U --serve-window MS --serve-seed N.
    --serve-replicas 1,2,4 switches to the replica sweep: the same
    mixed workload is offered at a FIXED rate (derived once, from the
    first arm) to a replicated mesh runner per arm, reporting QPS and
    p50/p99 per replica count — and gating that QPS does not degrade
    as replicas are added, no arm sheds, and tail bounds hold."""
    if _serve_flag(argv, "--serve-replicas", None, str) is not None:
        return _serve_replica_sweep(argv)
    from trino_tpu.serving.harness import run_serve_load

    report = run_serve_load(
        queries=SERVE_QUERIES,
        n_clients=int(_serve_flag(argv, "--serve-clients", 8, int)),
        duration_s=_serve_flag(argv, "--serve-duration", 6.0),
        rate_qps=_serve_flag(argv, "--serve-rate", None),
        utilization=_serve_flag(argv, "--serve-util", 0.5),
        micro_batch_window_ms=_serve_flag(argv, "--serve-window", 3.0),
        seed=int(_serve_flag(argv, "--serve-seed", 7, int)),
    )
    print(json.dumps({"serve": report}))
    return 0


def _serve_replica_sweep(argv) -> int:
    """--serve --serve-replicas 1,2,4: the PR 8 mixed workload against
    a replicated mesh serving plane, one arm per replica count. Each
    arm builds a distributed runner whose mesh is carved into R
    sub-meshes; every replica is warmed before the measured phase
    (warmup_rounds=R) and all arms share ONE offered rate, derived from
    the first arm's warm service times, so per-arm QPS and percentiles
    are comparable. Replicas are the mesh plane's units of serving
    concurrency (one program per sub-mesh at a time), so QPS must not
    DEGRADE as replicas are added while the offered load holds. Exit 1
    if any arm sheds, mismatches, errors, compiles after warmup, drops
    QPS below the 1-replica arm by more than 10%, or blows the tail
    bound (p99 <= 8x p50)."""
    if os.environ.get("SERVE_SWEEP_INNER") != "1":
        env = dict(os.environ)
        env["SERVE_SWEEP_INNER"] = "1"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + argv[1:],
            env=env,
        ).returncode

    import jax

    jax.config.update("jax_platforms", "cpu")

    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.runtime import DistributedQueryRunner
    from trino_tpu.runtime.metrics import install_xla_compile_listener
    from trino_tpu.serving.harness import run_serve_load

    install_xla_compile_listener()
    arms_spec = _serve_flag(argv, "--serve-replicas", "1,2,4", str)
    arm_replicas = [int(x) for x in arms_spec.split(",") if x.strip()]
    n_clients = int(_serve_flag(argv, "--serve-clients", 8, int))
    duration_s = _serve_flag(argv, "--serve-duration", 6.0)
    seed = int(_serve_flag(argv, "--serve-seed", 7, int))
    n_dev = len(jax.devices())
    print(f"bench: serve replica sweep arms={arm_replicas} "
          f"({n_dev}-device cpu mesh, clients={n_clients}, "
          f"duration={duration_s:g}s)")

    def mk(n_replicas: int):
        r = DistributedQueryRunner(
            Session(
                catalog="tpch", schema="tiny",
                mesh_replicas=n_replicas,
                mesh_chunk_rows=512,
                mesh_checkpoint_interval_chunks=4,
            ),
            n_workers=2, hash_partitions=2,
        )
        r.register_catalog("tpch", create_tpch_connector())
        return r

    violations = []
    arms = {}
    rate = _serve_flag(argv, "--serve-rate", None)
    for n_replicas in arm_replicas:
        runner = mk(n_replicas)
        report = run_serve_load(
            queries=SERVE_QUERIES,
            n_clients=n_clients,
            duration_s=duration_s,
            rate_qps=rate,
            utilization=_serve_flag(argv, "--serve-util", 0.9),
            # batched burst runs on the replicated runner too: the
            # combined IN-list lookups must ride the MeshScheduler
            # fast lane on the replica run queues (gated below)
            batch_phase_s=_serve_flag(argv, "--serve-batch", 1.0),
            seed=seed,
            runner=runner,
            warmup_rounds=max(1, n_replicas),
        )
        # all arms offer the SAME load: reuse the first arm's derived
        # rate so the sweep compares service capacity, not schedules
        rate = report["rate_qps"]
        rm = getattr(runner, "_replicas", None)
        arms[n_replicas] = {
            k: report[k]
            for k in ("rate_qps", "offered", "completed", "qps",
                      "p50_ms", "p95_ms", "p99_ms", "p99_over_p50",
                      "shed", "mismatches", "error_count",
                      "plan_cache_hit_rate", "xla_compiles_after_warmup")
        }
        arms[n_replicas]["replica_stats"] = rm.stats() if rm else None
        bp = report.get("batch_phase")
        if bp is not None:
            arms[n_replicas]["batch_phase"] = {
                k: bp[k]
                for k in ("queries", "mismatches", "error_count",
                          "batches", "batched_queries", "mesh_fast_lane")
            }
            if bp["mismatches"] or bp["error_count"]:
                violations.append(
                    f"arm r={n_replicas}: batch phase "
                    f"{bp['mismatches']} mismatches, "
                    f"{bp['error_count']} errors"
                )
            if bp["batches"] == 0 or bp["batched_queries"] <= bp["batches"]:
                violations.append(
                    f"arm r={n_replicas}: batch phase never coalesced "
                    f"(batches={bp['batches']}, "
                    f"batched_queries={bp['batched_queries']})"
                )
            if bp["mesh_fast_lane"] < bp["batches"]:
                violations.append(
                    f"arm r={n_replicas}: batched lookups bypassed the "
                    f"mesh scheduler fast lane "
                    f"(fast submissions {bp['mesh_fast_lane']} < "
                    f"batches {bp['batches']})"
                )
        if report["mismatches"]:
            violations.append(
                f"arm r={n_replicas}: {report['mismatches']} results "
                "diverged from the oracle"
            )
        if report["error_count"]:
            violations.append(
                f"arm r={n_replicas}: {report['error_count']} errors "
                f"(first: {report['errors'][:1]})"
            )
        if report["shed"]:
            violations.append(
                f"arm r={n_replicas}: {report['shed']} sheds under the "
                "shared offered rate"
            )
        if report["xla_compiles_after_warmup"]:
            violations.append(
                f"arm r={n_replicas}: "
                f"{report['xla_compiles_after_warmup']} XLA lowerings "
                "in the measured phase (warmup_rounds missed a replica)"
            )
        if report["p99_over_p50"] > 8.0:
            violations.append(
                f"arm r={n_replicas}: p99/p50 = "
                f"{report['p99_over_p50']} blows the 8x tail bound"
            )
    base_qps = arms[arm_replicas[0]]["qps"]
    for n_replicas in arm_replicas[1:]:
        if arms[n_replicas]["qps"] < 0.90 * base_qps:
            violations.append(
                f"arm r={n_replicas}: qps {arms[n_replicas]['qps']} "
                f"degraded >10% below the 1-replica arm ({base_qps})"
            )
    for v in violations:
        print(f"bench: serve-sweep VIOLATION: {v}", file=sys.stderr)
    print(json.dumps({
        "serve_replica_sweep": {
            "devices": n_dev,
            "arms": {str(k): v for k, v in arms.items()},
            "violations": len(violations),
        }
    }))
    return 1 if violations else 0


def _parse_compile_lines(text: str) -> dict:
    """Pull the compile-regime counters out of an EXPLAIN ANALYZE plan
    text (census + warmup + cache lines, engine._explain_analyze)."""
    import re

    out: dict = {}
    for key, pat in (
        ("expected_lowerings", r"expected_xla_lowerings=(\d+)"),
        ("observed_classes", r"observed_shape_classes=(\d+)"),
        ("xla_compiles", r"xla_compiles_this_query=(\d+)"),
    ):
        m = re.search(pat, text)
        if m:
            out[key] = int(m.group(1))
    m = re.search(
        r"warmup: mode=(\w+) entries=(\d+) compiled=(\d+) failed=(\d+) "
        r"skipped=(\d+)(?: hits=(\d+) misses=(\d+))?",
        text,
    )
    if m:
        out["warmup"] = {
            "mode": m.group(1),
            "entries": int(m.group(2)),
            "compiled": int(m.group(3)),
            "failed": int(m.group(4)),
            "skipped": int(m.group(5)),
        }
        if m.group(6) is not None:
            out["warmup"]["hits"] = int(m.group(6))
            out["warmup"]["misses"] = int(m.group(7))
    return out


def _warmup_smoke(argv) -> int:
    """--warmup-smoke: compile-regime gate. Runs the q72-class plan
    (deep multi-build join tree) twice from a cold compile state on the
    CPU backend — once with warmup off, once with warmup_mode=block —
    and prints one JSON line with per-arm compile counts and walls.
    Exit 1 iff the warmup-on arm observes more distinct shape classes
    at runtime than the census predicted (shape stabilization failed to
    land execution on the predicted lowerings) or the arms disagree on
    the answer."""
    import jax

    from trino_tpu.compile.cache import PROGRAM_CACHE
    from trino_tpu.compile.warmup import reset_warm_classes
    from trino_tpu.connectors.tpcds import create_tpcds_connector
    from trino_tpu.engine import LocalQueryRunner, Session

    def run_arm(warmup_mode: str) -> dict:
        # cold start: drop the engine's program cache, jax's dispatch
        # caches, and the warm-class registry so each arm pays (or
        # warms) its own compiles
        PROGRAM_CACHE.clear()
        reset_warm_classes()
        jax.clear_caches()
        r = LocalQueryRunner(Session(catalog="tpcds", schema="tiny"))
        r.register_catalog("tpcds", create_tpcds_connector())
        r.session.set_property("warmup_mode", warmup_mode)
        t0 = time.time()
        text = r.execute("EXPLAIN ANALYZE " + Q72).only_value()
        wall = time.time() - t0
        rows = r.execute(Q72).rows
        stats = _parse_compile_lines(text)
        stats["warmup_mode"] = warmup_mode
        stats["wall_s"] = round(wall, 2)
        return stats, rows

    print("bench: warmup smoke (q72-class plan, tpcds tiny, CPU ok)")
    base, base_rows = run_arm("off")
    warm, warm_rows = run_arm("block")
    violations = []
    expected = warm.get("expected_lowerings")
    observed = warm.get("observed_classes")
    if expected is None or observed is None:
        violations.append("compile census lines missing from EXPLAIN ANALYZE")
    elif observed > expected:
        violations.append(
            f"warmup-on run observed {observed} distinct shape classes, "
            f"census predicted {expected} — stabilization failed to land "
            "execution on the predicted lowerings"
        )
    if base_rows != warm_rows:
        violations.append("warmup changed the query answer")
    for v in violations:
        print(f"bench: warmup VIOLATION: {v}", file=sys.stderr)
    print(json.dumps({
        "warmup_smoke": {
            "query": "q72",
            "no_warmup": base,
            "with_warmup": warm,
            "violations": len(violations),
        }
    }))
    return 1 if violations else 0


def _trace_smoke(argv) -> int:
    """--trace-smoke: observability gate (runtime/tracing.py). Runs a
    traced distributed TPC-H query plus one chaos scenario, validates
    the exported span tree (invariants + Chrome trace-event schema),
    and measures tracing overhead traced-on vs traced-off on the Q1/Q6
    CPU pair. Exit 1 iff the trace fails to parse, an invariant is
    violated, a chaos annotation is missing, or overhead exceeds 5%
    wall on either query."""
    from trino_tpu.connectors.spi import CatalogManager
    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.runtime import DistributedQueryRunner, Worker
    from trino_tpu.runtime.failure import FailureInjector
    from trino_tpu.runtime.tracing import check_span_invariants

    def cluster(tag, **session_kw):
        inj = FailureInjector()
        cats = CatalogManager()
        cats.register("tpch", create_tpch_connector())
        workers = [
            Worker(f"{tag}-w{i}", cats, failure_injector=inj)
            for i in range(2)
        ]
        r = DistributedQueryRunner(
            Session(catalog="tpch", schema="tiny", **session_kw),
            worker_handles=workers, hash_partitions=2,
        )
        r.register_catalog("tpch", create_tpch_connector())
        return inj, r

    violations = []
    print("bench: trace smoke (distributed TPC-H, tpch tiny, CPU ok)")

    # 1. traced run: the exported tree is complete, valid, and renders
    # as loadable Chrome trace-event JSON
    _, traced = cluster("ts", query_trace="on")
    if not traced.execute(CHAOS_QUERIES["agg"]).rows:
        violations.append("traced query returned no rows")
    export = traced.query_trace_export(traced.last_query_id)
    if export is None:
        violations.append("traced query exported no trace")
        export = {"spans": []}
    violations += check_span_invariants(export)
    kinds = {s["kind"] for s in export["spans"]}
    missing = {"query", "phase", "stage", "task", "operator"} - kinds
    if missing:
        violations.append(f"trace missing span kinds: {sorted(missing)}")
    chrome = traced.query_chrome_trace(traced.last_query_id) or {}
    events = json.loads(json.dumps(chrome)).get("traceEvents", [])
    if not any(e.get("ph") == "X" for e in events):
        violations.append("chrome trace has no complete ('X') events")

    # 2. chaos scenario: a crash-injected FTE run still exports one
    # valid timeline, annotated where the fault and the retry landed
    inj, fte = cluster("tc", query_trace="on", retry_policy="task")
    inj.inject(where="start", kind="crash", fragment_id=0, partition=0,
               attempts=(0,), max_hits=1)
    try:
        if not fte.execute(CHAOS_QUERIES["join"]).rows:
            violations.append("chaos-injected query returned no rows")
    finally:
        inj.clear()
    chaos_export = fte.query_trace_export(fte.last_query_id)
    if chaos_export is None:
        violations.append("chaos-injected query exported no trace")
        chaos_export = {"spans": []}
    violations += check_span_invariants(chaos_export)
    task_events = [
        e["name"] for s in chaos_export["spans"] if s["kind"] == "task"
        for e in s["events"]
    ]
    stage_events = [
        e["name"] for s in chaos_export["spans"] if s["kind"] == "stage"
        for e in s["events"]
    ]
    if "chaos_fault" not in task_events:
        violations.append("chaos_fault annotation missing from task spans")
    if "task_retry" not in stage_events:
        violations.append("task_retry annotation missing from stage spans")

    # 3. overhead: best-of-N warm walls, traced-on vs traced-off, on
    # the Q1/Q6 pair (aggregation-heavy and scan-heavy) — the traced
    # arm pays operator spans + row counting, the baseline arm runs
    # with instrumentation gated off
    _, r_off = cluster("to")
    _, r_on = cluster("tn", query_trace="on")
    reps = 7
    overhead = {}
    for name, sql in (("q1", Q1), ("q6", Q6)):
        for r in (r_off, r_on):
            r.execute(sql)  # warm compiles before timing
        # interleave the arms so machine drift (page cache, turbo,
        # background load) lands on both equally; best-of-N per arm
        walls = {"off": float("inf"), "on": float("inf")}
        for _ in range(reps):
            for arm, r in (("off", r_off), ("on", r_on)):
                t0 = time.time()
                r.execute(sql)
                walls[arm] = min(walls[arm], time.time() - t0)
        pct = (walls["on"] - walls["off"]) / walls["off"] * 100.0
        overhead[name] = {
            "wall_off_s": round(walls["off"], 4),
            "wall_on_s": round(walls["on"], 4),
            "overhead_pct": round(pct, 2),
        }
        if pct > 5.0:
            violations.append(
                f"tracing overhead on {name}: {pct:.1f}% > 5% "
                f"(off={walls['off'] * 1000:.1f}ms "
                f"on={walls['on'] * 1000:.1f}ms)"
            )

    for v in violations:
        print(f"bench: trace VIOLATION: {v}", file=sys.stderr)
    print(json.dumps({
        "trace_smoke": {
            "spans": len(export["spans"]),
            "chaos_spans": len(chaos_export["spans"]),
            "overhead": overhead,
            "violations": len(violations),
        }
    }))
    return 1 if violations else 0


def _mesh_smoke(argv) -> int:
    """--mesh-smoke: CI gate for the chunked GSPMD mesh plane
    (parallel/mesh_chunk.py). Re-execs itself with an 8-virtual-device
    CPU host platform, then runs Q1, Q6 and a hash join chunked over
    the mesh and checks: answer-equality vs the page plane, at least
    one all_to_all exchange, zero new XLA lowerings when a query
    executes a second time, and a mid-query deadline kill that preempts
    between chunks with the typed EXCEEDED_TIME_LIMIT error and no
    page-plane fallback. Exit 1 on any violation."""
    if os.environ.get("MESH_SMOKE_INNER") != "1":
        # the 8-device mesh needs XLA_FLAGS before the backend
        # initializes — a child process is the clean slate
        env = dict(os.environ)
        env["MESH_SMOKE_INNER"] = "1"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mesh-smoke"],
            env=env,
        ).returncode

    import jax

    # legal until a backend initializes (see the BENCH_INNER note):
    # the mesh smoke is a CPU-semantics gate, not a device bench
    jax.config.update("jax_platforms", "cpu")
    n_dev = len(jax.devices())

    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.parallel.mesh_chunk import LAST_RUN_INFO
    from trino_tpu.parallel.mesh_plan import MESH_COUNTERS
    from trino_tpu.runtime import DistributedQueryRunner
    from trino_tpu.runtime.metrics import METRICS
    from trino_tpu.runtime.query_tracker import (
        EXCEEDED_TIME_LIMIT,
        QueryDeadlineError,
    )

    def mk(**session_kw):
        r = DistributedQueryRunner(
            Session(catalog="tpch", schema="tiny", **session_kw),
            n_workers=2, hash_partitions=2,
        )
        r.register_catalog("tpch", create_tpch_connector())
        return r

    join = (
        "select o_orderpriority, count(*) c from orders join customer "
        "on o_custkey = c_custkey group by o_orderpriority "
        "order by o_orderpriority"
    )
    violations = []
    print(f"bench: mesh smoke ({n_dev}-device cpu mesh, tpch tiny)")
    if n_dev < 8:
        violations.append(f"expected an 8-device mesh, got {n_dev}")

    page = mk(mesh_execution=False)
    mesh = mk(mesh_chunk_rows=512)
    report = {}
    for name, sql in (("q1", Q1), ("q6", Q6), ("join", join)):
        before = dict(MESH_COUNTERS)
        expect = page.execute(sql).rows
        got = mesh.execute(sql).rows
        if mesh._last_data_plane != "mesh":
            violations.append(
                f"{name}: ran on {mesh._last_data_plane}, not the mesh "
                f"(fallback: {mesh.last_mesh_fallback})"
            )
        if got != expect:
            violations.append(f"{name}: mesh answer != page answer")
        a2a = MESH_COUNTERS["all_to_all"] - before["all_to_all"]
        # second execution of the same program: the chunk-step records
        # are cached, so NO new XLA lowerings may appear
        compiles0 = METRICS.snapshot().get("xla_compiles", 0.0)
        got2 = mesh.execute(sql).rows
        compiles = METRICS.snapshot().get("xla_compiles", 0.0) - compiles0
        if got2 != expect:
            violations.append(f"{name}: second mesh run diverged")
        if compiles > 0:
            violations.append(
                f"{name}: second execution lowered {compiles:g} new "
                "XLA programs (expected 0)"
            )
        report[name] = {
            "rows": len(got),
            "all_to_all": a2a,
            "chunks": LAST_RUN_INFO.get("chunks"),
            "relowerings_second_run": compiles,
        }
    if all(r["all_to_all"] <= 0 for r in report.values()):
        violations.append("no query exchanged via all_to_all")

    # mid-query deadline kill: warm the chunked programs, slow the
    # tracker tick so the chunk-boundary check is the enforcement path,
    # then run under a wall budget that expires inside the chunk loop
    killer = mk(mesh_chunk_rows=128)
    killer.execute(Q1)
    killer.query_tracker.tick_interval_s = 60.0
    killer.session.query_max_execution_time_s = 0.05
    kill_msg = None
    try:
        killer.execute(Q1)
        violations.append("deadline query completed instead of dying")
    except QueryDeadlineError as e:
        kill_msg = str(e)
        if EXCEEDED_TIME_LIMIT not in kill_msg:
            violations.append(f"kill not typed: {kill_msg}")
        if "mesh chunk" not in kill_msg:
            violations.append(
                f"kill did not preempt at a chunk boundary: {kill_msg}"
            )
    except Exception as e:
        violations.append(f"wrong kill type {type(e).__name__}: {e}")
    if killer.last_mesh_fallback is not None:
        violations.append(
            f"deadline kill fell back to the page plane: "
            f"{killer.last_mesh_fallback}"
        )
    report["deadline_kill"] = kill_msg

    for v in violations:
        print(f"bench: mesh VIOLATION: {v}", file=sys.stderr)
    print(json.dumps({
        "mesh_smoke": {
            "devices": n_dev,
            "queries": report,
            "violations": len(violations),
        }
    }))
    return 1 if violations else 0


def _resident_smoke(argv) -> int:
    """--resident-smoke: CI gate for the resident state tier
    (trino_tpu/resident/). Checks: (1) warm pinned point lookups beat
    the cold execute path on p50 with resident.hits > 0 and zero
    rebuild pins in the warm loop; (2) repeated pinned probes — and
    repeated post-compaction probes — mint zero new XLA lowerings;
    (3) answers stay oracle-equal through DML invalidation (generation
    bump -> rebuild), the delta-append path, and background compaction;
    (4) a zero pin budget degrades to the cold path without failing any
    lookup. Exit 1 on any violation."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from trino_tpu import types as Ty
    from trino_tpu.connectors.memory import create_memory_connector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.engine import LocalQueryRunner, Session
    from trino_tpu.resident import RESIDENT
    from trino_tpu.resident.fastlane import (
        drain_compactions,
        try_resident_lookup,
    )
    from trino_tpu.runtime.metrics import METRICS

    violations = []
    print("bench: resident smoke (memory connector, pinned fast lane)")
    mem = create_memory_connector()
    r = LocalQueryRunner(Session(
        catalog="memory", schema="s",
        resident_tables="s.kv", resident_delta_max_rows=64,
    ))
    r.register_catalog("memory", mem)
    n = 1000
    rng = np.random.default_rng(3)
    mem.load_table(
        "s", "kv",
        [ColumnMetadata("k", Ty.BIGINT), ColumnMetadata("v", Ty.BIGINT)],
        [np.arange(n, dtype=np.int64),
         rng.integers(0, 1 << 30, n).astype(np.int64)],
    )
    RESIDENT.evict_all()
    RESIDENT.reset_stats()

    def oracle(k):
        return r.execute(f"select v from kv where k = {k}").rows

    def fast(k):
        res = try_resident_lookup(r, f"select v from kv where k = {k}")
        return None if res is None else res.rows

    # -- 1. build, then warm-loop latency + zero lowerings ------------
    if fast(7) != oracle(7):
        violations.append("first (build) lookup diverged from oracle")
    keys = [int(k) for k in rng.integers(0, n, 200)]
    fast(keys[0])  # one warm probe before timing
    pins0 = RESIDENT.stats()["pins"]
    compiles0 = METRICS.snapshot().get("xla_compiles", 0.0)
    warm_times = []
    for k in keys:
        t0 = time.perf_counter()
        rows = fast(k)
        warm_times.append(time.perf_counter() - t0)
        if rows is None:
            violations.append(f"warm lookup k={k} fell to the cold path")
            break
    warm_compiles = METRICS.snapshot().get("xla_compiles", 0.0) - compiles0
    if warm_compiles > 0:
        violations.append(
            f"warm probes lowered {warm_compiles:g} new XLA programs "
            "(expected 0)"
        )
    if RESIDENT.stats()["pins"] != pins0:
        violations.append("warm loop rebuilt the pinned table")
    if RESIDENT.stats()["hits"] <= 0:
        violations.append("no resident hits recorded")
    for k in keys[:5]:
        if fast(k) != oracle(k):
            violations.append(f"warm lookup k={k} diverged from oracle")
    cold_times = []
    for k in keys[:20]:
        t0 = time.perf_counter()
        oracle(k)
        cold_times.append(time.perf_counter() - t0)
    warm_p50 = sorted(warm_times)[len(warm_times) // 2]
    cold_p50 = sorted(cold_times)[len(cold_times) // 2]
    if warm_p50 >= cold_p50:
        violations.append(
            f"warm p50 {warm_p50 * 1e3:.3f}ms not below cold p50 "
            f"{cold_p50 * 1e3:.3f}ms"
        )

    # -- 2. DML invalidation: generation bump -> rebuild, oracle-equal
    r.execute("update kv set v = -1 where k = 7")
    if fast(7) != oracle(7) or fast(7) != [[-1]]:
        violations.append("post-UPDATE lookup not oracle-equal")
    if RESIDENT.stats()["evictions"] <= 0:
        violations.append("UPDATE did not evict the stale pin")

    # -- 3. delta-append path + background compaction -----------------
    pins_before_delta = RESIDENT.stats()["pins"]
    for i in range(40):  # delta_max_rows=64 -> compaction at 32
        r.execute(f"insert into kv values ({2000 + i}, {i})")
    drain_compactions()
    if RESIDENT.stats()["pins"] != pins_before_delta:
        violations.append(
            "delta appends re-pinned instead of re-keying the live pin"
        )
    if RESIDENT.stats()["compactions"] <= 0:
        violations.append("delta never crossed into background compaction")
    for k in (2000, 2039, 7, 500):
        if fast(k) != oracle(k):
            violations.append(
                f"post-delta/compaction lookup k={k} diverged from oracle"
            )
    compiles0 = METRICS.snapshot().get("xla_compiles", 0.0)
    for k in keys[:50]:
        fast(k)
    post_compiles = METRICS.snapshot().get("xla_compiles", 0.0) - compiles0
    if post_compiles > 0:
        violations.append(
            f"post-compaction probes lowered {post_compiles:g} new XLA "
            "programs (expected 0)"
        )

    # -- 4. pin-budget overflow degrades to the cold path -------------
    r.session.resident_pin_budget_mb = 0
    RESIDENT.evict_all()
    got = fast(7)
    if got != oracle(7):
        violations.append(
            f"zero-budget lookup failed or diverged (got {got})"
        )
    if RESIDENT.stats()["entries"] != 0:
        violations.append("zero-budget lookup left a pin behind")

    for v in violations:
        print(f"bench: resident VIOLATION: {v}", file=sys.stderr)
    stats = RESIDENT.stats()
    print(json.dumps({
        "resident_smoke": {
            "warm_p50_ms": round(warm_p50 * 1e3, 4),
            "cold_p50_ms": round(cold_p50 * 1e3, 4),
            "speedup": round(cold_p50 / max(warm_p50, 1e-9), 1),
            "hits": stats["hits"],
            "misses": stats["misses"],
            "pins": stats["pins"],
            "evictions": stats["evictions"],
            "compactions": stats["compactions"],
            "violations": len(violations),
        }
    }))
    return 1 if violations else 0


def _adaptive_smoke(argv) -> int:
    """--adaptive-smoke: CI gate for the adaptive execution tier
    (trino_tpu/adaptive/). A q72-class multi-join over the memory
    connector whose dimension stats LIE (a fan-out build side reported
    at 1/20th of its true cardinality), so the optimizer's first plan
    is wrong on purpose. Two arms run the same query over the same
    lying catalog: non-adaptive rides the bad plan; adaptive observes
    the completed build at the barrier, crosses the re-plan threshold,
    and re-optimizes the remainder seeded with observed stats. Exit 1
    iff the adaptive arm fails to re-plan, the arms disagree on the
    answer, the adaptive warm wall does not beat the non-adaptive warm
    wall, or the adaptive warm loop mints a new XLA lowering."""
    import dataclasses

    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.adaptive import SPOOL
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.engine import LocalQueryRunner, Session
    from trino_tpu.runtime.metrics import METRICS

    def build_catalog() -> MemoryConnector:
        conn = MemoryConnector()
        rng = np.random.default_rng(17)
        n, keys, fan = 50_000, 40, 20
        conn.load_table(
            "s", "facts",
            [ColumnMetadata("k1", T.BIGINT), ColumnMetadata("k2", T.BIGINT),
             ColumnMetadata("v", T.BIGINT)],
            [rng.integers(0, keys, n).astype(np.int64),
             rng.integers(0, 1000, n).astype(np.int64),
             rng.integers(0, 100, n).astype(np.int64)],
        )
        # d1 fans out (each key 20x); the lie below hides the fan-out
        conn.load_table(
            "s", "d1",
            [ColumnMetadata("k", T.BIGINT), ColumnMetadata("tag", T.BIGINT)],
            [np.repeat(np.arange(keys, dtype=np.int64), fan),
             np.arange(keys * fan, dtype=np.int64)],
        )
        conn.load_table(
            "s", "d2",
            [ColumnMetadata("k", T.BIGINT), ColumnMetadata("w", T.BIGINT)],
            [np.arange(2, dtype=np.int64), np.arange(2, dtype=np.int64)],
        )
        real = conn.metadata.get_table_statistics

        def lying(handle):
            ts = real(handle)
            if handle.table == "d1" and ts.row_count is not None:
                return dataclasses.replace(
                    ts, row_count=ts.row_count / 20.0, columns={}
                )
            return ts

        conn.metadata.get_table_statistics = lying
        return conn

    sql = (
        "select count(*), sum(f.v + d1.tag + d2.w) from facts f "
        "join d1 on f.k1 = d1.k join d2 on f.k2 = d2.k"
    )

    def run_arm(adaptive: bool) -> dict:
        SPOOL.clear()
        r = LocalQueryRunner(Session(
            catalog="memory", schema="s",
            adaptive_execution=adaptive,
            adaptive_replan_threshold=2.0,
        ))
        r.register_catalog("memory", build_catalog())
        t0 = time.time()
        rows = r.execute(sql).rows
        cold = time.time() - t0
        walls = []
        compiles0 = METRICS.counter("xla_compiles")
        for _ in range(3):
            t0 = time.time()
            assert r.execute(sql).rows == rows
            walls.append(time.time() - t0)
        new_lowerings = METRICS.counter("xla_compiles") - compiles0
        report = r._last_adaptive_report
        return {
            "rows": rows,
            "cold_wall_s": round(cold, 3),
            "warm_wall_s": round(sorted(walls)[1], 4),  # median of 3
            "warm_new_lowerings": int(new_lowerings),
            "replans": report.replans if report is not None else 0,
            "observations": (
                len(report.observations) if report is not None else 0
            ),
        }

    print("bench: adaptive smoke (misestimated q72-class join, "
          "memory connector, CPU ok)")
    base = run_arm(adaptive=False)
    adapt = run_arm(adaptive=True)
    violations = []
    if adapt["replans"] < 1:
        violations.append(
            "adaptive arm never re-planned — the misestimate was not "
            "observed at the barrier"
        )
    if base["rows"] != adapt["rows"]:
        violations.append(
            f"arms disagree: base={base['rows']} adaptive={adapt['rows']}"
        )
    if adapt["warm_wall_s"] >= base["warm_wall_s"]:
        violations.append(
            f"adaptive warm wall {adapt['warm_wall_s']}s did not beat "
            f"non-adaptive {base['warm_wall_s']}s"
        )
    if adapt["warm_new_lowerings"] != 0:
        violations.append(
            f"adaptive warm loop minted {adapt['warm_new_lowerings']} "
            "new XLA lowerings — re-planned programs left the "
            "capacity ladder"
        )
    for v in violations:
        print(f"bench: adaptive VIOLATION: {v}", file=sys.stderr)
    base.pop("rows")
    adapt.pop("rows")
    print(json.dumps({
        "adaptive_smoke": {
            "query": "q72-class misestimated join",
            "base": base,
            "adaptive": adapt,
            "speedup": round(
                base["warm_wall_s"] / max(adapt["warm_wall_s"], 1e-9), 2
            ),
            "violations": len(violations),
        }
    }))
    return 1 if violations else 0


# recovery-smoke query: a q72-class deep multi-build join (4 tables,
# grouped agg) that the mesh plane chunks into dozens of steps — deep
# enough that discarding completed chunks is genuinely expensive
RECOVERY_Q = (
    "select c_mktsegment, n_name, count(*) c, sum(l_quantity) q "
    "from lineitem join orders on l_orderkey = o_orderkey "
    "join customer on o_custkey = c_custkey "
    "join nation on c_nationkey = n_nationkey "
    "group by c_mktsegment, n_name order by c_mktsegment, n_name"
)


def _recovery_smoke(argv) -> int:
    """--recovery-smoke: CI gate for the recovery tier
    (trino_tpu/recovery/). An injected device loss lands at chunk k of
    K on a q72-class join, twice: the RESTART arm runs with
    checkpointing off — the fault discards every completed chunk and
    the page plane recomputes from zero (the pre-recovery behavior) —
    and the RESUME arm runs with chunk checkpointing on, so the mesh
    resumes from its last checkpoint. Gates: both arms oracle-equal to
    the page plane, the resume arm stays ON the mesh, resumes >= 1,
    re-executes fewer chunks than the restart discards, beats the
    restart wall, and mints zero new XLA lowerings (resumed carries
    land on already-warm capacity-ladder rungs). Exit 1 on violation."""
    if os.environ.get("RECOVERY_SMOKE_INNER") != "1":
        # same clean-slate re-exec as --mesh-smoke: the multi-device
        # host platform must be configured before jax initializes
        env = dict(os.environ)
        env["RECOVERY_SMOKE_INNER"] = "1"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--recovery-smoke"],
            env=env,
        ).returncode

    import jax

    jax.config.update("jax_platforms", "cpu")
    n_dev = len(jax.devices())

    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.parallel.mesh_chunk import LAST_RUN_INFO, MeshDeviceLost
    from trino_tpu.runtime import DistributedQueryRunner
    from trino_tpu.runtime.metrics import METRICS

    def mk(**session_kw):
        r = DistributedQueryRunner(
            Session(catalog="tpch", schema="tiny", **session_kw),
            n_workers=2, hash_partitions=2,
        )
        r.register_catalog("tpch", create_tpch_connector())
        return r

    violations = []
    print(f"bench: recovery smoke ({n_dev}-device cpu mesh, "
          "q72-class join, tpch tiny)")
    page = mk(mesh_execution=False)
    oracle = page.execute(RECOVERY_Q).rows

    resume = mk(mesh_chunk_rows=256, mesh_checkpoint_interval_chunks=4)
    warm = resume.execute(RECOVERY_Q).rows  # warm clean run
    if resume._last_data_plane != "mesh":
        violations.append(
            f"clean run took {resume._last_data_plane}, not the mesh "
            f"(fallback: {resume.last_mesh_fallback})"
        )
    if warm != oracle:
        violations.append("clean mesh run != page-plane oracle")
    K = int(LAST_RUN_INFO.get("chunks") or 0)
    fault_k = max(1, (3 * K) // 4)

    def make_hook():
        state = {"fired": 0}

        def hook(k, Ktot):
            if k == fault_k and not state["fired"]:
                state["fired"] = 1
                raise MeshDeviceLost(
                    f"recovery smoke: injected device loss at chunk "
                    f"{k}/{Ktot}"
                )

        return hook, state

    # RESTART arm: no checkpoints — the fault unwinds the whole mesh
    # run and the page plane recomputes from zero
    restart = mk(mesh_chunk_rows=256)
    restart.execute(RECOVERY_Q)  # warm its mesh programs too
    hook, st_restart = make_hook()
    mesh_chunk.MESH_FAULT_HOOK = hook
    t0 = time.time()
    try:
        rows_restart = restart.execute(RECOVERY_Q).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    wall_restart = time.time() - t0
    if rows_restart != oracle:
        violations.append("restart arm diverged from the oracle")
    if not st_restart["fired"]:
        violations.append("restart arm: fault never fired")

    # RESUME arm: same fault, checkpoint every 4 chunks
    hook, st_resume = make_hook()
    compiles0 = METRICS.snapshot().get("xla_compiles", 0.0)
    mesh_chunk.MESH_FAULT_HOOK = hook
    t0 = time.time()
    try:
        rows_resume = resume.execute(RECOVERY_Q).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    wall_resume = time.time() - t0
    new_lowerings = METRICS.snapshot().get("xla_compiles", 0.0) - compiles0
    info = dict(LAST_RUN_INFO)
    re_executed = int(info.get("executed_chunk_steps") or 0) - K
    if rows_resume != oracle:
        violations.append("resume arm diverged from the oracle")
    if not st_resume["fired"]:
        violations.append("resume arm: fault never fired")
    elif resume._last_data_plane != "mesh":
        violations.append(
            f"resume arm left the mesh plane "
            f"({resume._last_data_plane}: {resume.last_mesh_fallback})"
        )
    elif not info.get("resumes"):
        violations.append(f"resume arm never resumed ({info})")
    elif re_executed >= fault_k:
        violations.append(
            f"resume arm re-executed {re_executed} chunks — the "
            f"restart arm discards {fault_k}; the checkpoint saved "
            "nothing"
        )
    if wall_resume >= wall_restart:
        violations.append(
            f"resume wall {wall_resume:.2f}s did not beat the "
            f"full-restart wall {wall_restart:.2f}s"
        )
    if new_lowerings > 0:
        violations.append(
            f"resumed run lowered {new_lowerings:g} new XLA programs "
            "(expected 0: carries are ladder-stable)"
        )

    for v in violations:
        print(f"bench: recovery VIOLATION: {v}", file=sys.stderr)
    print(json.dumps({
        "recovery_smoke": {
            "devices": n_dev,
            "chunks": K,
            "fault_chunk": fault_k,
            "resumed_from_chunk": info.get("resumed_from_chunk"),
            "re_executed_chunks": re_executed,
            "restart_wall_s": round(wall_restart, 3),
            "resume_wall_s": round(wall_resume, 3),
            "new_lowerings_on_resume": new_lowerings,
            "violations": len(violations),
        }
    }))
    return 1 if violations else 0


def _failover_smoke(argv) -> int:
    """--failover-smoke: CI gate for the replicated serving plane
    (trino_tpu/runtime/replicas.py). Two replicas are carved from an
    8-device CPU mesh; an injected device loss hard-kills whichever
    replica serves the query at chunk 3K/4, twice: the RESTART arm runs
    with checkpointing off — the sibling sub-mesh takes the query over
    but must recompute from chunk 0 — and the RESUME arm runs with
    chunk checkpointing on, so the sibling restores the host-portable
    checkpoint and continues from chunk k. Gates: both arms
    oracle-equal and ON the mesh plane (failover, not page fallback),
    exactly one failover each, the resume arm re-executes fewer chunks
    than the restart arm recomputes, beats its wall, mints zero new XLA
    lowerings (the sibling is warm), and a deadline expiring during the
    failed-over stretch still kills typed, naming the resume point and
    replica. Exit 1 on violation."""
    if os.environ.get("FAILOVER_SMOKE_INNER") != "1":
        # same clean-slate re-exec as --recovery-smoke: the multi-device
        # host platform must be configured before jax initializes
        env = dict(os.environ)
        env["FAILOVER_SMOKE_INNER"] = "1"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--failover-smoke"],
            env=env,
        ).returncode

    import jax

    jax.config.update("jax_platforms", "cpu")
    n_dev = len(jax.devices())

    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.parallel.mesh_chunk import LAST_RUN_INFO, MeshDeviceLost
    from trino_tpu.recovery import CHECKPOINTS
    from trino_tpu.runtime import DistributedQueryRunner
    from trino_tpu.runtime.metrics import METRICS
    from trino_tpu.runtime.query_tracker import ExceededTimeLimitError

    def mk(**session_kw):
        r = DistributedQueryRunner(
            Session(
                catalog="tpch", schema="tiny", mesh_replicas=2,
                mesh_chunk_rows=256, mesh_resume_attempts=0,
                **session_kw,
            ),
            n_workers=2, hash_partitions=2,
        )
        r.register_catalog("tpch", create_tpch_connector())
        return r

    violations = []
    print(f"bench: failover smoke ({n_dev}-device cpu mesh, 2 replicas, "
          "q72-class join, tpch tiny)")
    page = mk(mesh_execution=False)
    oracle = page.execute(RECOVERY_Q).rows

    def warm(runner) -> int:
        """Warm BOTH replicas (sequential placements round-robin) and
        learn K; returns the chunk count of the warm run."""
        for _ in range(2):
            rows = runner.execute(RECOVERY_Q).rows
            if rows != oracle:
                violations.append("warm replicated run != page oracle")
            if runner._last_data_plane != "mesh":
                violations.append(
                    f"warm run took {runner._last_data_plane}, not the "
                    f"mesh (fallback: {runner.last_mesh_fallback})"
                )
        return int(LAST_RUN_INFO.get("chunks") or 0)

    def make_kill_hook(fault_k):
        """Kill whichever replica serves the run's first chunk — the
        victim is discovered, not hardcoded, so placement order cannot
        unseat the fault. Persistent: a hard-killed replica stays dead
        for the rest of the arm."""
        state = {"victim": None, "fired": 0}

        def hook(k, Ktot):
            rep = mesh_chunk.active_replica()
            if rep is None:
                return
            if state["victim"] is None:
                state["victim"] = rep
            if rep == state["victim"] and k >= fault_k:
                state["fired"] += 1
                raise MeshDeviceLost(
                    f"failover smoke: replica {rep} hard-killed at "
                    f"chunk {k}/{Ktot}"
                )

        return hook, state

    def run_arm(runner, fault_k):
        hook, st = make_kill_hook(fault_k)
        steps0 = METRICS.counter("mesh.chunk_steps")
        compiles0 = METRICS.counter("xla_compiles")
        mesh_chunk.MESH_FAULT_HOOK = hook
        t0 = time.time()
        try:
            rows = runner.execute(RECOVERY_Q).rows
        finally:
            mesh_chunk.MESH_FAULT_HOOK = None
        return {
            "rows": rows,
            "wall": time.time() - t0,
            "fired": st["fired"],
            "victim": st["victim"],
            "steps": int(METRICS.counter("mesh.chunk_steps") - steps0),
            "lowerings": int(METRICS.counter("xla_compiles") - compiles0),
            "plane": runner._last_data_plane,
            "info": dict(LAST_RUN_INFO),
            "rm": runner._replicas.stats() if runner._replicas else {},
        }

    # RESTART arm: no checkpoints — failover lands the sibling at chunk 0
    restart = mk()
    K = warm(restart)
    fault_k = max(1, (3 * K) // 4)
    a_restart = run_arm(restart, fault_k)
    # the victim executed chunks [0, fault_k), the sibling all K: the
    # failover recomputed everything the kill discarded
    re_restart = a_restart["steps"] - K
    if a_restart["rows"] != oracle:
        violations.append("restart arm diverged from the oracle")
    if not a_restart["fired"]:
        violations.append("restart arm: kill never fired")
    elif a_restart["plane"] != "mesh":
        violations.append(
            f"restart arm left the mesh plane ({a_restart['plane']}: "
            f"{restart.last_mesh_fallback})"
        )
    elif a_restart["rm"].get("failovers") != 1:
        violations.append(
            f"restart arm: expected exactly 1 failover "
            f"({a_restart['rm']})"
        )

    # RESUME arm: same kill, checkpoint every 4 chunks — the sibling
    # restores the host-portable checkpoint instead of starting over
    resume = mk(mesh_checkpoint_interval_chunks=4)
    warm(resume)
    a_resume = run_arm(resume, fault_k)
    re_resume = a_resume["steps"] - K
    info = a_resume["info"]
    if a_resume["rows"] != oracle:
        violations.append("resume arm diverged from the oracle")
    if not a_resume["fired"]:
        violations.append("resume arm: kill never fired")
    elif a_resume["plane"] != "mesh":
        violations.append(
            f"resume arm left the mesh plane ({a_resume['plane']}: "
            f"{resume.last_mesh_fallback})"
        )
    elif not info.get("resumes"):
        violations.append(
            f"resume arm: sibling never restored the checkpoint ({info})"
        )
    elif a_resume["rm"].get("failovers") != 1:
        violations.append(
            f"resume arm: expected exactly 1 failover ({a_resume['rm']})"
        )
    if re_resume >= max(re_restart, 1):
        violations.append(
            f"resume arm re-executed {re_resume} chunks — the restart "
            f"arm recomputed {re_restart}; the checkpoint saved nothing"
        )
    if a_resume["wall"] >= a_restart["wall"]:
        violations.append(
            f"resume wall {a_resume['wall']:.2f}s did not beat the "
            f"restart-from-zero wall {a_restart['wall']:.2f}s"
        )
    if a_resume["lowerings"] > 0:
        violations.append(
            f"failover lowered {a_resume['lowerings']} new XLA programs "
            "on the sibling (expected 0: both replicas are warm)"
        )

    # DEADLINE arm: the execution-time limit expires while the sibling
    # is working through the failed-over stretch — the kill must stay
    # typed and name where the run restarted. The hook stalls the
    # sibling (not the victim) past the deadline once a resume has been
    # recorded, so expiry deterministically lands mid-failed-over-chunk.
    deadline_s = 8.0
    resume.session.set_property(
        "query_max_execution_time_s", str(deadline_s)
    )
    hook, st = make_kill_hook(fault_k)
    resumed0 = CHECKPOINTS.resumed
    t_arm = [None]

    def deadline_hook(k, Ktot):
        hook(k, Ktot)
        rep = mesh_chunk.active_replica()
        if (
            rep is not None and st["victim"] is not None
            and rep != st["victim"] and k >= fault_k
            and CHECKPOINTS.resumed > resumed0
        ):
            stall = (t_arm[0] + deadline_s + 0.5) - time.time()
            if stall > 0:
                time.sleep(stall)

    deadline_err = None
    mesh_chunk.MESH_FAULT_HOOK = deadline_hook
    t_arm[0] = time.time()
    try:
        resume.execute(RECOVERY_Q)
        violations.append(
            "deadline arm: query outlived its execution-time limit"
        )
    except ExceededTimeLimitError as e:
        deadline_err = str(e)
    except Exception as e:
        violations.append(
            f"deadline arm: untyped kill {type(e).__name__}: {e}"
        )
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
        resume.session.set_property("query_max_execution_time_s", "0")
    if deadline_err is not None:
        if "[EXCEEDED_TIME_LIMIT]" not in deadline_err:
            violations.append(
                f"deadline arm: kill lost its code ({deadline_err})"
            )
        if "resumed from chunk" not in deadline_err \
                or "on replica" not in deadline_err:
            violations.append(
                f"deadline arm: kill does not name the resume point "
                f"({deadline_err})"
            )

    for v in violations:
        print(f"bench: failover VIOLATION: {v}", file=sys.stderr)
    print(json.dumps({
        "failover_smoke": {
            "devices": n_dev,
            "replicas": 2,
            "chunks": K,
            "fault_chunk": fault_k,
            "resumed_from_chunk": info.get("resumed_from_chunk"),
            "re_executed_restart": re_restart,
            "re_executed_resume": re_resume,
            "restart_wall_s": round(a_restart["wall"], 3),
            "resume_wall_s": round(a_resume["wall"], 3),
            "new_lowerings_on_failover": a_resume["lowerings"],
            "deadline_error": (deadline_err or "")[-120:],
            "violations": len(violations),
        }
    }))
    return 1 if violations else 0


def _multihost_victim() -> int:
    """The victim coordinator of --multihost-smoke: its own process,
    its own 8-device CPU mesh, the survivor's fabric endpoint as its
    only peer. Runs the recovery query with checkpointing every chunk
    (each boundary's snapshot streams to the survivor), then HARD-KILLS
    itself (os._exit — no unwind, no goodbye) at chunk 3K/4 after
    forcing the last snapshot onto the wire."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.recovery import CHECKPOINTS
    from trino_tpu.runtime import DistributedQueryRunner
    from trino_tpu.runtime.fabric import active_fabric

    uri = os.environ["MULTIHOST_FABRIC_URI"]
    runner = DistributedQueryRunner(
        Session(
            catalog="tpch", schema="tiny", mesh_replicas=2,
            mesh_chunk_rows=256, mesh_resume_attempts=0,
            mesh_checkpoint_interval_chunks=1, fabric_peers=uri,
        ),
        n_workers=2, hash_partitions=2,
    )
    runner.register_catalog("tpch", create_tpch_connector())

    def hook(k, K):
        fault_k = max(1, (3 * K) // 4)
        if k != fault_k:
            return
        fab = active_fabric()
        if fab is not None:
            # drain the async queue, then ship the LATEST snapshot of
            # every live entry synchronously: the survivor must hold
            # next_chunk == fault_k before this process ceases to exist
            fab.pusher.flush(10.0)
            for key in list(CHECKPOINTS._entries):
                fab.pusher._push(key)
        print(json.dumps(
            {"victim": {"fault_chunk": k, "chunks": K}}
        ), flush=True)
        os._exit(9)

    mesh_chunk.MESH_FAULT_HOOK = hook
    runner.execute(RECOVERY_Q)
    print(json.dumps({"victim": {"error": "fault never fired"}}),
          flush=True)
    return 1


def _multihost_smoke(argv) -> int:
    """--multihost-smoke: CI gate for the multi-host replica fabric
    (trino_tpu/runtime/fabric.py) across a REAL process boundary. Two
    coordinator processes, each over its own 8-device CPU mesh: the
    SURVIVOR warms the recovery query and opens a fabric endpoint over
    its checkpoint store; the VICTIM subprocess attaches that endpoint
    as its fabric peer, checkpoints every chunk (each boundary's bytes
    stream to the survivor), and hard-kills itself (os._exit 9, no
    unwind) at chunk 3K/4. Gates: the pushed snapshot landed in the
    survivor's store across the process boundary; a corrupted replay of
    it (bit-flipped bytes under the original digest) is digest-rejected
    without poisoning the store (fabric.digest_rejects >= 1); the
    survivor's next run of the same query resumes from exactly the
    victim's fault chunk — oracle-equal bytes, zero re-executed
    chunk-steps, zero new XLA lowerings — and beats the survivor's own
    warm full-length wall. Exit 1 on violation."""
    if os.environ.get("MULTIHOST_SMOKE_VICTIM") == "1":
        return _multihost_victim()
    if os.environ.get("MULTIHOST_SMOKE_INNER") != "1":
        env = dict(os.environ)
        env["MULTIHOST_SMOKE_INNER"] = "1"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--multihost-smoke"],
            env=env,
        ).returncode

    import jax

    jax.config.update("jax_platforms", "cpu")
    n_dev = len(jax.devices())

    # both processes authenticate fabric traffic with the same secret
    os.environ.setdefault("TRINO_TPU_INTERNAL_SECRET", "multihost-smoke")

    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.parallel.mesh_chunk import LAST_RUN_INFO
    from trino_tpu.recovery import CHECKPOINTS
    from trino_tpu.runtime import DistributedQueryRunner
    from trino_tpu.runtime.fabric import HostFabric, checkpoint_digest
    from trino_tpu.runtime.http import FabricClient, FabricServer
    from trino_tpu.runtime.metrics import METRICS

    violations = []
    print(f"bench: multihost smoke ({n_dev}-device cpu mesh per "
          "coordinator, 2 processes, q72-class join, tpch tiny)")

    def mk(**session_kw):
        r = DistributedQueryRunner(
            Session(
                catalog="tpch", schema="tiny", mesh_replicas=2,
                mesh_chunk_rows=256, mesh_resume_attempts=0,
                mesh_checkpoint_interval_chunks=1, **session_kw,
            ),
            n_workers=2, hash_partitions=2,
        )
        r.register_catalog("tpch", create_tpch_connector())
        return r

    page = mk(mesh_execution=False)
    oracle = page.execute(RECOVERY_Q).rows

    survivor = mk()
    # warm both replicas; the second (fully warm) run's wall is the
    # cold-restart baseline the resume must beat
    wall_cold = None
    for _ in range(2):
        t0 = time.time()
        rows = survivor.execute(RECOVERY_Q).rows
        wall_cold = time.time() - t0
        if rows != oracle:
            violations.append("survivor warm run != page oracle")
        if survivor._last_data_plane != "mesh":
            violations.append(
                f"survivor warm run took {survivor._last_data_plane}, "
                f"not the mesh ({survivor.last_mesh_fallback})"
            )
    K_local = int(LAST_RUN_INFO.get("chunks") or 0)

    # the survivor's fabric endpoint, bound over its LIVE store — what
    # the victim pushes is exactly what resume-on-entry will find
    peer = HostFabric(host_id="survivor")
    srv = FabricServer(peer)
    CHECKPOINTS.clear()  # all entries after the victim dies are pushed ones

    victim_env = dict(os.environ)
    victim_env["MULTIHOST_SMOKE_VICTIM"] = "1"
    victim_env["MULTIHOST_FABRIC_URI"] = srv.uri
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--multihost-smoke"],
        env=victim_env, capture_output=True, text=True, timeout=600,
    )
    wall_victim = time.time() - t0
    victim = {}
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                victim = json.loads(line).get("victim", {})
            except ValueError:
                pass
    if proc.returncode != 9:
        violations.append(
            f"victim exited {proc.returncode}, expected the hard-kill 9 "
            f"(stderr tail: {proc.stderr[-300:]!r})"
        )
    fault_k = victim.get("fault_chunk")
    K = victim.get("chunks")
    if not fault_k or not K:
        violations.append(f"victim never reported its fault point ({victim})")
    elif K != K_local:
        violations.append(
            f"chunking diverged across hosts: victim ran {K} chunks, "
            f"survivor {K_local} — checkpoint keys cannot line up"
        )
    if peer.received < 1 or len(CHECKPOINTS) < 1:
        violations.append(
            f"no checkpoint crossed the process boundary "
            f"(received={peer.received}, entries={len(CHECKPOINTS)})"
        )

    # corruption arm: replay the pushed snapshot bit-flipped under its
    # ORIGINAL digest — the digest gate must reject it and leave the
    # genuine entry untouched for the resume arm below
    rejects0 = METRICS.counter("fabric.digest_rejects")
    pushed_key = next(iter(CHECKPOINTS._entries), None)
    if pushed_key is not None:
        data = CHECKPOINTS.export_bytes(pushed_key)
        flipped = bytearray(data)
        flipped[len(flipped) // 2] ^= 0xFF
        client = FabricClient(srv.uri)
        out = client.push_checkpoint(
            pushed_key, bytes(flipped), digest=checkpoint_digest(data)
        )
        if out.get("imported") is not False or (
            out.get("reason") != "digest_mismatch"
        ):
            violations.append(
                f"corrupted payload was not digest-rejected ({out})"
            )
        if METRICS.counter("fabric.digest_rejects") - rejects0 < 1:
            violations.append(
                "fabric.digest_rejects did not count the corrupt replay"
            )
        if CHECKPOINTS.export_bytes(pushed_key) != data:
            violations.append(
                "corrupt replay POISONED the stored checkpoint bytes"
            )

    # resume arm: the survivor re-runs the query; resume-on-entry finds
    # the victim's pushed snapshot in the local store and continues from
    # exactly the fault chunk on warm programs
    steps0 = METRICS.counter("mesh.chunk_steps")
    compiles0 = METRICS.counter("xla_compiles")
    t0 = time.time()
    rows = survivor.execute(RECOVERY_Q).rows
    wall_resume = time.time() - t0
    steps = int(METRICS.counter("mesh.chunk_steps") - steps0)
    new_lowerings = int(METRICS.counter("xla_compiles") - compiles0)
    info = dict(LAST_RUN_INFO)
    if rows != oracle:
        violations.append("survivor resume diverged from the oracle")
    if survivor._last_data_plane != "mesh":
        violations.append(
            f"survivor resume took {survivor._last_data_plane}, not the "
            f"mesh ({survivor.last_mesh_fallback})"
        )
    if not info.get("resumes"):
        violations.append(
            f"survivor never resumed from the pushed checkpoint ({info})"
        )
    elif fault_k and info.get("resumed_from_chunk") != fault_k:
        violations.append(
            f"survivor resumed from chunk {info.get('resumed_from_chunk')}"
            f", not the victim's fault chunk {fault_k} — the last push "
            f"did not make it"
        )
    if fault_k and K and steps != K - fault_k:
        violations.append(
            f"re-executed {steps - (K - fault_k)} chunk-steps "
            f"({steps} steps for {K - fault_k} remaining chunks)"
        )
    if new_lowerings > 0:
        violations.append(
            f"survivor minted {new_lowerings} new XLA lowerings on "
            "resume (expected 0: its programs were already warm)"
        )
    if wall_cold is not None and wall_resume >= wall_cold:
        violations.append(
            f"resume wall {wall_resume:.2f}s did not beat the warm "
            f"full-length wall {wall_cold:.2f}s"
        )

    srv.stop()
    for v in violations:
        print(f"bench: multihost VIOLATION: {v}", file=sys.stderr)
    print(json.dumps({
        "multihost_smoke": {
            "devices": n_dev,
            "chunks": K,
            "fault_chunk": fault_k,
            "victim_exit": proc.returncode,
            "victim_wall_s": round(wall_victim, 3),
            "pushed_entries": peer.received,
            "digest_rejects": int(
                METRICS.counter("fabric.digest_rejects") - rejects0
            ),
            "resumed_from_chunk": info.get("resumed_from_chunk"),
            "re_executed_chunk_steps": (
                steps - (K - fault_k) if fault_k and K else None
            ),
            "new_lowerings_on_resume": new_lowerings,
            "cold_wall_s": round(wall_cold, 3) if wall_cold else None,
            "resume_wall_s": round(wall_resume, 3),
            "violations": len(violations),
        }
    }))
    return 1 if violations else 0


def _preempt_smoke(argv) -> int:
    """--preempt-smoke: CI gate for checkpoint-backed preemptive
    multi-tenancy (trino_tpu/runtime/scheduler.py). One full-width
    8-device CPU mesh is shared by a q72-class analytic and a
    dimension point lookup. Three sections:

    LATENCY: point p99 while the analytic streams chunks must stay
    within 5x the solo point p99 — the fast lane preempts at the next
    chunk boundary instead of queueing behind the whole scan — with
    preemptions >= 1 and every mixed-mode analytic run oracle-equal.

    PARK: a point arrival mid-analytic parks the analytic's device
    carries into the host checkpoint store, the point answers, and the
    analytic resumes from the parked boundary warm. Gates:
    byte-identical rows, executed_chunk_steps == K (zero re-executed
    chunks), parks == 1, zero new XLA lowerings, no parked state left.

    KILL baseline: the same arrival handled the pre-scheduler way —
    abandon the analytic at the same chunk, answer the point, re-run
    the analytic from scratch. The park arm must beat this wall while
    executing fewer chunk-steps. Exit 1 on any violation."""
    if os.environ.get("PREEMPT_SMOKE_INNER") != "1":
        # same clean-slate re-exec as --mesh-smoke: the multi-device
        # host platform must be configured before jax initializes
        env = dict(os.environ)
        env["PREEMPT_SMOKE_INNER"] = "1"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--preempt-smoke"],
            env=env,
        ).returncode

    import jax

    jax.config.update("jax_platforms", "cpu")
    n_dev = len(jax.devices())

    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.parallel.mesh_chunk import LAST_RUN_INFO
    from trino_tpu.recovery.checkpoint import CHECKPOINTS
    from trino_tpu.runtime import DistributedQueryRunner
    from trino_tpu.runtime.metrics import METRICS
    from trino_tpu.runtime.query_tracker import QueryAbandonedError

    POINT_Q = (
        "select n_name, r_name from nation join region "
        "on n_regionkey = r_regionkey where n_nationkey = 3"
    )

    def mk(**session_kw):
        r = DistributedQueryRunner(
            Session(catalog="tpch", schema="tiny", **session_kw),
            n_workers=2, hash_partitions=2,
        )
        r.register_catalog("tpch", create_tpch_connector())
        return r

    violations = []
    print(f"bench: preempt smoke ({n_dev}-device cpu mesh, q72-class "
          "analytic vs point lookups, tpch tiny)")
    oracle = mk(mesh_execution=False).execute(RECOVERY_Q).rows

    # periodic checkpointing stays at its default (off): park takes its
    # own exact snapshot at the preempted boundary, so the latency and
    # park arms don't need interval snapshots — and a device_get every
    # chunk boundary would widen the very gaps the point lookups wait on
    r = mk(mesh_chunk_rows=256)
    clean = r.execute(RECOVERY_Q).rows  # warm the analytic programs
    if r._last_data_plane != "mesh":
        violations.append(
            f"clean analytic took {r._last_data_plane}, not the mesh "
            f"(fallback: {r.last_mesh_fallback})"
        )
    if clean != oracle:
        violations.append("clean mesh analytic != page-plane oracle")
    K = int(LAST_RUN_INFO.get("chunks") or 0)
    point_clean = r.execute(POINT_Q).rows  # warm the point programs
    sched = r._mesh_scheduler
    if sched is None:
        violations.append("mesh scheduler never engaged on dispatch")
        for v in violations:
            print(f"bench: preempt VIOLATION: {v}", file=sys.stderr)
        return 1

    # -- KILL baseline: abandon at fault_k, answer, rerun from zero --
    fault_k = max(1, K // 2)
    st_kill = {"fired": 0}

    def abandon_hook(k, Ktot):
        if k == fault_k and not st_kill["fired"]:
            st_kill["fired"] = 1

    steps0 = METRICS.snapshot().get("mesh.chunk_steps", 0.0)
    mesh_chunk.MESH_FAULT_HOOK = abandon_hook
    t0 = time.time()
    try:
        r.execute(RECOVERY_Q, cancel=lambda: bool(st_kill["fired"]))
        violations.append("kill arm: abandoned analytic completed")
    except QueryAbandonedError:
        pass
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    if r.execute(POINT_Q).rows != point_clean:
        violations.append("kill arm: point run diverged")
    # belt and braces: if the abandoned run left any snapshot behind,
    # resubmitting the same statement would warm-resume mid-query —
    # that's the recovery tier helping, not the kill baseline. Drop
    # everything so the rerun honestly starts at zero.
    CHECKPOINTS.clear()
    if r.execute(RECOVERY_Q).rows != oracle:
        violations.append("kill arm: analytic rerun diverged")
    wall_kill = time.time() - t0
    steps_kill = METRICS.snapshot().get("mesh.chunk_steps", 0.0) - steps0
    if not st_kill["fired"]:
        violations.append("kill arm: the abandon hook never fired")

    # -- PARK: same arrival chunk, park/resume instead ----------------
    main_t = threading.current_thread()
    st_park = {"fired": 0, "rows": None, "err": None}

    def point_runner():
        try:
            st_park["rows"] = r.execute(POINT_Q).rows
        except Exception as e:
            st_park["err"] = f"{type(e).__name__}: {e}"

    pth = threading.Thread(target=point_runner, daemon=True)

    def park_hook(k, Ktot):
        # main-thread filter: the point thread's own chunk loop fires
        # this hook too. Holding the boundary until the fast seat is
        # visible makes the NEXT boundary park deterministically.
        if (k == fault_k and not st_park["fired"]
                and threading.current_thread() is main_t):
            st_park["fired"] = 1
            pth.start()
            wait_until = time.time() + 10.0
            while (not sched.waiting_count(fast=True)
                   and time.time() < wait_until):
                time.sleep(0.002)

    s0 = sched.stats()
    compiles0 = METRICS.snapshot().get("xla_compiles", 0.0)
    steps1 = METRICS.snapshot().get("mesh.chunk_steps", 0.0)
    mesh_chunk.MESH_FAULT_HOOK = park_hook
    t0 = time.time()
    try:
        rows_park = r.execute(RECOVERY_Q).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    pth.join(timeout=60.0)
    wall_park = time.time() - t0
    steps_park = METRICS.snapshot().get("mesh.chunk_steps", 0.0) - steps1
    new_lowerings = METRICS.snapshot().get("xla_compiles", 0.0) - compiles0
    info = dict(LAST_RUN_INFO)
    parks = sched.stats()["parks"] - s0["parks"]
    re_exec_park = int(info.get("executed_chunk_steps") or 0) - K
    if not st_park["fired"]:
        violations.append("park arm: the arrival hook never fired")
    if st_park["err"]:
        violations.append(f"park arm: point died: {st_park['err']}")
    elif st_park["rows"] != point_clean:
        violations.append("park arm: point run diverged")
    if rows_park != clean:
        violations.append(
            "park arm: resumed analytic is not byte-identical to the "
            "clean run"
        )
    if parks != 1:
        violations.append(f"park arm: expected exactly 1 park, saw "
                          f"{parks}")
    if re_exec_park != 0:
        violations.append(
            f"park arm re-executed {re_exec_park} chunk-steps "
            "(expected 0: resume is from the parked boundary)"
        )
    if new_lowerings > 0:
        violations.append(
            f"park arm lowered {new_lowerings:g} new XLA programs "
            "(expected 0: parked carries restore onto warm rungs)"
        )
    if CHECKPOINTS.parked_count():
        violations.append(
            f"{CHECKPOINTS.parked_count()} parked snapshots leaked "
            "past resume"
        )
    if wall_park >= wall_kill:
        violations.append(
            f"park wall {wall_park:.2f}s did not beat the "
            f"abandon+rerun wall {wall_kill:.2f}s"
        )
    if steps_park >= steps_kill:
        violations.append(
            f"park arm spent {steps_park:g} chunk-steps vs the kill "
            f"arm's {steps_kill:g} — parking saved nothing"
        )

    # -- LATENCY: solo point p99, then point p99 under the analytic --
    # runs last: the park arm above warmed the park path (first-ever
    # park pays one-time host-buffer costs that would otherwise land on
    # the first mixed sample)
    # 100 mixed samples so p99 is a real percentile (index 98), not the
    # sample max — the mixed tail is one in-flight chunk gap + a park
    # cycle + the point itself (~90-140ms), and a single GIL-jitter
    # outlier shouldn't decide the gate
    solo_reps, mixed_reps = 50, 100

    def p99(walls):
        w = sorted(walls)
        return w[min(len(w) - 1, int(round(0.99 * (len(w) - 1))))]

    solo = []
    for _ in range(solo_reps):
        t0 = time.time()
        rows = r.execute(POINT_Q).rows
        solo.append(time.time() - t0)
        if rows != point_clean:
            violations.append("solo point run diverged")
            break
    p99_solo = p99(solo)

    stop = threading.Event()
    analytic = {"runs": 0, "bad": 0, "err": None}

    def analytic_loop():
        try:
            while not stop.is_set():
                if r.execute(RECOVERY_Q).rows != oracle:
                    analytic["bad"] += 1
                analytic["runs"] += 1
        except Exception as e:  # surfaced as a violation below
            analytic["err"] = f"{type(e).__name__}: {e}"

    pre0 = sched.stats()["preemptions"]
    th = threading.Thread(target=analytic_loop, daemon=True)
    th.start()
    wait_until = time.time() + 5.0
    while sched.holder_query() is None and time.time() < wait_until:
        time.sleep(0.005)  # let the analytic actually hold the mesh
    mixed, streaming = [], []
    for _ in range(mixed_reps):
        # the p99 bound is scoped to arrivals while the analytic holds
        # the mesh (streams chunks) — that's the wait the scheduler
        # owns. Arrivals during the analytic's host planning/feed-build
        # phases contend only for host CPU (the seat is free); they're
        # reported in the overall p99 but not gated
        holder_at_arrival = sched.holder_query() is not None
        t0 = time.time()
        rows = r.execute(POINT_Q).rows
        wall = time.time() - t0
        mixed.append(wall)
        if holder_at_arrival:
            streaming.append(wall)
        if rows != point_clean:
            violations.append("mixed point run diverged")
            break
        time.sleep(0.02)  # hand chunks back to the analytic
    stop.set()
    th.join(timeout=120.0)
    p99_mixed = p99(mixed)
    p99_stream = p99(streaming) if streaming else p99_mixed
    preempts = sched.stats()["preemptions"] - pre0
    if len(streaming) < 10:
        violations.append(
            f"only {len(streaming)} of {len(mixed)} points arrived "
            "while the analytic held the mesh — the mixed window "
            "never really contended"
        )
    if analytic["err"]:
        violations.append(f"mixed analytic died: {analytic['err']}")
    if analytic["bad"]:
        violations.append(
            f"{analytic['bad']} mixed analytic runs diverged from "
            "the oracle"
        )
    if analytic["runs"] < 1:
        violations.append(
            "the analytic made no progress during the mixed window"
        )
    if preempts < 1:
        violations.append(
            "no fast-lane preemption ever fired during mixed traffic"
        )
    if p99_stream > 5.0 * p99_solo:
        violations.append(
            f"streaming-phase point p99 {p99_stream * 1e3:.1f}ms blew "
            f"the 5x solo-p99 bound ({p99_solo * 1e3:.1f}ms solo)"
        )

    for v in violations:
        print(f"bench: preempt VIOLATION: {v}", file=sys.stderr)
    print(json.dumps({
        "preempt_smoke": {
            "devices": n_dev,
            "chunks": K,
            "point_p99_solo_ms": round(p99_solo * 1e3, 2),
            "point_p99_streaming_ms": round(p99_stream * 1e3, 2),
            "point_p99_mixed_overall_ms": round(p99_mixed * 1e3, 2),
            "streaming_samples": len(streaming),
            "slowdown_x": round(p99_stream / max(p99_solo, 1e-9), 2),
            "analytic_runs_during_mixed": analytic["runs"],
            "preemptions_during_mixed": preempts,
            "park_chunk": fault_k + 1,
            "parks": parks,
            "re_executed_chunks_park": re_exec_park,
            "chunk_steps_kill": steps_kill,
            "chunk_steps_park": steps_park,
            "kill_wall_s": round(wall_kill, 3),
            "park_wall_s": round(wall_park, 3),
            "new_lowerings_on_park": new_lowerings,
            "violations": len(violations),
        }
    }))
    return 1 if violations else 0


def _zipf_keys(rng, n: int, n_keys: int, s: float):
    """Seedable zipf-distributed join keys in [0, n_keys): key rank r
    drawn with probability proportional to 1/(r+1)^s. At s=1.4 over 64
    keys the modal key holds ~38% of the rows — past any reasonable
    skew_hot_key_threshold — while staying bounded (np's unbounded
    rng.zipf tail would break fixture determinism across clips)."""
    import numpy as np

    p = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** s
    p /= p.sum()
    return rng.choice(n_keys, size=n, p=p).astype(np.int64)


def _skew_smoke(argv) -> int:
    """--skew-smoke: CI gate for the skew-aware join plane (heavy-hitter
    salted repartition + MXU join-project, ISSUE 16). Two sections over
    seedable zipf key distributions:

    SALTED (8-device cpu mesh): a join whose build side's modal key
    holds ~38% of its rows runs plain, then with adaptive execution +
    skewed_join_salting — the build barrier classifies the heavy hitter
    from OBSERVED stats, annotates the join, and the mesh plane runs
    the exchange salted (hot build rows replicated over all_gather, hot
    probe rows scattered across the all_to_all). Gates: the salted arm
    stays on the mesh, is oracle-equal to the unsalted arm,
    skew.heavy_hitters_detected and skew.salted_exchanges advance, and
    a warm repeat mints zero new XLA lowerings.

    MXU (local path): a high-fanout zipf join feeding SUM/COUNT runs on
    the gather-expansion path, then with mxu_join_enabled — the grouped
    aggregate lowers to the indicator-matmul kernel and the pair batch
    never exists. Gates: oracle-equal, skew.mxu_join_selected advances,
    zero new lowerings on the warm repeat, and the combined skew-aware
    warm wall (salted mesh + MXU local) beats the combined baseline
    warm wall. Exit 1 on any violation."""
    if os.environ.get("SKEW_SMOKE_INNER") != "1":
        # same clean-slate re-exec as --mesh-smoke: the multi-device
        # host platform must be configured before jax initializes
        env = dict(os.environ)
        env["SKEW_SMOKE_INNER"] = "1"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--skew-smoke"],
            env=env,
        ).returncode

    import jax

    jax.config.update("jax_platforms", "cpu")
    n_dev = len(jax.devices())

    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.adaptive import SPOOL
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.engine import LocalQueryRunner, Session
    from trino_tpu.runtime import DistributedQueryRunner
    from trino_tpu.runtime.metrics import METRICS

    def skew_counter(name: str) -> float:
        return METRICS.snapshot().get(f"skew.{name}", 0.0)

    def warm_wall(runner, sql: str, expect) -> tuple:
        """(median-of-3 warm wall, new lowerings over the loop)."""
        walls = []
        compiles0 = METRICS.counter("xla_compiles")
        for _ in range(3):
            t0 = time.time()
            rows = runner.execute(sql).rows
            walls.append(time.time() - t0)
            if rows != expect:
                return None, None
        return (
            sorted(walls)[1],
            METRICS.counter("xla_compiles") - compiles0,
        )

    violations = []
    print(f"bench: skew smoke ({n_dev}-device cpu mesh, zipf keys, "
          "CPU ok)")
    if n_dev < 8:
        violations.append(f"expected an 8-device mesh, got {n_dev}")

    # ---- SALTED section: heavy-hitter detection -> mesh salting ----
    def salted_catalog() -> MemoryConnector:
        conn = MemoryConnector()
        rng = np.random.default_rng(29)
        n, nk = 8000, 64
        conn.load_table(
            "s", "facts",
            [ColumnMetadata("k1", T.BIGINT), ColumnMetadata("v", T.BIGINT)],
            [_zipf_keys(rng, n, nk, 1.4),
             rng.integers(0, 100, n).astype(np.int64)],
        )
        conn.load_table(
            "s", "dim",
            [ColumnMetadata("k", T.BIGINT), ColumnMetadata("w", T.BIGINT)],
            [_zipf_keys(rng, 2000, nk, 1.4),
             rng.integers(0, 10, 2000).astype(np.int64)],
        )
        return conn

    def mk_mesh(**session_kw):
        r = DistributedQueryRunner(
            Session(
                catalog="memory", schema="s",
                broadcast_join_threshold=0, mesh_chunk_rows=4096,
                **session_kw,
            ),
            n_workers=2, hash_partitions=2,
        )
        r.register_catalog("memory", salted_catalog())
        return r

    # the partial aggregate above the join is placement-insensitive,
    # so the salted exchange map accepts the plan (a single-step agg
    # grouping ON the join key would rely on key colocation and is
    # correctly refused)
    salt_sql = (
        "select sum(f.v + d.w), count(*) from facts f "
        "join dim d on f.k1 = d.k"
    )
    SPOOL.clear()
    plain = mk_mesh()
    oracle = plain.execute(salt_sql).rows
    if plain._last_data_plane != "mesh":
        violations.append(
            f"unsalted arm ran on {plain._last_data_plane}, not the "
            f"mesh (fallback: {plain.last_mesh_fallback})"
        )
    plain_warm, _ = warm_wall(plain, salt_sql, oracle)
    if plain_warm is None:
        violations.append("unsalted warm repeat diverged")
        plain_warm = 0.0

    salted = mk_mesh(
        adaptive_execution=True, skewed_join_salting=True,
        skew_hot_key_threshold=0.2,
    )
    hh0 = skew_counter("heavy_hitters_detected")
    se0 = skew_counter("salted_exchanges")
    got = salted.execute(salt_sql).rows
    hh = skew_counter("heavy_hitters_detected") - hh0
    se = skew_counter("salted_exchanges") - se0
    if salted._last_data_plane != "mesh":
        violations.append(
            f"salted arm ran on {salted._last_data_plane}, not the "
            f"mesh (fallback: {salted.last_mesh_fallback})"
        )
    if got != oracle:
        violations.append("salted arm != unsalted oracle")
    if hh < 1:
        violations.append(
            "no heavy hitter detected from observed build stats"
        )
    if se < 1:
        violations.append("no exchange ran salted on the mesh")
    salted_warm, salted_lowerings = warm_wall(salted, salt_sql, oracle)
    if salted_warm is None:
        violations.append("salted warm repeat diverged")
        salted_warm = 0.0
    elif salted_lowerings > 0:
        violations.append(
            f"salted warm repeat lowered {salted_lowerings:g} new XLA "
            "programs (expected 0)"
        )

    # ---- MXU section: high-fanout join-project as matmul ----
    def mxu_catalog() -> MemoryConnector:
        conn = MemoryConnector()
        rng = np.random.default_rng(31)
        n, nk, fan = 50_000, 64, 16
        conn.load_table(
            "s", "facts",
            [ColumnMetadata("k1", T.BIGINT), ColumnMetadata("v", T.BIGINT)],
            [_zipf_keys(rng, n, nk, 1.2),
             rng.integers(0, 100, n).astype(np.int64)],
        )
        # uniform fan-out build: every probe row matches `fan` rows, so
        # the gather path expands n*fan pairs the MXU path never builds
        conn.load_table(
            "s", "dim",
            [ColumnMetadata("k", T.BIGINT), ColumnMetadata("g", T.BIGINT)],
            [np.repeat(np.arange(nk, dtype=np.int64), fan),
             np.arange(nk * fan, dtype=np.int64) % 11],
        )
        return conn

    def mk_local(**session_kw):
        r = LocalQueryRunner(
            Session(catalog="memory", schema="s", **session_kw)
        )
        r.register_catalog("memory", mxu_catalog())
        return r

    mxu_sql = (
        "select d.g, sum(f.v), count(*) from facts f "
        "join dim d on f.k1 = d.k group by d.g order by 1"
    )
    gather = mk_local()
    mxu_oracle = gather.execute(mxu_sql).rows
    gather_warm, _ = warm_wall(gather, mxu_sql, mxu_oracle)
    if gather_warm is None:
        violations.append("gather warm repeat diverged")
        gather_warm = 0.0

    mxu = mk_local(mxu_join_enabled=True, mxu_join_min_work=16.0)
    mj0 = skew_counter("mxu_join_selected")
    mxu_rows = mxu.execute(mxu_sql).rows
    mj = skew_counter("mxu_join_selected") - mj0
    if mxu_rows != mxu_oracle:
        violations.append("MXU arm != gather oracle")
    if mj < 1:
        violations.append("MXU join-project was never selected")
    mxu_warm, mxu_lowerings = warm_wall(mxu, mxu_sql, mxu_oracle)
    if mxu_warm is None:
        violations.append("MXU warm repeat diverged")
        mxu_warm = 0.0
    elif mxu_lowerings > 0:
        violations.append(
            f"MXU warm repeat lowered {mxu_lowerings:g} new XLA "
            "programs (expected 0)"
        )

    # the arm gate: everything-on must beat everything-off on warm
    # walls over the zipf config (the MXU fanout elimination is the
    # CPU-visible win; salting's serialization win needs real shards)
    base_total = plain_warm + gather_warm
    skew_total = salted_warm + mxu_warm
    if skew_total >= base_total:
        violations.append(
            f"skew-aware warm wall {skew_total:.3f}s did not beat the "
            f"baseline {base_total:.3f}s"
        )

    for v in violations:
        print(f"bench: skew VIOLATION: {v}", file=sys.stderr)
    print(json.dumps({
        "skew_smoke": {
            "devices": n_dev,
            "salted": {
                "heavy_hitters_detected": hh,
                "salted_exchanges": se,
                "plain_warm_wall_s": round(plain_warm, 4),
                "salted_warm_wall_s": round(salted_warm, 4),
                "warm_new_lowerings": salted_lowerings,
            },
            "mxu": {
                "selected": mj,
                "gather_warm_wall_s": round(gather_warm, 4),
                "mxu_warm_wall_s": round(mxu_warm, 4),
                "warm_new_lowerings": mxu_lowerings,
            },
            "violations": len(violations),
        }
    }))
    return 1 if violations else 0


def _validate_corpus(argv) -> int:
    """--validate-corpus: CI gate for the plan sanity checkers
    (sql/validate.py). Plans — without executing — every TPC-H and
    TPC-DS-subset query under plan_validation=rules (per-rule
    validation + determinism double-planning), fragments it with
    fragment-level validation, and prints per-checker violation counts
    plus the compile-churn census. Exit 1 on any violation."""
    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.connectors.tpcds import create_tpcds_connector
    from trino_tpu.engine import LocalQueryRunner, Session
    from trino_tpu.sql.fragmenter import plan_distributed
    from trino_tpu.sql.parser import parse
    from trino_tpu.sql.validate import (
        PlanValidationError,
        check_sql_stability,
        collect_subplan_violations,
        collect_violations,
        shape_census,
    )
    from tests.tpch_queries import QUERIES as TPCH_QUERIES
    from tests.test_tpcds import QUERIES as TPCDS_QUERIES

    def make_runner(catalog, create):
        r = LocalQueryRunner(Session(catalog=catalog, schema="tiny"))
        r.register_catalog(catalog, create())
        r.session.plan_validation = "rules"
        return r

    corpora = [
        ("tpch", make_runner("tpch", create_tpch_connector), TPCH_QUERIES),
        ("tpcds", make_runner("tpcds", create_tpcds_connector),
         TPCDS_QUERIES),
    ]
    per_checker: dict = {}
    total_classes = 0
    failures = 0
    t0 = time.time()
    for label, runner, queries in corpora:
        for qid, sql in sorted(queries.items(), key=lambda kv: str(kv[0])):
            name = f"{label} {qid if isinstance(qid, str) else f'q{qid}'}"
            try:
                check_sql_stability(sql, what=name)
                stmt = parse(sql)
                q = stmt.query if hasattr(stmt, "query") else stmt
                # rules mode: per-rule validation + determinism run
                # fire inside _analyze/optimize and raise on violation
                output = runner._analyze(q)
                subplan = plan_distributed(
                    output, runner.catalogs, target_splits=2,
                    validation="off",
                )
            except PlanValidationError as e:
                failures += 1
                per_checker[e.checker] = per_checker.get(e.checker, 0) + 1
                print(f"bench: {name}: VIOLATION {e}", file=sys.stderr)
                continue
            except Exception as e:
                failures += 1
                per_checker["error"] = per_checker.get("error", 0) + 1
                print(f"bench: {name}: ERROR {type(e).__name__}: {e}",
                      file=sys.stderr)
                continue
            # collect-all pass over the final artifacts so one bad plan
            # reports every checker it trips, not just the first
            found = list(collect_violations(output))
            found += list(collect_subplan_violations(subplan))
            for v in found:
                failures += 1
                per_checker[v.checker] = per_checker.get(v.checker, 0) + 1
                print(f"bench: {name}: VIOLATION [{v.checker}] "
                      f"{v.node_path}: {v.message}", file=sys.stderr)
            n_classes = sum(
                len(shape_census(f.root, runner.catalogs))
                for f in subplan.all_fragments()
            )
            total_classes += n_classes
            print(f"bench: {name}: ok "
                  f"fragments={len(subplan.all_fragments())} "
                  f"expected_xla_lowerings={n_classes}")
    checkers = ("refs", "types", "structure", "exchange_keys",
                "determinism", "error")
    print(json.dumps({
        "validate_corpus": {
            "queries": sum(len(q) for _, _, q in corpora),
            "violations": failures,
            "per_checker": {
                c: per_checker.get(c, 0) for c in checkers
            },
            "expected_xla_lowerings_total": total_classes,
            "wall_s": round(time.time() - t0, 2),
        }
    }))
    return 1 if failures else 0


def _analyze(argv) -> int:
    """--analyze: CI gate for the concurrency soundness plane
    (trino_tpu/analysis/). Statically scans every module in the package
    for lock-order cycles, guarded_by violations, unlocked writes to
    module-level mutable globals, condition-waits while holding another
    lock, non-reentrant re-entry, and thread spawns that bypass the
    registry. Exit 1 on any finding."""
    from trino_tpu.analysis import analyze_package

    t0 = time.time()
    rep = analyze_package()
    for f in rep.findings:
        print(f"bench: ANALYZE-VIOLATION [{f.kind}] {f.file}:{f.line}: "
              f"{f.message}", file=sys.stderr)
    summary = rep.summary()
    summary["wall_s"] = round(time.time() - t0, 2)
    print(json.dumps({"analyze": summary}))
    return 0 if rep.ok else 1


def main() -> None:
    if "--serve-smoke" in sys.argv:
        sys.exit(_serve_smoke(sys.argv))
    if "--serve" in sys.argv:
        sys.exit(_serve(sys.argv))
    if "--chaos-smoke" in sys.argv:
        sys.exit(_chaos_smoke(sys.argv))
    if "--warmup-smoke" in sys.argv:
        sys.exit(_warmup_smoke(sys.argv))
    if "--trace-smoke" in sys.argv:
        sys.exit(_trace_smoke(sys.argv))
    if "--mesh-smoke" in sys.argv:
        sys.exit(_mesh_smoke(sys.argv))
    if "--resident-smoke" in sys.argv:
        sys.exit(_resident_smoke(sys.argv))
    if "--adaptive-smoke" in sys.argv:
        sys.exit(_adaptive_smoke(sys.argv))
    if "--recovery-smoke" in sys.argv:
        sys.exit(_recovery_smoke(sys.argv))
    if "--failover-smoke" in sys.argv:
        sys.exit(_failover_smoke(sys.argv))
    if "--skew-smoke" in sys.argv:
        sys.exit(_skew_smoke(sys.argv))
    if "--multihost-smoke" in sys.argv:
        sys.exit(_multihost_smoke(sys.argv))
    if "--preempt-smoke" in sys.argv:
        sys.exit(_preempt_smoke(sys.argv))
    if "--validate-corpus" in sys.argv:
        sys.exit(_validate_corpus(sys.argv))
    if "--analyze" in sys.argv:
        sys.exit(_analyze(sys.argv))
    if os.environ.get("BENCH_INNER") == "1":
        import jax

        # the CPU-baseline child is demoted by JAX_PLATFORMS (_CPU_ENV)
        rec = run_benches()
        rec["_platform"] = jax.devices()[0].platform
        print(json.dumps(rec))
        return

    t_start = time.time()
    # the driver applies its own outer timeout and the incremental
    # emission keeps the last stdout line parseable whenever the kill
    # lands — so the self-deadline is generous and merely orders work
    # (device configs before CPU baselines, SF-large baselines last)
    deadline = float(os.environ.get("BENCH_DEADLINE", "2700"))
    cfg_timeout = int(os.environ.get("BENCH_CONFIG_TIMEOUT", "1800"))
    cpu_timeout = int(os.environ.get("BENCH_CPU_TIMEOUT", "1800"))
    skip_cpu = os.environ.get("BENCH_SKIP_CPU") == "1"

    def remaining() -> float:
        return deadline - (time.time() - t_start)

    device: dict = {}
    baseline: dict = {}
    cached = _load_cached_baselines()
    gbs = None
    platform = None
    _emit(device, baseline, gbs, cached)  # parseable line from the start

    # fail fast and loud: one bounded preflight; a backend that does not
    # come up, or comes up as anything but a TPU, ends the run with a
    # non-zero exit — a device metric is never printed from a CPU run
    pf_timeouts = [
        int(x) for x in
        os.environ.get("BENCH_PREFLIGHT_TIMEOUTS", "45,75").split(",")
    ]
    pf_platform, pf_tail = _preflight_device(pf_timeouts)
    if pf_platform != "tpu":
        why = (
            "backend init failed preflight: " + " ; ".join(pf_tail)
            if pf_platform is None
            else f"JAX found platform {pf_platform!r}, not a TPU"
        )
        print(f"bench: no TPU — {why}", file=sys.stderr, flush=True)
        sys.exit(1)

    # device configs run as subprocesses BEFORE this process touches
    # jax: a chip belongs to one process at a time, so a parent that had
    # initialized the backend would leave its children without one
    cfgs = _configs()
    for name, sf in cfgs:
        key = f"{name}_sf{sf:g}"
        budget = min(cfg_timeout, remaining() - 20)
        if budget < 60:
            print(f"bench: deadline — skipping {key} and later configs",
                  file=sys.stderr, flush=True)
            break
        secs, plat = _run_one_subprocess(name, sf, {}, int(budget))
        if secs is not None:
            device[key] = secs
            platform = plat or platform
            _emit(device, baseline, gbs, cached)
        # small-SF CPU baselines interleave right behind their device
        # run — they are cheap and give the headline a measured
        # vs_baseline as early as possible. SF-large baselines wait
        # until every device config has had its shot (skipped first).
        if (secs is not None and sf <= 1 and platform not in (None, "cpu")
                and not skip_cpu):
            budget = min(cpu_timeout, remaining() - 20)
            if budget >= 60:
                b, _ = _run_one_subprocess(
                    name, sf, _CPU_ENV,
                    int(budget),
                )
                if b is not None:
                    baseline[key] = b
                    _save_cached_baseline(key, b)
                    _emit(device, baseline, gbs, cached)

    # probe throughput (parent imports jax here — device children done)
    if platform not in (None, "cpu") and remaining() > 60:
        try:
            gbs = probe_gbs()
            _emit(device, baseline, gbs, cached)
        except Exception as ex:
            print(f"bench: probe_gbs skipped ({type(ex).__name__})",
                  file=sys.stderr, flush=True)

    # SF-large CPU baselines last: first to go when budget runs short
    if platform not in (None, "cpu") and not skip_cpu:
        for name, sf in cfgs:
            key = f"{name}_sf{sf:g}"
            if sf <= 1 or key not in device or key in baseline:
                continue
            budget = min(cpu_timeout, remaining() - 20)
            if budget < 120:
                print(f"bench: deadline — skipping cpu baseline for {key}",
                      file=sys.stderr, flush=True)
                continue
            b, _ = _run_one_subprocess(
                name, sf, _CPU_ENV,
                int(budget),
            )
            if b is not None:
                baseline[key] = b
                _save_cached_baseline(key, b)
                _emit(device, baseline, gbs, cached)

    _emit(device, baseline, gbs, cached)


if __name__ == "__main__":
    main()
