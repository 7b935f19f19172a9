"""Operator microbenchmarks — the JMH-class analogue (SURVEY.md §6:
BenchmarkGroupByHash, BenchmarkHashAndStreamingAggregationOperators,
HashBuildAndJoinBenchmark, BenchmarkPageProcessor).

Each benchmark jits the kernel under test, prewarm-compiles, then
measures steady-state device wall-clock with a forced host sync, and
prints one JSON line: {"bench": ..., "rows": N, "ms": ..., "mrows_s": ...}.

Usage: python benchmarks/micro.py [--rows 4000000] [--filter groupby]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402


def _measure(fn, *args, reps: int = 20):
    """Steady-state per-call device time by slope: dispatch K calls and
    sync ONCE (the TPU stream executes them in order), so the cost of
    the host read-back — which would otherwise be billed to every call
    — is paid once and cancelled out by the two-point fit.

    Robustness (the round-1 harness printed ms=0.0 when tk <= t1): take
    the MEDIAN of several slope samples, and when the spread is inside
    measurement noise, widen the rep count until the K-run batch costs
    at least ~4x the single run; if the slope still degenerates, fall
    back to the fully-synced per-call time (an upper bound that includes
    one round trip — honest, if pessimistic)."""
    import statistics

    import jax

    def force(out):
        # a data fetch is the synchronisation point that waits for
        # execution whatever the backend does with block_until_ready
        leaf = jax.tree_util.tree_leaves(out)[0]
        np.asarray(leaf)

    force(fn(*args))  # compile

    def timed(k):
        t0 = time.perf_counter()
        out = None
        for _ in range(k):
            out = fn(*args)
        force(out)
        return time.perf_counter() - t0

    k = reps
    for _ in range(4):
        slopes = []
        for _ in range(5):
            t1 = timed(1)
            tk = timed(k)
            slopes.append((tk - t1) / (k - 1))
        slope = statistics.median(slopes)
        t1_med = statistics.median(timed(1) for _ in range(3))
        # sanity: the batch must dominate the single call, else the
        # subtraction is noise-vs-noise
        if slope > 0 and slope * (k - 1) >= 3 * t1_med:
            return slope
        k *= 4
    # degenerate (kernel ~free relative to RTT jitter): report the
    # fully-synced per-call time instead of a fabricated slope
    return statistics.median(timed(1) for _ in range(5))


def _groupby_sort_bench(n: int, n_groups: int, capacity: int):
    import jax.numpy as jnp

    from trino_tpu.ops.groupby import sort_group_reduce

    rng = np.random.default_rng(0)
    keys = [jnp.asarray(rng.integers(0, n_groups, n).astype(np.int64))]
    valids = [jnp.ones(n, dtype=jnp.bool_)]
    live = jnp.ones(n, dtype=jnp.bool_)
    values = [jnp.asarray(rng.integers(0, 10**6, n).astype(np.int64))]

    def run():
        return sort_group_reduce(
            tuple(keys), tuple(valids), live, tuple(values), (None,),
            ("sum",), capacity,
        )

    return _measure(run)


def bench_groupby_sort(n: int):
    """sort_group_reduce, low cardinality (1k groups) — the single-device
    aggregation hot path (GroupByHash analogue)."""
    return _groupby_sort_bench(n, 1000, 2048)


def bench_groupby_sort_100k(n: int):
    """sort_group_reduce at high cardinality (100k groups) — the BIGINT
    group-key path (Q3/Q18 shape; MultiChannelGroupByHash.java:264).
    Capacity = bucket_capacity(100k), the engine's steady-state choice."""
    return _groupby_sort_bench(n, 100_000, 1 << 17)


def bench_groupby_mxu(n: int):
    """Pallas MXU one-hot contraction grouped sum (ops/mxu_groupby.py)."""
    import jax
    import jax.numpy as jnp

    from trino_tpu.ops.mxu_groupby import grouped_sum_mxu

    rng = np.random.default_rng(0)
    gid = jnp.asarray(rng.integers(0, 1000, n, dtype=np.int32))
    live = jnp.ones(n, dtype=jnp.bool_)
    values = (jnp.asarray(rng.integers(0, 10**6, n).astype(np.int64)),)
    interp = jax.default_backend() != "tpu"

    def run():
        return grouped_sum_mxu(gid, values, live, 1000, interpret=interp)

    return _measure(run)


def bench_join_probe(n: int):
    """Hash-join build + probe (PagesHash/LookupJoin analogue)."""
    import jax.numpy as jnp

    from trino_tpu.ops import join as J

    rng = np.random.default_rng(0)
    build_n = max(n // 8, 1024)
    bkeys = [jnp.asarray(np.arange(build_n, dtype=np.int64))]
    bvalids = [jnp.ones(build_n, dtype=jnp.bool_)]
    blive = jnp.ones(build_n, dtype=jnp.bool_)
    pkeys = [jnp.asarray(rng.integers(0, build_n * 2, n).astype(np.int64))]
    pvalids = [jnp.ones(n, dtype=jnp.bool_)]
    plive = jnp.ones(n, dtype=jnp.bool_)

    lookup = J.build_lookup(bkeys, bvalids, blive)

    def run():
        return J.probe_counts(lookup, pkeys, pvalids, plive)

    return _measure(run)


def bench_filter_project(n: int):
    """Fused filter + arithmetic projection (PageProcessor analogue)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 10**6, n).astype(np.int64))
    b = jnp.asarray(rng.integers(1, 100, n).astype(np.int64))

    @jax.jit
    def run(a, b):
        live = (a % 7 != 0) & (b > 10)
        x = a * (100 - b)
        y = x * (100 + b)
        return (
            jnp.sum(jnp.where(live, x, 0)),
            jnp.sum(jnp.where(live, y, 0)),
        )

    return _measure(run, a, b)


def bench_topn(n: int):
    """TopN via sort_order + slice (TopNOperator analogue)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.integers(0, 10**9, n).astype(np.int64))

    @jax.jit
    def run(v):
        return jax.lax.top_k(v, 100)

    return _measure(run, v)


BENCHES = {
    "groupby_sort": bench_groupby_sort,
    "groupby_sort_100k": bench_groupby_sort_100k,
    "groupby_mxu": bench_groupby_mxu,
    "join_probe": bench_join_probe,
    "filter_project": bench_filter_project,
    "topn": bench_topn,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4_000_000)
    ap.add_argument("--filter", type=str, default="")
    args = ap.parse_args()

    import jax

    for name, fn in BENCHES.items():
        if args.filter and args.filter not in name:
            continue
        try:
            secs = fn(args.rows)
            print(
                json.dumps(
                    {
                        "bench": name,
                        "rows": args.rows,
                        "ms": round(secs * 1000, 3),
                        "mrows_s": round(args.rows / secs / 1e6, 1),
                        "backend": jax.default_backend(),
                    }
                ),
                flush=True,
            )
        except Exception as ex:
            print(
                json.dumps(
                    {"bench": name,
                     "error": f"{type(ex).__name__}: {ex}"[:160]}
                ),
                flush=True,
            )


if __name__ == "__main__":
    main()
