"""Process-wide metrics registry.

Analogue of the reference's JMX metrics surface (airlift @Managed beans
exported through the jmx connector / GET /v1/jmx/mbean): named counters,
gauges, and fixed-bucket distributions (CounterStat / DistributionStat /
TimeStat) that subsystems bump, snapshotted as JSON by the coordinator's
`/v1/metrics` endpoint. Counters are monotonically increasing; gauges
are set-to-current; distributions expose count/total/min/max and
p50/p95/p99 quantile estimates.
"""

from __future__ import annotations

import math
import threading
from trino_tpu.analysis.witness import named_condition, named_lock, named_rlock
from typing import Callable, Dict, List, Optional

from trino_tpu.runtime.tracing import running_statement


class Distribution:
    """Fixed-bucket histogram (DistributionStat/TimeStat analogue).

    Buckets are geometric — powers of two over 1e-6..~5e5 in whatever
    unit the caller observes (seconds here) — so one layout serves
    microsecond page pulls and hour-long queries. Quantiles come from
    the bucket upper edge the cumulative count crosses, clamped to the
    exact observed min/max; for a fixed-bucket sketch that bounds the
    error at one bucket width (~2x), which is what p50-vs-p99 gating
    needs. All-zero-cost: add() is two dict-free array ops under the
    registry lock."""

    _LO = 1e-6
    _N = 40  # 1µs * 2^39 ≈ 6.4 days — saturates the top bucket beyond

    __slots__ = ("counts", "count", "total", "min", "max")

    def __init__(self):
        self.counts = [0] * self._N
        self.count = 0
        self.total = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    def add(self, value: float) -> None:
        v = float(value)
        if v <= self._LO:
            idx = 0
        else:
            idx = min(self._N - 1, 1 + int(math.log2(v / self._LO)))
        self.counts[idx] += 1
        self.count += 1
        self.total += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)

    def _edge(self, idx: int) -> float:
        return self._LO * (2.0 ** idx)

    def percentile(self, p: float) -> float:
        if self.count == 0:
            return 0.0
        target = p * self.count
        seen = 0
        for idx, c in enumerate(self.counts):
            seen += c
            if seen >= target:
                hi = min(self._edge(idx), self.max)
                return max(hi, self.min)
        return self.max or 0.0

    def summary(self) -> Dict[str, float]:
        return {
            "count": float(self.count),
            "total": self.total,
            "avg": self.total / self.count if self.count else 0.0,
            "min": self.min or 0.0,
            "max": self.max or 0.0,
            "p50": self.percentile(0.50),
            "p95": self.percentile(0.95),
            "p99": self.percentile(0.99),
        }


class MetricsRegistry:
    def __init__(self):
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, Callable[[], float]] = {}
        self._distributions: Dict[str, Distribution] = {}
        self._lock = named_lock("MetricsRegistry._lock")

    def increment(self, name: str, delta: float = 1.0) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + delta
        # the statement this thread is executing counts what it moved
        account = running_statement()
        if account is not None:
            mine = account.counters
            mine[name] = mine.get(name, 0.0) + delta

    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0.0)

    def register_gauge(self, name: str, fn: Callable[[], float]) -> None:
        """fn is evaluated at snapshot time (@Managed getter analogue)."""
        with self._lock:
            self._gauges[name] = fn

    def observe(self, name: str, value: float) -> None:
        """Record one sample into the named distribution."""
        with self._lock:
            dist = self._distributions.get(name)
            if dist is None:
                dist = self._distributions[name] = Distribution()
            dist.add(value)

    def distribution(self, name: str) -> Optional[Dict[str, float]]:
        with self._lock:
            dist = self._distributions.get(name)
            return dist.summary() if dist is not None else None

    # -- retention ------------------------------------------------------
    #
    # Per-query counters (xla_compiles_by_query.{qid}) would otherwise
    # accumulate one entry per query for the life of the process; the
    # coordinator retires them into the query's final QueryInfo at
    # completion and prunes here, keeping the registry bounded.

    def remove(self, name: str) -> float:
        """Drop one counter, returning its final value (0.0 if absent)."""
        with self._lock:
            return self._counters.pop(name, 0.0)

    def remove_prefix(self, prefix: str) -> Dict[str, float]:
        """Drop every counter and distribution whose name starts with
        `prefix`; returns the removed counters' final values."""
        with self._lock:
            removed = {
                k: self._counters.pop(k)
                for k in [k for k in self._counters if k.startswith(prefix)]
            }
            for k in [k for k in self._distributions
                      if k.startswith(prefix)]:
                del self._distributions[k]
            return removed

    def counter_names(self) -> List[str]:
        with self._lock:
            return list(self._counters)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self._counters)
            gauges = list(self._gauges.items())
            dists = [(n, d.summary()) for n, d in
                     self._distributions.items()]
        for name, summary in dists:
            for stat, v in summary.items():
                out[f"{name}.{stat}"] = v
        for name, fn in gauges:
            try:
                out[name] = float(fn())
            except Exception:
                pass  # a failing gauge must not poison the snapshot
        return out

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._distributions.clear()


# the process singleton (MBeanServer analogue)
METRICS = MetricsRegistry()


# -- per-query compile attribution -------------------------------------
#
# jax.monitoring compile events carry a duration but no originating jit
# name, and naming wrappers per query would split the persistent-cache
# key space (defeating cross-query executable reuse). Instead the
# execution paths bracket their dispatch with set_compile_attribution
# and the listener charges each compile to whichever query id the
# *compiling thread* is running — correct because backend compiles
# happen synchronously on the dispatching thread.
_attribution = threading.local()


def set_compile_attribution(query_id) -> object:
    """Tag this thread's subsequent XLA compiles with `query_id`
    (None to clear). Returns the previous tag so callers can restore
    it in a finally block."""
    prev = getattr(_attribution, "query_id", None)
    _attribution.query_id = query_id
    return prev


def compile_attribution():
    return getattr(_attribution, "query_id", None)


def retire_query_compiles(query_id) -> float:
    """Pull a query's compile-attribution counters out of the registry
    (base id plus every `{qid}r*` QUERY-retry namespace) and return the
    summed count, for retirement into the final QueryInfo. Exact-match
    plus an `r`-suffix prefix so q3 never swallows q30's counters."""
    total = METRICS.remove(f"xla_compiles_by_query.{query_id}")
    total += sum(
        METRICS.remove_prefix(f"xla_compiles_by_query.{query_id}r").values()
    )
    return total


_xla_listener_installed = False


def install_xla_compile_listener() -> bool:
    """Bump the `xla_compiles` counter on every backend compile via
    jax.monitoring. NOTE: this counts ALL compiles in the process —
    jax-internal helper jits (jnp.zeros, barriers) included — so it is a
    visibility counter for spotting churn trends, not a per-query
    cache-miss count; the per-query expected-vs-observed comparison uses
    the shape-class ledger (exec/stats.py), which shares a vocabulary
    with the static census (sql/validate.py). Idempotent; returns False
    when this jax build has no monitoring hooks."""
    global _xla_listener_installed
    if _xla_listener_installed:
        return True
    try:
        from jax import monitoring

        def _on_event(event: str, duration: float, **kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                METRICS.increment("xla_compiles")
                METRICS.observe("xla_compile_duration_s", duration)
                qid = compile_attribution()
                if qid is not None:
                    METRICS.increment(f"xla_compiles_by_query.{qid}")

        monitoring.register_event_duration_secs_listener(_on_event)
    except Exception:
        return False
    _xla_listener_installed = True
    return True
