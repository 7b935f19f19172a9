"""Layer parse / plan / cache: plan-cache hits over hits + misses inside
the window, %. Program counters `plan_cache.hits` / `.misses`."""


def read(run):
    hits = run.counters["plan_cache.hits"]
    total = hits + run.counters["plan_cache.misses"]
    return 100.0 * hits / total if total else None
