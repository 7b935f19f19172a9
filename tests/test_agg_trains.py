"""Trains of batches through the bounded-domain aggregation (issue 26):
where the plan bounds the group table and addresses it by slot (the
dense and MXU paths), `HashAggregationOperator` holds incoming batches
and one launch of `_agg_ingest_train` ingests TRAIN_BATCHES of them into
one state. CPU counts and answers only; what a launch costs is a chip
reading (PERF.md section 6)."""

import collections

import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.block import Dictionary
from trino_tpu.connectors.memory import create_memory_connector
from trino_tpu.connectors.spi import ColumnMetadata
from trino_tpu.engine import LocalQueryRunner, Session
from trino_tpu.exec import operators as O
from trino_tpu.exec.operators import (
    AggSpec,
    CollectorSink,
    HashAggregationOperator,
    TableScanOperator,
)
from trino_tpu.runtime.memory import MemoryContext, MemoryPool
from trino_tpu.runtime.metrics import METRICS

BATCH = 256
K1 = ["a", "b", "c"]                     # (3+1) x (4+1) = 20 slots: dense
K2 = ["w", "x", "y", "z"]
K3 = [f"m{i:02d}" for i in range(20)]    # (3+1) x (20+1) = 84 slots: MXU band
COLUMNS = ["k1", "k2", "k3", "v", "u"]
TYPES = [T.VARCHAR, T.VARCHAR, T.VARCHAR, T.BIGINT, T.BIGINT]
DICTS = [Dictionary(K1), Dictionary(K2), Dictionary(K3), None, None]
# full batches and rows of the masked tail batch: 59 = 7 x 8 + 3 leaves a
# short train at 8, an odd one at 2
SCANS = {"tail": (59, 85), "even": (58, 0), "one": (0, 100), "two": (2, 0)}


def make_table(full, tail, seed=26):
    rng = np.random.default_rng(seed)
    n = full * BATCH + tail
    codes = [rng.integers(0, len(d), n).astype(np.int32) for d in (K1, K2, K3)]
    key_valid = [rng.random(n) > 0.1 for _ in codes]          # NULL keys
    v = rng.integers(-10**12, 10**12, n)
    v_valid = rng.random(n) > 0.2                             # NULL arguments
    u = rng.integers(0, 1000, n)
    return [*codes, v, u], [*key_valid, v_valid, None]


@pytest.fixture(scope="module")
def catalog():
    mem = create_memory_connector()
    tables = {}
    for name, (full, tail) in SCANS.items():
        arrays, valids = make_table(full, tail)
        mem.load_table("s", name, [ColumnMetadata(c, t) for c, t in zip(COLUMNS, TYPES)],
                       arrays, valids, DICTS)
        tables[name] = (arrays, valids)
    return mem, tables


def scan_op(mem, table):
    handle = mem.metadata.get_table_handle("s", table)
    splits = mem.split_manager.get_splits(handle, 1)
    return TableScanOperator(mem.page_source, splits, COLUMNS, BATCH)


PATHS = {
    # path: (group channels, aggregates, operator attribute that must be set)
    "dense": ([0, 1], [AggSpec("count_star", None, T.BIGINT), AggSpec("sum", 3, T.BIGINT),
                       AggSpec("min", 3, T.BIGINT), AggSpec("max", 3, T.BIGINT),
                       AggSpec("count", 3, T.BIGINT), AggSpec("avg", 4, T.DOUBLE)],
              "_dense_dims"),
    "mxu": ([0, 2], [AggSpec("count_star", None, T.BIGINT), AggSpec("sum", 3, T.BIGINT),
                     AggSpec("count", 3, T.BIGINT), AggSpec("sum", 4, T.BIGINT)],
            "_mxu_dims"),
}


def aggregate(mem, table, path, step="single", k=None, monkeypatch=None,
              before_batch=None, memory=None):
    """The operator's output rows for one scan, and the counters' deltas."""
    if path == "mxu":
        monkeypatch.setenv("TRINO_TPU_FORCE_MXU", "1")
    if k is not None:
        monkeypatch.setattr(O, "TRAIN_BATCHES", k)
    groups, aggs, attr = PATHS[path]
    agg = HashAggregationOperator(groups, aggs, list(zip(TYPES, DICTS)), step=step,
                                  memory_context=memory)
    assert getattr(agg, attr) is not None and agg._trains
    scan = scan_op(mem, table)
    before = {c: METRICS.counter(c) for c in ("agg_ingest_batches", "agg_ingest_launches")}
    i = 0
    while True:
        batch = scan.get_output()
        if batch is None:
            break
        if before_batch is not None:
            before_batch(agg, i)
        agg.add_input(batch)
        i += 1
    agg.finish()
    sink = CollectorSink()
    sink.add_input(agg.get_output())
    counts = {c: METRICS.counter(c) - before[c] for c in before}
    return sorted(sink.rows(), key=repr), counts


def reference(tables, table, path):
    """Plain python over the loaded arrays: the `single` step's rows."""
    arrays, valids = tables[table]
    groups, aggs, _ = PATHS[path]
    rows = collections.defaultdict(list)
    for i in range(len(arrays[0])):
        key = tuple(DICTS[g].values[arrays[g][i]] if valids[g][i] else None for g in groups)
        rows[key].append(i)
    out = []
    for key, idx in rows.items():
        row = list(key)
        for a in aggs:
            if a.kind == "count_star":
                row.append(len(idx))
                continue
            ok = [i for i in idx if valids[a.arg_channel] is None or valids[a.arg_channel][i]]
            vals = [int(arrays[a.arg_channel][i]) for i in ok]
            if a.kind == "count":
                row.append(len(vals))
            elif not vals:
                row.append(None)
            elif a.kind == "avg":
                row.append(sum(vals) / len(vals))
            else:
                row.append({"sum": sum, "min": min, "max": max}[a.kind](vals))
        out.append(row)
    return sorted(out, key=repr)


def _cache_size(jitted) -> int:
    """Lowerings a jitted function holds."""
    return jitted._cache_size()


@pytest.fixture(scope="module")
def per_batch(catalog):
    """What one launch per batch answers (TRAIN_BATCHES = 1), per case."""
    mem, _tables = catalog
    memo = {}

    def get(table, path, step, monkeypatch):
        if (table, path, step) not in memo:
            memo[table, path, step] = aggregate(mem, table, path, step, 1, monkeypatch)[0]
        return memo[table, path, step]

    return get


@pytest.mark.parametrize("path", sorted(PATHS))
def test_one_launch_per_batch_answers_the_plain_reference(catalog, per_batch, path, monkeypatch):
    _mem, tables = catalog
    assert per_batch("tail", path, "single", monkeypatch) == reference(tables, "tail", path)


@pytest.mark.parametrize("step", ["single", "partial"])
@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("k", [2, 8])
@pytest.mark.parametrize("table", ["tail", "even"])
def test_train_results_equal_per_batch_results(catalog, per_batch, table, k, path, step,
                                               monkeypatch):
    mem, _tables = catalog
    rows, counts = aggregate(mem, table, path, step, k, monkeypatch)
    assert rows == per_batch(table, path, step, monkeypatch)
    full, tail = SCANS[table]
    assert counts["agg_ingest_batches"] == full + bool(tail)
    # the tail batch has another layout and travels alone
    assert counts["agg_ingest_launches"] == -(-full // k) + bool(tail)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_58_batch_scan_is_eight_launches(catalog, path, monkeypatch):
    mem, _tables = catalog
    assert O.TRAIN_BATCHES == 8
    _rows, counts = aggregate(mem, "even", path, monkeypatch=monkeypatch)
    assert counts == {"agg_ingest_batches": 58, "agg_ingest_launches": 8}


@pytest.mark.parametrize("table", ["one", "two"])
def test_short_scans(catalog, per_batch, table, monkeypatch):
    """A one-batch scan goes through the per-batch program, as before."""
    mem, _tables = catalog
    train_runs = _cache_size(O._agg_ingest_train)
    rows, counts = aggregate(mem, table, "dense", monkeypatch=monkeypatch)
    assert rows == per_batch(table, "dense", "single", monkeypatch)
    assert counts["agg_ingest_launches"] == 1
    if table == "one":
        assert _cache_size(O._agg_ingest_train) == train_runs


@pytest.mark.parametrize("path", sorted(PATHS))
def test_at_most_three_ingest_lowerings_and_none_the_second_time(catalog, path, monkeypatch):
    """Whatever the scan's length: the train program, the per-batch
    program for a full batch left alone, the per-batch program for the
    masked tail batch."""
    mem, _tables = catalog
    O._agg_ingest.clear_cache()
    O._agg_ingest_train.clear_cache()
    for _ in range(2):
        # 59 full batches at 2: 29 trains and one full batch alone, then
        # the tail; 58: 29 trains; 2: one train; 1: a masked batch alone
        for table in ("tail", "even", "two", "one"):
            aggregate(mem, table, path, k=2, monkeypatch=monkeypatch)
        assert _cache_size(O._agg_ingest_train) == 1
        assert _cache_size(O._agg_ingest) == 2


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("at", [3, 8, 21])
def test_revocation_in_the_middle_of_a_held_train(catalog, per_batch, path, at, monkeypatch):
    """Revocation flushes what is held before it spills; the spilled
    state comes back at finish."""
    mem, _tables = catalog
    seen = {}

    def revoke(agg, i):
        if i == at:
            seen["held"] = len(agg._held)
            agg._revoke_memory()
            assert agg._held == [] and agg._acc is None and agg._pending == []
            assert agg._spiller.batch_count == 1

    rows, counts = aggregate(mem, "tail", path, "single", 8, monkeypatch, before_batch=revoke)
    assert seen["held"] == at % 8
    assert rows == per_batch("tail", path, "single", monkeypatch)
    assert counts["agg_ingest_batches"] == 60


def test_revocations_from_another_thread_lose_no_batch(catalog, per_batch, monkeypatch):
    """MemoryPool.reserve runs a victim's revoker on the reserving
    thread: another thread revokes all the while batches are being held
    and launched. Every batch is in exactly one launch, whatever the
    interleaving."""
    import sys
    import threading

    mem, _tables = catalog
    stop = threading.Event()
    box = {}

    def revoker():
        while not stop.is_set():
            agg = box.get("agg")
            if agg is not None:
                agg._revoke_memory()

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    thread = threading.Thread(target=revoker, daemon=True)
    thread.start()
    try:
        rows, counts = aggregate(
            mem, "tail", "dense", "single", 8, monkeypatch,
            before_batch=lambda agg, i: box.setdefault("agg", agg))
    finally:
        stop.set()
        thread.join(timeout=30)
        sys.setswitchinterval(was)
    assert not thread.is_alive()
    assert rows == per_batch("tail", "dense", "single", monkeypatch)
    assert counts["agg_ingest_batches"] == 60
    assert 9 <= counts["agg_ingest_launches"] <= 60


def test_held_batches_are_accounted_and_a_full_pool_flushes_them(catalog, per_batch,
                                                                 monkeypatch):
    mem, _tables = catalog
    from trino_tpu.runtime.memory import batch_bytes

    pool = MemoryPool(1 << 30)
    ctx = MemoryContext(pool)
    seen = []

    def look(agg, i):
        if agg._held:
            assert ctx.reserved_bytes >= sum(batch_bytes(b) for b in agg._held)
        seen.append(len(agg._held))

    rows, _ = aggregate(mem, "tail", "dense", "single", 8, monkeypatch,
                        before_batch=look, memory=ctx)
    assert rows == per_batch("tail", "dense", "single", monkeypatch)
    assert max(seen) == 7 and ctx.reserved_bytes == 0
    # a pool that holds three batches and no more: the operator spills
    # itself (flushing the train first) instead of failing
    one = batch_bytes(scan_op(mem, "tail").get_output())
    small = MemoryContext(MemoryPool(3 * one + one // 2))
    held = []
    rows, _ = aggregate(mem, "tail", "dense", "single", 8, monkeypatch,
                        before_batch=lambda agg, i: held.append(len(agg._held)),
                        memory=small)
    assert rows == per_batch("tail", "dense", "single", monkeypatch)
    assert 0 < max(held) < 7


# -- through SQL, and the paths that keep one launch per batch -----------------------


@pytest.fixture(scope="module")
def runner(catalog):
    mem, _tables = catalog
    r = LocalQueryRunner(Session(catalog="memory", schema="s", batch_rows=BATCH))
    r.register_catalog("memory", mem)
    return r


def launches(runner, sql):
    before = {c: METRICS.counter(c) for c in ("agg_ingest_batches", "agg_ingest_launches")}
    rows = runner.execute(sql).rows
    return rows, {c: METRICS.counter(c) - before[c] for c in before}


def test_sql_statement_rides_trains_and_compiles_nothing_the_second_time(runner, catalog):
    _mem, tables = catalog
    sql = ("select k1, k2, count(*), sum(v), min(v), max(v), count(v), avg(u) "
           "from tail group by 1, 2")
    rows, counts = launches(runner, sql)
    assert sorted(rows, key=repr) == reference(tables, "tail", "dense")
    assert counts == {"agg_ingest_batches": 60, "agg_ingest_launches": 9}
    sizes = _cache_size(O._agg_ingest), _cache_size(O._agg_ingest_train)
    compiles = METRICS.counter("xla_compiles")
    again, counts = launches(runner, sql)
    assert again == rows and counts["agg_ingest_launches"] == 9
    assert (_cache_size(O._agg_ingest), _cache_size(O._agg_ingest_train)) == sizes
    assert METRICS.counter("xla_compiles") == compiles


@pytest.mark.parametrize("sql,ingest", [
    # the sort path: an unbounded key, `ovf` read one batch later
    ("select u, count(*), sum(v) from tail group by 1", True),
    # a bounded key the CPU sends down the sort path (84 slots, no MXU)
    ("select k1, k3, count(*), sum(v) from tail group by 1, 2", True),
    # the global path: `_update`, no `_agg_ingest` at all
    ("select count(*), sum(v), min(v) from tail", False),
])
def test_other_paths_still_launch_once_per_batch(runner, sql, ingest):
    O._agg_ingest_train.clear_cache()
    rows, counts = launches(runner, sql)
    assert rows
    assert counts["agg_ingest_batches"] == (60 if ingest else 0)
    assert counts["agg_ingest_launches"] == counts["agg_ingest_batches"]
    assert _cache_size(O._agg_ingest_train) == 0
