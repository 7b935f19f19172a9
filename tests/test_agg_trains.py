"""Trains of batches through the bounded-domain aggregation (issue 26):
where the plan bounds the group table and addresses it by slot (the
dense and MXU paths), `HashAggregationOperator` holds incoming batches
and one launch of `_agg_ingest_train` ingests TRAIN_BATCHES of them into
one state. CPU counts and answers only; what a launch costs is a chip
reading (PERF.md section 6)."""

import collections
import types

import jax
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.block import Dictionary
from trino_tpu.connectors.memory import create_memory_connector
from trino_tpu.connectors.spi import ColumnMetadata
from trino_tpu.engine import LocalQueryRunner, Session
from trino_tpu.exec import operators as O
from trino_tpu.ops import groupby as G
from trino_tpu.ops.int128 import from_python, to_python
from trino_tpu.exec.operators import (
    AggSpec,
    HashAggregationOperator,
    TableScanOperator,
)
from trino_tpu.runtime.memory import MemoryContext, MemoryPool
from trino_tpu.runtime.metrics import METRICS

BATCH = 256
K1 = ["a", "b", "c"]                     # (3+1) x (4+1) = 20 slots: dense
K2 = ["w", "x", "y", "z"]
K3 = [f"m{i:02d}" for i in range(20)]    # (3+1) x (20+1) = 84 slots: MXU band
K4 = ["F", "O"]                          # (3+1) x (2+1) = 12 slots: Q1's
D1, D2 = T.decimal(34, 4), T.decimal(38, 6)
COLUMNS = ["k1", "k2", "k3", "v", "u", "k4", "d1", "d2", "t"]
TYPES = [T.VARCHAR, T.VARCHAR, T.VARCHAR, T.BIGINT, T.BIGINT, T.VARCHAR, D1, D2, T.BIGINT]
DICTS = [Dictionary(K1), Dictionary(K2), Dictionary(K3), None, None, Dictionary(K4),
         None, None, None]
# full batches and rows of the masked tail batch: 59 = 7 x 8 + 3 leaves a
# short train at 8, an odd one at 2
SCANS = {"tail": (59, 85), "even": (58, 0), "one": (0, 100), "two": (2, 0)}


def make_table(full, tail, seed=26):
    """(arrays, valids) as the plain reference reads them: a long
    decimal is its unscaled python integer."""
    rng = np.random.default_rng(seed)
    n = full * BATCH + tail
    codes = [rng.integers(0, len(d), n).astype(np.int32) for d in (K1, K2, K3)]
    key_valid = [rng.random(n) > 0.1 for _ in codes]          # NULL keys
    v = rng.integers(-10**12, 10**12, n)
    v_valid = rng.random(n) > 0.2                             # NULL arguments
    u = rng.integers(0, 1000, n)
    k4 = rng.integers(0, len(K4), n).astype(np.int32)
    # past 2^64 and 2^96 on both sides of zero: every limb slot carries
    d1 = np.array([int(x) * 10**15 + int(y) for x, y in
                   zip(rng.integers(-10**15, 10**15, n), rng.integers(0, 10**15, n))],
                  dtype=object)
    d2 = np.array([int(x) * 10**18 * (-1) ** int(i) for i, x in
                   enumerate(rng.integers(0, 10**15, n))], dtype=object)
    t = rng.integers(-50, 50, n)
    return ([*codes, v, u, k4, d1, d2, t],
            [*key_valid, v_valid, None, rng.random(n) > 0.1, rng.random(n) > 0.3,
             rng.random(n) > 0.05, rng.random(n) > 0.5])


def physical(array, type_):
    """A long decimal as the connector stores it: (signed hi, lo) int64."""
    if not type_.is_long_decimal:
        return array
    pairs = np.array([from_python(int(x)) for x in array], dtype=object)
    return np.stack([pairs[:, 0].astype(np.int64),
                     (pairs[:, 1] % 2**64).astype(np.uint64).view(np.int64)], axis=1)


@pytest.fixture(scope="module")
def catalog():
    mem = create_memory_connector()
    tables = {}
    for name, (full, tail) in SCANS.items():
        arrays, valids = make_table(full, tail)
        mem.load_table("s", name, [ColumnMetadata(c, t) for c, t in zip(COLUMNS, TYPES)],
                       [physical(a, t) for a, t in zip(arrays, TYPES)], valids, DICTS)
        tables[name] = (arrays, valids)
    return mem, tables


def scan_op(mem, table):
    handle = mem.metadata.get_table_handle("s", table)
    splits = mem.split_manager.get_splits(handle, 1)
    return TableScanOperator(mem.page_source, splits, COLUMNS, BATCH)


Q1_AGGS = [AggSpec("sum", 3, T.BIGINT), AggSpec("sum", 4, T.BIGINT),
           AggSpec("sum", 6, T.decimal(38, 4)), AggSpec("sum", 7, T.decimal(38, 6)),
           AggSpec("avg", 3, T.DOUBLE), AggSpec("avg", 4, T.DOUBLE),
           AggSpec("avg", 8, T.DOUBLE), AggSpec("count_star", None, T.BIGINT)]
PATHS = {
    # path: (group channels, aggregates, operator attribute that must be
    # set, whether the test takes the MXU route the CPU would not)
    "dense": ([0, 1], [AggSpec("count_star", None, T.BIGINT), AggSpec("sum", 3, T.BIGINT),
                       AggSpec("min", 3, T.BIGINT), AggSpec("max", 3, T.BIGINT),
                       AggSpec("count", 3, T.BIGINT), AggSpec("avg", 4, T.DOUBLE)],
              "_dense_dims", False),
    "mxu": ([0, 2], [AggSpec("count_star", None, T.BIGINT), AggSpec("sum", 3, T.BIGINT),
                     AggSpec("count", 3, T.BIGINT), AggSpec("sum", 4, T.BIGINT)],
            "_mxu_dims", True),
    # Q1's shape (issue 31): 12 slots, 14 value slots, every one a sum or
    # a count of an int64, by the dense route and by the MXU route
    "q1-dense": ([0, 5], Q1_AGGS, "_dense_dims", False),
    "q1-mxu": ([0, 5], Q1_AGGS, "_mxu_dims", True),
}


def exact_rows(batch):
    """`CollectorSink.rows()` with a long decimal as its exact unscaled
    integer (rows() divides by the scale in floating point)."""
    host = jax.device_get(batch)
    live = np.asarray(host.live_mask())
    cols = []
    for c in host.columns:
        if c.type.is_long_decimal:
            cols.append([
                to_python(int(h), int(lo)) if ok else None
                for (h, lo), ok, keep in zip(np.asarray(c.data), np.asarray(c.valid_mask()), live)
                if keep])
        else:
            cols.append(c.to_pylist(live=live))
    return [list(row) for row in zip(*cols)]


def aggregate(mem, table, path, step="single", k=None, monkeypatch=None,
              before_batch=None, memory=None):
    """The operator's output rows for one scan, and the counters' deltas."""
    groups, aggs, attr, force_mxu = PATHS[path]
    if force_mxu:
        monkeypatch.setenv("TRINO_TPU_FORCE_MXU", "1")
    if k is not None:
        monkeypatch.setattr(O, "TRAIN_BATCHES", k)
    agg = HashAggregationOperator(groups, aggs, list(zip(TYPES, DICTS)), step=step,
                                  memory_context=memory)
    assert getattr(agg, attr) is not None and agg._trains
    scan = scan_op(mem, table)
    names = ("agg_ingest_batches", "agg_ingest_launches",
             "agg_ingest_path.dense", "agg_ingest_path.mxu", "agg_ingest_path.sort")
    before = {c: METRICS.counter(c) for c in names}
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
    counts = {c: METRICS.counter(c) - before[c] for c in names}
    # every batch counts under the route the operator took, and no other
    route = {"_dense_dims": "dense", "_mxu_dims": "mxu"}[attr]
    for r in ("dense", "mxu", "sort"):
        assert counts.pop("agg_ingest_path." + r) == (
            counts["agg_ingest_batches"] if r == route else 0)
    return sorted(exact_rows(agg.get_output()), key=repr), counts


def reference(tables, table, path):
    """Plain python over the loaded arrays: the `single` step's rows."""
    arrays, valids = tables[table]
    groups, aggs = PATHS[path][:2]
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
                # avg(bigint) is the exact sum divided as a double
                row.append(float(sum(vals)) / len(vals))
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


# -- which reduce a bounded domain gets (issue 31) -----------------------------------

G3_KEYS = [Dictionary([f"{i}.{j}" for j in range(d)]) for i, d in enumerate((7, 4, 3))]
ONE = Dictionary(["only"])
CHOICES = {
    # case: (key (type, dictionary)s, value (type, dictionary)s, aggregates over
    # the values by position, the route with the MXU kernel at hand, without)
    "q1": ([(T.VARCHAR, DICTS[0]), (T.VARCHAR, DICTS[5])],
           [(T.BIGINT, None), (T.BIGINT, None), (D1, None), (D2, None), (T.BIGINT, None)],
           [("sum", 0), ("sum", 1), ("sum", 2), ("sum", 3), ("avg", 0), ("avg", 1),
            ("avg", 4), ("count_star", None)], "mxu", "dense"),
    "min-max": ([(T.VARCHAR, DICTS[0]), (T.VARCHAR, DICTS[5])], [(T.BIGINT, None)],
                [("sum", 0), ("min", 0), ("max", 0)], "dense", "dense"),
    "float-sum": ([(T.VARCHAR, DICTS[0]), (T.VARCHAR, DICTS[5])], [(T.DOUBLE, None)],
                  [("sum", 0), ("count_star", None)], "dense", "dense"),
    # (1+1) x (1+1) slots x one value slot: a handful of reductions
    # against a plane and a kernel launch
    "four-slots-one-value": ([(T.VARCHAR, ONE), (T.VARCHAR, ONE)], [],
                             [("count_star", None)], "dense", "dense"),
    "g3": ([(T.VARCHAR, d) for d in G3_KEYS], [(T.decimal(12, 2), None)],
           [("count_star", None), ("sum", 0)], "mxu", "sort"),
    "g3-float": ([(T.VARCHAR, d) for d in G3_KEYS], [(T.DOUBLE, None)],
                 [("sum", 0)], "sort", "sort"),
    "unbounded": ([(T.BIGINT, None)], [(T.BIGINT, None)],
                  [("count_star", None), ("sum", 0)], "sort", "sort"),
}


def choice_case(case):
    keys, vals, aggs, with_mxu, without = CHOICES[case]
    schema = keys + vals
    specs = [AggSpec(kind, None if pos is None else len(keys) + pos,
                     T.BIGINT if pos is None else vals[pos][0]) for kind, pos in aggs]
    return list(range(len(keys))), specs, schema, with_mxu, without


@pytest.mark.parametrize("mxu", [True, False], ids=["forced", "cpu"])
@pytest.mark.parametrize("case", sorted(CHOICES))
def test_the_operator_asks_the_chooser(case, mxu, monkeypatch):
    groups, specs, schema, with_mxu, without = choice_case(case)
    monkeypatch.setenv("TRINO_TPU_FORCE_MXU", "1" if mxu else "0")
    agg = HashAggregationOperator(groups, specs, schema)
    want = with_mxu if mxu else without
    assert agg._path == want
    assert (agg._dense_dims is not None) == (want == "dense")
    assert (agg._mxu_dims is not None) == (want == "mxu")
    assert agg._trains == (want != "sort")


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("case", sorted(CHOICES))
def test_the_mesh_plane_asks_the_same_chooser(case, platform):
    """`_bounded_reduce` of the mesh plane answers what the operator
    does, but that its dense reduce folds sums and counts of integers
    only: a min/max or a float sum sorts there."""
    from trino_tpu.block import Column, RelBatch
    from trino_tpu.parallel.mesh_plan import _FragVisitor

    groups, specs, schema, with_mxu, without = choice_case(case)
    device = types.SimpleNamespace(platform=platform)
    ex = types.SimpleNamespace(mesh=types.SimpleNamespace(devices=np.array([device])))
    visitor = _FragVisitor(ex, 0, {}, {}, {}, [])
    n = 16
    batch = RelBatch([
        Column(t, np.zeros((n, 2) if t.is_long_decimal else n, t.dtype), None, d)
        for t, d in schema], None)
    node = types.SimpleNamespace(group_channels=groups)
    _live, values, _vvalids, reds = visitor._batch_agg_inputs(specs, batch)
    reduce, dims = visitor._bounded_reduce(node, batch, values, reds)
    want = with_mxu if platform == "tpu" else without
    if want == "dense" and case in ("min-max", "float-sum"):
        want = "sort"
    assert reduce is {"dense": G.dense_group_reduce, "mxu": G.mxu_group_reduce,
                      "sort": None}[want]
    assert (dims is None) == (want == "sort")
    if want != "sort":
        assert dims == tuple(len(d) for _t, d in schema[:len(groups)])


@pytest.mark.parametrize("table", sorted(SCANS))
@pytest.mark.parametrize("path", ["q1-dense", "q1-mxu"])
def test_q1_shape_equals_the_plain_reference_by_both_routes(catalog, table, path, monkeypatch):
    mem, tables = catalog
    rows, counts = aggregate(mem, table, path, monkeypatch=monkeypatch)
    assert rows == reference(tables, table, path)
    full, tail = SCANS[table]
    assert counts["agg_ingest_batches"] == full + bool(tail)
