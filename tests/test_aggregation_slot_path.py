"""A count by an integer key whose exact range the plan knows, past what
the MXU reduce takes, through `HashAggregationOperator`'s slot path
(issue 45): trains of batches into one scatter-addressed table, the
trains' states added slot for slot, no merge. Through SQL over the
memory connector (whose statistics are exact) and at the operator, with
its wire format and its spill. CPU counts and answers only; what any of
it costs is a chip reading (PERF.md section 6, PR 45)."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.block import Column, Dictionary, RelBatch
from trino_tpu.connectors.memory import create_memory_connector
from trino_tpu.connectors.spi import ColumnMetadata
from trino_tpu.engine import LocalQueryRunner, Session
from trino_tpu.exec import operators as O
from trino_tpu.exec.operators import AggSpec, HashAggregationOperator, partial_output_schema
from trino_tpu.ops import groupby as G
from trino_tpu.runtime.metrics import METRICS

BATCH = 1024
ROWS = 19 * BATCH + 300          # 19 full batches (8 + 8 + 3) and a masked tail
LOW, KEYS = 1_000_000, 5_000     # some 5,000 values from a low end that is not 0
REGIONS = ["east", "north", "west"]
COUNTERS = ("agg_ingest_batches", "agg_ingest_launches", "agg_merge_launches",
            "agg_ingest_path.dense", "agg_ingest_path.mxu", "agg_ingest_path.slot",
            "agg_ingest_path.sort", "agg_key_bound.range", "agg_key_bound.dictionary",
            "agg_key_bound.none", "agg_unordered_input.batches")


def moved(fn):
    before = {k: METRICS.counter(k) for k in COUNTERS}
    out = fn()
    return out, {k: METRICS.counter(k) - v for k, v in before.items()
                 if METRICS.counter(k) != v}


def make_table(seed=45):
    """k in no order with NULLs; q NULL in three rows of five and in
    every row of the keys that are multiples of 7 (groups that exist
    with count(q) = 0); v a value; r a dictionary key."""
    rng = np.random.default_rng(seed)
    k = rng.integers(LOW, LOW + KEYS, ROWS)
    k_valid = rng.random(ROWS) > 0.02
    q = rng.integers(-100, 100, ROWS)
    q_valid = (rng.random(ROWS) > 0.6) & (k % 7 != 0)
    v = rng.integers(0, 1000, ROWS)
    r = rng.integers(0, len(REGIONS), ROWS).astype(np.int32)
    return {"k": (k, k_valid), "q": (q, q_valid), "v": (v, None), "r": (r, None)}


TABLE = make_table()
COLUMNS = [ColumnMetadata("k", T.BIGINT), ColumnMetadata("q", T.BIGINT),
           ColumnMetadata("v", T.BIGINT), ColumnMetadata("r", T.VARCHAR)]
SCHEMA = [(T.BIGINT, None), (T.BIGINT, None), (T.BIGINT, None),
          (T.VARCHAR, Dictionary(REGIONS))]


def counted(keys=("k",), where=None):
    """{key tuple: [count(*), count(q), sum(v)]} by python's own counting."""
    want = collections.defaultdict(lambda: [0, 0, 0])
    for i in range(ROWS):
        if where is not None and not where(i):
            continue
        key = []
        for name in keys:
            data, valid = TABLE[name]
            value = None if valid is not None and not valid[i] else int(data[i])
            key.append(REGIONS[value] if name == "r" and value is not None else value)
        g = want[tuple(key)]
        g[0] += 1
        g[1] += int(TABLE["q"][1][i])
        g[2] += int(TABLE["v"][0][i])
    return want


@pytest.fixture(scope="module")
def runner():
    mem = create_memory_connector()
    mem.load_table("s", "t", COLUMNS, [TABLE[c.name][0] for c in COLUMNS],
                   [TABLE[c.name][1] for c in COLUMNS], [d for _, d in SCHEMA])
    r = LocalQueryRunner(Session(catalog="memory", schema="s", batch_rows=BATCH))
    r.register_catalog("memory", mem)
    return r


def aggregate_lines(runner, sql):
    text = runner.execute("explain " + sql).rows[0][0]
    return [line.strip() for line in text.splitlines() if line.strip().startswith("Aggregate")]


def as_set(rows):
    return sorted((tuple(r) for r in rows), key=repr)


# -- whole statements ---------------------------------------------------------------------------

def test_a_count_by_a_key_of_5000_values_takes_the_slot_path_and_no_merge(runner):
    """count(*) and count(nullable) together, twenty batches: three
    trains and the tail batch alone, their states added; what the sort
    path answers for the same rows (`k + 0` has no range to give) and
    what python counts."""
    sql = "select k, count(*), count(q) from t group by 1"
    assert aggregate_lines(runner, sql)[0].endswith(
        f"key_ranges=[({LOW}, {int(TABLE['k'][0][TABLE['k'][1]].max())})]")
    result, counts = moved(lambda: runner.execute(sql))
    assert counts == {"agg_ingest_batches": 20, "agg_ingest_launches": 4,
                      "agg_ingest_path.slot": 20, "agg_key_bound.range": 1}
    account = result.stats["account"]
    assert account["c.agg_ingest_path.slot"] == 20 and account["c.agg_key_bound.range"] == 1
    assert "s.agg.ingest_overflow.n" not in account and "s.agg.merge_overflow.n" not in account
    want = counted()
    assert as_set(result.rows) == as_set((*k, g[0], g[1]) for k, g in want.items())
    assert sum(1 for r in result.rows if r[2] == 0) > 500          # they exist, with 0
    assert any(r[0] is None for r in result.rows)                 # the NULL key's group
    plain_sql = "select k + 0, count(*), count(q) from t group by 1"
    assert not any("key_ranges" in line for line in aggregate_lines(runner, plain_sql))
    plain, counts = moved(lambda: runner.execute(plain_sql))
    assert counts["agg_ingest_path.sort"] == 20 and counts["agg_merge_launches"] >= 1
    assert counts["agg_unordered_input.batches"] == 20
    assert as_set(plain.rows) == as_set(result.rows)


def test_a_sum_beside_the_count_keeps_the_sort_path(runner):
    sql = "select k, count(*), sum(v) from t group by 1"
    assert not any("key_ranges" in line for line in aggregate_lines(runner, sql))
    result, counts = moved(lambda: runner.execute(sql))
    assert counts["agg_ingest_path.sort"] == 20 and counts["agg_key_bound.none"] == 1
    assert "agg_ingest_path.slot" not in counts
    assert as_set(result.rows) == as_set((*k, g[0], g[2]) for k, g in counted().items())


def test_a_dictionary_key_beside_the_integer_key_is_one_mixed_radix_table(runner):
    sql = "select r, k, count(q) from t group by 1, 2"
    result, counts = moved(lambda: runner.execute(sql))
    assert counts["agg_ingest_path.slot"] == 20 and counts["agg_key_bound.range"] == 1
    assert "agg_merge_launches" not in counts
    assert as_set(result.rows) == as_set(
        (*k, g[1]) for k, g in counted(("r", "k")).items())


def test_the_counts_feed_a_join_and_an_aggregation_over_it(runner):
    """Q13's shape: the counts a key, slot-addressed and not dense, are
    a join's build side and are aggregated again."""
    sql = ("select n, count(*) from (select k, count(q) n from t group by 1) c "
           "join (select distinct k from t where v < 500) d on c.k = d.k group by 1")
    result, counts = moved(lambda: runner.execute(sql))
    assert counts["agg_ingest_path.slot"] == 20
    per_key = {k[0]: g[1] for k, g in counted().items() if k[0] is not None}
    kept = {k[0] for k in counted(where=lambda i: TABLE["v"][0][i] < 500) if k[0] is not None}
    want = collections.Counter(per_key[k] for k in kept)
    assert as_set(result.rows) == as_set(want.items())


def test_a_count_of_few_rows_into_many_slots_keeps_the_sort_path(runner):
    """The plan hands a range past the MXU limit on only where the
    estimate gives a row to four slots: a table of millions of slots is
    zeroed, folded and handed on whatever it holds."""
    from trino_tpu.sql import plan as P
    from trino_tpu.sql.stats import ColStats, PlanStats, group_key_ranges

    fields = (P.Field("k", T.BIGINT),)
    child = P.ValuesNode(rows=(), fields=fields)
    node = P.AggregateNode(child, (0,), (P.AggCall("count_star", None, T.BIGINT),),
                           fields + (P.Field("n", T.BIGINT),))
    key = ColStats(low=1.0, high=1_500_000.0, exact=True)
    assert group_key_ranges(node, PlanStats(15_000_000.0, {0: key})) == ((1, 1_500_000),)
    assert group_key_ranges(node, PlanStats(375_001.0, {0: key})) == ((1, 1_500_000),)
    assert group_key_ranges(node, PlanStats(300_000.0, {0: key})) is None
    # under the MXU limit the estimate is not looked at, as before
    year = ColStats(low=1992.0, high=1998.0, exact=True)
    assert group_key_ranges(node, PlanStats(1.0, {0: year})) == ((1992, 1998),)
    # a sum, a distinct count, or a domain past the slot limit: no range
    wide = ColStats(low=1.0, high=float(G.SLOT_MAX_SLOTS), exact=True)
    assert group_key_ranges(node, PlanStats(1e9, {0: wide})) is None
    for call in (P.AggCall("sum", 0, T.BIGINT), P.AggCall("count", 0, T.BIGINT, distinct=True)):
        summed = P.AggregateNode(child, (0,), (node.aggs[0], call),
                                 fields + (P.Field("n", T.BIGINT), P.Field("s", T.BIGINT)))
        assert group_key_ranges(summed, PlanStats(15_000_000.0, {0: key})) is None


# -- the operator -------------------------------------------------------------------------------

COUNTS = [AggSpec("count_star", None, T.BIGINT), AggSpec("count", 1, T.BIGINT)]
HIGH = LOW + KEYS - 1


def batches():
    out = []
    for at in range(0, ROWS, BATCH):
        n = min(BATCH, ROWS - at)
        cols = []
        for (name, (t, d)) in zip(("k", "q", "v", "r"), SCHEMA):
            data, valid = TABLE[name]
            pad = np.zeros(BATCH, data.dtype)
            pad[:n] = data[at:at + n]
            ok = None
            if valid is not None:
                ok = np.zeros(BATCH, bool)
                ok[:n] = valid[at:at + n]
                ok = jnp.asarray(ok)
            cols.append(Column(t, jnp.asarray(pad), ok, d))
        out.append(RelBatch(cols, None if n == BATCH else jnp.asarray(np.arange(BATCH) < n)))
    return out


def run(key_ranges=((LOW, HIGH),), before_batch=None, rows=None):
    agg = HashAggregationOperator([0], COUNTS, SCHEMA, key_ranges=key_ranges)
    for i, b in enumerate(rows or batches()):
        if before_batch is not None:
            before_batch(agg, i)
        agg.add_input(b)
    agg.finish()
    return agg, agg.get_output()


def rows_of(batch):
    host = jax.device_get(batch)
    live = np.asarray(host.live_mask())
    return as_set(zip(*(c.to_pylist(live=live) for c in host.columns)))


WANT = as_set((*k, g[0], g[1]) for k, g in counted().items())


def test_the_trains_states_are_added_slot_for_slot(monkeypatch):
    """Four launches (8 + 8 + 3 batches and the tail alone) leave ONE
    state: three additions, no merge program, the table as wide as the
    domain and emitted with its `used` mask."""
    adds, merges = [], []
    inner_add, inner_merge = O._add_slot_states, O._merge_group_states
    monkeypatch.setattr(O, "_add_slot_states", lambda a, b: adds.append(1) or inner_add(a, b))
    monkeypatch.setattr(O, "_merge_group_states",
                        lambda *a, **k: merges.append(1) or inner_merge(*a, **k))
    (agg, out), counts = moved(run)
    assert agg._path == "slot" and agg._slot_dims == (KEYS,) and agg._key_lows == (LOW,)
    assert agg._static_bound == KEYS + 1 and agg._trains
    assert agg._dense_dims is None and agg._mxu_dims is None
    assert counts == {"agg_ingest_batches": 20, "agg_ingest_launches": 4,
                      "agg_ingest_path.slot": 20, "agg_key_bound.range": 1}
    assert (len(adds), merges) == (3, [])
    assert agg._pending == [] and agg._slot_acc is None and agg._folded == []
    assert out.capacity == 8192 and rows_of(out) == WANT
    # the same rows from the sort path
    (plain, sorted_out), counts = moved(lambda: run(key_ranges=None))
    assert plain._path == "sort" and counts["agg_merge_launches"] >= 1
    assert rows_of(sorted_out) == WANT


def test_partial_to_wire_to_final_gives_the_same_rows():
    """The partial step's wire state holds the keys' own values; a final
    step takes states that are not addressed by slot (two partials' rows
    side by side, a key in both) through the sort merge it always had."""
    halves = []
    for part in (slice(0, 11), slice(11, None)):
        agg = HashAggregationOperator([0], COUNTS, SCHEMA, key_ranges=((LOW, HIGH),),
                                      step="partial")
        for b in batches()[part]:
            agg.add_input(b)
        agg.finish()
        assert agg._path == "slot"
        halves.append(agg.get_output())
    wire_schema = partial_output_schema(COUNTS, [0], SCHEMA)
    final_aggs = [AggSpec(a.kind, 1 + 2 * i, a.out_type) for i, a in enumerate(COUNTS)]
    final = HashAggregationOperator([0], final_aggs, wire_schema, key_ranges=((LOW, HIGH),),
                                    step="final")
    for wire in halves:
        final.add_input(wire)
    final.finish()
    assert rows_of(final.get_output()) == WANT


@pytest.mark.parametrize("at", [3, 8, 19])
def test_a_forced_revoke_gives_the_same_rows(at):
    """Revocation flushes the held train, spills the one added state in
    the wire format and starts again; at finish what came out of the
    spill is merged with the table by the sort merge (right, not fast)."""
    seen = {}

    def revoke(agg, i):
        if i == at:
            seen["held"] = len(agg._held)
            agg._revoke_memory()
            assert agg._held == [] and agg._acc is None and agg._slot_acc is None
            assert agg._pending == [] and agg._spiller.batch_count == 1

    (agg, out), counts = moved(lambda: run(before_batch=revoke))
    assert seen["held"] == at % 8
    assert counts["agg_ingest_path.slot"] == 20 and counts["agg_merge_launches"] == 1
    assert rows_of(out) == WANT


@pytest.mark.parametrize("stray", [HIGH + 1, LOW - 1, LOW + 2**32])
def test_a_key_outside_the_plans_range_fails_loudly(stray):
    rows = batches()
    k = rows[5].columns[0]
    loud = int(np.nonzero(np.asarray(k.valid))[0][0])
    rows[5] = RelBatch([Column(k.type, k.data.at[loud].set(stray), k.valid, None),
                        *rows[5].columns[1:]], rows[5].live)
    with pytest.raises(RuntimeError, match="outside the value range"):
        run(rows=rows)


def test_dictionaries_alone_keep_their_limit_of_65536_slots():
    """A count by dictionaries alone takes the slot path up to 2^16
    slots and no further, whatever SLOT_MAX_SLOTS is: past it the
    operator is what it was (no bound, the sort path, its replays)."""
    names = [f"n{i:05d}" for i in range(3000)]
    schema = [(T.VARCHAR, Dictionary(names)), (T.BIGINT, None)]
    agg = HashAggregationOperator([0], COUNTS[:1], schema)
    assert agg._path == "slot" and agg._static_bound == 3001 and agg._key_lows is None
    assert agg._key_bound_counter == "agg_key_bound.dictionary"
    summed = HashAggregationOperator([0], [AggSpec("sum", 1, T.BIGINT)], schema)
    assert summed._path == "sort" and summed._static_bound == 3001     # as before
    wide = [(T.VARCHAR, Dictionary([f"n{i:06d}" for i in range(100_000)])), (T.BIGINT, None)]
    assert 100_001 < G.SLOT_MAX_SLOTS
    agg = HashAggregationOperator([0], COUNTS[:1], wide)
    assert agg._path == "sort" and agg._static_bound is None and not agg._trains
    assert agg._key_bound_counter == "agg_key_bound.none"
