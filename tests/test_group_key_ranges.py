"""An integer group key whose exact value range the plan knows bounds
the group table (issue 39): the range goes from the connector's
statistics through a closed list of projections to the AggregateNode,
and the operator addresses its table by `value - low` on the dense and
MXU reduces, as it does by a dictionary's codes; past the MXU reduce's
2,048 slots counts alone keep the range, for the slot path (issue 45).
CPU counts and answers
only; what any of it costs is a chip reading (PERF.md section 6)."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.oracle import assert_rows_match, oracle_rows
from tests.test_tpch import to_sqlite
from tests.tpch_queries import QUERIES
from trino_tpu import types as T
from trino_tpu.block import Column, Dictionary, RelBatch
from trino_tpu.connectors.memory import create_memory_connector
from trino_tpu.connectors.tpch import create_tpch_connector
from trino_tpu.engine import LocalQueryRunner, Session
from trino_tpu.exec import operators as O
from trino_tpu.exec.operators import AggSpec, HashAggregationOperator
from trino_tpu.ops import groupby as G
from trino_tpu.ops.int128 import from_python, to_python
from trino_tpu.runtime.metrics import METRICS

Q9_TABLES = {
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
                 "l_extendedprice", "l_discount", "l_shipdate"],
    "orders": ["o_orderkey", "o_orderdate", "o_shippriority", "o_totalprice"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
    "part": ["p_partkey", "p_name"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name"],
}
COUNTERS = ("agg_ingest_batches", "agg_ingest_launches", "agg_merge_launches",
            "agg_ingest_path.dense", "agg_ingest_path.mxu", "agg_ingest_path.slot",
            "agg_ingest_path.sort",
            "agg_key_bound.range", "agg_key_bound.dictionary", "agg_key_bound.none")


def moved(fn):
    """fn()'s result and what it moved of COUNTERS."""
    before = {k: METRICS.counter(k) for k in COUNTERS}
    out = fn()
    return out, {k: METRICS.counter(k) - v for k, v in before.items()
                 if METRICS.counter(k) != v}


def new_runner():
    """Q9's six tables at `tiny`, copied into a memory connector (whose
    statistics are exact), beside the tpch connector (whose are not)."""
    r = LocalQueryRunner(Session(catalog="memory", schema="s"))
    r.register_catalog("tpch", create_tpch_connector())
    r.register_catalog("memory", create_memory_connector())
    for table, cols in Q9_TABLES.items():
        r.execute(f"create table memory.s.{table} as "
                  f"select {', '.join(cols)} from tpch.tiny.{table}")
    return r


@pytest.fixture(scope="module")
def runner():
    return new_runner()


def aggregate_lines(runner, sql):
    text = runner.execute("explain " + sql).rows[0][0]
    return [line.strip() for line in text.splitlines()
            if line.strip().startswith("Aggregate")]


# -- the range through the plan ----------------------------------------------

PLANNED = {
    # the year of a joined, filtered date column: monotone in the day
    "year-joined-filtered": (
        "select extract(year from o_orderdate), count(*) from lineitem, orders "
        "where l_orderkey = o_orderkey and l_quantity < 30 group by 1",
        "[(1992, 1998)]"),
    # month, day of the month and quarter are bounded whatever the date
    "month-day-quarter": (
        "select month(o_orderdate), quarter(o_orderdate), count(*) from orders group by 1, 2",
        "[(1, 12), (1, 4)]"),
    "day": ("select extract(day from l_shipdate), sum(l_quantity) from lineitem group by 1",
            "[(1, 31)]"),
    # a string key has none to give and does not stand in the way
    "q9": (QUERIES[9], "[None, (1992, 1998)]"),
    # a column itself, through an integer cast
    "cast": ("select cast(l_linenumber as integer), count(*) from lineitem group by 1",
             "[(1, 7)]"),
    "column": ("select l_linenumber, o_shippriority, count(*) from lineitem, orders "
               "where l_orderkey = o_orderkey group by 1, 2", "[(1, 7), (0, 0)]"),
    # through a subquery's aggregation: its keys keep their range
    "aggregate-below": (
        "select y, max(n) from (select extract(year from o_orderdate) y, o_shippriority p, "
        "count(*) n from orders group by 1, 2) group by y", "[(1992, 1998)]"),
    # past the MXU reduce's 2,048 slots a COUNT keeps its range (the slot
    # path's): some 2,400 dates
    "wide-date": ("select o_orderdate, count(*) from orders group by 1", "[(8035, 10440)]"),
    # three keys' digits, 32 x 13 x 8 = 3,328 slots, counted
    "product-counted": (
        "select extract(day from l_shipdate), month(l_shipdate), l_linenumber, count(*) "
        "from lineitem group by 1, 2, 3", "[(1, 31), (1, 12), (1, 7)]"),
}
NOT_PLANNED = {
    # an estimate or a declared range never bounds a table
    "tpch-connector": "select extract(year from o_orderdate), count(*) "
                      "from tpch.tiny.orders group by 1",
    # outside the closed list of expressions
    "arithmetic": "select extract(year from o_orderdate) + 1, count(*) from orders group by 1",
    "modulo": "select o_orderkey % 7, count(*) from orders group by 1",
    "week": "select week(o_orderdate), count(*) from orders group by 1",
    "case": "select case when l_linenumber > 3 then 1 else 0 end, count(*) "
            "from lineitem group by 1",
    # past 2,048 slots only counts address a table by slot: a SUM by
    # 60,000 order keys has no range to use, a sum beside the count neither
    "wide-key": "select l_orderkey, sum(l_quantity) from lineitem group by 1",
    "wide-date-sum": "select o_orderdate, count(*), sum(o_totalprice) from orders group by 1",
    # all or none: one key without a range leaves the node without any
    "half": "select extract(year from o_orderdate), o_orderkey % 7, count(*) "
            "from orders group by 1, 2",
    # 32 x 13 x 8 = 3,328 slots and a sum: over the limit its reduce has
    "product": "select extract(day from l_shipdate), month(l_shipdate), l_linenumber, "
               "sum(l_quantity) from lineitem group by 1, 2, 3",
    # a decimal is no integer kind
    "decimal": "select l_discount, count(*) from lineitem group by 1",
    # strings alone are the operator's own business, as before
    "strings": "select n_name, count(*) from nation group by 1",
}


@pytest.mark.parametrize("name", sorted(PLANNED))
def test_an_exact_range_reaches_the_aggregate(runner, name):
    sql, ranges = PLANNED[name]
    top = aggregate_lines(runner, sql)[0]
    assert top.endswith(" key_ranges=" + ranges), top


@pytest.mark.parametrize("name", sorted(NOT_PLANNED))
def test_nothing_else_gives_a_range(runner, name):
    lines = aggregate_lines(runner, NOT_PLANNED[name])
    assert lines and not any("key_ranges" in line for line in lines), lines


def test_the_memory_connector_says_which_ranges_it_counted(runner):
    """Integer columns' extremes are taken over every row: exact. A
    long decimal, a string and a float are not listed."""
    mem = runner.catalogs.get("memory")
    stats = mem.metadata.get_table_statistics(mem.metadata.get_table_handle("s", "orders"))
    assert {"o_orderkey", "o_orderdate", "o_shippriority"} <= stats.exact_ranges
    assert stats.columns["o_orderdate"][2:] == (8035.0, 10440.0)
    tpch = runner.catalogs.get("tpch")
    declared = tpch.metadata.get_table_statistics(tpch.metadata.get_table_handle("tiny", "orders"))
    assert declared.exact_ranges == frozenset()


def test_a_partial_and_its_final_step_carry_the_same_range(runner):
    from trino_tpu.sql import plan as P
    from trino_tpu.sql.fragmenter import push_partial_aggregation_through_exchange

    child = P.ValuesNode(rows=(), fields=(P.Field("y", T.BIGINT), P.Field("v", T.BIGINT)))
    ex = P.ExchangeNode(child, "repartition", (0,), child.fields)
    single = P.AggregateNode(
        ex, (0,), (P.AggCall("sum", 1, T.BIGINT),),
        (P.Field("y", T.BIGINT), P.Field("sum", T.BIGINT)), key_ranges=((1992, 1998),))
    final = push_partial_aggregation_through_exchange(single)
    partial = final.child.child
    assert (final.step, partial.step) == ("final", "partial")
    assert final.key_ranges == partial.key_ranges == ((1992, 1998),)


# -- the operator -------------------------------------------------------------

BATCH = 256
NATIONS = ["ALGERIA", "BRAZIL", "CANADA"]
D = T.decimal(35, 4)
SCHEMA = [(T.VARCHAR, Dictionary(NATIONS)), (T.BIGINT, None), (D, None), (T.BIGINT, None),
          (T.DATE, None)]
RANGES = (None, (1992, 1998))
SUMS = [AggSpec("sum", 2, T.decimal(38, 4)), AggSpec("count_star", None, T.BIGINT),
        AggSpec("sum", 3, T.BIGINT), AggSpec("count", 3, T.BIGINT)]
EXTREMES = SUMS + [AggSpec("min", 3, T.BIGINT), AggSpec("max", 3, T.BIGINT)]


def make_rows(n_batches, seed=39, years=(1992, 1998)):
    """(nation code, year, signed 128-bit amount, small value, day) a
    row, NULLs among the keys and the values; the amounts pass 2^64 and
    2^96 on both sides of zero, so every limb slot carries and the top
    one is negative in half the rows."""
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(n_batches):
        n = BATCH
        nation = rng.integers(0, len(NATIONS), n)
        year = rng.integers(years[0], years[1] + 1, n)
        amount = [int(x) * 10**(6 * (i % 4)) * (-1) ** (i % 2)
                  for i, x in enumerate(rng.integers(1, 10**15, n))]
        small = rng.integers(-1000, 1000, n)
        day = rng.integers(9000, 9004, n)
        valid = {c: rng.random(n) > 0.1 for c in ("nation", "year", "amount", "small")}
        rows.append((nation, year, amount, small, day, valid, rng.random(n) > 0.05))
    return rows


def to_batch(nation, year, amount, small, day, valid, live):
    pairs = [from_python(int(x)) for x in amount]
    limbs = np.stack([
        np.array([p[0] for p in pairs], dtype=np.int64),
        (np.array([p[1] % 2**64 for p in pairs], dtype=object)
         .astype(np.uint64).view(np.int64)),
    ], axis=1)
    return RelBatch([
        Column(T.VARCHAR, jnp.asarray(nation, jnp.int32), jnp.asarray(valid["nation"]),
               SCHEMA[0][1]),
        Column(T.BIGINT, jnp.asarray(year, jnp.int64), jnp.asarray(valid["year"]), None),
        Column(D, jnp.asarray(limbs), jnp.asarray(valid["amount"]), None),
        Column(T.BIGINT, jnp.asarray(small, jnp.int64), jnp.asarray(valid["small"]), None),
        Column(T.DATE, jnp.asarray(day, jnp.int32), None, None),
    ], jnp.asarray(live))


def exact_rows(batch):
    """The output's rows, a long decimal as its exact unscaled integer."""
    host = jax.device_get(batch)
    live = np.asarray(host.live_mask())
    cols = []
    for c in host.columns:
        if c.type.is_long_decimal:
            cols.append([
                to_python(int(h), int(lo)) if ok else None
                for (h, lo), ok, keep in zip(
                    np.asarray(c.data), np.asarray(c.valid_mask()), live) if keep])
        else:
            cols.append(c.to_pylist(live=live))
    return sorted(zip(*cols), key=repr)


def want_rows(rows, extremes):
    groups = collections.defaultdict(lambda: [None, 0, None, 0, None, None])
    for nation, year, amount, small, _day, valid, live in rows:
        for i in np.nonzero(live)[0]:
            g = groups[(NATIONS[nation[i]] if valid["nation"][i] else None,
                        int(year[i]) if valid["year"][i] else None)]
            g[1] += 1
            if valid["amount"][i]:
                g[0] = (g[0] or 0) + amount[i]
            if valid["small"][i]:
                s = int(small[i])
                g[2] = (g[2] or 0) + s
                g[3] += 1
                g[4] = s if g[4] is None else min(g[4], s)
                g[5] = s if g[5] is None else max(g[5], s)
    return sorted(((*k, *(g if extremes else g[:4])) for k, g in groups.items()), key=repr)


def aggregate(rows, aggs, key_ranges, groups=(0, 1), step="single"):
    agg = HashAggregationOperator(list(groups), aggs, SCHEMA, key_ranges=key_ranges,
                                  step=step)
    for r in rows:
        agg.add_input(to_batch(*r))
    agg.finish()
    return agg, agg.get_output()


@pytest.mark.parametrize("path", ["dense", "mxu"])
def test_an_integer_key_counted_from_its_low_end_equals_the_sort_path(path, monkeypatch):
    """A dictionary key and a BIGINT key in [1992, 1998], NULLs in
    both: (3 + 1) x (7 + 1) = 32 slots. Signed 128-bit sums, counts and
    (dense alone) minima and maxima, eleven batches in trains of eight:
    row for row what the sort path and python's integers give."""
    if path == "mxu":
        monkeypatch.setenv("TRINO_TPU_FORCE_MXU", "1")
    aggs = SUMS if path == "mxu" else EXTREMES
    rows = make_rows(11)
    (agg, out), counts = moved(lambda: aggregate(rows, aggs, RANGES))
    assert agg._path == path and agg._static_bound == 32 and agg._key_lows == (0, 1992)
    assert (agg._mxu_dims if path == "mxu" else agg._dense_dims) == (3, 7)
    # two trains leave two states of the table's size: one merge
    assert counts == {"agg_ingest_batches": 11, "agg_ingest_launches": 2,
                      "agg_merge_launches": 1,
                      "agg_ingest_path." + path: 11, "agg_key_bound.range": 1}
    assert out.capacity == 32                     # only used slots are live
    monkeypatch.delenv("TRINO_TPU_FORCE_MXU", raising=False)
    (plain, sorted_out), counts = moved(lambda: aggregate(rows, aggs, None))
    assert plain._path == "sort" and plain._static_bound is None
    assert counts["agg_ingest_path.sort"] == 11 and counts["agg_key_bound.none"] == 1
    got = exact_rows(out)
    assert got == exact_rows(sorted_out) == want_rows(rows, extremes=path == "dense")
    assert any(r[2] is not None and r[2] < 0 for r in got)       # negative 128-bit sums
    assert any(r[1] is None for r in got) and any(r[0] is None for r in got)


@pytest.mark.parametrize("path", ["dense", "mxu"])
def test_a_date_key_and_a_partial_step_take_the_same_road(path, monkeypatch):
    """An int32 key (DATE) alone, low 9000; the partial step's wire
    state holds the key's own values, not its digits."""
    if path == "mxu":
        monkeypatch.setenv("TRINO_TPU_FORCE_MXU", "1")
    rows = make_rows(3)
    aggs = [AggSpec("count_star", None, T.BIGINT), AggSpec("sum", 3, T.BIGINT),
            AggSpec("sum", 2, T.decimal(38, 4))]
    agg, out = aggregate(rows, aggs, ((9000, 9003),), groups=(4,), step="partial")
    assert agg._path == path and agg._key_lows == (9000,)
    host = jax.device_get(out)
    live = np.asarray(host.live_mask())
    days = sorted(np.asarray(host.columns[0].data)[live].tolist())
    assert days == [9000, 9001, 9002, 9003] and host.columns[0].data.dtype == np.int32
    counted = dict(zip(np.asarray(host.columns[0].data)[live].tolist(),
                       np.asarray(host.columns[1].data)[live].tolist()))
    want = collections.Counter()
    for r in rows:
        want.update(r[4][r[6]].tolist())
    assert counted == dict(want)


@pytest.mark.parametrize("path", ["dense", "mxu"])
@pytest.mark.parametrize("stray", [1999, 1991, 1992 + 2**32, -(2**40)])
def test_a_value_outside_the_range_fails_loudly(path, stray, monkeypatch):
    """The fail-loud guard a dictionary that outgrew its plan has: a
    live, valid key outside [low, high] raises at finish, also one that
    would fold into the range once narrowed to 32 bits. A NULL or a dead
    row may hold anything."""
    if path == "mxu":
        monkeypatch.setenv("TRINO_TPU_FORCE_MXU", "1")
    rows = make_rows(2)
    nation, year, amount, small, day, valid, live = rows[1]
    quiet = np.nonzero(~valid["year"] | ~live)[0]
    year = year.copy()
    year[quiet] = stray                            # NULL or dead: not looked at
    rows[1] = (nation, year, amount, small, day, valid, live)
    agg, out = aggregate(rows, SUMS, RANGES)
    assert agg._path == path
    assert exact_rows(out) == want_rows(rows, extremes=False)
    loud = np.nonzero(valid["year"] & live)[0][:1]
    year = year.copy()
    year[loud] = stray
    rows[1] = (nation, year, amount, small, day, valid, live)
    with pytest.raises(RuntimeError, match="outside the value range"):
        aggregate(rows, SUMS, RANGES)


def recorded_syncs(monkeypatch):
    sites = []
    inner = O.host_sync
    monkeypatch.setattr(
        O, "host_sync", lambda site, *a, **k: sites.append(site) or inner(site, *a, **k))
    return sites


UNBOUNDED = {
    # over 2,048 slots with the NULL digits, (3 + 1) x (600 + 1), and
    # sums among the aggregates: past the MXU limit only counts keep a range
    "product-over-2048": (SUMS, (None, (1900, 2499)), {}),
    # a SUM by a key of 60,000 values (the plan hands such a node no
    # range; an operator handed one drops it)
    "wide-key": (SUMS[2:3], ((-30000, 29999),), {"groups": (3,)}),
    # the chooser answers `sort`: a minimum is no MXU sum, and (3 + 1) x
    # (30 + 1) = 124 slots are more than the dense reduce takes
    "chooser-says-sort": (EXTREMES, (None, (1990, 2019)), {}),
    # Q9's own 208 slots on a backend without the MXU kernel
    "no-mxu-here": (SUMS, (None, (1980, 2030)), {}),
    # a range for a key that is no integer kind is not read
    "not-an-integer-key": (SUMS, ((0, 2), (1992, 1998)), {"groups": (2, 1)}),
}


@pytest.mark.parametrize("name", sorted(UNBOUNDED))
def test_a_range_that_cannot_bound_the_table_leaves_the_operator_as_it_was(name, monkeypatch):
    """No bound, the sort path, and the sort path's launches, merges and
    readbacks where they are without any range."""
    aggs, ranges, kwargs = UNBOUNDED[name]
    rows = make_rows(11)
    sites = recorded_syncs(monkeypatch)
    (agg, out), counts = moved(lambda: aggregate(rows, aggs, ranges, **kwargs))
    with_range = list(sites)
    del sites[:]
    (plain, want), plain_counts = moved(lambda: aggregate(rows, aggs, None, **kwargs))
    assert agg._static_bound is None and agg._path == "sort" and not agg._trains
    assert agg._key_lows is None and agg._dense_dims is None and agg._mxu_dims is None
    assert agg._slot_dims is None and agg._slot_acc is None
    assert agg._cap == plain._cap
    assert counts == plain_counts and counts["agg_key_bound.none"] == 1
    assert counts["agg_ingest_launches"] == counts["agg_ingest_path.sort"] == 11
    assert with_range == sites and sites.count("agg.ingest_overflow") == 11
    assert exact_rows(out) == exact_rows(want)


def test_a_count_only_twin_of_the_product_over_2048_takes_the_slot_path():
    """The same (3 + 1) x (600 + 1) = 2,404 slots with counts alone:
    eleven batches in two trains, their states added, no merge, no
    readback; row for row the sort path's answer."""
    counts_only = [AggSpec("count_star", None, T.BIGINT), AggSpec("count", 3, T.BIGINT)]
    ranges = (None, (1900, 2499))
    rows = make_rows(11, years=(1900, 2499))
    (agg, out), counts = moved(lambda: aggregate(rows, counts_only, ranges))
    assert agg._path == "slot" and agg._static_bound == 2404 and agg._trains
    assert agg._slot_dims == (3, 600) and agg._key_lows == (0, 1900)
    assert counts == {"agg_ingest_batches": 11, "agg_ingest_launches": 2,
                      "agg_ingest_path.slot": 11, "agg_key_bound.range": 1}
    assert out.capacity == 4096
    (plain, want), counts = moved(lambda: aggregate(rows, counts_only, None))
    assert plain._path == "sort" and counts["agg_merge_launches"] >= 1
    assert exact_rows(out) == exact_rows(want)
    assert len(exact_rows(out)) > 1000


def test_a_count_by_2400_dates_equals_the_sort_path(runner):
    """`wide-date` through SQL: planned since the slot path (it was
    NOT_PLANNED while 2,048 slots were the only limit), counted as
    `range` and `slot`, and what the tpch connector's own table (no
    exact range, the sort path) answers."""
    sql = "select o_orderdate, count(*) from {}orders group by 1 order by 1"
    result, counts = moved(lambda: runner.execute(sql.format("")))
    assert counts["agg_key_bound.range"] == 1
    assert counts["agg_ingest_path.slot"] == counts["agg_ingest_batches"] >= 1
    assert "agg_ingest_path.sort" not in counts and "agg_merge_launches" not in counts
    plain, counts = moved(lambda: runner.execute(sql.format("tpch.tiny.")))
    assert counts["agg_ingest_path.sort"] >= 1 and counts["agg_key_bound.none"] == 1
    assert result.rows == plain.rows and len(result.rows) > 2000


def test_dictionaries_alone_keep_their_bound_and_their_programs(monkeypatch):
    """A table bounded by dictionaries and booleans is what it was: the
    bound up to 2^16, no offsets among the programs' static arguments
    (so their traces are the parent's), counted as `dictionary`."""
    (agg, _), counts = moved(lambda: aggregate(make_rows(2), SUMS, None, groups=(0,)))
    assert agg._static_bound == 4 and agg._path == "dense" and agg._key_lows is None
    assert counts["agg_key_bound.dictionary"] == 1
    # a range that starts at 0 needs no offset either
    agg, _ = aggregate(make_rows(2, years=(0, 6)), SUMS, (None, (0, 6)))
    assert agg._static_bound == 32 and agg._key_lows is None
    # the kernels' own view: no `lows`, or all zero, one and the same trace
    keys = [jnp.zeros(64, jnp.int32), jnp.zeros(64, jnp.bool_)]
    valids = [jnp.ones(64, jnp.bool_)] * 2
    args = (keys, valids, jnp.ones(64, jnp.bool_), [jnp.ones(64, jnp.int64)], (None,))

    def trace(**lows):
        return str(jax.make_jaxpr(lambda a: G.dense_group_reduce.__wrapped__(
            *a, ("sum",), (3, 2), 16, **lows))(args))

    assert trace() == trace(lows=(0, 0)) != trace(lows=(0, 1))


# -- whole statements ---------------------------------------------------------

def test_q9_at_tiny_takes_the_mxu_reduce_and_equals_the_oracle(runner, monkeypatch):
    monkeypatch.setenv("TRINO_TPU_FORCE_MXU", "1")
    result, counts = moved(lambda: runner.execute(QUERIES[9]))
    assert counts["agg_key_bound.range"] == 1
    assert counts["agg_ingest_path.mxu"] == counts["agg_ingest_batches"] >= 1
    assert "agg_ingest_path.sort" not in counts and "agg_merge_launches" not in counts
    account = result.stats["account"]
    assert account["c.agg_key_bound.range"] == 1 and "s.agg.ingest_overflow.n" not in account
    expected = oracle_rows(0.01, to_sqlite(QUERIES[9]), tables=sorted(Q9_TABLES))
    assert len(expected) > 150
    assert_rows_match(result.rows, expected, ordered=True, abs_tol=1e-2)
    # without the kernel the same text sorts, and answers the same
    monkeypatch.delenv("TRINO_TPU_FORCE_MXU")
    fresh = new_runner()
    again, counts = moved(lambda: fresh.execute(QUERIES[9]))
    assert counts["agg_ingest_path.sort"] >= 1 and counts["agg_key_bound.none"] == 1
    assert again.rows == result.rows


def test_an_insert_outside_the_range_replans_the_same_text():
    """The range is the table's at one version: an order from 1999
    between two runs of one text invalidates the cached plan, the second
    run plans [1992, 1999] and answers right (8 + 1 slots: the dense
    reduce, on any backend)."""
    r = new_runner()
    sql = ("select extract(year from o_orderdate) y, count(*) n, sum(o_totalprice) t "
           "from orders group by 1 order by 1")
    assert aggregate_lines(r, sql)[0].endswith("key_ranges=[(1992, 1998)]")
    first, counts = moved(lambda: r.execute(sql))
    assert counts["agg_key_bound.range"] == 1 and counts["agg_ingest_path.dense"] >= 1
    assert [row[0] for row in first.rows] == list(range(1992, 1999))
    assert r.execute(sql).stats["account"]["plan_hit"] == 1
    r.execute("insert into orders values (999999, date '1999-07-04', 0, 1234.56)")
    second, counts = moved(lambda: r.execute(sql))
    assert second.stats["account"]["plan_hit"] == 0
    assert counts["agg_key_bound.range"] == 1
    assert aggregate_lines(r, sql)[0].endswith("key_ranges=[(1992, 1999)]")
    assert second.rows[:-1] == first.rows
    assert second.rows[-1][:2] == [1999, 1] and float(second.rows[-1][2]) == 1234.56
