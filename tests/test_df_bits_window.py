"""The key-bits filter on a scan in key order (issue 42): a block of rows
shares one window of the bit table and picks its words inside it
(`_df_filter_bits_window`), where `_df_filter_bits` gathers a word a
row; the connector says which columns are stored in order, the planner
which filters stand on such a scan, and the program itself takes the
gather for a batch that does not fit its windows. CPU masks, counts and
answers only; what either lookup costs is a chip reading (PERF.md
section 6, PR 42)."""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.oracle import assert_rows_match, oracle_rows
from tests.test_semi_join_sides import dynamic_filters
from tests.test_tpch import to_sqlite
from tests.tpch_queries import QUERIES
from trino_tpu import types as T
from trino_tpu.block import Column, RelBatch, bucket_capacity
from trino_tpu.connectors.memory import create_memory_connector
from trino_tpu.connectors.tpch import create_tpch_connector
from trino_tpu.engine import LocalQueryRunner, Session
from trino_tpu.exec import operators as O
from trino_tpu.runtime.metrics import METRICS

COUNTERS = ("df_bits_lookup.window", "df_bits_lookup.gather", "df_bits_window_fallbacks",
            "df_filter_path.bits", "df_filter_path.set", "df_filter_path.range",
            "df_rows_kept", "df_reverse_rows_kept")
WINDOW_VALUES = 2 * O.DF_WINDOW_WORDS * 32      # the key values one window covers


def moved(fn):
    before = {k: METRICS.counter(k) for k in COUNTERS}
    out = fn()
    return out, {k: METRICS.counter(k) - v for k, v in before.items()}


def order_key(i):
    """The generator's sparse order keys: 8 of every 32 values used."""
    i = np.asarray(i, dtype=np.int64)
    return (i >> 3 << 5) + (i & 7) + 1


def lineitem_keys(rng, rows, first_order=0):
    """`rows` order keys in order, 1 to 7 rows a key."""
    orders = first_order + np.arange(rows, dtype=np.int64)
    return np.repeat(order_key(orders), rng.integers(1, 8, rows))[:rows]


def case(name):
    """(keys, live, valid | None, build keys, falls back) of one batch."""
    rng = np.random.default_rng(sum(name.encode()))
    n = 1 << 14
    keys = lineitem_keys(rng, n, first_order=5000)
    live = np.ones(n, dtype=bool)
    live[rng.choice(n, 50, replace=False)] = False
    valid = None
    build = order_key(rng.choice(40_000, 2_000, replace=False))
    falls_back = False
    if name == "in_order":
        pass
    elif name == "shuffled":
        keys, falls_back = rng.permutation(keys), True
    elif name == "a_gap_inside_a_block":
        # in order still, and every key inside the build side's range
        at = 37 * O.DF_WINDOW_ROWS + O.DF_WINDOW_ROWS // 2
        keys[at:] += WINDOW_VALUES + 64
        build = np.concatenate([build, keys[at:at + 300:3]])
        live[at - 1:at + 1] = True
        falls_back = True
    elif name == "dead_and_null_rows_at_block_starts":
        # their payloads ask for words far from their blocks'
        valid = np.ones(n, dtype=bool)
        starts = np.arange(0, n, O.DF_WINDOW_ROWS)
        live[starts[::2]] = False
        valid[starts[1::2]] = False
        keys[starts] = rng.choice(build, len(starts))
    elif name == "a_padded_last_batch":
        live = np.arange(n) < 1000
        keys[1000:] = 0
    elif name == "a_table_of_one_row":
        n = 1 << 12
        keys = np.sort(rng.integers(3, 3 + O.DF_WINDOW_WORDS * 32, n)).astype(np.int64)
        live = np.ones(n, dtype=bool)
        build = np.unique(rng.choice(keys, 300))
    elif name == "keys_below_lo_and_above_hi":
        build = build[(build > keys.min() + 3000) & (build < keys.max() - 3000)]
        assert len(build) > 100
    elif name == "descending":
        # a block's window starts at its LEAST word, wherever that row is
        keys = keys[::-1].copy()
    elif name == "int32_keys":
        keys = keys.astype(np.int32)
    elif name == "every_row_dead":
        keys, live[:] = rng.permutation(keys), False
    elif name == "a_gap_between_blocks":
        # as wide as the one inside a block, and no block sees it
        at = 37 * O.DF_WINDOW_ROWS
        keys[at:] += WINDOW_VALUES + 64
        build = np.concatenate([build, keys[at:at + 300:3]])
    elif name == "keys_at_the_top_of_the_table":
        # the last table row has no neighbour above it
        build = build[build <= keys.max()]
        build = np.concatenate([build, keys[-200::7]])
        assert keys.max() == build.max()
    elif name == "a_single_block":
        n = O.DF_WINDOW_ROWS
        keys, live = keys[:n], np.ones(n, dtype=bool)
        build = keys[::3]
    elif name.startswith("stretches_in_and_out_of_order"):
        # one stretch out of order sends the whole batch to the gather
        at = int(rng.integers(0, n - 2048))
        keys[at:at + 2048] = rng.permutation(keys)[:2048]
        falls_back = True
    else:
        raise AssertionError(name)
    return keys, live, valid, build, falls_back


CASES = ["in_order", "shuffled", "a_gap_inside_a_block", "dead_and_null_rows_at_block_starts",
         "a_padded_last_batch", "a_table_of_one_row", "keys_below_lo_and_above_hi",
         "descending", "int32_keys", "every_row_dead", "a_gap_between_blocks",
         "keys_at_the_top_of_the_table", "a_single_block",
         "stretches_in_and_out_of_order.1", "stretches_in_and_out_of_order.2",
         "stretches_in_and_out_of_order.3"]


def bit_table(build):
    """The table as `DynamicFilterOperator._prepare` makes it."""
    lo, hi = int(build.min()), int(build.max())
    n_words = max(bucket_capacity(-(-(hi - lo + 1) // 32)), 128)
    low = jnp.asarray(lo, dtype=jnp.int64)
    words = O._df_bit_table(jnp.asarray(build), jnp.ones(len(build), dtype=bool), low, n_words)
    return words, low, jnp.asarray(hi, dtype=jnp.int64)


@pytest.mark.parametrize("name", CASES)
def test_the_window_keeps_the_rows_the_gather_keeps(name):
    keys, live, valid, build, falls_back = case(name)
    key_type = T.INTEGER if keys.dtype == np.int32 else T.BIGINT
    batch = RelBatch(
        [Column(key_type, jnp.asarray(keys), None if valid is None else jnp.asarray(valid), None),
         Column(T.BIGINT, jnp.arange(len(keys), dtype=jnp.int64), None, None)],
        jnp.asarray(live))
    key = (batch.columns[0].data, batch.columns[0].valid)
    bits = bit_table(build)
    if name == "a_table_of_one_row":
        assert bits[0].shape == (O.DF_WINDOW_WORDS,)
    start = jnp.asarray([7, 3], dtype=jnp.int64)
    want, want_kept, want_totals = O._df_filter_bits(batch, key, *bits, start)
    got, kept, totals, fallbacks = O._df_filter_bits_window(
        batch, key, *bits, start, jnp.asarray(2, dtype=jnp.int32))
    mask = np.asarray(want.live_mask())
    assert np.array_equal(np.asarray(got.live_mask()), mask)
    # (and it is the filter: the live, valid rows whose key was built)
    usable = live if valid is None else live & valid
    assert np.array_equal(mask, usable & np.isin(keys, build))
    assert int(kept) == int(want_kept) == int(mask.sum())
    assert name in ("a_padded_last_batch", "every_row_dead") or mask.sum() > 10
    assert np.array_equal(np.asarray(totals), np.asarray(want_totals))
    assert int(fallbacks) - 2 == int(falls_back)


SCHEMA = [(T.BIGINT, None), (T.BIGINT, None)]


def built_bridge(build):
    bridge = O.JoinBridge()
    sink = O.HashBuildSink(bridge, [0], SCHEMA)
    slots = bucket_capacity(len(build))
    keys = np.zeros(slots, dtype=np.int64)
    keys[:len(build)] = build
    sink.add_input(RelBatch(
        [Column(T.BIGINT, jnp.asarray(keys), None, None),
         Column(T.BIGINT, jnp.zeros(slots, dtype=jnp.int64), None, None)],
        jnp.asarray(np.arange(slots) < len(build))))
    sink.finish()
    return bridge


def filtered(bridge, batches, **kwargs):
    df = O.DynamicFilterOperator(bridge, [0], **kwargs)
    out = []
    for b in batches:
        df.add_input(b)
        while (o := df.get_output()) is not None:
            out.append(o)
    df.finish()
    while (o := df.get_output()) is not None:
        out.append(o)
    rows = sorted(tuple(r) for b in out for r in b.to_pylists())
    return df, rows


@pytest.mark.parametrize("ordered, shuffled, window, gather, fallbacks", [
    (False, False, 0, 3, 0), (True, False, 3, 0, 0), (True, True, 3, 0, 3),
], ids=["the_plan_says_nothing", "in_order", "the_plan_was_wrong"])
def test_the_operator_launches_the_window_on_the_plans_word_and_counts_it(
        monkeypatch, ordered, shuffled, window, gather, fallbacks):
    """The bits path forced by the operator's own limits (a `tiny` build
    side would take the key set), three batches of a scan."""
    monkeypatch.setattr(O, "DF_SET_MAX_SLOTS", 64)
    rng = np.random.default_rng(42)
    build = order_key(rng.choice(30_000, 3_000, replace=False))
    bridge = built_bridge(build)
    n = 1 << 13
    keys = lineitem_keys(rng, 3 * n)
    if shuffled:
        keys = rng.permutation(keys)
    batches = [
        RelBatch([Column(T.BIGINT, jnp.asarray(keys[at:at + n]), None, None),
                  Column(T.BIGINT, jnp.arange(at, at + n, dtype=jnp.int64), None, None)], None)
        for at in range(0, 3 * n, n)
    ]
    (df, rows), counts = moved(lambda: filtered(bridge, batches, key_ordered=ordered))
    assert df._path == "bits"
    assert counts["df_filter_path.bits"] == 3
    assert counts["df_bits_lookup.window"] == window
    assert counts["df_bits_lookup.gather"] == gather
    assert counts["df_bits_window_fallbacks"] == fallbacks
    want = sorted((int(k), at) for at, k in enumerate(keys) if k in set(build.tolist()))
    assert rows == want and counts["df_rows_kept"] == len(want)


def test_a_batch_of_no_whole_block_takes_the_gather(monkeypatch):
    monkeypatch.setattr(O, "DF_SET_MAX_SLOTS", 64)
    bridge = built_bridge(order_key(np.arange(0, 3000, 3)))
    n = O.DF_WINDOW_ROWS // 2
    batch = RelBatch([Column(T.BIGINT, jnp.asarray(order_key(np.arange(n))), None, None),
                      Column(T.BIGINT, jnp.arange(n, dtype=jnp.int64), None, None)], None)
    (df, rows), counts = moved(lambda: filtered(bridge, [batch], key_ordered=True))
    assert df._path == "bits" and len(rows) == len(range(0, n, 3))
    assert counts["df_bits_lookup.gather"] == 1 and counts["df_bits_lookup.window"] == 0


def scan_batches(keys, n):
    return [
        RelBatch([Column(T.BIGINT, jnp.asarray(keys[at:at + n]), None, None),
                  Column(T.BIGINT, jnp.arange(at, at + n, dtype=jnp.int64), None, None)], None)
        for at in range(0, len(keys), n)
    ]


@pytest.mark.parametrize("path, build", [
    ("set", order_key(np.arange(0, 3000, 100))), ("range", np.arange(1, 5001)),
], ids=["the_key_set", "the_range"])
def test_a_filter_that_takes_no_bits_counts_no_lookup(path, build):
    """(5,000 keys are past the set's slots, and fill their range.)"""
    keys = lineitem_keys(np.random.default_rng(7), 1 << 13)
    (df, rows), counts = moved(lambda: filtered(built_bridge(build), scan_batches(keys, 1 << 13)))
    assert df._path == path and df._window_fallbacks is None
    assert counts[f"df_filter_path.{path}"] == 1 and counts["df_filter_path.bits"] == 0
    assert counts["df_bits_lookup.window"] == counts["df_bits_lookup.gather"] == 0
    assert counts["df_bits_window_fallbacks"] == 0
    assert len(rows) == (np.isin(keys, build).sum() if path == "set" else (keys <= 5000).sum())


TABLES = {
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity", "l_extendedprice",
                 "l_discount", "l_shipdate", "l_commitdate", "l_receiptdate"],
    "orders": ["o_orderkey", "o_custkey", "o_orderstatus", "o_orderdate", "o_shippriority"],
    "customer": ["c_custkey", "c_mktsegment"],
    "supplier": ["s_suppkey", "s_name", "s_nationkey"],
    "nation": ["n_nationkey", "n_name"],
    "part": ["p_partkey", "p_name"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
}


def new_runner(tables=tuple(TABLES)):
    """TPC-H at `tiny` copied into a memory connector, which looks at
    what it stores (the tpch connector's statistics are declared)."""
    r = LocalQueryRunner(Session(catalog="memory", schema="s"))
    r.register_catalog("tpch", create_tpch_connector())
    r.register_catalog("memory", create_memory_connector())
    for table, cols in ((t, TABLES[t]) for t in tables):
        r.execute(f"create table memory.s.{table} as "
                  f"select {', '.join(cols)} from tpch.tiny.{table}")
    return r


@pytest.fixture(scope="module")
def runner():
    return new_runner()


def table_statistics(runner, table):
    meta = runner.catalogs.get("memory").metadata
    return meta.get_table_statistics(meta.get_table_handle("s", table))


def test_the_memory_connector_says_which_columns_it_stores_in_order(runner):
    lineitem = table_statistics(runner, "lineitem").ordered
    assert "l_orderkey" in lineitem
    assert not {"l_suppkey", "l_partkey", "l_shipdate"} & lineitem
    orders = table_statistics(runner, "orders").ordered
    assert "o_orderkey" in orders and "o_custkey" not in orders
    # a string's codes and a decimal are not asked
    assert "o_orderstatus" not in orders and "l_extendedprice" not in lineitem
    tpch = runner.catalogs.get("tpch").metadata
    assert tpch.get_table_statistics(tpch.get_table_handle("tiny", "orders")).ordered == frozenset()


def test_a_write_that_descends_takes_the_word_back():
    """(A plan made before the write keeps its word, as it keeps its
    `key_fill`: the window program then falls back, batch by batch.)"""
    from trino_tpu.connectors.spi import TableHandle
    from trino_tpu.sql import plan as P
    from trino_tpu.sql.local_planner import LocalPlanner

    r = new_runner(("orders",))
    scan = P.ScanNode("memory", TableHandle("memory", "s", "orders"), ("o_orderkey",),
                      (P.Field("o_orderkey", T.BIGINT),))
    store = r.catalogs.get("memory").metadata.store
    before = store.tables[("s", "orders")].version
    assert "o_orderkey" in table_statistics(r, "orders").ordered
    assert LocalPlanner(r.catalogs)._scan_key_ordered(scan, [0])
    r.execute("update orders set o_orderkey = 2 where o_orderkey = 1601")
    assert store.tables[("s", "orders")].version > before
    assert "o_orderkey" not in table_statistics(r, "orders").ordered
    assert not LocalPlanner(r.catalogs)._scan_key_ordered(scan, [0])


PLANNED = {
    # (reverse, key_ordered) of every filter, sorted
    # the late lines' order keys in front of `orders` and, reversed, of
    # both subqueries' scans; not the suppliers' in front of `l1`, nor
    # the nation's in front of `supplier`
    "q21": (QUERIES[21], [(False, False), (False, False), (False, True),
                          (True, True), (True, True)]),
    # the orders' keys in front of `lineitem`'s filtered copy; not the
    # customers' in front of `orders` (o_custkey)
    "q3": (QUERIES[3], [(False, False), (False, True)]),
    # the parts' keys stand on the scan, on l_partkey; every other filter
    # of the fact table, the orders' on l_orderkey too, behind a join
    "q9": (QUERIES[9], [(False, False)] * 5),
    "a_projected_key": (
        "select count(*) from (select l_orderkey + 0 as k from lineitem) "
        "join orders on k = o_orderkey where o_custkey < 100", [(False, False)]),
    "two_keys": (
        "select count(*) from lineitem join partsupp on l_partkey = ps_partkey "
        "and l_suppkey = ps_suppkey where ps_supplycost < 10", [(False, False)]),
}


@pytest.mark.parametrize("name", sorted(PLANNED))
def test_the_planner_says_which_filters_stand_on_a_scan_in_key_order(runner, name):
    sql, want = PLANNED[name]
    filters = dynamic_filters(runner, sql)
    got = sorted((d._reverse, d._key_ordered) for d in filters)
    assert got == want, got


def plan_side(name):
    """(a plan node, the filter's key channels, whether the key is a
    column in order that nothing has moved) over `lineitem`'s scan."""
    from trino_tpu.connectors.spi import TableHandle
    from trino_tpu.expr import ir
    from trino_tpu.sql import plan as P

    def scan(table, cols):
        fields = tuple(P.Field(c, T.BIGINT) for c in cols)
        return P.ScanNode("memory", TableHandle("memory", "s", table), tuple(cols), fields)

    lineitem = scan("lineitem", ["l_suppkey", "l_orderkey"])
    positive = ir.Call("gt", (ir.InputRef(0, T.BIGINT), ir.Literal(0, T.BIGINT)), T.BOOLEAN)
    filtered = P.FilterNode(lineitem, positive, lineitem.fields)
    swapped = P.ProjectNode(
        filtered, (ir.InputRef(1, T.BIGINT), ir.InputRef(0, T.BIGINT)),
        (P.Field("k", T.BIGINT), P.Field("s", T.BIGINT)))
    computed = P.ProjectNode(
        lineitem, (ir.Call("add", (ir.InputRef(1, T.BIGINT), ir.Literal(0, T.BIGINT)), T.BIGINT),),
        (P.Field("k", T.BIGINT),))
    supplier = scan("supplier", ["s_suppkey"])
    joined = P.JoinNode("inner", lineitem, supplier, (0,), (0,), None,
                        lineitem.fields + supplier.fields)
    return {
        "the_scan": (lineitem, [1], True),
        "another_column_of_it": (lineitem, [0], False),
        "a_filter_over_it": (filtered, [1], True),
        "a_projection_that_hands_the_column_on": (swapped, [0], True),
        "the_column_beside_it": (swapped, [1], False),
        "a_projection_that_computes": (computed, [0], False),
        "behind_a_join": (joined, [1], False),
        "a_filter_over_a_join": (P.FilterNode(joined, positive, joined.fields), [1], False),
        "two_keys": (lineitem, [1, 0], False),
    }[name]


@pytest.mark.parametrize("name", [
    "the_scan", "another_column_of_it", "a_filter_over_it",
    "a_projection_that_hands_the_column_on", "the_column_beside_it",
    "a_projection_that_computes", "behind_a_join", "a_filter_over_a_join", "two_keys"])
def test_only_a_scans_own_column_with_nothing_moved_is_in_order(runner, name):
    from trino_tpu.sql.local_planner import LocalPlanner

    side, keys, ordered = plan_side(name)
    assert LocalPlanner(runner.catalogs)._scan_key_ordered(side, keys) is ordered


def test_a_filtered_copys_scan_still_gets_the_window(runner, monkeypatch):
    """A pushed-down predicate's copy is a subsequence of the table."""
    monkeypatch.setattr(O, "DF_SET_MAX_SLOTS", 0)
    sql = ("select count(*), sum(l_quantity) from lineitem join orders on l_orderkey = o_orderkey "
           "where l_shipdate > date '1995-03-15' and o_orderdate < date '1992-03-01'")
    result, counts = moved(lambda: runner.execute(sql))
    assert "pushed=[l_shipdate gt" in runner.execute("explain " + sql).rows[0][0]
    assert counts["df_bits_lookup.window"] >= 1 and counts["df_bits_lookup.gather"] == 0
    assert counts["df_bits_window_fallbacks"] == 0
    assert_rows_match(result.rows, oracle_rows(0.01, to_sqlite(sql), tables=["lineitem", "orders"]),
                      ordered=True, abs_tol=1e-2)


def test_a_table_rewritten_in_no_order_keeps_the_gather_and_answers_the_same(runner, monkeypatch):
    """`lineitem` rewritten in l_partkey's order: the connector sees
    that its order keys descend, the plan says nothing, and the filter
    in front of its scan gathers."""
    monkeypatch.setattr(O, "DF_SET_MAX_SLOTS", 0)
    runner.execute("create table memory.s.lineitem_by_part as "
                   "select l_orderkey, l_quantity from tpch.tiny.lineitem order by l_partkey")
    assert table_statistics(runner, "lineitem_by_part").ordered == frozenset()
    sql = ("select count(*), sum(l_quantity) from {} join orders on l_orderkey = o_orderkey "
           "where o_orderdate < date '1992-03-01'")
    result, counts = moved(lambda: runner.execute(sql.format("lineitem_by_part")))
    assert counts["df_bits_lookup.gather"] >= 1 and counts["df_bits_lookup.window"] == 0
    assert counts["df_bits_window_fallbacks"] == 0
    assert_rows_match(
        result.rows, oracle_rows(0.01, to_sqlite(sql.format("lineitem")),
                                 tables=["lineitem", "orders"]), ordered=True, abs_tol=1e-2)


@pytest.mark.parametrize("q, window, gather", [(21, 3, 1), (3, 1, 1), (9, 0, 1)],
                         ids=["q21", "q3", "q9"])
def test_the_statements_count_their_lookups_and_answer_the_same(runner, monkeypatch, q, window,
                                                               gather):
    """At `tiny` every build side fits the key set; with the set's limit
    at nothing each filter takes what it would at size. Q21: the three
    filters on an order key by window, the suppliers' in front of `l1` by
    gather (one nation's one key fills its range: the range). Q3: the
    orders' in front of `lineitem`'s filtered copy by window, a
    segment's customers' in front of `orders` (a fifth of their range,
    on o_custkey) by gather. Q9: the parts' in front of `lineitem`, on
    l_partkey, by gather."""
    plain = runner.execute(QUERIES[q]).rows
    monkeypatch.setattr(O, "DF_SET_MAX_SLOTS", 0)
    result, counts = moved(lambda: runner.execute(QUERIES[q]))
    assert counts["df_filter_path.set"] == 0
    assert counts["df_bits_lookup.window"] == window
    assert counts["df_bits_lookup.gather"] == gather
    assert counts["df_filter_path.bits"] == window + gather
    assert counts["df_bits_window_fallbacks"] == 0
    account = result.stats["account"]
    assert account.get("c.df_bits_lookup.window", 0) == window
    assert account["c.df_bits_lookup.gather"] == gather
    assert account.get("c.df_bits_window_fallbacks", 0) == 0
    assert result.rows == plain
    tables = {21: ["lineitem", "orders", "supplier", "nation"],
              3: ["lineitem", "orders", "customer"],
              9: ["lineitem", "orders", "supplier", "nation", "part", "partsupp"]}[q]
    assert_rows_match(result.rows, oracle_rows(0.01, to_sqlite(QUERIES[q]), tables=tables),
                      ordered=True, abs_tol=1e-2)
