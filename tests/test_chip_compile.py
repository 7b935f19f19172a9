"""Ask the chip's compiler about the main-path kernels, without a chip.

The TPU compiler is installed here and compiles for a *described* v5e
(`jax.experimental.topologies`), so the Pallas kernels that every other
test only ever runs interpreted are compiled for real: a misaligned
slice, an index map the Mosaic compiler cannot legalize or a kernel
that needs more fast memory than it may use fails here, at no chip
time. Nothing runs and nothing is timed — a compile that passes is not
a chip run (chip_smoke.py is).

All cases live in this one file and the topology is described inside a
module-scoped fixture, never at import: only one process may load the
TPU's library, xdist workers import every test file, and only the
worker that is handed this file may load it.

Not here, on purpose: the XLA programs the join/aggregate queries mint
(`ops/join.build_lookup`, `probe_counts`, `ops/groupby.sort_group_reduce`,
`ops/sort.sort_order`) at the default batch of 2^20 rows. The chip's
compiler accepts them, but takes 63-290 s for each here, and still 38 s
for `build_lookup` at 2^16; the seconds are in CHANGES.md (PR 22). One
of them is kept, at the length that compiles in about 10 s: the join
build at 2^14 rows.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

import trino_tpu  # noqa: F401  (x64)

BATCH = 1 << 20  # the engine's default batch_rows
Q1_LIMBS = (8, 8, 1, 4, 4, 4, 8, 1, 4, 4, 4, 8, 8, 8, 8)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry written for a described chip cannot be read back without
    # one: keep these compiles out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "n,value_columns,capacity,limbs",
    [
        pytest.param(BATCH, 9, 16, None, id="1M-9cols-cap16"),
        pytest.param(BATCH, 9, 2048, None, id="1M-9cols-cap2048"),
        pytest.param(1 << 22, 9, 16, None, id="4M-9cols-cap16"),
        # what the parent's G3 handed over at SF1: two 0/1 indicators
        # and sum(l_quantity) over a 160-slot key domain, all as int64
        pytest.param(BATCH, 3, 160, None, id="g3-1M-3cols-cap160"),
        # what G3 hands over since PR 29: the value alone (its counts
        # ride the kernel's live-row count), at the engine's batch and
        # at the mesh plane's chunk
        pytest.param(BATCH, 1, 160, (8,), id="g3-1M-limbs8-cap160"),
        pytest.param(1 << 22, 1, 160, (8,), id="g3-4M-limbs8-cap160"),
        # a masked sum: its 0/1 indicator has one limb and no high word
        pytest.param(BATCH, 2, 160, (1, 8), id="1M-limbs1+8-cap160"),
        # what Q1 hands over since PR 31: five BIGINT sums, two long
        # decimal sums as (4, 4, 4, 8) limb slots behind one indicator
        # each; 22 word rows and the gid row, w8 = 24, 12 slots
        pytest.param(BATCH, 15, 12, Q1_LIMBS, id="q1-1M-cap12"),
    ],
)
def test_grouped_sum_mxu_compiles_for_v5e(
    one_chip, n, value_columns, capacity, limbs
):
    """Legal for Mosaic and inside the default scoped VMEM at the tile
    `_row_tile` picks for the shape (no limit is raised in the call)."""
    from trino_tpu.ops.mxu_groupby import grouped_sum_mxu

    compiled = grouped_sum_mxu.lower(
        _sds((n,), jnp.int32, one_chip),
        tuple(_sds((n,), jnp.int64, one_chip) for _ in range(value_columns)),
        _sds((n,), jnp.bool_, one_chip),
        capacity=capacity, interpret=False, limbs=limbs,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("path", ["mxu", "dense", "q1", "q9"])
def test_agg_ingest_train_compiles_for_v5e(one_chip, path, monkeypatch):
    """The train of issue 26 at the engine's batch: eight batches of 2^20
    rows, the per-batch body once inside a loop (a `while`) that picks
    its batch out of the operands (a `conditional`). `mxu` is G3's shape
    (160 slots, count(*) and a sum) with the Pallas kernel itself inside
    the loop, not its interpreted form: the operator asks
    `jax.default_backend()`, which is the CPU here. `q1` is Q1's (issue
    31): 12 slots, 14 value slots of which eight are two long decimals'
    limb slots behind one validity mask each, through the same kernel
    at `w8` = 24. `q9` is Q9's (issue 39): a dictionary of 25 and a
    BIGINT year counted from 1992, (25 + 1) x (7 + 1) = 208 slots, one
    long decimal's sum."""
    from trino_tpu import types as T
    from trino_tpu.block import Column, Dictionary, RelBatch
    from trino_tpu.exec import operators as O

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dims = {"mxu": (7, 4, 3), "q9": (25, 7)}.get(path, (3, 2))
    lows = (0, 1992) if path == "q9" else None
    dicts = [Dictionary([f"{i}.{j}" for j in range(d)]) for i, d in enumerate(dims)]
    k = len(dims)
    short, longs = T.decimal(12, 2), (T.decimal(34, 4), T.decimal(38, 6))

    def batch():
        cols = [Column(T.VARCHAR, _sds((BATCH,), jnp.int32, one_chip), None, d)
                for d in dicts]
        if path == "q9":
            cols[1] = Column(T.BIGINT, _sds((BATCH,), jnp.int64, one_chip))
            cols.append(Column(longs[0], _sds((BATCH, 2), jnp.int64, one_chip)))
            return RelBatch(cols, _sds((BATCH,), jnp.bool_, one_chip))
        cols.append(Column(short, _sds((BATCH,), jnp.int64, one_chip)))
        if path == "q1":
            cols.extend(Column(short, _sds((BATCH,), jnp.int64, one_chip))
                        for _ in range(2))
            cols.extend(Column(t, _sds((BATCH, 2), jnp.int64, one_chip),
                               _sds((BATCH,), jnp.bool_, one_chip)) for t in longs)
        return RelBatch(cols, None)

    aggs = [O.AggSpec("count_star", None, T.BIGINT),
            O.AggSpec("sum", k, T.decimal(18, 2))]
    if path == "dense":
        aggs.append(O.AggSpec("min", k, short))
    if path == "q1":
        aggs = [O.AggSpec("sum", k, short), O.AggSpec("sum", k + 1, short),
                O.AggSpec("sum", k + 3, T.decimal(38, 4)),
                O.AggSpec("sum", k + 4, T.decimal(38, 6)),
                O.AggSpec("avg", k, short), O.AggSpec("avg", k + 1, short),
                O.AggSpec("avg", k + 2, short), aggs[0]]
    if path == "q9":
        aggs = [O.AggSpec("sum", k, T.decimal(38, 4))]
    compiled = O._agg_ingest_train.lower(
        tuple(batch() for _ in range(O.TRAIN_BATCHES)),
        _sds((), jnp.int32, one_chip),
        tuple(range(k)), tuple(aggs), 256 if path in ("mxu", "q9") else 16, None,
        dims if path == "dense" else None, dims if path != "dense" else None,
        lows,
    ).compile()
    text = compiled.as_text()
    assert "while" in text and "conditional" in text
    assert ("tpu_custom_call" in text) == (path != "dense")
    # the word rows go to the kernel as they are: no stacked plane
    assert "s32[24,1048576]" not in text and "s32[8,1048576]" not in text


def test_slot_count_train_compiles_for_v5e(one_chip):
    """Q13's count under its join at SF10 (issue 45): eight batches of
    2^20 orders, `count(o_orderkey)` by `o_custkey` counted from 1 into
    1,500,000 + 1 slots of a 2^21-slot table, one scatter-add a batch
    inside the train's loop, and the two tables' addition."""
    from trino_tpu import types as T
    from trino_tpu.block import Column, RelBatch
    from trino_tpu.exec import operators as O

    def batch():
        return RelBatch([Column(T.BIGINT, _sds((BATCH,), jnp.int64, one_chip)),
                         Column(T.BIGINT, _sds((BATCH,), jnp.int64, one_chip))],
                        _sds((BATCH,), jnp.bool_, one_chip))

    aggs = (O.AggSpec("count", 0, T.BIGINT),)
    cap = 1 << 21
    lowered = O._agg_ingest_train.lower(
        tuple(batch() for _ in range(O.TRAIN_BATCHES)), _sds((), jnp.int32, one_chip),
        (1,), aggs, cap, None, None, None, (1,), (1_500_000,))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "while" in text and "scatter" in text and "sort(" not in text
    table = ((_sds((cap,), jnp.int64, one_chip),), (_sds((cap,), jnp.bool_, one_chip),),
             _sds((cap,), jnp.bool_, one_chip), (_sds((cap,), jnp.int64, one_chip),),
             (_sds((cap,), jnp.int64, one_chip),))
    added = O._add_slot_states.lower(table, table).compile()
    assert "sort(" not in added.as_text()
    # two states, the sum and a batch in flight fit the chip many times over
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_mxu_join_probe_page_sums_compiles_for_v5e(one_chip):
    """The MXU join-project contraction at its largest key domain. It is
    behind mxu_join_enabled=False today; a refusal here is recorded, not
    repaired, in this PR."""
    from trino_tpu.ops import join as J
    from trino_tpu.ops.mxu_join import MAX_CAPACITY, probe_page_sums

    def s(shape, dtype):
        return _sds(shape, dtype, one_chip)

    ls = jax.tree_util.tree_map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(
            J.build_lookup,
            (s((MAX_CAPACITY,), jnp.int64),),
            (s((MAX_CAPACITY,), jnp.bool_),),
            s((MAX_CAPACITY,), jnp.bool_),
        ),
    )
    try:
        compiled = probe_page_sums.lower(
            ls,
            s((MAX_CAPACITY,), jnp.int32),  # kid_by_pos
            s((MAX_CAPACITY,), jnp.int64),  # distinct_keys
            s((), jnp.int32),  # n_distinct
            s((BATCH,), jnp.int64), s((BATCH,), jnp.bool_),
            s((BATCH,), jnp.bool_),
            (s((BATCH,), jnp.int64), s((BATCH,), jnp.int64)),
            (s((BATCH,), jnp.bool_), s((BATCH,), jnp.bool_)),
            kinds=("sum", "count"), capacity=MAX_CAPACITY,
            use_mxu=True, interpret=False, hash_path=False,
        ).compile()
    except Exception as e:  # the compiler's refusal is the finding
        pytest.xfail(f"v5e compiler refused probe_page_sums: {str(e)[:300]}")
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("key_columns", [1, 2])
def test_join_build_lookup_compiles_for_v5e(one_chip, key_columns):
    """One packed uint64 sort: the program every join's build side runs
    (2^14 rows; see the module docstring for why not 2^20). With two
    integer key columns asked to be exact (PR 35) the sorted words are
    42 bits wide, and a probe of as many rows compiles against them."""
    from trino_tpu.ops.join import build_lookup, probe_counts

    n = 1 << 14
    keys = tuple(_sds((n,), jnp.int64, one_chip) for _ in range(key_columns))
    valids = tuple(_sds((n,), jnp.bool_, one_chip) for _ in range(key_columns))
    lowered = build_lookup.lower(
        keys, valids, _sds((n,), jnp.bool_, one_chip), exact_keys=key_columns > 1)
    compiled = lowered.compile()
    assert "sort" in compiled.as_text()
    if key_columns > 1:
        ls = jax.tree_util.tree_map(
            lambda x: _sds(x.shape, x.dtype, one_chip), lowered.out_info)
        assert ls.hash_bits == 42 and ls.sorted_hash.dtype == jnp.uint64
        probe = probe_counts.lower(ls, keys, valids, _sds((n,), jnp.bool_, one_chip)).compile()
        assert "sort" in probe.as_text()


@pytest.mark.parametrize("bits", [32, 38])
def test_two_level_probe_bounds_compile_for_v5e(one_chip, bits):
    """The bounds of 2^10 queries in a sorted array of 2^16 words (PR 37:
    `sorted_run_bounds` against an array much larger than the batch):
    the two packed sorts over the splitters and the queries only, and
    two gathers of whole 128-word rows, u32 words and the u64 words of
    several key columns."""
    from trino_tpu.ops import join

    dtype = jnp.uint32 if bits <= 32 else jnp.uint64
    assert join.probe_path(1 << 16, 1 << 10, bits) == "blocked"
    compiled = jax.jit(lambda t, q: join.sorted_run_bounds(t, q, bits)).lower(
        _sds((1 << 16,), dtype, one_chip), _sds((1 << 10,), dtype, one_chip)).compile()
    text = compiled.as_text()
    assert "sort" in text and f"[{1 << 10},{join.PROBE_BLOCK}]" in text


def test_packed_parts_are_placed_and_taken_on_v5e(one_chip):
    """`_pack_place` writes a packed part into the buffer of a batch and
    a quarter at a position the host gives, `_pack_take` cuts a batch
    off its front (PR 35): copies, no sort, seconds to compile at the
    engine's batch."""
    from trino_tpu import types as T
    from trino_tpu.block import Column, RelBatch
    from trino_tpu.exec import operators as O

    def batch(n):
        return RelBatch([Column(T.BIGINT, _sds((n,), jnp.int64, one_chip), None, None)
                         for _ in range(6)], _sds((n,), jnp.bool_, one_chip))

    room = batch(BATCH + BATCH // 4)
    for slots in (BATCH // O.DF_PACK_PARTS, 2 * BATCH // O.DF_PACK_PARTS):
        placed = O._pack_place.lower(room, batch(slots), _sds((), jnp.int32, one_chip)).compile()
        assert "dynamic-update-slice" in placed.as_text()
    taken = O._pack_take.lower(room, capacity=BATCH).compile()
    assert taken.memory_analysis().temp_size_in_bytes < 64 << 20


def test_key_set_dynamic_filter_compiles_for_v5e(one_chip):
    """The dynamic filter by key set (exec/operators.py `_df_filter_set`,
    PR 33) at the engine's batch against the largest build side it
    takes: 2^20 x 2^12 equality tests have to stay ONE fused reduction
    (the compare materialised would be 4 GB), which the compiled
    program's scratch shows."""
    from trino_tpu import types as T
    from trino_tpu.block import Column, RelBatch
    from trino_tpu.exec import operators as O

    key = _sds((BATCH,), jnp.int64, one_chip)
    batch = RelBatch([Column(T.BIGINT, key, None, None),
                      Column(T.decimal(12, 2), key, None, None)], None)
    compiled = O._df_filter_set.lower(
        batch, (key, None),
        _sds((O.DF_SET_MAX_SLOTS,), jnp.int32, one_chip),
        _sds((), jnp.bool_, one_chip), _sds((2,), jnp.int64, one_chip),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_key_bits_dynamic_filter_compiles_for_v5e(one_chip):
    """The dynamic filter by key bits (`_df_filter_bits`, PR 35) at the
    engine's batch against the table of 2 M part keys (2^16 words) and
    against the widest it takes (DF_BITS_MAX_DOMAIN: 2^22 words), and
    the scatter that makes the table from a build side of 2^17 slots
    (`_df_bit_table`). One gather a row, no sort: the programs are small
    and compile in seconds. (`_pack_rows`, the sort that carries a
    batch's columns, takes the compiler 171 s at 2^20 rows and 33 s at
    2^14: not here, see the module docstring.)"""
    from trino_tpu import types as T
    from trino_tpu.block import Column, RelBatch
    from trino_tpu.exec import operators as O

    key = _sds((BATCH,), jnp.int64, one_chip)
    batch = RelBatch([Column(T.BIGINT, key, None, None) for _ in range(6)], None)
    scalar = _sds((), jnp.int64, one_chip)
    for n_words in (1 << 16, O.DF_BITS_MAX_DOMAIN // 32):
        compiled = O._df_filter_bits.lower(
            batch, (key, None), _sds((n_words,), jnp.uint32, one_chip),
            scalar, scalar, _sds((2,), jnp.int64, one_chip),
        ).compile()
        assert "gather" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    slots = 1 << 17
    compiled = O._df_bit_table.lower(
        _sds((slots,), jnp.int64, one_chip), _sds((slots,), jnp.bool_, one_chip),
        scalar, n_words=1 << 16,
    ).compile()
    assert "scatter" in compiled.as_text()


@pytest.mark.parametrize("n_words", [128, 1 << 16, 1 << 21],
                         ids=["one_table_row", "q9s_part_keys", "q21s_order_keys"])
def test_key_bits_window_compiles_for_v5e(one_chip, n_words):
    """The key bits looked up a window a block (`_df_filter_bits_window`,
    PR 42) at the engine's batch, `lineitem`'s two keys, against the
    table of Q21's order keys (60 M values, 2^21 words), of Q9's part
    keys (2 M values, 2^16 words: a shape this program meets only on a
    wrong word of the plan's) and against the least table there is (one row of 128
    words): two row gathers a block
    and the pick in one fusion that never lays out its blocks x rows x
    lanes (1 GB as u32), the gather a row kept as the other branch of
    the `cond`. No sort: seconds."""
    from trino_tpu import types as T
    from trino_tpu.block import Column, RelBatch
    from trino_tpu.exec import operators as O

    key = _sds((BATCH,), jnp.int64, one_chip)
    batch = RelBatch([Column(T.BIGINT, key, None, None) for _ in range(2)],
                     _sds((BATCH,), jnp.bool_, one_chip))
    scalar = _sds((), jnp.int64, one_chip)
    compiled = O._df_filter_bits_window.lower(
        batch, (key, None), _sds((n_words,), jnp.uint32, one_chip),
        scalar, scalar, _sds((2,), jnp.int64, one_chip), _sds((), jnp.int32, one_chip),
    ).compile()
    text = compiled.as_text()
    assert "conditional" in text and "gather" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_flag_build_rows_first_candidates_compile_for_v5e(one_chip):
    """`_flag_build_rows` without `out_cap` (PR 40: a semi- or anti-join
    that builds the side it preserves): a probe batch of 2^20 rows of
    `lineitem`'s two keys against Q21's preserved side (2^20 slots of
    s_name, l_orderkey, l_suppkey), the residual on the pairs, the
    flags scattered: gathers and one scatter, about 8 s. With
    `out_cap` (the candidates after the first) it sorts the offsets and
    takes the compiler 45 to 51 s at this size (PERF.md section 6, PR
    40): not here. And the key-bits table of a build side of 2^21 slots
    over `l_orderkey`'s 60 M values (`DF_BITS_PLANNED_MAX_SLOTS`)."""
    from trino_tpu import types as T
    from trino_tpu.block import Column, RelBatch
    from trino_tpu.exec import operators as O
    from trino_tpu.expr import ir
    from trino_tpu.expr.compile import ExprBinder
    from trino_tpu.ops.join import build_lookup

    def column(t, n):
        return Column(t, _sds((n,), jnp.int32 if t.is_string else jnp.int64, one_chip),
                      None, None)

    n = slots = BATCH
    probe = RelBatch([column(T.BIGINT, n), column(T.BIGINT, n)],
                     _sds((n,), jnp.bool_, one_chip))
    build = RelBatch([column(T.VARCHAR, slots), column(T.BIGINT, slots),
                      column(T.BIGINT, slots)], _sds((slots,), jnp.bool_, one_chip))
    lowered = build_lookup.lower(
        (_sds((slots,), jnp.int64, one_chip),), (_sds((slots,), jnp.bool_, one_chip),),
        _sds((slots,), jnp.bool_, one_chip), exact_keys=True)
    ls = jax.tree_util.tree_map(lambda x: _sds(x.shape, x.dtype, one_chip), lowered.out_info)
    differs = ir.Call("ne", (ir.InputRef(2, T.BIGINT), ir.InputRef(4, T.BIGINT)), T.BOOLEAN)
    residual = O.make_residual_fn(ExprBinder(
        [T.VARCHAR, T.BIGINT, T.BIGINT, T.BIGINT, T.BIGINT], [None] * 5).bind(differs))
    run = _sds((n,), jnp.int32, one_chip)
    compiled = O._flag_build_rows.lower(
        ls, probe, build, (_sds((n,), jnp.int64, one_chip),),
        (_sds((n,), jnp.bool_, one_chip),), run, run, _sds((slots,), jnp.bool_, one_chip),
        _sds((2,), jnp.int64, one_chip), pkc=(0,), bkc=(1,), unread=(0, 2),
        residual_fn=residual,
    ).compile()
    text = compiled.as_text()
    # (the chip's compiler makes the flags' scatter of a sort of its
    # own; the program's pair expansion has none)
    assert "scatter" in text and "gather" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    assert O.DF_BITS_PLANNED_MAX_SLOTS >= 1 << 21
    table = O._df_bit_table.lower(
        _sds((1 << 21,), jnp.int64, one_chip), _sds((1 << 21,), jnp.bool_, one_chip),
        _sds((), jnp.int64, one_chip), n_words=1 << 21,
    ).compile()
    assert "scatter" in table.as_text()


def test_mark_build_rows_compiles_for_v5e(one_chip):
    """`_mark_build_rows` (PR 43: a LEFT join that builds the side it
    preserves): a probe batch of 2^20 orders' pairs into the flags of
    Q13's 1.5 M customers (2^21 slots), one scatter; and the fanout-one
    expansion that hands it `bi` and `ok`, the orders' three columns
    passed through and `c_custkey` gathered beside them."""
    from trino_tpu import types as T
    from trino_tpu.block import Column, RelBatch
    from trino_tpu.exec import operators as O
    from trino_tpu.ops.join import build_lookup

    def column(t, n):
        return Column(t, _sds((n,), jnp.int32 if t.is_string else jnp.int64, one_chip),
                      None, None)

    n, slots = BATCH, 1 << 21
    compiled = O._mark_build_rows.lower(
        _sds((slots,), jnp.bool_, one_chip), _sds((n,), jnp.int32, one_chip),
        _sds((n,), jnp.bool_, one_chip)).compile()
    assert "scatter" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    probe = RelBatch([column(T.BIGINT, n), column(T.BIGINT, n), column(T.VARCHAR, n)],
                     _sds((n,), jnp.bool_, one_chip))
    build = RelBatch([column(T.BIGINT, slots)], _sds((slots,), jnp.bool_, one_chip))
    lowered = build_lookup.lower(
        (_sds((slots,), jnp.int64, one_chip),), (_sds((slots,), jnp.bool_, one_chip),),
        _sds((slots,), jnp.bool_, one_chip), exact_keys=True)
    ls = jax.tree_util.tree_map(lambda x: _sds(x.shape, x.dtype, one_chip), lowered.out_info)
    run = _sds((n,), jnp.int32, one_chip)
    pairs = O._expand_pairs_fanout1.lower(
        ls, probe, build, (_sds((n,), jnp.int64, one_chip),),
        (_sds((n,), jnp.bool_, one_chip),), run, run, pkc=(1,), bkc=(0,),
    ).compile()
    assert "gather" in pairs.as_text()


def test_distributed_groupby_step_compiles_for_four_v5e(topo):
    """The partial -> all_to_all -> final aggregation step as one SPMD
    program over the four described chips."""
    from trino_tpu.parallel.exchange import distributed_groupby_step

    mesh = Mesh(np.array(topo.devices), ("shard",))
    rows = NamedSharding(mesh, PartitionSpec("shard"))
    n = 4 * (1 << 14)
    step = distributed_groupby_step(mesh, "shard", 1 << 10, 1)
    compiled = step.lower(
        [_sds((n,), jnp.int64, rows)], [_sds((n,), jnp.bool_, rows)],
        _sds((n,), jnp.bool_, rows), [_sds((n,), jnp.int64, rows)],
    ).compile()
    assert "all-to-all" in compiled.as_text()


def test_mesh_hash_exchange_compiles_for_four_v5e(topo):
    """The mesh plane's hash exchange of a large batch (PR 28): one sort
    by destination that carries the columns, a send block of a quarter
    over the even share, one all-to-all per column. 2^18 rows a chip:
    the sort's compile time grows with the rows, and the 2^22-row chunk
    `sf30.mesh4` streams takes minutes (PERF.md)."""
    from trino_tpu import types as T
    from trino_tpu.block import Column, RelBatch
    from trino_tpu.parallel.mesh_plan import (
        AXIS, _exchange_hash, exchange_block, shard_map,
    )

    mesh = Mesh(np.array(topo.devices), (AXIS,))
    rows = NamedSharding(mesh, PartitionSpec(AXIS))
    cap = 1 << 18
    block = exchange_block(cap, 4)
    assert cap // 4 < block < cap

    def body(key, value, live):
        batch = RelBatch([Column(T.BIGINT, key, None, None),
                          Column(T.BIGINT, value, None, None)], live)
        out, short = _exchange_hash(batch, [0], 4, block)
        return out.columns[1].data, out.live, short[None]

    program = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(PartitionSpec(AXIS),) * 3,
        out_specs=PartitionSpec(AXIS), check_vma=False))
    compiled = program.lower(
        _sds((4 * cap,), jnp.int64, rows), _sds((4 * cap,), jnp.int64, rows),
        _sds((4 * cap,), jnp.bool_, rows),
    ).compile()
    text = compiled.as_text()
    assert "all-to-all" in text and "sort" in text
    assert " scatter(" not in text        # no scatter operation: slices


def test_mesh_bounded_group_reduce_compiles_for_four_v5e(topo, monkeypatch):
    """G3's partial aggregation as the mesh plane runs it on the chips:
    the Pallas MXU group reduce over one 2^22-row chunk a device, inside
    a shard_map (160 key slots; `sf30.mesh4`'s step program)."""
    from trino_tpu.ops import groupby as G
    from trino_tpu.parallel.mesh_plan import AXIS, shard_map

    # the kernel runs interpreted unless the default backend is a TPU;
    # the described chips are not the default backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices), (AXIS,))
    rows = NamedSharding(mesh, PartitionSpec(AXIS))
    cap, dims = 1 << 22, (7, 4, 3)

    def body(a, b, c, q, live):
        ones = jnp.ones_like(live)
        _gk, _gv, used, sums, counts, _n, _ovf = G.mxu_group_reduce(
            (a, b, c), (ones, ones, ones), live,
            (live.astype(jnp.int64), q), (None, None), ("sum", "sum"),
            dims, 256,
        )
        return sums[1], counts[0], used

    program = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(PartitionSpec(AXIS),) * 5,
        out_specs=PartitionSpec(AXIS), check_vma=False))
    codes = _sds((4 * cap,), jnp.int32, rows)
    compiled = program.lower(
        codes, codes, codes, _sds((4 * cap,), jnp.int64, rows),
        _sds((4 * cap,), jnp.bool_, rows),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
