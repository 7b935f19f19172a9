"""The slot-addressed COUNT reduce for key domains past what the MXU
reduce takes (issue 45): `ops/groupby.slot_group_reduce` against plain
python counting, and `choose_bounded_reduce`'s table with its fourth
word. CPU answers only; what a scatter-add costs is a chip reading
(PERF.md section 6, PR 45)."""

import collections

import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops import groupby as G

N = 2048
INT64 = np.dtype(np.int64)
FLOAT64 = np.dtype(np.float64)


def columns(seed, **over):
    """One batch: two keys, a live mask, two counted columns' validity."""
    rng = np.random.default_rng(seed)
    cols = {
        "a": rng.integers(0, 3000, N).astype(np.int64),
        "a_valid": np.ones(N, bool),
        "b": rng.integers(0, 3, N).astype(np.int32),
        "b_valid": np.ones(N, bool),
        "live": np.ones(N, bool),
        "x_valid": rng.random(N) > 0.4,
    }
    cols.update(over)
    return cols


def rng_mask(seed, keep):
    return np.random.default_rng(seed).random(N) < keep


CASES = {
    # name: (columns, key names, dims, lows, out_capacity)
    "plain": (columns(1), ("a",), (3000,), None, 4096),
    "null-keys": (columns(2, a_valid=rng_mask(20, 0.8)), ("a",), (3000,), None, 4096),
    "low-not-zero": (columns(3, a=np.random.default_rng(30).integers(-700, 2300, N)),
                     ("a",), (3000,), (-700,), 4096),
    "two-keys-mixed-radix": (
        columns(4, a_valid=rng_mask(40, 0.9), b_valid=rng_mask(41, 0.9)),
        ("a", "b"), (3000, 3), None, 16384),
    "two-keys-lows": (
        columns(5, a=np.random.default_rng(50).integers(100, 3100, N),
                b=np.random.default_rng(51).integers(7, 10, N).astype(np.int32)),
        ("b", "a"), (3, 3000), (7, 100), 16384),
    "dead-rows": (columns(6, live=rng_mask(60, 0.5)), ("a",), (3000,), None, 4096),
    "dead-rows-hold-anything": (
        columns(7, live=rng_mask(70, 0.5),
                a=np.where(rng_mask(70, 0.5), np.random.default_rng(71).integers(0, 3000, N),
                           10**12)),
        ("a",), (3000,), None, 4096),
    "empty-batch": (columns(8, live=np.zeros(N, bool)), ("a",), (3000,), None, 4096),
    "no-rows-at-all": ({k: v[:0] for k, v in columns(9).items()}, ("a",), (3000,), None, 4096),
    "table-as-wide-as-the-domain": (columns(10), ("a",), (3000,), None, 3001),
}


def reduce(cols, keys, dims, lows, cap, value_valids, valid_of=None):
    live = jnp.asarray(cols["live"])
    values = [live.astype(jnp.int64)] * len(value_valids)
    return G.slot_group_reduce(
        [jnp.asarray(cols[k]) for k in keys],
        [jnp.asarray(cols[k + "_valid"]) for k in keys],
        live, values, tuple(value_valids), ("count",) * len(value_valids),
        dims, cap, valid_of=valid_of, lows=lows,
    )


def groups_of(out, n_keys):
    """{key tuple (None a NULL): (count per value slot...)} of the used slots."""
    gk, gv, used, vals, cnts, n_groups, flag = out
    used = np.asarray(used)
    assert int(n_groups) == used.sum()
    assert all(np.array_equal(np.asarray(v), np.asarray(c)) for v, c in zip(vals, cnts))
    assert all(np.asarray(v).dtype == np.int64 for v in vals)
    got = {}
    for slot in np.nonzero(used)[0]:
        key = tuple(int(np.asarray(gk[i])[slot]) if np.asarray(gv[i])[slot] else None
                    for i in range(n_keys))
        assert key not in got
        got[key] = tuple(int(np.asarray(v)[slot]) for v in vals)
    return got, bool(flag)


def counted(cols, keys):
    """(count(*), count(x)) a group, by python's own counting."""
    want = collections.defaultdict(lambda: [0, 0])
    for i in np.nonzero(cols["live"])[0]:
        key = tuple(int(cols[k][i]) if cols[k + "_valid"][i] else None for k in keys)
        want[key][0] += 1
        want[key][1] += int(cols["x_valid"][i])
    return {k: tuple(v) for k, v in want.items()}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_scatter_counts_what_python_counts(name):
    """count(*) and count(x) a group: NULL keys a group of their own,
    a `low` that is not 0, two keys in mixed radix (either order), dead
    rows (which may hold anything), no live row, no row."""
    cols, keys, dims, lows, cap = CASES[name]
    out = reduce(cols, keys, dims, lows, cap, (None, jnp.asarray(cols["x_valid"])))
    got, flag = groups_of(out, len(keys))
    assert not flag
    assert got == counted(cols, keys)
    assert out[2].shape == (cap,) and out[3][0].shape == (cap,)
    for k, key_col in zip(keys, out[0]):
        assert key_col.dtype == cols[k].dtype


def test_a_group_whose_counted_values_are_all_null_exists_with_count_0():
    """`used` is "a live row had this key", not "the count is above 0"."""
    cols = columns(11)
    cols["x_valid"] = cols["x_valid"] & (cols["a"] % 5 != 0)
    out = reduce(cols, ("a",), (3000,), None, 4096, (jnp.asarray(cols["x_valid"]),))
    got, _ = groups_of(out, 1)
    want = {k: v[1:] for k, v in counted(cols, ("a",)).items()}
    assert got == want
    fifths = [k for k in got if k[0] % 5 == 0]
    assert len(fifths) > 100 and all(got[k] == (0,) for k in fifths)


@pytest.mark.parametrize("stray", [3000, -1, 2**32 + 5, -(2**40)])
def test_a_live_valid_key_outside_the_domain_raises_the_flag(stray):
    """dense_group_reduce's contract: a NULL or a dead row may hold
    anything; a live valid value outside [low, low + d) flags, also one
    that would fold into the domain once narrowed to 32 bits."""
    cols = columns(12, live=rng_mask(120, 0.9), a_valid=rng_mask(121, 0.9))
    quiet = np.nonzero(~cols["live"] | ~cols["a_valid"])[0]
    cols["a"] = cols["a"].copy()
    cols["a"][quiet] = stray
    out = reduce(cols, ("a",), (3000,), None, 4096, (None,))
    got, flag = groups_of(out, 1)
    assert not flag and got == {k: v[:1] for k, v in counted(cols, ("a",)).items()}
    loud = np.nonzero(cols["live"] & cols["a_valid"])[0][:1]
    cols["a"][loud] = stray
    assert groups_of(reduce(cols, ("a",), (3000,), None, 4096, (None,)), 1)[1]


def test_value_slots_that_share_a_validity_array_share_one_scatter():
    """`valid_of` (shared_valids) names the first slot with the same
    validity array: a second scatter-add is traced only for a second
    mask, and a slot without one reads the live rows' table."""
    import jax

    cols = columns(13)
    x, y = jnp.asarray(cols["x_valid"]), jnp.asarray(rng_mask(130, 0.7))
    vvalids = (None, x, x, y, None)
    valid_of = G.shared_valids(vvalids)
    assert valid_of == (0, 1, 1, 3, 4)
    out = reduce(cols, ("a",), (3000,), None, 4096, vvalids, valid_of)
    got, _ = groups_of(out, 1)
    base = counted(cols, ("a",))
    with_y = counted(dict(cols, x_valid=np.asarray(y)), ("a",))
    assert got == {k: (v[0], v[1], v[1], with_y[k][1], v[0]) for k, v in base.items()}
    keys = [jnp.asarray(cols["a"])]
    args = (keys, [jnp.asarray(cols["a_valid"])], jnp.asarray(cols["live"]),
            [jnp.ones(N, jnp.int64)] * 5, vvalids)
    text = str(jax.make_jaxpr(lambda a: G.slot_group_reduce.__wrapped__(
        *a, ("count",) * 5, (3000,), 4096, valid_of=valid_of))(args))
    assert text.count("scatter-add") == 3          # live rows, x, y


def test_only_counts_are_taken():
    cols = columns(14)
    with pytest.raises(AssertionError):
        G.slot_group_reduce(
            [jnp.asarray(cols["a"])], [jnp.asarray(cols["a_valid"])],
            jnp.asarray(cols["live"]), [jnp.asarray(cols["a"])], (None,), ("sum",),
            (3000,), 4096)


# -- the chooser ----------------------------------------------------------------

COUNTS = ("count",)
SLOT = G.SLOT_MAX_SLOTS
CHOICES = [
    # (bound, reducers, dtypes, mxu, dense_sums_only) -> path
    # past the MXU reduce's limit counts alone take the scatter, on any backend
    ((2049, COUNTS, [INT64], False, False), "slot"),
    ((2049, COUNTS, [INT64], True, False), "slot"),
    ((1_500_002, COUNTS, [INT64], True, False), "slot"),
    ((5004, ("count", "count"), [INT64, FLOAT64], False, False), "slot"),
    ((SLOT, COUNTS, [INT64], True, False), "slot"),
    ((SLOT + 1, COUNTS, [INT64], True, False), "sort"),
    ((60_000_002, COUNTS, [INT64], True, False), "sort"),
    # any sum, minimum or maximum there keeps the sort path
    ((2049, ("count", "sum"), [INT64, INT64], True, False), "sort"),
    ((1_500_002, ("sum",), [INT64], True, False), "sort"),
    ((5004, ("count", "min"), [INT64, INT64], False, False), "sort"),
    ((5004, ("max",), [INT64], True, False), "sort"),
    ((5004, ("count", "first"), [INT64, INT64], True, False), "sort"),
    ((5004, (), [], True, False), "sort"),
    # the mesh plane has no such reduce: its answers are what they were
    ((2049, COUNTS, [INT64], True, True), "sort"),
    ((100_001, COUNTS, [INT64], False, True), "sort"),
    # at or under 2,048 slots: today's answers, for the bounds the
    # existing tests use (32, 124, 208, 2,048; 4 and 160 slots of G3)
    ((2048, COUNTS, [INT64], True, False), "mxu"),
    ((2048, COUNTS, [INT64], False, False), "sort"),
    ((2048, COUNTS, [INT64], True, True), "mxu"),
    ((208, ("sum", "count"), [INT64, INT64], True, False), "mxu"),
    ((208, ("sum", "count"), [INT64, INT64], False, False), "sort"),
    ((124, ("sum", "min"), [INT64, INT64], True, False), "sort"),
    ((32, ("sum", "count", "min", "max"), [INT64] * 4, False, False), "dense"),
    ((32, ("sum", "count"), [INT64, INT64], True, False), "mxu"),
    ((32, ("sum", "count"), [INT64, INT64], True, True), "mxu"),
    ((32, ("sum", "min"), [INT64, INT64], True, True), "sort"),
    ((4, COUNTS, [INT64], True, False), "dense"),
    ((4, ("sum", "count"), [INT64, INT64], True, False), "dense"),
    ((160, ("sum", "count"), [INT64, INT64], True, False), "mxu"),
    ((64, ("sum",), [FLOAT64], True, False), "dense"),
    ((65, ("sum",), [FLOAT64], True, False), "sort"),
]


@pytest.mark.parametrize("args,path", CHOICES,
                         ids=[f"{a[0]}-{'+'.join(a[1]) or 'none'}-"
                              f"{'mxu' if a[3] else 'nomxu'}{'-mesh' if a[4] else ''}"
                              for a, _ in CHOICES])
def test_the_chooser_answers_a_fourth_word_for_counts_past_the_mxu_limit(args, path):
    bound, reducers, dtypes, mxu, dense_sums_only = args
    assert G.choose_bounded_reduce(
        bound, reducers, dtypes, mxu=mxu, dense_sums_only=dense_sums_only) == path


def test_the_two_limits_stand_beside_each_other():
    assert G.DENSE_MAX_SLOTS < G.MXU_MAX_SLOTS < G.SLOT_MAX_SLOTS
    assert G.SLOT_MAX_SLOTS >= 1 << 21           # TPC-H SF10's customers fit
