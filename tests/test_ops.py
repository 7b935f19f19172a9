"""Kernel unit tests vs numpy oracles — analogue of Trino's operator
unit tests (TestGroupByHash, TestHashJoinOperator etc., SURVEY.md §4.1)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from trino_tpu.ops import groupby, join, sort
from trino_tpu.ops.hashing import hash32, hash64, partition_of


def test_hash_deterministic_and_spread():
    x = jnp.arange(1000, dtype=jnp.int64)
    h1 = np.asarray(hash32([x], [jnp.ones(1000, bool)]))
    h2 = np.asarray(hash32([x], [jnp.ones(1000, bool)]))
    assert (h1 == h2).all()
    # good spread into 8 partitions
    parts = np.asarray(partition_of(jnp.asarray(h1), 8))
    counts = np.bincount(parts, minlength=8)
    assert counts.min() > 60  # roughly uniform

    h64 = np.asarray(hash64([x], [jnp.ones(1000, bool)]))
    assert len(np.unique(h64)) == 1000
    assert (h64 >= 0).all()


def _group_oracle(keys, mask):
    seen = {}
    gids = []
    for i in range(len(mask)):
        if not mask[i]:
            gids.append(None)
            continue
        k = tuple(col[i] for col in keys)
        gids.append(seen.setdefault(k, len(seen)))
    return gids, len(seen)


@pytest.mark.parametrize("n,card", [(64, 4), (512, 100), (256, 256)])
def test_assign_group_ids_matches_oracle(n, card):
    rng = np.random.default_rng(7)
    k1 = rng.integers(0, card, n).astype(np.int64)
    k2 = rng.integers(0, 3, n).astype(np.int32)
    mask = rng.random(n) > 0.1
    C = 1024
    gid, table, overflow = groupby.assign_group_ids(
        [jnp.asarray(k1), jnp.asarray(k2)],
        [jnp.ones(n, bool), jnp.ones(n, bool)],
        jnp.asarray(mask),
        C,
    )
    assert not bool(overflow)
    gid = np.asarray(gid)
    oracle_gids, n_groups = _group_oracle([k1, k2], mask)
    assert int(table.num_groups()) == n_groups
    # same key -> same gid; different keys -> different gid
    remap = {}
    for i in range(n):
        if not mask[i]:
            assert gid[i] == C
            continue
        og = oracle_gids[i]
        if og in remap:
            assert gid[i] == remap[og], f"row {i}"
        else:
            assert gid[i] not in remap.values()
            remap[og] = gid[i]
    # table stores the right keys at each slot
    sk1 = np.asarray(table.slot_keys[0])
    for i in range(n):
        if mask[i]:
            assert sk1[gid[i]] == k1[i]


def test_group_ids_null_is_its_own_group():
    k = jnp.asarray([1, 1, 1, 5], dtype=jnp.int64)
    v = jnp.asarray([True, False, False, True])
    gid, table, _ = groupby.assign_group_ids(
        [k], [v], jnp.ones(4, bool), 16
    )
    gid = np.asarray(gid)
    assert gid[1] == gid[2]  # NULL == NULL for grouping
    assert gid[0] != gid[1] and gid[0] != gid[3]
    assert int(table.num_groups()) == 3


def test_group_overflow_flag():
    n = 64
    k = jnp.arange(n, dtype=jnp.int64)
    gid, table, overflow = groupby.assign_group_ids(
        [k], [jnp.ones(n, bool)], jnp.ones(n, bool), 32
    )
    assert bool(overflow)


def test_segment_aggregates():
    gid = jnp.asarray([0, 1, 0, 2, 16, 1], dtype=jnp.int32)  # 16 = dead
    vals = jnp.asarray([1.0, 2.0, 3.0, 4.0, 100.0, 6.0])
    w = jnp.asarray([True, True, True, True, False, True])
    s = np.asarray(groupby.seg_sum(gid, vals, w, 16))
    assert s[0] == 4.0 and s[1] == 8.0 and s[2] == 4.0
    c = np.asarray(groupby.seg_count(gid, w, 16))
    assert c[0] == 2 and c[1] == 2 and c[2] == 1
    mn = np.asarray(groupby.seg_min(gid, vals, w, 16))
    mx = np.asarray(groupby.seg_max(gid, vals, w, 16))
    assert mn[0] == 1.0 and mx[1] == 6.0


def _join_oracle(bkeys, blive, pkeys, plive):
    out = set()
    for i, (pk, pl) in enumerate(zip(pkeys, plive)):
        if not pl:
            continue
        for j, (bk, bl) in enumerate(zip(bkeys, blive)):
            if bl and bk == pk:
                out.add((i, j))
    return out


@pytest.mark.parametrize("nb,np_,card", [(32, 32, 8), (128, 256, 20), (64, 64, 1000)])
def test_join_probe_matches_oracle(nb, np_, card):
    rng = np.random.default_rng(3)
    bk = rng.integers(0, card, nb).astype(np.int64)
    pk = rng.integers(0, card, np_).astype(np.int64)
    blive = rng.random(nb) > 0.2
    plive = rng.random(np_) > 0.2
    ls = join.build_lookup(
        [jnp.asarray(bk)], [jnp.ones(nb, bool)], jnp.asarray(blive)
    )
    lo, counts, total = join.probe_counts(
        ls, [jnp.asarray(pk)], [jnp.ones(np_, bool)], jnp.asarray(plive)
    )
    cap = max(16, 1 << int(np.ceil(np.log2(max(1, int(total))))))
    pi, bi, ok = join.expand_matches(
        ls, [jnp.asarray(pk)], [jnp.ones(np_, bool)], lo, counts, cap
    )
    got = {
        (int(p), int(b))
        for p, b, o in zip(np.asarray(pi), np.asarray(bi), np.asarray(ok))
        if o
    }
    assert got == _join_oracle(bk, blive, pk, plive)


def test_join_null_keys_never_match():
    bk = jnp.asarray([1, 2], dtype=jnp.int64)
    bv = jnp.asarray([True, False])
    pk = jnp.asarray([1, 2], dtype=jnp.int64)
    pv = jnp.asarray([False, True])
    ls = join.build_lookup([bk], [bv], jnp.ones(2, bool))
    lo, counts, total = join.probe_counts(ls, [pk], [pv], jnp.ones(2, bool))
    assert int(total) == 0


@pytest.mark.parametrize("case", ["dense", "negative_and_sparse", "int32_against_int64",
                                  "span_too_wide", "nulls_and_dead", "empty_build"])
def test_exact_keys_count_matches_and_never_candidates(case):
    """`build_lookup(exact_keys=True)` (PR 35): ONE integer key whose
    values span under 2^32 is sorted by its distance from the least, so
    `probe_counts` counts each probe row's true matches: no second
    candidate from a 32-bit hash both keys happen to share, which at
    millions of build keys kept every probe batch off the fanout-one
    path. A probe key outside the build's range (far outside too) finds
    nothing; a wider span falls back to the hash, whose counts are an
    upper bound the verify still culls."""
    rng = np.random.default_rng(35)
    nb, npr = 5000, 20000
    bvalid, blive = np.ones(nb, bool), np.ones(nb, bool)
    pdtype = np.int64
    if case == "dense":
        bk = rng.permutation(np.arange(100, 100 + nb)).astype(np.int64)
        pk = rng.integers(0, 6000, npr)
    elif case == "negative_and_sparse":
        bk = rng.choice(np.arange(-3_000_000, 3_000_000, 7), nb, replace=False).astype(np.int64)
        pk = np.concatenate([rng.choice(bk, npr - 4), [-2**63, 2**63 - 1, -3_000_001, 3_000_001]])
    elif case == "int32_against_int64":
        bk = rng.integers(0, 3000, nb).astype(np.int64)          # repeats: true fan-out
        pk, pdtype = rng.integers(-5, 3005, npr), np.int32
    elif case == "span_too_wide":
        bk = rng.choice(np.arange(0, 2**40, 2**20 + 1), nb, replace=False).astype(np.int64)
        pk = np.concatenate([rng.choice(bk, npr // 2), rng.integers(0, 2**40, npr // 2)])
    elif case == "nulls_and_dead":
        bk = rng.integers(0, 4000, nb).astype(np.int64)
        bvalid, blive = rng.random(nb) > 0.1, rng.random(nb) > 0.1
        bk[~blive] = 2**62                                        # a dead slot's value bounds nothing
        pk = rng.integers(0, 4000, npr)
    else:
        bk, blive = np.zeros(nb, np.int64), np.zeros(nb, bool)
        pk = rng.integers(-10, 10, npr)
    pk = pk.astype(pdtype)
    pvalid = rng.random(npr) > 0.05
    plive = rng.random(npr) > 0.05
    ls = join.build_lookup([jnp.asarray(bk)], [jnp.asarray(bvalid)], jnp.asarray(blive),
                           exact_keys=True)
    lo, counts, total = join.probe_counts(
        ls, [jnp.asarray(pk)], [jnp.asarray(pvalid)], jnp.asarray(plive))
    usable = bk[bvalid & blive]
    values, multiplicity = np.unique(usable, return_counts=True)
    at = np.searchsorted(values, pk.astype(np.int64))
    at[at == len(values)] = 0
    true_counts = np.where(
        (len(values) > 0) & (values[at] == pk if len(values) else False), multiplicity[at]
        if len(values) else 0, 0) * (pvalid & plive)
    exact = bool(ls.exact_base[1])
    assert exact == (case not in ("span_too_wide", "empty_build"))
    if exact:
        assert np.asarray(counts).tolist() == true_counts.tolist()
    else:
        assert (np.asarray(counts) >= true_counts).all()
    assert int(total) == int(np.asarray(counts).sum())
    cap = max(16, 1 << int(np.ceil(np.log2(max(1, int(total))))))
    pi, bi, ok = join.expand_matches(
        ls, [jnp.asarray(pk)], [jnp.asarray(pvalid)], lo, counts, cap)
    got = sorted((int(p), int(b)) for p, b, o in zip(np.asarray(pi), np.asarray(bi), np.asarray(ok)) if o)
    want = sorted((p, b) for p in np.nonzero(pvalid & plive)[0]
                  for b in np.nonzero((bk == pk[p]) & bvalid & blive)[0]) if case != "int32_against_int64" else None
    if want is not None:
        assert got == want
    else:
        assert len(got) == int(true_counts.sum())
    # not asked: the hash, as before
    plain = join.build_lookup([jnp.asarray(bk)], [jnp.asarray(bvalid)], jnp.asarray(blive))
    assert plain.exact_base is None and plain.hash_bits == 32


@pytest.mark.parametrize("case", ["pairs", "negative_and_int32", "too_wide", "nulls_and_dead",
                                  "empty_build", "three_columns", "probe_in_chunks"])
def test_exact_keys_of_several_columns_count_matches_and_never_candidates(case):
    """`build_lookup(exact_keys=True)` on SEVERAL integer key columns (PR
    35: `partsupp`'s (partkey, suppkey)): the columns' distances from
    their least values make one word of up to 42 bits (what the build
    side's slot numbers leave of 64), the build side is sorted by it and
    `probe_counts` counts true matches; a probe key with a column outside
    the build side's values finds nothing; columns whose values take more
    bits than the word has fall back to the hash, whose counts are an
    upper bound the verify still culls; a probe batch too long for a
    42-bit value, two tag bits and a position in 64 is bounded in
    chunks."""
    rng = np.random.default_rng(3535)
    nb, npr = 6000, 20000
    columns = 2
    spans = [2_000_000, 100_000]
    lows = [1, 1]
    dtypes = [np.int64, np.int64]
    if case == "negative_and_int32":
        lows, dtypes = [-1_000_000, -50_000], [np.int64, np.int32]
    elif case == "too_wide":
        spans = [2**30, 2**20]
    elif case == "three_columns":
        columns, spans, lows, dtypes = 3, [5000, 300, 7], [10, -3, 0], [np.int64] * 3
    elif case == "probe_in_chunks":
        npr = (1 << 20) + 5000
    bks = [rng.integers(lo, lo + span, nb).astype(np.int64) for lo, span in zip(lows, spans)]
    if case in ("pairs", "three_columns", "probe_in_chunks"):
        bks = [np.concatenate([k, k[:50]]) for k in bks]       # repeats: true fan-out
    nb = len(bks[0])
    bvalid = [np.ones(nb, bool) for _ in bks]
    blive = np.ones(nb, bool)
    if case == "nulls_and_dead":
        bvalid = [rng.random(nb) > 0.1 for _ in bks]
        blive = rng.random(nb) > 0.1
        bks[0][~blive] = 2**62                                 # a dead slot's value bounds nothing
    elif case == "empty_build":
        blive = np.zeros(nb, bool)
    hit = rng.integers(0, nb, npr)
    pks = [np.where(rng.random(npr) < 0.5, k[hit], rng.integers(lo - 5, lo + span + 5, npr))
           for k, lo, span in zip(bks, lows, spans)]
    same = rng.random(npr) < 0.5                               # whole build keys, half the time
    pks = [np.where(same, k[hit], p).astype(dt) for k, p, dt in zip(bks, pks, dtypes)]
    pks[0][:3] = [-2**31, 2**31 - 1, 0] if dtypes[0] is np.int32 else [-2**63, 2**63 - 1, 0]
    pvalid = [rng.random(npr) > 0.03 for _ in pks]
    plive = rng.random(npr) > 0.05
    ls = join.build_lookup([jnp.asarray(k) for k in bks], [jnp.asarray(v) for v in bvalid],
                           jnp.asarray(blive), exact_keys=True)
    assert ls.hash_bits == 42 and ls.sorted_hash.dtype == jnp.uint64
    lo, counts, total = join.probe_counts(
        ls, [jnp.asarray(k) for k in pks], [jnp.asarray(v) for v in pvalid], jnp.asarray(plive))
    busable = blive & np.logical_and.reduce(bvalid)
    pusable = plive & np.logical_and.reduce(pvalid)
    have = {}
    for i in np.nonzero(busable)[0]:
        have.setdefault(tuple(int(k[i]) for k in bks), []).append(int(i))
    true_counts = np.array([len(have.get(tuple(int(k[i]) for k in pks), ())) if pusable[i] else 0
                            for i in range(npr)])
    exact = bool(ls.exact_base[2])
    assert exact == (case not in ("too_wide", "empty_build"))
    if exact:
        assert np.array_equal(np.asarray(counts), true_counts)
    else:
        assert (np.asarray(counts) >= true_counts).all()
    assert int(total) == int(np.asarray(counts).sum())
    if case == "probe_in_chunks":
        return
    cap = max(16, 1 << int(np.ceil(np.log2(max(1, int(total))))))
    pi, bi, ok = join.expand_matches(
        ls, [jnp.asarray(k) for k in pks], [jnp.asarray(v) for v in pvalid], lo, counts, cap)
    got = sorted((int(p), int(b)) for p, b, o in zip(np.asarray(pi), np.asarray(bi), np.asarray(ok)) if o)
    assert got == sorted((p, b) for p in np.nonzero(pusable)[0]
                         for b in have.get(tuple(int(k[p]) for k in pks), ()))
    # a build side of so many slots that they leave a word of 32 bits at most: the hash
    with pytest.raises(TypeError):
        join.probe_counts(ls, [jnp.asarray(pks[0])], [jnp.asarray(pvalid[0])], jnp.asarray(plive))


def _parent_sorted_run_bounds(sorted_arr, q, value_bits=32):
    """`ops/join.sorted_run_bounds` as it was before PR 37, letter for
    letter: the one-level bounds the two-level ones must equal, and the
    program a shape below the threshold must still trace."""
    B = sorted_arr.shape[0]
    N = q.shape[0]
    if B == 0 or N == 0:
        z = jnp.zeros(N, jnp.int32)
        return z, z
    id_bits = max(int(N - 1).bit_length(), 1)
    if value_bits + 2 + id_bits > 64:
        if value_bits <= 32:
            raise ValueError("too many queries")
        step = 1 << (62 - value_bits)
        bounds = [
            _parent_sorted_run_bounds(sorted_arr, q[at:at + step], value_bits)
            for at in range(0, N, step)
        ]
        return (jnp.concatenate([lo for lo, _ in bounds]),
                jnp.concatenate([hi for _, hi in bounds]))
    vshift = jnp.uint64(2 + id_bits)
    tshift = jnp.uint64(id_bits)
    qv = q.astype(jnp.uint64)
    tv = sorted_arr.astype(jnp.uint64)
    iota = jnp.arange(N, dtype=jnp.uint64)
    t0 = jnp.uint64(0) << tshift
    t1 = jnp.uint64(1) << tshift
    t2 = jnp.uint64(2) << tshift
    words = jnp.concatenate(
        [
            (qv << vshift) | t0 | iota,
            (tv << vshift) | t1,
            (qv << vshift) | t2 | iota,
        ]
    )
    ws = jnp.sort(words)
    tag = (ws >> tshift) & jnp.uint64(3)
    is_table = tag == jnp.uint64(1)
    bp = jnp.cumsum(is_table.astype(jnp.int32)).astype(jnp.uint64)
    qid = ws & jnp.uint64((1 << id_bits) - 1)
    rid = jnp.where(is_table, jnp.uint64(N), qid)
    is_hi = (tag == jnp.uint64(2)).astype(jnp.uint64)
    res = jnp.sort(
        (rid << jnp.uint64(33)) | (is_hi << jnp.uint64(32)) | bp
    )
    pair = (res[: 2 * N] & jnp.uint64(0xFFFFFFFF)).astype(jnp.int32)
    pair = pair.reshape(N, 2)
    return pair[:, 0], pair[:, 1]


@pytest.mark.parametrize("case", [
    "unique", "runs_cross_a_block_edge", "one_key_over_two_blocks", "ragged_last_block",
    "below_the_least_and_above_the_greatest", "words_of_38_bits", "queries_in_chunks",
    "one_query", "three_levels"])
def test_two_level_bounds_equal_the_one_level_bounds_and_numpy(case):
    """`sorted_run_bounds` against an array much larger than the batch
    (PR 37): the queries are placed among every PROBE_BLOCK-th word by
    the two packed sorts, then inside one block by comparison. The same
    (lo, hi) as the one-level bounds and as `numpy.searchsorted`, for
    runs that cross blocks and fill them, with no flag and no fallback."""
    W = join.PROBE_BLOCK
    rng = np.random.default_rng(37)
    bits, dtype, nq = 32, np.uint32, 64
    B = 40 * W
    if case == "unique":
        t = np.sort(rng.choice(1 << 20, B, replace=False))
    elif case == "runs_cross_a_block_edge":
        t = np.sort(rng.integers(0, B // 24, B))            # runs of about 24: most blocks end inside one
    elif case == "one_key_over_two_blocks":
        t = np.sort(np.concatenate([rng.integers(0, 1000, B - 3 * W - 9), np.full(3 * W + 9, 500)]))
    elif case == "ragged_last_block":
        B = 40 * W + 37
        t = np.sort(rng.integers(0, 3000, B))
    elif case == "below_the_least_and_above_the_greatest":
        t = np.sort(rng.integers(1000, 2000, B))
    elif case == "words_of_38_bits":
        bits, dtype = 38, np.uint64
        t = np.sort(rng.integers(0, 1 << 38, B))
        t[W - 2:W + 3] = t[W]                               # a run over the first edge
    elif case == "queries_in_chunks":
        bits, dtype, nq = 58, np.uint64, 50                 # 16 queries a chunk: each chunk decides
        t = np.sort(rng.integers(0, 1 << 58, B))
    elif case == "one_query":
        nq = 1
        t = np.sort(rng.integers(0, 3000, B))
    else:
        B, nq = 4 * W * W * 4 + 5, 4                         # the splitters are blocked again
        t = np.sort(rng.integers(0, 50_000, B))
    t = t.astype(dtype)
    q = np.concatenate([rng.choice(t, nq - nq // 2), rng.integers(0, int(t[-1]) + 2, nq // 2)])
    if case == "below_the_least_and_above_the_greatest":
        q[:4] = [0, 999, 2000, (1 << 32) - 1]
    elif case != "one_query":
        q[:4] = [t[0], t[-1], t[W], t[W - 1]]
    q = q.astype(dtype)
    assert join.probe_path(B, nq, bits) == "blocked"
    lo, hi = join.sorted_run_bounds(jnp.asarray(t), jnp.asarray(q), bits)
    one_lo, one_hi = _parent_sorted_run_bounds(jnp.asarray(t), jnp.asarray(q), bits)
    assert lo.dtype == hi.dtype == jnp.int32
    assert np.array_equal(np.asarray(lo), np.asarray(one_lo))
    assert np.array_equal(np.asarray(hi), np.asarray(one_hi))
    assert np.array_equal(np.asarray(lo), np.searchsorted(t, q, side="left"))
    assert np.array_equal(np.asarray(hi), np.searchsorted(t, q, side="right"))
    text = str(jax.make_jaxpr(lambda a, b: join.sorted_run_bounds(a, b, bits))(t, q))
    assert text.count("gather") >= (4 if case == "three_levels" else 2)


@pytest.mark.parametrize("exact_keys", [False, True], ids=["hashed", "exact"])
def test_two_level_probe_counts_keep_the_sentinels_apart(exact_keys, monkeypatch):
    """Through `build_lookup` and `probe_counts` at shapes that take the
    two levels: a NULL or dead probe row (`_NO_MATCH_HASH`) finds
    nothing, a dead or NULL-keyed build row (`_DEAD_BUILD_HASH`, which
    is also what pads the last block) is never found, and (lo, counts,
    total) equal the one-level path's."""
    rng = np.random.default_rng(3737)
    nb, npr = 64 * join.PROBE_BLOCK + 11, 256
    bk = rng.integers(0, 3000, nb).astype(np.int64)
    bvalid, blive = rng.random(nb) > 0.1, rng.random(nb) > 0.2
    pk = rng.integers(-5, 3005, npr).astype(np.int64)
    pvalid, plive = rng.random(npr) > 0.2, rng.random(npr) > 0.2
    ls = join.build_lookup([jnp.asarray(bk)], [jnp.asarray(bvalid)], jnp.asarray(blive),
                           exact_keys=exact_keys)
    assert join.probe_path(ls.build_capacity, npr, ls.hash_bits) == "blocked"
    probe = ([jnp.asarray(pk)], [jnp.asarray(pvalid)], jnp.asarray(plive))
    lo, counts, total = join.probe_counts(ls, *probe)
    usable = bk[bvalid & blive]
    true_counts = np.array([(usable == k).sum() for k in pk]) * (pvalid & plive)
    if exact_keys:
        assert np.array_equal(np.asarray(counts), true_counts)
    else:
        assert (np.asarray(counts) >= true_counts).all()
    assert (np.asarray(counts)[~(pvalid & plive)] == 0).all()
    assert int(np.asarray(lo + counts).max()) <= int((bvalid & blive).sum())
    monkeypatch.setattr(join, "probe_path", lambda *shape: "sorted")
    one = join.probe_counts.__wrapped__(ls, *probe)
    for got, want in zip((lo, counts, total), one):
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("build,queries,bits", [(4096, 4096, 32), (3 * 4096 + 4095, 4096, 32),
                                                (1 << 14, 1 << 13, 38), (300, 2, 32)])
def test_a_shape_below_the_threshold_traces_the_program_it_always_did(build, queries, bits):
    """Below PROBE_BLOCK_RATIO times the batch (and for an array of a
    few blocks) `sorted_run_bounds` is the program it was before PR 37,
    equation for equation: every compiled program of a cell that does
    not qualify is found in the compile cache again. The expansion's
    own use of it (offsets against output positions) stays so at every
    shape."""
    dtype = jnp.uint32 if bits <= 32 else jnp.uint64
    t = jax.ShapeDtypeStruct((build,), dtype)
    q = jax.ShapeDtypeStruct((queries,), dtype)
    assert join.probe_path(build, queries, bits) == "sorted"
    now = jax.make_jaxpr(lambda a, b: join.sorted_run_bounds(a, b, bits))(t, q)
    then = jax.make_jaxpr(lambda a, b: _parent_sorted_run_bounds(a, b, bits))(t, q)
    assert str(now) == str(then)
    off = jax.ShapeDtypeStruct((1 << 14,), jnp.int32)
    j = jax.ShapeDtypeStruct((64,), jnp.int32)
    assert str(jax.make_jaxpr(lambda a, b: join._sorted_bounds(a, b, 32))(off, j)) == str(
        jax.make_jaxpr(_parent_sorted_run_bounds)(off, j))


def test_semi_and_outer_flags():
    bk = jnp.asarray([1, 1, 3], dtype=jnp.int64)
    pk = jnp.asarray([1, 2, 3, 4], dtype=jnp.int64)
    ls = join.build_lookup([bk], [jnp.ones(3, bool)], jnp.ones(3, bool))
    lo, counts, total = join.probe_counts(
        ls, [pk], [jnp.ones(4, bool)], jnp.ones(4, bool)
    )
    pi, bi, ok = join.expand_matches(ls, [pk], [jnp.ones(4, bool)], lo, counts, 16)
    pm = np.asarray(join.probe_matched_flags(4, pi, ok))
    assert list(pm) == [True, False, True, False]
    bm = np.asarray(join.build_matched_flags(3, bi, ok))
    assert list(bm) == [True, True, True]


def test_sort_multi_key_with_nulls_and_desc():
    a = jnp.asarray([3, 1, 2, 1, 2], dtype=jnp.int64)
    av = jnp.asarray([True, True, False, True, True])
    b = jnp.asarray([1.0, 9.0, 5.0, 7.0, 2.0])
    live = jnp.asarray([True, True, True, True, True])
    order = sort.sort_order(
        [a, b], [av, None], [False, True], [False, False], live
    )
    # a asc nulls last, then b desc: rows (1,b9),(3,b7),(4,b2),(0,b1),(2=null)
    assert list(np.asarray(order)) == [1, 3, 4, 0, 2]


def test_sort_dead_rows_last():
    a = jnp.asarray([5, 4, 3, 2], dtype=jnp.int64)
    live = jnp.asarray([True, False, True, True])
    order = sort.sort_order([a], [None], [False], [False], live)
    assert list(np.asarray(order)) == [3, 2, 0, 1]


def test_sort_nan_is_largest_both_directions():
    x = jnp.asarray([1.0, float("nan"), 2.0])
    live = jnp.ones(3, bool)
    asc = sort.sort_order([x], [None], [False], [False], live)
    assert list(np.asarray(asc)) == [0, 2, 1]
    desc = sort.sort_order([x], [None], [True], [False], live)
    assert list(np.asarray(desc)) == [1, 2, 0]


def test_temporal_coercion():
    from trino_tpu import types as T

    assert T.common_super_type(T.DATE, T.TIMESTAMP) == T.TIMESTAMP
    assert T.common_super_type(T.DATE, T.INTERVAL_DAY) is None
    assert T.common_super_type(T.DATE, T.BIGINT) is None
    assert T.arithmetic_result_type("+", T.DATE, T.INTERVAL_DAY) == T.DATE


def test_decimal_supertype_widens_to_int128():
    from trino_tpu import types as T

    # r4: wide operand pairs widen into the Int128 carrier (capped at
    # 38) instead of raising — spi/type/Decimals MAX_PRECISION
    wide = T.common_super_type(T.decimal(18, 0), T.decimal(18, 18))
    assert wide == T.decimal(36, 18) and wide.is_long_decimal
    assert T.common_super_type(T.decimal(12, 2), T.decimal(10, 4)) == T.decimal(14, 4)


class TestMxuGroupby:
    """Pallas MXU one-hot contraction kernel (ops/mxu_groupby.py) — the
    GroupByHash+accumulate hot loop on the systolic array (SURVEY.md
    §3.3). Interpret mode on CPU computes the identical program."""

    def _check(self, n, c, n_vals, seed, live_frac=1.0):
        import jax
        import numpy as np
        import jax.numpy as jnp
        from trino_tpu.ops.mxu_groupby import (
            grouped_sum_mxu, grouped_sum_reference,
        )

        rng = np.random.default_rng(seed)
        gid = jnp.asarray(rng.integers(0, c, n, dtype=np.int32))
        live = jnp.asarray(rng.random(n) < live_frac)
        vals = tuple(
            jnp.asarray(rng.integers(-(10**12), 10**12, n).astype(np.int64))
            for _ in range(n_vals)
        )
        interp = jax.default_backend() != "tpu"
        got = grouped_sum_mxu(gid, vals, live, c, interpret=interp)
        want = grouped_sum_reference(gid, vals, live, c)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))

    def test_exact_int64_sums(self):
        self._check(n=3000, c=300, n_vals=2, seed=1)

    def test_masked_rows_and_row_padding(self):
        # n not a multiple of the contraction's chunk; 30% dead rows
        self._check(n=1001, c=17, n_vals=1, seed=2, live_frac=0.7)

    def test_many_values_multi_sublane_tile(self):
        # 9 value columns are 18 word rows: w8 = 24, three sublane tiles
        self._check(n=2048, c=100, n_vals=9, seed=3)

    # PR 29's operand type and tile rule at their edges, each against
    # the scatter oracle: name -> (value of row i per column, limbs,
    # one group for all rows, capacity, rows). Cases share shapes so
    # that they share compiled programs. 100 slots pad to 128, whose
    # tile is the longest (32,768 rows); 77 rows more make a second,
    # ragged grid step.
    _LONG = 32768 + 77
    _EDGES = {
        "all-limbs-255": ([lambda i: -1], None, False, 100, _LONG),
        "int64-min-max": (
            [lambda i: -(2**63) if i % 2 else 2**63 - 1], None, False,
            100, _LONG),
        # the largest sum a grid step can hold: a whole tile in one
        # group, every limb 255
        "one-group-fills-a-tile": ([lambda i: -1], None, True, 100, _LONG),
        "capacity-1": ([lambda i: i - 500], None, False, 1, 1001),
        "capacity-160": ([lambda i: i - 500], None, False, 160, 1001),
        "capacity-2048": ([lambda i: i - 500], None, False, 2048, 1001),
        "limbs-1": ([lambda i: i % 3 == 0], (1,), False, 160, 1001),
        "limbs-mixed": (
            [lambda i: -(2**40) * i - 1, lambda i: i % 2,
             lambda i: 65535 - (i % 7), lambda i: 2**40 - 1 - i],
            (8, 1, 2, 5), False, 160, 1001),
        # `select k, count(*) ... group by k`: the live-row count alone
        "no-columns": ([], None, False, 160, 1001),
    }

    @pytest.mark.parametrize("edge", sorted(_EDGES))
    def test_exact_at_the_edges(self, edge):
        import jax
        import numpy as np
        import jax.numpy as jnp
        from trino_tpu.ops.mxu_groupby import (
            grouped_sum_mxu, grouped_sum_reference,
        )

        cols, limbs, one_group, c, n = self._EDGES[edge]
        rng = np.random.default_rng(29)
        gid = np.zeros(n, np.int32) if one_group else rng.integers(
            0, c, n, dtype=np.int32)
        live = np.ones(n, bool) if one_group else rng.random(n) < 0.9
        vals = tuple(
            jnp.asarray(np.array([int(f(i)) for i in range(n)], dtype=np.int64))
            for f in cols
        )
        interp = jax.default_backend() != "tpu"
        got = grouped_sum_mxu(jnp.asarray(gid), vals, jnp.asarray(live), c,
                              interpret=interp, limbs=limbs)
        want = jax.jit(grouped_sum_reference, static_argnums=3)(
            jnp.asarray(gid), vals, jnp.asarray(live), c)
        assert len(got) == len(want) == len(cols) + 1
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))
        if one_group:
            assert int(got[1][0]) == n and int(got[0][0]) == -n

    @pytest.mark.parametrize("C", [128, 256, 1024, 2048])
    @pytest.mark.parametrize("w8", [8, 24, 64])
    def test_row_tile_bounds(self, C, w8):
        """The tile rule's promises, for every padded slot count and
        word-row count: float32 sums exact within a grid step, whole
        chunks, a one-hot and a word block the scoped VMEM holds."""
        from trino_tpu.ops.mxu_groupby import MAX_ROWS, MAX_TILE, _row_tile

        assert MAX_TILE * 255 < 2**24 and MAX_ROWS >= 2**23
        for n in (1, 1000, 1 << 20, MAX_ROWS):
            tile, chunk = _row_tile(n, C, w8)
            assert tile * 255 < 2**24 and tile % chunk == 0
            assert chunk % 128 == 0 and C * chunk <= 1 << 19
            assert w8 * tile * 4 <= 2 << 20
            assert tile - chunk < max(n, chunk)  # no chunk of padding alone
        assert _row_tile(1 << 20, 256, 8) == (16384, 2048)  # G3's

    # Q1's 14 value slots (issue 31): five BIGINT sums without a
    # validity mask, two long decimal sums of four limb slots each
    # behind one mask each, count(*)
    _Q1 = (
        ("sum",) * 13 + ("count",),
        (None, None, "a", "a", "a", "a", "b", "b", "b", "b", None, None, None, None),
        (8, 8, 4, 4, 4, 8, 4, 4, 4, 8, 8, 8, 8, 8),
    )

    @pytest.mark.parametrize("reducers,masks,value_limbs,want_limbs", [
        pytest.param(("sum", "count"), ("a", None), None, (1, 8), id="masked-sum"),
        # G3's: count(*) and a sum without a validity mask read the one
        # live-row count the kernel appends
        pytest.param(("count", "sum"), (None, None), None, (8,), id="shared-count"),
        # slots that share a validity array share its indicator column
        pytest.param(("sum", "sum", "count", "sum"), ("a", "a", "a", "b"), None,
                     (1, 8, 8, 1, 8), id="shared-valid"),
        # a slot stated under 2^32 has four limbs and no high word
        pytest.param(("sum", "sum", "sum"), (None, "a", None), (4, 4, 8),
                     (4, 1, 4, 8), id="under-2^32"),
        pytest.param(*_Q1, (8, 8, 1, 4, 4, 4, 8, 1, 4, 4, 4, 8, 8, 8, 8), id="q1"),
    ])
    def test_mxu_group_reduce_contract(self, reducers, masks, value_limbs,
                                       want_limbs, monkeypatch):
        """mxu_group_reduce matches dense_group_reduce on the same
        bounded-domain inputs (sum/count reducers), and its word plane
        carries each thing once: the columns and `limbs` it hands to
        grouped_sum_mxu."""
        import numpy as np
        import jax.numpy as jnp
        from trino_tpu.ops import mxu_groupby
        from trino_tpu.ops.groupby import (
            dense_group_reduce, mxu_group_reduce, shared_valids,
        )

        rng = np.random.default_rng(4)
        n, d0, d1 = 5000, 3, 2
        keys = [
            jnp.asarray(rng.integers(0, d0, n).astype(np.int64)),
            jnp.asarray(rng.integers(0, d1, n).astype(np.int64)),
        ]
        valids = [
            jnp.asarray(rng.random(n) < 0.9),
            jnp.ones(n, dtype=jnp.bool_),
        ]
        mask = jnp.asarray(rng.random(n) < 0.8)
        limbs_in = value_limbs or (8,) * len(reducers)
        values = [
            jnp.asarray(rng.integers(0, 2**32, n) if k == 4
                        else rng.integers(-2**62, 2**62, n))
            for k in limbs_in
        ]
        by_name = {m: jnp.asarray(rng.random(n) < 0.95)
                   for m in sorted(set(masks) - {None})}
        vvalids = tuple(by_name.get(m) for m in masks)
        args = (keys, valids, mask, values, vvalids, reducers, (d0, d1), 16)
        want = dense_group_reduce(*args)
        handed = []
        real = mxu_groupby.grouped_sum_mxu

        def spy(gid, cols, live, capacity, interpret=False, limbs=None):
            handed.append(limbs)
            assert len(cols) == len(limbs)
            return real(gid, cols, live, capacity, interpret=interpret, limbs=limbs)

        monkeypatch.setattr(mxu_groupby, "grouped_sum_mxu", spy)
        # the function under the jit, so that the spy sees this call
        got = mxu_group_reduce.__wrapped__(
            *args, value_limbs=value_limbs, valid_of=shared_valids(vvalids))
        assert handed == [want_limbs]
        # 32-bit word rows of the call, the gid row among them
        word_rows = sum(1 + (k > 4) for k in want_limbs) + 1
        assert word_rows <= 23
        for g, w in zip(got[:5], want[:5]):
            for ga, wa in zip(
                (g if isinstance(g, (list, tuple)) else [g]),
                (w if isinstance(w, (list, tuple)) else [w]),
            ):
                assert np.array_equal(np.asarray(ga), np.asarray(wa))
        assert int(got[5]) == int(want[5])

    def test_shared_valids_is_by_identity(self):
        import jax.numpy as jnp
        from trino_tpu.ops.groupby import shared_valids

        a, b = jnp.ones(4, bool), jnp.ones(4, bool)  # equal, not the same
        assert shared_valids([None, a, a, b, None, a]) == (0, 1, 1, 3, 4, 1)

    def test_engine_routes_through_mxu(self, monkeypatch):
        """A bounded-dictionary GROUP BY in the (64, 2048] band runs
        through the Pallas path and matches the sort-path answer."""
        monkeypatch.setenv("TRINO_TPU_FORCE_MXU", "1")
        from trino_tpu.connectors.tpch import create_tpch_connector
        from trino_tpu.engine import LocalQueryRunner, Session

        sql = (
            "SELECT s_name, count(*), sum(ps_availqty)"
            " FROM partsupp, supplier WHERE ps_suppkey = s_suppkey"
            " GROUP BY s_name ORDER BY s_name"
        )
        r = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
        r.register_catalog("tpch", create_tpch_connector())
        forced = r.execute(sql).rows
        monkeypatch.setenv("TRINO_TPU_FORCE_MXU", "0")
        r2 = LocalQueryRunner(Session(catalog="tpch", schema="tiny"))
        r2.register_catalog("tpch", create_tpch_connector())
        assert forced == r2.execute(sql).rows
        assert len(forced) == 100  # one row per supplier


# -- the sort path's order check (issue 34) -----------------------------------

N_ORD = 64


def _ordered_case(name):
    """(keys, valids, mask) of N_ORD rows, four to a key."""
    keys = np.repeat(np.arange(N_ORD // 4, dtype=np.int64) * 7 - 20, 4)
    valids = np.ones(N_ORD, bool)
    mask = np.ones(N_ORD, bool)
    if name == "reversed":
        keys = keys[::-1].copy()
    elif name == "shuffled":
        keys = np.random.default_rng(34).permutation(keys)
    elif name == "a_late_key_out_of_place":
        keys[-1] = keys[0]
    elif name == "dead_row_in_the_middle":
        mask[N_ORD // 2] = False
    elif name == "dead_rows_last":
        mask[-5:] = False
    elif name == "nulls_last":
        valids[-6:] = False
    elif name == "nulls_first":
        valids[:6] = False
    else:
        assert name == "ordered"
    return keys, valids, mask


@pytest.mark.parametrize("dtype", [np.int64, np.float64], ids=["bigint", "double"])
@pytest.mark.parametrize("name,in_order", [
    ("ordered", True), ("dead_rows_last", True), ("nulls_last", True),
    ("reversed", False), ("shuffled", False), ("a_late_key_out_of_place", False),
    ("dead_row_in_the_middle", False), ("nulls_first", False),
])
@pytest.mark.parametrize("cap", [8, 32], ids=["top_k", "carried"])
def test_sort_group_reduce_skips_the_key_sort_of_rows_in_key_order(
        name, in_order, cap, dtype):
    """With `check_order` the reduce says which way the rows went (bit
    ORDERED of its flag word) and answers what it answers without: the
    same groups in the same slots, whichever compaction the table's
    size picks."""
    keys, valids, mask = _ordered_case(name)
    rng = np.random.default_rng(7)
    args = (
        [jnp.asarray(keys.astype(dtype))], [jnp.asarray(valids)], jnp.asarray(mask),
        [jnp.asarray(rng.integers(-50, 50, N_ORD)), jnp.asarray(rng.integers(0, 9, N_ORD)),
         jnp.asarray(rng.integers(0, 9, N_ORD)), jnp.ones(N_ORD, jnp.int64)],
        (None, jnp.asarray(rng.random(N_ORD) < 0.7), None, None),
        ("sum", "min", "max", "count"), cap,
    )
    *want, want_flag = groupby.sort_group_reduce(*args)
    *got, word = groupby.sort_group_reduce(*args, check_order=True)
    assert bool(int(word) & groupby.ORDERED) == in_order
    assert bool(int(word) & 1) == bool(want_flag) == (cap == 8)
    gk, gv, used, results, counts, n_groups = got
    wk, wv, w_used, w_results, w_counts, w_groups = want
    live_keys = keys[mask & valids]
    assert int(n_groups) == int(w_groups) == len(set(live_keys)) + bool((mask & ~valids).any())
    u = np.asarray(w_used)
    np.testing.assert_array_equal(np.asarray(used), u)
    np.testing.assert_array_equal(np.asarray(gv[0]), np.asarray(wv[0]))
    live_key = u & np.asarray(wv[0])
    np.testing.assert_array_equal(np.asarray(gk[0])[live_key], np.asarray(wk[0])[live_key])
    for a, b in zip(list(results) + list(counts), list(w_results) + list(w_counts)):
        np.testing.assert_array_equal(np.asarray(a)[u], np.asarray(b)[u])


def test_the_order_check_is_for_a_single_key_and_for_those_who_ask():
    """Several keys sort by the tuple hash and keep the plain flag; so
    does a single key whose caller did not ask."""
    k = jnp.arange(N_ORD, dtype=jnp.int64)
    ones = jnp.ones(N_ORD, bool)
    for keys, check in (([k, k], True), ([k], False)):
        flag = groupby.sort_group_reduce(
            keys, [ones] * len(keys), ones, [k], (None,), ("sum",), N_ORD,
            check_order=check)[-1]
        assert flag.dtype == jnp.bool_ and not bool(flag)
