"""Hash-join build/probe kernels.

Analogue of Trino's PagesIndex + PagesHash + JoinProbe family
(main/operator/PagesIndex.java:80, join/DefaultPagesHash.java:44,
join/LookupJoinOperator.java:36) — re-designed around sorting, which is
what TPUs do well, instead of pointer-chasing:

- Build ("LookupSource"): hash the build keys to 32 bits, sort build
  rows by hash. The sorted-hash array + permutation IS the lookup
  structure — duplicates are adjacent runs, playing the role of Trino's
  PositionLinks chains without linked lists.
- Probe: `sorted_run_bounds` positions every probe hash among the
  sorted build hashes. Against a build side not much larger than the
  batch, with two single-operand packed sorts over both (r4 rewrite;
  `_sorted_bounds` says why sorts beat one-word gathers on this
  hardware). Against one much larger (`probe_path`), in two levels:
  the two sorts place the batch among every 128th build word only, and
  two gathers of whole 128-word rows finish inside one block each, so
  the build side, sorted once, is not sorted again with every batch.
  On a v5e the two gathers of 2^20 rows of 512 B and their compares are
  7 to 8 ms together, where ONE one-word gather of as many indices is
  9.5 (PR 35): a gather is paid a row, and a 128-lane row is the cheap
  one (PROBE_BLOCK's comment has the rates).
- Fan-out (dynamic output size): two-phase — count matches, host picks
  a bucketed output capacity, then a dense expansion pass materializes
  (probe_row, build_row) pairs. 32-bit hash collisions are culled by an
  exact key-equality verify on the expanded pairs (the same verify
  already required for correctness under any hash width).
- Outer/semi/anti variants derive from the same expansion plus
  scatter-or'd matched flags (probe side) and a build-side matched
  bitmap (the LookupOuterOperator analogue for RIGHT/FULL joins).

SQL join-key semantics: NULL never matches NULL.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from trino_tpu.ops.gather import take_clip
from trino_tpu.ops.hashing import hash32

# u32 hash domain layout: real hashes clamp to <= REAL_MAX so the two
# sentinels own distinct top values. A probe with a NULL key must find
# nothing (NO_MATCH < DEAD: never meets dead build rows either); a dead
# or NULL-keyed build row must never be found (DEAD is the max, and no
# probe can carry it).
_H_REAL_MAX = jnp.uint32(0xFFFFFFFD)
_NO_MATCH_HASH = jnp.uint32(0xFFFFFFFE)  # probes that must find nothing
_DEAD_BUILD_HASH = jnp.uint32(0xFFFFFFFF)  # dead build rows sort last


# The two-level probe (`sorted_run_bounds`): the sorted array is cut into
# blocks of PROBE_BLOCK words, and the queries are placed among the
# blocks' first words before they are placed inside one block. A block
# is one 128-lane row of the chip: narrower rows gather slower (2^20
# queries against 15.7 M u32 words, ms: 22.7 at 128, 43.8 at 64, 105.7
# at 32, 154.2 in one level). Taken where the array holds at least
# PROBE_BLOCK_RATIO times the queries: at twice it reads what one level
# reads (21.6 for 20.6), at eight times 22.0 for 78.2. 64-bit words (a
# u64 array is two u32 arrays on the chip, and their row gathers cost
# more than twice) gain from eight times on: 62.9 for 78.4, and 27.0
# for 20.8 at twice (PERF.md section 6, PR 37, has the table).
PROBE_BLOCK = 128
PROBE_BLOCK_RATIO = 4
PROBE_BLOCK_RATIO_WIDE = 8


def probe_path(build_capacity: int, probe_capacity: int,
               hash_bits: int = 32) -> str:
    """Which form `sorted_run_bounds` takes for `probe_capacity` queries
    against a sorted array of `build_capacity` words of `hash_bits`
    bits: "blocked" (two levels) or "sorted" (all of it through two
    packed sorts). A function of the shapes alone; the kernel and
    `LookupJoinOperator`'s counter `join_probe_path.*` both ask it."""
    # (queries too many for one packed word are bounded in chunks, and a
    # chunk decides)
    probe_capacity = min(probe_capacity, 1 << max(62 - hash_bits, 0))
    ratio = PROBE_BLOCK_RATIO if hash_bits <= 32 else PROBE_BLOCK_RATIO_WIDE
    if probe_capacity > 0 and build_capacity >= ratio * max(
        probe_capacity, PROBE_BLOCK
    ):
        return "blocked"
    return "sorted"


def sorted_run_bounds(sorted_arr: jnp.ndarray, q: jnp.ndarray,
                      value_bits: int = 32):
    """For each query, the run [lo, hi) of equal values in a sorted
    array — the PagesHash probe (DefaultPagesHash.java:159). Values of
    both inputs must fit in `value_bits` bits (32: key hashes and
    expansion offsets do by construction; more for the exact words of
    several key columns, `_wide_bits`), and a value, two tag bits and a
    query's position share one 64-bit word: queries beyond what is left
    for the position are bounded in chunks.

    One algorithm whose first level shrinks with the shapes
    (`probe_path`). Where the array is not much larger than the batch,
    `_sorted_bounds`: array and queries through two packed sorts. Where
    it is, `_blocked_bounds`: the same two sorts place the queries among
    every PROBE_BLOCK-th word only, and two row gathers and a compare
    finish inside one block each; the array, in order already, is not
    sorted again with every batch. Same (lo, hi) either way."""
    B = sorted_arr.shape[0]
    N = q.shape[0]
    if B == 0 or N == 0:
        z = jnp.zeros(N, jnp.int32)
        return z, z
    id_bits = max(int(N - 1).bit_length(), 1)
    if value_bits + 2 + id_bits > 64:
        if value_bits <= 32:
            raise ValueError(
                f"sorted_run_bounds: query batch of {N} rows exceeds the "
                "2^30 packed-word id budget; split the batch"
            )
        step = 1 << (62 - value_bits)
        bounds = [
            sorted_run_bounds(sorted_arr, q[at:at + step], value_bits)
            for at in range(0, N, step)
        ]
        return (jnp.concatenate([lo for lo, _ in bounds]),
                jnp.concatenate([hi for _, hi in bounds]))
    if probe_path(B, N, value_bits) == "blocked":
        return _blocked_bounds(sorted_arr, q, value_bits)
    return _sorted_bounds(sorted_arr, q, value_bits)


def _sorted_bounds(sorted_arr: jnp.ndarray, q: jnp.ndarray, value_bits: int):
    """`sorted_run_bounds` in one level, for queries that fit one packed
    word (its caller chunks them).

    TPU-native formulation (r4). On this chip a gather of 2^20 one-word
    indices takes 9.5 ms whatever the table's bytes, a scatter of 2^20
    slots 8.5-10.1 ms (PERF.md section 6, PR 35), XLA's searchsorted is
    a loop of such gathers, and the scan primitives lax.cummax/cummin
    hang XLA:TPU compiles the way associative_scan does — while a
    single-operand lax.sort of 64-bit words is 4.1 ns a word (PR 35).
    So the bounds are exactly TWO single-operand packed sorts + cumsum:

    1. Each query enters the combined array TWICE — tagged to sort
       before any equal table value (where its table-prefix count = lo)
       and after (= hi). The duplicate entry replaces the rightward
       run-boundary propagation the previous design needed (a
       scatter+gather pair measured at 15.9ms per 1M rows).
    2. value(32b) | tag(2b) | query-id packs into one int64 word, so
       the combined sort carries no payload operands; a second packed
       sort on (query-id | is-hi | count) routes both bounds back to
       query order, where each query's (lo, hi) land adjacent and
       reshape to (N, 2) — no gather, no scatter anywhere.
    """
    N = q.shape[0]
    id_bits = max(int(N - 1).bit_length(), 1)
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
    # at a query entry, tables at-or-before == tables strictly before
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


def _blocked_bounds(sorted_arr: jnp.ndarray, q: jnp.ndarray, value_bits: int):
    """`sorted_run_bounds` in two levels, for an array much larger than
    the batch of queries.

    1. Splitters: every PROBE_BLOCK-th word of the array, a strided
       slice. `sorted_run_bounds` places the queries among them (lo_s
       splitters below a query, hi_s at or below it).
    2. The array as rows of PROBE_BLOCK words (padded with the greatest
       word): every block before row lo_s - 1 ends at or below a
       splitter that is below the query, every block from lo_s on
       starts at a splitter not below it, so lo is (lo_s - 1) * width
       plus the words of that ONE row below the query; hi likewise from
       row hi_s - 1 and the words at or below it. Exact for duplicate
       runs, also where one crosses blocks: no flag, no fallback.
    """
    width = PROBE_BLOCK
    B = sorted_arr.shape[0]
    word = jnp.uint32 if value_bits <= 32 else jnp.uint64
    t = sorted_arr.astype(word)
    qw = q.astype(word)
    blocks = -(-B // width)
    lo_s, hi_s = sorted_run_bounds(t[::width], qw, value_bits)
    if blocks * width > B:
        t = jnp.concatenate(
            [t, jnp.full(blocks * width - B, jnp.iinfo(word).max, word)]
        )
    rows = t.reshape(blocks, width)

    def within(at_s, below):
        row = jnp.maximum(at_s - 1, 0)
        inside = jnp.sum(below(take_clip(rows, row, axis=0), qw[:, None]),
                         axis=1, dtype=jnp.int32)
        at = jnp.where(at_s > 0, row * width + inside, 0)
        return jnp.minimum(at, B)       # (padding is never below a query)

    return within(lo_s, jnp.less), within(hi_s, jnp.less_equal)


def _one_integer_key(keys) -> bool:
    return len(keys) == 1 and _integer_keys(keys)


def _integer_keys(keys) -> bool:
    return bool(keys) and all(
        getattr(k, "ndim", 1) == 1 and jnp.issubdtype(k.dtype, jnp.integer)
        for k in keys
    )


# the most bits an exact word of several key columns may take: a probe
# batch of 2^20 rows still packs (value, tag, position) into 64 bits
_WIDE_BITS = 42


def _wide_bits(keys, build_capacity: int) -> int:
    """Bits of the sorted word for SEVERAL integer key columns asked to
    be exact: what the build side's slot numbers leave of 64, at most
    _WIDE_BITS; 32 (the hash's own) where that is no more."""
    slot_bits = max(int(build_capacity - 1).bit_length(), 1)
    return max(min(64 - slot_bits, _WIDE_BITS), 32)


def _real_max(bits: int) -> int:
    """The greatest word a usable key may have: the two above it are
    the sentinels (_NO_MATCH_HASH and _DEAD_BUILD_HASH at 32 bits)."""
    return (1 << bits) - 3


def _exact_base(keys, usable):
    """(least usable key, whether every usable key lies within the real
    hash range above it) of ONE integer key column: where it holds, a
    key's distance from the least IS its hash, and no two keys share
    one. A 32-bit hash of 15 M order keys gives one probe row in 300 a
    second candidate, which alone kept every probe batch off the
    fanout-one path (PERF.md section 6, PR 35)."""
    k = keys[0].astype(jnp.int64)
    info = jnp.iinfo(jnp.int64)
    lo = jnp.min(jnp.where(usable, k, info.max))
    hi = jnp.max(jnp.where(usable, k, info.min))
    span = hi - lo          # (wraps below zero where the keys span over 2^63)
    return lo, (hi >= lo) & (span >= 0) & (span <= jnp.int64(int(_H_REAL_MAX)))


def _exact_bases(keys, usable, bits: int):
    """`_exact_base` for SEVERAL integer key columns: (each column's
    least usable value, each column's count of values from its least to
    its greatest, whether the counts' bits together fit a word of `bits`
    bits). Where they do, a key's word is its columns' distances from
    their least, each weighted by the counts of the columns after it,
    and no two keys share one: `partsupp`'s 8 M (partkey, suppkey) pairs
    take 21 + 17 bits, and a 32-bit hash of them gave one probe row in
    500 a second candidate (PERF.md section 6, PR 35)."""
    info = jnp.iinfo(jnp.int64)
    los, spans = [], []
    fits = jnp.any(usable)
    taken = jnp.int64(0)
    for key in keys:
        k = key.astype(jnp.int64)
        lo = jnp.min(jnp.where(usable, k, info.max))
        hi = jnp.max(jnp.where(usable, k, info.min))
        span = hi - lo + 1      # (wraps where the values span over 2^63)
        fits = fits & (hi >= lo) & (span > 0)
        span = jnp.where(fits, span, 1)
        taken = taken + (64 - jax.lax.clz(span - 1))
        los.append(lo)
        spans.append(span)
    # (under 2^(bits - 1) words: the two sentinels stay above them)
    return tuple(los), tuple(spans), fits & (taken < bits)


def _key_hash(keys, valids, usable, sentinel, base=None):
    """Clamped 32-bit key hash; rows not usable get the sentinel. With
    `base` (`_exact_base` of the build side) and the flag it carries
    set, the key's distance from the base instead; a key outside the
    range finds nothing."""
    if keys:
        h = jnp.minimum(hash32(list(keys), list(valids)), _H_REAL_MAX)
    else:
        h = jnp.zeros(usable.shape[0], dtype=jnp.uint32)
    if base is not None:
        lo, exact = base
        d = keys[0].astype(jnp.int64) - lo
        inside = (d >= 0) & (d <= jnp.int64(int(_H_REAL_MAX)))
        h = jnp.where(exact, d.astype(jnp.uint32), h)
        usable = usable & (~exact | inside)
    return jnp.where(usable, h, sentinel)


def _wide_key_word(keys, valids, usable, sentinel: int, bases):
    """`_key_hash` for `_exact_bases`: a 64-bit word, the exact one where
    the flag is set (a key with a column outside the build side's values
    finds nothing) and the clamped 32-bit hash where it is not."""
    los, spans, exact = bases
    h = jnp.minimum(hash32(list(keys), list(valids)), _H_REAL_MAX)
    word = jnp.zeros(usable.shape[0], dtype=jnp.int64)
    inside = jnp.ones(usable.shape[0], dtype=jnp.bool_)
    for key, lo, span in zip(keys, los, spans):
        d = key.astype(jnp.int64) - lo
        inside = inside & (d >= 0) & (d < span)
        word = word * span + d
    word = jnp.where(exact & inside, word, h.astype(jnp.int64))
    usable = usable & (~exact | inside)
    return jnp.where(usable, word.astype(jnp.uint64), jnp.uint64(sentinel))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LookupSource:
    """Device-resident build side: sorted hashes + row permutation."""

    sorted_hash: jnp.ndarray  # (B,) uint32, dead rows = 0xFFFFFFFF (uint64: hash_bits)
    perm: jnp.ndarray  # (B,) int32 — build row index at each sorted pos
    key_cols: List[jnp.ndarray]  # original (unsorted) build key columns
    key_valids: List[jnp.ndarray]
    build_live: jnp.ndarray  # (B,) bool
    # `_exact_base` of the keys where the build asked for it: the probe
    # must hash its keys the same way (`_exact_bases` where
    # `hash_bits` is over 32: several key columns, and `sorted_hash`
    # holds uint64 words of that many bits)
    exact_base: Optional[tuple] = None
    hash_bits: int = 32

    def tree_flatten(self):
        return (
            (self.sorted_hash, self.perm, self.key_cols, self.key_valids,
             self.build_live, self.exact_base),
            (self.hash_bits,),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        sh, perm, kc, kv, bl, base = children
        return cls(sh, perm, list(kc), list(kv), bl, base, *aux)

    @property
    def build_capacity(self) -> int:
        return int(self.perm.shape[0])


@partial(jax.jit, static_argnames=("exact_keys",))
def build_lookup(
    keys: Sequence[jnp.ndarray],
    valids: Sequence[jnp.ndarray],
    live: jnp.ndarray,
    exact_keys: bool = False,
) -> LookupSource:
    """Build phase — HashBuilderOperator analogue, ONE single-operand
    packed sort instead of row-at-a-time inserts
    (join/HashBuilderOperator.java:58). `exact_keys`: where the key is
    one integer column whose values span under 2^32, sort by the key's
    distance from the least (no two keys then share a run); where it is
    several integer columns, by their distances from their least in one
    word of up to _WIDE_BITS bits (`_exact_bases`)."""
    any_null = None
    for v in valids:
        any_null = ~v if any_null is None else (any_null | ~v)
    usable = live if any_null is None else (live & ~any_null)
    bits = 32
    if exact_keys and len(keys) > 1 and _integer_keys(keys):
        bits = _wide_bits(keys, live.shape[0])
    if bits > 32:
        bases = _exact_bases(keys, usable, bits)
        word = _wide_key_word(
            keys, valids, usable, _real_max(bits) + 2, bases
        )
        shift = jnp.uint64(64 - bits)
        sp = jnp.sort(
            (word << shift) | jnp.arange(live.shape[0], dtype=jnp.uint64)
        )
        perm = (sp & ((jnp.uint64(1) << shift) - jnp.uint64(1))).astype(jnp.int32)
        return LookupSource(
            sp >> shift, perm, list(keys), list(valids), usable, bases, bits
        )
    base = None
    if exact_keys and _one_integer_key(keys):
        base = _exact_base(keys, usable)
    h = _key_hash(keys, valids, usable, _DEAD_BUILD_HASH, base)
    B = h.shape[0]
    packed = (h.astype(jnp.uint64) << jnp.uint64(32)) | jnp.arange(
        B, dtype=jnp.uint64
    )
    sp = jnp.sort(packed)
    sorted_hash = (sp >> jnp.uint64(32)).astype(jnp.uint32)
    perm = (sp & jnp.uint64(0xFFFFFFFF)).astype(jnp.int32)
    return LookupSource(sorted_hash, perm, list(keys), list(valids), usable, base)


@jax.jit
def probe_counts(
    ls: LookupSource,
    probe_keys: Sequence[jnp.ndarray],
    probe_valids: Sequence[jnp.ndarray],
    probe_live: jnp.ndarray,
):
    """Phase 1: per-probe-row candidate run [lo, hi). Returns
    (lo, counts, total) — `total` is a device scalar (callers defer
    reading it; see LookupJoinOperator's speculative expansion)."""
    any_null = None
    for v in probe_valids:
        any_null = ~v if any_null is None else (any_null | ~v)
    usable = probe_live if any_null is None else (probe_live & ~any_null)
    if ls.hash_bits > 32:
        if len(probe_keys) != len(ls.key_cols) or not _integer_keys(probe_keys):
            raise TypeError(
                "probe_counts: the build side sorted its integer key "
                "columns' exact word; the probe must bring as many"
            )
        ph = _wide_key_word(
            probe_keys, probe_valids, usable, _real_max(ls.hash_bits) + 1,
            ls.exact_base,
        )
    else:
        if ls.exact_base is not None and not _one_integer_key(probe_keys):
            raise TypeError(
                "probe_counts: the build side sorted ONE integer key by its "
                "distance from the least; the probe must bring one too"
            )
        ph = _key_hash(
            probe_keys, probe_valids, usable, _NO_MATCH_HASH, ls.exact_base
        )
    lo, hi = sorted_run_bounds(ls.sorted_hash, ph, ls.hash_bits)
    counts = hi - lo
    return lo, counts, jnp.sum(counts)


@partial(jax.jit, static_argnames=("out_capacity", "verify"))
def expand_matches(
    ls: LookupSource,
    probe_keys: Sequence[jnp.ndarray],
    probe_valids: Sequence[jnp.ndarray],
    lo: jnp.ndarray,
    counts: jnp.ndarray,
    out_capacity: int,
    verify: bool = True,
):
    """Phase 2: materialize candidate pairs; verify exact key equality
    (32-bit hash collisions) unless the CALLER verifies on its gathered
    pair columns instead (verify=False — saves four gathers per key:
    the pair batch carries the key columns anyway).

    Returns (probe_idx, build_idx, pair_live) each (out_capacity,).
    """
    off = jnp.cumsum(counts)  # inclusive
    total = off[-1] if counts.shape[0] else jnp.int32(0)
    j = jnp.arange(out_capacity, dtype=jnp.int32)
    # which probe row produced output j: #offs <= j (hi-rank of j among
    # the sorted offsets). One level at every shape: a sparse join's
    # offsets qualify for two, which nobody has measured here, and Q3's
    # programs stay the ones in the compile cache (ROADMAP S4)
    _, pi = _sorted_bounds(off, j, 32)
    pi_c = jnp.clip(pi, 0, counts.shape[0] - 1)
    # lo and start ride one packed int64 gather instead of three
    packed = (
        lo.astype(jnp.int64) << jnp.int64(31)
    ) | (off - counts).astype(jnp.int64)
    g = take_clip(packed, pi_c)
    start = (g & jnp.int64((1 << 31) - 1)).astype(jnp.int32)
    spos = (g >> jnp.int64(31)).astype(jnp.int32) + (j - start)
    spos = jnp.clip(spos, 0, ls.perm.shape[0] - 1)
    bi = take_clip(ls.perm, spos)
    ok = j < total
    if verify:
        # exact verify: join equality — NULLs never match
        for pk, pv, bk, bv in zip(
            probe_keys, probe_valids, ls.key_cols, ls.key_valids
        ):
            a = take_clip(pk, pi_c)
            av = take_clip(pv, pi_c)
            b = take_clip(bk, jnp.clip(bi, 0, bk.shape[0] - 1))
            bvv = take_clip(bv, jnp.clip(bi, 0, bv.shape[0] - 1))
            eqd = a == b
            if getattr(eqd, "ndim", 1) == 2:  # long-decimal limb pairs
                eqd = eqd.all(axis=-1)
            ok = ok & eqd & av & bvv
    return pi_c, bi, ok


def probe_matched_flags(probe_capacity, pi, pair_live):
    """Per-probe-row 'has >=1 verified match' — drives semi/anti joins
    (HashSemiJoinOperator analogue) and LEFT-outer row emission."""
    z = jnp.zeros(probe_capacity + 1, dtype=jnp.bool_)
    idx = jnp.where(pair_live, pi, probe_capacity)
    return z.at[idx].max(True, mode="drop")[:probe_capacity]


def build_matched_flags(build_capacity, bi, pair_live, prior=None):
    """Build-side matched bitmap for RIGHT/FULL outer joins
    (join/LookupOuterOperator.java analogue)."""
    z = prior if prior is not None else jnp.zeros(build_capacity, dtype=jnp.bool_)
    idx = jnp.where(pair_live, bi, build_capacity)
    return z.at[idx].max(True, mode="drop")
