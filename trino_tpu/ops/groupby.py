"""Group-by hash kernels.

Analogue of Trino's GroupByHash (main/operator/GroupByHash.java:30;
MultiChannelGroupByHash.putIfAbsent:264 open-addressing linear probe) —
re-designed as a *vectorized, fixed-capacity* linear-probe table:

- Capacity is a power of two chosen by the host (bucketed), replacing
  tryRehash (MultiChannelGroupByHash.java:350) with
  rebuild-at-larger-capacity on overflow — static shapes for XLA.
- Insertion is data-parallel over all rows at once: each round, every
  unresolved row inspects its probe slot; empty slots are claimed by a
  min-row-id scatter race (one winner per slot per round, losers retry),
  occupied slots compare keys. Rounds loop via lax.while_loop. This is
  the standard way to express a concurrent hash-table insert as a
  sequence of dense vector ops — the whole batch makes progress each
  round instead of Trino's per-row scalar loop.
- SQL GROUP BY semantics: NULL is its own group, so validity bits are
  part of the key.

Aggregation itself is masked segment scatter-add/min/max into (C,)
accumulators — XLA turns these into efficient sorted-scatter updates.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from trino_tpu.ops.gather import take_clip
from trino_tpu.ops.hashing import hash32, hash64


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GroupTable:
    """Fixed-capacity group table: slot i holds the keys of group id i."""

    slot_keys: List[jnp.ndarray]  # each (C,)
    slot_valids: List[jnp.ndarray]  # each (C,) bool
    slot_used: jnp.ndarray  # (C,) bool

    def tree_flatten(self):
        return (self.slot_keys, self.slot_valids, self.slot_used), (len(self.slot_keys),)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(list(children[0]), list(children[1]), children[2])

    @property
    def capacity(self) -> int:
        return int(self.slot_used.shape[0])

    def num_groups(self) -> jnp.ndarray:
        return jnp.sum(self.slot_used)


def _keys_equal(a_keys, a_valids, b_keys, b_valids):
    """GROUP-BY equality: NULL == NULL (IS NOT DISTINCT FROM)."""
    eq = None
    for ak, av, bk, bv in zip(a_keys, a_valids, b_keys, b_valids):
        e = ((ak == bk) & av & bv) | (~av & ~bv)
        eq = e if eq is None else (eq & e)
    return eq


def new_group_table(key_dtypes: Sequence, capacity: int) -> GroupTable:
    """Fresh empty table (host helper for streaming aggregation)."""
    assert capacity & (capacity - 1) == 0
    return GroupTable(
        [jnp.zeros(capacity, dtype=dt) for dt in key_dtypes],
        [jnp.zeros(capacity, dtype=jnp.bool_) for _ in key_dtypes],
        jnp.zeros(capacity, dtype=jnp.bool_),
    )


@jax.jit
def insert_group_ids(
    table: GroupTable,
    keys: Sequence[jnp.ndarray],
    valids: Sequence[jnp.ndarray],
    mask: jnp.ndarray,
):
    """Map each live row to a group id in [0, C), inserting new groups
    into `table` (streaming multi-batch form of assign_group_ids — the
    putIfAbsent analogue, MultiChannelGroupByHash.java:264).

    Returns (group_ids, table', overflowed). Dead rows get id = C
    (callers scatter with mode='drop'). `overflowed` is True if the
    table filled up — host rebuilds at 2x capacity (rehash analogue).
    """
    C = table.capacity
    n = keys[0].shape[0]
    keys = [k for k in keys]
    valids = [v for v in valids]

    h = (hash32(keys, valids) & jnp.uint32(C - 1)).astype(jnp.int32)

    slot_keys = list(table.slot_keys)
    slot_valids = list(table.slot_valids)
    slot_used = table.slot_used
    gid = jnp.where(mask, -1, C).astype(jnp.int32)
    probe = jnp.zeros(n, dtype=jnp.int32)
    row_id = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        gid, probe, slot_keys, slot_valids, slot_used, it = state
        return jnp.any(gid < 0) & (it < C + 2)

    def body(state):
        gid, probe, slot_keys, slot_valids, slot_used, it = state
        active = gid < 0
        pos = (h + probe) & (C - 1)
        occ = take_clip(slot_used, pos)
        slot_k = [take_clip(sk, pos) for sk in slot_keys]
        slot_v = [take_clip(sv, pos) for sv in slot_valids]
        match = occ & _keys_equal(slot_k, slot_v, keys, valids)
        gid = jnp.where(active & match, pos, gid)
        # claim race for empty slots: min row id wins the slot this round
        want = active & ~occ & ~match
        claim = jnp.full(C, n, dtype=jnp.int32)
        claim = claim.at[jnp.where(want, pos, C)].min(row_id, mode="drop")
        winner = want & (take_clip(claim, pos) == row_id)
        wpos = jnp.where(winner, pos, C)
        for i in range(len(keys)):
            slot_keys[i] = slot_keys[i].at[wpos].set(keys[i], mode="drop")
            slot_valids[i] = slot_valids[i].at[wpos].set(valids[i], mode="drop")
        slot_used = slot_used.at[wpos].set(True, mode="drop")
        gid = jnp.where(winner, pos, gid)
        # occupied-with-different-key rows advance; claim losers retry same slot
        advance = active & occ & ~match
        probe = jnp.where(advance, probe + 1, probe)
        return gid, probe, slot_keys, slot_valids, slot_used, it + 1

    gid, probe, slot_keys, slot_valids, slot_used, it = jax.lax.while_loop(
        cond, body, (gid, probe, slot_keys, slot_valids, slot_used, jnp.int32(0))
    )
    overflowed = jnp.any(gid < 0)
    gid = jnp.where(gid < 0, C, gid)
    return gid, GroupTable(slot_keys, slot_valids, slot_used), overflowed


@partial(jax.jit, static_argnames=("capacity",))
def assign_group_ids(
    keys: Sequence[jnp.ndarray],
    valids: Sequence[jnp.ndarray],
    mask: jnp.ndarray,
    capacity: int,
):
    """One-shot form: insert a single batch into a fresh table."""
    table = new_group_table([k.dtype for k in keys], capacity)
    return insert_group_ids(table, keys, valids, mask)


def grow_table(table: GroupTable, new_capacity: int):
    """Rebuild at a larger capacity — the tryRehash analogue
    (MultiChannelGroupByHash.java:350). Returns (new_table, remap) where
    remap[old_slot] = new group id (or new_capacity for unused slots) so
    callers migrate accumulator state with a scatter."""
    remap, table2, overflowed = insert_group_ids(
        new_group_table([k.dtype for k in table.slot_keys], new_capacity),
        table.slot_keys,
        table.slot_valids,
        table.slot_used,
    )
    assert not bool(overflowed)
    return table2, remap


# ---------------------------------------------------------------------------
# Masked segment accumulators — the Accumulator/GroupedAccumulator analogue
# (main/operator/aggregation/GroupedAccumulator.java:21). Each returns the
# new accumulator state array(s) of shape (C,).
# ---------------------------------------------------------------------------


def seg_sum(gid, values, weight_mask, capacity, dtype=None):
    dtype = dtype or values.dtype
    z = jnp.zeros(capacity + 1, dtype=dtype)
    contrib = jnp.where(weight_mask, values.astype(dtype), jnp.zeros((), dtype))
    return z.at[gid].add(contrib)[:capacity]


def seg_count(gid, weight_mask, capacity):
    z = jnp.zeros(capacity + 1, dtype=jnp.int64)
    return z.at[gid].add(weight_mask.astype(jnp.int64))[:capacity]


def seg_min(gid, values, weight_mask, capacity):
    info = jnp.iinfo(values.dtype) if jnp.issubdtype(values.dtype, jnp.integer) else None
    big = info.max if info else jnp.inf
    z = jnp.full(capacity + 1, big, dtype=values.dtype)
    contrib = jnp.where(weight_mask, values, jnp.asarray(big, dtype=values.dtype))
    return z.at[gid].min(contrib)[:capacity]


def seg_max(gid, values, weight_mask, capacity):
    info = jnp.iinfo(values.dtype) if jnp.issubdtype(values.dtype, jnp.integer) else None
    small = info.min if info else -jnp.inf
    z = jnp.full(capacity + 1, small, dtype=values.dtype)
    contrib = jnp.where(weight_mask, values, jnp.asarray(small, dtype=values.dtype))
    return z.at[gid].max(contrib)[:capacity]


def seg_any(gid, flags, weight_mask, capacity):
    z = jnp.zeros(capacity + 1, dtype=jnp.bool_)
    return z.at[gid].max(flags & weight_mask)[:capacity]


# ---------------------------------------------------------------------------
# Sort-based group-reduce — the TPU-native fast path.
#
# XLA lowers scatters to (near-)serial loops on TPU, so the linear-probe
# table above is only used where its streaming API is required (the
# mesh-exchange partial tables). The single-device aggregation hot path
# instead sorts rows by key (TPU sorts are fast), finds segment
# boundaries, and reduces segments with cumsum+gather (sums/counts) and
# segmented associative scans (min/max/first) — zero scatters end to end.
# Group ids come out dense [0, n_groups), which also makes the output
# batch compact for free.
# ---------------------------------------------------------------------------


def _order_seed(out_capacity: int) -> int:
    """Hash seed tied to the retry capacity: every overflow-doubling
    ALSO reseeds, so a detected 62-bit hash collision (p ~ 1e-7 per
    batch) cannot recur on the rerun."""
    return out_capacity.bit_length() * 0x9E37


_DEAD_ROW_HASH = jnp.iinfo(jnp.int64).max  # above every 62-bit hash


def _group_hash(keys, valids, mask, seed: int):
    """62-bit key-tuple hash (validity folded in: NULL == NULL groups),
    dead rows forced last."""
    if keys:
        h = hash64(list(keys), list(valids), seed=seed)
    else:
        h = jnp.zeros(mask.shape[0], dtype=jnp.int64)
    return jnp.where(mask, h, _DEAD_ROW_HASH)


def split_limb_keys(keys, valids):
    """Expand long-decimal (n, 2) limb-pair key columns into two int64
    key lanes (lax.sort operands must share one shape). EVERY grouping
    kernel normalizes through this before sorting/segmenting — pair
    equality == value equality, so grouping semantics are unchanged
    (Int128ArrayBlock keys, spi/block/Int128ArrayBlock.java)."""
    if not any(getattr(k, "ndim", 1) == 2 for k in keys):
        return tuple(keys), tuple(valids)
    nk, nv = [], []
    for k, v in zip(keys, valids):
        if getattr(k, "ndim", 1) == 2:
            nk.extend([k[:, 0], k[:, 1]])
            nv.extend([v, v])
        else:
            nk.append(k)
            nv.append(v)
    return tuple(nk), tuple(nv)


def class_and_key(k, v, mask):
    """A single grouping key as the pair the sort path orders by: class
    (0 valid / 1 NULL / 2 dead) and the order-mapped key, zeroed where
    NULL or dead (-0.0 normalized to +0.0 first: SQL groups them
    together)."""
    from trino_tpu.ops.sort import _order_value

    if jnp.issubdtype(k.dtype, jnp.floating):
        kb = _order_value(
            jnp.where(k == 0, jnp.zeros((), k.dtype), k), False
        )
    else:
        kb = k
    kb = jnp.where(v & mask, kb, jnp.zeros((), kb.dtype))
    cls = jnp.where(mask, jnp.where(v, 0, 1), 2).astype(jnp.int8)
    return cls, kb


def _key_lt(a, b):
    """`a` before `b` in lax.sort's order (floats: NaN last)."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        return (a < b) | (jnp.isnan(b) & ~jnp.isnan(a))
    return a < b


def pair_lt(ca, ka, cb, kb):
    """(class, key) pair a before pair b, lexicographically."""
    return (ca < cb) | ((ca == cb) & _key_lt(ka, kb))


def ascends(cls, kb, strict: bool):
    """Per adjacent pair of slots: the (class, key) pair at i + 1 comes
    after the one at i (`strict`), or at least not before it. All true
    without `strict` means the array is what the key sort would return
    (the sort is unstable: ties carry no promise)."""
    if strict:
        return pair_lt(cls[:-1], kb[:-1], cls[1:], kb[1:])
    return ~pair_lt(cls[1:], kb[1:], cls[:-1], kb[:-1])


# A reduce that looked at its input's order answers with a flag WORD
# where the others answer with the overflow flag: bit 0 is that flag (a
# bool reads as a word with no other bit set), bit 1 says the input was
# in key order already and was not sorted.
ORDERED = 2


def flag_word(overflowed, ordered):
    return overflowed.astype(jnp.int32) | jnp.where(ordered, ORDERED, 0)


def _key_order(keys, valids, mask, order=None, seed: int = 0):
    """Stable permutation grouping equal key tuples (NULL == NULL),
    live rows first. MUST order groups exactly like sort_group_reduce
    so order-statistic kernels' slots align with its group slots:
    a single key sorts exactly by (liveness class, order-mapped key);
    several keys sort by the 62-bit tuple hash (collision probability
    ~1e-7 per 1M-row batch; sort_group_reduce DETECTS collisions via an
    independent stream and the reseeding retry re-runs the whole
    family). An incoming `order` acts as the least-significant
    pre-ordering (within-group value order for order statistics —
    stability preserves it)."""
    keys, valids = split_limb_keys(keys, valids)
    n = mask.shape[0]
    if order is None:
        order = jnp.arange(n, dtype=jnp.int32)
    if len(keys) == 1:
        cls, kb = class_and_key(keys[0], valids[0], mask)
        order = take_clip(
            order, jnp.argsort(take_clip(kb, order), stable=True)
        )
        return take_clip(
            order, jnp.argsort(take_clip(cls, order), stable=True)
        )
    hm = _group_hash(keys, valids, mask, seed)
    return take_clip(
        order, jnp.argsort(take_clip(hm, order), stable=True)
    )


# (collision detection lives inline in sort_group_reduce: an
# independent 32-bit stream rides the sort and any in-run variation
# flags the overflow/reseed retry)



def _eq_vals(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Value equality for grouping: SQL groups NaNs together, but float
    == is false for NaN — make NaN equal NaN (floats only; cheap no-op
    for ints). Long-decimal limb pairs (n, 2) compare per row."""
    eq = a == b
    if jnp.issubdtype(a.dtype, jnp.floating):
        eq = eq | (jnp.isnan(a) & jnp.isnan(b))
    if getattr(eq, "ndim", 1) == 2:
        eq = eq.all(axis=-1)
    return eq

def _segment_bounds(sk, sv, sm, n, out_capacity):
    """Per-group segment geometry over key-sorted rows: boundary flags,
    compacted (starts, safe_starts, ends, used), n_groups, overflowed.
    Group ordering is the sorted key order — deterministic, so two
    passes over identically-sorted rows align slot for slot."""
    same = None
    for k, v in zip(sk, sv):
        prev_k = jnp.roll(k, 1)
        prev_v = jnp.roll(v, 1)
        eq = (_eq_vals(k, prev_k) & v & prev_v) | (~v & ~prev_v)
        same = eq if same is None else (same & eq)
    if same is None:  # no keys: single segment
        same = jnp.ones(n, dtype=jnp.bool_)
    first_row = jnp.arange(n) == 0
    prev_live = jnp.roll(sm, 1) & ~first_row
    boundary = sm & (first_row | ~same | ~prev_live)
    n_groups = jnp.sum(boundary.astype(jnp.int32)) if n else jnp.int32(0)
    overflowed = n_groups > out_capacity
    sidx = jnp.where(boundary, jnp.arange(n, dtype=jnp.int32), jnp.int32(n))
    starts = jnp.sort(sidx)[:out_capacity]
    if starts.shape[0] < out_capacity:
        # fewer rows than group slots: pad so every caller's group
        # arrays come out (out_capacity,) — an unpadded short array
        # misaligns against sort_group_reduce's padded key columns
        starts = jnp.pad(
            starts, (0, out_capacity - starts.shape[0]), constant_values=n
        )
    used = starts < n
    safe_starts = jnp.clip(starts, 0, max(n - 1, 0))
    next_starts = jnp.concatenate(
        [starts[1:], jnp.full((1,), n, dtype=starts.dtype)]
    )
    ends = jnp.clip(jnp.where(used, next_starts, 1) - 1, 0, max(n - 1, 0))
    return boundary, starts, safe_starts, ends, used, n_groups, overflowed


# NOTE on scans: multi-operand lax.associative_scan compiles
# pathologically on XLA:TPU at multi-million-element shapes (measured
# HANGING >400s where a full sort of the same array compiles in ~60s).
# Everything here therefore uses cumsum / scatter / gather / segment
# reduces, which compile flat regardless of length.


def _seg_id(boundary: jnp.ndarray) -> jnp.ndarray:
    """Per row: its segment ordinal (rows before the first boundary get
    -1; callers clip or mask)."""
    return jnp.cumsum(boundary.astype(jnp.int32)) - 1


def _seg_first(boundary: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """Per row: vals at its segment's FIRST position (keep-first
    broadcast). Rows before the first boundary read segment 0's value."""
    n = boundary.shape[0]
    g = _seg_id(boundary)
    S = jnp.zeros(n, jnp.int32).at[
        jnp.where(boundary, g, n)
    ].set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    return take_clip(vals, take_clip(S, g))


def _seg_reduce(red, contrib, boundary, num_segments: int):
    """Per-SEGMENT min/max reduction (not a running scan — the grouped
    consumers only read each segment's total). Returns an array indexed
    by segment ordinal, aligned with _segment_bounds' group slots. bool
    participates via an int32 round-trip (segment_min lacks bool)."""
    g = _seg_id(boundary)
    as_bool = contrib.dtype == jnp.bool_
    if as_bool:
        contrib = contrib.astype(jnp.int32)
    fn = jax.ops.segment_min if red == "min" else jax.ops.segment_max
    out = fn(contrib, g, num_segments=num_segments)
    return out.astype(jnp.bool_) if as_bool else out


def _dense_gid(keys, valids, mask, dims, radices, lows=None):
    """Mixed-radix dense group id for plan-time-bounded key domains;
    NULL takes the extra digit d. A key's digit is its dictionary or
    boolean code, or, for an integer key whose exact range the plan
    knows, its distance from the range's low end (`lows`, one a key;
    None: every key counts from 0). Returns (gid, out_of_domain) where
    out_of_domain flags a live valid digit outside [0, d): the runtime
    dictionary outgrew the plan-time bound, or a row holds a value the
    plan's range does not (fail-loud, same contract as
    sort_group_reduce's overflow flag). A key wider than 32 bits is
    tested before it is narrowed."""
    n = mask.shape[0]
    gid = jnp.zeros(n, dtype=jnp.int32)
    out_of_domain = jnp.asarray(False)
    for k, v, d, r, low in zip(
            keys, valids, dims, radices, lows or (0,) * len(dims)):
        raw = k if k.dtype.itemsize > 4 else k.astype(jnp.int32)
        if low:
            raw = raw - jnp.asarray(low, raw.dtype)
        out_of_domain = out_of_domain | jnp.any(
            mask & v & ((raw < 0) | (raw >= d))
        )
        code = jnp.clip(raw, 0, d - 1).astype(jnp.int32)
        code = jnp.where(v, code, d)
        gid = gid * r + code
    return gid, out_of_domain


def _slot_keys(keys, dims, radices, lows, pad):
    """Slot -> (key values, key valids) of a mixed-radix table (last
    key fastest): the digit, counted from the key's low end, in the
    key's own dtype; the NULL digit and unused slots hold any value."""
    total = 1
    for r in radices:
        total *= r
    digits = []
    rem = jnp.arange(total, dtype=jnp.int32)
    for r in reversed(radices):
        digits.append(rem % r)
        rem = rem // r
    digits.reverse()
    group_keys, group_valids = [], []
    for k, d, digit, low in zip(keys, dims, digits, lows or (0,) * len(dims)):
        value = jnp.clip(digit, 0, d - 1).astype(k.dtype)
        if low:
            value = value + jnp.asarray(low, k.dtype)
        group_keys.append(pad(value))
        group_valids.append(pad(digit < d, False))
    return group_keys, group_valids


# Which bounded-domain reduce a plan gets (choose_bounded_reduce). The
# dense reduce unrolls one masked whole-column reduction per slot and
# value slot; the MXU reduce cuts the values into 32-bit word rows and
# makes one pass over them. The jitted reduce alone on a v5e, device
# clock, ms per 2^20 rows, dense / MXU by (slots, value slots: 1 is
# count(*), 2 adds a BIGINT sum, 14 are Q1's) (PERF.md section 6, PR 31):
#   (4, 1) 0.053 / 0.109    (12, 1) 0.126 / 0.109    (60, 1) 0.555 / 0.116
#   (4, 2) 0.085 / 0.126    (12, 2) 0.196 / 0.126    (60, 2) 0.844 / 0.133
#   (4, 14) 0.363 / 0.315   (12, 14) 0.854 / 0.315   (60, 14) 3.927 / 0.323
# The dense reduce grows with slots x value slots, the MXU reduce with
# the word rows alone from a floor of 0.11 ms: they cross between a work
# of 8 and of 12, and every point measured falls on its faster side.
DENSE_MAX_SLOTS = 64
MXU_MAX_SLOTS = 2048
MXU_MIN_WORK = 12  # slots x value slots from which the MXU reduce is taken
# Past MXU_MAX_SLOTS a table is addressed by slot only for COUNTS
# (slot_group_reduce: one scatter-add of 32-bit ones a row mask). The
# scatter is paid a row, not a slot; what grows with the table is the
# rest of a batch's turn, which zeroes it, widens it and folds it into
# the running state, and the state every later operator is handed. On a
# v5e, device ms a batch of 2^20 rows of TPC-H SF10's `o_custkey` by the
# table's slots, the reduce alone / a batch of a train of eight with a
# dictionary look-up fused in (PERF.md section 6, PR 45, step 0):
#   2^21  7.10 / 15.67     2^23  10.51 / 19.28     2^25  16.43 / 22.16
# (up to 2^21 slots the compiler keeps the 32-bit table in fast memory;
# past it the scatter sorts its indices first). The sort path beside it
# is 14.4 ms a batch and as much again in merges, so the scatter wins
# at every size read; the limit is where a state stops being small: 26
# bytes a slot and key, 218 MB at 2^23 and 872 MB at 2^25 of the chip's
# 16 GB, three of them alive inside a train, and a join or a sort above
# handed a batch that wide.
SLOT_MAX_SLOTS = 1 << 23


def choose_bounded_reduce(bound: int, reducers: Sequence[str],
                          dtypes: Sequence, mxu: bool,
                          dense_sums_only: bool = False) -> str:
    """"mxu", "dense", "slot" or "sort": the group reduce for a key
    domain the plan bounds at `bound` slots (NULL digits included), by
    what the callers can observe: the bound, the per-value-slot
    reducers, the value dtypes and whether the backend has the MXU
    kernel (`mxu`: a TPU, or the tests' hook). Four paths, two limits.
    Sums and counts of integer-kind values go to the MXU reduce up to
    MXU_MAX_SLOTS, unless the domain is one the dense reduce takes and
    the work (slots x value slots) is under MXU_MIN_WORK. The dense
    reduce takes up to DENSE_MAX_SLOTS: sums, counts, minima and maxima
    of any dtype, or, where the caller's folds only add
    (`dense_sums_only`: the mesh plane's, which has no other
    slot-addressed reduce either), integer sums and counts alone. Past
    MXU_MAX_SLOTS and up to SLOT_MAX_SLOTS counts alone take the
    scatter-add (slot_group_reduce), on any backend: a 64-bit sum
    scattered costs twenty times a count (PERF.md section 6, PR 44),
    and a minimum does not add. Everything else sorts."""
    adds = all(r in ("sum", "count") for r in reducers)
    ints = not any(jnp.issubdtype(d, jnp.floating) for d in dtypes)
    dense = bound <= DENSE_MAX_SLOTS and (
        adds and ints if dense_sums_only
        else all(r in ("sum", "count", "min", "max") for r in reducers)
    )
    if mxu and adds and ints and bound <= MXU_MAX_SLOTS and not (
        dense and bound * len(reducers) < MXU_MIN_WORK
    ):
        return "mxu"
    if dense:
        return "dense"
    counts = bool(reducers) and all(r == "count" for r in reducers)
    if counts and not dense_sums_only \
            and MXU_MAX_SLOTS < bound <= SLOT_MAX_SLOTS:
        return "slot"
    return "sort"


def shared_valids(value_valids: Sequence) -> tuple:
    """Per value slot, the first slot that carries the same validity
    array (the same object, as a long decimal's four limb slots do); a
    slot without one points at itself. Identity is lost at a jit
    boundary, so the caller of mxu_group_reduce states it."""
    first = {}
    return tuple(
        i if vv is None else first.setdefault(id(vv), i)
        for i, vv in enumerate(value_valids)
    )


@partial(jax.jit, static_argnames=(
    "dims", "reducers", "out_capacity", "value_limbs", "valid_of", "lows"))
def mxu_group_reduce(
    keys: Sequence[jnp.ndarray],
    valids: Sequence[jnp.ndarray],
    mask: jnp.ndarray,
    values: Sequence[jnp.ndarray],
    value_valids: Sequence[Optional[jnp.ndarray]],
    reducers: tuple,
    dims: tuple,
    out_capacity: int,
    value_limbs: Optional[tuple] = None,
    valid_of: Optional[tuple] = None,
    lows: Optional[tuple] = None,
):
    """dense_group_reduce contract, executed by the Pallas MXU one-hot
    contraction kernel (ops/mxu_groupby.py) in one pass over rows of
    32-bit words, for bounded key domains that fit VMEM.
    Restrictions (caller gates, choose_bounded_reduce): reducers in
    {sum, count}; integer-kind value dtypes (BIGINT/decimal-scaled/bool).
    The word rows carry each thing once. `value_limbs` states, per value
    slot, how many 8-bit limbs its values can have (8 unless the caller
    knows 0 <= value < 2^(8k): a long decimal's three low limb slots
    are 4 and have no high word). `valid_of` (shared_valids) names, per
    slot, the first slot with the same validity array: they share one
    indicator column. `lows` as in dense_group_reduce."""
    from trino_tpu.ops.mxu_groupby import MAX_ROWS, grouped_sum_mxu

    assert all(r in ("sum", "count") for r in reducers), reducers
    if mask.shape[0] > MAX_ROWS:
        # per-tile int32 limb accumulators overflow past MAX_ROWS; the
        # sort path has no row bound
        return sort_group_reduce(
            tuple(keys), tuple(valids), mask, tuple(values),
            tuple(value_valids), reducers, out_capacity,
        )
    radices = tuple(d + 1 for d in dims)
    total = 1
    for r in radices:
        total *= r
    assert total <= out_capacity
    gid, out_of_domain = _dense_gid(keys, valids, mask, dims, radices, lows)
    if value_limbs is None:
        value_limbs = (8,) * len(values)
    if valid_of is None:
        valid_of = tuple(range(len(values)))

    # per aggregate: a zero-masked value column and the count of its
    # valid rows: a 0/1 indicator of one limb (one for all the slots
    # that share the validity array), or, where the value has no
    # validity mask, the live-row count the kernel appends anyway (index
    # -1), so that G3's count(*) and its sum's count ride once; for
    # count reducers the count IS the value
    cols, limbs = [], []
    col_of_value = []  # per aggregate: index of its value column
    col_of_count = []  # per aggregate: index of its count column
    for i, (v, vv, red) in enumerate(zip(values, value_valids, reducers)):
        w = mask if vv is None else (mask & vv)
        cnt_idx = -1
        if valid_of[i] != i:
            cnt_idx = col_of_count[valid_of[i]]
        elif vv is not None:
            cnt_idx = len(cols)
            cols.append(w.astype(jnp.int64))
            limbs.append(1)
        col_of_count.append(cnt_idx)
        if red == "sum":
            col_of_value.append(len(cols))
            cols.append(jnp.where(w, v.astype(jnp.int64), 0))
            limbs.append(value_limbs[i])
        else:
            col_of_value.append(cnt_idx)
    interpret = jax.default_backend() != "tpu"
    sums = grouped_sum_mxu(
        gid, tuple(cols), mask, total, interpret=interpret,
        limbs=tuple(limbs),
    )
    row_count = sums[-1]  # appended live-row count per slot

    def pad(x, fill=0):
        return jnp.pad(x, (0, out_capacity - total), constant_values=fill)

    group_keys, group_valids = _slot_keys(keys, dims, radices, lows, pad)

    results = [pad(sums[i]) for i in col_of_value]
    counts = [pad(sums[i]) for i in col_of_count]
    used = pad(row_count > 0, False)
    n_groups = jnp.sum(used.astype(jnp.int32))
    return (
        group_keys,
        group_valids,
        used,
        results,
        counts,
        n_groups,
        out_of_domain,
    )


@partial(jax.jit, static_argnames=(
    "dims", "reducers", "out_capacity", "valid_of", "lows"))
def slot_group_reduce(
    keys: Sequence[jnp.ndarray],
    valids: Sequence[jnp.ndarray],
    mask: jnp.ndarray,
    values: Sequence[jnp.ndarray],
    value_valids: Sequence[Optional[jnp.ndarray]],
    reducers: tuple,
    dims: tuple,
    out_capacity: int,
    valid_of: Optional[tuple] = None,
    lows: Optional[tuple] = None,
):
    """dense_group_reduce contract for COUNTS over a key domain of
    millions of slots (caller gates, choose_bounded_reduce): the table
    is as wide as the domain, slot g the same group in every batch, and
    a batch is one scatter-add of 32-bit ones a DISTINCT row mask into
    a zeroed table: the live rows, and the live rows whose counted
    column is not NULL, once for all the value slots that share the
    validity array (`valid_of`: shared_valids; a slot without one
    counts the live rows). No sort, no merge. A batch holds fewer rows
    than 32 bits count; the tables are widened to the state's 64 bits
    on the way out, and it is the caller's states that add up. `used`
    is "a live row had this key", not "the count is above 0": a group
    whose counted values are all NULL exists, with count 0. `values`
    are not read. `lows` as in dense_group_reduce."""
    assert reducers and all(r == "count" for r in reducers), reducers
    radices = tuple(d + 1 for d in dims)
    total = 1
    for r in radices:
        total *= r
    assert total <= out_capacity
    gid, out_of_domain = _dense_gid(keys, valids, mask, dims, radices, lows)
    if valid_of is None:
        valid_of = tuple(range(len(values)))

    def count_rows(w):
        return jnp.zeros(out_capacity, jnp.int32).at[gid].add(w.astype(jnp.int32))

    rows = count_rows(mask)
    used = rows > 0
    rows = rows.astype(jnp.int64)
    counts = []
    for i, vv in enumerate(value_valids):
        if vv is None:
            counts.append(rows)
        elif valid_of[i] != i:
            counts.append(counts[valid_of[i]])
        else:
            counts.append(count_rows(mask & vv).astype(jnp.int64))

    def pad(x, fill=0):
        return jnp.pad(x, (0, out_capacity - total), constant_values=fill)

    group_keys, group_valids = _slot_keys(keys, dims, radices, lows, pad)
    n_groups = jnp.sum(used.astype(jnp.int32))
    return (
        group_keys,
        group_valids,
        used,
        list(counts),
        list(counts),
        n_groups,
        out_of_domain,
    )


@partial(jax.jit, static_argnames=("dims", "reducers", "out_capacity", "lows"))
def dense_group_reduce(
    keys: Sequence[jnp.ndarray],
    valids: Sequence[jnp.ndarray],
    mask: jnp.ndarray,
    values: Sequence[jnp.ndarray],
    value_valids: Sequence[Optional[jnp.ndarray]],
    reducers: tuple,
    dims: tuple,  # per key: digits (codes or value - low in [0, d)); NULL -> d
    out_capacity: int,
    lows: Optional[tuple] = None,  # per key: the value of digit 0 (None: 0)
):
    """Group-reduce for PLAN-TIME-BOUNDED key domains (dictionary and
    boolean codes; integer keys whose exact range the plan knows, by
    their distance from `lows`): the group id is the dense mixed-radix
    composition of the digits — no sort, no hash table, no scatter.
    Each group reduces with
    a masked whole-column reduction; the per-group loop unrolls into one
    fused XLA program (total domain is capped small by the caller).
    Same output contract as sort_group_reduce; group ids are slot
    positions rather than dense-from-zero, which every consumer already
    handles via `used`."""
    n = mask.shape[0]
    radices = tuple(d + 1 for d in dims)  # one extra slot per key: NULL
    total = 1
    for r in radices:
        total *= r
    assert total <= out_capacity
    gid, out_of_domain = _dense_gid(keys, valids, mask, dims, radices, lows)

    def pad(x, fill=0):
        return jnp.pad(x, (0, out_capacity - total), constant_values=fill)

    group_keys, group_valids = _slot_keys(keys, dims, radices, lows, pad)

    results = []
    counts = []
    for v, vv, red in zip(values, value_valids, reducers):
        w = mask if vv is None else (mask & vv)
        outs = []
        cnts = []
        for g in range(total):
            sel = w & (gid == g)
            cnts.append(jnp.sum(sel.astype(jnp.int64)))
            if red in ("sum", "count"):
                acc_dt = (
                    jnp.float64
                    if jnp.issubdtype(v.dtype, jnp.floating)
                    else jnp.int64
                )
                contrib = (
                    sel.astype(jnp.int64)
                    if red == "count"
                    else jnp.where(sel, v.astype(acc_dt), jnp.zeros((), acc_dt))
                )
                outs.append(jnp.sum(contrib))
            elif red in ("min", "max"):
                if jnp.issubdtype(v.dtype, jnp.floating):
                    neutral = jnp.inf if red == "min" else -jnp.inf
                elif v.dtype == jnp.bool_:
                    neutral = red == "min"
                else:
                    info = jnp.iinfo(v.dtype)
                    neutral = info.max if red == "min" else info.min
                contrib = jnp.where(sel, v, jnp.asarray(neutral, v.dtype))
                outs.append(
                    jnp.min(contrib) if red == "min" else jnp.max(contrib)
                )
            else:
                raise ValueError(red)
        results.append(pad(jnp.stack(outs)))
        counts.append(pad(jnp.stack(cnts)))
    # used: any live row landed in the slot
    row_cnt = jnp.stack(
        [jnp.sum((mask & (gid == g)).astype(jnp.int32)) for g in range(total)]
    )
    used = pad(row_cnt > 0, False)
    n_groups = jnp.sum(used.astype(jnp.int32))
    return (
        group_keys,
        group_valids,
        used,
        results,
        counts,
        n_groups,
        out_of_domain,
    )


def _segment_geometry(boundary, n: int, out_capacity: int):
    """starts/safe_starts/ends/used/n_groups/overflow from boundary
    flags. Compaction of boundary positions uses top_k when the capacity
    is small relative to n (the common case — far cheaper than a second
    full sort), else a full sort."""
    n_groups = jnp.sum(boundary.astype(jnp.int32)) if n else jnp.int32(0)
    overflowed = n_groups > out_capacity
    sidx = jnp.where(boundary, jnp.arange(n, dtype=jnp.int32), jnp.int32(n))
    if out_capacity * 4 <= n:
        starts = -jax.lax.top_k(-sidx, out_capacity)[0]
    else:
        starts = jnp.sort(sidx)[:out_capacity]
        if starts.shape[0] < out_capacity:
            starts = jnp.pad(
                starts, (0, out_capacity - starts.shape[0]),
                constant_values=n,
            )
    used = starts < n
    safe_starts = jnp.clip(starts, 0, max(n - 1, 0))
    next_starts = jnp.concatenate(
        [starts[1:], jnp.full((1,), n, dtype=starts.dtype)]
    )
    ends = jnp.clip(jnp.where(used, next_starts, 1) - 1, 0, max(n - 1, 0))
    return starts, safe_starts, ends, used, n_groups, overflowed


# sorts with more operands than this gather their remaining payloads
# post-sort instead (XLA:TPU sort compile time grows ~linearly with
# operand count, ~7s each at 1M rows)
_MAX_SORT_OPERANDS = 10


def _fast_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive scan via a (tiles, 256) two-level decomposition: the
    1-D lowering runs log2(n) full passes; the 2-D form does one short
    lane scan per tile plus a tiny inter-tile scan."""
    n = x.shape[0]
    tile = 256
    if n % tile:
        return jnp.cumsum(x)
    x2 = x.reshape(n // tile, tile)
    intra = jnp.cumsum(x2, axis=1)
    totals = intra[:, -1]
    offs = jnp.cumsum(totals) - totals
    return (intra + offs[:, None]).reshape(-1)


def _segment_sums_at(c: jnp.ndarray, ends, used):
    """Per-segment totals from an inclusive scan: segments tile the live
    prefix contiguously, so sum(g) = c[end_g] - c[end_{g-1}] — ONE
    capacity-sized gather + a shifted diff, instead of two gathers."""
    at_ends = jnp.where(used, take_clip(c, ends), jnp.zeros((), c.dtype))
    prev = jnp.concatenate([jnp.zeros(1, c.dtype), at_ends[:-1]])
    return jnp.where(used, at_ends - prev, jnp.zeros((), c.dtype))


@partial(jax.jit, static_argnames=("reducers", "out_capacity", "check_order"))
def sort_group_reduce(
    keys: Sequence[jnp.ndarray],
    valids: Sequence[jnp.ndarray],
    mask: jnp.ndarray,
    values: Sequence[jnp.ndarray],
    value_valids: Sequence[Optional[jnp.ndarray]],
    reducers: tuple,  # per value: 'sum' | 'count' | 'min' | 'max' | 'first'
    out_capacity: int,
    check_order: bool = False,
):
    """Group by `keys` and reduce each value column in one pass.

    Returns (group_keys, group_valids, used, results, counts, n_groups,
    overflowed): group arrays of shape (out_capacity,) dense from 0;
    `results[i]` is reducer i's per-group result; `counts[i]` the number
    of non-null contributions (for SQL empty-group NULL semantics).

    Engine hot path (GroupByHash analogue). A multi-operand lax.sort on
    the grouping key (exact (class, key) for a single key column; the
    62-bit tuple hash for several) moves the rows: value columns ride
    as payload operands, so per-column random gathers — ~10ms per 1M
    rows on TPU, the old design's dominant cost — disappear. Segment
    boundaries come from the sorted key itself. Compaction of the
    boundary positions uses top_k where the table is small beside the
    batch and a second sort, carrying the per-group outputs, where it
    is not.

    `check_order` (the aggregation's ingest asks; a single key only): a
    compare over the keys first, and rows that are in key order already
    (a scan of a table clustered on the key) skip the key sort. The
    answer is the same either way; `overflowed` is then a flag_word
    that also says which way the rows went.
    """
    n = mask.shape[0]
    seed = _order_seed(out_capacity)
    iota = jnp.arange(n, dtype=jnp.int32)

    # long-decimal (n, 2) limb-pair keys split into two int64 key lanes
    # here (lax.sort operands must share one shape) and restack on
    # output, so every caller passes columns as-is (Int128ArrayBlock
    # keys group like any other type, spi/block/Int128ArrayBlock.java)
    key_lanes = [2 if getattr(k, "ndim", 1) == 2 else 1 for k in keys]
    keys, valids = split_limb_keys(keys, valids)

    single_key = len(keys) == 1
    if single_key:
        # exact: class (0 valid / 1 NULL / 2 dead) + order-mapped key
        # (-0.0 normalized to +0.0 first: SQL groups them together)
        cls, kb = class_and_key(keys[0], valids[0], mask)
        sort_keys = (cls, kb)
        num_keys = 2
        extra = []
    else:
        # tuple hash; collisions detected via an independent 32-bit
        # stream riding as payload, resolved by the reseeding retry
        hm = _group_hash(keys, valids, mask, seed)
        sort_keys = (hm,)
        num_keys = 1
        extra = (
            [hash32(list(keys), list(valids), seed=seed + 0x7F4A)]
            if keys
            else []
        )

    # payload assembly: row ids, collision stream, then value columns
    # (+ their validity) until the operand budget forces gathers
    payloads: List[jnp.ndarray] = [iota] + extra
    carried: List[Optional[int]] = []  # per value: payload idx or None
    carried_vv: List[Optional[int]] = []
    for val, vv, red in zip(values, value_valids, reducers):
        vi = None
        if red != "count" and len(sort_keys) + len(payloads) < _MAX_SORT_OPERANDS:
            vi = len(payloads)
            payloads.append(val)
        carried.append(vi)
        wi = None
        if vv is not None and len(sort_keys) + len(payloads) < _MAX_SORT_OPERANDS:
            wi = len(payloads)
            payloads.append(vv)
        carried_vv.append(wi)
    # multi-key: group key columns ride too when budget allows, so the
    # output extraction reads sorted data at `starts` (one cap-sized
    # gather) instead of chaining through the row permutation (two)
    carried_keys: List[Optional[int]] = []
    carried_kv: List[Optional[int]] = []
    if not single_key:
        for k, v in zip(keys, valids):
            ki = None
            if len(sort_keys) + len(payloads) < _MAX_SORT_OPERANDS:
                ki = len(payloads)
                payloads.append(k)
            carried_keys.append(ki)
            kvi = None
            if len(sort_keys) + len(payloads) < _MAX_SORT_OPERANDS:
                kvi = len(payloads)
                payloads.append(v)
            carried_kv.append(kvi)

    key_sort = partial(jax.lax.sort, num_keys=num_keys, is_stable=False)
    ordered = None
    if check_order and single_key:
        ordered = jnp.all(ascends(cls, kb, strict=False))
        sorted_ops = jax.lax.cond(
            ordered, tuple, key_sort, sort_keys + tuple(payloads)
        )
    else:
        sorted_ops = key_sort(sort_keys + tuple(payloads))
    order = sorted_ops[num_keys]

    first = iota == 0
    if single_key:
        s_cls, s_kb = sorted_ops[0], sorted_ops[1]
        sm = s_cls < 2
        changed = (s_cls != jnp.roll(s_cls, 1)) | ~_eq_vals(
            s_kb, jnp.roll(s_kb, 1)
        )
        boundary = sm & (first | changed)
        collision = jnp.asarray(False)
    else:
        hs = sorted_ops[0]
        sm = hs != _DEAD_ROW_HASH
        boundary = sm & (first | (hs != jnp.roll(hs, 1)))
        if extra:
            # rows of one segment are adjacent after the sort, so "some
            # row's independent stream differs from its segment's" ⟺
            # "some adjacent pair inside a segment differs" — a dense
            # roll+compare instead of _seg_first's scatter+gather
            # (scatters cost ~117ms/M on this TPU)
            h2s = sorted_ops[num_keys + 1]
            collision = jnp.any(
                sm & ~boundary & (h2s != jnp.roll(h2s, 1))
            )
        else:
            collision = jnp.asarray(False)

    def sorted_payload(idx, col):
        if idx is not None:
            return sorted_ops[num_keys + idx]
        return take_clip(col, order)

    # -- boundary compaction + per-group extraction ------------------
    # Large group counts (cap*4 > n) use CARRIED compaction: the
    # boundary-position sort carries every per-group output value as a
    # payload operand, so the cap-sized gathers of the gather path
    # (~16.5ms per 1M gathered elements on this TPU — they dominated
    # Q18's 1.5M-group aggregation) disappear. Segment sums ride as
    # exclusive prefix sums whose shifted diff is the per-group total;
    # non-boundary filler entries carry the grand total so the last
    # group's diff closes correctly. Small caps keep the top_k + tiny
    # gather path (a full multi-operand n-sort would cost more).
    big_cap = out_capacity * 4 > n > 0
    iota32 = jnp.arange(n, dtype=jnp.int32)
    sidx = jnp.where(boundary, iota32, jnp.int32(n))

    carry_cols: List[jnp.ndarray] = []
    carry_totals: dict = {}

    def carry(arr):
        if arr.dtype == jnp.bool_:
            arr = arr.astype(jnp.int8)
        carry_cols.append(arr)
        return len(carry_cols) - 1

    def excl_carry(contrib):
        """Exclusive cumsum at boundaries, grand total elsewhere."""
        c = _fast_cumsum(contrib)
        total = c[-1] if n else jnp.zeros((), contrib.dtype)
        slot = carry(jnp.where(boundary, c - contrib, total))
        carry_totals[slot] = total
        return slot

    plan: dict = {}
    if big_cap:
        if single_key:
            plan["kb"] = carry(sorted_ops[1])
            plan["cls"] = carry(sorted_ops[0].astype(jnp.int32))
        else:
            plan["mk"] = []
            for i, (k, v) in enumerate(zip(keys, valids)):
                plan["mk"].append((
                    carry(sorted_payload(carried_keys[i], k)),
                    carry(sorted_payload(carried_kv[i], v)),
                ))
        plan["rows"] = excl_carry(sm.astype(jnp.int64))
        plan["vals"] = []
        for i, (val, vv, red) in enumerate(
            zip(values, value_valids, reducers)
        ):
            svv = None if vv is None else sorted_payload(carried_vv[i], vv)
            w = sm if svv is None else (sm & svv)
            cnt_slot = None if svv is None else excl_carry(w.astype(jnp.int64))
            sum_slot = None
            if red == "sum":
                sv_ = sorted_payload(carried[i], val)
                acc_dt = (
                    jnp.float64
                    if jnp.issubdtype(sv_.dtype, jnp.floating)
                    else jnp.int64
                )
                contrib = jnp.where(
                    w, sv_.astype(acc_dt), jnp.zeros((), acc_dt)
                )
                sum_slot = excl_carry(contrib)
            plan["vals"].append((cnt_slot, sum_slot))
        # compaction sorts share the boundary-position key; payloads
        # chunk under the operand budget (compile time grows with
        # operand count)
        comp: List[jnp.ndarray] = []
        starts_full = None
        budget = _MAX_SORT_OPERANDS - 1
        for c0 in range(0, len(carry_cols), budget):
            chunk = carry_cols[c0 : c0 + budget]
            out = jax.lax.sort(tuple([sidx] + chunk), num_keys=1)
            starts_full = out[0]
            comp.extend(out[1:])
        starts = starts_full[:out_capacity]
        if starts.shape[0] < out_capacity:
            starts = jnp.pad(
                starts, (0, out_capacity - starts.shape[0]),
                constant_values=n,
            )
        comp = [c[:out_capacity] for c in comp]
        used = starts < n
        safe_starts = jnp.clip(starts, 0, max(n - 1, 0))
        next_starts = jnp.concatenate(
            [starts[1:], jnp.full((1,), n, dtype=starts.dtype)]
        )
        ends = jnp.clip(jnp.where(used, next_starts, 1) - 1, 0, max(n - 1, 0))
        n_groups = jnp.sum(boundary.astype(jnp.int32)) if n else jnp.int32(0)
        overflowed = (n_groups > out_capacity) | collision

        def pad_slot(slot, fill=0):
            c = comp[slot]
            if c.shape[0] < out_capacity:
                c = jnp.pad(c, (0, out_capacity - c.shape[0]))
                c = jnp.where(
                    jnp.arange(out_capacity) < comp[slot].shape[0],
                    c, jnp.asarray(fill, c.dtype),
                )
            return c

        def seg_total(slot):
            total = carry_totals[slot]
            e = pad_slot(slot, fill=total)
            nxt = jnp.concatenate([e[1:], total[None]])
            # unused slots carry the grand total (the filler), so the
            # last used group's diff reads total - its prefix
            return jnp.where(used, nxt - e, jnp.zeros((), e.dtype))

        if single_key:
            kvals = pad_slot(plan["kb"])
            if jnp.issubdtype(keys[0].dtype, jnp.floating):
                # the carried operand holds order-mapped BITS; recover
                # through the row permutation (cap-sized, rare path)
                kvals = take_clip(keys[0], take_clip(order, safe_starts))
            group_keys = [
                jnp.where(
                    used, kvals.astype(keys[0].dtype),
                    jnp.zeros((), keys[0].dtype),
                )
            ]
            group_valids = [(pad_slot(plan["cls"], fill=2) == 0) & used]
        else:
            group_keys = []
            group_valids = []
            for i, (k, v) in enumerate(zip(keys, valids)):
                ks, vs_ = plan["mk"][i]
                group_keys.append(
                    jnp.where(
                        used, pad_slot(ks).astype(k.dtype),
                        jnp.zeros((), k.dtype),
                    )
                )
                group_valids.append((pad_slot(vs_) != 0) & used)
        seg_rows = seg_total(plan["rows"])
    else:
        starts, safe_starts, ends, used, n_groups, overflowed = (
            _segment_geometry(boundary, n, out_capacity)
        )
        overflowed = overflowed | collision

        # group key columns: read the SORTED key at each segment start —
        # one capacity-sized gather per column, no permutation chase
        if single_key:
            if jnp.issubdtype(keys[0].dtype, jnp.floating):
                # the sorted operand holds order-mapped BITS; recover the
                # float through the row permutation instead
                kvals = take_clip(keys[0], take_clip(order, safe_starts))
            else:
                kvals = take_clip(sorted_ops[1], safe_starts)
            group_keys = [
                jnp.where(used, kvals, jnp.zeros((), keys[0].dtype))
            ]
            group_valids = [
                (take_clip(sorted_ops[0], safe_starts) == 0) & used
            ]
        else:
            group_keys = []
            group_valids = []
            for i, (k, v) in enumerate(zip(keys, valids)):
                sk_full = sorted_payload(carried_keys[i], k)
                sv_full = sorted_payload(carried_kv[i], v)
                group_keys.append(
                    jnp.where(
                        used, take_clip(sk_full, safe_starts),
                        jnp.zeros((), k.dtype),
                    )
                )
                group_valids.append(take_clip(sv_full, safe_starts) & used)

        # per-segment live-row count straight from the geometry (no
        # scan); the LAST segment's `ends` extends to n-1 past the dead
        # tail, so clamp to the final live row
        n_live = jnp.sum(sm.astype(jnp.int32))
        seg_rows = jnp.where(
            used,
            (jnp.minimum(ends, n_live - 1) - safe_starts + 1).astype(jnp.int64),
            0,
        )

    results = []
    counts = []
    for i, (val, vv, red) in enumerate(zip(values, value_valids, reducers)):
        svv = None if vv is None else sorted_payload(carried_vv[i], vv)
        sv_ = (
            sorted_payload(carried[i], val)
            if red != "count"
            else jnp.zeros(n, dtype=jnp.int64)
        )
        w = sm if svv is None else (sm & svv)
        if svv is None:
            cnt = seg_rows
        elif big_cap:
            cnt = seg_total(plan["vals"][i][0])
        else:
            cnt = _segment_sums_at(
                _fast_cumsum(w.astype(jnp.int64)), ends, used
            )
        counts.append(cnt)
        if red in ("sum", "count"):
            if red == "count":
                out = cnt
                results.append(out)
                continue
            if big_cap:
                out = seg_total(plan["vals"][i][1])
                results.append(out)
                continue
            acc_dt = (
                jnp.float64
                if jnp.issubdtype(sv_.dtype, jnp.floating)
                else jnp.int64
            )
            contrib = jnp.where(w, sv_.astype(acc_dt), jnp.zeros((), acc_dt))
            out = _segment_sums_at(_fast_cumsum(contrib), ends, used)
        elif red in ("min", "max"):
            if jnp.issubdtype(sv_.dtype, jnp.floating):
                neutral = jnp.inf if red == "min" else -jnp.inf
            elif sv_.dtype == jnp.bool_:
                neutral = red == "min"
            else:
                info = jnp.iinfo(sv_.dtype)
                neutral = info.max if red == "min" else info.min
            contrib = jnp.where(w, sv_, jnp.asarray(neutral, dtype=sv_.dtype))
            out = _seg_reduce(
                "min" if red == "min" else "max",
                contrib, boundary, ends.shape[0],
            )
        elif red in ("min128h", "max128h"):
            # Int128 extreme, high limb: plain signed min/max. The LOW
            # limb rides the NEXT slot with the matching *128l reducer.
            base = red[:3]
            info = jnp.iinfo(jnp.int64)
            neutral = info.max if base == "min" else info.min
            contrib = jnp.where(w, sv_, jnp.asarray(neutral, jnp.int64))
            out = _seg_reduce(base, contrib, boundary, ends.shape[0])
        elif red in ("min128l", "max128l"):
            # Int128 extreme, low limb: unsigned min/max among rows
            # whose high limb equals the group's extreme (lexicographic
            # (hi, lo-as-u64) = Int128 order; Int128Math.compare). The
            # matching *128h slot precedes this one, though not always
            # adjacently (state merges interleave count slots).
            base = red[:3]
            hi_idx = max(
                j for j in range(i) if reducers[j] == f"{base}128h"
            )
            s_hi = sorted_payload(carried[hi_idx], values[hi_idx])
            hi_grp = results[hi_idx]
            g = jnp.clip(_seg_id(boundary), 0, ends.shape[0] - 1)
            hi_row = take_clip(hi_grp, g)
            sgn = jnp.int64(-0x8000000000000000)
            info = jnp.iinfo(jnp.int64)
            neutral = info.max if base == "min" else info.min
            sel = w & (s_hi == hi_row)
            contrib = jnp.where(sel, sv_ ^ sgn, jnp.asarray(neutral, jnp.int64))
            out = _seg_reduce(base, contrib, boundary, ends.shape[0]) ^ sgn
        elif red == "first":
            # first non-null value per segment: the smallest row index
            # whose value is non-null, then one gather
            pos = jax.ops.segment_min(
                jnp.where(w, jnp.arange(n, dtype=jnp.int32), jnp.int32(n)),
                _seg_id(boundary),
                num_segments=ends.shape[0],
            )
            out = take_clip(sv_, pos)
        else:
            raise ValueError(red)
        results.append(out)
    if any(l == 2 for l in key_lanes):
        gk2, gv2 = [], []
        i = 0
        for l in key_lanes:
            if l == 2:
                gk2.append(
                    jnp.stack([group_keys[i], group_keys[i + 1]], axis=-1)
                )
                gv2.append(group_valids[i])
            else:
                gk2.append(group_keys[i])
                gv2.append(group_valids[i])
            i += l
        group_keys, group_valids = gk2, gv2
    if ordered is not None:
        overflowed = flag_word(overflowed, ordered)
    return group_keys, group_valids, used, results, counts, n_groups, overflowed


# ---------------------------------------------------------------------------
# Holistic (order-statistic) grouped aggregates — min_by/max_by and
# approx_percentile need the raw rows, not mergeable accumulators
# (Trino's MinMaxByNStateFactory / qdigest aggregations). The planner
# runs them single-step after a gather; these kernels share the key
# sort + segment geometry with sort_group_reduce, so their per-slot
# outputs align with its group ordering exactly.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("out_capacity",))
def key_order(keys, valids, mask, out_capacity: int = 0):
    """Jitted public form of the grouping sort permutation, so callers
    computing several order statistics over the same keys sort ONCE and
    pass the permutation into each kernel. `out_capacity` must match the
    capacity passed to the kernels sharing this order (it seeds the
    group hash, and slot alignment requires one ordering). Long-decimal
    (n, 2) keys split into limb lanes like sort_group_reduce."""
    return _key_order(keys, valids, mask, seed=_order_seed(out_capacity))


@partial(jax.jit, static_argnames=("kind", "out_capacity"))
def grouped_argbest(
    keys, valids, mask, by, by_valid, x, x_valid, kind: str,
    out_capacity: int, order=None,
):
    """min_by/max_by: x at the row with the smallest/largest `by` per
    group (rows with NULL `by` are ignored; ties keep the first row in
    sort order — Trino returns an arbitrary one). Returns
    (x_data, x_valid) aligned with sort_group_reduce's group slots.
    Long-decimal (n, 2) group keys, `by`, and `x` columns all
    supported (keys split into limb lanes; Int128 `by` reduces
    lexicographically; `x` gathers row-wise)."""
    n = mask.shape[0]
    keys, valids = split_limb_keys(keys, valids)
    if order is None:
        order = _key_order(
            keys, valids, mask, seed=_order_seed(out_capacity)
        )
    sm = take_clip(mask, order)
    sk = [take_clip(k, order) for k in keys]
    sv = [take_clip(v, order) for v in valids]
    boundary, starts, safe_starts, ends, used, _, _ = _segment_bounds(
        sk, sv, sm, n, out_capacity
    )
    w = sm if by_valid is None else (sm & take_clip(by_valid, order))
    s_x = take_clip(x, order, axis=0)
    s_xv = (
        jnp.ones(n, dtype=jnp.bool_)
        if x_valid is None
        else take_clip(x_valid, order)
    )
    # two segment reduces + gathers instead of a 5-operand associative
    # scan (see the scan NOTE above): (1) the best `by` per segment,
    # (2) the FIRST row attaining it (ties keep first in sort order).
    # NaN `by` values diverge from the old scan (NaN poisons the
    # reduce -> NULL result, where the scan kept the first valid row);
    # SQL comparison keys are NaN-free in practice.
    cap = ends.shape[0]
    g = _seg_id(boundary)
    red = "min" if kind == "min_by" else "max"
    if getattr(by, "ndim", 1) == 2:
        # Int128 `by`: lexicographic (signed hi, unsigned lo) best
        s_bh = take_clip(by[:, 0], order)
        s_bl = take_clip(by[:, 1], order)
        sgn = jnp.int64(-0x8000000000000000)
        info = jnp.iinfo(jnp.int64)
        neutral = info.max if kind == "min_by" else info.min
        nbh = jnp.where(w, s_bh, jnp.asarray(neutral, jnp.int64))
        best_h = _seg_reduce(red, nbh, boundary, cap)
        at_h = w & (s_bh == take_clip(best_h, g))
        lo_u = s_bl ^ sgn
        nbl = jnp.where(at_h, lo_u, jnp.asarray(neutral, jnp.int64))
        best_l = _seg_reduce(red, nbl, boundary, cap)
        is_best = at_h & (lo_u == take_clip(best_l, g))
    else:
        s_by = take_clip(by, order)
        if jnp.issubdtype(s_by.dtype, jnp.floating):
            neutral = jnp.inf if kind == "min_by" else -jnp.inf
        elif s_by.dtype == jnp.bool_:
            neutral = kind == "min_by"
        else:
            info = jnp.iinfo(s_by.dtype)
            neutral = info.max if kind == "min_by" else info.min
        nb = jnp.where(w, s_by, jnp.asarray(neutral, s_by.dtype))
        best = _seg_reduce(red, nb, boundary, cap)
        is_best = w & (nb == take_clip(best, g))
    pos = jax.ops.segment_min(
        jnp.where(is_best, jnp.arange(n, dtype=jnp.int32), jnp.int32(n)),
        g, num_segments=cap,
    )
    has = pos < n
    out_x = take_clip(s_x, pos, axis=0)
    out_valid = has & take_clip(s_xv, pos) & used
    used_b = used[:, None] if getattr(out_x, "ndim", 1) == 2 else used
    return jnp.where(used_b, out_x, jnp.zeros((), out_x.dtype)), out_valid


@partial(jax.jit, static_argnames=("fraction", "out_capacity"))
def grouped_weighted_percentile(
    keys, valids, mask, mn, mn_valid, cnt, mx,
    fraction: float, out_capacity: int,
):
    """Percentile over per-BUCKET summaries (count, min, max) — the
    merge half of the mergeable approx_percentile
    (sql/optimizer.RewriteApproxPercentile): rows are quantile-bucket
    summaries, weights are exact element counts, and the estimate
    interpolates between the chosen bucket's min and max. Exact when
    the bucket holds one distinct value. Returns (data, valid) aligned
    with sort_group_reduce's group slots."""
    from trino_tpu.ops.sort import _order_value

    n = mask.shape[0]
    mv = jnp.ones(n, jnp.bool_) if mn_valid is None else mn_valid
    # pre-order: bucket min ascending (bucket ids are order-preserving,
    # so min-order == bucket order); invalid rows last
    pre = jnp.argsort(_order_value(mn, False), stable=True).astype(jnp.int32)
    pre = take_clip(pre, jnp.argsort(take_clip(~mv, pre), stable=True))
    keys, valids = split_limb_keys(keys, valids)
    order = _key_order(
        keys, valids, mask, order=pre, seed=_order_seed(out_capacity)
    )
    sm = take_clip(mask, order)
    sk = [take_clip(k, order) for k in keys]
    sv = [take_clip(v, order) for v in valids]
    boundary, starts, safe_starts, ends, used, _, _ = _segment_bounds(
        sk, sv, sm, n, out_capacity
    )
    w = sm & take_clip(mv, order)
    s_mn = take_clip(mn, order)
    s_mx = take_clip(mx, order)
    s_c = jnp.where(w, take_clip(cnt, order).astype(jnp.int64), 0)
    cum = jnp.cumsum(s_c)
    cum_ex = cum - s_c
    # per segment: total weight N, target rank R = floor(f*(N-1)+0.5)
    N = take_clip(cum, ends) - take_clip(cum_ex, safe_starts)
    R = jnp.clip(
        jnp.floor(fraction * (N - 1).astype(jnp.float64) + 0.5)
        .astype(jnp.int64),
        0, jnp.maximum(N - 1, 0),
    )
    g = _seg_id(boundary)
    base = take_clip(cum_ex, safe_starts)  # per-slot segment weight offset
    cum_in = cum - take_clip(base, g)  # within-segment inclusive weight
    R_row = take_clip(R, g)
    hit = w & (cum_in > R_row)
    pos = jax.ops.segment_min(
        jnp.where(hit, jnp.arange(n, dtype=jnp.int32), jnp.int32(n)),
        g, num_segments=ends.shape[0],
    )
    safe_pos = jnp.clip(pos, 0, max(n - 1, 0))
    c_at = jnp.maximum(take_clip(s_c, safe_pos), 1)
    p_in = R - (take_clip(cum_in, safe_pos) - c_at)
    lo_v = take_clip(s_mn, safe_pos)
    hi_v = take_clip(s_mx, safe_pos)
    frac_in = jnp.where(
        c_at > 1,
        p_in.astype(jnp.float64) / (c_at - 1).astype(jnp.float64),
        0.0,
    )
    est = lo_v.astype(jnp.float64) + (
        hi_v.astype(jnp.float64) - lo_v.astype(jnp.float64)
    ) * frac_in
    if jnp.issubdtype(mn.dtype, jnp.floating):
        out = est.astype(mn.dtype)
    else:
        out = (jnp.sign(est) * jnp.floor(jnp.abs(est) + 0.5)).astype(mn.dtype)
    valid = used & (N > 0) & (pos < n)
    return jnp.where(valid, out, jnp.zeros((), out.dtype)), valid


@partial(jax.jit, static_argnames=("fraction", "out_capacity"))
def grouped_percentile(
    keys, valids, mask, x, x_valid, fraction: float, out_capacity: int,
):
    """approx_percentile(x, fraction) per group, computed EXACTLY by
    nearest-rank over the sorted segment (exact answers satisfy the
    approximate contract; the reference's qdigest sketch trades
    accuracy for mergeability we don't need single-step). NULL x rows
    are excluded. Returns (data, valid) aligned with
    sort_group_reduce's group slots."""
    from trino_tpu.ops.sort import _order_value

    n = mask.shape[0]
    xv = (
        jnp.ones(n, dtype=jnp.bool_) if x_valid is None else x_valid
    )
    # pre-order: x ascending, NULL x last within each group
    pre = jnp.argsort(_order_value(x, False), stable=True).astype(jnp.int32)
    pre = take_clip(pre, jnp.argsort(take_clip(~xv, pre), stable=True))
    keys, valids = split_limb_keys(keys, valids)
    order = _key_order(
        keys, valids, mask, order=pre, seed=_order_seed(out_capacity)
    )
    sm = take_clip(mask, order)
    sk = [take_clip(k, order) for k in keys]
    sv = [take_clip(v, order) for v in valids]
    boundary, starts, safe_starts, ends, used, _, _ = _segment_bounds(
        sk, sv, sm, n, out_capacity
    )
    w = sm & take_clip(xv, order)
    s_x = take_clip(x, order)
    cnt_c = jnp.cumsum(w.astype(jnp.int64))
    cnt_ex = cnt_c - w.astype(jnp.int64)
    cnt = take_clip(cnt_c, ends) - take_clip(cnt_ex, safe_starts)
    # nearest rank: index floor(fraction * (cnt-1) + 0.5) into the
    # valid prefix of the segment (invalid rows sorted to its tail)
    rank = jnp.floor(
        fraction * (cnt - 1).astype(jnp.float64) + 0.5
    ).astype(jnp.int64)
    rank = jnp.clip(rank, 0, jnp.maximum(cnt - 1, 0))
    idx = jnp.clip(
        safe_starts.astype(jnp.int64) + rank, 0, max(n - 1, 0)
    ).astype(jnp.int32)
    out = take_clip(s_x, idx)
    valid = used & (cnt > 0)
    return jnp.where(valid, out, jnp.zeros((), out.dtype)), valid


@partial(jax.jit, static_argnames=("out_capacity",))
def grouped_count_distinct(keys, valids, mask, x, x_valid, out_capacity):
    """Distinct non-NULL x per group (approx_distinct's contract with
    error 0 — exact answers satisfy the approximate bound; the
    mergeable HLL sketch is planned work). Rows pre-order by (valid x
    first, x ascending) so equal values sit adjacent within each group;
    a distinct value = a valid row at a group boundary or where x
    changes. Slots align with sort_group_reduce's group ordering."""
    from trino_tpu.ops.sort import _order_value

    n = mask.shape[0]
    xv = jnp.ones(n, dtype=jnp.bool_) if x_valid is None else x_valid
    xb = (
        _order_value(x, False)
        if jnp.issubdtype(x.dtype, jnp.floating)
        else x
    )
    pre = jnp.argsort(xb, stable=True).astype(jnp.int32)
    pre = take_clip(pre, jnp.argsort(take_clip(~xv, pre), stable=True))
    keys, valids = split_limb_keys(keys, valids)
    order = _key_order(
        keys, valids, mask, order=pre, seed=_order_seed(out_capacity)
    )
    sm = take_clip(mask, order)
    sk = [take_clip(k, order) for k in keys]
    sv = [take_clip(v, order) for v in valids]
    boundary, starts, safe_starts, ends, used, _, _ = _segment_bounds(
        sk, sv, sm, n, out_capacity
    )
    sx = take_clip(xb, order)
    sxv = take_clip(xv, order) & sm
    first = jnp.arange(n) == 0
    flag = sxv & (boundary | first | ~_eq_vals(sx, jnp.roll(sx, 1)))
    c = jnp.cumsum(flag.astype(jnp.int64))
    cnt = take_clip(c, ends) - take_clip(c - flag.astype(jnp.int64), safe_starts)
    return jnp.where(used, cnt, 0)


@partial(jax.jit, static_argnames=("out_capacity",))
@partial(jax.jit, static_argnames=("out_capacity",))
def grouped_rows_order(keys, valids, mask, x, x_valid, out_capacity):
    """Rows grouped and value-ordered for HOST-side assembly, returned
    as a row ORDER so the assembler (array_agg, map_agg, histogram —
    the collect-path aggregates) can gather ANY number of argument
    columns into the same group-contiguous, value-ordered layout.
    Returns (dense_gid_per_sorted_row, group_live, order, n_groups,
    overflowed); dense gids index sort_group_reduce's compacted slots
    1:1 (same sort chain, same segment ordering)."""
    n = mask.shape[0]
    xv = jnp.ones(n, dtype=jnp.bool_) if x_valid is None else x_valid
    from trino_tpu.ops.sort import _order_value

    pre = jnp.argsort(_order_value(x, False), stable=True).astype(jnp.int32)
    pre = take_clip(pre, jnp.argsort(take_clip(~xv, pre), stable=True))
    seed = _order_seed(out_capacity)
    keys, valids = split_limb_keys(keys, valids)
    order = _key_order(keys, valids, mask, order=pre, seed=seed)
    sm = take_clip(mask, order)
    sk = [take_clip(k, order) for k in keys]
    sv = [take_clip(v, order) for v in valids]
    # no collision overlay here: the caller (_finish_holistic) settles
    # capacity/seed through sort_group_reduce's detector over the SAME
    # keys and seed first, which flags exactly the collisions this
    # ordering could have
    boundary, starts, safe_starts, ends, used, n_groups, overflowed = (
        _segment_bounds(sk, sv, sm, n, out_capacity)
    )
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    return gid, sm, order, n_groups, overflowed


@partial(jax.jit, static_argnames=("out_capacity",))
def grouped_rows_sorted(keys, valids, mask, x, x_valid, out_capacity):
    """grouped_rows_order with the value column pre-gathered (listagg:
    building new strings is host work by nature — Trino's
    ListaggAggregationFunction builds its VARCHAR on the heap too).
    Returns (dense_gid_per_sorted_row, weight, sorted_x, n_groups,
    overflowed)."""
    gid, sm, order, n_groups, overflowed = grouped_rows_order(
        keys, valids, mask, x, x_valid, out_capacity
    )
    n = mask.shape[0]
    xv = jnp.ones(n, dtype=jnp.bool_) if x_valid is None else x_valid
    w = sm & take_clip(xv, order)
    return gid, w, take_clip(x, order), n_groups, overflowed
