"""The aggregation's large-state path and the filtering semi-join
(issues 32 and 33): the sort path folds its group states FOLD_STATES at a time
and sizes its last merge from the group counts the launches left; a
semi-join is planned on the source that holds its key; a small build
side filters probes by its key set and packs what is left. CPU counts
and answers only; what any of it costs is a chip reading (PERF.md
section 6)."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.oracle import assert_rows_match, oracle_rows
from trino_tpu import types as T
from trino_tpu.block import MIN_CAPACITY, Column, RelBatch
from trino_tpu.exec import operators as O
from trino_tpu.exec.operators import AggSpec, HashAggregationOperator
from trino_tpu.ops.int128 import from_python, to_python
from trino_tpu.runtime import tracing
from trino_tpu.runtime.metrics import METRICS
from trino_tpu.sql import plan as P

BATCH = 256
D = T.decimal(38, 2)
SCHEMA = [(T.BIGINT, None), (D, None), (T.BIGINT, None)]
AGGS = [AggSpec("sum", 1, D), AggSpec("count_star", None, T.BIGINT),
        AggSpec("min", 2, T.BIGINT), AggSpec("max", 2, T.BIGINT)]


def make_rows(n_batches, keys_per_batch, seed, overlap):
    """(key, 128-bit value, small value) per row. Values sit just under
    2^64 and 2^96, so a group's sum carries across every limb; `overlap`
    makes every batch draw from the same keys (the merge's worst case),
    else batch i has keys of its own (a clustered scan: Q18's)."""
    rng = np.random.default_rng(seed)
    rows = []
    for b in range(n_batches):
        base = 0 if overlap else b * keys_per_batch
        keys = base + rng.integers(0, keys_per_batch, BATCH)
        big = [(2**64 - 1 - int(x)) * (2**32 if i % 2 else 1) * (-1) ** (i % 3 == 0)
               for i, x in enumerate(rng.integers(0, 1000, BATCH))]
        small = rng.integers(-1000, 1000, BATCH)
        rows.append((keys, big, small))
    return rows


def to_batch(keys, big, small):
    pairs = [from_python(int(x)) for x in big]
    limbs = np.stack([
        np.array([p[0] for p in pairs], dtype=np.int64),
        (np.array([p[1] % 2**64 for p in pairs], dtype=object)
         .astype(np.uint64).view(np.int64)),
    ], axis=1)
    return RelBatch([
        Column(T.BIGINT, jnp.asarray(keys, dtype=jnp.int64), None, None),
        Column(D, jnp.asarray(limbs), None, None),
        Column(T.BIGINT, jnp.asarray(small, dtype=jnp.int64), None, None),
    ], None)


def exact_rows(batch):
    host = jax.device_get(batch)
    live = np.asarray(host.live_mask())
    out = []
    for i in np.nonzero(live)[0]:
        h, lo = np.asarray(host.columns[1].data)[i]
        out.append((int(host.columns[0].data[i]), to_python(int(h), int(lo)),
                    *(int(c.data[i]) for c in host.columns[2:])))
    return sorted(out)


def want_rows(rows):
    groups = collections.defaultdict(lambda: [0, 0, None, None])
    for keys, big, small in rows:
        for k, v, s in zip(keys, big, small):
            g = groups[int(k)]
            g[0] += v
            g[1] += 1
            g[2] = int(s) if g[2] is None else min(g[2], int(s))
            g[3] = int(s) if g[3] is None else max(g[3], int(s))
    return sorted((k, *g) for k, g in groups.items())


def aggregate(rows, watch=None):
    names = ("agg_merge_launches", "agg_merge_retries", "agg_ingest_launches")
    before = {k: METRICS.counter(k) for k in names}
    agg = HashAggregationOperator([0], AGGS, SCHEMA)
    for i, r in enumerate(rows):
        agg.add_input(to_batch(*r))
        if watch is not None:
            watch(agg, i)
    agg.finish()
    return exact_rows(agg.get_output()), {
        k: METRICS.counter(k) - v for k, v in before.items()}


@pytest.mark.parametrize("overlap", [True, False], ids=["shared-keys", "keys-of-its-own"])
@pytest.mark.parametrize("n_batches", [1, 3, 7, 8, 9, 27, 64, 65, 70])
def test_folding_step_by_step_equals_the_one_shot_merge(n_batches, overlap, monkeypatch):
    """128-bit sums with carries, counts, min and max: folds of
    FOLD_STATES (two tiers from 65 batches on: the 65th settles the
    64th, whose fold fills tier 0) against ONE merge of all the states,
    and both against python's integers."""
    rows = make_rows(n_batches, 40, seed=32 + n_batches, overlap=overlap)
    pending = []
    folded, counts = aggregate(
        rows, watch=lambda agg, i: pending.append(
            (len(agg._pending), [len(t) for t in agg._folded])))
    assert folded == want_rows(rows)
    # what is pending is bounded by the tiers, never by the scan
    assert max(p for p, _ in pending) <= O.FOLD_STATES
    assert all(n < O.FOLD_STATES for _, tiers in pending for n in tiers)
    # a fold once FOLD_STATES states have settled (the newest is still in
    # flight), a fold of folds once FOLD_STATES of those are there, and
    # the last merge of what is left
    folds = (n_batches - 1) // O.FOLD_STATES
    last = 1 if n_batches > 1 else 0        # one batch is one state: nothing to merge
    assert counts["agg_merge_launches"] == folds + folds // O.FOLD_STATES + last
    assert counts["agg_merge_retries"] == 0
    monkeypatch.setattr(O, "FOLD_STATES", 10**6)      # never fold: the parent's merge
    one_shot, counts = aggregate(rows)
    assert one_shot == folded and counts["agg_merge_launches"] == last


def test_the_last_merge_is_sized_by_the_group_counts_and_runs_once():
    """Six batches of keys of their own, some 225 groups each. The
    operator's table (1,024 slots) is too small for them all, and the
    parent launched the merge there first and again after the overflow;
    the counts the launches left add up to over 1,024, so the merge is
    launched once, at 2,048."""
    rows = make_rows(6, 1000, seed=7, overlap=False)
    seen = []
    inner = O._merge_group_states

    def spy(states, reducers, out_capacity, takes=None):
        seen.append((len(states), out_capacity))
        return inner(states, reducers, out_capacity, takes)

    try:
        O._merge_group_states = spy
        got, counts = aggregate(rows)
    finally:
        O._merge_group_states = inner
    assert got == want_rows(rows) and 1024 < len(got) <= 6 * BATCH
    assert seen == [(6, 2048)]
    assert counts["agg_merge_launches"] == 1 and counts["agg_merge_retries"] == 0


def test_a_fold_takes_its_states_slots_whatever_they_hold():
    """A fold's table follows from its operands' shapes alone, so one
    program serves every fold of a scan."""
    rows = make_rows(17, 30, seed=3, overlap=True)
    seen = []
    inner = O._merge_group_states

    def spy(states, reducers, out_capacity, takes=None):
        seen.append((tuple(int(s[2].shape[0]) for s in states), out_capacity))
        return inner(states, reducers, out_capacity, takes)

    try:
        O._merge_group_states = spy
        got, _ = aggregate(rows)
    finally:
        O._merge_group_states = inner
    assert got == want_rows(rows)
    folds, last = seen[:-1], seen[-1]
    assert len(folds) == 2 and all(len(caps) == O.FOLD_STATES for caps, _ in folds)
    assert all(out == O.bucket_capacity(sum(caps)) for caps, out in folds)
    # 2 folded, then what is left (1 state) made up to FOLD_STATES
    assert len(last[0]) == 2 + O.FOLD_STATES and last[1] < sum(last[0])


@pytest.mark.parametrize("n_batches", [17, 20, 24])
def test_the_last_merge_of_a_folded_scan_has_one_shape_whatever_is_left(n_batches):
    """Two folds and 1, 4 or 8 states left: empty states make up what is
    left to FOLD_STATES, so the three scans hand their last merge the
    same operand shapes (its table follows the group counts), and answer
    right."""
    rows = make_rows(n_batches, 30, seed=5, overlap=True)
    seen = []
    inner = O._merge_group_states

    def spy(states, reducers, out_capacity, takes=None):
        seen.append((tuple(int(s[2].shape[0]) for s in states), out_capacity))
        return inner(states, reducers, out_capacity, takes)

    try:
        O._merge_group_states = spy
        got, counts = aggregate(rows)
    finally:
        O._merge_group_states = inner
    assert got == want_rows(rows)
    assert counts["agg_merge_launches"] == 3 and counts["agg_merge_retries"] == 0
    (fold_in, fold_out), (caps, out) = seen[0], seen[-1]
    assert caps == (fold_out,) * 2 + (fold_in[0],) * O.FOLD_STATES
    assert out < sum(caps)                                  # sized by the counts


class MergeSpans:
    """Stands where `host_span` stands: the stats of every `agg.merge`."""

    def __init__(self):
        self.merges = []

    def __call__(self, name, **stats):
        if name == "agg.merge":
            self.merges.append(stats)
        return tracing.OFF


SHORT_READS = ("agg_merge_short_reads", "agg_merge_slots_spared", "agg_ordered_merge.launches")


@pytest.mark.parametrize("in_key_order", [False, True], ids=["keys-in-no-order", "keys-in-order"])
def test_the_last_merge_reads_a_fold_that_resorted_up_to_its_groups(in_key_order, monkeypatch):
    """17 batches, two folds. Keys in no order: the folds re-sort, hold
    30 groups in 2,048 slots, and the last merge reads 32 of them; the 8
    states left are read whole. Every batch a key range of its own: the
    folds lay their states end to end and the last merge reads them
    whole, under today's program."""
    rows = make_rows(17, 30, seed=3, overlap=not in_key_order)
    seen, inner = [], O._merge_group_states
    spans = MergeSpans()

    def spy(states, reducers, out_capacity, takes=None):
        seen.append((tuple(int(s[2].shape[0]) for s in states), out_capacity, takes))
        return inner(states, reducers, out_capacity, takes)

    monkeypatch.setattr(O, "_merge_group_states", spy)
    monkeypatch.setattr(O, "host_span", spans)
    before = {k: METRICS.counter(k) for k in SHORT_READS}
    got, counts = aggregate(rows)
    moved = {k: METRICS.counter(k) - v for k, v in before.items()}
    assert got == want_rows(rows)
    assert counts["agg_merge_launches"] == 3 and counts["agg_merge_retries"] == 0
    (fold_in, fold_out, fold_takes), (caps, out, takes) = seen[0], seen[-1]
    assert fold_takes is None and seen[1][2] is None          # a fold reads its states whole
    assert caps == (fold_out,) * 2 + (fold_in[0],) * O.FOLD_STATES
    assert [m["slots_in"] for m in spans.merges[:2]] == [sum(fold_in)] * 2
    if in_key_order:
        assert takes is None
        assert spans.merges[2]["slots_in"] == sum(caps)
        assert moved == {"agg_merge_short_reads": 0, "agg_merge_slots_spared": 0,
                         "agg_ordered_merge.launches": 3}
    else:
        held = O.bucket_capacity(30)
        assert takes == (held,) * 2 + (None,) * O.FOLD_STATES
        assert spans.merges[2]["slots_in"] == 2 * held + O.FOLD_STATES * fold_in[0]
        assert moved == {"agg_merge_short_reads": 2,
                         "agg_merge_slots_spared": 2 * (fold_out - held),
                         "agg_ordered_merge.launches": 0}


def test_a_cut_that_drops_a_used_slot_is_retried_on_the_states_whole(monkeypatch):
    """The guard against a state that is not a dense prefix of its
    count: the last merge's cut made too short drops groups, the program
    says so in its overflow bit, and the operator merges again, whole,
    into the same table."""
    rows = make_rows(17, 30, seed=3, overlap=True)
    seen, inner = [], O._merge_group_states

    def spy(states, reducers, out_capacity, takes=None):
        if takes is not None:
            takes = tuple(t and MIN_CAPACITY for t in takes)     # 16 slots of 30 groups
        seen.append((out_capacity, takes))
        return inner(states, reducers, out_capacity, takes)

    monkeypatch.setattr(O, "_merge_group_states", spy)
    got, counts = aggregate(rows)
    assert got == want_rows(rows)
    assert counts["agg_merge_launches"] == 4 and counts["agg_merge_retries"] == 1
    (cap, cut), (again_cap, whole) = seen[-2:]
    assert cut == (MIN_CAPACITY,) * 2 + (None,) * O.FOLD_STATES
    assert whole is None and again_cap == cap


def test_revocation_between_folds_loses_nothing():
    from trino_tpu.runtime.memory import MemoryContext, MemoryPool

    rows = make_rows(20, 40, seed=11, overlap=True)
    pool = MemoryPool(1 << 30)
    agg = HashAggregationOperator([0], AGGS, SCHEMA,
                                  memory_context=MemoryContext(pool))
    for i, r in enumerate(rows):
        agg.add_input(to_batch(*r))
        if i in (4, 11):
            agg._revoke_memory()
            assert agg._pending == [] and agg._folded == [] and agg._acc is None
    agg.finish()
    assert exact_rows(agg.get_output()) == want_rows(rows)


# -- the dynamic filter by key set -------------------------------------------------------


def keyed_batch(keys, valid=None, live=None, payload=None):
    keys = np.asarray(keys, dtype=np.int64)
    payload = np.arange(len(keys)) if payload is None else payload
    return RelBatch([
        Column(T.BIGINT, jnp.asarray(keys), None if valid is None else jnp.asarray(valid), None),
        Column(T.BIGINT, jnp.asarray(payload, dtype=jnp.int64), None, None),
    ], None if live is None else jnp.asarray(live))


def test_the_set_filter_keeps_the_rows_that_will_match_and_no_null():
    probe = keyed_batch([5, 7, 9, 7, 11, 13, 5, 2],
                        valid=[True, True, True, False, True, True, True, True],
                        live=[True] * 7 + [False])
    build_keys = jnp.asarray([7, 5, 2, 11], dtype=jnp.int64)
    usable = jnp.asarray([True, True, True, False])                 # 11: a dead slot
    key = (probe.columns[0].data, probe.columns[0].valid)
    none = jnp.zeros(2, dtype=jnp.int64)
    out, kept, totals = O._df_filter_set(
        probe, key, *O._df_key_set(build_keys, usable, 4), none)
    assert int(kept) == 3
    # (rows in, rows kept) so far: 7 live rows came, 3 stay
    assert np.asarray(totals).tolist() == [7, 3]
    assert np.asarray(out.live_mask()).tolist() == [
        True, True, False, False, False, False, True, False]
    # no live key: nothing passes
    out, kept, totals = O._df_filter_set(probe, key, *O._df_key_set(
        build_keys, jnp.zeros(4, dtype=jnp.bool_), 4), totals)
    assert int(kept) == 0 and not np.asarray(out.live_mask()).any()
    assert np.asarray(totals).tolist() == [14, 3]
    # the comparison is on the low 32 bits: a key 2^32 away passes the
    # filter (and the join, which compares whole keys, drops it)
    far = keyed_batch([5 + 2**32, 6 + 2**32])
    out, kept, _ = O._df_filter_set(far, (far.columns[0].data, None),
                                    *O._df_key_set(build_keys, usable, 4), none)
    assert np.asarray(out.live_mask()).tolist() == [True, False]


def test_a_sparse_build_sides_key_set_takes_the_slots_its_keys_need():
    """A build side HashBuildSink does not pack (under 2^17 slots) may be
    mostly dead: its usable keys are counted once and compared in the
    power of two that holds them (DF_SET_MIN_SLOTS at least), and the
    filter keeps what it kept; a build side of at most DF_SET_MIN_SLOTS
    slots is not counted."""
    rng = np.random.default_rng(351)
    keys = rng.choice(50_000, 2048, replace=False)
    live = np.zeros(2048, dtype=bool)
    live[rng.choice(2048, 150, replace=False)] = True
    probed = rng.integers(0, 50_000, 8192)
    syncs = []
    inner = O.host_sync
    try:
        O.host_sync = lambda site, *a, **k: syncs.append(site) or inner(site, *a, **k)
        df, out = filtered_by(build_of(keys, live=live), [keyed_batch(probed)])
        assert df._path == "set" and df._key_set[0].shape == (256,)
        assert sorted(np.asarray(df._key_set[0]).tolist()) == sorted(
            keys[live].tolist() + [int(keys[live][0])] * 106)
        assert syncs.count("join.dynamic_filter_keys") == 1
        kept = [k for k, _ in live_rows(out)]
        assert kept == probed[np.isin(probed, keys[live])].tolist()
        del syncs[:]
        small, _ = filtered_by(build_of(keys[:128], live=live[:128]), [keyed_batch(probed)])
        assert small._key_set[0].shape == (128,) and "join.dynamic_filter_keys" not in syncs
    finally:
        O.host_sync = inner


def test_front_rows_packs_the_live_rows_in_order():
    live = np.zeros(64, dtype=bool)
    live[[3, 17, 40, 63]] = True
    batch = keyed_batch(np.arange(64) * 10, live=live)
    out = O._front_rows(batch, 16)
    assert out.capacity == 16
    assert np.asarray(out.live_mask()).tolist() == [True] * 4 + [False] * 12
    assert np.asarray(out.columns[0].data)[:4].tolist() == [30, 170, 400, 630]
    assert np.asarray(out.columns[1].data)[:4].tolist() == [3, 17, 40, 63]


@pytest.mark.parametrize("build_rows, exact", [(100, True), (O.DF_SET_MAX_SLOTS + 1, False)])
def test_a_small_build_filters_by_its_set_a_large_one_by_its_range(build_rows, exact):
    bridge = O.JoinBridge()
    sink = O.HashBuildSink(bridge, [0], [(T.BIGINT, None), (T.BIGINT, None)])
    sink.add_input(keyed_batch(np.arange(build_rows) * 2))       # even keys
    sink.finish()
    df = O.DynamicFilterOperator(bridge, [0])
    probe = keyed_batch(np.arange(8192))                          # 0..8191
    df.add_input(probe)
    if not exact:
        out = df.get_output()
        assert int(np.asarray(out.live_mask()).sum()) == 8192 and out.capacity == 8192
        return
    # packed, and gathered with the next batches' survivors: nothing
    # comes out until the gathered batch is full or the scan ends
    assert df.get_output() is None and df.needs_input()
    df.add_input(keyed_batch(np.arange(8192) + 100))              # 100..8291: 50 more
    assert df.get_output() is None
    df.finish()
    out = df.get_output()
    assert out.capacity == O.DF_PACK_MIN_SLOTS and df.get_output() is None and df.is_finished()
    live = np.asarray(out.live_mask())
    assert live.tolist() == [True] * 150 + [False] * (out.capacity - 150)
    assert np.asarray(out.columns[0].data)[:150].tolist() == (
        list(range(0, 200, 2)) + list(range(100, 200, 2)))


def drain(op):
    out = []
    while (batch := op.get_output()) is not None:
        out.append(batch)
    return out


def joined_keys(bridge, filtered):
    """The probe keys an inner join emits for the batches a dynamic
    filter let through (the build side's keys are distinct)."""
    join = O.LookupJoinOperator(bridge, [0], "inner", [(T.BIGINT, None), (T.BIGINT, None)])
    keys = []
    for batch in filtered:
        join.add_input(batch)
        keys += [np.asarray(b.columns[0].data)[np.asarray(b.live_mask())] for b in drain(join)]
    join.finish()
    keys += [np.asarray(b.columns[0].data)[np.asarray(b.live_mask())] for b in drain(join)]
    return sorted(np.concatenate(keys).tolist()) if keys else []


@pytest.mark.parametrize("build_rows,path", [(64 * 128, "blocked"), (128 // 4, "sorted")])
def test_join_probe_path_counts_each_probe_batch_by_the_form_its_bounds_took(build_rows, path):
    """`join_probe_path.blocked` / `.sorted` (PR 37): one a probe batch,
    by what `ops/join.probe_path` answers for the build side's slots,
    the batch's and the word's bits: the two-level bounds against a build
    of 64 times the batch, the two packed sorts against one of a
    quarter of it. The matches are the same either way."""
    from trino_tpu.ops import join as J

    bridge = O.JoinBridge()
    sink = O.HashBuildSink(bridge, [0], [(T.BIGINT, None), (T.BIGINT, None)])
    sink.add_input(keyed_batch(np.arange(build_rows) * 3))
    sink.finish()
    ls = bridge.lookup_source
    probes = [keyed_batch(np.arange(128) + at) for at in (0, 40, 90)]
    assert J.probe_path(ls.build_capacity, 128, ls.hash_bits) == path
    names = ("join_probe_path.blocked", "join_probe_path.sorted")
    before = {n: METRICS.counter(n) for n in names}
    keys = joined_keys(bridge, probes)
    moved = {n: METRICS.counter(n) - before[n] for n in names}
    assert moved == {"join_probe_path." + path: 3,
                     "join_probe_path." + ("sorted" if path == "blocked" else "blocked"): 0}
    assert keys == sorted(k for at in (0, 40, 90) for k in range(at, at + 128)
                          if k % 3 == 0 and k < 3 * build_rows)


@pytest.mark.parametrize("build_rows", [0, 1, O.DF_SET_MAX_SLOTS, O.DF_SET_MAX_SLOTS + 1])
def test_the_set_filter_and_the_range_filter_hand_the_join_the_same_matches(build_rows):
    """Random keys, three probe batches with NULLs and dead rows: the
    key-set filter's survivors joined equal the range filter's survivors
    joined equal what numpy says matches; up to DF_SET_MAX_SLOTS build
    slots the filter itself is the set's (it keeps the matches and
    nothing else), one above it the bits' (PR 35: so does it)."""
    rng = np.random.default_rng(33 + build_rows)
    build_keys = rng.choice(1 << 16, size=build_rows, replace=False)
    bridge = O.JoinBridge()
    sink = O.HashBuildSink(bridge, [0], [(T.BIGINT, None), (T.BIGINT, None)])
    sink.add_input(keyed_batch(build_keys) if build_rows else keyed_batch(
        [0] * 16, live=[False] * 16))
    sink.finish()
    probes, matches = [], []
    for _ in range(3):
        keys = rng.integers(0, 1 << 16, 8192)
        valid, live = rng.random(8192) < 0.95, rng.random(8192) < 0.9
        probes.append(keyed_batch(keys, valid=valid, live=live))
        matches += keys[valid & live & np.isin(keys, build_keys)].tolist()
    by = {}
    for name in ("chosen", "range"):
        df = O.DynamicFilterOperator(bridge, [0])
        filtered = []
        for probe in probes:
            if name == "range" and df._active_channels is None:
                df._prepare(probe)
                if df._key_set is not None:
                    df._use_range()
            df.add_input(probe)
            filtered += drain(df)
        df.finish()
        filtered += drain(df)
        by[name] = (df, filtered)
    chosen, filtered = by["chosen"]
    # (one above the set's limit, 4,097 keys scattered over 2^16 values:
    # the bits, which keep the matches and nothing else too)
    assert (chosen._key_set is not None) is (build_rows <= O.DF_SET_MAX_SLOTS)
    assert (chosen._bits is not None) is (build_rows > O.DF_SET_MAX_SLOTS)
    assert by["range"][0]._key_set is None
    if chosen._domains is None:
        kept = [np.asarray(b.columns[0].data)[np.asarray(b.live_mask())] for b in filtered]
        assert sorted(np.concatenate(kept).tolist() if kept else []) == sorted(matches)
    assert joined_keys(bridge, filtered) == joined_keys(bridge, by["range"][1]) == sorted(matches)


@pytest.mark.parametrize("second_keeps, then_by", [(1500, "packed"), (8192, "range")])
def test_a_batch_that_keeps_more_than_the_gathered_batch_holds_ends_the_gathering(
        second_keeps, then_by):
    """The set is not that selective on this probe. A count is read one
    batch late (the next batch's filter is on the device by then). A
    batch that keeps over DF_PACK_MIN_SLOTS rows and at most a quarter
    of its slots is packed into a power of two of slots, and comes out
    put behind the parts before it (they come out as one batch of the
    power of two that holds their rows where they fill no batch of the
    scan's capacity); one that keeps
    more ends the readbacks: from it on the filter hands on what it
    masked, at the batch's own capacity and without reading a count, by
    the build side's range, as a large build side's filter."""
    bridge = O.JoinBridge()
    sink = O.HashBuildSink(bridge, [0], [(T.BIGINT, None), (T.BIGINT, None)])
    sink.add_input(keyed_batch(np.arange(600) * 2))               # even keys 0..1198
    sink.finish()
    df = O.DynamicFilterOperator(bridge, [0])
    df.add_input(keyed_batch(np.arange(8192)))                    # keeps 600: gathered
    assert df.get_output() is None
    keys = np.full(8192, -1)
    keys[:second_keeps] = (np.arange(second_keeps) % 600) * 2
    df.add_input(keyed_batch(keys))
    assert df.get_output() is None                # the first batch's count is read now
    df.add_input(keyed_batch(np.arange(8192)))    # and the second's now: keeps 600
    gathered = df.get_output()
    assert gathered.capacity == O.DF_PACK_MIN_SLOTS
    assert int(np.asarray(gathered.live_mask()).sum()) == 600
    if then_by == "packed":
        assert df.get_output() is None and df._gathering and df._packing
        assert (df._room.capacity, df._room_taken) == (8192 + 2048, 1500)
        df.finish()
        (whole,) = drain(df)
        # 1,500 rows packed into 2,048 slots, the last batch's 600 into
        # 512: one behind the other, in the power of two that holds them
        assert whole.capacity == 4096
        live = np.asarray(whole.live_mask())
        assert live.tolist() == [True] * 2100 + [False] * (4096 - 2100)
        assert np.asarray(whole.columns[0].data)[live].tolist() == (
            keys[:1500].tolist() + list(range(0, 1200, 2)))
        assert np.asarray(whole.columns[1].data)[live].tolist() == (
            list(range(1500)) + list(range(0, 1200, 2)))
        assert df.is_finished()
        return
    whole, third = drain(df)
    assert whole.capacity == third.capacity == 8192 and not df._gathering
    assert int(np.asarray(whole.live_mask()).sum()) == second_keeps
    # the batch that was on the device meanwhile (by the set still)
    # leaves masked too, and from here on nothing is read
    assert int(np.asarray(third.live_mask()).sum()) == 600
    syncs = []
    inner = O.host_sync
    try:
        O.host_sync = lambda site, *a, **k: syncs.append(site) or inner(site, *a, **k)
        df.add_input(keyed_batch(np.arange(8192)))
    finally:
        O.host_sync = inner
    (fourth,) = drain(df)
    assert fourth.capacity == 8192 and syncs == []
    assert int(np.asarray(fourth.live_mask()).sum()) == 1199      # by the range
    df.finish()
    assert df.get_output() is None and df.is_finished()


def test_the_gathered_batch_is_emitted_when_the_next_would_overfill_it():
    bridge = O.JoinBridge()
    sink = O.HashBuildSink(bridge, [0], [(T.BIGINT, None), (T.BIGINT, None)])
    sink.add_input(keyed_batch(np.arange(700)))                   # keys 0..699
    sink.finish()
    df = O.DynamicFilterOperator(bridge, [0])
    df.add_input(keyed_batch(np.arange(8192)))                    # keeps 700
    assert df.get_output() is None
    df.add_input(keyed_batch(np.arange(8192) - 7600))             # keeps 592: 1,292 in all
    assert df.get_output() is None            # (its count is read a batch late)
    df.add_input(keyed_batch(np.arange(8192) + 10000))            # keeps none
    first = df.get_output()
    assert int(np.asarray(first.live_mask()).sum()) == 700 and df.get_output() is None
    df.finish()
    second = df.get_output()
    assert int(np.asarray(second.live_mask()).sum()) == 592 and df.is_finished()


@pytest.mark.parametrize("live_rows, on_device", [(66, True), (6000, True)])
def test_a_sparse_build_side_is_packed_on_the_device_when_small(live_rows, on_device,
                                                                monkeypatch):
    """What a HAVING leaves of a large group table: up to
    `_DEVICE_PACK_MAX_SLOTS` rows are picked out on the device by a
    top_k, more by one sort that carries the columns (`_pack_sorted`),
    so the table's slots never cross to the host (the host's pass is
    left for nested columns). Either way the lookup source holds the
    live rows, in order."""
    from trino_tpu.exec import serde

    crossed = []
    inner = serde.Page.from_batch
    monkeypatch.setattr(serde.Page, "from_batch",
                        staticmethod(lambda b: crossed.append(b.capacity) or inner(b)))
    n = O._SHRINK_MIN_CAPACITY
    live = np.zeros(n, dtype=bool)
    live[np.linspace(0, n - 1, live_rows).astype(int)] = True
    bridge = O.JoinBridge()
    sink = O.HashBuildSink(bridge, [0], [(T.BIGINT, None), (T.BIGINT, None)])
    sink.add_input(keyed_batch(np.arange(n) * 3, live=live))
    sink.finish()
    build = bridge.build_batch
    assert build.capacity == O.bucket_capacity(live_rows)
    assert (crossed == []) is on_device
    kept = np.asarray(build.columns[0].data)[np.asarray(build.live_mask())]
    assert kept.tolist() == (np.nonzero(live)[0] * 3).tolist()


# -- the dynamic filter by key bits, and the packing behind it (PR 35) -----------------------


def build_of(keys, valid=None, live=None):
    bridge = O.JoinBridge()
    sink = O.HashBuildSink(bridge, [0], [(T.BIGINT, None), (T.BIGINT, None)])
    sink.add_input(keyed_batch(keys, valid=valid, live=live))
    sink.finish()
    return bridge


def filtered_by(bridge, probes):
    df = O.DynamicFilterOperator(bridge, [0])
    out = []
    for probe in probes:
        df.add_input(probe)
        out += drain(df)
    df.finish()
    return df, out + drain(df)


def live_rows(batches):
    """[(key, payload)] of the live rows, in order."""
    rows = []
    for b in batches:
        live = np.asarray(b.live_mask())
        rows += list(zip(np.asarray(b.columns[0].data)[live].tolist(),
                         np.asarray(b.columns[1].data)[live].tolist()))
    return rows


@pytest.mark.parametrize("case", ["scattered", "negative_keys", "null_and_dead_build_slots",
                                  "two_keys_far_apart", "wide_domain_inside_the_limit"])
def test_the_bits_filter_keeps_what_numpy_isin_keeps(case):
    """Seeded keys, three probe batches with NULLs and dead rows, the
    domain's edges among the probe keys: behind a build side of more
    than DF_SET_MAX_SLOTS slots whose keys lie scattered over a narrow
    domain the filter keeps exactly the rows `numpy.isin` keeps, once
    each and in order, and the join finds all of them."""
    rng = np.random.default_rng(3500 + len(case))
    slots = 2 * O.DF_SET_MAX_SLOTS
    lo, hi = {"scattered": (1, 200_000), "negative_keys": (-150_000, 50_000),
              "null_and_dead_build_slots": (7, 90_000), "two_keys_far_apart": (41, 150_041),
              "wide_domain_inside_the_limit": (-(1 << 25), 1 << 25)}[case]
    n_keys = 2 if case == "two_keys_far_apart" else 6000
    keys = np.zeros(slots, dtype=np.int64)
    keys[1:n_keys - 1] = rng.choice(np.arange(lo + 1, hi), n_keys - 2, replace=False)
    keys[0], keys[n_keys - 1] = lo, hi                      # the edges are keys
    live = np.arange(slots) < n_keys
    valid = np.ones(slots, dtype=bool)
    if case == "null_and_dead_build_slots":
        valid[5:400:7] = False
        live[1000:1100] = False
    bridge = build_of(keys, valid=valid, live=live)
    usable = keys[live & valid]
    probes, want = [], []
    for _ in range(3):
        pk = rng.integers(lo - 50, hi + 51, 8192)
        pk[:6] = [lo - 1, lo, lo + 1, hi - 1, hi, hi + 1]
        pv, pl = rng.random(8192) < 0.95, rng.random(8192) < 0.9
        pv[:6] = pl[:6] = True
        probes.append(keyed_batch(pk, valid=pv, live=pl))
        hit = pv & pl & np.isin(pk, usable)
        want += [(int(k), i) for i, (k, h) in enumerate(zip(pk, hit)) if h]
    df, filtered = filtered_by(bridge, probes)
    assert df._bits is not None and df._key_set is None and df._path == "bits"
    assert live_rows(filtered) == want
    assert joined_keys(bridge, filtered) == sorted(k for k, _ in want)


@pytest.mark.parametrize("case, path", [
    ("few_slots", "set"), ("scattered", "bits"), ("dense_domain", "range"),
    ("domain_too_wide", "range"), ("dead_build", "range"), ("two_keys", "range"),
    ("too_many_slots", "range")])
def test_which_of_the_three_filters_a_build_side_gets(case, path, monkeypatch):
    """By what the build side is: its slots, its live keys, hi - lo."""
    slots = 2 * O.DF_SET_MAX_SLOTS
    keys = np.arange(slots, dtype=np.int64) * 5               # a fifth of the domain
    live = None
    if case == "few_slots":
        keys = keys[:O.DF_SET_MAX_SLOTS]
    elif case == "dense_domain":
        keys = np.arange(slots, dtype=np.int64) + 10           # every value of the domain
    elif case == "domain_too_wide":
        keys[-1] = O.DF_BITS_MAX_DOMAIN + 5
    elif case == "dead_build":
        live = np.zeros(slots, dtype=bool)
    elif case == "too_many_slots":
        monkeypatch.setattr(O, "DF_BITS_MAX_SLOTS", slots // 2)
    before = {p: METRICS.counter(f"df_filter_path.{p}") for p in ("set", "bits", "range")}
    rows = (METRICS.counter("df_rows_in"), METRICS.counter("df_rows_kept"))
    if case == "two_keys":
        bridge = O.JoinBridge()
        sink = O.HashBuildSink(bridge, [0, 1], [(T.BIGINT, None), (T.BIGINT, None)])
        sink.add_input(keyed_batch(keys))
        sink.finish()
        df = O.DynamicFilterOperator(bridge, [0, 1])
    else:
        df = O.DynamicFilterOperator(build_of(keys, live=live), [0])
    probed = np.arange(8192) * 7
    df.add_input(keyed_batch(probed))
    df.finish()
    out = drain(df)
    assert df._path == path
    assert (df._key_set is not None, df._bits is not None, df._domains is not None) == (
        path == "set", path == "bits", path == "range")
    moved = {p: METRICS.counter(f"df_filter_path.{p}") - n for p, n in before.items()}
    assert moved == {p: int(p == path) for p in moved}
    kept = sum(int(np.asarray(b.live_mask()).sum()) for b in out)
    usable = keys if live is None else keys[live]
    if path == "range":
        want = int(((probed >= usable.min()) & (probed <= usable.max())).sum()) if len(usable) else 0
    else:
        want = int(np.isin(probed, usable).sum())
    assert want == {"few_slots": 586, "scattered": 1171, "dense_domain": 1170,
                    "domain_too_wide": 8192, "dead_build": 0, "two_keys": 5851,
                    "too_many_slots": 5851}[case]
    assert kept == want
    assert (METRICS.counter("df_rows_in") - rows[0],
            METRICS.counter("df_rows_kept") - rows[1]) == (8192, want)


def test_the_bit_table_holds_one_bit_a_key_and_nothing_for_a_dead_slot():
    keys = jnp.asarray([10, 41, 42, 10, 73, 500], dtype=jnp.int64)
    usable = jnp.asarray([True, True, True, True, True, False])
    words = np.asarray(O._df_bit_table(keys, usable, jnp.int64(10), 128))
    assert words.dtype == np.uint32 and words.shape == (128,)
    bits = np.nonzero((words[:, None] >> np.arange(32, dtype=np.uint32)) & 1)
    assert (bits[0] * 32 + bits[1] + 10).tolist() == [10, 41, 42, 73]


def wide_batch(n, live, rng):
    """A batch of every lane a sort has to carry: a bigint with NULLs, a
    varchar's codes, a boolean, a long decimal's two limbs."""
    from trino_tpu.block import Dictionary

    limbs = rng.integers(-(1 << 40), 1 << 40, (n, 2))
    return RelBatch([
        Column(T.BIGINT, jnp.arange(n, dtype=jnp.int64), jnp.asarray(rng.random(n) < 0.9), None),
        Column(T.VARCHAR, jnp.asarray(rng.integers(0, 3, n).astype(np.int32)), None,
               Dictionary(["a", "b", "c"])),
        Column(T.BOOLEAN, jnp.asarray(rng.random(n) < 0.5), None, None),
        Column(T.decimal(38, 2), jnp.asarray(limbs), None, None),
    ] + [Column(T.BIGINT, jnp.asarray(rng.integers(0, 99, n)), None, None) for _ in range(9)],
        jnp.asarray(live))


@pytest.mark.parametrize("live_rows_, capacity", [(0, 64), (1, 64), (37, 64), (64, 64), (300, 64)])
def test_packing_keeps_every_live_row_once_and_in_order(live_rows_, capacity):
    """`_pack_rows`: one sort that carries every lane (13 columns: more
    than one sort's operands, so two sorts on the same key). The first
    `capacity` live rows come out, at the front, in order, with their
    NULLs, codes, booleans and limbs."""
    rng = np.random.default_rng(35 + live_rows_)
    n = 1024
    live = np.zeros(n, dtype=bool)
    live[rng.choice(n, live_rows_, replace=False)] = True
    batch = wide_batch(n, live, rng)
    packed = O._pack_rows(batch, capacity)
    assert packed.capacity == capacity and packed.width == batch.width
    kept = min(live_rows_, capacity)
    assert np.asarray(packed.live_mask()).tolist() == [True] * kept + [False] * (capacity - kept)
    at = np.nonzero(live)[0][:kept]
    for got, had in zip(packed.columns, batch.columns):
        assert got.type == had.type and got.dictionary == had.dictionary
        assert got.data.dtype == had.data.dtype
        assert np.array_equal(np.asarray(got.data)[:kept], np.asarray(had.data)[at])
        assert (got.valid is None) == (had.valid is None)
        if had.valid is not None:
            assert np.array_equal(np.asarray(got.valid)[:kept], np.asarray(had.valid)[at])
    assert packed.to_pylists() == [r for r, keep in zip(
        RelBatch(batch.columns, None).to_pylists(), live) if keep][:kept]


@pytest.mark.parametrize("cap, packed_by, build_keys, batches_out", [
    (32768, "_front_rows", 10_000, 3), (131072, "_pack_rows", 10_000, 3),
    (131072, "_pack_rows", 16_600, 4)])
def test_packed_parts_fill_batches_of_the_scans_capacity(
        cap, packed_by, build_keys, batches_out, monkeypatch):
    """Behind the bits a scan of 40 batches that keep about a twentieth
    each comes out as 3 batches of the scan's own capacity, every
    survivor once and in order: a batch is packed into a power of two of
    slots (a sixteenth of its own at least) and put where the part
    before it ends, a sixteenth of the scan's slots on at least, so the
    join's programs see one shape and scans that differ by a few rows
    the same number of batches. A build side whose keys keep a little
    over a sixteenth of each batch (a twelfth: parts of an eighth) fills
    4 batches, its rows' worth, not the 5 its parts' slots would. A part
    of at most _DEVICE_PACK_MAX_SLOTS slots is picked out by top_k, a
    larger one by the sort that carries the columns."""
    rng = np.random.default_rng(350)
    keys = rng.choice(200_000, build_keys, replace=False)
    bridge = build_of(np.concatenate([keys, np.zeros(32768 - build_keys, dtype=np.int64)]),
                      live=np.arange(32768) < build_keys)
    calls = []
    for name in ("_front_rows", "_pack_rows"):
        inner = getattr(O, name)
        monkeypatch.setattr(
            O, name, lambda *a, _inner=inner, _name=name, **k: calls.append(_name) or _inner(*a, **k))
    probes, want = [], []
    for b in range(40):
        pk = rng.integers(0, 200_000, cap)
        probes.append(keyed_batch(pk, payload=np.arange(cap) + b * cap))
        hit = np.nonzero(np.isin(pk, keys))[0]
        want += [(int(pk[i]), int(i) + b * cap) for i in hit]
    taken, at = [], 0
    for b in range(40):
        kept = sum(1 for _, payload in want if payload // cap == b)
        taken.append((at, kept))
        at += max(kept, cap // O.DF_PACK_PARTS)
    assert -(-at // cap) == batches_out
    before = (METRICS.counter("df_pack_batches_in"), METRICS.counter("df_pack_batches_out"))
    df, out = filtered_by(bridge, probes)
    assert df._path == "bits" and df._packing
    assert calls == [packed_by] * 40
    assert [b.capacity for b in out] == [cap] * batches_out
    live = np.concatenate([np.asarray(b.live_mask()) for b in out])
    assert np.nonzero(live)[0].tolist() == [
        start + i for start, kept in taken for i in range(kept)]
    assert live_rows(out) == want
    assert (METRICS.counter("df_pack_batches_in") - before[0],
            METRICS.counter("df_pack_batches_out") - before[1]) == (40, batches_out)
    # a column no sort can carry (a nested one): the batch leaves masked
    monkeypatch.setattr(O, "_sortable", lambda batch: False)
    df, out = filtered_by(bridge, probes[:3])
    assert [b.capacity for b in out] == [cap] * 3 and not df._packing
    assert live_rows(out) == [w for w in want if w[1] < 3 * cap]


# -- columns nothing reads after a join (issue 35) ---------------------------------------------


def joined(bridge, probes, kind="inner", **kwargs):
    join = O.LookupJoinOperator(
        bridge, [0], kind, [(T.BIGINT, None), (T.BIGINT, None)], **kwargs)
    out = []
    for probe in probes:
        join.add_input(probe)
        out += drain(join)
    join.finish()
    return join, out + drain(join)


@pytest.mark.parametrize("build_keys, path", [
    (np.arange(512) * 3, "fanout_one"), (np.arange(512) % 128 * 3, "general")])
def test_a_join_hands_on_zeros_for_the_columns_nothing_reads(build_keys, path, monkeypatch):
    """An inner join told that nothing downstream reads output channels
    1 (the probe's payload) and 2 (the build side's key) puts out the
    same pairs with zeros there, on the fanout-one path and on the
    general one; the join still verifies the build side's key, which it
    reads itself. A join with a residual, or one that is not inner,
    keeps every column."""
    calls = []
    for name in ("_expand_pairs", "_expand_pairs_fanout1"):
        inner = getattr(O, name)
        monkeypatch.setattr(
            O, name, lambda *a, _inner=inner, _name=name, **k: calls.append(_name) or _inner(*a, **k))
    rng = np.random.default_rng(352)
    probes = [keyed_batch(rng.integers(0, 1536, 2048), payload=np.arange(2048) + 7 + 2048 * b)
              for b in range(2)]
    bridge = build_of(build_keys)
    _, whole = joined(bridge, probes)
    _, pruned = joined(bridge, probes, unread=(1, 2))
    assert set(calls) == {"_expand_pairs_fanout1" if path == "fanout_one" else "_expand_pairs"}
    assert len(whole) == len(pruned) == 2
    pairs = 0
    for a, b in zip(whole, pruned):
        live = np.asarray(a.live_mask())
        pairs += int(live.sum())
        assert np.array_equal(live, np.asarray(b.live_mask()))
        for c in (0, 3):
            assert np.array_equal(np.asarray(a.columns[c].data)[live],
                                  np.asarray(b.columns[c].data)[live])
        assert np.asarray(a.columns[1].data)[live].min() >= 7
        for c in (1, 2):
            assert b.columns[c].type == a.columns[c].type
            assert not np.asarray(b.columns[c].data).any() and b.columns[c].valid is None
    assert pairs == sum(int(np.isin(build_keys, np.asarray(p.columns[0].data)[i]).sum())
                        for p in probes for i in range(2048))
    for kind, kwargs in (("left", {}), ("inner", {"residual_fn": lambda pairs: pairs.live_mask()})):
        join, out = joined(bridge, probes, kind=kind, unread=(1, 2), **kwargs)
        assert join._unread == ()
        assert np.asarray(out[0].columns[1].data)[np.asarray(out[0].live_mask())].min() >= 7


def test_what_nothing_reads_of_a_joins_output_is_found_from_the_plan(tpch_local, monkeypatch):
    """`sql/local_planner.unread_join_outputs` on Q3 and Q9: the last
    join of Q9 hands on the six columns the projection reads of its
    seventeen, every join below it those and the keys of the joins above
    it; a semi-join's and a left join's own columns are all read."""
    from trino_tpu.sql import local_planner as LP
    from trino_tpu.sql import plan as P

    found = []
    inner = LP.unread_join_outputs

    def spy(root):
        unread = inner(root)
        joins = []

        def walk(node):
            if isinstance(node, P.JoinNode):
                joins.append((node, unread.get(id(node))))
            for child in node.children():
                walk(child)

        walk(root)
        found.append(joins)
        return unread

    monkeypatch.setattr(LP, "unread_join_outputs", spy)

    def read_of(sql):
        del found[:]
        tpch_local.execute(sql)
        return [(node.kind, None if unread is None else
                 sorted(f.name for i, f in enumerate(node.fields) if i not in unread))
                for node, unread in found[-1]]

    q9 = read_of("""
        select n_name, extract(year from o_orderdate), sum(l_extendedprice * (1 - l_discount)
               - ps_supplycost * l_quantity)
        from part, supplier, lineitem, partsupp, orders, nation
        where s_suppkey = l_suppkey and ps_suppkey = l_suppkey and ps_partkey = l_partkey
        and p_partkey = l_partkey and o_orderkey = l_orderkey and s_nationkey = n_nationkey
        and p_name like '%green%' group by 1, 2""")
    assert len(q9) == 5 and all(kind == "inner" for kind, _ in q9)
    assert q9[0][1] == sorted(["l_quantity", "l_extendedprice", "l_discount", "o_orderdate",
                               "ps_supplycost", "n_name"])
    for (_, above), (_, below) in zip(q9, q9[1:]):
        assert below is not None and len(below) <= len(above) + 2
    assert all("p_name" not in read for _, read in q9)
    q3 = read_of("""
        select l_orderkey, sum(l_extendedprice * (1 - l_discount)), o_orderdate, o_shippriority
        from customer, orders, lineitem
        where c_mktsegment = 'BUILDING' and c_custkey = o_custkey and l_orderkey = o_orderkey
        and o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15'
        group by l_orderkey, o_orderdate, o_shippriority""")
    assert [kind for kind, _ in q3] == ["inner", "inner"]
    assert set(q3[0][1]) == {"l_orderkey", "l_extendedprice", "l_discount", "o_orderdate",
                             "o_shippriority"}
    others = read_of("""
        select o_orderkey, c_name from orders left join customer on c_custkey = o_custkey
        where o_orderkey in (select l_orderkey from lineitem where l_quantity > 49)""")
    assert others and all(read is None for _, read in others)


# -- the semi-join: where it is planned, what it answers ------------------------------------


def plan_of(runner, sql):
    text = runner.execute("explain " + sql).rows[0][0]
    return [line for line in text.splitlines() if line.strip()]


def depth_of(plan, prefix):
    return [len(line) - len(line.lstrip()) for line in plan
            if line.lstrip().startswith(prefix)]


def test_a_semi_join_is_planned_on_the_source_that_holds_its_key(tpch_local):
    plan = plan_of(tpch_local, """
        select o_orderkey, c_name from customer, orders
        where c_custkey = o_custkey
        and o_orderkey in (select l_orderkey from lineitem where l_quantity > 49)""")
    (semi,), (inner,) = depth_of(plan, "Join semi"), depth_of(plan, "Join inner")
    assert semi > inner
    below = plan[[i for i, l in enumerate(plan) if l.lstrip().startswith("Join semi")][0] + 1]
    assert "orders" in below


def test_a_semi_join_stays_above_an_outer_join(tpch_local):
    plan = plan_of(tpch_local, """
        select c_custkey, o_orderkey from customer left join orders on c_custkey = o_custkey
        where o_orderkey in (select l_orderkey from lineitem where l_quantity > 49)""")
    (semi,), (left,) = depth_of(plan, "Join semi"), depth_of(plan, "Join left")
    assert semi < left


def test_the_rule_moves_a_semi_join_through_filter_project_and_join():
    from trino_tpu.expr import ir
    from trino_tpu.sql.optimizer import IterativeOptimizer, PushSemiJoinDown

    def values(n):
        return P.ValuesNode(tuple(P.Field(f"c{i}", T.BIGINT) for i in range(n)), ())

    a, b, s = values(2), values(3), values(1)
    join = P.JoinNode("inner", a, b, (0,), (1,), None, a.fields + b.fields)
    keep = P.FilterNode(join, ir.comparison(
        "gt", ir.InputRef(0, T.BIGINT), ir.Literal(1, T.BIGINT)), join.fields)
    swap = P.ProjectNode(
        keep, (ir.InputRef(3, T.BIGINT), ir.InputRef(0, T.BIGINT)),
        (P.Field("x", T.BIGINT), P.Field("y", T.BIGINT)))
    semi = P.JoinNode("semi", swap, s, (0,), (0,), None, swap.fields)
    out = IterativeOptimizer((PushSemiJoinDown(),)).optimize(semi)
    # Project(Filter(Join(a, Semi(b, s)))): channel 0 of the projection
    # is channel 3 of the join, which is channel 1 of its right side
    assert isinstance(out, P.ProjectNode) and isinstance(out.child, P.FilterNode)
    moved = out.child.child.right
    assert isinstance(moved, P.JoinNode) and moved.kind == "semi"
    assert moved.left == b and moved.left_keys == (1,) and moved.fields == b.fields
    # a semi-join with a residual reads both sides' columns: it stays
    residual = P.JoinNode("semi", swap, s, (0,), (0,), ir.comparison(
        "gt", ir.InputRef(1, T.BIGINT), ir.InputRef(2, T.BIGINT)), swap.fields)
    assert IterativeOptimizer((PushSemiJoinDown(),)).optimize(residual) == residual


@pytest.mark.parametrize("name, having", [
    ("an-empty-build", "sum(l_quantity) > 100000"),
    ("every-key", "sum(l_quantity) > 0"),
    ("a-few-keys", "sum(l_quantity) > 270"),
])
def test_semi_join_over_joins_answers_what_the_oracle_answers(name, having, tpch_local):
    sql = f"""
        select c_custkey, o_orderkey, sum(l_quantity), count(*)
        from customer, orders, lineitem
        where o_orderkey in (select l_orderkey from lineitem group by l_orderkey
                             having {having})
        and c_custkey = o_custkey and o_orderkey = l_orderkey
        group by c_custkey, o_orderkey"""
    got = tpch_local.execute(sql).rows
    want = oracle_rows(0.01, sql)
    assert len(want) == {"an-empty-build": 0, "every-key": 15000}.get(name, len(want))
    assert_rows_match(got, want, ordered=False)


# -- states whose key ranges ascend are laid end to end (issue 34) ------------------------

I64 = np.iinfo(np.int64)
# merge reducers of sum, count, min, max and any, slot by slot
SEAM_REDUCERS = ("sum", "sum", "min", "max", "first")
STATE_CAP, TABLE = 16, 256


def one_state(keys, rng, cap=STATE_CAP, holes=()):
    """A group state of `cap` slots holding `keys` from slot 0 (None: the
    NULL group), random slot values; a slot that counted no row holds
    what a reduce leaves there. `holes`: used slots switched off."""
    n = len(keys)
    cnts = rng.integers(0, 3, (len(SEAM_REDUCERS), n))
    cnts[:2] = np.maximum(cnts[:2], 1)
    vals = rng.integers(-99, 99, (len(SEAM_REDUCERS), n))
    vals[2][cnts[2] == 0], vals[3][cnts[3] == 0] = I64.max, I64.min

    def slots(a, dtype=np.int64):
        return jnp.asarray(np.concatenate([a, np.zeros(cap - n, a.dtype)]).astype(dtype))

    used = np.arange(n) >= 0
    used[list(holes)] = False
    return (
        (slots(np.array([0 if k is None else k for k in keys], np.int64)),),
        (slots(np.array([k is not None for k in keys], bool), bool),),
        slots(used, bool),
        tuple(slots(v) for v in vals), tuple(slots(c) for c in cnts),
    )


def seam_case(name, n_states):
    """Key lists, one a state, and whether they ascend as operands."""
    own = [list(range(10 * i, 10 * i + 5)) for i in range(n_states)]
    if name == "ranges_of_their_own":
        return own, True
    if name == "meeting_in_one_group_at_every_seam":
        return [list(range(5 * i, 5 * i + 6)) for i in range(n_states)], True
    if name == "one_key_spanning_three_states":
        return [[0, 1, 2], [2]] + ([[2, 3, 4]] + own[3:] if n_states > 2 else []), True
    if name == "an_empty_state_in_the_middle_and_at_the_end":
        own[n_states // 2], own[-1] = [], []
        return own, True
    if name == "the_null_group_across_the_last_seam":
        own[-2], own[-1] = own[-2] + [None], [None]
        return own, True
    if name == "a_key_twice_in_a_state":
        own[-1] = own[-1][:2] + own[-1][1:]
        return own, False
    if name == "two_states_swapped":
        own[0], own[-1] = own[-1], own[0]
        return own, False
    if name == "a_null_before_a_value":
        own[0] = [None] + own[0]
        return own, False
    assert name == "a_hole_in_used"
    return own, False


@pytest.fixture(scope="module")
def resort():
    return jax.jit(O._resort_states, static_argnames=("reducers", "out_capacity"))


@pytest.mark.parametrize("n_states", [2, 8, 9])
@pytest.mark.parametrize("name", [
    "ranges_of_their_own", "meeting_in_one_group_at_every_seam",
    "one_key_spanning_three_states", "an_empty_state_in_the_middle_and_at_the_end",
    "the_null_group_across_the_last_seam", "a_key_twice_in_a_state",
    "two_states_swapped", "a_null_before_a_value", "a_hole_in_used",
])
def test_states_laid_end_to_end_equal_the_states_sorted(name, n_states, resort):
    """sum, count, min, max and first over 2, 8 and 9 single-key states:
    the merge says which way it went and equals the concatenate-and-
    reduce slot for slot."""
    rng = np.random.default_rng(34 + n_states)
    key_lists, ascend = seam_case(name, n_states)
    states = tuple(
        one_state(keys, rng, holes=(1,) if name == "a_hole_in_used" and i == 1 else ())
        for i, keys in enumerate(key_lists))
    want, want_groups, want_ovf = resort(states, SEAM_REDUCERS, TABLE)
    got, groups, word = O._merge_group_states(states, SEAM_REDUCERS, TABLE)
    assert bool(int(word) & O.G.ORDERED) == ascend
    assert not int(word) & 1 and not bool(want_ovf)
    distinct = {k for keys, s in zip(key_lists, states)
                for k, u in zip(keys, np.asarray(s[2])) if u}
    assert int(groups) == int(want_groups) == len(distinct)
    assert_same_state(got, want, states, ascend)


def assert_same_state(got, want, states, laid):
    (gk,), (gv,), used, vals, cnts = jax.device_get(got)
    (wk,), (wv,), w_used, w_vals, w_cnts = jax.device_get(want)
    np.testing.assert_array_equal(used, w_used)
    np.testing.assert_array_equal(gv, wv)
    np.testing.assert_array_equal(gk[w_used & wv], wk[w_used & wv])
    for v, c, wv_, wc, red in zip(vals, cnts, w_vals, w_cnts, SEAM_REDUCERS):
        np.testing.assert_array_equal(c[w_used], wc[w_used])
        if red != "first":
            np.testing.assert_array_equal(v[w_used], wv_[w_used])
    # `first` is any of the group's values that counted a row (the sort
    # is unstable); states laid end to end keep the earliest state's
    firsts = collections.defaultdict(list)
    for (k,), (kv,), s_used, s_vals, s_cnts in jax.device_get(states):
        for i in np.nonzero(s_used & (s_cnts[-1] > 0))[0]:
            firsts[int(k[i]) if kv[i] else None].append(int(s_vals[-1][i]))
    for i in np.nonzero(w_used & (w_cnts[-1] > 0))[0]:
        among = firsts[int(gk[i]) if gv[i] else None]
        assert int(w_vals[-1][i]) in among
        assert int(vals[-1][i]) in (among[:1] if laid else among)


WIDE = 64


def states_with_a_wide_one(rng, null_group, slot=None):
    """A state of WIDE slots holding 20 groups at its front (21 with the
    NULL group), as a fold that re-sorted leaves it, and two of
    STATE_CAP slots whose keys fall among its own; `slot`: that one
    value slot of SEAM_REDUCERS alone."""
    key_lists = [list(range(0, 40, 2)) + ([None] if null_group else []),
                 [7, 8, 9, 10] + ([None] if null_group else []), [1, 2, 3]]
    states = [one_state(keys, rng, cap=WIDE if i == 0 else STATE_CAP)
              for i, keys in enumerate(key_lists)]
    if slot is not None:
        states = [(k, v, used, vals[slot:slot + 1], cnts[slot:slot + 1])
                  for k, v, used, vals, cnts in states]
    return tuple(states), len(key_lists[0])


@pytest.mark.parametrize("null_group", [False, True], ids=["no-null", "null-group"])
@pytest.mark.parametrize("slot", range(len(SEAM_REDUCERS)),
                         ids=["sum", "count", "min", "max", "first"])
def test_a_merge_that_reads_a_state_short_equals_the_merge_that_reads_it_whole(slot, null_group):
    """Slot for slot and flag for flag; `first` is any of the group's
    values that counted a row (the sort is unstable, and the two sorts
    differ in length)."""
    rng = np.random.default_rng(44 + slot)
    states, held = states_with_a_wide_one(rng, null_group, slot)
    reducers = SEAM_REDUCERS[slot:slot + 1]
    takes = (O.bucket_capacity(held), None, None)
    assert takes[0] < WIDE
    want, want_groups, want_word = O._merge_group_states(states, reducers, TABLE)
    got, groups, word = O._merge_group_states(states, reducers, TABLE, takes)
    assert int(word) == int(want_word) == 0 and int(groups) == int(want_groups) == 24 + null_group
    (gk,), (gv,), used, (v,), (c,) = jax.device_get(got)
    (wk,), (wv,), w_used, (w_v,), (w_c,) = jax.device_get(want)
    for a, b in ((gk, wk), (gv, wv), (used, w_used), (c, w_c)):
        np.testing.assert_array_equal(a, b)
    if reducers != ("first",):
        np.testing.assert_array_equal(v, w_v)
        return
    firsts = collections.defaultdict(list)
    for (k,), (kv,), s_used, (s_v,), (s_c,) in jax.device_get(states):
        for i in np.nonzero(s_used & (s_c > 0))[0]:
            firsts[int(k[i]) if kv[i] else None].append(int(s_v[i]))
    for i in np.nonzero(used & (c > 0))[0]:
        assert int(v[i]) in firsts[int(gk[i]) if gv[i] else None]


def test_a_used_slot_behind_a_cut_raises_the_overflow_bit():
    """Single key (the flag is a word, and keeps which way the merge
    went) and several keys (a plain flag); a cut behind every used slot
    raises nothing."""
    rng = np.random.default_rng(44)
    states, held = states_with_a_wide_one(rng, null_group=False)
    for takes, dropped in (((held, None, None), False), ((held - 1, None, None), True),
                           ((None, None, 2), True), ((None, 4, 3), False)):
        _, _, word = O._merge_group_states(states, SEAM_REDUCERS, TABLE, takes)
        assert bool(int(word) & 1) == dropped and not int(word) & O.G.ORDERED
    two_keys = tuple((k * 2, v * 2, *rest) for k, v, *rest in states)
    for takes, dropped in (((held, None, None), False), ((held - 1, None, None), True)):
        _, _, flag = O._merge_group_states(two_keys, SEAM_REDUCERS, TABLE, takes)
        assert flag.dtype == jnp.bool_ and bool(flag) == dropped


@pytest.mark.parametrize("name", ["ranges_of_their_own", "two_states_swapped"])
def test_more_groups_than_the_table_raise_the_flag_either_way(name, resort):
    key_lists, ascend = seam_case(name, 8)           # 40 groups into 32 slots
    rng = np.random.default_rng(5)
    states = tuple(one_state(keys, rng) for keys in key_lists)
    _, groups, word = O._merge_group_states(states, SEAM_REDUCERS, 32)
    _, want_groups, want_ovf = resort(states, SEAM_REDUCERS, 32)
    assert int(word) & 1 and bool(want_ovf) and int(groups) == int(want_groups) == 40
    assert bool(int(word) & O.G.ORDERED) == ascend


def test_a_table_smaller_than_the_last_offset_plus_a_state_is_written_whole():
    """Three states of 64 slots, 20 groups each, into a table of 64: the
    third is written at 40, where 64 more slots do not fit; the buffer
    is the table plus a state, so it lands at 40 all the same."""
    rng = np.random.default_rng(6)
    states = tuple(one_state(list(range(20 * i, 20 * i + 20)), rng, cap=64) for i in range(3))
    got, groups, word = O._merge_group_states(states, SEAM_REDUCERS, 64)
    assert int(word) == O.G.ORDERED and int(groups) == 60
    assert np.asarray(got[0][0])[:60].tolist() == list(range(60))
    assert np.asarray(got[2]).tolist() == [True] * 60 + [False] * 4
    np.testing.assert_array_equal(
        np.asarray(got[3][0])[40:60], np.asarray(states[2][3][0])[:20])


def test_multi_key_states_keep_the_plain_flag_and_todays_program(resort):
    rng = np.random.default_rng(8)
    single = [one_state(list(range(5 * i, 5 * i + 5)), rng) for i in range(2)]
    states = tuple((k + k, v + v, *rest) for k, v, *rest in single)
    got, groups, flag = O._merge_group_states(states, SEAM_REDUCERS, TABLE)
    assert flag.dtype == jnp.bool_ and not bool(flag) and int(groups) == 10
    want = resort(states, SEAM_REDUCERS, TABLE)[0]
    np.testing.assert_array_equal(np.asarray(got[3][0]), np.asarray(want[3][0]))


@pytest.mark.parametrize("kind", ["min", "max"])
def test_a_128_bit_extreme_across_a_seam(kind, resort):
    """The (hi, lo) pair of a long-decimal min / max folds as one
    number: lo compares unsigned among equal hi, and a side that
    counted no row gives way."""
    reducers = (f"{kind}128h", f"{kind}128l")
    pairs = [(5, -1), (5, 3), (-2, 7), (5, -1), (9, 9)]       # (hi, lo); lo -1 is 2^64 - 1
    for (ah, al), (bh, bl), ac, bc in [
            (pairs[0], pairs[1], 1, 1), (pairs[1], pairs[0], 2, 1), (pairs[2], pairs[3], 1, 1),
            (pairs[4], pairs[2], 0, 1), (pairs[2], pairs[4], 1, 0)]:
        def state(keys, hi, lo, cnt):
            pad = np.zeros(STATE_CAP - 2, np.int64)
            col = lambda *x: jnp.asarray(np.concatenate([np.array(x, np.int64), pad]))  # noqa: E731
            return ((col(*keys),), (col(1, 1).astype(bool),), col(1, 1).astype(bool),
                    (col(*hi), col(*lo)), (col(*cnt), col(*cnt)))
        # key 2 ends the first state and begins the second
        states = (state((1, 2), (1, ah), (1, al), (1, ac)),
                  state((2, 3), (bh, 1), (bl, 1), (bc, 1)))
        got, groups, word = O._merge_group_states(states, reducers, 64)
        want, _, _ = resort(states, reducers, 64)
        assert int(word) == O.G.ORDERED and int(groups) == 3
        if ac and bc:       # the reduce's extreme is over every slot, counted or not
            for i in (0, 1):
                assert int(got[3][i][1]) == int(want[3][i][1])
        a, b = to_python(ah, al), to_python(bh, bl)
        best = (min if kind == "min" else max)(a, b) if ac and bc else (a if ac else b)
        assert to_python(int(got[3][0][1]), int(got[3][1][1])) == best
        assert int(got[4][0][1]) == ac + bc


def test_a_long_decimal_sum_across_a_seam():
    """Batches whose keys overlap by one group with the next (an order's
    rows straddling two batches): four limb slots and their carries add
    across every seam, and every merge lays its states end to end."""
    rng = np.random.default_rng(9)
    rows = []
    for b in range(18):
        keys = np.sort(39 * b + rng.integers(0, 40, BATCH))
        keys[0], keys[-1] = 39 * b, 39 * b + 39              # both seams are there
        big = [(2**64 - 1 - int(x)) * (2**32 if i % 2 else 1) for i, x in
               enumerate(rng.integers(0, 1000, BATCH))]
        rows.append((keys, big, rng.integers(-1000, 1000, BATCH)))
    names = ("agg_merge_launches", "agg_ordered_merge.launches",
             "agg_ordered_input.batches", "agg_ingest_path.sort")
    before = {k: METRICS.counter(k) for k in names}
    got, _ = aggregate(rows)
    moved = {k: METRICS.counter(k) - v for k, v in before.items()}
    assert got == want_rows(rows)
    assert moved["agg_ordered_merge.launches"] == moved["agg_merge_launches"] == 3
    assert moved["agg_ordered_input.batches"] == moved["agg_ingest_path.sort"] == 18


def test_keys_out_of_order_sort_as_before_and_count_nothing():
    rows = make_rows(18, 40, seed=3, overlap=True)
    names = ("agg_merge_launches", "agg_ordered_merge.launches", "agg_ordered_input.batches")
    before = {k: METRICS.counter(k) for k in names}
    got, _ = aggregate(rows)
    moved = {k: METRICS.counter(k) - v for k, v in before.items()}
    assert got == want_rows(rows)
    assert moved == {"agg_merge_launches": 3, "agg_ordered_merge.launches": 0,
                     "agg_ordered_input.batches": 0}


# -- Q18 at `tiny`, over the ordered tables and over a permuted lineitem ------------------

Q18_SQL = """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, sum(l_quantity)
from customer, orders, lineitem
where o_orderkey in (
    select l_orderkey from lineitem group by l_orderkey having sum(l_quantity) > 250)
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate
limit 100
"""
Q18_TABLES = {
    "lineitem": ["l_orderkey", "l_quantity"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
    "customer": ["c_custkey", "c_name"],
}


def q18_runner(permute):
    from trino_tpu.connectors.memory import create_memory_connector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.connectors.tpch import TABLES, base_row_count, generate_column
    from trino_tpu.engine import LocalQueryRunner, Session

    mem = create_memory_connector()
    for table, names in Q18_TABLES.items():
        cols = [generate_column(table, c, 0.01, 0, base_row_count(table, 0.01)) for c in names]
        data = [d for d, _ in cols]
        if permute and table == "lineitem":
            order = np.random.default_rng(34).permutation(len(data[0]))
            data = [np.asarray(d)[order] for d in data]
        types = dict(TABLES[table])
        mem.load_table("tiny", table, [ColumnMetadata(n, types[n]) for n in names],
                       data, None, [d for _, d in cols])
    runner = LocalQueryRunner(Session(catalog="memory", schema="tiny", batch_rows=4096))
    runner.register_catalog("memory", mem)
    return runner


@pytest.mark.parametrize("permute", [False, True], ids=["ordered", "permuted_lineitem"])
def test_q18_takes_the_ordered_branch_where_lineitem_is_in_key_order(permute):
    """15 batches of 4,096 rows, a fold and a last merge in the
    sub-query's aggregation (the statement's second aggregation has
    five keys: one more batch on the sort path, never checked)."""
    names = ("agg_ingest_path.sort", "agg_ordered_input.batches",
             "agg_merge_launches", "agg_ordered_merge.launches", "agg_merge_retries")
    before = {k: METRICS.counter(k) for k in names}
    rows = q18_runner(permute).execute(Q18_SQL).rows
    moved = {k: METRICS.counter(k) - v for k, v in before.items()}
    assert len(rows) == 63
    assert_rows_match(rows, oracle_rows(0.01, Q18_SQL), ordered=True)
    assert moved["agg_ingest_path.sort"] == 15 + 1 and moved["agg_merge_launches"] == 2
    assert moved["agg_merge_retries"] == 0
    assert moved["agg_ordered_input.batches"] == (0 if permute else 15)
    assert moved["agg_ordered_merge.launches"] == (0 if permute else 2)
