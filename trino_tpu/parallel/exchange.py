"""Device-mesh exchange: hash repartition over ICI.

The remote-exchange data plane of the reference — AddExchanges inserting
FIXED_HASH_DISTRIBUTION repartitions between stages + the page-shuffle
wire (SURVEY.md §2.7/§2.8, optimizations/AddExchanges.java:266–276,
PartitionedOutputOperator.java:46) — rebuilt the TPU way: instead of
HTTP page streams between worker JVMs, a `shard_map` over a
`jax.sharding.Mesh` where every shard scatters its rows into
per-destination blocks and one `lax.all_to_all` rides the ICI.

Static-shape discipline: each shard owns R rows and sends at most B rows
to each destination (B bounded by R). Overflow cannot happen when
B == R; smaller B trades memory for a host-visible overflow flag the
caller can react to (grow + retry, like the group table).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PSpec

from trino_tpu.ops import groupby as G
from trino_tpu.ops.gather import take_clip
from trino_tpu.ops.hashing import hash64


def partition_for_exchange(
    keys: Sequence[jnp.ndarray],
    valids: Sequence[jnp.ndarray],
    live: jnp.ndarray,
    payloads: Sequence[jnp.ndarray],
    n_shards: int,
    block_rows: int,
):
    """Per-shard half of the exchange: scatter local rows into
    (n_shards, block_rows) destination blocks by key hash.

    Runs INSIDE shard_map (operates on one shard's local rows). Returns
    (key_blocks, valid_blocks, live_blocks, payload_blocks, overflowed).
    The PagePartitioner analogue (output/PartitionedOutputOperator.java:191).
    """
    h = hash64(list(keys), list(valids))
    target = (h.astype(jnp.uint64) % jnp.uint64(n_shards)).astype(jnp.int32)
    target = jnp.where(live, target, n_shards)  # dead rows go nowhere
    # stable order by destination; rank within destination = slot index
    order = jnp.argsort(target, stable=True)
    sorted_target = take_clip(target, order)
    idx = jnp.arange(sorted_target.shape[0], dtype=jnp.int32)
    dest_start = jnp.searchsorted(sorted_target, jnp.arange(n_shards, dtype=jnp.int32))
    slot = idx - take_clip(dest_start, jnp.clip(sorted_target, 0, n_shards - 1))
    overflowed = jnp.any((slot >= block_rows) & (sorted_target < n_shards))
    flat = jnp.where(
        sorted_target < n_shards,
        jnp.clip(sorted_target, 0, n_shards - 1) * block_rows
        + jnp.clip(slot, 0, block_rows - 1),
        n_shards * block_rows,
    )

    def scatter(col):
        z = jnp.zeros(n_shards * block_rows + 1, dtype=col.dtype)
        return z.at[flat].set(take_clip(col, order), mode="drop")[:-1].reshape(
            n_shards, block_rows
        )

    live_blocks = (
        jnp.zeros(n_shards * block_rows + 1, dtype=jnp.bool_)
        .at[flat]
        .set(take_clip(live, order), mode="drop")[:-1]
        .reshape(n_shards, block_rows)
    )
    key_blocks = [scatter(k) for k in keys]
    valid_blocks = [scatter(v) for v in valids]
    payload_blocks = [scatter(p) for p in payloads]
    return key_blocks, valid_blocks, live_blocks, payload_blocks, overflowed


def distributed_groupby_step(
    mesh: Mesh,
    axis: str,
    table_capacity: int,
    n_aggs: int,
):
    """Build the jitted distributed aggregation step: rows sharded over
    `axis` -> local partial aggregation -> all_to_all hash repartition of
    group states -> final aggregation per shard.

    This is the partial->FIXED_HASH exchange->final pattern Trino plans
    for every GROUP BY (AddExchanges.java:276 + HashAggregationOperator
    PARTIAL/FINAL steps), expressed as one SPMD program. Returns
    step(keys, valids, live, values) -> per-shard
    (group_keys, group_valids, used, sums, counts, overflowed), sharded
    so every group lives on exactly one shard; a nonzero `overflowed`
    means some shard's table filled — the host reruns at 2x capacity.
    """
    n = mesh.shape[axis]

    def local(keys, valids, live, values):
        # shard_map hands us the local (rows/n,) blocks directly
        # partial aggregation into a local table
        gid, table, _ = G.assign_group_ids(keys, valids, live, table_capacity)
        sums = [
            G.seg_sum(gid, v, live, table_capacity, dtype=jnp.float32
                      if jnp.issubdtype(v.dtype, jnp.floating) else jnp.int64)
            for v in values
        ]
        counts = G.seg_count(gid, live, table_capacity)

        # exchange partial states: rows = table slots. block == capacity
        # means a destination can absorb every slot of a source shard, so
        # overflow is impossible by construction; smaller blocks would
        # need the grow-and-retry protocol, so surface the flag.
        block = table_capacity
        kb, vb, lb, pb, overflowed = partition_for_exchange(
            table.slot_keys,
            table.slot_valids,
            table.slot_used,
            sums + [counts],
            n,
            block,
        )
        # all_to_all over the mesh axis: axis index 0 of the (n, block) blocks
        kb = [jax.lax.all_to_all(k, axis, 0, 0, tiled=True) for k in kb]
        vb = [jax.lax.all_to_all(v, axis, 0, 0, tiled=True) for v in vb]
        lb = jax.lax.all_to_all(lb, axis, 0, 0, tiled=True)
        pb = [jax.lax.all_to_all(p, axis, 0, 0, tiled=True) for p in pb]

        # final aggregation of received partials
        fkeys = [k.reshape(-1) for k in kb]
        fvalids = [v.reshape(-1) for v in vb]
        flive = lb.reshape(-1)
        fsums = [p.reshape(-1) for p in pb[:-1]]
        fcounts = pb[-1].reshape(-1)
        fgid, ftable, final_overflow = G.assign_group_ids(
            fkeys, fvalids, flive, table_capacity
        )
        out_sums = [
            G.seg_sum(fgid, s, flive, table_capacity, dtype=s.dtype) for s in fsums
        ]
        out_counts = G.seg_sum(fgid, fcounts, flive, table_capacity, dtype=jnp.int64)
        any_overflow = jax.lax.pmax(
            (overflowed | final_overflow).astype(jnp.int32), axis
        )
        # local (C,) outputs concatenate over the mesh axis -> (n*C,)
        return (
            list(ftable.slot_keys),
            list(ftable.slot_valids),
            ftable.slot_used,
            out_sums,
            out_counts,
            any_overflow[None],
        )

    row_spec = PSpec(axis)
    out_spec = PSpec(axis)

    def step(keys, valids, live, values):
        f = shard_map(
            local,
            mesh=mesh,
            in_specs=(row_spec, row_spec, row_spec, row_spec),
            out_specs=out_spec,
            check_vma=False,
        )
        return f(keys, valids, live, values)

    return jax.jit(step)
