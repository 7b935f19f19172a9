"""Pallas MXU grouped-aggregation kernel.

The GroupByHash + accumulate hot loop (Trino
main/operator/GroupByHash.java:30 probe + Aggregator.processPage,
SURVEY.md §3.3) mapped onto the systolic array: per chunk of rows, the
transposed group-membership one-hot matrix is contracted against the
byte-limb decomposition of the value columns on the MXU —

    acc[L, C] += limbs(words_chunk)[L, R] @ one_hot_T(gid_chunk)[C, R]^T

Exactness: every value is cut into 8-bit limbs *inside the kernel*, from
the 32-bit words the prologue hands over (no HBM blowup). Both
MXU operands are bf16 and exact in it: a limb is an integer <= 255 and
a one-hot entry is 0 or 1, and bf16 carries 8 significant bits. Their
products are <= 255 and the MXU accumulates them in float32, which is
exact while a sum stays under 2^24: one grid step sums at most
MAX_TILE rows, MAX_TILE * 255 < 2^24. Each step's sums are added to an
int32 accumulator, which holds MAX_ROWS * 255 < 2^31. XLA recombines
limbs into int64 afterwards; two's-complement wraparound makes the limb
sum equal the true int64 sum mod 2^64 — exactly SQL BIGINT arithmetic.
(float32 operands buy nothing: Mosaic multiplies them in one bf16 pass
as well, at the same speed, in twice the VMEM; PERF.md section 6, PR 29.)

Only words that can carry data are handed over: a column the caller
states to be under 2^32 (`limbs` <= 4: a 0/1 indicator has one limb) has
no high word, and the live-row count rides the gid row, which the kernel
turns into ones. Each word row is an operand of its own, a (1, N) int32
array whose tiling in HBM is the 1-D array's, and the kernel lays a
grid step's rows side by side in VMEM. Stacking them into one (w8, N)
plane beforehand was a pass of its own over HBM, read and written: 0.307
of the 0.708 ms that 2^20 rows of Q1's 22 word rows took on a v5e, 0.069
of G3's 0.287 (PERF.md section 6, PR 31).

The tile follows from what the call states (`_row_tile`): a grid step
costs about 0.12 us whatever it holds, so the step is as long as the
default scoped VMEM lets the one-hot and the word rows be, and the
padded slot count C decides that. Measured on a v5e, 2^20 rows, 160
slots: 0.759 ms at the 256-row tile this kernel had, 0.183 ms at 16,384
(PERF.md section 6, PR 29).

Layout notes (the part that makes this TPU-native rather than a CUDA
translation): all row-major (N, k) arrays with tiny k are poison under
TPU (8, 128) tiling (the lane dim pads to 128 — measured 128x HBM
expansion), so the words are (1, N) rows with rows as lanes, and the
group-id vector is the last of them. Constants that meet an int32 in the
kernel must be int32 themselves: under jax x64 a Python int traces as
i64 and Mosaic fails to legalize it.

CPU/test path: pallas interpret mode computes the identical program.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
import numpy as np

MAX_CAPACITY = 2048
# one grid step's float32 sums stay exact
MAX_TILE = 1 << 15
assert MAX_TILE * 255 < 1 << 24
# the int32 accumulator cannot wrap — callers must split or fall back
# past this (the mesh plane's 2^22-row chunk and the aggregation's
# trains count on 2^23)
MAX_ROWS = 1 << 23
assert MAX_ROWS * 255 < 1 << 31
# a grid step's rows are contracted in this many unrolled chunks (a
# rolled loop cannot overlap one chunk's one-hot with the last one's
# matmul: 0.235 against 0.183 ms, PERF.md section 6, PR 29)
_CHUNKS = 8
_I0 = np.int32(0)


def _row_tile(n: int, C: int, w8: int) -> Tuple[int, int]:
    """(rows a grid step, rows a contraction) for n rows, C padded slots
    and w8 word rows: a power of two that keeps a chunk's one-hot at
    2^19 entries (1 MB of bf16 beside its 2 MB int32 comparison) and the
    step's word rows under 2 MB, so both fit the default scoped VMEM
    twice over; shorter where the call has fewer rows."""
    tile = min((1 << 22) // C, (1 << 19) // w8, MAX_TILE)
    tile = 1 << (tile.bit_length() - 1)
    chunk = max(tile // _CHUNKS, 128)
    tile = min(tile, max(1, -(-n // chunk)) * chunk)
    assert tile % chunk == 0 and tile <= MAX_TILE, (n, C, w8, tile, chunk)
    return tile, chunk


def _bf16(x):
    # v5e's VPU has no bf16 lanes: integers and masks go through float32
    return x.astype(jnp.float32).astype(jnp.bfloat16)


def _make_kernel(n_rows: int, w8: int, planes: int, chunk: int):
    def kernel(*refs):
        row_refs, out_ref = refs[:n_rows], refs[n_rows]

        @pl.when(pl.program_id(0) == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        C = out_ref.shape[1]
        acc = jnp.zeros(out_ref.shape, jnp.float32)
        for s in range(row_refs[0].shape[1] // chunk):
            rows = [r[:, s * chunk:(s + 1) * chunk] for r in row_refs]
            gid = rows[-1]  # dead rows carry >= capacity
            onehot_t = _bf16(
                jax.lax.broadcasted_iota(jnp.int32, (C, chunk), 0) == gid
            )
            # the gid row becomes the live-row count's row of ones
            rows[-1] = jnp.ones_like(gid)
            if w8 > n_rows:
                rows.append(jnp.zeros((w8 - n_rows, chunk), jnp.int32))
            words = jnp.concatenate(rows, axis=0)  # (w8, chunk)
            limbs = jnp.concatenate(
                [_bf16((words >> (8 * j)) & 0xFF) for j in range(planes)],
                axis=0,
            )  # (planes * w8, chunk)
            acc = acc + jax.lax.dot_general(
                limbs, onehot_t, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (planes * w8, C)
        out_ref[:] += acc.astype(jnp.int32)

    return kernel


@partial(jax.jit, static_argnames=("capacity", "interpret", "limbs"))
def grouped_sum_mxu(
    gid: jnp.ndarray,
    values: Sequence[jnp.ndarray],
    live: jnp.ndarray,
    capacity: int,
    interpret: bool = False,
    limbs: Optional[Tuple[int, ...]] = None,
) -> List[jnp.ndarray]:
    """Per-group int64 sums of each value column, with the live-row
    count appended last. gid in [0, capacity) for live rows; dead or
    masked rows are dropped. `limbs` states, per value column, how many
    8-bit limbs its values can have: 8 (the default) for any int64, k <
    8 where the caller knows 0 <= value < 2^(8k), so 1 for a 0/1
    indicator."""
    assert capacity <= MAX_CAPACITY, capacity
    n = gid.shape[0]
    assert n <= MAX_ROWS, (n, "int32 limb accumulator would overflow")
    if limbs is None:
        limbs = (8,) * len(values)
    assert len(limbs) == len(values) and all(1 <= k <= 8 for k in limbs), limbs
    C = max(128, -(-capacity // 128) * 128)

    # word rows: (column, limbs in this word, first limb)
    words = []
    for k, nk in enumerate(limbs):
        words.append((k, min(nk, 4), 0))
        if nk > 4:
            words.append((k, nk - 4, 4))
    n_rows = len(words) + 1  # + the gid row
    w8 = -(-n_rows // 8) * 8  # the kernel pads to the sublane tile
    planes = max(w[1] for w in words) if words else 1
    tile, chunk = _row_tile(n, C, w8)
    n_pad = -n % tile

    def row(x, fill=0):
        x = x.astype(jnp.int32)  # truncating wrap: the low 32 bits
        if n_pad:
            x = jnp.concatenate([x, jnp.full(n_pad, fill, jnp.int32)])
        return x.reshape(1, -1)

    cols = [v.astype(jnp.int64) for v in values]
    rows = [row(cols[k] >> (8 * first)) for k, _nl, first in words]
    rows.append(row(jnp.where(live, gid, capacity), capacity))

    out = pl.pallas_call(
        _make_kernel(n_rows, w8, planes, chunk),
        grid=((n + n_pad) // tile,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (_I0, i))] * n_rows,
        out_specs=pl.BlockSpec((planes * w8, C), lambda i: (_I0, _I0)),
        out_shape=jax.ShapeDtypeStruct((planes * w8, C), jnp.int32),
        interpret=interpret,
    )(*rows)

    # XLA epilogue: recombine limb rows -> int64 per value
    results = [jnp.zeros(C, dtype=jnp.int64) for _ in values]
    for r, (k, nl, first) in enumerate(words):
        for j in range(nl):
            results[k] = results[k] + (
                out[j * w8 + r].astype(jnp.int64) << (8 * (first + j))
            )
    results.append(out[n_rows - 1].astype(jnp.int64))  # limb 0 of the ones row
    return [x[:capacity] for x in results]


def grouped_sum_reference(gid, values, live, capacity):
    """Scatter-based oracle with identical semantics."""
    idx = jnp.where(live, gid, capacity)
    outs = []
    for v in list(values) + [jnp.ones(gid.shape[0], jnp.int64)]:
        z = jnp.zeros(capacity + 1, dtype=jnp.int64)
        outs.append(z.at[idx].add(v.astype(jnp.int64))[:capacity])
    return outs
