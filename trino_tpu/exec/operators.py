"""Physical operators.

Analogue of Trino's operator layer (main/operator/Operator.java:21-96 —
needsInput/addInput/getOutput/finish/isBlocked; SURVEY.md §2.6), pulled
batch-at-a-time by the host Driver while all data-parallel work runs as
jit-compiled XLA programs over RelBatch pytrees. TPU-first deltas:

- Operators never loop over rows; each add_input/get_output launches a
  fixed-shape device program (the analogue of the JIT'd PageProcessor /
  GroupByHash / PagesHash inner loops, compiled by jax.jit instead of
  airlift-bytecode — SURVEY.md §2.9).
- Filters only flip `live` mask bits; dead rows ride along until an
  explicit compact (static shapes).
- Dynamic result sizes (join fan-out, group counts) are handled by the
  two-phase count/expand pattern with host-chosen bucketed capacities.
"""

from __future__ import annotations

import dataclasses
import os as _os
import threading as _threading
from trino_tpu.analysis.witness import named_condition, named_lock, named_rlock
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.block import (
    Column,
    Dictionary,
    RelBatch,
    bucket_capacity,
    concat_batches,
)
from trino_tpu.compile.cache import tpu_compiler_options
from trino_tpu.expr.compile import Bound
from trino_tpu.ops import groupby as G
from trino_tpu.ops.gather import take_clip
from trino_tpu.ops import join as J
from trino_tpu.ops.sort import SortKey, sort_order
from trino_tpu.runtime.metrics import METRICS
from trino_tpu.runtime.tracing import host_span, host_sync


class Operator:
    """Pull/push contract (main/operator/Operator.java:21)."""

    def needs_input(self) -> bool:
        return not self._finishing

    def add_input(self, batch: RelBatch) -> None:
        raise NotImplementedError

    def get_output(self) -> Optional[RelBatch]:
        return None

    def finish(self) -> None:
        """No more input will arrive (Operator.finish)."""
        self._finishing = True

    def is_finished(self) -> bool:
        raise NotImplementedError

    def is_blocked(self) -> bool:
        """True when the operator is waiting on an async event (remote
        pages, buffer space) — Operator.isBlocked's ListenableFuture
        collapsed to a poll (the driver sleeps instead of parking on a
        future)."""
        return False

    _finishing = False


def empty_batch(schema: Sequence[Tuple[T.DataType, Optional[Dictionary]]],
                capacity: int = 16) -> RelBatch:
    from trino_tpu.block import phys_zeros

    cols = [
        Column(t, phys_zeros(t, capacity), None, d) for t, d in schema
    ]
    return RelBatch(cols, jnp.zeros(capacity, dtype=jnp.bool_))


def batch_schema(batch: RelBatch) -> List[Tuple[T.DataType, Optional[Dictionary]]]:
    return [(c.type, c.dictionary) for c in batch.columns]


# ---------------------------------------------------------------------------
# Sources
# ---------------------------------------------------------------------------


class TableScanOperator(Operator):
    """Pulls batches from a ConnectorPageSource over a list of splits
    (TableScanOperator.java:47)."""

    def __init__(self, page_source, splits, columns: Sequence[str], batch_rows: int,
                 stabilizer=None):
        self._page_source = page_source
        self._splits = list(splits)
        self._columns = columns
        self._batch_rows = batch_rows
        self._stabilizer = stabilizer
        # zero-arg callable -> ColumnConstraints discovered at runtime
        # (dynamic-filter build domains); folded into every split's
        # handle just before the first page is pulled, so connector-
        # level pruning (parquet row-group stats, constraint masks)
        # applies to them exactly like planned pushdown
        self._runtime_constraints = None
        self._iters = None
        self._done = False

    def set_runtime_constraints(self, fn) -> None:
        self._runtime_constraints = fn

    def _start(self):
        splits = self._splits
        if self._runtime_constraints is not None:
            try:
                cs = tuple(self._runtime_constraints() or ())
            except Exception:
                cs = ()  # pruning is best-effort; the join still filters
            if cs:
                import dataclasses as _dc

                from trino_tpu.connectors.pushdown import (
                    merge_handle_constraints,
                )

                splits = [
                    _dc.replace(
                        s, table=merge_handle_constraints(s.table, cs)
                    )
                    for s in splits
                ]
                METRICS.increment("dynamic_filter_scan_constraints")
        page_source, columns = self._page_source, self._columns
        batch_rows, stabilizer = self._batch_rows, self._stabilizer

        def _gen():
            for split in splits:
                if stabilizer is not None:
                    try:
                        # argument binding raises TypeError immediately
                        # for page sources predating the stabilizer kwarg
                        it = page_source.batches(
                            split, columns, batch_rows, stabilizer=stabilizer
                        )
                    except TypeError:
                        it = page_source.batches(split, columns, batch_rows)
                else:
                    it = page_source.batches(split, columns, batch_rows)
                yield from it

        return _gen()

    def needs_input(self) -> bool:
        return False

    def get_output(self) -> Optional[RelBatch]:
        if self._done:
            return None
        if self._iters is None:
            self._iters = self._start()
        nxt = next(self._iters, None)
        if nxt is None:
            self._done = True
            return None
        if nxt.live is not None:
            with host_sync("scan.rows_scanned", nxt.live.shape[0]):
                n = int(np.asarray(nxt.live).sum())
        elif nxt.columns:
            n = int(nxt.columns[0].data.shape[0])
        else:
            n = 0
        METRICS.increment("rows_scanned", n)
        return nxt

    def is_finished(self) -> bool:
        return self._done


class ValuesOperator(Operator):
    """Emits a fixed list of batches (ValuesOperator.java)."""

    def __init__(self, batches: Sequence[RelBatch]):
        self._batches = list(batches)

    def needs_input(self) -> bool:
        return False

    def get_output(self) -> Optional[RelBatch]:
        if self._batches:
            return self._batches.pop(0)
        return None

    def is_finished(self) -> bool:
        return not self._batches


# ---------------------------------------------------------------------------
# Filter + project
# ---------------------------------------------------------------------------


def make_filter_project_fn(
    filter_bound: Optional[Bound], projections: Sequence[Bound],
    name: str = "filter_project",
):
    """Compile the fused filter+project device program once; shared by
    every operator instance the factory creates (the PageProcessor cache
    discipline — PageFunctionCompiler.java:103 caches per expression).
    `name` labels the jit for profiles/compile logs; it must be stable
    across queries (operator-derived, never a query id) or it would
    split the persistent compile-cache key space."""
    projections = list(projections)

    def fn(batch: RelBatch) -> RelBatch:
        # nested columns (ARRAY/MAP/ROW) ride the cols list WHOLE — their
        # starts/flat/children would be silently dropped by a bare data
        # array; nested-aware bindings unwrap what they need
        cols = [
            c if c.type.is_nested else c.data for c in batch.columns
        ]
        valids = [c.valid for c in batch.columns]
        live = batch.live
        if filter_bound is not None:
            d, v = filter_bound.fn(cols, valids)
            keep = d if v is None else (d & v)  # NULL predicate = drop
            live = keep if live is None else (live & keep)
        out_cols = []
        for b in projections:
            data, valid = b.fn(cols, valids)
            if isinstance(data, Column):
                # nested-typed result (column passthrough, map_keys,
                # row_pack, ...): already a full Column; merge validity
                if valid is not None:
                    v0 = data.valid
                    data = data.with_data(
                        data.data, valid if v0 is None else (v0 & valid)
                    )
                out_cols.append(data)
                continue
            d = b.dictionary
            from trino_tpu.block import RuntimeDictionary

            if (
                (d is None or isinstance(d, RuntimeDictionary))
                and b.type.is_string
                and b.input_ref is not None
                and b.input_ref < len(batch.columns)
            ):
                # runtime-dictionary passthrough for pure column refs:
                # the dictionary is pytree aux data, so a new runtime
                # dictionary (listagg output) retraces this program
                d = batch.columns[b.input_ref].dictionary
            out_cols.append(Column(b.type, data, valid, d))
        return RelBatch(out_cols, live)

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def compose_batch_fns(f1, f2, name: str = "filter_project_chain"):
    """Fuse two per-batch device programs into one (plan-time; the
    composed jit is cached with the plan). On remote-attached devices
    every separate program launch costs a host round trip, so the
    planner folds adjacent filter/project stages — and folds them into
    the consuming blocking operator's kernel — the way XLA fusion folds
    elementwise ops into the matmul."""
    def composed(b):
        return f2(f1(b))

    composed.__name__ = composed.__qualname__ = name
    return jax.jit(composed)


class FilterProjectOperator(Operator):
    """Bound filter/projections fused into one jitted device program —
    the FilterAndProjectOperator + PageProcessor analogue
    (main/operator/FilterAndProjectOperator.java:40, project/PageProcessor.java:53)."""

    def __init__(
        self,
        filter_bound: Optional[Bound],
        projections: Sequence[Bound],
        fn=None,
        read_row_bytes: Optional[int] = None,
    ):
        self._out: Optional[RelBatch] = None
        self._done = False
        self._fn = fn if fn is not None else make_filter_project_fn(
            filter_bound, projections
        )
        # bytes a row of what the stage's predicate and computed columns
        # READ (the planner's count; columns handed on untouched are not
        # in it), for METRICS `filter_read_bytes`: with a byte of mask a
        # slot, the least a launch has to move
        self._read_row_bytes = read_row_bytes

    def needs_input(self) -> bool:
        return self._out is None and not self._finishing

    def add_input(self, batch: RelBatch) -> None:
        if self._read_row_bytes is not None:
            METRICS.increment(
                "filter_read_bytes", batch.capacity * (self._read_row_bytes + 1)
            )
        self._out = self._fn(batch)

    def get_output(self) -> Optional[RelBatch]:
        out, self._out = self._out, None
        return out

    def is_finished(self) -> bool:
        return self._finishing and self._out is None


# ---------------------------------------------------------------------------
# Limit
# ---------------------------------------------------------------------------


@jax.jit
def _limit_batch(batch: RelBatch, skip: jnp.ndarray, remaining: jnp.ndarray):
    live = batch.live_mask()
    rank = jnp.cumsum(live.astype(jnp.int64))  # 1-based among live rows
    keep = live & (rank > skip) & (rank <= skip + remaining)
    n_live = rank[-1] if live.shape[0] else jnp.int64(0)
    skipped = jnp.minimum(n_live, skip)
    taken = jnp.minimum(n_live - skipped, remaining)
    return RelBatch(batch.columns, keep), skipped, taken


class LimitOperator(Operator):
    """LIMIT n OFFSET k (LimitOperator.java): masks rows outside the
    remaining window. The skip/remaining counters live ON DEVICE —
    reading them back per batch would be a host synchronisation point
    per batch; the cost is only that the operator cannot
    early-terminate its upstream, which engine sources bound anyway."""

    def __init__(self, n: Optional[int], offset: int = 0):
        self._skip = None  # device scalars, lazily initialized
        self._remaining = None
        self._init = (n if n is not None else (1 << 60), offset)
        self._out: Optional[RelBatch] = None

    def needs_input(self) -> bool:
        return self._out is None and not self._finishing

    def add_input(self, batch: RelBatch) -> None:
        if self._remaining is None:
            n, offset = self._init
            self._remaining = jnp.int64(n)
            self._skip = jnp.int64(offset)
        out, skipped, taken = _limit_batch(batch, self._skip, self._remaining)
        self._skip = self._skip - skipped
        self._remaining = self._remaining - taken
        self._out = out

    def get_output(self) -> Optional[RelBatch]:
        out, self._out = self._out, None
        return out

    def is_finished(self) -> bool:
        return self._out is None and self._finishing


# ---------------------------------------------------------------------------
# Sort / TopN
# ---------------------------------------------------------------------------


def _apply_sort(batch: RelBatch, keys: Sequence[SortKey]) -> jnp.ndarray:
    return sort_order(
        [batch.columns[k.channel].data for k in keys],
        [batch.columns[k.channel].valid for k in keys],
        [k.descending for k in keys],
        [k.nulls_first for k in keys],
        batch.live,
    )


@partial(jax.jit, static_argnames=("keys", "pre_fn"))
def _concat_sort_pre(
    parts: Tuple[RelBatch, ...], keys: Tuple[SortKey, ...], pre_fn
) -> RelBatch:
    """_concat_sort with a fused upstream filter/project applied to each
    part inside the same program."""
    return _concat_sort.__wrapped__(
        tuple(pre_fn(p) for p in parts), keys
    )


@partial(jax.jit, static_argnames=("keys",))
def _concat_sort(parts: Tuple[RelBatch, ...], keys: Tuple[SortKey, ...]) -> RelBatch:
    """Consolidate + sort + front-pack in ONE device program — eager op
    dispatch is a per-op host round trip on remote-attached TPUs, so
    whole-phase fusion matters beyond XLA fusion itself."""
    merged = concat_batches(list(parts))
    order = _apply_sort(merged, keys)
    n_live = jnp.sum(merged.live_mask())
    live = jnp.arange(order.shape[0]) < n_live
    return merged.gather(order, live)


@partial(jax.jit, static_argnames=("keys", "n", "cap"))
def _topn_merge(
    parts: Tuple[RelBatch, ...], keys: Tuple[SortKey, ...], n: int, cap: int
) -> RelBatch:
    merged = concat_batches(list(parts))
    order = _apply_sort(merged, keys)
    # clamp to the merged capacity: a bucketed cap larger than the
    # concatenated parts (mixed part capacities, e.g. 16+64=80 -> 128)
    # would slice order short while building a longer live mask
    cap = min(cap, int(order.shape[0]))
    top = order[:cap]
    n_live = jnp.minimum(jnp.sum(merged.live_mask()), n)
    live = jnp.arange(cap) < n_live
    return merged.gather(top, live)


class SortOperator(Operator):
    """Full ORDER BY: consolidate + one device sort at finish
    (OrderByOperator.java:44; comparator chains become stable argsorts)."""

    def __init__(self, keys: Sequence[SortKey],
                 input_schema: Sequence[Tuple[T.DataType, Optional[Dictionary]]],
                 memory_context=None, pre_fn=None):
        self._keys = list(keys)
        self._schema = list(input_schema)
        self._pre = pre_fn  # fused upstream filter/project (plan-time jit)
        self._inputs: List[RelBatch] = []
        self._out: Optional[RelBatch] = None
        # revocable accumulation (OrderByOperator's spill path): revoke
        # compacts buffered input into a sorted run on disk; finish
        # re-reads runs for the final device sort (which materializes —
        # the streaming k-way merge is the MergeOperator's job upstream)
        self._memory = memory_context
        self._spiller = None
        self._in_finish = False
        # cross-thread revocation (see HashAggregationOperator) serializes
        # all buffered-state mutation on this lock
        self._state_lock = named_lock("SortOperator._state_lock")
        if self._memory is not None:
            self._memory.set_revoker(self._revoke_memory)

    def add_input(self, batch: RelBatch) -> None:
        with self._state_lock:
            self._inputs.append(batch)
        self._track_memory()

    def _track_memory(self) -> None:
        """Bounds ACCUMULATION memory; the final sort materializes the
        output batch outside the accounted state (same exemption as the
        aggregation finish — see HashAggregationOperator._track_memory)."""
        if self._memory is None:
            return
        from trino_tpu.runtime.memory import batch_bytes

        total = sum(batch_bytes(b) for b in self._inputs)
        try:
            self._memory.set_bytes(total)
        except Exception:
            if not self._inputs:
                raise
            self._revoke_memory()
            return
        self._memory.set_revocable_bytes(total)

    def _revoke_memory(self) -> None:
        with self._state_lock:
            if not self._inputs or self._in_finish:
                return
            if self._spiller is None:
                from trino_tpu.exec.spill import FileSpiller

                self._spiller = FileSpiller()
            run = self._sorted(tuple(self._inputs)).compact()
            self._spiller.spill(run)
            self._inputs = []
        self._track_memory()

    def _sorted(self, parts: tuple) -> RelBatch:
        if self._pre is not None:
            return _concat_sort_pre(parts, tuple(self._keys), self._pre)
        return _concat_sort(parts, tuple(self._keys))

    def finish(self) -> None:
        if self._finishing:
            return
        self._finishing = True
        with self._state_lock:
            self._in_finish = True
            batches = list(self._inputs)
            self._inputs = []
            spiller, self._spiller = self._spiller, None
        if spiller is not None:
            # spilled runs already passed the fused pre stage; fold the
            # remaining raw inputs first, then merge runs un-prefixed
            folded = [self._sorted(tuple(batches))] if batches else []
            folded.extend(spiller.unspill())
            spiller.close()
            self._out = _concat_sort(tuple(folded), tuple(self._keys))
        elif batches:
            self._out = self._sorted(tuple(batches))
        else:
            # no input at all: emit the (post-pre) empty schema directly
            self._out = _concat_sort(
                (empty_batch(self._schema),), tuple(self._keys)
            )
        if self._memory is not None:
            self._memory.set_bytes(0)
            self._memory.set_revocable_bytes(0)

    def get_output(self) -> Optional[RelBatch]:
        out, self._out = self._out, None
        return out

    def is_finished(self) -> bool:
        return self._finishing and self._out is None


class TopNOperator(Operator):
    """ORDER BY + LIMIT n with a bounded device reservoir
    (TopNOperator.java:35)."""

    def __init__(self, keys: Sequence[SortKey], n: int,
                 input_schema: Sequence[Tuple[T.DataType, Optional[Dictionary]]],
                 pre_fn=None):
        self._keys = list(keys)
        self._n = n
        self._schema = list(input_schema)
        self._pre = pre_fn
        self._reservoir: Optional[RelBatch] = None
        self._out: Optional[RelBatch] = None

    def add_input(self, batch: RelBatch) -> None:
        if self._pre is not None:
            # fused into the same program as the reservoir merge below
            # only when shapes allow; one extra launch is still bounded
            batch = self._pre(batch)
        parts = (
            (batch,)
            if self._reservoir is None
            else (self._reservoir, batch)
        )
        cap = bucket_capacity(min(self._n, sum(p.capacity for p in parts)))
        self._reservoir = _topn_merge(parts, tuple(self._keys), self._n, cap)

    def finish(self) -> None:
        if self._finishing:
            return
        self._finishing = True
        self._out = (
            self._reservoir
            if self._reservoir is not None
            else empty_batch(self._schema)
        )

    def get_output(self) -> Optional[RelBatch]:
        out, self._out = self._out, None
        return out

    def is_finished(self) -> bool:
        return self._finishing and self._out is None


# ---------------------------------------------------------------------------
# Window functions
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=("partition_channels", "order_keys", "functions", "frame"),
)
def _window_compute(
    batch: RelBatch,
    partition_channels: tuple,
    order_keys: tuple,
    functions: tuple,  # (kind, arg_channel, out_dtype_str, offset, arg_scale_factor, out_is_float)
    frame: str,
):
    """One device program computing every window column over the sorted
    batch (the whole WindowOperator inner loop as segmented scans —
    ops/window.py). Traced under jit by the operator."""
    from trino_tpu.ops import window as W

    live = batch.live_mask()
    n = batch.capacity
    part_cols = [batch.columns[c] for c in partition_channels]
    key_data = [c.data for c in part_cols]
    key_valids = [c.valid for c in part_cols]
    descending = [False] * len(part_cols)
    nulls_first = [False] * len(part_cols)
    for k in order_keys:
        col = batch.columns[k.channel]
        key_data.append(col.data)
        key_valids.append(col.valid)
        descending.append(k.descending)
        nulls_first.append(k.nulls_first)
    order = (
        sort_order(key_data, key_valids, descending, nulls_first, live)
        if key_data
        else jnp.argsort(~live, stable=True)
    )
    s_live = take_clip(live, order)
    s_cols = [c.gather(order) for c in batch.columns]

    # partition boundaries (dead tail isolated as its own segment)
    part_inputs = [take_clip(d, order) for d in key_data[: len(part_cols)]]
    part_vmasks = [
        None if v is None else take_clip(v, order)
        for v in key_valids[: len(part_cols)]
    ]
    part_start = W.segment_starts(
        part_inputs + [s_live], part_vmasks + [None], n
    )
    peer_inputs = [
        take_clip(batch.columns[k.channel].data, order) for k in order_keys
    ]
    peer_vmasks = [
        None
        if batch.columns[k.channel].valid is None
        else take_clip(batch.columns[k.channel].valid, order)
        for k in order_keys
    ]
    peer_start = part_start | W.segment_starts(peer_inputs, peer_vmasks, n) if peer_inputs else part_start

    out_cols = []
    for kind, arg_ch, out_dt, offset, arg_sf, out_float, out_sf, out_lanes in functions:
        out_dtype = np.dtype(out_dt)
        if kind == "row_number":
            out_cols.append((W.row_number(part_start).astype(out_dtype), None))
        elif kind == "rank":
            out_cols.append((W.rank(part_start, peer_start).astype(out_dtype), None))
        elif kind == "dense_rank":
            out_cols.append((W.dense_rank(part_start, peer_start).astype(out_dtype), None))
        elif kind == "percent_rank":
            out_cols.append((W.percent_rank(part_start, peer_start).astype(out_dtype), None))
        elif kind == "cume_dist":
            out_cols.append((W.cume_dist(part_start, peer_start).astype(out_dtype), None))
        elif kind == "ntile":
            out_cols.append((W.ntile(offset, part_start).astype(out_dtype), None))
        elif kind in ("lead", "lag"):
            col = s_cols[arg_ch]
            off = offset if kind == "lag" else -offset
            data, valid = W.shift_in_partition(col.data, col.valid, part_start, off)
            out_cols.append((data, valid & s_live))
        elif kind == "first_value":
            col = s_cols[arg_ch]
            data, valid = W.first_value(col.data, col.valid, part_start)
            out_cols.append((data, valid))
        elif kind == "last_value":
            col = s_cols[arg_ch]
            data, valid = W.last_value(col.data, col.valid, part_start, peer_start, frame)
            out_cols.append((data, valid))
        elif kind == "nth_value":
            col = s_cols[arg_ch]
            data, valid = W.nth_value(
                col.data, col.valid, part_start, peer_start, frame, offset
            )
            out_cols.append((data, valid & s_live if valid is not None else None))
        elif kind in ("count", "count_star"):
            if arg_ch is None:
                vals, valid = None, None
            else:
                vals, valid = s_cols[arg_ch].data, s_cols[arg_ch].valid
            v, _ = W.windowed_agg("count", vals, valid, s_live, part_start, peer_start, frame, 0)
            out_cols.append((v.astype(out_dtype), None))
        elif kind in ("sum", "avg", "min", "max"):
            col = s_cols[arg_ch]
            if getattr(col.data, "ndim", 1) == 2:
                raise NotImplementedError(
                    "window aggregates over decimal(>18) arguments"
                )
            if kind in ("min", "max"):
                vals = col.data
                neutral = minmax_neutral(col.data.dtype, kind)
            else:
                acc_dt = (
                    jnp.float64
                    if jnp.issubdtype(col.data.dtype, jnp.floating)
                    else jnp.int64
                )
                vals = col.data.astype(acc_dt)
                neutral = 0
            v, cnt = W.windowed_agg(kind, vals, col.valid, s_live, part_start, peer_start, frame, neutral)
            has = cnt > 0
            if kind == "avg":
                q = v.astype(jnp.float64) / jnp.maximum(cnt, 1) / arg_sf
                if out_sf is not None:
                    # decimal avg: at the output's scale, rounded half
                    # away from zero, in integers as _agg_output's is
                    # (the chip's float64 quotient lands under a half)
                    q = _decimal_avg(v, cnt, arg_sf, out_sf)
                out_cols.append((q.astype(out_dtype), has))
            elif kind == "sum" and out_float:
                out_cols.append(((v / arg_sf).astype(out_dtype), has))
            else:
                safe = jnp.where(has, v, jnp.zeros((), v.dtype))
                if out_lanes == 2:
                    # sum(decimal) -> decimal(38,s): widen the int64
                    # accumulator into limb pairs (same contract as
                    # _agg_output's short-input long-output sum)
                    from trino_tpu.ops import int128 as I128

                    h, lo = I128.from_i64(safe.astype(jnp.int64))
                    out_cols.append((jnp.stack([h, lo], axis=-1), has))
                else:
                    out_cols.append((safe.astype(out_dtype), has))
        else:
            raise NotImplementedError(f"window function {kind}")
    return s_cols, s_live, out_cols


def window_fn_tuples(specs, schema) -> tuple:
    """Static per-function tuples for the jitted window kernel —
    shared by WindowOperator and the mesh fragment compiler."""
    fns = []
    for s in specs:
        # decimal args are int64 at the arg scale; divide only when
        # the OUTPUT leaves the scaled domain (avg -> DOUBLE, float
        # sums). Decimal sum/min/max keep the arg scale unchanged.
        arg_sf = 1
        out_float = s.out_type.is_floating
        # decimal OUTPUT scale factor: avg over decimal re-scales its
        # float quotient back into the output's scaled-int64 domain
        out_sf = (
            T.decimal_scale_factor(s.out_type)
            if s.out_type.is_decimal
            else None
        )
        if s.arg_channel is not None:
            arg_t = schema[s.arg_channel][0]
            if arg_t.is_decimal and (s.kind == "avg" or out_float):
                arg_sf = T.decimal_scale_factor(arg_t)
        fns.append(
            (s.kind, s.arg_channel, s.out_type.dtype.str, s.offset,
             arg_sf, out_float, out_sf, s.out_type.lanes)
        )
    return tuple(fns)


class WindowOperator(Operator):
    """Blocking window evaluation (WindowOperator.java:69): consume all
    input, sort once by (partition, order), emit child columns + window
    results in sorted order."""

    def __init__(
        self,
        partition_channels: Sequence[int],
        order_keys: Sequence[SortKey],
        functions: Sequence,  # plan.WindowFuncSpec
        frame: str,
        input_schema: Sequence[Tuple[T.DataType, Optional[Dictionary]]],
    ):
        self._partition = tuple(partition_channels)
        self._order = tuple(order_keys)
        self._specs = list(functions)
        self._frame = frame
        self._schema = list(input_schema)
        self._inputs: List[RelBatch] = []
        self._out: Optional[RelBatch] = None
        self._fns = window_fn_tuples(self._specs, self._schema)

    def add_input(self, batch: RelBatch) -> None:
        self._inputs.append(batch)

    def finish(self) -> None:
        if self._finishing:
            return
        self._finishing = True
        parts = self._inputs or [empty_batch(self._schema)]
        merged = concat_batches(parts)
        self._inputs = []
        s_cols, s_live, out_cols = _window_compute(
            merged, self._partition, self._order, self._fns, self._frame
        )
        cols = list(s_cols)
        for spec, (data, valid) in zip(self._specs, out_cols):
            d = None
            if spec.arg_channel is not None and spec.kind in (
                "lead", "lag", "first_value", "last_value", "nth_value",
                "min", "max"
            ):
                d = s_cols[spec.arg_channel].dictionary
            cols.append(Column(spec.out_type, data, valid, d))
        self._out = RelBatch(cols, s_live)

    def get_output(self) -> Optional[RelBatch]:
        out, self._out = self._out, None
        return out

    def is_finished(self) -> bool:
        return self._finishing and self._out is None


# ---------------------------------------------------------------------------
# Hash aggregation
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregate: kind in {sum,count,count_star,avg,min,max,any} or
    the holistic kinds {min_by,max_by,approx_percentile} (which need the
    raw rows, not mergeable accumulators — the planner forces them
    single-step); arg_channel indexes the operator's input (None for
    count_star), out_type is the SQL result type. The holistic set
    below (HOLISTIC_KINDS) is the single source of truth the fragmenter
    gates single-step planning on."""

    kind: str
    arg_channel: Optional[int]
    out_type: T.DataType
    distinct: bool = False
    arg2_channel: Optional[int] = None
    percentile: Optional[float] = None
    separator: Optional[str] = None  # listagg
    arg3_channel: Optional[int] = None  # pctl_merge bucket-max channel
    param: Optional[float] = None  # numeric_histogram/approx_most_frequent b
    post: Optional[str] = None  # fused sketch accessor: card | vq | qv


# pctl_merge is the bounded MERGE half of the mergeable approx_percentile
# (sql/optimizer.RewriteApproxPercentile): it buffers quantile-bucket
# summaries, never raw rows. approx_distinct / approx_percentile appear
# here only as the enable_optimizer=False fallback.
# r4 collect-path aggregates: per-group containers are assembled
# host-side from the device's group-contiguous row order (the
# reference's ArrayAggregationFunction and MapAggregationFunction
# likewise build their Blocks on the heap). Finalized by
# _collect_column.
_COLLECT_KINDS = (
    "array_agg", "map_agg", "multimap_agg", "histogram",
    "numeric_histogram", "approx_most_frequent", "map_union",
    "bitwise_and_agg", "bitwise_or_agg", "bitwise_xor_agg",
    # sketch builders (expr/pyfns digests on the varchar carrier)
    "approx_set", "tdigest_agg", "sketch_merge",
)

HOLISTIC_KINDS = (
    "min_by", "max_by", "approx_percentile", "listagg", "approx_distinct",
    "pctl_merge",
) + _COLLECT_KINDS


def _bht_histogram(vals, b: int):
    """Ben-Haim/Tom-Tov streaming histogram, batch form: merge the two
    closest centroids until <= b remain (the reference's
    NumericHistogram, operator/aggregation/NumericHistogramAggregation).
    Returns {centroid: weight} or None for empty input."""
    if not vals or b <= 0:
        return None
    pts: List[List[float]] = []
    for v in sorted(float(x) for x in vals):
        if pts and pts[-1][0] == v:
            pts[-1][1] += 1.0
        else:
            pts.append([v, 1.0])
    while len(pts) > b:
        bi, bgap = 0, float("inf")
        for i in range(len(pts) - 1):
            gap = pts[i + 1][0] - pts[i][0]
            if gap < bgap:
                bi, bgap = i, gap
        (v1, c1), (v2, c2) = pts[bi], pts[bi + 1]
        pts[bi] = [(v1 * c1 + v2 * c2) / (c1 + c2), c1 + c2]
        del pts[bi + 1]
    return {v: c for v, c in pts}


def minmax_neutral(dtype, kind: str):
    """Identity element for min/max accumulators: the single source of
    truth shared by every aggregation path (batch init, global fold,
    partial-state merge) — keep these in sync or partial->final
    aggregation silently diverges from single-step."""
    if jnp.issubdtype(np.dtype(dtype), np.floating):
        return np.inf if kind == "min" else -np.inf
    if np.dtype(dtype) == np.bool_:
        return kind == "min"
    info = np.iinfo(np.dtype(dtype))
    return info.max if kind == "min" else info.min


def _agg_state_init(spec: AggSpec, arg_dtype, capacity: int):
    """(value_state, count_state) arrays of shape (capacity,)."""
    if spec.kind in ("count", "count_star"):
        return (jnp.zeros(capacity, dtype=jnp.int64),)
    if spec.kind in ("sum", "avg"):
        acc_dt = jnp.float64 if np.issubdtype(arg_dtype, np.floating) else jnp.int64
        return (
            jnp.zeros(capacity, dtype=acc_dt),
            jnp.zeros(capacity, dtype=jnp.int64),
        )
    if spec.kind in ("min", "max"):
        return (
            jnp.full(capacity, minmax_neutral(arg_dtype, spec.kind), dtype=arg_dtype),
            jnp.zeros(capacity, dtype=jnp.int64),
        )
    if spec.kind == "any":
        return (
            jnp.zeros(capacity, dtype=arg_dtype),
            jnp.zeros(capacity, dtype=jnp.int64),
        )
    raise NotImplementedError(spec.kind)


def _agg_state_update(spec: AggSpec, state, gid, data, valid, live, capacity):
    """Scatter one batch into the running state. gid == capacity drops."""
    weight = live if valid is None else (live & valid)
    idx = jnp.where(weight, gid, capacity)
    if spec.kind in ("count", "count_star"):
        (cnt,) = state
        return (cnt.at[idx].add(1, mode="drop"),)
    if spec.kind in ("sum", "avg"):
        acc, cnt = state
        return (
            acc.at[idx].add(data.astype(acc.dtype), mode="drop"),
            cnt.at[idx].add(1, mode="drop"),
        )
    if spec.kind in ("min", "max"):
        acc, cnt = state
        op = acc.at[idx].min if spec.kind == "min" else acc.at[idx].max
        return (op(data, mode="drop"), cnt.at[idx].add(1, mode="drop"))
    if spec.kind == "any":
        acc, cnt = state
        first = cnt == 0
        upd = acc.at[idx].set(data, mode="drop")
        return (jnp.where(first, upd, acc), cnt.at[idx].add(1, mode="drop"))
    raise NotImplementedError(spec.kind)


def _agg_state_migrate(spec: AggSpec, arg_dtype, state, remap, new_capacity):
    """Move accumulator state through a table rebuild: new[remap[i]] = old[i].
    Fresh slots must hold the same identity element as _agg_state_init
    (min/max extremes, not zero)."""
    fresh = _agg_state_init(spec, arg_dtype, new_capacity)
    return tuple(
        f.at[remap].set(arr, mode="drop") for f, arr in zip(fresh, state)
    )


def _agg_output(spec: AggSpec, state, arg_type: Optional[T.DataType],
                arg_dict: Optional[Dictionary]) -> Column:
    """Finalize a state into the SQL result column. Decimal accumulators
    hold scaled int64 at the ARG's scale; rescale to the output type."""
    out_t = spec.out_type
    if spec.kind in ("count", "count_star"):
        (cnt,) = state
        return Column(out_t, cnt.astype(jnp.int64), None, None)
    if len(state) == 3:
        # Int128 limb-join state (sum/avg over a long-decimal arg)
        from trino_tpu.ops import int128 as I128

        h, lo, cnt = state
        has = cnt > 0
        if spec.kind in ("min", "max", "any"):
            return Column(
                out_t, jnp.stack([h, lo], axis=-1), has, arg_dict
            )
        if spec.kind == "avg":
            h, lo = I128.div_round_i64(
                h, lo, jnp.maximum(cnt, 1).astype(jnp.int64)
            )
        arg_s = arg_type.scale or 0
        out_s = out_t.scale or 0
        if out_s > arg_s:
            h, lo = I128.rescale_up(h, lo, out_s - arg_s)
        elif arg_s > out_s:
            h, lo = I128.rescale_down_round(h, lo, arg_s - out_s)
        if out_t.is_long_decimal:
            return Column(out_t, jnp.stack([h, lo], axis=-1), has, None)
        x, _ = I128.to_i64(h, lo)
        return Column(out_t, x.astype(out_t.dtype), has, None)
    acc, cnt = state
    has = cnt > 0
    arg_sf = (
        T.decimal_scale_factor(arg_type)
        if arg_type is not None and arg_type.is_decimal
        else 1
    )
    out_sf = T.decimal_scale_factor(out_t) if out_t.is_decimal else None
    if spec.kind == "sum":
        if out_t.is_floating:
            return Column(out_t, acc.astype(out_t.dtype) / arg_sf, has, None)
        if out_sf is not None and out_sf != arg_sf:
            acc = acc * (out_sf // arg_sf) if out_sf > arg_sf else acc // (arg_sf // out_sf)
        if out_t.is_long_decimal:
            # sum(decimal) -> decimal(38, s): the int64 accumulator
            # widens into limb pairs (exact while per-batch partials fit
            # int64; the limb-split accumulator is the extension point)
            from trino_tpu.ops import int128 as I128

            h, lo = I128.from_i64(acc.astype(jnp.int64))
            return Column(out_t, jnp.stack([h, lo], axis=-1), has, None)
        return Column(out_t, acc.astype(out_t.dtype), has, None)
    if spec.kind == "avg":
        if out_t.is_floating:
            q = acc.astype(jnp.float64) / jnp.maximum(cnt, 1)
            return Column(out_t, (q / arg_sf).astype(out_t.dtype), has, None)
        # decimal average: at the output scale, half away from zero, in
        # integers (_decimal_avg: the chip's float64 lands under a half)
        data = _decimal_avg(acc, cnt, arg_sf, out_sf).astype(out_t.dtype)
        return Column(out_t, data, has, None)
    if spec.kind in ("min", "max", "any"):
        safe = jnp.where(has, acc, jnp.zeros((), dtype=acc.dtype))
        if out_t.is_floating and arg_sf != 1:
            return Column(out_t, safe.astype(out_t.dtype) / arg_sf, has, None)
        return Column(out_t, safe.astype(out_t.dtype), has, arg_dict)
    raise NotImplementedError(spec.kind)


def agg_state_meta(
    spec: AggSpec,
    input_schema: Sequence[Tuple[T.DataType, "Optional[Dictionary]"]],
) -> List[Tuple[T.DataType, "Optional[Dictionary]"]]:
    """Wire schema of one aggregate's partial state: (value, count)
    columns. This is the accumulator-serialization contract between
    PARTIAL and FINAL aggregation steps (the analogue of Trino's
    aggregation state serialized to Blocks for partial->final,
    main/operator/aggregation/ — SURVEY.md §2.6)."""
    if spec.kind in ("count", "count_star"):
        return [(T.BIGINT, None), (T.BIGINT, None)]
    arg_t, arg_d = input_schema[spec.arg_channel]
    if spec.kind in ("sum", "avg"):
        if arg_t.is_long_decimal:
            # ONE (hi, lo) Int128 value column at the argument's scale:
            # per-state limb sums join into an exact Int128 before the
            # wire, and the final step limb-splits them again — so long
            # decimals ride any exchange as an ordinary (n, 2) column
            # (Int128ArrayBlock on the page wire, AddExchanges.java:140)
            return [
                (T.DataType(T.TypeKind.DECIMAL, 38, arg_t.scale), None),
                (T.BIGINT, None),
            ]
        if arg_t.is_floating:
            val_t = T.DOUBLE
        elif arg_t.is_decimal:
            val_t = T.DataType(T.TypeKind.DECIMAL, 18, arg_t.scale)
        else:
            val_t = T.BIGINT
        return [(val_t, None), (T.BIGINT, None)]
    # min/max/any carry the argument representation through the wire
    return [(arg_t, arg_d), (T.BIGINT, None)]


def partial_output_schema(
    aggs: Sequence[AggSpec],
    group_channels: Sequence[int],
    input_schema: Sequence[Tuple[T.DataType, "Optional[Dictionary]"]],
) -> List[Tuple[T.DataType, "Optional[Dictionary]"]]:
    """Schema of a PARTIAL aggregation's output batch:
    [group keys..., (value, count) per aggregate...]."""
    out = [input_schema[c] for c in group_channels]
    for a in aggs:
        out.extend(agg_state_meta(a, input_schema))
    return out


# -- Int128 sum accumulation (DecimalSumAggregation analogue) --------------
# A long-decimal (n, 2) argument cannot ride the 1-D sort-carry
# aggregation kernels, and a single int64 accumulator would overflow; it
# splits into FOUR 32-bit limb columns whose int64 sums are each exact
# for < 2^31 rows, recombined into (hi, lo) at finalize:
#   value = l0 + l1*2^32 + h0*2^64 + h1*2^96   (h1 signed, rest unsigned)

_LIMB_MASK = 0xFFFFFFFF


def _append_long_decimal_slots(a, col, live, values, vvalids, reds) -> None:
    """Value-slot assembly for an aggregate over a decimal(>18) (n, 2)
    column: count reads only validity; sum/avg limb-split into four
    exact int64 slots; min/max ride the coupled (hi, lo) lexicographic
    reducers; any picks both limbs at the same first row. Shared by the
    three ingest paths (per-batch, streaming, holistic)."""
    if a.kind == "count":
        values.append(live.astype(jnp.int64))
        vvalids.append(col.valid)
        reds.append("count")
        return
    if a.kind in ("min", "max"):
        values.extend([col.data[:, 0], col.data[:, 1]])
        vvalids.extend([col.valid, col.valid])
        reds.extend([f"{a.kind}128h", f"{a.kind}128l"])
        return
    if a.kind == "any":
        values.extend([col.data[:, 0], col.data[:, 1]])
        vvalids.extend([col.valid, col.valid])
        reds.extend(["first", "first"])
        return
    if a.kind not in ("sum", "avg"):
        raise NotImplementedError(
            f"{a.kind}() over decimal(>18) arguments"
        )
    for piece in _limb_split(col.data):
        values.append(piece)
        vvalids.append(col.valid)
        reds.append("sum")


def _agg_slot_count(spec: "AggSpec", arg_type: Optional[T.DataType]) -> int:
    """State (value, count) slot pairs one aggregate occupies."""
    if arg_type is None or not arg_type.is_long_decimal:
        return 1
    if spec.kind in ("sum", "avg"):
        return 4
    if spec.kind in ("min", "max", "any"):
        return 2
    return 1


def _value_slot_layout(aggs, arg_types):
    """Per value slot of the ingest paths (_agg_slot_count's layout):
    its batch reducer, the dtype of its values and the 8-bit limbs they
    can have. A long decimal's sum is four int64 limb slots of which the
    three low ones are under 2^32 by construction (_limb_split); its
    extremes are the coupled (hi, lo) reducers only the sort path has."""
    reds, dtypes, limbs = [], [], []
    for a, t in zip(aggs, arg_types):
        k = _agg_slot_count(a, t)
        red = _BATCH_REDUCER.get(a.kind)
        if k == 2 and a.kind in ("min", "max"):
            red = f"{a.kind}128"
        reds.extend([red] * k)
        wide = t is None or t.is_long_decimal
        dtypes.extend([np.dtype(np.int64) if wide else t.dtype] * k)
        limbs.extend((4, 4, 4, 8) if k == 4 else (8,) * k)
    return tuple(reds), tuple(dtypes), tuple(limbs)


def _mxu_word_layout(aggs, batch: RelBatch, vvalids) -> dict:
    """G.mxu_group_reduce's keyword arguments for one batch's value
    slots: what its word rows need not carry twice."""
    types = [None if a.arg_channel is None
             else batch.columns[a.arg_channel].type for a in aggs]
    return dict(value_limbs=_value_slot_layout(aggs, types)[2],
                valid_of=G.shared_valids(vvalids))


def _slots_to_state(spec: "AggSpec", arg_type: Optional[T.DataType],
                    vals, cnts, si: int):
    """One aggregate's finalize-ready state from its value/count slots
    starting at `si`. Returns (state, next_si) — the ONE slots->state
    switch shared by every finalize path (4 limb-sum slots join into an
    Int128; 2 slots ARE the (hi, lo) pair; count reads one slot)."""
    kslots = _agg_slot_count(spec, arg_type)
    if kslots == 4:
        h, lo = _limb_join(vals[si: si + 4])
        state = (h, lo, cnts[si])
    elif kslots == 2:
        state = (vals[si], vals[si + 1], cnts[si])
    elif spec.kind in ("count", "count_star"):
        state = (vals[si],)
    else:
        state = (vals[si], cnts[si])
    return state, si + kslots


def _slots_to_wire_column(spec: "AggSpec", arg_type: Optional[T.DataType],
                          vt, vd, vals, si: int):
    """One aggregate's wire-format VALUE column from its slots at `si`
    (the serialization half of _slots_to_state: partial emit and spill
    share it on both data planes). Returns (column, next_si)."""
    kslots = _agg_slot_count(spec, arg_type)
    if kslots == 4:
        h, lo = _limb_join(vals[si: si + 4])
        col = Column(vt, jnp.stack([h, lo], axis=-1), None, vd)
    elif kslots == 2:
        col = Column(
            vt, jnp.stack([vals[si], vals[si + 1]], axis=-1), None, vd
        )
    else:
        col = Column(vt, vals[si].astype(vt.dtype), None, vd)
    return col, si + kslots


def _slot_merge_reducers(spec: "AggSpec", arg_type: Optional[T.DataType]):
    """Per-slot reducers for MERGING two group states of one aggregate
    (the _MERGE_REDUCER analogue at slot granularity: long-decimal sums
    merge as four limb sums, extremes as the coupled (hi, lo) pair)."""
    if arg_type is not None and arg_type.is_long_decimal:
        if spec.kind in ("sum", "avg"):
            return ["sum"] * 4
        if spec.kind in ("min", "max"):
            return [f"{spec.kind}128h", f"{spec.kind}128l"]
        if spec.kind == "any":
            return ["first", "first"]
    return [_MERGE_REDUCER[spec.kind]]


def _limb_split(d: jnp.ndarray) -> List[jnp.ndarray]:
    h, lo = d[:, 0], d[:, 1]
    m = jnp.int64(_LIMB_MASK)
    return [
        lo & m,
        (lo >> jnp.int64(32)) & m,
        h & m,
        h >> jnp.int64(32),
    ]


def _lex128_reduce(h, lo, w, kind: str):
    """Masked whole-array Int128 min/max over (hi, lo) rows: signed hi
    first, then unsigned lo among rows holding the winning hi
    (Int128Math.compare's lexicographic order, vectorized)."""
    big = jnp.iinfo(jnp.int64).max
    sgn = jnp.int64(-0x8000000000000000)
    lo_u = lo ^ sgn
    if kind == "min":
        m1 = jnp.min(jnp.where(w, h, big))
        m2 = jnp.min(jnp.where(w & (h == m1), lo_u, big)) ^ sgn
    else:
        m1 = jnp.max(jnp.where(w, h, -big - 1))
        m2 = jnp.max(jnp.where(w & (h == m1), lo_u, -big - 1)) ^ sgn
    return m1, m2


def _limb_join(sums: Sequence[jnp.ndarray]):
    """Four limb-sum arrays -> (hi, lo) Int128."""
    from trino_tpu.ops import int128 as I128

    h, lo = I128.from_i64(sums[3].astype(jnp.int64))
    for s in (sums[2], sums[1], sums[0]):
        h, lo = I128.mul_128_64(h, lo, jnp.int64(1 << 32))
        ah, al = I128.from_i64(s.astype(jnp.int64))
        h, lo = I128.add(h, lo, ah, al)
    return h, lo


_BATCH_REDUCER = {"sum": "sum", "avg": "sum", "count": "count",
                  "count_star": "count", "min": "min", "max": "max",
                  "any": "first"}
# merging two partial states: counts add, mins min, firsts keep-first
_MERGE_REDUCER = {"sum": "sum", "avg": "sum", "count": "sum",
                  "count_star": "sum", "min": "min", "max": "max",
                  "any": "first"}

def _resort_states(states: tuple, reducers: tuple, out_capacity: int):
    """N (keys, valids, used, vals, cnts) group-state sets as one:
    concatenated and group-reduced again, whatever order their slots are
    in."""
    n_keys = len(states[0][0])
    keys = [
        jnp.concatenate([s[0][i] for s in states]) for i in range(n_keys)
    ]
    valids = [
        jnp.concatenate([s[1][i] for s in states]) for i in range(n_keys)
    ]
    mask = jnp.concatenate([s[2] for s in states])
    values, vvalids, reds = [], [], []
    for i, mred in enumerate(reducers):
        v = jnp.concatenate([s[3][i] for s in states])
        c = jnp.concatenate([s[4][i] for s in states])
        values.append(v)
        vvalids.append((c > 0) if mred == "first" else None)
        reds.append(mred)
        values.append(c)
        vvalids.append(None)
        reds.append("sum")
    gk, gv, used, vals, _, ngroups, ovf = G.sort_group_reduce(
        keys, valids, mask, values, tuple(vvalids), tuple(reds), out_capacity
    )
    return (
        (tuple(gk), tuple(gv), used, tuple(vals[0::2]), tuple(vals[1::2])),
        ngroups,
        ovf,
    )


def _states_ascend(states: tuple):
    """Whether single-key states, taken in operand order, are one
    ascending run of groups: every state's `used` a dense prefix, its
    (class, key) pairs strictly ascending over that prefix (what the
    sort path's reduce leaves; a state off the wire may hold a key
    twice), and each state's first group not before the last group so
    far (empty states skipped). Read off the slots themselves, one pass
    over the keys. Returns (the answer, each state's group count, each
    state's `seam`: its first group IS the last one so far, an order
    whose rows straddled two batches)."""
    ok = jnp.bool_(True)
    counts, seams = [], []
    last = None  # (class, key) of the last group so far
    for (key,), (valid,), used, _, _ in states:
        n = jnp.sum(used.astype(jnp.int32))
        cls, kb = G.class_and_key(key, valid, used)
        ok &= jnp.all(used == (jnp.arange(used.shape[0]) < n))
        ok &= jnp.all(G.ascends(cls, kb, strict=True) | ~used[1:])
        holds = n > 0
        first = cls[0], kb[0]
        end = jnp.maximum(n - 1, 0)
        if last is None:
            seams.append(jnp.bool_(False))
            last = cls[end], kb[end]
            some = holds
        else:
            ok &= ~(holds & some & G.pair_lt(*first, *last))
            seams.append(holds & some & ~G.pair_lt(*last, *first))
            last = tuple(
                jnp.where(holds, a[end], b) for a, b in zip((cls, kb), last)
            )
            some |= holds
        counts.append(n)
    # a seam is read only where `ok` holds, and there "not after" is "equal"
    return ok, counts, seams


def _fold_seam(reducers: tuple, a_vals, a_cnts, b_vals, b_cnts):
    """One group's value slots out of two states as one state's: slot
    by slot the merge reducer (sums add; min and max keep the extreme of
    the sides that counted a row, as _agg_ingest_train's fold does, a
    128-bit one as its (hi, lo) pair; first keeps the earlier state's
    where that counted one). Counts add."""
    from trino_tpu.ops import int128 as I128

    vals = []
    for i, red in enumerate(reducers):
        a, b, ac, bc = a_vals[i], b_vals[i], a_cnts[i], b_cnts[i]
        if red == "sum":
            vals.append(a + b)
        elif red in ("min", "max"):
            best = jnp.minimum(a, b) if red == "min" else jnp.maximum(a, b)
            vals.append(jnp.where(ac == 0, b, jnp.where(bc == 0, a, best)))
        elif red == "first":
            vals.append(jnp.where(ac > 0, a, b))
        elif red in ("min128h", "max128h"):
            pair_a, pair_b = (a, a_vals[i + 1]), (b, b_vals[i + 1])
            better = (I128.lt(*pair_b, *pair_a) if red == "min128h"
                      else I128.lt(*pair_a, *pair_b))
            take_b = (ac == 0) | ((bc > 0) & better)
            vals.append(jnp.where(take_b, b, a))
            vals.append(jnp.where(take_b, b_vals[i + 1], a_vals[i + 1]))
        elif red not in ("min128l", "max128l"):  # those came with their hi
            raise ValueError(red)
    return vals, [ac + bc for ac, bc in zip(a_cnts, b_cnts)]


def _lay_end_to_end(states: tuple, counts, seams, reducers: tuple,
                    out_capacity: int, like):
    """The merge of states that _states_ascend passed: state after state
    written at a running end offset, each over the dead tail of the one
    before, and where a state begins with the group the last one ended
    with (`seams`), that one group folded and the state written one slot
    earlier. `like` gives the merged state's dtypes. A copy and no sort.

    dynamic_update_slice clamps a start so that the update fits, and the
    table may be smaller than the last offset plus a state's capacity,
    so the buffers are the table plus the widest state and are cut to
    the table at the end."""
    width = out_capacity + max(s[2].shape[0] for s in states)
    (like_key,), _, _, like_vals, like_cnts = like
    key = jnp.zeros(width, like_key.dtype)
    valid = jnp.zeros(width, jnp.bool_)
    vals = [jnp.zeros(width, v.dtype) for v in like_vals]
    cnts = [jnp.zeros(width, c.dtype) for c in like_cnts]
    end = jnp.int32(0)

    def put(buf, arr, at):
        return jax.lax.dynamic_update_slice(buf, arr.astype(buf.dtype), (at,))

    for ((s_key,), (s_valid,), _, s_vals, s_cnts), n, seam in zip(
            states, counts, seams):
        at = end - seam.astype(jnp.int32)
        heads = [v[0].astype(b.dtype) for v, b in zip(s_vals, vals)]
        head_cnts = [c[0].astype(b.dtype) for c, b in zip(s_cnts, cnts)]
        folded, folded_cnts = _fold_seam(
            reducers, [v[at] for v in vals], [c[at] for c in cnts],
            heads, head_cnts,
        )
        key, valid = put(key, s_key, at), put(valid, s_valid, at)
        for bufs, arrs, whole, own in ((vals, s_vals, folded, heads),
                                       (cnts, s_cnts, folded_cnts, head_cnts)):
            for i, arr in enumerate(arrs):
                bufs[i] = put(bufs[i], arr, at)
                bufs[i] = put(
                    bufs[i], jnp.where(seam, whole[i], own[i])[None], at
                )
        end = at + n
    used = jnp.arange(out_capacity, dtype=jnp.int32) < end

    def cut(buf):
        return jnp.where(used, buf[:out_capacity], jnp.zeros((), buf.dtype))

    return (
        ((cut(key),), (cut(valid),), used,
         tuple(cut(v) for v in vals), tuple(cut(c) for c in cnts)),
        end,
        end > out_capacity,
    )


# The TPU compiler gives a 64-bit reduce-window (the reduce's scans) 19 MB
# of scoped VMEM inside a conditional's branch, 64 MB where it splits a
# long 1-D scan itself, and refuses both at its limit of 16 MB ("it
# should not be possible to run out of scoped vmem"); outside a branch
# the same scans fit. The chip has 128 MB.
_MERGE_COMPILER_OPTIONS = tpu_compiler_options(
    {"xla_tpu_scoped_vmem_limit_kib": 96 * 1024}
)


def _merge_read_states(states: tuple, reducers: tuple, out_capacity: int):
    """_merge_group_states' body over the states as they are read."""
    if len(states[0][0]) != 1:
        return _resort_states(states, reducers, out_capacity)
    ordered, counts, seams = _states_ascend(states)
    like = jax.eval_shape(
        lambda s: _resort_states(s, reducers, out_capacity), states
    )[0]
    merged, ngroups, ovf = jax.lax.cond(
        ordered,
        lambda: _lay_end_to_end(
            states, counts, seams, reducers, out_capacity, like),
        lambda: _resort_states(states, reducers, out_capacity),
    )
    return merged, ngroups, G.flag_word(ovf, ordered)


@partial(jax.jit, static_argnames=("reducers", "out_capacity", "takes"),
         compiler_options=_MERGE_COMPILER_OPTIONS)
def _merge_group_states(states: tuple, reducers: tuple, out_capacity: int,
                        takes: Optional[tuple] = None):
    """N (keys, valids, used, vals, cnts) group-state sets merged into
    one — the whole N-way merge is ONE device program (per-batch
    pairwise merges would cost a program launch each). States come out
    of the sort path dense and ascending in their key, and a scan of a
    table clustered on that key hands them over in ascending ranges
    that meet in at most one group a seam: single-key states are looked
    at first (_states_ascend), and where that is what they are, laid
    end to end (_lay_end_to_end); otherwise, and for several keys
    (hash order) always, concatenated and group-reduced again
    (_resort_states). The flag of a single-key merge is a G.flag_word:
    overflow, and which of the two it did.

    `takes`, one entry a state: the slots of it to read, from slot 0
    (None: all of them). The caller knows the state dense from slot 0
    and how many groups it holds, so what lies behind is empty and is
    kept out of the sorts. A used slot behind a cut raises the overflow
    bit: the caller then merges the states whole."""
    if takes is None:
        return _merge_read_states(states, reducers, out_capacity)
    dropped = jnp.bool_(False)
    read = []
    for state, take in zip(states, takes):
        if take is not None:
            dropped |= jnp.any(state[2][take:])
            state = jax.tree_util.tree_map(lambda a: a[:take], state)
        read.append(state)
    merged, ngroups, flag = _merge_read_states(
        tuple(read), reducers, out_capacity)
    return merged, ngroups, flag | dropped


@partial(jax.jit, static_argnames=("capacity",))
def _pad_state(state: tuple, capacity: int):
    """A group state with unused slots appended up to `capacity`."""
    return jax.tree_util.tree_map(
        lambda a: jnp.pad(a, (0, capacity - a.shape[0])), state
    )


@jax.jit
def _empty_state(state: tuple):
    """A group state of `state`'s shape with no slot used."""
    return jax.tree_util.tree_map(jnp.zeros_like, state)


def _common_capacity(states: list) -> list:
    """The states of one tier at the largest capacity among them: the
    merge programs of a long scan then differ by how many states they
    take, not by which of them came out of a smaller batch."""
    top = max(int(s[2].shape[0]) for s in states)
    return [
        s if int(s[2].shape[0]) == top else _pad_state(s, top) for s in states
    ]


@jax.jit
def _any_flags(flags: tuple):
    return jnp.any(jnp.stack(flags))


@jax.jit
def _overflow_bit(word):
    """The overflow flag of a G.flag_word."""
    return (word & 1) != 0


def _ingest_batch(batch: RelBatch, groups: tuple, aggs: tuple, cap: int, pre_fn,
                  dense_dims, mxu_dims, key_lows, slot_dims=None):
    """The per-batch body both ingest programs trace: the fused upstream
    filter/project, then one batch's group-reduce. Returns the reduce's
    7-tuple and the per-slot reducers it ran with."""
    if pre_fn is not None:
        batch = pre_fn(batch)
    keys, valids = [], []
    for c in groups:
        col = batch.columns[c]
        v = col.valid_mask()
        if getattr(col.data, "ndim", 1) == 2:
            # long-decimal key: group by its two int64 limbs (pair
            # equality == value equality; output reassembles them)
            keys.extend([col.data[:, 0], col.data[:, 1]])
            valids.extend([v, v])
        else:
            keys.append(col.data)
            valids.append(v)
    live = batch.live_mask()
    values, vvalids, reds = [], [], []
    for a in aggs:
        if a.arg_channel is None:
            values.append(live.astype(jnp.int64))
            vvalids.append(None)
        elif getattr(batch.columns[a.arg_channel].data, "ndim", 1) == 2:
            _append_long_decimal_slots(
                a, batch.columns[a.arg_channel], live, values, vvalids, reds
            )
            continue
        else:
            col = batch.columns[a.arg_channel]
            values.append(col.data)
            vvalids.append(col.valid)
        reds.append(_BATCH_REDUCER[a.kind])
    reds = tuple(reds)
    if dense_dims is not None:
        out = G.dense_group_reduce(
            keys, valids, live, values, tuple(vvalids), reds, dense_dims, cap,
            lows=key_lows,
        )
    elif mxu_dims is not None:
        out = G.mxu_group_reduce(
            keys, valids, live, values, tuple(vvalids), reds, mxu_dims, cap,
            lows=key_lows, **_mxu_word_layout(aggs, batch, vvalids),
        )
    elif slot_dims is not None:
        out = G.slot_group_reduce(
            keys, valids, live, values, tuple(vvalids), reds, slot_dims, cap,
            valid_of=G.shared_valids(vvalids), lows=key_lows,
        )
    else:
        out = G.sort_group_reduce(
            keys, valids, live, values, tuple(vvalids), reds, cap,
            check_order=True,
        )
    return out, reds


_INGEST_STATICS = ("groups", "aggs", "cap", "pre_fn", "dense_dims", "mxu_dims",
                   "key_lows", "slot_dims")


@partial(jax.jit, static_argnames=_INGEST_STATICS)
def _agg_ingest(batch: RelBatch, groups: tuple, aggs: tuple, cap: int, pre_fn,
                dense_dims=None, mxu_dims=None, key_lows=None, slot_dims=None):
    """Fused upstream filter/project + ONE batch's group-reduce in one
    device program (scan->filter->project->partial-aggregate is the Q1
    hot path; separate launches pay a host round trip each on
    remote-attached devices). Every path that may overflow its table
    launches this once per batch; where the plan bounds the table, whole
    trains of batches go through _agg_ingest_train and only a train of
    one comes here."""
    return _ingest_batch(
        batch, groups, aggs, cap, pre_fn, dense_dims, mxu_dims, key_lows,
        slot_dims,
    )[0]


# Group key types a plan-time value range can bound (a digit a value):
# not a decimal, not a float, not a long decimal's limb pair. The one
# list: sql/stats.group_key_ranges gives a range to no other key.
RANGE_KEY_KINDS = frozenset((
    T.TypeKind.TINYINT, T.TypeKind.SMALLINT, T.TypeKind.INTEGER,
    T.TypeKind.BIGINT, T.TypeKind.DATE,
))

# States one fold of the sort path merges (HashAggregationOperator.
# _fold_settled_locked). A scan of N batches leaves N group states; held
# until finish they are N operands of one program (a program per N, and
# every state resident until the last batch). Folding eight at a time
# keeps at most 8 states a tier pending, gives every fold the same arity
# whatever the scan's length, and costs one more pass over the states
# per tier (a copy where their key ranges ascend, a sort where they do
# not: _merge_group_states): 58 batches are 7 folds and one last merge
# of 7 folded states and 8 of what is left. A fold hands on its state,
# as wide as its operands' slots together and dense from slot 0, the
# group count it left on the device, and which of the two it did. The
# last merge, which reads the counts to size its table, reads a state
# that a fold RE-SORTED only up to the power of two that holds its
# groups: such states will be sorted again, slot for slot, and what
# lies behind their groups is empty. A fold that laid its states end to
# end is read whole: it will be laid end to end again, a copy, and a
# read that followed its count would give the merge a shape of its own
# for every count near a power of two.
FOLD_STATES = 8

# Batches one launch of _agg_ingest_train takes. A launch costs the host
# about as much as the device needs for two batches of 2^20 rows (PERF.md
# section 6, PR 26), so the train is long enough that the device is what
# a scan waits for and short enough that its first batch does not wait
# for a whole scan to be handed over.
TRAIN_BATCHES = 8


@partial(jax.jit, static_argnames=_INGEST_STATICS)
def _agg_ingest_train(batches: tuple, n, groups: tuple, aggs: tuple, cap: int,
                      pre_fn, dense_dims=None, mxu_dims=None, key_lows=None,
                      slot_dims=None):
    """The first `n` of `batches` (equal in layout; `n` is an operand, so
    a short train is this same program) through _agg_ingest's body, ONE
    launch and ONE group state for all of them. The body is traced once,
    inside a loop: each turn picks its batch out of the operands (a
    `conditional`, which copies it: a loop cannot index operands),
    reduces it and folds the result into the running state. Laying the
    batches side by side and slicing the turn's out costs the device
    more (PERF.md section 6, PR 26). Only the slot-addressed tables
    (dense_dims, mxu_dims, slot_dims) come here: slot g is the same group in every
    batch, so the fold is elementwise (counts and sums add, min/max keep
    the extreme of the batches that had a row) and the keys are any
    batch's."""
    assert (dense_dims, mxu_dims, slot_dims) != (None, None, None)
    flat = [jax.tree_util.tree_flatten(b) for b in batches]
    treedef = flat[0][1]
    picks = [lambda leaves=tuple(leaves): leaves for leaves, _ in flat]
    reds = []  # the body's per-slot reducers, known once it is traced

    def one(i):
        leaves = list(jax.lax.switch(i, picks))
        out, r = _ingest_batch(
            jax.tree_util.tree_unflatten(treedef, leaves),
            groups, aggs, cap, pre_fn, dense_dims, mxu_dims, key_lows,
            slot_dims,
        )
        reds[:] = r
        gk, gv, used, vals, cnts, _ngroups, ovf = out
        return tuple(gk), tuple(gv), used, tuple(vals), tuple(cnts), ovf

    def fold(i, acc):
        gk, gv, used, vals, cnts, ovf = one(i)
        _, _, a_used, a_vals, a_cnts, a_ovf = acc
        merged = []
        for red, v, c, av, ac in zip(reds, vals, cnts, a_vals, a_cnts):
            if red in ("sum", "count"):
                merged.append(av + v)
            else:
                best = jnp.minimum(av, v) if red == "min" else jnp.maximum(av, v)
                merged.append(jnp.where(ac == 0, v, jnp.where(c == 0, av, best)))
        return (gk, gv, a_used | used, tuple(merged),
                tuple(ac + c for ac, c in zip(a_cnts, cnts)), a_ovf | ovf)

    empty = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(one, 0)
    )
    assert all(r in ("sum", "count", "min", "max") for r in reds), reds
    gk, gv, used, vals, cnts, ovf = jax.lax.fori_loop(0, n, fold, empty)
    # the reduce's 7-tuple without its group count: a bounded table is
    # never grown, so nobody reads one (and an output costs the host)
    return gk, gv, used, vals, cnts, None, ovf


@jax.jit
def _add_slot_states(a: tuple, b: tuple):
    """Two states of ONE operator's slot-addressed count table
    (G.slot_group_reduce) as one: slot g is the same group in both, so
    `used` ORs and the counts add, slot for slot, as _agg_ingest_train's
    fold does inside a train, and the keys are either's. No sort: what
    _merge_group_states would do to them is sort every slot again."""
    gk, gv, a_used, a_vals, a_cnts = a
    _, _, b_used, b_vals, b_cnts = b
    return (gk, gv, a_used | b_used,
            tuple(x + y for x, y in zip(a_vals, b_vals)),
            tuple(x + y for x, y in zip(a_cnts, b_cnts)))


@partial(jax.jit, static_argnames=("aggs", "arg_types"))
def _finalize_grouped(acc, aggs: tuple, arg_types: tuple):
    """Whole grouped finalize as ONE device program (the eager
    per-aggregate finalize costs one host dispatch per op)."""
    gk, gv, used, vals, cnts = acc
    out = []
    si = 0
    for a, arg_t in zip(aggs, arg_types):
        state, si = _slots_to_state(a, arg_t, vals, cnts, si)
        col = _agg_output(a, state, arg_t, None)
        out.append((col.data, col.valid))
    return out


# Shared across concurrent query threads; the unlocked check-then-insert
# let two threads mint distinct jitted callables for the same spec
# (dispatch-cache churn on every later call). First build wins now.
_global_fn_lock = named_lock("operators._global_fn_lock")
_GLOBAL_FN_CACHE: Dict[Tuple[AggSpec, ...], object] = {}


def _global_update_fn(aggs: Tuple[AggSpec, ...], long_flags: tuple = ()):
    """Jitted whole-batch reduction for GROUP-BY-less aggregation —
    shared across instances (AccumulatorCompiler cache analogue).
    long_flags marks aggregates whose argument is a long decimal: their
    sum state is an Int128 (hi, lo) pair accumulated from limb sums."""
    if not long_flags:
        long_flags = (False,) * len(aggs)
    if (aggs, long_flags) not in _GLOBAL_FN_CACHE:

        @jax.jit
        def update(states, batch: RelBatch):
            from trino_tpu.ops import int128 as I128

            live = batch.live_mask()
            out = []
            for a, is_long, (val, cnt) in zip(aggs, long_flags, states):
                if a.arg_channel is None:
                    data, valid = live.astype(jnp.int64), None
                else:
                    col = batch.columns[a.arg_channel]
                    data, valid = col.data, col.valid
                w = live if valid is None else (live & valid)
                n = jnp.sum(w.astype(jnp.int64))
                if a.kind in ("count", "count_star"):
                    out.append((val + n, cnt + n))
                elif is_long and a.kind in ("sum", "avg"):
                    limb_sums = [
                        jnp.sum(jnp.where(w, piece, jnp.int64(0)))
                        for piece in _limb_split(data)
                    ]
                    bh, bl = _limb_join(limb_sums)
                    h, lo = I128.add(val[0], val[1], bh, bl)
                    out.append((jnp.stack([h, lo]), cnt + n))
                elif a.kind in ("sum", "avg"):
                    contrib = jnp.where(w, data.astype(val.dtype), 0)
                    out.append((val + jnp.sum(contrib), cnt + n))
                elif is_long and a.kind in ("min", "max"):
                    # lexicographic (hi, unsigned lo) batch reduce, then
                    # an Int128 compare against the running state
                    m1, m2 = _lex128_reduce(data[:, 0], data[:, 1], w, a.kind)
                    from trino_tpu.ops import int128 as I128x

                    better = I128x.lt(m1, m2, val[0], val[1])
                    if a.kind == "max":
                        better = I128x.lt(val[0], val[1], m1, m2)
                    better = better & (n > 0)
                    first = cnt == 0
                    take = (better | first) & (n > 0)
                    nh = jnp.where(take, m1, val[0])
                    nl = jnp.where(take, m2, val[1])
                    out.append((jnp.stack([nh, nl]), cnt + n))
                elif a.kind in ("min", "max"):
                    neutral = minmax_neutral(data.dtype, a.kind)
                    masked = jnp.where(w, data, jnp.asarray(neutral, data.dtype))
                    red = jnp.min(masked) if a.kind == "min" else jnp.max(masked)
                    op = jnp.minimum if a.kind == "min" else jnp.maximum
                    out.append((op(val, red.astype(val.dtype)), cnt + n))
                elif a.kind == "any":
                    first = data[jnp.argmax(w)]
                    new_val = jnp.where(
                        cnt > 0, val, jnp.where(jnp.any(w), first, val)
                    )
                    out.append((new_val, cnt + n))
                else:
                    raise NotImplementedError(a.kind)
            return out

        with _global_fn_lock:
            _GLOBAL_FN_CACHE.setdefault((aggs, long_flags), update)
    return _GLOBAL_FN_CACHE[(aggs, long_flags)]


class HashAggregationOperator(Operator):
    """GROUP BY + aggregates (HashAggregationOperator.java:53 +
    GroupByHash). The engine-path implementation is the SORT-BASED
    group-reduce (ops/groupby.sort_group_reduce) — XLA lowers scatters
    near-serially on TPU, so the linear-probe table is reserved for the
    mesh-exchange partials while this operator reduces each batch by
    sort + segmented scans and then merges per-batch group states
    (partial->final within one operator): by the same reduce over their
    concatenation, or, where single-key states hold ascending key
    ranges (a scan of a table clustered on the key), by laying them end
    to end. A batch that arrives in key order skips its key sort too.
    Both are read off the data on the device, and METRICS
    `agg_ordered_input.batches` (of `agg_ingest_path.sort`; the others
    `agg_unordered_input.batches`) and `agg_ordered_merge.launches` (of
    `agg_merge_launches`) count how often; `agg_merge_short_reads` and
    `agg_merge_slots_spared` count the states a last merge read only up
    to their groups and the slots it left unread (FOLD_STATES). Output
    schema = [group keys..., aggregate results...]; group rows come out
    dense.

    Launches: one per batch on the sort, global, holistic and `final`
    paths, whose batches may overflow a table or need the raw rows.
    Where the plan bounds the table and addresses it by slot (dictionary
    and boolean keys, integer keys of a known exact range: `_dense_dims`,
    `_mxu_dims`, `_slot_dims`, `_key_lows`), batches are held and
    go TRAIN_BATCHES at a time through one launch of _agg_ingest_train,
    which leaves one state; finish and revocation flush what is held.
    Four paths (`_path`, G.choose_bounded_reduce) and two limits: the
    dense and the MXU reduce up to G.MXU_MAX_SLOTS, for COUNTS the
    scatter-add (`slot`, G.slot_group_reduce) from there to
    G.SLOT_MAX_SLOTS where an integer range is among what bounds the
    table and to 2^16 where dictionaries and booleans alone do, the sort
    path for everything else. The slot path's trains leave states that
    are ADDED slot for slot (_add_slot_states, into `_slot_acc`): no
    merge program, no sort, whatever the scan's length; only a state
    that came off the wire or out of a spill, which is not addressed by
    slot, takes the sort merge there. METRICS `agg_ingest_batches` over
    `agg_ingest_launches` is the train length achieved;
    `agg_ingest_path.dense`, `.mxu`, `.slot` and `.sort` count the same
    batches by the reduce the plan got, and `agg_key_bound.range`,
    `.dictionary` and `.none` the operators by what bounded their
    table."""

    def __init__(
        self,
        group_channels: Sequence[int],
        aggregates: Sequence[AggSpec],
        input_schema: Sequence[Tuple[T.DataType, Optional[Dictionary]]],
        initial_capacity: int = 1024,
        step: str = "single",
        memory_context=None,
        deferred_checks: Optional[List] = None,
        pre_fn=None,
        key_ranges: Optional[Sequence[Optional[Tuple[int, int]]]] = None,
    ):
        """step: "single" (raw rows in, results out), "partial" (raw rows
        in, serialized accumulator state out) or "final" (accumulator
        state in, results out) — AggregationNode.Step analogue. In final
        mode the input layout is partial_output_schema's, whose state
        value columns carry each aggregate's original argument
        representation (decimal scale, dictionary) — finalization reads
        it straight from the input schema. key_ranges: per group
        channel, the EXACT (low, high) of its values where the plan
        knows one (sql/stats.group_key_ranges), else None."""
        assert step in ("single", "partial", "final"), step
        self._step = step
        self._pre = pre_fn  # fused upstream stage (plan-time jit)
        self._group_channels = list(group_channels)
        self._aggs = list(aggregates)
        self._schema = list(input_schema)
        self._global = not self._group_channels
        self._cap = initial_capacity
        # accumulated group state: (keys, valids, used, vals, cnts);
        # per-launch states (one a batch, or one a train of batches)
        # collect in _pending and merge in ONE N-way device program at
        # the next materialization point
        self._acc = None
        self._pending: List[tuple] = []
        # per pending state, what bounds its groups: the group count its
        # launch left on the device (read when a merge is sized), or
        # None where only the state's capacity does (trains, wire input)
        self._pending_groups: List = []
        self._acc_groups = None
        # sort path: tier t holds (state, group count, whether the fold
        # re-sorted) triples that t + 1 folds of FOLD_STATES states
        # produced (_fold_settled_locked)
        self._folded: List[List[tuple]] = []
        # merges launched / retried, states read short and the slots
        # that spared, under _state_lock, not yet in METRICS
        self._merges = [0, 0, 0, 0]
        # likewise the batches and the merges whose reduce found its
        # input in key order already (G.flag_word), and the batches
        # whose reduce did not (they paid their key sort)
        self._ordered = [0, 0, 0]
        # a state ingested off the wire (_add_state_input) may carry
        # DUPLICATE group keys within one batch (a spooled-stage replay
        # concatenates several producer pages into one values batch), so
        # it must pass through a group-reduce even when it is the only
        # pending state
        self._unreduced_state = False
        # deferred per-batch overflow records: (pending index, device
        # ovf flag, device ngroups, retained input batch, capacity)
        self._pending_meta: List[tuple] = []
        self._gstate = None
        self._out: Optional[RelBatch] = None
        # spill support (SpillableHashAggregationBuilder analogue):
        # revoke() serializes the group state in the partial wire format
        # and resets; finish() merges spilled state back via the same
        # FINAL-step machinery the distributed exchange uses.
        self._memory = memory_context
        self._spiller = None
        self._in_finish = False
        # holistic aggregates (min_by/max_by/approx_percentile) need the
        # raw rows: collect batches, reduce once at finish (the planner
        # guarantees step == "single"); no spill, no partial wire format
        self._holistic = any(a.kind in HOLISTIC_KINDS for a in self._aggs)
        if self._holistic:
            assert step == "single", "holistic aggregates run single-step"
        self._collected: List[RelBatch] = []
        # revocation runs on the RESERVING thread (MemoryPool.reserve
        # calls the victim's callback), so every state mutation and the
        # revoke itself serialize on this lock; accounting calls happen
        # OUTSIDE it to keep lock ordering acyclic across operators
        self._state_lock = named_lock("HashAggregationOperator._state_lock")
        if self._memory is not None and not self._global and not self._holistic:
            self._memory.set_revoker(self._revoke_memory)
        self._arg_meta = [
            input_schema[a.arg_channel] if a.arg_channel is not None else (None, None)
            for a in self._aggs
        ]
        # state (value, count) slot pairs across all aggregates: long-
        # decimal sums occupy four limb slots (_agg_slot_count)
        self._n_slots = sum(
            _agg_slot_count(a, m[0])
            for a, m in zip(self._aggs, self._arg_meta)
        )
        # Static group-cardinality bound: dictionary-coded and boolean
        # keys, and integer keys whose exact value range the plan knows
        # (`key_ranges`: a digit a value, counted from the range's low
        # end), bound the distinct-group count at PLAN time, so the table
        # can never overflow and the per-batch host sync on the overflow
        # flag disappears (a host read-back is a synchronisation point
        # — the reason Trino precomputes hash channels is the same
        # "decide statically, not per row" discipline).
        bound = 1
        dims, lows = [], []
        ranged = False
        for i, c in enumerate(self._group_channels):
            t, d = self._schema[c]
            span = key_ranges[i] if key_ranges is not None else None
            low = 0
            if t.is_string and d is not None and len(d) > 0:
                dims.append(len(d))
            elif t.kind == T.TypeKind.BOOLEAN:
                dims.append(2)  # true/false
            elif span is not None and t.kind in RANGE_KEY_KINDS:
                low = span[0]
                dims.append(span[1] - low + 1)
                ranged = True
            else:
                bound = 0
                break
            lows.append(low)
            bound *= dims[-1] + 1  # +1: the NULL group
        # a range bounds a table only where the table is addressed by
        # slot: the whole product within what the largest slot-addressed
        # reduce takes and a chooser that answers `dense`, `mxu` or
        # `slot`; otherwise the operator is built as it is without the
        # range (no bound, the sort path). Dictionaries and booleans
        # alone keep their limit of 2^16, whatever the reducers: a
        # dictionary is as large as its column's distinct values, not as
        # its rows' groups, and a count of a few thousand rows by a
        # dictionary of 100,000 names (TPC-H Q21's) is better sorted
        # than spread over a table it leaves empty
        limit = G.SLOT_MAX_SLOTS if ranged else 1 << 16
        self._static_bound = bound if 0 < bound <= limit else None
        # Which reduce the bounded domain gets is the kernels' layer's
        # rule (ops/groupby.choose_bounded_reduce): the dense slot
        # reduce (per-group masked reductions unrolled into one fused
        # program), the MXU one-hot contraction (ops/mxu_groupby.py
        # Pallas kernel) on a TPU, for counts over a larger domain one
        # scatter-add a batch, or the sort path
        self._path = "sort"
        if self._static_bound is not None and self._group_channels:
            reds, dtypes, _ = _value_slot_layout(
                self._aggs, [m[0] for m in self._arg_meta]
            )
            self._path = G.choose_bounded_reduce(
                bound, reds, dtypes,
                mxu=jax.default_backend() == "tpu"
                or _os.environ.get("TRINO_TPU_FORCE_MXU") == "1",
            )
        if ranged and self._path == "sort":
            self._static_bound = None
        # METRICS `agg_key_bound.*`: what bounded the table, counted
        # once an operator, with its first batch
        self._key_bound_counter = "agg_key_bound." + (
            "none" if self._static_bound is None
            else "range" if ranged else "dictionary"
        )
        self._path_counter = "agg_ingest_path." + self._path
        self._dense_dims = tuple(dims) if self._path == "dense" else None
        self._mxu_dims = tuple(dims) if self._path == "mxu" else None
        self._slot_dims = tuple(dims) if self._path == "slot" else None
        # the slot path's states so far, added up (_add_slot_states)
        self._slot_acc = None
        # per key, the value of digit 0; None where every key counts
        # from 0, so that a table bounded by dictionaries alone keeps the
        # programs it had
        self._key_lows = (
            tuple(lows) if self._path != "sort" and any(lows) else None
        )
        self._deferred_ovf: List = []
        # Trains: where the plan bounds the table AND addresses it by
        # slot, a batch needs no readback and no replay and every batch's
        # state has the table's size, so incoming batches are HELD (as
        # references; the scan's arrays are not copied) until
        # TRAIN_BATCHES of one layout are there, and one launch of
        # _agg_ingest_train leaves one state for all of them. Every
        # other path launches once per batch.
        self._trains = self._path != "sort"
        self._held: List[RelBatch] = []
        self._held_layout = None
        self._launched = 0  # trains launched and not yet in METRICS
        # execution-level list of (device flag, message): checked ONCE
        # after results materialize, so no mid-query host sync
        self._checks = deferred_checks
        if self._static_bound is not None:
            self._cap = max(bucket_capacity(self._static_bound), 16)
        if self._global and step != "final":
            self._update = _global_update_fn(
                tuple(self._aggs),
                tuple(
                    a.arg_channel is not None
                    and input_schema[a.arg_channel][0].is_long_decimal
                    for a in self._aggs
                ),
            )

    # -- grouped path --
    def add_input(self, batch: RelBatch) -> None:
        if self._holistic:
            if self._pre is not None:
                batch = self._pre(batch)
            self._collected.append(batch)
            if self._memory is not None:
                # the collect path buffers raw rows: account them so the
                # pool sees the pressure (not revocable — no sketch to
                # spill; oversized holistic inputs fail loudly instead)
                total = 0
                for b in self._collected:
                    for c in b.columns:
                        total += c.data.size * c.data.dtype.itemsize
                        if c.valid is not None:
                            total += c.valid.size
                self._memory.set_bytes(total)
            return
        if self._step == "final":
            if self._pre is not None:
                batch = self._pre(batch)
            self._add_state_input(batch)
            return
        if self._global:
            if self._pre is not None:
                batch = self._pre(batch)
            if self._gstate is None:
                self._gstate = self._global_init()
            self._gstate = self._update(self._gstate, batch)
            return
        METRICS.increment("agg_ingest_batches")
        METRICS.increment(self._path_counter)
        if self._key_bound_counter is not None:
            METRICS.increment(self._key_bound_counter)
            self._key_bound_counter = None
        if self._trains:
            layout = self._train_layout(batch)
            with self._state_lock:
                if layout != self._held_layout:
                    self._flush_held_locked()
                    self._held_layout = layout
                self._held.append(batch)
                if layout is None or len(self._held) == TRAIN_BATCHES:
                    self._flush_held_locked()
            self._report_launches()
            self._track_memory()
            return
        # a batch can never have more groups than rows, so the
        # per-batch table caps at the batch capacity regardless of
        # how large the operator's table has grown (an oversized
        # per-batch cap multiplies every state array for nothing).
        # The dense/MXU paths (trains, above) are exempt: they address
        # slots by mixed-radix position, so the table must hold the
        # FULL domain even when the batch has fewer rows than slots.
        cap = min(self._cap, bucket_capacity(batch.capacity))
        METRICS.increment("agg_ingest_launches")
        gk, gv, used, vals, cnts, ngroups, ovf = _agg_ingest(
            batch, tuple(self._group_channels), tuple(self._aggs),
            cap, self._pre, self._dense_dims, self._mxu_dims, self._key_lows,
        )
        new = (tuple(gk), tuple(gv), used, tuple(vals), tuple(cnts))
        if self._static_bound is not None:
            # overflow impossible by the plan-time bound: defer the
            # flag and verify ONCE at finish (fail-loud guard against
            # a runtime dictionary outgrowing the plan-time one)
            self._deferred_ovf.append(ovf)
            with self._state_lock:
                self._push_pending_locked(new)
        else:
            # Deferred rehash: reading `ovf` here would stall the host
            # on the device PER BATCH. The flag + group count start an
            # async host copy now and are READ one batch later (depth-1
            # pipeline: the copy overlaps the next batch's upstream
            # device work), so an overflow replays immediately at the
            # true group count (the tryRehash analogue) and grows
            # self._cap for the batches that follow.
            for scalar in (ovf, ngroups):
                try:
                    scalar.copy_to_host_async()
                except AttributeError:
                    pass
            with self._state_lock:
                self._push_pending_locked(new, ngroups)
                self._pending_meta.append(
                    (len(self._pending) - 1, ovf, ngroups, batch, cap)
                )
                while len(self._pending_meta) > 1:
                    self._resolve_one_locked()
                self._fold_settled_locked()
            self._report_launches()
        self._track_memory()

    def _push_pending_locked(self, state: tuple, groups=None) -> None:
        self._pending.append(state)
        self._pending_groups.append(groups)

    def _train_layout(self, batch: RelBatch):
        """What the batches of one train have in common, so that one
        traced body fits them all; None for a batch that travels alone
        (nested columns; rows past what the MXU kernel takes in one
        call, where the reduce falls back to the sort path and its
        groups are not slot-addressed)."""
        if self._mxu_dims is not None:
            from trino_tpu.ops.mxu_groupby import MAX_ROWS

            if batch.capacity > MAX_ROWS:
                return None
        cols = []
        for c in batch.columns:
            if type(c) is not Column:
                return None
            cols.append((c.type, c.data.dtype, c.data.shape,
                         c.valid is None, id(c.dictionary)))
        return batch.live is None, tuple(cols)

    def _flush_held_locked(self) -> None:
        """Launch the held train: ONE program, ONE state in _pending, ONE
        deferred flag (caller holds _state_lock). A train of one goes
        through the per-batch program, so a one-batch scan costs what it
        always did; a short train is the train program with a smaller
        `n` (its unused operands repeat the last batch), so no scan
        length mints a lowering of its own."""
        held, self._held = self._held, []
        if not held:
            return
        # slot-addressed tables hold the FULL domain whatever the batch
        statics = (
            tuple(self._group_channels), tuple(self._aggs), self._cap,
            self._pre, self._dense_dims, self._mxu_dims, self._key_lows,
            self._slot_dims,
        )
        self._launched += 1
        if len(held) == 1:
            out = _agg_ingest(held[0], *statics)
        else:
            pad = [held[-1]] * (TRAIN_BATCHES - len(held))
            out = _agg_ingest_train(
                tuple(held + pad), np.int32(len(held)), *statics
            )
        gk, gv, used, vals, cnts, _ngroups, ovf = out
        # overflow impossible by the plan-time bound: defer the flag and
        # verify ONCE at finish (fail-loud guard against a runtime
        # dictionary outgrowing the plan-time one, or a value outside
        # the plan-time range)
        self._deferred_ovf.append(ovf)
        state = (tuple(gk), tuple(gv), used, tuple(vals), tuple(cnts))
        if self._slot_dims is None:
            self._push_pending_locked(state)
        elif self._slot_acc is None:
            self._slot_acc = state
        else:
            # slot g is the same group in every train of this operator:
            # the states add, and a scan of any length holds one
            self._slot_acc = _add_slot_states(self._slot_acc, state)

    def _report_launches(self) -> None:
        """`agg_ingest_launches` for the trains launched under
        _state_lock: METRICS takes a lock of its own, so outside it."""
        with self._state_lock:
            n, self._launched = self._launched, 0
            (merges, retries, short, spared), self._merges = (
                self._merges, [0, 0, 0, 0])
            (batches, laid, unordered), self._ordered = self._ordered, [0, 0, 0]
        for name, moved in (("agg_ingest_launches", n),
                            ("agg_merge_launches", merges),
                            ("agg_merge_retries", retries),
                            ("agg_merge_short_reads", short),
                            ("agg_merge_slots_spared", spared),
                            ("agg_ordered_input.batches", batches),
                            ("agg_unordered_input.batches", unordered),
                            ("agg_ordered_merge.launches", laid)):
            if moved:
                METRICS.increment(name, moved)

    def _resolve_one_locked(self) -> None:
        """Settle the OLDEST deferred per-batch overflow record; its
        flag has been copying to the host since ingest (caller holds
        _state_lock). The flag also covers sort_group_reduce's 62-bit
        hash-collision detector, so the replay LOOPS (capacity doubling
        reseeds via _order_seed) until it comes back clean — same
        semantics as the old per-batch retry ladder."""
        idx, ovf, ngroups, batch, cap = self._pending_meta.pop(0)
        while True:
            overflowed, ordered = _flag_word("agg.ingest_overflow", ovf)
            if not overflowed:
                self._ordered[0 if ordered else 2] += 1
                break
            cap = max(cap * 2, bucket_capacity(int(ngroups)))
            self._cap = max(self._cap, cap)
            gk, gv, used, vals, cnts, ngroups, ovf = _agg_ingest(
                batch, tuple(self._group_channels), tuple(self._aggs),
                cap, self._pre, self._dense_dims, self._mxu_dims,
                self._key_lows,
            )
            self._pending[idx] = (
                tuple(gk), tuple(gv), used, tuple(vals), tuple(cnts)
            )
            self._pending_groups[idx] = ngroups

    def _resolve_pending_locked(self) -> None:
        """Drain every deferred overflow record (merge points)."""
        while self._pending_meta:
            self._resolve_one_locked()

    def _fold_settled_locked(self) -> None:
        """Sort path: once FOLD_STATES ingest states have settled (their
        overflow flags read; the newest, still in flight, stays), merge
        them into ONE state of the next tier, and so on up the tiers
        (caller holds _state_lock). What is pending is then bounded by
        the tiers, not by the scan, and every fold has FOLD_STATES
        operands."""
        if len(self._pending) - len(self._pending_meta) < FOLD_STATES:
            return
        states = _common_capacity(self._pending[:FOLD_STATES])
        del self._pending[:FOLD_STATES]
        del self._pending_groups[:FOLD_STATES]
        self._pending_meta = [
            (idx - FOLD_STATES, *rest) for idx, *rest in self._pending_meta
        ]
        tier = 0
        while True:
            folded = self._merge_states_locked(states)
            if len(self._folded) == tier:
                self._folded.append([])
            self._folded[tier].append(folded)
            if len(self._folded[tier]) < FOLD_STATES:
                return
            full, self._folded[tier] = self._folded[tier], []
            states = _common_capacity([s for s, _, _ in full])
            tier += 1

    def _merge_states_locked(self, states: list, groups=None, resorted=None):
        """ONE device program merges `states` into one; returns (state,
        its group count on the device, whether the merge re-sorted: read
        off its flag word, False where that is not read). The table is
        sized from what the states can hold between them, so the merge
        does not overflow on a count and runs once: a fold (`groups`
        None) takes the states' slots (its shape then follows from
        theirs alone, and one program serves every fold of a scan); the
        last merge takes the group counts the launches left (`groups`,
        one a state; None: the state's capacity), which is what the
        output is sized by. With the counts in hand it also reads a
        state that a fold re-sorted (`resorted`, one a state) only up to
        the power of two that holds its count (`takes` of
        _merge_group_states): the sort path leaves a state dense from
        slot 0, a fold as wide as all its operands, and this merge would
        sort the empty slots again. No other state is read short: an
        ingest's count sits near its capacity, and a fold that laid its
        states end to end is copied, not sorted (FOLD_STATES). The flag
        still covers sort_group_reduce's hash-collision detector, whose
        retry doubles the table to reseed, and a used slot behind a cut,
        whose retry reads the states whole."""
        reducers = []
        for i, x in enumerate(self._aggs):
            reducers.extend(_slot_merge_reducers(x, self._arg_meta[i][0]))
        reducers = tuple(reducers)
        # distinct groups across N states cannot exceed the concatenated
        # slot count, so the merge table caps there (bounds the output
        # arrays by the data, not by a possibly-overgrown _cap)
        caps = [int(s[2].shape[0]) for s in states]
        concat_len = sum(caps)
        takes = None
        if self._static_bound is not None:
            cap = min(
                max(self._cap, 16), bucket_capacity(max(concat_len, 16))
            )
        elif groups is None:
            cap = bucket_capacity(concat_len)
        else:
            with host_sync("agg.merge_groups", 4 * len(states)) as span:
                counts = jax.device_get(
                    [0 if g is None else g for g in groups]
                )
                bound = sum(
                    c if g is None else min(int(n), c)
                    for c, g, n in zip(caps, groups, counts)
                )
                span.set_metadata(groups=bound)
            cap = bucket_capacity(max(bound, 16))
            holds = [bucket_capacity(int(n)) for n in counts]
            takes = tuple(
                h if cut and h < c else None
                for h, c, cut in zip(holds, caps, resorted)
            )
            if takes.count(None) == len(takes):
                takes = None
        retry = 0
        re_sorted = False
        while True:
            slots_in = concat_len if takes is None else sum(
                t or c for t, c in zip(takes, caps))
            # the span ends once the flag is read (`sync.agg.
            # merge_overflow`, inside it): which way the merge went is
            # known no sooner
            with host_span("agg.merge", states=len(states),
                           slots_in=slots_in, cap=cap, retry=retry) as span:
                merged, ngroups, ovf = _merge_group_states(
                    tuple(states), reducers, cap, takes
                )
                self._merges[0] += 1
                if takes is not None:
                    self._merges[2] += len(takes) - takes.count(None)
                    self._merges[3] += concat_len - slots_in
                if self._static_bound is not None:
                    self._deferred_ovf.append(ovf)
                    break
                overflowed, ordered = _flag_word("agg.merge_overflow", ovf)
                span.set_metadata(ordered=int(ordered))
            self._ordered[1] += ordered
            re_sorted = not ordered
            if not overflowed:
                break
            retry += 1
            self._merges[1] += 1
            if takes is not None:
                takes = None
                continue
            cap = max(cap * 2, bucket_capacity(int(ngroups)))
            self._cap = max(self._cap, cap)
        return merged, ngroups, re_sorted

    def _merge_pending_locked(self) -> None:
        """Fold the current acc, the folded tiers and _pending into ONE
        merged state with a single N-way device program (caller holds
        _state_lock)."""
        self._flush_held_locked()
        if self._slot_acc is not None:
            # alone it becomes the result as it is; beside a state off
            # the wire or out of a spill (keys in any slot, a key
            # perhaps twice) it is one more operand of the sort merge
            self._push_pending_locked(self._slot_acc)
            self._slot_acc = None
        self._resolve_pending_locked()
        states = [] if self._acc is None else [self._acc]
        groups = [] if self._acc is None else [self._acc_groups]
        resorted = [False] * len(states)
        for tier in reversed(self._folded):  # oldest rows first
            if tier:
                states.extend(_common_capacity([s for s, _, _ in tier]))
                groups.extend(g for _, g, _ in tier)
                resorted.extend(r for _, _, r in tier)
        pending, left = self._pending, self._pending_groups
        if self._folded and pending:
            # a scan long enough to fold brings what is left to one
            # shape and one count, empty states making it up: the last
            # merge's program then follows from the tiers, not from the
            # scan's length modulo FOLD_STATES. A short scan merges its
            # states as they are.
            pending = _common_capacity(pending)
            spare = FOLD_STATES - len(pending)
            pending = pending + [_empty_state(pending[0])] * spare
            left = left + [0] * spare
        states.extend(pending)
        groups.extend(left)
        resorted.extend([False] * len(pending))
        self._pending, self._pending_groups, self._folded = [], [], []
        if not states:
            return
        if len(states) == 1 and not self._unreduced_state:
            self._acc, self._acc_groups = states[0], groups[0]
            return
        self._acc, self._acc_groups, _ = self._merge_states_locked(
            states, groups, resorted)
        self._unreduced_state = False

    # -- final step: consume serialized accumulator state --
    def _add_state_input(self, batch: RelBatch) -> None:
        """Ingest a partial_output_schema-layout batch (the exchange's
        output) directly as a group-state set and merge it in."""
        k = len(self._group_channels)
        live = batch.live_mask()
        if self._global:
            self._merge_global_state(batch, live)
            return
        # the wire layout is uniform — k key columns then ONE
        # (value, count) pair per aggregate; long-decimal columns arrive
        # as (n, 2) limb pairs and split back into the internal slot
        # layout here (keys into limb key lanes, sums into four 32-bit
        # limb-sum slots, extremes/firsts into (hi, lo) slots)
        keys, valids = [], []
        for c in range(k):
            col = batch.columns[c]
            v = col.valid_mask()
            if getattr(col.data, "ndim", 1) == 2:
                keys.extend([col.data[:, 0], col.data[:, 1]])
                valids.extend([v, v])
            else:
                keys.append(col.data)
                valids.append(v)
        vals, cnts = [], []
        for i, a in enumerate(self._aggs):
            val_col = batch.columns[k + 2 * i]
            cnt = batch.columns[k + 2 * i + 1].data.astype(jnp.int64)
            if getattr(val_col.data, "ndim", 1) == 2:
                if a.kind in ("sum", "avg"):
                    pieces = _limb_split(val_col.data)
                else:  # min/max/any: the slots ARE the (hi, lo) pair
                    pieces = [val_col.data[:, 0], val_col.data[:, 1]]
                for p in pieces:
                    vals.append(p)
                    cnts.append(cnt)
            else:
                vals.append(val_col.data)
                cnts.append(cnt)
        new = (tuple(keys), tuple(valids), live, tuple(vals), tuple(cnts))
        with self._state_lock:
            self._push_pending_locked(new)
            self._unreduced_state = True
        self._track_memory()

    def _merge_global_state(self, batch: RelBatch, live) -> None:
        """Global (no GROUP BY) final step: fold incoming single-row
        states with the merge reducers."""
        if self._gstate is None:
            self._gstate = self._global_init()
        from trino_tpu.ops import int128 as I128

        out = []
        for i, a in enumerate(self._aggs):
            val, cnt = self._gstate[i]
            v_in = batch.columns[2 * i].data
            c_in = batch.columns[2 * i + 1].data.astype(jnp.int64)
            c_in = jnp.where(live, c_in, 0)
            n = jnp.sum(c_in)
            red = _MERGE_REDUCER[a.kind]
            if getattr(v_in, "ndim", 1) == 2:
                # Int128 partial states: merge in limb arithmetic
                present = live & (c_in > 0)
                if red == "sum":
                    limb_sums = [
                        jnp.sum(jnp.where(live, piece, jnp.int64(0)))
                        for piece in _limb_split(v_in)
                    ]
                    bh, bl = _limb_join(limb_sums)
                    h, lo = I128.add(val[0], val[1], bh, bl)
                    out.append((jnp.stack([h, lo]), cnt + n))
                elif red in ("min", "max"):
                    h, lo = v_in[:, 0], v_in[:, 1]
                    m1, m2 = _lex128_reduce(h, lo, present, red)
                    better = (
                        I128.lt(m1, m2, val[0], val[1])
                        if red == "min"
                        else I128.lt(val[0], val[1], m1, m2)
                    )
                    take = (better | (cnt == 0)) & jnp.any(present)
                    nh = jnp.where(take, m1, val[0])
                    nl = jnp.where(take, m2, val[1])
                    out.append((jnp.stack([nh, nl]), cnt + n))
                else:  # first
                    first = v_in[jnp.argmax(present)]
                    new_val = jnp.where(
                        cnt > 0, val, jnp.where(jnp.any(present), first, val)
                    )
                    out.append((new_val, cnt + n))
                continue
            if red == "sum":
                neutral = jnp.zeros((), dtype=val.dtype)
                contrib = jnp.where(live, v_in.astype(val.dtype), neutral)
                out.append((val + jnp.sum(contrib), cnt + n))
            elif red in ("min", "max"):
                neutral = minmax_neutral(v_in.dtype, red)
                present = live & (c_in > 0)
                masked = jnp.where(present, v_in, jnp.asarray(neutral, v_in.dtype))
                r = jnp.min(masked) if red == "min" else jnp.max(masked)
                op = jnp.minimum if red == "min" else jnp.maximum
                out.append((op(val, r.astype(val.dtype)), cnt + n))
            else:  # first
                present = live & (c_in > 0)
                first = v_in[jnp.argmax(present)]
                new_val = jnp.where(
                    cnt > 0, val, jnp.where(jnp.any(present), first, val)
                )
                out.append((new_val, cnt + n))
        self._gstate = out

    # -- partial step: emit serialized accumulator state --
    def _partial_state_batch(self) -> RelBatch:
        """Current grouped state as a partial-wire-format batch (the
        accumulator serialization shared by the exchange AND the
        spiller)."""
        if self._acc is None:
            key_dts = []
            for c in self._group_channels:
                t = self._schema[c][0]
                key_dts.extend([t.dtype] * t.lanes)
            self._acc = (
                [jnp.zeros(16, dtype=dt) for dt in key_dts],
                [jnp.zeros(16, dtype=jnp.bool_) for _ in key_dts],
                jnp.zeros(16, dtype=jnp.bool_),
                [jnp.zeros(16, dtype=jnp.int64) for _ in range(self._n_slots)],
                [jnp.zeros(16, dtype=jnp.int64) for _ in range(self._n_slots)],
            )
        cols: List[Column] = []
        gk, gv, used, vals, cnts = self._acc
        ki = 0
        for ch in self._group_channels:
            t, d = self._schema[ch]
            if t.lanes == 2:  # reassemble split long-decimal key limbs
                cols.append(Column(
                    t, jnp.stack([gk[ki], gk[ki + 1]], axis=-1), gv[ki], d,
                ))
                ki += 2
            else:
                cols.append(Column(t, gk[ki], gv[ki], d))
                ki += 1
        si = 0
        for a, (arg_t, _) in zip(self._aggs, self._arg_meta):
            vt, vd = agg_state_meta(a, self._schema)[0]
            cnt = cnts[si]
            col, si = _slots_to_wire_column(a, arg_t, vt, vd, vals, si)
            cols.append(col)
            cols.append(Column(T.BIGINT, cnt.astype(jnp.int64), None, None))
        return RelBatch(cols, used)

    def _emit_partial(self) -> None:
        if self._global:
            cols: List[Column] = []
            states = self._gstate if self._gstate is not None else self._global_init()
            for a, (val, cnt) in zip(self._aggs, states):
                vt, vd = agg_state_meta(a, self._schema)[0]
                cols.append(Column(vt, val[None].astype(vt.dtype), None, vd))
                cols.append(Column(T.BIGINT, cnt[None].astype(jnp.int64), None, None))
            self._out = RelBatch(cols, jnp.ones(1, dtype=jnp.bool_))
            return
        out = self._partial_state_batch()
        if out.capacity >= _SHRINK_MIN_CAPACITY and not self._trains:
            out = _shrink_prefix(out, _count("agg.partial_rows", out.live_mask()))
        self._out = out

    # -- holistic (collect) path: min_by/max_by/approx_percentile --
    def _finish_holistic(self) -> RelBatch:
        """One pass over ALL collected rows: regular aggregates via
        sort_group_reduce, order statistics via grouped_argbest /
        grouped_percentile — all three sort by the same key chain, so
        their group slots align (ops/groupby._segment_bounds)."""
        if self._collected:
            mega = concat_batches(self._collected)
        else:
            mega = None
        if mega is None or mega.live_mask().shape[0] == 0:
            # zero rows collected: one all-dead row keeps every shape
            # non-empty so the global path can slice its single slot
            cols = [
                Column(t, jnp.zeros(1, dtype=t.dtype),
                       jnp.zeros(1, dtype=jnp.bool_), d)
                for t, d in self._schema
            ]
            mega = RelBatch(cols, jnp.zeros(1, dtype=jnp.bool_))
        self._collected = []
        keys = [mega.columns[c].data for c in self._group_channels]
        valids = [mega.columns[c].valid_mask() for c in self._group_channels]
        live = mega.live_mask()

        regular = [
            (i, a) for i, a in enumerate(self._aggs)
            if a.kind not in HOLISTIC_KINDS
        ]
        values, vvalids, reds = [], [], []
        for _, a in regular:
            if a.arg_channel is None:
                values.append(live.astype(jnp.int64))
                vvalids.append(None)
            elif getattr(mega.columns[a.arg_channel].data, "ndim", 1) == 2:
                _append_long_decimal_slots(
                    a, mega.columns[a.arg_channel], live,
                    values, vvalids, reds,
                )
                continue
            else:
                col = mega.columns[a.arg_channel]
                values.append(col.data)
                vvalids.append(col.valid)
            reds.append(_BATCH_REDUCER[a.kind])

        cap = self._cap
        while True:
            gk, gv, used, vals, cnts, ngroups, ovf = G.sort_group_reduce(
                tuple(keys), tuple(valids), live, tuple(values),
                tuple(vvalids), tuple(reds), cap,
            )
            if not self._group_channels or not bool(ovf):
                break
            cap = max(cap * 2, bucket_capacity(int(ngroups)))
        self._cap = cap

        agg_cols: Dict[int, Column] = {}
        si = 0
        for (i, a) in regular:
            arg_t, arg_d = self._arg_meta[i]
            state, si = _slots_to_state(a, arg_t, vals, cnts, si)
            agg_cols[i] = _agg_output(a, state, arg_t, arg_d)
        # one key sort shared by every argbest kernel (percentile needs
        # its own value pre-ordering and sorts separately)
        shared_order = (
            G.key_order(tuple(keys), tuple(valids), live, cap)
            if any(a.kind in ("min_by", "max_by") for a in self._aggs)
            else None
        )
        for i, a in enumerate(self._aggs):
            if a.kind not in HOLISTIC_KINDS:
                continue
            xcol = mega.columns[a.arg_channel]
            if a.kind in ("min_by", "max_by"):
                bycol = mega.columns[a.arg2_channel]
                data, valid = G.grouped_argbest(
                    tuple(keys), tuple(valids), live,
                    bycol.data, bycol.valid, xcol.data, xcol.valid,
                    a.kind, cap, order=shared_order,
                )
            elif a.kind == "listagg":
                agg_cols[i] = self._listagg_column(
                    a, keys, valids, live, xcol, cap
                )
                continue
            elif a.kind in _COLLECT_KINDS:
                agg_cols[i] = self._collect_column(
                    a, keys, valids, live, mega, cap
                )
                continue
            elif a.kind == "approx_distinct":
                cnts_d = G.grouped_count_distinct(
                    tuple(keys), tuple(valids), live,
                    xcol.data, xcol.valid, cap,
                )
                agg_cols[i] = Column(T.BIGINT, cnts_d, None, None)
                continue
            elif a.kind == "pctl_merge":
                ccol = mega.columns[a.arg2_channel]
                mxcol = mega.columns[a.arg3_channel]
                data, valid = G.grouped_weighted_percentile(
                    tuple(keys), tuple(valids), live,
                    xcol.data, xcol.valid, ccol.data, mxcol.data,
                    a.percentile, cap,
                )
            else:  # approx_percentile
                data, valid = G.grouped_percentile(
                    tuple(keys), tuple(valids), live,
                    xcol.data, xcol.valid, a.percentile, cap,
                )
            agg_cols[i] = Column(
                a.out_type, data.astype(a.out_type.dtype), valid,
                xcol.dictionary,
            )

        out_cols: List[Column] = []
        for ch, kk, vv in zip(self._group_channels, gk, gv):
            t, d = self._schema[ch]
            out_cols.append(Column(t, kk, vv, d))
        for i in range(len(self._aggs)):
            out_cols.append(agg_cols[i])
        if self._global:
            # global aggregation over empty input still yields ONE row
            # (counts 0, other aggregates NULL) — slot 0 carries it.
            # Nested (map/array) outputs slice through gather: rebuilding
            # a flat Column from .data would drop their starts/flat
            # arrays (the lengths array alone is not the value)
            pos = jnp.zeros(1, dtype=jnp.int32)
            return RelBatch(
                [c.gather(pos) if c.type.is_nested
                 or c.type.kind == T.TypeKind.ARRAY
                 else Column(c.type, c.data[:1], None if c.valid is None
                             else c.valid[:1], c.dictionary)
                 for c in out_cols],
                jnp.ones(1, dtype=jnp.bool_),
            )
        return RelBatch(out_cols, used)

    def _collect_column(self, a: AggSpec, keys, valids, live, mega, cap):
        """Collect-path aggregates (array_agg/map_agg/histogram/...):
        the device delivers group-contiguous, value-ordered row order
        (ops/groupby.grouped_rows_order); the host assembles each
        group's container. Holistic by construction — the fragmenter
        runs these single-step after a gather, exactly like listagg
        (reference: ArrayAggregationFunction / MapAggregationFunction /
        Histogram build their result Blocks on the heap too)."""
        xcol = mega.columns[a.arg_channel]
        gid, sm, order, n_groups, overflowed = G.grouped_rows_order(
            tuple(keys), tuple(valids), live, xcol.data, xcol.valid, cap
        )
        gid_h, sm_h, ord_h, n_h, ov_h = jax.device_get(
            (gid, sm, order, n_groups, overflowed)
        )
        if bool(ov_h):
            # the finish loop settles capacity through sort_group_reduce
            # before holistic finalizers run, so this cannot fire unless
            # that invariant breaks — fail loudly, not with a bad gather
            raise RuntimeError("collect aggregate group overflow")
        n_h = int(n_h)

        def pyvals(ch):
            lst = jax.device_get(mega.columns[ch]).to_pylist()
            return [lst[i] for i in ord_h]

        xs = pyvals(a.arg_channel)
        ys = pyvals(a.arg2_channel) if a.arg2_channel is not None else None
        groups: List[list] = [[] for _ in range(n_h)]
        for j, (g, ok) in enumerate(zip(gid_h, sm_h)):
            if ok and 0 <= g < n_h:
                groups[g].append(
                    (xs[j], ys[j]) if ys is not None else xs[j]
                )

        kind = a.kind
        if kind in ("bitwise_and_agg", "bitwise_or_agg", "bitwise_xor_agg"):
            op = {"bitwise_and_agg": lambda s, v: s & v,
                  "bitwise_or_agg": lambda s, v: s | v,
                  "bitwise_xor_agg": lambda s, v: s ^ v}[kind]
            data = np.zeros(cap, dtype=np.int64)
            valid = np.zeros(cap, dtype=bool)
            for g, vals in enumerate(groups):
                vals = [v for v in vals if v is not None]
                if not vals:
                    continue
                acc = vals[0]
                for v in vals[1:]:
                    acc = op(acc, v)
                # wrap to signed 64-bit (python ints are unbounded)
                acc &= (1 << 64) - 1
                data[g] = acc - (1 << 64) if acc >= (1 << 63) else acc
                valid[g] = True
            return Column(
                T.BIGINT, jnp.asarray(data), jnp.asarray(valid), None
            )

        out_vals: List[object] = [None] * cap
        for g, vals in enumerate(groups):
            if kind == "array_agg":
                # NULL elements are kept (the reference's array_agg does)
                out_vals[g] = vals if vals else None
            elif kind == "map_agg":
                m = {k: v for k, v in vals if k is not None}
                out_vals[g] = m or None
            elif kind == "multimap_agg":
                mm: Dict[object, list] = {}
                for k, v in vals:
                    if k is not None:
                        mm.setdefault(k, []).append(v)
                out_vals[g] = mm or None
            elif kind == "histogram":
                h: Dict[object, int] = {}
                for v in vals:
                    if v is not None:
                        h[v] = h.get(v, 0) + 1
                out_vals[g] = h or None
            elif kind == "approx_most_frequent":
                b = int(a.param or 3)
                h = {}
                for v in vals:
                    if v is not None:
                        h[v] = h.get(v, 0) + 1
                top = sorted(h.items(), key=lambda kv: (-kv[1], str(kv[0])))
                out_vals[g] = dict(top[:b]) or None
            elif kind == "numeric_histogram":
                out_vals[g] = _bht_histogram(
                    [v for v in vals if v is not None], int(a.param or 10)
                )
            elif kind == "map_union":
                merged: Dict[object, object] = {}
                for m in vals:
                    if m:
                        merged.update(m)
                out_vals[g] = merged or None
            elif kind == "approx_set":
                from trino_tpu.expr.pyfns import hll_from_values

                nn = [v for v in vals if v is not None]
                out_vals[g] = hll_from_values(nn) if nn else None
            elif kind == "tdigest_agg":
                from trino_tpu.expr.pyfns import tdigest_from_values

                nn = [v for v in vals if v is not None]
                out_vals[g] = tdigest_from_values(nn) if nn else None
            elif kind == "sketch_merge":
                from trino_tpu.expr.pyfns import sketch_merge

                nn = [v for v in vals if v is not None]
                out_vals[g] = sketch_merge(nn) if nn else None
        if a.post:
            # fused sketch accessor: the digest never leaves the host
            from trino_tpu.expr.pyfns import (
                hll_cardinality, tdigest_quantile_at_value,
                tdigest_value_at_quantile,
            )

            if a.post == "vaq":
                # values_at_quantiles: one array(double) per group
                arrs: List[object] = [None] * cap
                for g in range(n_h):
                    d = out_vals[g]
                    if d is None:
                        continue
                    arrs[g] = [
                        tdigest_value_at_quantile(d, float(q))
                        for q in (a.param or ())
                    ]
                return Column.from_pylist(a.out_type, arrs, capacity=cap)
            data = np.zeros(
                cap, dtype=np.int64 if a.post == "card" else np.float64
            )
            valid = np.zeros(cap, dtype=bool)
            for g in range(n_h):
                d = out_vals[g]
                if d is None:
                    continue
                if a.post == "card":
                    r = hll_cardinality(d)
                elif a.post == "vq":
                    r = tdigest_value_at_quantile(d, float(a.param))
                else:
                    r = tdigest_quantile_at_value(d, float(a.param))
                if r is not None:
                    data[g] = r
                    valid[g] = True
            return Column(
                a.out_type, jnp.asarray(data), jnp.asarray(valid), None
            )
        return Column.from_pylist(a.out_type, out_vals, capacity=cap)

    def _listagg_column(self, a: AggSpec, keys, valids, live, xcol, cap):
        """listagg/string_agg: concatenating group members into NEW
        strings is host-side work by nature (Trino's
        ListaggAggregationFunction builds its VARCHAR on the heap too);
        the device groups and value-orders the rows, the host joins
        dictionary values per dense group id. Element order is the
        value's lexical order (deterministic; WITHIN GROUP custom
        orderings are future work)."""
        gid, w, codes, n_groups, _ = G.grouped_rows_sorted(
            tuple(keys), tuple(valids), live, xcol.data, xcol.valid, cap
        )
        gid_h, w_h, codes_h, n_h = jax.device_get((gid, w, codes, n_groups))
        dict_values = xcol.dictionary.values if xcol.dictionary else []
        parts: List[List[str]] = [[] for _ in range(int(n_h))]
        for g, ok, c in zip(gid_h, w_h, codes_h):
            if ok and 0 <= g < len(parts) and 0 <= c < len(dict_values):
                parts[g].append(dict_values[int(c)])
        sep = a.separator or ""
        strings = [sep.join(p) for p in parts]
        out_dict = Dictionary(strings)
        data = np.zeros(cap, dtype=np.int32)
        valid = np.zeros(cap, dtype=bool)
        for g, s in enumerate(strings):
            if parts[g]:
                data[g] = out_dict.code(s)
                valid[g] = True
        return Column(
            T.VARCHAR, jnp.asarray(data), jnp.asarray(valid), out_dict
        )

    # -- spill (revocable memory) --
    def _revoke_memory(self) -> None:
        """startMemoryRevoke/finishMemoryRevoke collapsed: dump the group
        state to disk in the partial wire format and reset. May be called
        from ANOTHER task's thread (MemoryPool.reserve picks victims), so
        the whole snapshot-spill-reset runs under the state lock."""
        with self._state_lock:
            if self._in_finish:
                return  # finish owns state
            self._merge_pending_locked()
            if self._acc is None:
                return  # nothing to give back
            if self._spiller is None:
                from trino_tpu.exec.spill import FileSpiller

                self._spiller = FileSpiller()
            self._spiller.spill(self._partial_state_batch())
            self._acc = self._acc_groups = None
        self._report_launches()
        self._track_memory()

    def _track_memory(self) -> None:
        """Account the accumulation-state footprint. The pool bounds
        ACCUMULATION memory; the finish-phase merge+finalize produces the
        operator's output (not operator state) and is exempt — the
        partitioned-spill refinement (grace merge of 1/N partitions at a
        time) is the next step toward bounding finish too."""
        if self._memory is None or self._in_finish:
            return
        from trino_tpu.runtime.memory import batch_bytes

        total = 0
        folded = [st for tier in list(self._folded) for st, _, _ in tier]
        for st in [s for s in (self._acc, self._slot_acc) if s is not None] \
                + folded + list(self._pending):
            gk, gv, used, vals, cnts = st
            for arr in [*gk, *gv, used, *vals, *cnts]:
                total += arr.size * arr.dtype.itemsize
        # the depth-1 deferred-rehash queue retains one input batch, a
        # held train up to TRAIN_BATCHES - 1 of them
        for _, _, _, b, _ in self._pending_meta:
            total += batch_bytes(b)
        for b in list(self._held):
            total += batch_bytes(b)
        try:
            self._memory.set_bytes(total)
        except Exception:
            # pool exhausted even after revoking others: spill our own
            # state (self-revocation) and account the reset footprint
            if self._acc is None and self._slot_acc is None \
                    and not self._pending and not self._held \
                    and not any(self._folded):
                raise
            self._revoke_memory()
            return
        self._memory.set_revocable_bytes(total)

    # -- global path --
    def _global_init(self):
        states = []
        for a in self._aggs:
            dt = (
                self._schema[a.arg_channel][0].dtype
                if a.arg_channel is not None
                else np.dtype(np.int64)
            )
            if a.kind in ("count", "count_star"):
                val = jnp.int64(0)
            elif a.kind in ("sum", "avg"):
                if (
                    a.arg_channel is not None
                    and self._schema[a.arg_channel][0].is_long_decimal
                ):
                    val = jnp.zeros(2, dtype=jnp.int64)  # Int128 (hi, lo)
                else:
                    acc_dt = (
                        jnp.float64 if np.issubdtype(dt, np.floating) else jnp.int64
                    )
                    val = jnp.zeros((), dtype=acc_dt)
            elif a.kind in ("min", "max"):
                if (
                    a.arg_channel is not None
                    and self._schema[a.arg_channel][0].is_long_decimal
                ):
                    val = jnp.zeros(2, dtype=jnp.int64)  # replaced on first row
                else:
                    val = jnp.asarray(minmax_neutral(dt, a.kind), dtype=dt)
            else:  # any
                if (
                    a.arg_channel is not None
                    and self._schema[a.arg_channel][0].is_long_decimal
                ):
                    val = jnp.zeros(2, dtype=jnp.int64)
                else:
                    val = jnp.zeros((), dtype=dt)
            states.append((val, jnp.int64(0)))
        return states

    def finish(self) -> None:
        if self._finishing:
            return
        self._finishing = True
        if self._holistic:
            self._out = self._finish_holistic()
            return
        with self._state_lock:
            # flips revocation off atomically; from here finish owns state
            self._in_finish = True
            spiller, self._spiller = self._spiller, None
        if spiller is not None:
            # merge-on-unspill: spilled partial states re-enter through
            # the FINAL-step ingestion path
            for b in spiller.unspill():
                self._add_state_input(b)
            spiller.close()
        with self._state_lock:
            self._merge_pending_locked()
        self._report_launches()
        if self._memory is not None and not self._global:
            self._memory.set_bytes(0)
            self._memory.set_revocable_bytes(0)
        if self._deferred_ovf:
            flag = _any_flags(tuple(
                f if f.dtype == jnp.bool_ else _overflow_bit(f)
                for f in self._deferred_ovf
            ))
            msg = (
                "group table overflowed its plan-time bound (runtime "
                "dictionary larger than planned, or a key outside the "
                "value range the plan was made for)"
            )
            if self._checks is not None:
                # deferred to the end-of-query sync point
                self._checks.append((flag, msg))
            elif _flag("agg.bound_overflow", flag):
                raise RuntimeError(msg)
            self._deferred_ovf = []
        if self._step == "partial":
            self._emit_partial()
            return
        cols: List[Column] = []
        if self._global:
            states = self._gstate if self._gstate is not None else self._global_init()
            live = jnp.ones(1, dtype=jnp.bool_)
            for i, (a, (val, cnt)) in enumerate(zip(self._aggs, states)):
                arg_t, arg_d = self._arg_meta[i]
                long_arg = arg_t is not None and arg_t.is_long_decimal
                if a.kind in ("count", "count_star"):
                    state = (val[None],)
                elif long_arg and a.kind in ("sum", "avg", "min", "max", "any"):
                    # Int128 (hi, lo) scalar state
                    state = (val[0][None], val[1][None], cnt[None])
                else:
                    state = (val[None], cnt[None])
                cols.append(_agg_output(a, state, arg_t, arg_d))
            self._out = RelBatch(cols, live)
            return
        if self._acc is None:
            # no input: empty group set (long-decimal keys occupy two
            # int64 limb slots — the split-key layout of _agg_ingest)
            key_dts = []
            for c in self._group_channels:
                t = self._schema[c][0]
                key_dts.extend([t.dtype] * t.lanes)
            self._acc = (
                [jnp.zeros(16, dtype=dt) for dt in key_dts],
                [jnp.zeros(16, dtype=jnp.bool_) for _ in key_dts],
                jnp.zeros(16, dtype=jnp.bool_),
                [jnp.zeros(16, dtype=jnp.int64) for _ in range(self._n_slots)],
                [jnp.zeros(16, dtype=jnp.int64) for _ in range(self._n_slots)],
            )
        gk, gv, used, vals, cnts = self._acc
        ki = 0
        for ch in self._group_channels:
            t, d = self._schema[ch]
            if t.lanes == 2:  # reassemble split long-decimal limbs
                cols.append(Column(
                    t, jnp.stack([gk[ki], gk[ki + 1]], axis=-1),
                    gv[ki], d,
                ))
                ki += 2
            else:
                cols.append(Column(t, gk[ki], gv[ki], d))
                ki += 1
        outs = _finalize_grouped(
            (tuple(gk), tuple(gv), used, tuple(vals), tuple(cnts)),
            tuple(self._aggs),
            tuple(t for t, _ in self._arg_meta),
        )
        for a, (arg_t, arg_d), (data, valid) in zip(
            self._aggs, self._arg_meta, outs
        ):
            d = arg_d if a.kind in ("min", "max", "any") else None
            cols.append(Column(a.out_type, data, valid, d))
        out = RelBatch(cols, used)
        if out.capacity >= _SHRINK_MIN_CAPACITY and not self._trains:
            # sort-path group rows are prefix-dense: hand downstream
            # operators the live size, not the table capacity
            groups = _count("agg.group_rows", used)
            METRICS.increment("agg_groups_out", groups)
            out = _shrink_prefix(out, groups)
        self._out = out

    def get_output(self) -> Optional[RelBatch]:
        out, self._out = self._out, None
        return out

    def is_finished(self) -> bool:
        return self._finishing and self._out is None


# ---------------------------------------------------------------------------
# Hash join
# ---------------------------------------------------------------------------


class JoinBridge:
    """Build->probe handoff (PartitionedLookupSourceFactory analogue,
    join/PartitionedLookupSourceFactory.java:56). The planner runs the
    build pipeline to completion before starting the probe pipeline.
    When the build side spilled (grace mode), `grace` carries the
    hash-partitioned build pages instead of a device lookup source."""

    def __init__(self):
        self.lookup_source: Optional[J.LookupSource] = None
        self.build_batch: Optional[RelBatch] = None
        # build-side key dictionaries, for probe-side code remapping
        self.key_dicts: Optional[List[Optional[Dictionary]]] = None
        # build-side key channel indexes (dynamic-filter domains)
        self.build_key_channels: List[int] = []
        # grace mode: partitioned build spill + schema to rebuild from
        self.grace = None  # Optional[spill.GracePartitionSpill]
        self.build_schema: Optional[list] = None


@partial(jax.jit, static_argnames=("key_channels",))
def _consolidate_build(parts: Tuple[RelBatch, ...], key_channels: Tuple[int, ...]):
    """Consolidate build batches + build the LookupSource in one device
    program (HashBuilderOperator.java:58)."""
    merged = concat_batches(list(parts))
    keys, valids = [], []
    for c in key_channels:
        col = merged.columns[c]
        v = col.valid_mask()
        if getattr(col.data, "ndim", 1) == 2:  # long-decimal limbs
            keys.extend([col.data[:, 0], col.data[:, 1]])
            valids.extend([v, v])
        else:
            keys.append(col.data)
            valids.append(v)
    return J.build_lookup(
        keys, valids, merged.live_mask(), exact_keys=True
    ), merged


GRACE_PARTITIONS = 8

# batches whose capacity dwarfs their live count get host-compacted at
# blocking boundaries: every downstream kernel then compiles at the
# small shape and moves less HBM. (An earlier note here blamed sort
# compile time "growing brutally with array length"; r3 measurement
# localized that to lax.associative_scan — now banned, see
# ops/groupby.py — while sort itself compiles in ~20-60s at any
# multi-million-row shape. Compaction remains worthwhile for runtime.)
_SHRINK_MIN_CAPACITY = 1 << 17
# up to this many live rows a sparse build side is packed on the device
# by _pack_parts (top_k over the live positions, small gathers: 37 ms for
# 66 live rows in 2^24 slots of two 8-byte columns on a v5e); a larger
# one by _pack_sorted, one sort that carries the columns. The host's
# pass brings every slot over first (84 ms there, and more a column;
# PERF.md section 6, PR 33) and is kept for nested columns, which no
# sort carries
_DEVICE_PACK_MAX_SLOTS = 1 << 12


def _flag(site: str, flag) -> bool:
    """Read one device flag back: the host waits for whatever computes
    it (`sync.<site>` in a profiler trace)."""
    with host_sync(site, 1):
        return bool(flag)


def _flag_word(site: str, flag):
    """_flag for a flag that may be a G.flag_word: (overflowed, the
    reduce found its input in key order); a plain flag never says the
    second."""
    with host_sync(site, 1):
        word = int(flag)
    return bool(word & 1), bool(word & G.ORDERED)


def _count(site: str, mask) -> int:
    """Live rows of a device mask, read back (`sync.<site>`, with the
    count as its stat `rows`)."""
    with host_sync(site, 8) as span:
        n = int(jnp.sum(mask))
        span.set_metadata(rows=n)
    return n


def _shrink_prefix(batch: RelBatch, live_count: int) -> RelBatch:
    """Slice a PREFIX-dense batch (live rows packed from slot 0 — the
    sort-path aggregation output contract) down to a bucketed capacity."""
    new_cap = max(bucket_capacity(live_count), 16)
    if new_cap >= batch.capacity:
        return batch
    cols = [
        Column(
            c.type,
            c.data[:new_cap],
            None if c.valid is None else c.valid[:new_cap],
            c.dictionary,
        )
        for c in batch.columns
    ]
    live = None if batch.live is None else batch.live[:new_cap]
    return RelBatch(cols, live)


class HashBuildSink(Operator):
    """Consumes the build side, consolidates, builds the LookupSource
    (HashBuilderOperator.java:58 — one sort instead of row inserts).

    Out-of-core: under memory pressure the revocation protocol flips
    the sink into GRACE mode (HashBuilderOperator spill states,
    HashBuilderOperator.java:163-206): accumulated and future batches
    hash-partition to disk and the probe runs partition-wise."""

    def __init__(self, bridge: JoinBridge, key_channels: Sequence[int],
                 input_schema: Sequence[Tuple[T.DataType, Optional[Dictionary]]],
                 memory_context=None, force_spill: bool = False):
        self._bridge = bridge
        self._keys = list(key_channels)
        self._schema = list(input_schema)
        self._inputs: List[RelBatch] = []
        self._memory = memory_context
        self._grace = None
        self._state_lock = named_lock("HashBuildSink._state_lock")
        if force_spill:
            # adaptive spill-mode re-plan (skewed/oversized build): open
            # the grace partitions up front instead of waiting for the
            # pool's revocation callback — every batch partitions to
            # disk on arrival and the device never holds the full build
            from trino_tpu.exec.spill import GracePartitionSpill

            self._grace = GracePartitionSpill(GRACE_PARTITIONS, self._keys)
        if self._memory is not None:
            self._memory.set_revoker(self._revoke_memory)

    def add_input(self, batch: RelBatch) -> None:
        with self._state_lock:
            if self._grace is not None:
                self._grace.add(batch)
                return
            self._inputs.append(batch)
        self._track_memory()

    def _track_memory(self) -> None:
        if self._memory is None:
            return
        from trino_tpu.runtime.memory import batch_bytes

        with self._state_lock:
            total = sum(batch_bytes(b) for b in self._inputs)
        try:
            self._memory.set_bytes(total)
        except Exception:
            if total == 0:
                raise
            self._revoke_memory()
            return
        # a concurrent revocation may have spilled the inputs between the
        # snapshot and set_bytes; advertise only what is STILL revocable
        # (set_bytes cannot run under _state_lock — the pool's victim
        # callbacks re-enter this operator)
        with self._state_lock:
            still = sum(batch_bytes(b) for b in self._inputs)
        self._memory.set_revocable_bytes(min(total, still))

    def _revoke_memory(self) -> None:
        """startMemoryRevoke: dump accumulated build rows into the
        hash-partitioned spill and continue in grace mode."""
        with self._state_lock:
            if self._finishing or self._grace is not None and not self._inputs:
                return
            if self._grace is None:
                from trino_tpu.exec.spill import GracePartitionSpill

                self._grace = GracePartitionSpill(
                    GRACE_PARTITIONS, self._keys
                )
            for b in self._inputs:
                self._grace.add(b)
            self._inputs = []
        if self._memory is not None:
            self._memory.set_bytes(0)
            self._memory.set_revocable_bytes(0)

    def finish(self) -> None:
        if self._finishing:
            return
        with self._state_lock:
            self._finishing = True
            grace, inputs = self._grace, self._inputs
            self._inputs = []
        if grace is not None:
            for b in inputs:
                grace.add(b)
            self._bridge.grace = grace
            self._bridge.build_schema = self._schema
            self._bridge.build_key_channels = list(self._keys)
            if self._memory is not None:
                self._memory.set_bytes(0)
                self._memory.set_revocable_bytes(0)
            return
        parts = tuple(inputs or [empty_batch(self._schema)])
        total_cap = sum(b.capacity for b in parts)
        if total_cap >= _SHRINK_MIN_CAPACITY:
            # sparse build side (e.g. a HAVING-filtered aggregate):
            # host-compact so the lookup build and every probe compile
            # at the live size, not the upstream capacity
            with host_sync("join.build_rows", 4 * len(parts)) as span:
                counts = jax.device_get(
                    [jnp.sum(b.live_mask().astype(jnp.int32)) for b in parts]
                )
                n_live = int(sum(int(c) for c in counts))
                span.set_metadata(rows=n_live)
            target = max(bucket_capacity(n_live), 16)
            if target * 4 <= total_cap:
                if target <= _DEVICE_PACK_MAX_SLOTS:
                    parts = (_pack_parts(parts, target),)
                elif all(_sortable(b) for b in parts):
                    parts = (_pack_sorted(parts, target),)
                else:
                    from trino_tpu.exec.serde import Page as _Page
                    from trino_tpu.exec.serde import concat_pages

                    merged_host = concat_pages(
                        [_Page.from_batch(b) for b in parts]
                    )
                    parts = (merged_host.to_batch(target),)
        ls, merged = _consolidate_build(parts, tuple(self._keys))
        self._bridge.lookup_source = ls
        self._bridge.build_batch = merged
        self._bridge.key_dicts = [
            merged.columns[c].dictionary for c in self._keys
        ]
        self._bridge.build_key_channels = list(self._keys)
        if self._memory is not None:
            # the retained build side still occupies its reservation,
            # but it is NOT revocable anymore (the probe needs it live);
            # leaving revocable bytes registered would make the pool's
            # revoke loop pick a victim that can never release
            self._memory.set_revocable_bytes(0)

    def get_output(self) -> Optional[RelBatch]:
        return None

    def is_finished(self) -> bool:
        return self._finishing


class MxuJoinAggOperator(Operator):
    """Join-project-aggregate over the MXU (ops/mxu_join.py): consumes
    probe pages of an inner single-key equi-join whose aggregate
    arguments are all probe-side and whose group columns are all
    build-side, and contracts each page against the one-hot key-id
    indicator on the systolic array instead of expanding pairs.

    Emits ONE partial page at finish — per build row, the summed probe
    contributions of its key — which the planner feeds into an ordinary
    HashAggregationOperator for the final grouping. The build side
    arrives through the standard JoinBridge (the planner runs the build
    pipeline to completion first); the planner constructs that sink
    without a memory context, so the bridge never flips to grace mode
    under this operator."""

    def __init__(self, bridge: JoinBridge, key_channel: int, aggs,
                 group_channels: Sequence[int]):
        self._bridge = bridge
        self._key = key_channel
        # static layout for the kernel: agg kinds + probe arg channels
        self._kinds = tuple(a.kind for a in aggs)
        self._args = tuple(a.arg_channel for a in aggs)
        self._groups = list(group_channels)
        self._analysis = None
        self._acc = None
        self._outputs: List[RelBatch] = []

    def _analyze(self):
        from trino_tpu.ops import mxu_join as MJ

        ls = self._bridge.lookup_source
        build = self._bridge.build_batch
        kc = self._bridge.build_key_channels[0]
        col = build.columns[kc]
        kid, kid_by_pos, distinct, n_distinct, hash_pure = (
            MJ.build_key_analysis(
                col.data, col.valid_mask(), build.live_mask(),
                ls.sorted_hash, ls.perm,
            )
        )
        # one host read at the build barrier: hash-collision purity
        # decides the probe lookup path for the whole query
        self._analysis = (
            kid, kid_by_pos, distinct, n_distinct,
            _flag("join.hash_pure", hash_pure),
        )

    def add_input(self, probe: RelBatch) -> None:
        from trino_tpu.ops import mxu_join as MJ

        if self._analysis is None:
            self._analyze()
        _kid, kid_by_pos, distinct, n_distinct, hash_pure = self._analysis
        kcol = probe.columns[self._key]
        kv = kcol.valid_mask()
        arg_data, arg_valid = [], []
        for ch in self._args:
            if ch is None:  # count_star placeholder, unread
                arg_data.append(kcol.data)
                arg_valid.append(kv)
            else:
                c = probe.columns[ch]
                arg_data.append(c.data)
                arg_valid.append(c.valid_mask())
        capacity = self._bridge.build_batch.capacity
        use_mxu = (
            capacity <= MJ.MAX_CAPACITY and probe.capacity <= MJ.MAX_ROWS
        )
        sums = MJ.probe_page_sums(
            self._bridge.lookup_source, kid_by_pos, distinct, n_distinct,
            kcol.data, kv, probe.live_mask(),
            tuple(arg_data), tuple(arg_valid), self._kinds, capacity,
            use_mxu, jax.default_backend() != "tpu", hash_pure,
        )
        self._acc = (
            list(sums)
            if self._acc is None
            else [a + s for a, s in zip(self._acc, sums)]
        )

    def finish(self) -> None:
        from trino_tpu.ops import mxu_join as MJ

        if self._finishing:
            return
        self._finishing = True
        if self._analysis is None:
            self._analyze()
        kid = self._analysis[0]
        build = self._bridge.build_batch
        if self._acc is None:
            # no probe pages arrived: zero accumulators, nothing matches
            n_cols = sum(
                2 if k == "sum" else (1 if k == "count" else 0)
                for k in self._kinds
            )
            z = jnp.zeros(build.capacity, dtype=jnp.int64)
            self._acc = [z] * (n_cols + 1)
        live, outs = MJ.finalize_partials(
            kid, build.live_mask(), tuple(self._acc), self._kinds
        )
        cols = [build.columns[ch] for ch in self._groups]
        for data, valid in outs:
            cols.append(Column(T.BIGINT, data, valid, None))
        self._outputs.append(RelBatch(cols, live))

    def get_output(self) -> Optional[RelBatch]:
        return self._outputs.pop(0) if self._outputs else None

    def is_finished(self) -> bool:
        return self._finishing and not self._outputs


def _without_unread(cols: List[Column], unread: Tuple[int, ...]) -> List[Column]:
    """`cols` with zeros for the channels nothing downstream reads
    (`sql/local_planner.unread_join_outputs`): inside a jitted program
    the gather that made such a column is then dead code."""
    return [
        Column(c.type, jnp.zeros_like(c.data), None, c.dictionary)
        if i in unread and type(c) is Column else c
        for i, c in enumerate(cols)
    ]


@partial(jax.jit, static_argnames=("out_cap", "pkc", "bkc", "unread"))
def _expand_pairs(ls, probe: RelBatch, build: RelBatch, keys, valids,
                  lo, counts, out_cap: int, pkc=None, bkc=None, unread=()):
    """Expansion + pair gather in one device program (JoinProbe +
    LookupJoinPageBuilder fused — join/LookupJoinOperator.java:36).

    When the join keys are plain pass-through columns (pkc/bkc name
    them in the probe/build schemas), the hash-collision verify runs on
    the GATHERED pair columns — the expansion would gather them anyway,
    so the separate per-key verify gathers disappear."""
    on_pairs = pkc is not None
    pi, bi, ok = J.expand_matches(
        ls, keys, valids, lo, counts, out_cap, verify=not on_pairs
    )
    pairs_probe = probe.gather(pi)
    pairs_build = build.gather(bi)
    if on_pairs:
        for pc, bc in zip(pkc, bkc):
            a = pairs_probe.columns[pc]
            b = pairs_build.columns[bc]
            eqd = a.data == b.data
            if getattr(eqd, "ndim", 1) == 2:  # long-decimal limb pairs
                eqd = eqd.all(axis=-1)
            ok = ok & eqd
            if a.valid is not None:
                ok = ok & a.valid
            if b.valid is not None:
                ok = ok & b.valid
    cols = list(pairs_probe.columns) + list(pairs_build.columns)
    return pi, bi, ok, RelBatch(_without_unread(cols, unread), ok)


@jax.jit
def _fanout_le_one(counts):
    """Device flag: no probe row has more than one candidate match."""
    return jnp.all(counts <= 1)


@partial(jax.jit, static_argnames=("pkc", "bkc", "unread"))
def _expand_pairs_fanout1(ls, probe: RelBatch, build: RelBatch, keys,
                          valids, lo, counts, pkc=None, bkc=None, unread=()):
    """Fanout<=1 expansion (every probe row matches at most one build
    row — the PK-side FK join that dominates TPC-H/DS): the pair batch
    IS the probe batch with the matched build row appended. The probe
    columns pass through untouched — no offsets, no repeat machinery,
    and none of the ~16ms/M-element random gathers the general
    expansion pays per probe column. Caller guarantees max(counts) <= 1
    (checked on device alongside the deferred total)."""
    spos = jnp.clip(lo, 0, ls.perm.shape[0] - 1)
    bi = take_clip(ls.perm, spos)
    ok = counts > 0
    pairs_build = build.gather(bi)
    if pkc is not None:
        for pc, bc in zip(pkc, bkc):
            a = probe.columns[pc]
            b = pairs_build.columns[bc]
            eqd = a.data == b.data
            if getattr(eqd, "ndim", 1) == 2:  # long-decimal limb pairs
                eqd = eqd.all(axis=-1)
            ok = ok & eqd
            if a.valid is not None:
                ok = ok & a.valid
            if b.valid is not None:
                ok = ok & b.valid
    else:
        for pk, pv, bk, bv in zip(keys, valids, ls.key_cols, ls.key_valids):
            b = take_clip(bk, jnp.clip(bi, 0, bk.shape[0] - 1))
            bvv = take_clip(bv, jnp.clip(bi, 0, bv.shape[0] - 1))
            eqd = pk == b
            if getattr(eqd, "ndim", 1) == 2:
                eqd = eqd.all(axis=-1)
            ok = ok & eqd & pv & bvv
    live = probe.live_mask() & ok
    pi = jnp.arange(probe.capacity, dtype=jnp.int32)
    cols = list(probe.columns) + list(pairs_build.columns)
    return pi, bi, live, RelBatch(_without_unread(cols, unread), live)


@jax.jit
def _segment_any(counts, pi, ok, probe_capacity):
    """Per-probe-row 'any verified pair' WITHOUT scatter: pi is emitted
    in nondecreasing order by expand_matches, so each probe row's pairs
    are the segment [off-counts, off) — reduce via cumsum+gather."""
    e = ok.shape[0]
    okc = jnp.cumsum(ok.astype(jnp.int32))
    exc = okc - ok.astype(jnp.int32)
    off = jnp.cumsum(counts)
    start = off - counts
    seg = take_clip(okc, jnp.clip(off - 1, 0, max(e - 1, 0))) - take_clip(
        exc, jnp.clip(start, 0, max(e - 1, 0))
    )
    return (counts > 0) & (seg > 0)


@partial(jax.jit, static_argnames=("out_cap", "pkc", "bkc", "unread",
                                   "residual_fn"))
def _flag_build_rows(ls, probe: RelBatch, build: RelBatch, keys, valids,
                     lo, counts, flags, totals, out_cap=None,
                     pkc=None, bkc=None, unread=(), residual_fn=None):
    """One probe batch of a semi- or anti-join whose PRESERVED side is
    the build: `flags` (a build slot: some pair held) with this batch's
    pairs added, and `totals` (pairs the residual saw, pairs it kept).

    Without `out_cap`: every probe row's FIRST candidate, the fanout-one
    expansion whatever the counts (the probe batch as it stands, one
    build row gathered beside it: no offsets, no sorts). With it: the
    candidates AFTER the first, expanded the general way into `out_cap`
    slots. The two are every pair; a join key that is nearly unique on
    the build side (TPC-H Q21: 1.04 late lines of one nation's suppliers
    an order) then pays the general expansion for a few rows in a
    hundred and not for a batch and a bit, which a power of two of
    slots would double. Of the pairs' columns only what the keys'
    compare and the residual read is gathered (`unread`); the residual
    is typed build side first."""
    if out_cap is None:
        _, bi, ok, pairs = _expand_pairs_fanout1(
            ls, probe, build, keys, valids, lo, counts, pkc=pkc, bkc=bkc,
            unread=unread,
        )
    else:
        _, bi, ok, pairs = _expand_pairs(
            ls, probe, build, keys, valids, lo + 1,
            jnp.maximum(counts - 1, 0), out_cap, pkc=pkc, bkc=bkc,
            unread=unread,
        )
    seen = jnp.sum(ok.astype(jnp.int64))
    if residual_fn is not None:
        n = len(probe.columns)
        cols = list(pairs.columns[n:]) + list(pairs.columns[:n])
        ok = ok & residual_fn(RelBatch(cols, ok))
    flags = J.build_matched_flags(build.capacity, bi, ok, prior=flags)
    return flags, totals + jnp.stack([seen, jnp.sum(ok.astype(jnp.int64))])


@jax.jit
def _probe_row_counts(counts):
    """(candidate pairs, probe rows with a candidate) of a probe batch:
    the second says how many pairs are somebody's first."""
    return jnp.stack([jnp.sum(counts), jnp.sum((counts > 0).astype(jnp.int32))])


@jax.jit
def _flagged_rows(build: RelBatch, flags):
    """(live build rows, those of them flagged)."""
    live = build.live_mask()
    return jnp.stack([jnp.sum(live.astype(jnp.int64)),
                      jnp.sum((live & flags).astype(jnp.int64))])


@jax.jit
def _left_unmatched(probe: RelBatch, build: RelBatch, matched):
    """Unmatched probe rows with NULL build columns (LEFT outer arm).
    null_column keeps nested build columns structurally valid."""
    from trino_tpu.block import null_column

    nulls = [
        null_column(c.type, probe.capacity, c.dictionary)
        for c in build.columns
    ]
    return RelBatch(
        list(probe.columns) + nulls, probe.live_mask() & ~matched
    )


def _right_unmatched(probe_schema, build: RelBatch, matched_b):
    """Unmatched BUILD rows with NULL probe columns (the RIGHT/FULL
    outer arm — join/LookupOuterOperator.java analogue)."""
    from trino_tpu.block import null_column

    nulls = [
        null_column(t, build.capacity, d) for t, d in probe_schema
    ]
    return RelBatch(
        nulls + list(build.columns), build.live_mask() & ~matched_b
    )


@jax.jit
def _preserved_probe_rows(live, unmatched):
    """(probe rows, those of them nothing matched) of one batch of a
    LEFT join that preserves its probe side."""
    return jnp.stack([jnp.sum(live.astype(jnp.int64)),
                      jnp.sum(unmatched.astype(jnp.int64))])


@jax.jit
def _mark_build_rows(flags, bi, ok):
    """`flags` (a build slot: some pair held) with one probe batch's
    pairs added: the build rows `bi` of the pairs that are `ok`. The
    one program a LEFT join that builds the side it preserves runs a
    batch beyond an inner join's."""
    return J.build_matched_flags(flags.shape[0], bi, ok, prior=flags)


def _unmatched_build_rows(probe_schema, build: RelBatch, flags, unmatched: int):
    """The build rows no pair flagged, build columns first, NULLs for
    the probe's: the rows a LEFT join that built its preserved side
    owes at its input's end. Packed to the power of two that holds them
    where that is at most half the build side's slots (what follows
    then runs at their size, not the lookup's)."""
    from trino_tpu.block import null_column

    rows = build.mask(~flags)
    target = max(bucket_capacity(unmatched), 16)
    if target * 2 <= rows.capacity and _sortable(rows):
        rows = _pack_rows(rows, target)
    nulls = [null_column(t, rows.capacity, d) for t, d in probe_schema]
    return RelBatch(list(rows.columns) + nulls, rows.live_mask())


def make_residual_fn(residual: Bound):
    """Plan-time compiled residual evaluator over pair batches."""

    @jax.jit
    def fn(pairs: RelBatch):
        # nested columns ride whole (same contract as
        # make_filter_project_fn) so map/row navigation works in
        # residual conjuncts too
        cols = [
            c if c.type.is_nested else c.data for c in pairs.columns
        ]
        vs = [c.valid for c in pairs.columns]
        d, v = residual.fn(cols, vs)
        return d if v is None else (d & v)

    return fn


class LookupJoinOperator(Operator):
    """Probe side (LookupJoinOperator.java:36). join_type in
    {inner, left, semi, anti}. Output schema for inner/left =
    [probe columns..., build columns...]; for semi/anti = probe columns.

    `residual` (optional Bound over the concatenated pair schema) is
    evaluated on candidate pairs BEFORE match flags are computed, which
    is what makes filtered semi/anti joins (Q21-style `l2.suppkey <>
    l1.suppkey`) correct.

    `build_preserved` (semi/anti/left; the plan's `JoinNode.build_left`):
    the side the join preserves is the BUILD and the other side probes.
    Of a semi- or anti-join nothing leaves while batches arrive: each
    adds its pairs to a flag a build row (`_flag_build_rows`, the
    residual on the pairs first), and at finish the flagged build rows
    (semi) or the others (anti) go out as one batch. `residual_fn` is
    then typed build side first, and `unread` names the pair channels
    (probe first) it does not read. A LEFT join's batches are expanded
    as an inner join's are (the fanout-one form where no probe row has
    two candidates) and leave as they come, BUILD columns first (the
    plan's left), each adding its pairs to the build rows' flags
    (`_mark_build_rows`); at finish, and at the end of every grace
    partition, the build rows no pair flagged go out once with NULLs for
    the probe's columns (`_unmatched_build_rows`). METRICS
    `join_outer_side.build` / `.probe` count the LEFT and FULL joins by
    the side they preserved (a FULL join preserves both and counts
    under `.probe`), `join_outer_build_rows` / `join_outer_unmatched_rows`
    what the span `sync.join.outer_flags` read back at a LEFT join's
    finish, whichever side it built (the live build rows, and the
    preserved side's rows that went out with NULLs).
    METRICS `join_semi_side.source` / `.filtering` count the semi- and
    anti-joins by the side they built, `join_expand_launches.first` /
    `.general` / `.fanout1` every expansion by its form, and
    `semi_pairs_seen` / `semi_pairs_kept` / `semi_build_rows` /
    `semi_build_flagged` what the span `sync.join.semi_flags` read back
    at finish.

    METRICS `join_probe_path.blocked` and `.sorted` count the probe
    batches by the form their bounds took (`ops/join.probe_path`: a
    function of the build side's slots, the batch's and the word's
    bits).
    """

    def __init__(
        self,
        bridge: JoinBridge,
        key_channels: Sequence[int],
        join_type: str,
        probe_schema: Sequence[Tuple[T.DataType, Optional[Dictionary]]],
        residual: Optional[Bound] = None,
        residual_fn=None,
        unread: Sequence[int] = (),
        build_preserved: bool = False,
    ):
        if build_preserved and join_type not in ("semi", "anti", "left"):
            raise ValueError("build_preserved is a semi-, anti- or left join's")
        # a semi- or anti-join that flags its build rows and puts out
        # nothing else / a LEFT join that puts out pairs and owes the
        # unflagged build rows
        self._build_preserved = build_preserved and join_type != "left"
        self._outer_build = build_preserved and join_type == "left"
        if self._build_preserved:
            self.span_stats = {"preserved": 1}
        elif join_type in ("left", "full"):
            self.span_stats = {"outer": 1}
        self._bridge = bridge
        self._keys = list(key_channels)
        self._type = join_type
        self._probe_schema = list(probe_schema)
        self._residual = residual
        self._residual_fn = (
            residual_fn
            if residual_fn is not None
            else (make_residual_fn(residual) if residual is not None else None)
        )
        # output channels no operator downstream reads: an inner join
        # whose pairs nothing else looks at (no residual) hands them on
        # as zeros, and its expansion gathers nothing for them
        self._unread = (
            tuple(unread)
            if self._build_preserved
            or join_type == "inner" and self._residual_fn is None else ()
        )
        # build_preserved: (pairs the residual saw, pairs it kept), on
        # the device until finish
        self._pair_totals = None
        self._outputs: List[RelBatch] = []
        self._remap_cache: Dict[tuple, jnp.ndarray] = {}
        # grace mode: probe rows hash-partition to disk alongside the
        # spilled build; partitions join pairwise at finish
        self._probe_spill = None
        # FULL outer: build-side matched bitmap accumulated across probe
        # batches; unmatched build rows emit at finish (LookupOuter)
        self._build_matched = None
        # LEFT outer, probe side preserved: (probe rows, those that went
        # out with NULLs) so far, on the device until finish
        self._probe_unmatched = None
        # Pipelined expansion (the per-batch `int(total)` host read
        # is a synchronisation point that drains the device): batch i's
        # match total starts copying to the host the moment its count
        # pass is dispatched, and is only READ when batch i+1 arrives —
        # by then the copy has overlapped with the next batch's
        # upstream device work, so the expansion still gets its EXACT
        # bucketed capacity (small outputs stay small) without a
        # blocking round trip per batch.
        self._probe_pending: List[dict] = []

    def needs_input(self) -> bool:
        return not self._outputs and not self._finishing

    def add_input(self, probe: RelBatch) -> None:
        if self._bridge.grace is not None:
            if self._probe_spill is None:
                from trino_tpu.exec.spill import GracePartitionSpill

                self._probe_spill = GracePartitionSpill(
                    self._bridge.grace.n, self._keys
                )
            self._probe_spill.add(probe)
            return
        self._probe_one(
            self._bridge.lookup_source,
            self._bridge.build_batch,
            self._bridge.key_dicts,
            probe,
        )

    def _probe_one(self, ls, build, key_dicts, probe: RelBatch) -> None:
        keys = []
        valids = []
        remapped = False
        for i, c in enumerate(self._keys):
            col = probe.columns[c]
            v = col.valid_mask()
            build_dict = key_dicts[i] if key_dicts else None
            if (
                col.dictionary is not None
                and build_dict is not None
                and col.dictionary != build_dict
            ):
                # cross-dictionary string join: remap probe codes onto the
                # build dictionary by VALUE; absent values -> -1 (never
                # matches a build code). TypeOperators' equality contract
                # for the dictionary-encoded representation.
                ck = (col.dictionary.values, build_dict.values)
                remap = self._remap_cache.get(ck)
                if remap is None:
                    remap = jnp.asarray(
                        [build_dict.code(v) for v in col.dictionary.values],
                        dtype=jnp.int32,
                    )
                    self._remap_cache[ck] = remap
                keys.append(
                    take_clip(remap, col.data)
                )
                valids.append(v)
                remapped = True
            elif getattr(col.data, "ndim", 1) == 2:
                # long-decimal key: probe by its two int64 limbs (the
                # build side split identically in _consolidate_build)
                keys.extend([col.data[:, 0], col.data[:, 1]])
                valids.extend([v, v])
            else:
                keys.append(col.data)
                valids.append(v)
        live = probe.live_mask()
        METRICS.increment("join_probe_path." + J.probe_path(
            ls.build_capacity, probe.capacity, ls.hash_bits
        ))
        lo, counts, total = J.probe_counts(ls, keys, valids, live)
        if self._build_preserved:
            # (both forms of its expansion run whatever the fanout)
            scalars = {"total": _probe_row_counts(counts)}
        else:
            scalars = {"total": total, "fan1": _fanout_le_one(counts)}
        for scalar in scalars.values():
            try:
                scalar.copy_to_host_async()
            except AttributeError:
                pass
        self._probe_pending.append({
            "ls": ls, "build": build, "probe": probe, "keys": keys,
            "valids": valids, "lo": lo, "counts": counts,
            "remapped": remapped, **scalars,
        })
        # depth-1 pipeline: settle the PREVIOUS batch — its total has
        # been in flight while this batch's upstream ran on device
        while len(self._probe_pending) > 1:
            self._expand_oldest()

    def _expand_oldest(self) -> None:
        rec = self._probe_pending.pop(0)
        ls, build, probe = rec["ls"], rec["build"], rec["probe"]
        # pair-column verify only when every key is a pass-through
        # column (a dictionary remap substitutes codes the pair batch
        # does not carry)
        pkc = bkc = None
        if not rec.get("remapped") and self._bridge.build_key_channels:
            pkc = tuple(self._keys)
            bkc = tuple(self._bridge.build_key_channels)
        if self._build_preserved:
            self._flag_oldest(rec, pkc, bkc)
            return
        with host_sync("join.match_total", 8) as span:
            total = int(rec["total"])
            span.set_metadata(rows=total, probe_slots=probe.capacity)
        dense = total * 4 >= rec["probe"].capacity
        if dense and "fan1" in rec and _flag("join.fanout_one", rec["fan1"]):
            # fanout<=1 (PK-side FK join) AND most probe rows match:
            # pairs = probe batch + one matched build row, probe
            # columns untouched — skips the repeat expansion AND every
            # probe-side gather. Sparse joins keep the exact-capacity
            # expansion below: reusing the 4M-padded probe batch for a
            # 30k-match join would drag the FULL padding through every
            # downstream operator (measured 4x on TPC-H Q3)
            METRICS.increment("join_expand_launches.fanout1")
            pi, bi, ok, pairs = _expand_pairs_fanout1(
                ls, probe, build, rec["keys"], rec["valids"],
                rec["lo"], rec["counts"], pkc=pkc, bkc=bkc,
                unread=self._unread,
            )
            fanout1 = True
        else:
            out_cap = bucket_capacity(max(total, 1))
            if dense:
                # most probe rows match: the pairs take the probe
                # batch's slots at least, so that what follows compiles
                # for ONE shape whatever the batch holds (Q9's last
                # packed batch is 50 to 62 % full by the colour: half of
                # 2^20 slots for a few colours, 14 more programs and
                # 466 s of compiling for each of them; PERF.md section
                # 6, PR 35)
                out_cap = max(out_cap, probe.capacity)
            METRICS.increment("join_expand_launches.general")
            pi, bi, ok, pairs = _expand_pairs(
                ls, probe, build, rec["keys"], rec["valids"],
                rec["lo"], rec["counts"], out_cap, pkc=pkc, bkc=bkc,
                unread=self._unread,
            )
            fanout1 = False
        if self._outer_build:
            # (the plan's left is the build: its columns first; a list
            # reordered on the host, no program)
            cols = list(pairs.columns)
            n = len(probe.columns)
            pairs = RelBatch(cols[n:] + cols[:n], pairs.live)
        if self._residual_fn is not None:
            ok = ok & self._residual_fn(pairs)
            pairs = RelBatch(pairs.columns, ok)
        # (a fanout-one batch's pairs are its probe rows)
        matched = ok if fanout1 else None
        if self._type == "inner":
            self._outputs.append(pairs)
            return
        if self._outer_build:
            if self._build_matched is None:
                self._build_matched = jnp.zeros(build.capacity, dtype=jnp.bool_)
            self._build_matched = _mark_build_rows(self._build_matched, bi, ok)
            self._outputs.append(pairs)
            return
        if matched is None:
            matched = _segment_any(rec["counts"], pi, ok, probe.capacity)
        if self._type == "semi":
            self._outputs.append(probe.mask(matched))
            return
        if self._type == "anti":
            self._outputs.append(probe.mask(~matched))
            return
        if self._type in ("mark", "mark_exists"):
            # mark join: probe rows pass through with an appended
            # BOOLEAN match column (SemiJoinNode's semiJoinOutput — the
            # device for subqueries in general positions: under OR, in
            # the SELECT list). "mark" carries IN's three-valued
            # semantics on the validity lane: no match is UNKNOWN when
            # the probe key is NULL against a nonempty build, or the
            # build side contains NULL keys; "mark_exists" is two-valued.
            valid = None
            if self._type == "mark":
                build = self._bridge.build_batch
                b_live = build.live_mask()
                nonempty = jnp.any(b_live)
                has_null = jnp.zeros((), dtype=jnp.bool_)
                for ch in self._bridge.build_key_channels:
                    bc = build.columns[ch]
                    if bc.valid is not None:
                        has_null = has_null | jnp.any(b_live & ~bc.valid)
                pv = None
                for vv in rec["valids"]:
                    pv = vv if pv is None else (pv & vv)
                probe_null = (
                    ~pv if pv is not None
                    else jnp.zeros_like(matched)
                )
                unknown = (~matched) & (
                    (probe_null & nonempty) | has_null
                )
                valid = ~unknown
            col = Column(T.BOOLEAN, matched, valid, None)
            self._outputs.append(
                RelBatch(list(probe.columns) + [col], probe.live_mask())
            )
            return
        if self._type == "left":
            self._outputs.append(pairs)
            unmatched = _left_unmatched(probe, build, matched)
            self._outputs.append(unmatched)
            n = _preserved_probe_rows(probe.live_mask(), unmatched.live)
            self._probe_unmatched = (
                n if self._probe_unmatched is None else self._probe_unmatched + n
            )
            return
        if self._type == "full":
            self._outputs.append(pairs)
            self._outputs.append(_left_unmatched(probe, build, matched))
            self._build_matched = J.build_matched_flags(
                build.capacity, bi, ok, prior=self._build_matched
            )
            return
        raise NotImplementedError(self._type)

    def _emit_unmatched(self, build: RelBatch) -> None:
        """A LEFT join, the input's end (or a grace partition's). One
        that built its preserved side: the build rows no pair flagged,
        once. Either way what the join came to is read back and counted:
        the live build rows and the preserved rows that went out with
        NULLs (the build's here, else the probe's, which left with their
        batches)."""
        flags = self._build_matched
        if flags is None:
            flags = jnp.zeros(build.capacity, dtype=jnp.bool_)
        probe = self._probe_unmatched
        if probe is None:
            probe = np.zeros(2, dtype=np.int64)
        with host_sync("join.outer_flags", 32) as span:
            (rows, flagged), (preserved, unmatched) = (
                [int(x) for x in v]
                for v in jax.device_get((_flagged_rows(build, flags), probe))
            )
            if self._outer_build:
                preserved, unmatched = rows, rows - flagged
            span.set_metadata(build_rows=rows, unmatched=unmatched,
                              preserved_rows=preserved, build_slots=build.capacity,
                              preserved="build" if self._outer_build else "probe")
        METRICS.increment("join_outer_build_rows", rows)
        METRICS.increment("join_outer_unmatched_rows", unmatched)
        if self._outer_build and unmatched:
            self._outputs.append(_unmatched_build_rows(
                self._probe_schema, build, flags, unmatched
            ))
        self._build_matched = self._probe_unmatched = None

    def _flag_oldest(self, rec: dict, pkc, bkc) -> None:
        """build_preserved: the oldest pending batch's pairs into the
        build rows' flags: every row's first candidate through the
        fanout-one form at the batch's own capacity, and the candidates
        after the first, where there are any, the general way into the
        power of two that holds them."""
        ls, build, probe = rec["ls"], rec["build"], rec["probe"]
        with host_sync("join.match_total", 8) as span:
            total, firsts = (int(x) for x in jax.device_get(rec["total"]))
            span.set_metadata(rows=total, probe_slots=probe.capacity,
                              first_candidates=firsts)
        if not total:
            return
        if self._build_matched is None:
            self._build_matched = jnp.zeros(build.capacity, dtype=jnp.bool_)
            self._pair_totals = jnp.zeros(2, dtype=jnp.int64)
        out_caps = [None]
        if total > firsts:
            # (an eighth of the batch's slots at least: the offsets' two
            # sorts are over the batch's rows whatever the pairs, and
            # every smaller power of two would be one more program of 45
            # to 50 s of compiling, PERF.md section 6, PR 40)
            out_caps.append(max(bucket_capacity(total - firsts),
                                probe.capacity // 8))
        for out_cap in out_caps:
            METRICS.increment("join_expand_launches." + (
                "first" if out_cap is None else "general"
            ))
            self._build_matched, self._pair_totals = _flag_build_rows(
                ls, probe, build, rec["keys"], rec["valids"], rec["lo"],
                rec["counts"], self._build_matched, self._pair_totals,
                out_cap=out_cap, pkc=pkc, bkc=bkc,
                unread=self._unread, residual_fn=self._residual_fn,
            )

    def _emit_preserved(self, build: RelBatch) -> None:
        """build_preserved, the input's end: the flagged build rows
        (semi) or the others (anti), and what the pairs came to."""
        flags = self._build_matched
        if flags is None:
            flags = jnp.zeros(build.capacity, dtype=jnp.bool_)
            self._pair_totals = jnp.zeros(2, dtype=jnp.int64)
        with host_sync("join.semi_flags", 32) as span:
            (seen, kept), (rows, flagged) = (
                [int(x) for x in v] for v in jax.device_get(
                    (self._pair_totals, _flagged_rows(build, flags))
                )
            )
            span.set_metadata(pairs_seen=seen, pairs_kept=kept,
                              build_rows=rows, build_flagged=flagged,
                              kind=self._type)
        for name, n in (("pairs_seen", seen), ("pairs_kept", kept),
                        ("build_rows", rows), ("build_flagged", flagged)):
            METRICS.increment("semi_" + name, n)
        self._outputs.append(
            build.mask(flags if self._type == "semi" else ~flags)
        )
        self._build_matched = self._pair_totals = None

    def _resolve_spec(self) -> None:
        """Drain every pending probe batch (finish / partition end)."""
        while self._probe_pending:
            self._expand_oldest()

    def finish(self) -> None:
        if self._finishing:
            return
        self._finishing = True
        if self._type in ("semi", "anti"):
            METRICS.increment("join_semi_side." + (
                "source" if self._build_preserved else "filtering"
            ))
        elif self._type in ("left", "full"):
            METRICS.increment("join_outer_side." + (
                "build" if self._outer_build else "probe"
            ))
        self._resolve_spec()
        if self._bridge.grace is None:
            if self._build_preserved:
                self._emit_preserved(self._bridge.build_batch)
            if self._type == "left":
                self._emit_unmatched(self._bridge.build_batch)
            if self._type == "full":
                build = self._bridge.build_batch
                mb = (
                    self._build_matched
                    if self._build_matched is not None
                    else jnp.zeros(build.capacity, dtype=jnp.bool_)
                )
                self._outputs.append(
                    _right_unmatched(self._probe_schema, build, mb)
                )
            return
        # grace probe (PartitionedConsumption analogue): for each hash
        # partition, rebuild that slice of the build side on device and
        # probe its probe-side pages — partition-wise correctness holds
        # because both sides routed by the same canonical key hash
        grace = self._bridge.grace
        for p in range(grace.n):
            probe_pages = (
                self._probe_spill.partition_pages(p)
                if self._probe_spill is not None
                else []
            )
            if not probe_pages and self._type != "full" and not (
                self._build_preserved and self._type == "anti"
            ) and not self._outer_build:
                continue  # before touching the build spill: no probe rows
            build_pages = grace.partition_pages(p)
            parts = tuple(
                [pg.to_batch() for pg in build_pages]
                or [empty_batch(self._bridge.build_schema)]
            )
            ls, merged = _consolidate_build(
                parts, tuple(self._bridge.build_key_channels)
            )
            key_dicts = [
                merged.columns[c].dictionary
                for c in self._bridge.build_key_channels
            ]
            # full outer: matched flags are PER PARTITION (each build row
            # lives in exactly one hash partition, so partition-local
            # flags are complete)
            self._build_matched = None
            for pg in probe_pages:
                self._probe_one(ls, merged, key_dicts, pg.to_batch())
            self._resolve_spec()
            if self._build_preserved:
                self._emit_preserved(merged)
            if self._type == "left":
                self._emit_unmatched(merged)
            if self._type == "full":
                mb = (
                    self._build_matched
                    if self._build_matched is not None
                    else jnp.zeros(merged.capacity, dtype=jnp.bool_)
                )
                self._outputs.append(
                    _right_unmatched(self._probe_schema, merged, mb)
                )
        if self._probe_spill is not None:
            self._probe_spill.close()
            self._probe_spill = None
        # the build spill is fully consumed too: release its files (the
        # probe operator is the bridge's single consumer)
        grace.close()
        self._bridge.grace = None

    def get_output(self) -> Optional[RelBatch]:
        if self._outputs:
            return self._outputs.pop(0)
        return None

    def is_finished(self) -> bool:
        return self._finishing and not self._outputs


@partial(jax.jit, static_argnames=("channels",))
def _df_domains(build: RelBatch, channels: tuple):
    """Per-key min/max over the build side's live+valid rows."""
    live = build.live_mask()
    out = []
    for c in channels:
        col = build.columns[c]
        w = live if col.valid is None else (live & col.valid)
        lo_n = minmax_neutral(col.data.dtype, "min")
        hi_n = minmax_neutral(col.data.dtype, "max")
        lo = jnp.min(jnp.where(w, col.data, jnp.asarray(lo_n, col.data.dtype)))
        hi = jnp.max(jnp.where(w, col.data, jnp.asarray(hi_n, col.data.dtype)))
        out.append((lo, hi, jnp.any(w)))
    return out


def _df_count(totals, batch: RelBatch, keep):
    """`totals` (rows in, rows kept) of a filter's batches so far, with
    this batch's added: the operator reads them back once, at finish."""
    return totals + jnp.stack([
        jnp.sum(batch.live_mask().astype(jnp.int64)),
        jnp.sum(keep.astype(jnp.int64)),
    ])


@jax.jit
def _df_filter(batch: RelBatch, keys, domains, totals):
    """Drop probe rows outside [lo, hi] on every key (NULL keys never
    match an inner/semi join, so they drop too)."""
    keep = batch.live_mask()
    for (c_data, c_valid), (lo, hi, any_rows) in zip(keys, domains):
        ok = (c_data >= lo) & (c_data <= hi) & any_rows
        if c_valid is not None:
            ok = ok & c_valid
        keep = keep & ok
    return batch.mask(keep), _df_count(totals, batch, keep)


# A build side of at most this many slots, on one integer key, filters
# probes by its key SET, not by its key range: every probe key is
# compared with every build key, on their low 32 bits (one fused
# compare-and-reduce, no gather, sort or scatter; a filter may pass a
# row the join then drops, never drop one that matches). A 2^20-row
# batch against 128 / 1,024 / 4,096 keys takes 2.1 / 2.4 / 4.9 ms on a
# v5e, launch included, where the range takes 1.1 and the probe the
# filter spares (probe_counts) 16.3 (PERF.md section 6, PR 33).
DF_SET_MAX_SLOTS = 1 << 12
# A build side of more slots than this has its usable keys counted (one
# readback a scan) and is compared in the power of two that holds them,
# this at least: a build side too small for HashBuildSink to pack
# (_SHRINK_MIN_CAPACITY) may be mostly dead slots (TPC-H Q9 at `tiny`:
# 110 coloured parts in 2,048 slots, 50 of the statement's 90 ms on a
# CPU). Not measured on the chip, where the builds that take the set
# are packed already (PERF.md section 6, PR 35).
DF_SET_MIN_SLOTS = 1 << 7
# A larger build side on one integer key filters by its key BITS where
# its keys lie scattered over a domain narrow enough to hold as one bit
# a value (2 M part keys are 256 KB, 60 M order keys 8 MB): a word
# gathered and a bit tested a row, exact, where the range would keep
# every row between the least key and the greatest. Measured on a v5e
# (PERF.md section 6, PR 35; a 2^20-row batch, launch and a readback
# included): the bits take 9.5-9.6 ms whatever the table's size (2^16
# to 2^22 words: the gather is paid a row, not a byte), the range 1.8,
# the set 3.1 / 5.5 at 1,024 / 4,096 keys, and the probe a dropped
# batch is spared (probe_counts) 14.4 against a build of 2^17 slots
# and 78.1 against one of 2^23. So the bits earn their 9.5 ms only
# where the batches behind them are packed (at most a quarter of the
# slots kept, below): a build side that fills more than
# DF_BITS_MAX_FILL of its domain gets the range. The table is made by
# one scatter of the build side's slots (1.5 ms for 2^17 slots into 2^16
# words, 10.1 for 2^20 into 2^22), which is what bounds them
# (DF_BITS_MAX_SLOTS); a wider domain (DF_BITS_MAX_DOMAIN: 16 MB of
# bits) was not measured. Whatever fits none of these gets the range.
# (On a scan in key order the word is not gathered: DF_WINDOW_ROWS.)
# A build side of more slots, up to DF_BITS_PLANNED_MAX_SLOTS (four such
# scatters once a join), has its keys' domain read back and takes the
# bits only where the PLAN expects it to fill at most DF_BITS_MAX_FILL of
# its key's value range (`key_fill`: the build side's estimated rows
# over the statistics' high - low + 1). TPC-H Q21's 0.8 to 1.5 M late
# lines of one nation's suppliers, in 2^20 to 3,407,872 slots, are a
# fortieth of l_orderkey's 60 M values: the range of their keys keeps
# every row of `orders` and of the 60 M-row scans they stand in front
# of. Where the plan says the build fills its range, or cannot say, such
# a build side keeps the range and reads nothing back (TPC-H Q18's 1.5 M
# customers in 2^21 slots: reading the domain to learn that they fill it
# cost its p50 0.3 %, PERF.md section 6, PR 40).
DF_BITS_MAX_SLOTS = 1 << 20
DF_BITS_PLANNED_MAX_SLOTS = 1 << 22
DF_BITS_MAX_DOMAIN = 1 << 27
DF_BITS_MAX_FILL = 0.25
# The gather is not paid where the scan hands the key on in stored order
# (the plan's `key_ordered`: a column the connector lists in
# `TableStatistics.ordered`, `lineitem` and `orders` by their order key,
# with nothing between the scan and the filter that moves rows): the
# batch's words are looked up by WINDOW (_df_filter_bits_window), a block
# of DF_WINDOW_ROWS rows sharing two adjacent table rows of
# DF_WINDOW_WORDS words (8,192 key values; 128 rows of `orders` span 16
# words, of `lineitem` 4 or 5) and each row picking its word among them.
# Measured on a v5e (PERF.md section 6, PR 42; a 2^20-row batch of SF10's
# l_orderkey against 2^21 words, device time a launch): the gather 7.76
# ms, the window 0.85 (0.84-0.95 at 128 to 512 rows a block and 128- or
# 256-word windows; 0.99 inside TPC-H Q21, where the gather is 7.35); a
# batch that does not fit its windows (the same keys shuffled) 7.77: the
# guard inside the program sends the whole batch to the gather, for 0.02
# ms, so the window is exact whatever the plan said and a wrong word
# costs time, never a row. A batch of no whole number of blocks takes
# the gather. The guard does not make the plan's word redundant: on keys
# that ask all over the table (l_partkey, l_suppkey; not a batch's own
# order keys shuffled, as above) the gather inside the `cond` costs 9.30
# ms for _df_filter_bits' 7.76 alone, at 2^12, 2^16 and 2^21 words
# alike (8.2 for 6.4 inside TPC-H Q9), so launching this program for
# EVERY bits batch cost Q9 4.5-5.3 % of its p50 (58 such batches a
# statement) and Q21 4 %: measured and taken back (PERF.md section 6,
# PR 42, after the review).
DF_WINDOW_ROWS = 128
DF_WINDOW_WORDS = 128
# The slots of the ONE batch the set filter gathers a scan's survivors
# into, and the most rows a batch may keep and still be gathered: below
# it a sort is no faster, and every smaller power of two would be one
# more shape for each of the join's programs to compile at.
DF_PACK_MIN_SLOTS = 1 << 10
# Behind a set or a bit table a batch that keeps more than that, and at
# most a quarter of its slots, is packed by one sort that carries its
# columns into a power of two of slots, no fewer than this share of the
# batch; the packed parts fill batches of the scan's own capacity, the
# shape the join's programs compile for anyway. A part is put where the
# rows of the one before it end, this share of the scan's slots on at
# least: a batch that keeps a little over a sixteenth (TPC-H Q9's
# `tomato`: 70,096 rows of 2^20, parts of 2^17 slots) then costs the
# joins its rows' worth of batches and not its slots' (4 for 8:
# PERF.md section 6, PR 35), and scans that keep a few rows in a
# hundred more or fewer hand the joins the same number of batches.
DF_PACK_PARTS = 16


@partial(jax.jit, static_argnames=("slots",))
def _df_key_set(build_keys, usable, slots: int):
    """What `_df_filter_set` compares with: the low 32 bits of the build
    side's keys in `slots` slots (no fewer than it has usable keys: they
    are moved to the front where the build side has more slots), a dead
    or NULL slot repeating a live one, and whether there is a live one
    at all."""
    low = build_keys.astype(jnp.int32)
    if slots < low.shape[0]:
        front = jnp.argsort(~usable, stable=True)[:slots]
        low, usable = low[front], usable[front]
    return jnp.where(usable, low, low[jnp.argmax(usable)]), jnp.any(usable)


@jax.jit
def _df_filter_set(batch: RelBatch, key, key_set, any_key, totals):
    """Keep the probe rows whose key is one of the build side's, by its
    low 32 bits (NULL keys never match an inner/semi join). Returns
    (batch, rows kept, totals)."""
    c_data, c_valid = key
    low = c_data.astype(jnp.int32)
    hit = jnp.any(low[:, None] == key_set[None, :], axis=1) & any_key
    keep = batch.live_mask() & hit
    if c_valid is not None:
        keep = keep & c_valid
    return (batch.mask(keep), jnp.sum(keep.astype(jnp.int32)),
            _df_count(totals, batch, keep))


@partial(jax.jit, static_argnames=("n_words",))
def _df_bit_table(build_keys, usable, lo, n_words: int):
    """One bit a value of [lo, lo + 32 * n_words), set where the build
    side has the key, as uint32 words: one scatter of the build side's
    slots (a dead or NULL slot lands outside and is dropped) and a
    reduce over the 32 bits of each word."""
    slot = jnp.where(
        usable, build_keys.astype(jnp.int64) - lo, jnp.int64(32 * n_words)
    )
    there = jnp.zeros(32 * n_words, dtype=jnp.bool_).at[slot].set(
        True, mode="drop"
    )
    bit = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(
        jnp.where(there.reshape(n_words, 32), bit, jnp.uint32(0)),
        axis=1, dtype=jnp.uint32,
    )


@jax.jit
def _df_filter_bits(batch: RelBatch, key, words, lo, hi, totals):
    """Keep the probe rows whose key the build side has: inside
    [lo, hi], and its bit set in `words` (one gather a row; NULL keys
    never match an inner/semi join). Returns (batch, rows kept,
    totals)."""
    c_data, c_valid = key
    k = c_data.astype(jnp.int64)
    slot = k - lo
    word = take_clip(
        words, jnp.clip(slot >> 5, 0, words.shape[0] - 1).astype(jnp.int32)
    )
    bit = (word >> (slot & 31).astype(jnp.uint32)) & jnp.uint32(1)
    keep = batch.live_mask() & (k >= lo) & (k <= hi) & (bit == 1)
    if c_valid is not None:
        keep = keep & c_valid
    return (batch.mask(keep), jnp.sum(keep.astype(jnp.int32)),
            _df_count(totals, batch, keep))


@jax.jit
def _df_filter_bits_window(batch: RelBatch, key, words, lo, hi, totals,
                           fallbacks):
    """`_df_filter_bits` for a batch whose key column is in order: the
    word of a row is not gathered, it is picked out of a WINDOW of the
    table that its block of DF_WINDOW_ROWS rows shares. The table is
    read as rows of DF_WINDOW_WORDS words; a block's window is the two
    adjacent rows from the one that holds the least word any of its
    rows that can match (live, not NULL, inside [lo, hi]) asks for, one
    gather of two table rows a block; a row's word is the window's lane
    at its distance from there (compare, select, reduce over the
    lanes). Exact whatever the batch: where some row that can match
    asks past its block's window (keys in no order, a sparse stretch)
    the whole batch takes `_df_filter_bits`' gather, and `fallbacks`
    counts it. A row that cannot match anchors no window and is kept by
    none. Returns (batch, rows kept, totals, fallbacks)."""
    c_data, c_valid = key
    k = c_data.astype(jnp.int64)
    slot = k - lo
    can = batch.live_mask() & (k >= lo) & (k <= hi)
    if c_valid is not None:
        can = can & c_valid
    n_words = words.shape[0]
    at = jnp.clip(slot >> 5, 0, n_words - 1).astype(jnp.int32)
    shift = (slot & 31).astype(jnp.uint32)
    width = DF_WINDOW_WORDS
    rows = n_words // width
    blocks = k.shape[0] // DF_WINDOW_ROWS
    at_b = at.reshape(blocks, DF_WINDOW_ROWS)
    can_b = can.reshape(blocks, DF_WINDOW_ROWS)
    first = jnp.min(jnp.where(can_b, at_b, jnp.int32(n_words - 1)), axis=1)
    row = first // width
    # (not below 0 for a row that can match: its block's window starts
    # at or before the least of them)
    rel = at_b - (row * width)[:, None]
    held = jnp.all(~can_b | (rel < 2 * width))

    def by_window():
        table = words.reshape(rows, width)
        window = jnp.concatenate([
            take_clip(table, row, axis=0),
            take_clip(table, jnp.minimum(row + 1, rows - 1), axis=0),
        ], axis=1)
        lane = jnp.arange(2 * width, dtype=jnp.int32)
        word = jnp.sum(
            jnp.where(rel[:, :, None] == lane[None, None, :],
                      window[:, None, :], jnp.uint32(0)),
            axis=2, dtype=jnp.uint32,
        )
        return (word.reshape(-1) >> shift) & jnp.uint32(1)

    def by_gather():
        return (take_clip(words, at) >> shift) & jnp.uint32(1)

    keep = can & (jax.lax.cond(held, by_window, by_gather) == 1)
    return (batch.mask(keep), jnp.sum(keep.astype(jnp.int32)),
            _df_count(totals, batch, keep),
            fallbacks + (~held).astype(jnp.int32))


@partial(jax.jit, static_argnames=("capacity",))
def _front_rows(batch: RelBatch, capacity: int) -> RelBatch:
    """The first `capacity` live rows of a sparse batch, in order, as a
    batch of that capacity: top_k over the live positions and one
    `capacity`-sized gather a column (no full-length sort)."""
    n = batch.capacity
    pos = jnp.where(
        batch.live_mask(), jnp.arange(n, dtype=jnp.int32), jnp.int32(n)
    )
    first = -jax.lax.top_k(-pos, capacity)[0]
    return RelBatch([c.gather(first) for c in batch.columns], first < n)


@partial(jax.jit, static_argnames=("capacity",))
def _pack_parts(parts: Tuple[RelBatch, ...], capacity: int) -> RelBatch:
    """The live rows of `parts`, in order, as ONE batch of `capacity`."""
    return _front_rows(concat_batches(list(parts)), capacity)


def _sortable(batch: RelBatch) -> bool:
    """Whether every column can ride a sort as payload (flat columns;
    nested ones keep their element stores elsewhere)."""
    return all(type(c) is Column for c in batch.columns)


@partial(jax.jit, static_argnames=("capacity",))
def _pack_rows(batch: RelBatch, capacity: int) -> RelBatch:
    """The first `capacity` live rows of `batch`, in order, at the front
    of a batch of `capacity` slots: ONE sort on the live rows' positions
    that carries every column as payload (a dead slot sorts last). No
    gather: a carried column costs a fraction of what gathering it at
    `capacity` places would (ops/groupby.py, the carried compaction)."""
    n = batch.capacity
    pos = jnp.where(
        batch.live_mask(), jnp.arange(n, dtype=jnp.int32), jnp.int32(n)
    )
    lanes, layout = [], []
    for col in batch.columns:
        if getattr(col.data, "ndim", 1) == 2:  # long-decimal limbs
            data = [col.data[:, j] for j in range(col.data.shape[1])]
        else:
            data = [col.data]
        layout.append((len(data), col.valid is not None))
        lanes.extend(data)
        if col.valid is not None:
            lanes.append(col.valid)
    lanes = [x.astype(jnp.int8) if x.dtype == jnp.bool_ else x for x in lanes]
    carried, order = [], pos
    budget = G._MAX_SORT_OPERANDS - 1
    for at in range(0, len(lanes), budget):
        out = jax.lax.sort(
            tuple([pos] + lanes[at:at + budget]), num_keys=1, is_stable=False
        )
        order = out[0]
        carried.extend(x[:capacity] for x in out[1:])
    cols, at = [], 0
    for col, (n_data, has_valid) in zip(batch.columns, layout):
        data = carried[at:at + n_data]
        at += n_data
        data = data[0] if n_data == 1 else jnp.stack(data, axis=1)
        valid = None
        if has_valid:
            valid = carried[at].astype(jnp.bool_)
            at += 1
        cols.append(Column(col.type, data.astype(col.data.dtype), valid,
                           col.dictionary))
    return RelBatch(cols, order[:capacity] < n)


@partial(jax.jit, static_argnames=("capacity",))
def _pack_sorted(parts: Tuple[RelBatch, ...], capacity: int) -> RelBatch:
    """The live rows of `parts`, in order, as ONE batch of `capacity`
    (`_pack_parts` for a capacity a top_k is no good at)."""
    return _pack_rows(concat_batches(list(parts)), capacity)


@partial(jax.jit, static_argnames=("capacity",))
def _pack_room(part: RelBatch, capacity: int) -> RelBatch:
    """`capacity` empty slots of `part`'s columns: where a scan's packed
    parts are put one behind the other (`_pack_place`)."""

    def room(x):
        return jnp.zeros((capacity,) + x.shape[1:], x.dtype)

    cols = [
        Column(c.type, room(c.data),
               None if c.valid is None else room(c.valid), c.dictionary)
        for c in part.columns
    ]
    return RelBatch(cols, jnp.zeros(capacity, dtype=jnp.bool_))


@jax.jit
def _pack_place(room: RelBatch, part: RelBatch, at) -> RelBatch:
    """`part` written into `room` from slot `at` on, its mask with it. A
    packed part's live rows are its first, so the next part, put where
    they end, overwrites only dead slots."""

    def put(x, y):
        return jax.lax.dynamic_update_slice(
            x, y, (at,) + (0,) * (y.ndim - 1)
        )

    cols = [
        Column(c.type, put(c.data, p.data),
               None if c.valid is None else put(c.valid, p.valid),
               c.dictionary)
        for c, p in zip(room.columns, part.columns)
    ]
    return RelBatch(cols, put(room.live, part.live_mask()))


@partial(jax.jit, static_argnames=("capacity",))
def _pack_take(room: RelBatch, capacity: int):
    """(the first `capacity` slots of `room` as a batch, `room` with what
    lay behind them moved to its front)."""

    def head(x):
        return x[:capacity]

    def rest(x):
        return jnp.concatenate([x[capacity:], jnp.zeros_like(x[:capacity])])

    def cut(f):
        cols = [
            Column(c.type, f(c.data),
                   None if c.valid is None else f(c.valid), c.dictionary)
            for c in room.columns
        ]
        return RelBatch(cols, f(room.live))

    return cut(head), cut(rest)


class DynamicFilterOperator(Operator):
    """Probe-side pruning from build-side key domains — the LOCAL form
    of dynamic filtering (DynamicFilterSourceOperator + DynamicFilter
    SPI, SURVEY.md §5.6): the build pipeline has already completed when
    the probe pipeline starts, so the bridge's build batch supplies
    min/max domains directly. The coordinator-distributed form (domains
    shipped to remote scan fragments) rides the same domain computation.
    Applies to inner/semi probes only; dictionary-coded keys are skipped
    unless both sides share the dictionary (code order is only
    meaningful within one dictionary).

    One of three filters, by what the build side is (`_prepare`): on one
    integer key a small build side (DF_SET_MAX_SLOTS) filters by its key
    SET, a larger one whose keys lie scattered over a narrow domain by
    its key BITS (past DF_BITS_MAX_SLOTS only where the plan's `key_fill`
    expects the domain sparse; a batch's words are picked out of a window
    a block where the plan's `key_ordered` says the scan hands the key
    on in stored order, gathered a row otherwise), and everything else
    by the RANGE of each key. Behind the set and the bits the probe sees
    only rows that will match, so
    their batches are mostly dead slots, and the operator packs them
    before the join sorts anything. A batch's count of survivors is read
    back one batch late (the next batch's filter is on the device by
    then). While batches keep no more rows than one small batch holds
    (DF_PACK_MIN_SLOTS), the survivors of successive batches are
    gathered into that one batch; from the first batch that keeps more,
    each batch is packed (`_front_rows` while the part is small, one
    sort that carries the columns beyond) into a part of a sixteenth of
    its slots or more (DF_PACK_PARTS) and put behind the parts before it
    (`_pack_place`: where their rows end, a sixteenth of the scan's slots
    on at least), and what they fill goes out in batches of the scan's
    capacity, as they fill and at finish (a scan whose parts fill no
    batch puts out one batch, of the power of two that holds them).
    The first batch that keeps over a quarter of its slots ends the
    reading: the rest of the scan leaves masked and unread, by the range
    where the set would cost more than it drops."""

    def __init__(self, bridge: JoinBridge, key_channels: Sequence[int],
                 reverse: bool = False, key_fill: Optional[float] = None,
                 key_ordered: bool = False, under_aggregate: bool = False):
        self._bridge = bridge
        self._keys = list(key_channels)
        # not at the end of the filtered side, in front of the probe, but
        # UNDER the aggregation that side ends in, on its group keys
        # (`JoinNode.filter_under_aggregate`): the same filters in front
        # of the aggregation's input, told apart by the stat
        # `under_aggregate` and counted once an operator; the batches it
        # hands the aggregation are `agg_filtered_input.batches`
        self._under_aggregate = under_aggregate
        if under_aggregate:
            METRICS.increment("df_under_aggregate")
        # whether the plan found the ONE key to be a column its connector
        # stores in order, scanned with nothing in between that moves
        # rows: the bits of such a batch are looked up a window a block
        # (_df_filter_bits_window), which stays exact if the plan is wrong
        self._key_ordered = key_ordered
        # batches of the window program that took its gather after all,
        # counted on the device and read with `totals` at finish (None:
        # no bits, or not in order)
        self._window_fallbacks = None
        # the plan's estimate of the share of its ONE key's value range
        # that the build side's rows fill (None: it cannot say); consulted
        # for a build side of over DF_BITS_MAX_SLOTS slots only
        self._key_fill = key_fill
        # in front of the FILTERING side of a semi- or anti-join whose
        # preserved side is the build (a filtering row whose key no
        # preserved row has decides nothing): the same filters, counted
        # apart (`df_reverse_rows_in` / `_kept`, stat `reverse`)
        self._reverse = reverse
        if reverse:
            self.span_stats = {"reverse": 1}
        if under_aggregate:
            self.span_stats = {**getattr(self, "span_stats", {}), "under_aggregate": 1}
        self._domains = None
        self._key_set = None
        self._bits = None
        self._active_channels: Optional[List[int]] = None
        self._outs: List[RelBatch] = []
        # (rows in, rows kept) so far, on the device until finish, and
        # what the host knows of them: batches, their slots, the filter
        self._totals = None
        self._batches = self._slots = self._key_bytes = 0
        self._path = None
        # filtered batches whose count of survivors is not read yet
        self._unread: List[tuple] = []
        # whether the survivors' counts are still read and acted on
        self._gathering = True
        # the set filter's survivors, gathered over the scan's batches
        # into ONE batch of DF_PACK_MIN_SLOTS slots: the join and what
        # follows it then run once, not once a scan batch
        self._gathered: Optional[RelBatch] = None
        self._gathered_rows = 0
        # packed parts put one behind the other until they fill a batch
        # of the scan's capacity: the slots they lie in (a batch and the
        # largest part more), how many of them are taken, the scan's
        # capacity
        self._packing = False
        self._room: Optional[RelBatch] = None
        self._room_taken = 0
        self._part_capacity = 0
        # whether a batch of laid parts, or one left unpacked, has gone
        # out at the scan's capacity
        self._laid_full = False

    def _prepare(self, probe: RelBatch) -> None:
        build = self._bridge.build_batch
        if build is None:  # grace mode: no device build to read domains from
            self._active_channels = []
            return
        key_dicts = self._bridge.key_dicts or [None] * len(self._keys)
        active = []
        for i, c in enumerate(self._keys):
            if getattr(probe.columns[c].data, "ndim", 1) == 2:
                continue  # long-decimal keys: no scalar min/max domain
            probe_dict = probe.columns[c].dictionary
            if key_dicts[i] is None and probe_dict is None:
                active.append((i, c))
            elif key_dicts[i] is not None and key_dicts[i] == probe_dict:
                active.append((i, c))
        self._active_channels = active
        if not active:
            return
        self._totals = jnp.zeros(2, dtype=jnp.int64)
        key = None
        if len(active) == 1:
            key = build.columns[self._bridge.build_key_channels[active[0][0]]]
            if not jnp.issubdtype(key.data.dtype, jnp.integer):
                key = None
        with host_span("df.prepare", build_slots=build.capacity) as span:
            if key is not None and build.capacity <= DF_SET_MAX_SLOTS:
                usable = build.live_mask() & key.valid_mask()
                slots = build.capacity
                if slots > DF_SET_MIN_SLOTS:
                    # every probe row is compared with every slot: a
                    # sparse build side's keys are counted, once, and
                    # compared in the power of two that holds them
                    slots = min(slots, max(
                        bucket_capacity(_count("join.dynamic_filter_keys", usable)),
                        DF_SET_MIN_SLOTS,
                    ))
                self._key_set = _df_key_set(key.data, usable, slots)
                self._path = "set"
                span.set_metadata(path="set", slots=slots)
                return
            self._use_range()
            if key is None or build.capacity > (
                DF_BITS_PLANNED_MAX_SLOTS
                if self._key_fill is not None
                and self._key_fill <= DF_BITS_MAX_FILL else DF_BITS_MAX_SLOTS
            ):
                span.set_metadata(path="range")
                return
            usable = build.live_mask() & key.valid_mask()
            lo, hi, any_key = self._domains[0]
            with host_sync("join.dynamic_filter_domain", 32):
                lo, hi, any_key, keys = jax.device_get(
                    (lo, hi, any_key, jnp.sum(usable.astype(jnp.int32)))
                )
            domain = int(hi) - int(lo) + 1 if any_key else 0
            span.set_metadata(keys=int(keys), domain=domain)
            if not 0 < domain <= DF_BITS_MAX_DOMAIN or (
                keys > domain * DF_BITS_MAX_FILL
            ):
                span.set_metadata(path="range")
                return
            n_words = max(bucket_capacity(-(-domain // 32)), 128)
            low = jnp.asarray(int(lo), dtype=jnp.int64)
            self._bits = (
                _df_bit_table(key.data, usable, low, n_words), low,
                jnp.asarray(int(hi), dtype=jnp.int64),
            )
            self._domains, self._path = None, "bits"
            if self._key_ordered:
                self._window_fallbacks = jnp.zeros((), dtype=jnp.int32)
            span.set_metadata(path="bits", table_bytes=4 * n_words)

    def _use_range(self) -> None:
        all_domains = _df_domains(
            self._bridge.build_batch, tuple(self._bridge.build_key_channels)
        )
        self._domains = [all_domains[i] for i, _ in self._active_channels]
        self._key_set = self._bits = None
        self._path = "range"

    def needs_input(self) -> bool:
        return not self._outs and not self._finishing

    def add_input(self, batch: RelBatch) -> None:
        if self._active_channels is None:
            self._prepare(batch)
        if not self._active_channels:
            self._outs.append(batch)
            return
        keys = tuple(
            (batch.columns[c].data, batch.columns[c].valid)
            for _, c in self._active_channels
        )
        self._batches += 1
        self._slots += batch.capacity
        self._key_bytes = sum(data.dtype.itemsize for data, _ in keys)
        if self._key_set is not None:
            METRICS.increment("df_filter_path.set")
            out, kept, self._totals = _df_filter_set(
                batch, keys[0], *self._key_set, self._totals
            )
        elif self._bits is not None:
            METRICS.increment("df_filter_path.bits")
            if self._key_ordered and batch.capacity % DF_WINDOW_ROWS == 0:
                METRICS.increment("df_bits_lookup.window")
                out, kept, self._totals, self._window_fallbacks = (
                    _df_filter_bits_window(
                        batch, keys[0], *self._bits, self._totals,
                        self._window_fallbacks,
                    )
                )
            else:
                METRICS.increment("df_bits_lookup.gather")
                out, kept, self._totals = _df_filter_bits(
                    batch, keys[0], *self._bits, self._totals
                )
        else:
            METRICS.increment("df_filter_path.range")
            out, self._totals = _df_filter(
                batch, keys, tuple(self._domains), self._totals
            )
            self._outs.append(out)
            return
        if not self._gathering or out.capacity < 4 * DF_PACK_MIN_SLOTS:
            # (a batch that small gains nothing from being packed)
            self._outs.append(out)
            return
        try:
            kept.copy_to_host_async()
        except AttributeError:
            pass
        self._unread.append((out, kept))
        # this batch's filter is on the device: settle the one before
        # (and this one too, once nothing more is read)
        while len(self._unread) > 1 or self._unread and not self._gathering:
            self._settle_oldest()

    def _settle_oldest(self) -> None:
        out, kept = self._unread.pop(0)
        if not self._gathering:
            self._outs.append(out)
            return
        with host_sync("join.dynamic_filter", 4) as span:
            kept = int(kept)
            span.set_metadata(rows=kept)
        if not kept:
            return
        if kept <= DF_PACK_MIN_SLOTS and not self._packing:
            if self._gathered_rows + kept > DF_PACK_MIN_SLOTS:
                self._emit_gathered()
            packed = _front_rows(out, DF_PACK_MIN_SLOTS)
            self._gathered = packed if self._gathered is None else _pack_parts(
                (self._gathered, packed), DF_PACK_MIN_SLOTS
            )
            self._gathered_rows += kept
            return
        self._emit_gathered()
        slots = max(bucket_capacity(kept), out.capacity // DF_PACK_PARTS)
        if slots * 4 > out.capacity or not _sortable(out):
            # the filter is not that selective here: no more readbacks,
            # and where the set keeps most of a batch, no more of its
            # compares (4.9 ms a 2^20-row batch at 4,096 keys against
            # the range's 1.1, PERF.md section 6, PR 33): the range from
            # here on
            self._gathering = False
            if self._key_set is not None and kept * 4 > out.capacity:
                self._use_range()
            self._emit_parts()
            self._laid_full = True
            self._outs.append(out)
            return
        self._packing = True
        METRICS.increment("df_pack_batches_in")
        if out.capacity > self._part_capacity:
            # (a scan's last batch may be a smaller one: its part goes
            # behind the others all the same)
            self._emit_parts()
        # (HashBuildSink's rule: top_k while a part is small, one sort
        # that carries the columns beyond)
        pack = _front_rows if slots <= _DEVICE_PACK_MAX_SLOTS else _pack_rows
        part = pack(out, slots)
        if self._room is None:
            # a part has a quarter of the scan's slots at most
            self._room = _pack_room(part, out.capacity + out.capacity // 4)
            self._part_capacity = out.capacity
        self._room = _pack_place(
            self._room, part, np.int32(self._room_taken)
        )
        # the next part goes where this one's rows end, a sixteenth of
        # the scan's slots on at least: scans that differ by a few rows
        # in a hundred hand the joins the same number of batches, and a
        # part that needs more slots than that (the next power of two:
        # twice as many) takes its rows' worth, not its slots'
        self._room_taken += max(kept, out.capacity // DF_PACK_PARTS)
        while self._room_taken >= self._part_capacity:
            self._laid_full = True
            self._take_packed(self._part_capacity)

    def _emit_gathered(self) -> None:
        if self._gathered is not None:
            self._outs.append(self._gathered)
        self._gathered, self._gathered_rows = None, 0

    def _take_packed(self, capacity: int) -> None:
        batch, self._room = _pack_take(self._room, capacity)
        self._room_taken = max(self._room_taken - capacity, 0)
        METRICS.increment("df_pack_batches_out")
        self._outs.append(batch)

    def _emit_parts(self, last: bool = False) -> None:
        """What is left of the packed parts, as one batch."""
        if self._room_taken:
            capacity = self._part_capacity
            if last and not self._laid_full:
                # the scan's only packed batch: no join has compiled for
                # the scan's capacity on its account, so it takes the
                # power of two that holds its parts
                capacity = min(
                    capacity, max(bucket_capacity(self._room_taken), 16)
                )
            self._laid_full = True
            self._take_packed(capacity)
        self._room, self._room_taken = None, 0

    def finish(self) -> None:
        if self._finishing:
            return
        self._finishing = True
        while self._unread:
            self._settle_oldest()
        self._emit_gathered()
        self._emit_parts(last=True)
        if self._totals is not None:
            with host_sync("join.dynamic_filter_totals", 16) as span:
                totals, fallbacks = jax.device_get(
                    (self._totals, self._window_fallbacks)
                )
                rows_in, kept = (int(x) for x in totals)
                span.set_metadata(
                    rows_in=rows_in, rows_kept=kept, batches=self._batches,
                    slots=self._slots, path=self._path,
                    key_bytes=self._key_bytes, reverse=int(self._reverse),
                )
                if self._under_aggregate:
                    span.set_metadata(under_aggregate=1)
            METRICS.increment("df_rows_in", rows_in)
            METRICS.increment("df_rows_kept", kept)
            if fallbacks is not None:
                METRICS.increment("df_bits_window_fallbacks", int(fallbacks))
            if self._reverse:
                METRICS.increment("df_reverse_rows_in", rows_in)
                METRICS.increment("df_reverse_rows_kept", kept)

    def get_output(self) -> Optional[RelBatch]:
        if self._outs and self._under_aggregate:
            METRICS.increment("agg_filtered_input.batches")
        return self._outs.pop(0) if self._outs else None

    def is_finished(self) -> bool:
        return self._finishing and not self._outs


def dynamic_filter_constraints(
    bridge: JoinBridge,
    key_types,
    key_names,
    max_in_list: int = 64,
) -> tuple:
    """Build-side key domains as ColumnConstraints — the connector
    reuse of dynamic filtering: when the probe is a bare scan, these
    fold into its splits' handles so build-side bounds prune parquet
    row groups (range_predicate) and mask rows (constraint_mask) at the
    source, not just at the DynamicFilterOperator.

    Per key: an IN-list when the build has few distinct values (exact
    multi-range domain), else the [min, max] range. Returns () until
    the build completes (the probe's driver runs after the build
    pipeline, so by first probe page the bridge is populated — but a
    non-blocking peek keeps this safe anywhere)."""
    from trino_tpu.connectors.pushdown import _pushable_type
    from trino_tpu.connectors.spi import ColumnConstraint

    build = bridge.build_batch
    if build is None:
        return ()
    with host_sync("join.dynamic_filter", build.capacity):
        live = np.asarray(jax.device_get(build.live_mask())).astype(bool)
    out = []
    for i, bc in enumerate(bridge.build_key_channels):
        if i >= len(key_names):
            break
        t = key_types[i]
        if t is None or not _pushable_type(t):
            continue
        col = build.columns[bc]
        if getattr(col.data, "ndim", 1) == 2 or col.dictionary is not None:
            continue  # long-decimal limbs / dictionary codes: no raw domain
        with host_sync("join.dynamic_filter", col.data.nbytes):
            data = np.asarray(jax.device_get(col.data))
            w = live
            if col.valid is not None:
                w = w & np.asarray(jax.device_get(col.valid)).astype(bool)
        vals = data[w]
        if vals.size == 0:
            continue  # empty build: the join itself yields nothing
        uniq = np.unique(vals)
        if uniq.size <= max_in_list:
            out.append(ColumnConstraint(
                key_names[i], "in", tuple(v.item() for v in uniq)
            ))
        else:
            out.append(
                ColumnConstraint(key_names[i], "ge", uniq[0].item())
            )
            out.append(
                ColumnConstraint(key_names[i], "le", uniq[-1].item())
            )
    return tuple(out)


# ---------------------------------------------------------------------------
# Cross join (NestedLoopJoinOperator.java analogue)
# ---------------------------------------------------------------------------


@jax.jit
def _consolidate_compact(parts: Tuple[RelBatch, ...]) -> RelBatch:
    return concat_batches(list(parts)).compact()


@partial(jax.jit, static_argnames=("b",))
def _cross_row(probe: RelBatch, build: RelBatch, b: int) -> RelBatch:
    def bcast(c):
        # long-decimal columns broadcast their (2,) limb row
        shape = (
            (probe.capacity, 2)
            if getattr(c.data, "ndim", 1) == 2
            else (probe.capacity,)
        )
        return jnp.broadcast_to(c.data[b], shape)

    bcols = [
        Column(
            c.type,
            bcast(c),
            None
            if c.valid is None
            else jnp.broadcast_to(c.valid[b], (probe.capacity,)),
            c.dictionary,
        )
        for c in build.columns
    ]
    return RelBatch(list(probe.columns) + bcols, probe.live)


class CrossJoinBuildSink(Operator):
    """Collects the (small) build side of a cross join."""

    def __init__(self, bridge: JoinBridge,
                 input_schema: Sequence[Tuple[T.DataType, Optional[Dictionary]]]):
        self._bridge = bridge
        self._schema = list(input_schema)
        self._inputs: List[RelBatch] = []

    def add_input(self, batch: RelBatch) -> None:
        self._inputs.append(batch)

    def finish(self) -> None:
        if self._finishing:
            return
        self._finishing = True
        merged = _consolidate_compact(tuple(self._inputs or [empty_batch(self._schema)]))
        self._bridge.build_batch = merged
        self._inputs = []

    def is_finished(self) -> bool:
        return self._finishing


class CrossJoinOperator(Operator):
    """Probe x build cartesian product; build side expected small
    (scalar-subquery bridges are 1 row)."""

    def __init__(self, bridge: JoinBridge):
        self._bridge = bridge
        self._outputs: List[RelBatch] = []

    def needs_input(self) -> bool:
        return not self._outputs and not self._finishing

    def add_input(self, probe: RelBatch) -> None:
        build = self._bridge.build_batch
        n_build = build.row_count()
        for b in range(n_build):
            self._outputs.append(_cross_row(probe, build, b))

    def get_output(self) -> Optional[RelBatch]:
        if self._outputs:
            return self._outputs.pop(0)
        return None

    def is_finished(self) -> bool:
        return self._finishing and not self._outputs


# ---------------------------------------------------------------------------
# Sink
# ---------------------------------------------------------------------------


class ScaledWriterSink:
    """Writer scale-out driven by OBSERVED output volume — the
    SCALED_WRITER_* partitioning + ScaledWriterScheduler analogue
    (main/sql/planner/SystemPartitioningHandle.java:53-54,
    main/execution/scheduler/ScaledWriterScheduler.java): start with
    one connector sink, add another whenever the written volume
    exceeds scale_rows x current writer count (up to max_writers), and
    round-robin batches across the active sinks. Volume is measured in
    batch capacities — static shapes, so no device sync on the write
    path."""

    COUNTERS = {"max_writers": 0, "scale_ups": 0}

    def __init__(self, make_sink, max_writers: int,
                 scale_rows: int = 1 << 21):
        self._make = make_sink
        self._sinks = [make_sink()]
        self._max = max(1, max_writers)
        self._scale_rows = scale_rows
        self._rows = 0
        self._rr = 0

    def append(self, batch) -> None:
        self._rows += batch.capacity
        if (
            self._rows > self._scale_rows * len(self._sinks)
            and len(self._sinks) < self._max
        ):
            self._sinks.append(self._make())
            ScaledWriterSink.COUNTERS["scale_ups"] += 1
        self._rr += 1
        self._sinks[self._rr % len(self._sinks)].append(batch)

    def finish(self) -> int:
        total = 0
        for s in self._sinks:
            total += s.finish()
        ScaledWriterSink.COUNTERS["max_writers"] = max(
            ScaledWriterSink.COUNTERS["max_writers"], len(self._sinks)
        )
        return total


class TableWriterOperator(Operator):
    """Terminal sink writing batches into a connector page sink
    (TableWriterOperator + TableFinishOperator collapsed — the commit
    handshake is the sink's finish(), whose row count lands in
    `rows_written`; SURVEY.md §2.6)."""

    def __init__(self, sink):
        self._sink = sink
        self.rows_written = 0

    def add_input(self, batch: RelBatch) -> None:
        self._sink.append(batch)

    def finish(self) -> None:
        if self._finishing:
            return
        self._finishing = True
        self.rows_written = self._sink.finish()

    def is_finished(self) -> bool:
        return self._finishing


class BufferSink(Operator):
    """Collects batches for a later pipeline (the LocalExchange handoff,
    main/operator/exchange/LocalExchange.java:67 — single-buffer form)."""

    def __init__(self):
        self.batches: List[RelBatch] = []

    def add_input(self, batch: RelBatch) -> None:
        self.batches.append(batch)

    def is_finished(self) -> bool:
        return self._finishing


class BufferSource(Operator):
    """Replays one or more BufferSinks' batches (consumer side of the
    handoff). The producing pipelines must run first."""

    def __init__(self, sinks: Sequence[BufferSink]):
        self._sinks = list(sinks)
        self._batches: Optional[List[RelBatch]] = None
        self._i = 0

    def needs_input(self) -> bool:
        return False

    def _all(self) -> List[RelBatch]:
        # producers are guaranteed finished before this pipeline runs
        if self._batches is None:
            self._batches = [b for s in self._sinks for b in s.batches]
        return self._batches

    def get_output(self) -> Optional[RelBatch]:
        batches = self._all()
        if self._i < len(batches):
            b = batches[self._i]
            self._i += 1
            return b
        return None

    def is_finished(self) -> bool:
        return self._i >= len(self._all())


class EnforceSingleRowOperator(Operator):
    """Scalar-subquery cardinality guard (the reference's
    EnforceSingleRowOperator): exactly one input row passes through;
    ZERO rows produce one all-NULL row (the SQL scalar-subquery empty
    result); more than one raises. The row-count sync happens once at
    finish — scalar subqueries are tiny by construction."""

    def __init__(self, input_schema: Sequence[Tuple[T.DataType, Optional[Dictionary]]]):
        self._schema = list(input_schema)
        self._inputs: List[RelBatch] = []
        self._out: Optional[RelBatch] = None

    def add_input(self, batch: RelBatch) -> None:
        self._inputs.append(batch)

    def finish(self) -> None:
        if self._finishing:
            return
        self._finishing = True
        total = sum(b.row_count() for b in self._inputs)
        if total > 1:
            raise RuntimeError("Scalar sub-query has returned multiple rows")
        if total == 1:
            merged = concat_batches(self._inputs) if len(self._inputs) > 1 \
                else self._inputs[0]
            self._out = merged.compact()
            self._inputs = []
            return
        # zero rows: one all-NULL row
        cols = [
            Column(
                t,
                jnp.zeros(16, dtype=t.dtype),
                jnp.zeros(16, dtype=jnp.bool_),
                d,
            )
            for t, d in self._schema
        ]
        live = jnp.zeros(16, dtype=jnp.bool_).at[0].set(True)
        self._out = RelBatch(cols, live)

    def get_output(self) -> Optional[RelBatch]:
        out, self._out = self._out, None
        return out

    def is_finished(self) -> bool:
        return self._finishing and self._out is None


class CollectorSink(Operator):
    """Terminal sink gathering result batches (the coordinator-protocol
    Query.getNextResult analogue for the in-process runner)."""

    def __init__(self):
        self.batches: List[RelBatch] = []

    def add_input(self, batch: RelBatch) -> None:
        self.batches.append(batch)

    def is_finished(self) -> bool:
        return self._finishing

    def rows(self) -> List[list]:
        return self.rows_with(())[0]

    def rows_with(self, extra: tuple):
        """Fetch all result batches PLUS auxiliary device values (e.g.
        deferred assertion flags) in ONE device->host round trip.
        device_get puts every leaf's transfer in flight before waiting,
        so the whole tree costs about one read-back, while a
        device-side pack-into-one-buffer program costs a dispatch plus
        a fetch. Don't 'optimize' this into a packing kernel."""
        # the statement's account has the bytes with or without a trace
        nbytes = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.batches)
        )
        with host_span("result.fetch", batches=len(self.batches)):
            with host_sync("result", nbytes):
                host_batches, host_extra = jax.device_get(
                    (self.batches, list(extra)))
            out = []
            with host_span("result.to_rows"):
                for b in host_batches:
                    out.extend(b.to_pylists())
        return out, host_extra


def _decimal_avg(acc, cnt, arg_sf: int, out_sf: int):
    """`acc / cnt` of a decimal sum scaled by `arg_sf`, at the scale
    `out_sf`, rounded half away from zero, in int64 (Trino's
    `avg(decimal)`: `(2 * sum + n) // (2 * n)` for a sum that is not
    negative). The float64 quotient this replaces is exact on a CPU and
    not on the chip, whose float64 is made of narrower words: of 100,000
    (sum, count) pairs up to TPC-H Q17's sizes the 2,196 exact halves
    among them came out one unit low 3 times (PERF.md section 6, PR 48).
    Exact while `2 * |sum| * (out_sf // g) + n` fits int64: a sum at the
    output's scale under 2^62, half the headroom the float64 path had
    (which was exact to 2^53 only); past it the doubling wraps.
    (It stands at the file's end, far below its caller `_agg_output`,
    because a Pallas program's cache key carries the line numbers of
    this file's frames up to `add_input`: a debt, ROADMAP S5.)"""
    import math

    g = math.gcd(int(out_sf), int(arg_sf))
    a = acc.astype(jnp.int64) * (int(out_sf) // g)
    n = jnp.maximum(cnt, 1).astype(jnp.int64) * (int(arg_sf) // g)
    return jnp.sign(a) * ((2 * jnp.abs(a) + n) // (2 * n))
