"""Mesh-resident distributed execution: ICI collectives as the SQL data plane.

The reference's distributed data plane is HTTP page streams between
worker JVMs, stitched by AddExchanges-inserted REMOTE exchanges
(optimizations/AddExchanges.java:266-276) and PartitionedOutputOperator
(output/PartitionedOutputOperator.java:46). The TPU-native form of the
same plan is ONE SPMD program over a `jax.sharding.Mesh`:

- every fragment's operator pipeline becomes a per-shard traced function
  over a fixed-capacity local RelBatch;
- a FIXED_HASH exchange between fragments becomes an on-device hash
  partition + `lax.all_to_all` over the mesh axis (ICI);
- a FIXED_BROADCAST exchange becomes `lax.all_gather`;
- the final gather boundary ships per-shard results to the host, where
  the root (single-partition) fragment runs through the ordinary local
  operator pipeline (merge-sorting RemoteSource included).

The compiler consumes the SAME SubPlan the HTTP scheduler would run
(sql/fragmenter.plan_distributed), so planning decisions — partial/final
aggregation, broadcast-vs-partitioned joins, merge exchanges, adaptive
partition counts — are shared between both data planes; only the
transport differs. Mesh execution is selected when all tasks would be
colocated on one host's device mesh (in-process workers); cross-host /
elastic / FTE execution keeps the pull+ack HTTP exchange.

Static-shape discipline: per-shard batch capacities are fixed at trace
time; group tables, join fan-out and the send blocks of large hash
exchanges use host-chosen capacities with device overflow flags and a
double-and-retrace protocol (the tryRehash analogue). A small batch's
all_to_all send block equals its capacity, so its exchange cannot
overflow (`exchange_block`).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PSpec

from trino_tpu.analysis.witness import named_lock

from trino_tpu import types as T
from trino_tpu.block import (
    Column,
    RelBatch,
    bucket_capacity,
    concat_batches,
    unify_column_dicts,
)
from trino_tpu.exec.operators import (
    _BATCH_REDUCER,
    AggSpec,
    _agg_output,
    _agg_slot_count,
    _append_long_decimal_slots,
    _expand_pairs,
    _left_unmatched,
    _lex128_reduce,
    _limb_join,
    _limb_split,
    _mxu_word_layout,
    _right_unmatched,
    _segment_any,
    _slot_merge_reducers,
    _slots_to_state,
    _slots_to_wire_column,
    agg_state_meta,
    make_filter_project_fn,
    make_residual_fn,
)
from trino_tpu.exec.serde import Page
from trino_tpu.expr.compile import ExprBinder
from trino_tpu.ops import groupby as G
from trino_tpu.ops import join as J
from trino_tpu.ops.gather import take_clip
from trino_tpu.ops.hashing import (
    canonical_hash_input,
    dictionary_lut,
    hash32,
    partition_of,
)
from trino_tpu.ops.sort import sort_order
from trino_tpu.sql import plan as P
from trino_tpu.sql.fragmenter import SubPlan

AXIS = "shard"

# Second named axis of the replicated serving plane: the full device
# set carves into a (replica x partition) grid (runtime/replicas.py)
# whose rows are identical 1-D sub-meshes over AXIS. Sub-mesh programs
# never reference REPLICA_AXIS — that is the point: the SAME
# prelude/step/flush lowerings serve any replica unchanged.
REPLICA_AXIS = "replica"

# Trace-time counters, monotonically increasing for the process life
# (capacity-overflow retraces count again). Tests must assert on
# before/after deltas, never absolute values.  `+=` on a dict slot is a
# non-atomic read-modify-write, and these fire from concurrent query
# threads — all bumps go through bump_mesh_counter.
_counters_lock = named_lock("mesh_plan._counters_lock")
MESH_COUNTERS = {"queries": 0, "all_to_all": 0, "all_gather": 0, "fallbacks": 0}  # guarded_by: _counters_lock

_METRICS_REGISTERED = False

# (time.perf_counter(), reason) of the process's last mesh->page
# fallbacks: the times behind the `mesh.fallbacks` counter, for a reader
# that holds them against a window (chipbench mesh_fallbacks_in_window)
FALLBACK_LOG: "collections.deque" = collections.deque(maxlen=1024)


def bump_mesh_counter(name: str, n: int = 1) -> None:
    with _counters_lock:
        MESH_COUNTERS[name] += n


def mesh_counter(name: str) -> int:
    with _counters_lock:
        return MESH_COUNTERS[name]


def mesh_counters_snapshot() -> dict:
    with _counters_lock:
        return dict(MESH_COUNTERS)


def register_mesh_metrics() -> None:
    """Expose MESH_COUNTERS as mesh_* gauges in the METRICS registry
    (and so in /v1/metrics). Idempotent; gauges read live at snapshot
    time, so the export tracks the trace-time counters for free."""
    global _METRICS_REGISTERED
    if _METRICS_REGISTERED:
        return
    from trino_tpu.runtime.metrics import METRICS

    for name in mesh_counters_snapshot():
        METRICS.register_gauge(
            f"mesh_{name}", lambda n=name: float(mesh_counter(n))
        )
    _METRICS_REGISTERED = True


class MeshUnsupported(Exception):
    """Plan shape the mesh compiler cannot run; the coordinator falls
    back to the host page-exchange data plane."""


# ---------------------------------------------------------------------------
# Eligibility
# ---------------------------------------------------------------------------


def _check_node(n: P.PlanNode) -> None:
    if isinstance(n, P.OutputNode):
        raise MeshUnsupported(type(n).__name__)
    if isinstance(n, P.WindowNode) and not n.partition_channels:
        # PARTITION BY-less windows are one global partition; the
        # fragmenter gathers them to the root, so a distributed one
        # reaching here is a plan bug — fall back loudly
        raise MeshUnsupported("window without partition keys")
    if isinstance(n, P.AggregateNode):
        for a in n.aggs:
            if a.distinct or a.kind not in _BATCH_REDUCER:
                raise MeshUnsupported(f"agg {a.kind}")
    if isinstance(n, P.JoinNode) and n.kind not in (
        "inner", "left", "full", "semi", "anti", "cross",
        "mark", "mark_exists",
    ):
        raise MeshUnsupported(f"join {n.kind}")
    for c in n.children():
        _check_node(c)


def _scan_nodes(n: P.PlanNode) -> List[P.ScanNode]:
    out = []
    if isinstance(n, P.ScanNode):
        out.append(n)
    for c in n.children():
        out.extend(_scan_nodes(c))
    return out


def _contains_scan(n: P.PlanNode) -> bool:
    return bool(_scan_nodes(n))


# ---------------------------------------------------------------------------
# In-trace exchange primitives
# ---------------------------------------------------------------------------


def _partition_ids(batch: RelBatch, channels: Sequence[int], n: int):
    """Row -> destination shard by canonicalized key hash (dictionary
    codes mapped through value-hash LUTs so co-partitioned producers
    agree — the exchange_ops._partition_ids contract). Dead rows -> -1."""
    lanes, valids = [], []
    for ch in channels:
        col = batch.columns[ch]
        lut = dictionary_lut(col.dictionary)
        if lut is not None:
            lanes.append(canonical_hash_input(col.data, jnp.asarray(lut)))
        else:
            lanes.append(canonical_hash_input(col.data))
        valids.append(col.valid_mask())
    pid = partition_of(hash32(lanes, valids), n)
    return jnp.where(batch.live_mask(), pid, -1)


# sorts with more operands than this gather their remaining payloads
# through the sorted row ids instead (ops/groupby._MAX_SORT_OPERANDS:
# XLA:TPU sort compile time grows with the operand count)
_MAX_SORT_PAYLOADS = 9
# a batch of at most this many rows keeps one send block of its own
# capacity per destination: overflow impossible, nothing to learn
_FULL_BLOCK_ROWS = 1 << 16


# a probe batch of at most this many rows gets a pair slot per row
_FULL_JOIN_ROWS = 1 << 20


def exchange_block(capacity: int, n: int) -> int:
    """Rows a sender reserves for each destination of a hash exchange.
    Small batches reserve their whole capacity. A large one reserves its
    even share and a quarter more: the full capacity would make every
    receiver's batch n times the sender's, nearly all of it dead, and
    every operator downstream pays for capacity, not for live rows. A
    destination that gets more than its block raises the site's overflow
    flag, and the runner's capacity ladder retraces with a wider block
    (as for group tables and join fan-out)."""
    if capacity <= _FULL_BLOCK_ROWS:
        return capacity
    share = -(-capacity // n)
    return min(capacity, -(-(share + share // 4) // 1024) * 1024)


def _scatter_to_blocks(arrays, pid, n: int, block: int):
    """Deal local rows into (n, block) destination blocks (the
    PagePartitioner analogue, on device). pid < 0 drops the row. One
    stable multi-operand sort by destination carries the columns (sorts
    are what this hardware does well; a scatter per column cost fifty
    times as much per row), then each destination's run is one
    contiguous slice. Returns (blocks, live_b, needed): `needed` is the
    largest destination run, which exceeds `block` when rows were cut
    (with block == capacity it cannot)."""
    cap = pid.shape[0]
    tgt = jnp.where(pid < 0, n, pid).astype(jnp.int32)
    # lax.sort operands share one shape: trailing lanes (long-decimal
    # (cap, 2) limb pairs) travel as one operand per lane, masks as int8
    lanes = []
    for a in arrays:
        a = a.astype(jnp.int8) if a.dtype == jnp.bool_ else a
        if a.ndim == 1:
            lanes.append(a)
        else:
            lanes.extend(a[:, i] for i in range(a.shape[1]))
    carried = lanes[:_MAX_SORT_PAYLOADS]
    rest = lanes[_MAX_SORT_PAYLOADS:]
    iota = [jnp.arange(cap, dtype=jnp.int32)] if rest else []
    out = jax.lax.sort(
        (tgt, *iota, *carried), num_keys=1, is_stable=True
    )
    st = out[0]
    moved = list(out[1 + len(iota):])
    moved += [take_clip(a, out[1]) for a in rest]
    starts = jnp.searchsorted(st, jnp.arange(n + 1, dtype=jnp.int32))
    counts = starts[1:] - starts[:-1]

    def deal(a):
        # padded so that no slice is clamped back into its neighbour
        a = jnp.concatenate([a, jnp.zeros((block,), a.dtype)])
        return jnp.stack([
            jax.lax.dynamic_slice_in_dim(a, starts[d], block)
            for d in range(n)
        ])

    dealt = [deal(a) for a in moved]
    blocks, i = [], 0
    for a in arrays:
        k = 1 if a.ndim == 1 else a.shape[1]
        b = dealt[i] if a.ndim == 1 else jnp.stack(dealt[i:i + k], axis=-1)
        blocks.append(b != 0 if a.dtype == jnp.bool_ else b)
        i += k
    slot = jnp.arange(block, dtype=jnp.int32)
    live_b = slot[None, :] < jnp.minimum(counts, block)[:, None]
    return blocks, live_b, jnp.max(counts)


def _exchange_with_pids(batch: RelBatch, pid, n: int,
                        block: Optional[int] = None):
    """Deal + all_to_all with caller-supplied destination ids (the
    shared tail of the plain and salted hash exchanges). With `block`
    given (fewer rows per destination than the batch holds) returns
    (batch, overflow flag): the rows the fullest destination needed
    where that is more than `block`, else 0."""
    full = block is None
    block = batch.capacity if full else block
    arrays = []
    for c in batch.columns:
        arrays.append(c.data)
        if c.valid is not None:
            arrays.append(c.valid)
    blocks, live_b, needed = _scatter_to_blocks(arrays, pid, n, block)
    bump_mesh_counter("all_to_all")
    ex = iter([jax.lax.all_to_all(b, AXIS, 0, 0, tiled=True) for b in blocks])
    live_ex = jax.lax.all_to_all(live_b, AXIS, 0, 0, tiled=True)
    cols = []
    for c in batch.columns:
        d = next(ex)
        # (n, block, lanes...) -> rows-major local layout
        d = d.reshape((-1,) + d.shape[2:])
        valid = None if c.valid is None else next(ex).reshape(-1)
        cols.append(Column(c.type, d, valid, c.dictionary))
    out = RelBatch(cols, live_ex.reshape(-1))
    if full:
        return out
    return out, jnp.where(needed > block, needed, 0).astype(jnp.int32)


def _exchange_hash(batch: RelBatch, channels: Sequence[int], n: int,
                   block: Optional[int] = None):
    """FIXED_HASH remote exchange as partition + all_to_all over ICI
    (`block`: see `_exchange_with_pids`)."""
    return _exchange_with_pids(
        batch, _partition_ids(batch, channels, n), n, block
    )


# -- skew-aware salted repartition (ISSUE 16, the JSPIM playbook) ------
#
# A hash exchange serializes every row of one key onto one shard; with
# a heavy hitter that IS the wall-clock. The salted form keeps cold
# keys on the normal hash path and treats the adaptive controller's
# observed hot keys specially: hot BUILD rows are replicated to every
# shard (riding the same all_gather a FIXED_BROADCAST uses), hot PROBE
# rows are dealt round-robin across shards. Every probe row still
# appears on exactly one shard and finds ALL build rows of its key
# there, so inner/left/semi/anti verdicts and pair multiplicity are
# exact; full-outer and mark joins are excluded by the annotation gate
# (replicated build rows would be counted once per shard).


def _hot_mask(batch: RelBatch, channels: Sequence[int], hot_values) -> jnp.ndarray:
    """Live rows whose (single) key column holds a hot value. Guarded
    to plain integer columns: dictionary codes must never be compared
    against observed key VALUES, and both join sides share the key
    type, so the guard degrades both sides together (no salting, plain
    hash placement — correct, just not skew-resistant)."""
    col = batch.columns[channels[0]]
    if col.dictionary is not None or col.data.ndim != 1:
        return jnp.zeros((batch.capacity,), dtype=bool)
    hv = jnp.asarray(list(hot_values), dtype=col.data.dtype)
    eq = (col.data[:, None] == hv[None, :]).any(axis=1)
    return eq & col.valid_mask() & batch.live_mask()


def _salted_exchange_hash(
    batch: RelBatch, channels: Sequence[int], n: int, hot_values, role: str
) -> RelBatch:
    """Salted FIXED_HASH exchange for one side of a skew-annotated
    join. role="build": cold rows all_to_all as usual, hot rows
    all_gather to every shard (output capacity 2*n*cap). role="probe":
    hot rows' destination is overridden to a round-robin salt (offset
    by the shard index so shard locality doesn't re-converge on one
    destination); capacity unchanged."""
    hot = _hot_mask(batch, channels, hot_values)
    if role == "build":
        cold = batch.mask(~hot)
        out = _exchange_with_pids(
            cold, _partition_ids(cold, channels, n), n
        )
        return concat_batches((out, _replicate(batch.mask(hot))))
    pid = _partition_ids(batch, channels, n)
    me = jax.lax.axis_index(AXIS).astype(jnp.int32)
    salt = (jnp.cumsum(hot.astype(jnp.int32)) - 1 + me) % n
    return _exchange_with_pids(
        batch, jnp.where(hot, salt.astype(pid.dtype), pid), n
    )


def _salted_local_partition(
    batch: RelBatch, channels: Sequence[int], n: int, hot_values, role: str
) -> RelBatch:
    """Salted hash output of a REPLICATED producer (every shard already
    holds all rows — the spool-substituted build side lands here).
    build: keep own partition plus every hot row (a zero-collective
    broadcast of the hot set). probe: deal each hot row to exactly one
    shard by its position — the batch is identical on every shard, so
    the deal is globally consistent without any collective."""
    pid = _partition_ids(batch, channels, n)
    me = jax.lax.axis_index(AXIS).astype(pid.dtype)
    hot = _hot_mask(batch, channels, hot_values)
    if role == "build":
        return batch.mask((pid == me) | hot)
    salt = (jnp.cumsum(hot.astype(jnp.int32)) - 1) % n
    return batch.mask(
        jnp.where(hot, salt == me.astype(jnp.int32), pid == me)
    )


def _replicate(batch: RelBatch) -> RelBatch:
    """FIXED_BROADCAST exchange as all_gather (every shard gets all rows)."""
    bump_mesh_counter("all_gather")

    def ag(x):
        return jax.lax.all_gather(x, AXIS, tiled=True)

    cols = [
        Column(c.type, ag(c.data), ag(c.valid_mask()), c.dictionary)
        for c in batch.columns
    ]
    return RelBatch(cols, ag(batch.live_mask()))


def _local_partition(batch: RelBatch, channels: Sequence[int], n: int) -> RelBatch:
    """Hash output of a REPLICATED producer: every shard already holds
    all rows, so each keeps only its own partition (no collective)."""
    pid = _partition_ids(batch, channels, n)
    me = jax.lax.axis_index(AXIS).astype(pid.dtype)
    return batch.mask(pid == me)


# ---------------------------------------------------------------------------
# Fragment-body compiler (runs at trace time, inside shard_map)
# ---------------------------------------------------------------------------


class _FragVisitor:
    """Compiles one fragment's plan tree into per-shard array math over
    the local RelBatch (the LocalExecutionPlanner analogue for the mesh
    data plane)."""

    def __init__(self, executor: "MeshExecutor", frag_id: int,
                 feeds: Dict[int, RelBatch], ctx: Dict[int, RelBatch],
                 caps: Dict[str, int], flags: List[Tuple[str, jnp.ndarray]],
                 streaming: bool = False):
        self.ex = executor
        # inside a chunk step: the batch is one chunk of the driver scan
        self.streaming = streaming
        self.frag_id = frag_id
        self.feeds = feeds  # id(ScanNode) -> local RelBatch
        self.ctx = ctx  # fragment id -> post-exchange local RelBatch
        self.caps = caps
        self.flags = flags
        self._site_counter = 0

    def _site(self, kind: str) -> str:
        self._site_counter += 1
        return f"f{self.frag_id}:{kind}{self._site_counter}"

    def visit(self, node: P.PlanNode) -> RelBatch:
        m = getattr(self, f"_visit_{type(node).__name__}", None)
        if m is None:
            raise MeshUnsupported(type(node).__name__)
        return m(node)

    # -- leaves --
    def _visit_ScanNode(self, node):
        return self.feeds[id(node)]

    def _visit_ValuesNode(self, node):
        keys = [f.name or f"_c{i}" for i, f in enumerate(node.fields)]
        if len(set(keys)) != len(keys):
            # spooled join subtrees repeat column names (k, name, k,
            # name); a name-keyed dict would silently drop channels
            keys = [f"{k}_{i}" for i, k in enumerate(keys)]
        data = {k: [] for k in keys}
        for row in node.rows:
            for k, v in zip(keys, row):
                data[k].append(v)
        schema_t = [(k, f.type) for k, f in zip(keys, node.fields)]
        return RelBatch.from_pydict(schema_t, data)

    _visit_SpooledValuesNode = _visit_ValuesNode

    def _visit_RemoteSourceNode(self, node):
        parts = [self.ctx[fid] for fid in node.fragment_ids]
        out = parts[0] if len(parts) == 1 else concat_batches(parts)
        if node.merge_keys:
            # a merge-gather consumed mid-mesh arrives as an all_gather
            # of locally-sorted runs (shard-major, globally unsorted);
            # restore the global order with a full re-sort (the mesh form
            # of the MergeOperator)
            out = self._sorted(out, node.merge_keys)
        return out

    # -- row transforms --
    def _bind(self, e, batch: RelBatch):
        types = [c.type for c in batch.columns]
        dicts = [c.dictionary for c in batch.columns]
        return ExprBinder(types, dicts).bind(e)

    def _identity(self, batch: RelBatch):
        from trino_tpu.expr.ir import InputRef

        return [
            self._bind(InputRef(i, c.type), batch)
            for i, c in enumerate(batch.columns)
        ]

    def _visit_FilterNode(self, node):
        batch = self.visit(node.child)
        flt = self._bind(node.predicate, batch)
        fn = make_filter_project_fn(flt, self._identity(batch))
        return fn(batch)

    def _visit_ProjectNode(self, node):
        child = node.child
        flt = None
        if isinstance(child, P.FilterNode):
            batch = self.visit(child.child)
            flt = self._bind(child.predicate, batch)
        else:
            batch = self.visit(child)
        bounds = [self._bind(e, batch) for e in node.exprs]
        fn = make_filter_project_fn(flt, bounds)
        return fn(batch)

    # -- aggregation --
    def _agg_specs(self, node) -> Tuple[AggSpec, ...]:
        return tuple(
            AggSpec(a.kind, a.arg_channel, a.out_type, a.distinct,
                    a.arg2_channel, a.percentile, a.separator,
                    a.arg3_channel, a.param, a.post)
            for a in node.aggs
        )

    @staticmethod
    def _key_dims(node, batch: RelBatch) -> Optional[List[int]]:
        """Sizes of the group keys' domains where the plan bounds every
        one of them (dictionary codes, booleans); else None."""
        dims = []
        for ch in node.group_channels:
            c = batch.columns[ch]
            if c.type.is_string and c.dictionary is not None and len(c.dictionary) > 0:
                dims.append(len(c.dictionary))
            elif c.type.kind == T.TypeKind.BOOLEAN:
                dims.append(2)
            else:
                return None
        return dims

    def _initial_agg_cap(self, node, batch: RelBatch) -> int:
        """Dictionary/boolean-bounded key domains fix the capacity at
        plan time (the HashAggregationOperator static-bound rule)."""
        dims = self._key_dims(node, batch)
        bound = int(np.prod([d + 1 for d in dims])) if dims is not None else 0
        if dims is not None and 0 < bound <= (1 << 16):
            return max(bucket_capacity(bound), 16)
        # unbounded keys: an eighth of the input's capacity and at
        # least 1024 groups; the site's flag says when that was short
        return max(1024, bucket_capacity(batch.capacity) // 8)

    def _bounded_reduce(self, node, batch: RelBatch, values, reds):
        """(group-reduce, key dims) where the plan bounds the key domain
        (dictionary and boolean keys), by the rule HashAggregationOperator
        asks too (ops/groupby.choose_bounded_reduce): on a TPU mesh the
        MXU one-hot contraction or, where the work is small, the unrolled
        dense reduce, for sums and counts of integer values (all this
        plane's dense reduce folds); (None, None) means the sort path.
        Neither sorts, so a chunk of millions of rows costs one pass over
        its columns."""
        dims = self._key_dims(node, batch)
        if not dims or any(
            getattr(batch.columns[ch].data, "ndim", 1) != 1
            for ch in node.group_channels
        ):
            return None, None
        path = G.choose_bounded_reduce(
            int(np.prod([d + 1 for d in dims])), reds,
            [v.dtype for v in values],
            mxu=self.ex.mesh.devices.flat[0].platform == "tpu",
            dense_sums_only=True,
        )
        reduce = {"dense": G.dense_group_reduce, "mxu": G.mxu_group_reduce}
        return (reduce[path], tuple(dims)) if path in reduce else (None, None)

    def _batch_agg_inputs(self, aggs, batch: RelBatch):
        """Value slots + reducers per aggregate (long-decimal args split
        into their limb-slot layout, same as the local _agg_ingest)."""
        live = batch.live_mask()
        values, vvalids, reds = [], [], []
        for a in aggs:
            if a.arg_channel is None:
                values.append(live.astype(jnp.int64))
                vvalids.append(None)
            elif getattr(batch.columns[a.arg_channel].data, "ndim", 1) == 2:
                _append_long_decimal_slots(
                    a, batch.columns[a.arg_channel], live,
                    values, vvalids, reds,
                )
                continue
            else:
                col = batch.columns[a.arg_channel]
                values.append(col.data)
                vvalids.append(col.valid)
            reds.append(_BATCH_REDUCER[a.kind])
        return live, values, vvalids, reds

    def _visit_AggregateNode(self, node):
        batch = self.visit(node.child)
        if node.step == "final":
            return self._agg_final(node, batch)
        if not node.group_channels:
            if node.step != "partial":
                raise MeshUnsupported("global single-step agg in mesh fragment")
            return self._global_partial(node, batch)
        return self._agg_grouped(node, batch)

    def _agg_grouped(self, node, batch: RelBatch) -> RelBatch:
        """Grouped partial OR single-step aggregation (raw rows in)."""
        aggs = self._agg_specs(node)
        groups = tuple(node.group_channels)
        keys = [batch.columns[c].data for c in groups]
        valids = [batch.columns[c].valid_mask() for c in groups]
        live, values, vvalids, reds = self._batch_agg_inputs(aggs, batch)
        site = self._site("agg")
        cap = self.caps.setdefault(site, self._initial_agg_cap(node, batch))
        reduce, dims = self._bounded_reduce(node, batch, values, reds)
        args = (tuple(keys), tuple(valids), live, tuple(values),
                tuple(vvalids), tuple(reds))
        layout = (_mxu_word_layout(aggs, batch, vvalids)
                  if reduce is G.mxu_group_reduce else {})
        gk, gv, used, vals, cnts, ngroups, ovf = (
            G.sort_group_reduce(*args, cap) if reduce is None
            else reduce(*args, dims, cap, **layout)
        )
        self.flags.append((site, jnp.where(ovf, ngroups, 0).astype(jnp.int32)))
        cols: List[Column] = []
        for ch, kk, vv in zip(groups, gk, gv):
            c = batch.columns[ch]
            cols.append(Column(c.type, kk, vv, c.dictionary))
        schema = [(c.type, c.dictionary) for c in batch.columns]
        if node.step == "partial":
            # accumulator wire format (operators.partial_output_schema):
            # long-decimal limb slots join into ONE (n, 2) value column
            si = 0
            for a in aggs:
                arg_t = (
                    schema[a.arg_channel][0]
                    if a.arg_channel is not None else None
                )
                vt, vd = agg_state_meta(a, schema)[0]
                cnt = cnts[si]
                col, si = _slots_to_wire_column(a, arg_t, vt, vd, vals, si)
                cols.append(col)
                cols.append(
                    Column(T.BIGINT, cnt.astype(jnp.int64), None, None)
                )
            return RelBatch(cols, used)
        # single step: finalize in place (the operator finish path)
        si = 0
        for a in aggs:
            arg_t, arg_d = (
                schema[a.arg_channel] if a.arg_channel is not None else (None, None)
            )
            state, si = _slots_to_state(a, arg_t, vals, cnts, si)
            out = _agg_output(a, state, arg_t, None)
            d = arg_d if a.kind in ("min", "max", "any") else None
            cols.append(Column(a.out_type, out.data, out.valid, d))
        return RelBatch(cols, used)

    def _global_partial(self, node, batch: RelBatch) -> RelBatch:
        """GROUP-BY-less partial: one wire row of accumulator state."""
        aggs = self._agg_specs(node)
        live = batch.live_mask()
        schema = [(c.type, c.dictionary) for c in batch.columns]
        cols: List[Column] = []
        for a in aggs:
            if a.arg_channel is None:
                data, vvalid = live.astype(jnp.int64), None
            else:
                col = batch.columns[a.arg_channel]
                data, vvalid = col.data, col.valid
            w = live if vvalid is None else (live & vvalid)
            n = jnp.sum(w.astype(jnp.int64))
            red = _BATCH_REDUCER[a.kind]
            vt, vd = agg_state_meta(a, schema)[0]
            if getattr(data, "ndim", 1) == 2 and red != "count":
                # Int128 arg: one (1, 2) limb-pair state value (count
                # states stay scalar BIGINT regardless of arg type)
                if red == "sum":
                    limb_sums = [
                        jnp.sum(jnp.where(w, piece, jnp.int64(0)))
                        for piece in _limb_split(data)
                    ]
                    h, lo = _limb_join(limb_sums)
                elif red in ("min", "max"):
                    h, lo = _lex128_reduce(data[:, 0], data[:, 1], w, red)
                else:  # first
                    first = data[jnp.argmax(w)]
                    h, lo = first[0], first[1]
                val = jnp.stack([h, lo])[None, :]
                cols.append(Column(vt, val, None, vd))
                cols.append(
                    Column(T.BIGINT, n[None].astype(jnp.int64), None, None)
                )
                continue
            if red == "count":
                val = n
            elif red == "sum":
                acc_dt = (
                    jnp.float64
                    if jnp.issubdtype(data.dtype, jnp.floating)
                    else jnp.int64
                )
                val = jnp.sum(jnp.where(w, data.astype(acc_dt), 0))
            elif red in ("min", "max"):
                from trino_tpu.exec.operators import minmax_neutral

                neutral = minmax_neutral(data.dtype, red)
                masked = jnp.where(w, data, jnp.asarray(neutral, data.dtype))
                val = jnp.min(masked) if red == "min" else jnp.max(masked)
            else:  # first
                val = data[jnp.argmax(w)]
            cols.append(Column(vt, val[None].astype(vt.dtype), None, vd))
            cols.append(Column(T.BIGINT, n[None].astype(jnp.int64), None, None))
        return RelBatch(cols, jnp.ones(1, dtype=jnp.bool_))

    def _agg_final(self, node, batch: RelBatch) -> RelBatch:
        """FINAL step over partial-wire-format state rows: merge-reduce
        per group then finalize (HashAggregationOperator final mode).
        Long-decimal state values arrive as (n, 2) limb pairs and split
        into their internal slot layout for the merge."""
        k = len(node.group_channels)
        keys = [batch.columns[c].data for c in range(k)]
        valids = [batch.columns[c].valid_mask() for c in range(k)]
        live = batch.live_mask()
        values, vvalids, reds = [], [], []
        for a in node.aggs:
            val_col = batch.columns[a.arg_channel]
            cnt_col = batch.columns[a.arg_channel + 1]
            cnt = cnt_col.data
            mreds = _slot_merge_reducers(a, val_col.type)
            if getattr(val_col.data, "ndim", 1) == 2:
                pieces = (
                    _limb_split(val_col.data)
                    if a.kind in ("sum", "avg")
                    else [val_col.data[:, 0], val_col.data[:, 1]]
                )
            else:
                pieces = [val_col.data]
            for p, mred in zip(pieces, mreds):
                values.append(p)
                vvalids.append((cnt > 0) if mred == "first" else None)
                reds.append(mred)
                values.append(cnt)
                vvalids.append(None)
                reds.append("sum")
        site = self._site("aggf")
        cap = self.caps.setdefault(site, self._initial_agg_cap(node, batch))
        gk, gv, used, vals, _, ngroups, ovf = G.sort_group_reduce(
            tuple(keys), tuple(valids), live, tuple(values), tuple(vvalids),
            tuple(reds), cap,
        )
        self.flags.append((site, jnp.where(ovf, ngroups, 0).astype(jnp.int32)))
        cols: List[Column] = []
        for c_idx, kk, vv in zip(range(k), gk, gv):
            c = batch.columns[c_idx]
            cols.append(Column(c.type, kk, vv, c.dictionary))
        # de-interleave the merged (value, cnt) stream into slot lists
        vals_v = [v for v in vals[0::2]]
        vals_c = [c.astype(jnp.int64) for c in vals[1::2]]
        si = 0
        for a in node.aggs:
            arg_col = batch.columns[a.arg_channel]
            state, si = _slots_to_state(a, arg_col.type, vals_v, vals_c, si)
            out = _agg_output(a, state, arg_col.type, None)
            d = arg_col.dictionary if a.kind in ("min", "max", "any") else None
            cols.append(Column(a.out_type, out.data, out.valid, d))
        return RelBatch(cols, used)

    # -- joins --
    def _visit_JoinNode(self, node):
        build = self.visit(node.right)
        probe = self.visit(node.left)
        if node.kind == "cross":
            return self._cross_join(node, probe, build)
        rkeys = list(node.right_keys)
        lkeys = list(node.left_keys)
        b_keys, b_valids = [], []
        for c in rkeys:
            col = build.columns[c]
            v = col.valid_mask()
            if getattr(col.data, "ndim", 1) == 2:
                # long-decimal key: build/probe by its two int64 limbs
                b_keys.extend([col.data[:, 0], col.data[:, 1]])
                b_valids.extend([v, v])
            else:
                b_keys.append(col.data)
                b_valids.append(v)
        ls = J.build_lookup(b_keys, b_valids, build.live_mask())
        keys, valids = [], []
        for i, c in enumerate(lkeys):
            col = probe.columns[c]
            v = col.valid_mask()
            bd = build.columns[rkeys[i]].dictionary
            if getattr(col.data, "ndim", 1) == 2:
                keys.extend([col.data[:, 0], col.data[:, 1]])
                valids.extend([v, v])
                continue
            if (
                col.dictionary is not None
                and bd is not None
                and col.dictionary != bd
            ):
                # cross-dictionary string join: remap probe codes onto
                # the build dictionary by value (LookupJoinOperator rule)
                remap = jnp.asarray(
                    [bd.code(v) for v in col.dictionary.values], dtype=jnp.int32
                )
                keys.append(take_clip(remap, col.data))
            else:
                keys.append(col.data)
            valids.append(v)
        lo, counts, total = J.probe_counts(ls, keys, valids, probe.live_mask())
        site = self._site("join")
        # room for one pair per probe row to start with. A large probe
        # starts at a quarter of that; one chunk of a streamed scan at a
        # sixty-fourth: the expansion gathers every column once per SLOT
        # of capacity (at a million slots the gathers were 290 of a
        # chunk step's 530 ms on the v5e, for 40 thousand pairs: PERF.md,
        # PR 28), a join under filters fills few, and the site's flag
        # widens it where it does not (the runner keeps what a plan's
        # run learned, so that is paid once)
        start = bucket_capacity(max(probe.capacity, 16))
        if start > _FULL_JOIN_ROWS:
            start = (max(start // 64, 1 << 16) if self.streaming
                     else max(start // 4, _FULL_JOIN_ROWS))
        out_cap = self.caps.setdefault(site, start)
        self.flags.append(
            (site, jnp.where(total > out_cap, total, 0).astype(jnp.int32))
        )
        pi, bi, ok, pairs = _expand_pairs(
            ls, probe, build, keys, valids, lo, counts, out_cap
        )
        if node.residual is not None:
            rfn = make_residual_fn(self._bind_pair(node.residual, probe, build))
            ok = ok & rfn(pairs)
            pairs = RelBatch(pairs.columns, ok)
        if node.kind == "inner":
            return pairs
        matched = _segment_any(counts, pi, ok, probe.capacity)
        if node.kind == "semi":
            return probe.mask(matched)
        if node.kind == "anti":
            return probe.mask(~matched)
        if node.kind in ("mark", "mark_exists"):
            # appended BOOLEAN match column; "mark" (IN) adds the
            # three-valued lanes. Build-side emptiness/null flags are
            # GLOBAL properties — psum over the mesh axis (a shard with
            # an empty build slice must not report empty)
            valid = None
            if node.kind == "mark":
                b_live = build.live_mask()
                nonempty = jax.lax.psum(
                    jnp.any(b_live).astype(jnp.int32), AXIS
                ) > 0
                hn = jnp.zeros((), dtype=jnp.bool_)
                for c in rkeys:
                    bc = build.columns[c]
                    if bc.valid is not None:
                        hn = hn | jnp.any(b_live & ~bc.valid)
                has_null = jax.lax.psum(hn.astype(jnp.int32), AXIS) > 0
                pv = None
                for vv in valids:
                    pv = vv if pv is None else (pv & vv)
                probe_null = (
                    ~pv if pv is not None else jnp.zeros_like(matched)
                )
                unknown = (~matched) & (
                    (probe_null & nonempty) | has_null
                )
                valid = ~unknown
            col = Column(T.BOOLEAN, matched, valid, None)
            return RelBatch(
                list(probe.columns) + [col], probe.live_mask()
            )
        if node.kind == "full":
            # hash-partitioned full outer: every build row lives on
            # exactly one shard, so shard-local matched flags are
            # complete (the fragmenter never broadcasts full joins)
            matched_b = J.build_matched_flags(build.capacity, bi, ok)
            return concat_batches([
                pairs,
                _left_unmatched(probe, build, matched),
                _right_unmatched(
                    [(c.type, c.dictionary) for c in probe.columns],
                    build, matched_b,
                ),
            ])
        # left outer: matched pairs + unmatched probe rows with NULL build
        return concat_batches([pairs, _left_unmatched(probe, build, matched)])

    def _bind_pair(self, e, probe: RelBatch, build: RelBatch):
        cols = list(probe.columns) + list(build.columns)
        return ExprBinder(
            [c.type for c in cols], [c.dictionary for c in cols]
        ).bind(e)

    def _cross_join(self, node, probe: RelBatch, build: RelBatch) -> RelBatch:
        probe_c = probe.compact()
        build_c = build.compact()
        site = self._site("cross")
        nb = self.caps.setdefault(site, 16)
        n_l = jnp.sum(probe_c.live_mask().astype(jnp.int32))
        n_r = jnp.sum(build_c.live_mask().astype(jnp.int32))
        self.flags.append((site, jnp.where(n_r > nb, n_r, 0).astype(jnp.int32)))
        k = jnp.arange(probe_c.capacity * nb, dtype=jnp.int32)
        pi = k // nb
        bi = k % nb
        live = (pi < n_l) & (bi < n_r)
        cols = [c.gather(pi) for c in probe_c.columns]
        cols += [c.gather(bi) for c in build_c.columns]
        return RelBatch(cols, live)

    def _visit_UnionAllNode(self, node):
        outs = [self.visit(c) for c in node.inputs]
        # string columns must share dictionaries for the concatenated
        # column to stay bindable (same rule as the local UnionAll);
        # all-NULL/empty inputs are compatible with anything
        base = outs[0]
        for other in outs[1:]:
            for c0, c1 in zip(base.columns, other.columns):
                if not c0.type.is_string:
                    continue
                d0, d1 = c0.dictionary, c1.dictionary
                if (
                    d0 is not None and len(d0) > 0
                    and d1 is not None and len(d1) > 0
                    and d0 != d1
                ):
                    raise MeshUnsupported("union dictionary mismatch")
        return concat_batches(outs)

    def _visit_EnforceSingleRowNode(self, node):
        child = self.visit(node.child)
        full = _replicate(child)  # all shards see the full row set
        live = full.live_mask()
        n = jnp.sum(live.astype(jnp.int32))
        # >1 rows is a QUERY ERROR (not a capacity retry): err: flags
        # raise in the executor instead of resizing
        self.flags.append((
            f"err:single_row:{self._site('sr')}",
            jnp.where(n > 1, n, 0).astype(jnp.int32),
        ))
        order = jnp.argsort(jnp.where(live, 0, 1), stable=True)
        pos = order[:16]
        idx = jnp.arange(16, dtype=jnp.int32)
        cols = []
        for c in full.columns:
            g = c.gather(pos)
            valid = g.valid_mask() & (idx < n)  # 0 rows -> all-NULL row
            cols.append(g.with_data(g.data, valid))
        out_live = jnp.where(n > 0, idx < n, idx == 0)
        return RelBatch(cols, out_live)

    # -- ordering / limits --
    def _sorted(self, batch: RelBatch, keys) -> RelBatch:
        datas = [batch.columns[k.channel].data for k in keys]
        valids = [batch.columns[k.channel].valid for k in keys]
        order = sort_order(
            datas, valids, [k.descending for k in keys],
            [k.nulls_first for k in keys], batch.live_mask(),
        )
        return batch.gather(order, take_clip(batch.live_mask(), order))

    def _visit_SortNode(self, node):
        return self._sorted(self.visit(node.child), node.keys)

    def _visit_TopNNode(self, node):
        out = self._sorted(self.visit(node.child), node.keys)
        idx = jnp.arange(out.capacity, dtype=jnp.int32)
        return out.mask(idx < node.count)

    def _visit_LimitNode(self, node):
        out = self.visit(node.child).compact()
        idx = jnp.arange(out.capacity, dtype=jnp.int32)
        keep = idx >= node.offset
        if node.count is not None:
            keep = keep & (idx < node.offset + node.count)
        return out.mask(keep)

    def _visit_WindowNode(self, node):
        """Window over hash-distributed partition keys: the fragmenter
        repartitioned the child on PARTITION BY (an all_to_all on this
        plane), so every window partition is shard-local and the local
        window kernel applies per shard unchanged
        (optimizations/AddExchanges.java:140 window distribution)."""
        from trino_tpu.exec.operators import (
            _window_compute, window_fn_tuples,
        )

        batch = self.visit(node.child)
        schema = [(c.type, c.dictionary) for c in batch.columns]
        fns = window_fn_tuples(list(node.functions), schema)
        s_cols, s_live, out_cols = _window_compute(
            batch,
            tuple(node.partition_channels),
            tuple(node.order_keys),
            fns,
            node.frame,
        )
        cols = list(s_cols)
        for spec, (data, valid) in zip(node.functions, out_cols):
            d = None
            if spec.arg_channel is not None and spec.kind in (
                "lead", "lag", "first_value", "last_value", "nth_value",
                "min", "max"
            ):
                d = s_cols[spec.arg_channel].dictionary
            cols.append(Column(spec.out_type, data, valid, d))
        return RelBatch(cols, s_live)


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


class _ListSource:
    """make_remote_source duck type over pre-materialized pages."""

    def __init__(self, pages: List[Page]):
        self._pages = list(pages)

    def poll(self) -> Optional[Page]:
        return self._pages.pop(0) if self._pages else None

    def is_finished(self) -> bool:
        return not self._pages


def _replicated_map(mesh_sps) -> Dict[int, bool]:
    """Compile-time data placement per fragment: a fragment with no
    scans whose inputs are all replicated executes replicated (every
    shard computes the full result deterministically)."""
    repl: Dict[int, bool] = {}
    for sp in mesh_sps:
        frag = sp.fragment
        if _contains_scan(frag.root):
            repl[frag.id] = False
            continue
        child_ok = True
        for c in sp.children:
            k = c.fragment.output_kind
            # hash input -> sharded; broadcast/gather input -> the
            # exchange itself replicates it
            if k == "hash":
                child_ok = False
        repl[frag.id] = child_ok
    return repl


def mesh_eligibility(subplan: SubPlan) -> Dict[str, int]:
    """Static mesh-plane eligibility check (no execution, no device
    work): raises MeshUnsupported with the fallback reason for plan
    shapes the mesh compiler cannot run, else returns a structural
    summary with the per-compiled-pass collective census. Deterministic,
    so EXPLAIN surfaces can print it under program-cache hits (when the
    trace-time counters would not move)."""
    from trino_tpu.parallel.mesh_chunk import static_collective_counts
    from trino_tpu.runtime.stages import topo_order

    order = topo_order(subplan)
    if len(order) < 2:
        raise MeshUnsupported("single-fragment plan")
    mesh_sps = order[:-1]
    root_sp = order[-1]
    for sp in mesh_sps:
        _check_node(sp.fragment.root)
    root_child_ids = {c.fragment.id for c in root_sp.children}
    repl = _replicated_map(mesh_sps)
    a2a, ag = static_collective_counts(mesh_sps, root_child_ids, repl)
    return {
        "fragments": len(mesh_sps),
        "all_to_all": a2a,
        "all_gather": ag,
    }


class MeshExecutor:
    """Runs a SubPlan with the device mesh as the exchange data plane.

    All non-root fragments execute as one shard_map/jit program; the root
    fragment consumes the gathered results through the ordinary local
    pipeline (so sort-merge gathers, final TopN/limit and output
    decoration share code with the HTTP path)."""

    def __init__(self, catalogs, session, devices=None, replica_id=None,
                 drain_check=None):
        """`devices` restricts the mesh to a sub-mesh (a replica row of
        the replica x partition grid); `replica_id` labels it for
        observability (chunk runners export it as ACTIVE_REPLICA, fault
        messages and deadline kills name it); `drain_check` is the
        replica manager's chunk-boundary lifecycle hook — it raises
        MeshReplicaDraining when the replica leaves rotation so the
        coordinator fails the run over to a sibling."""
        self.catalogs = catalogs
        self.session = session
        devs = list(devices) if devices is not None else list(jax.devices())
        self.n = len(devs)
        self.mesh = Mesh(np.array(devs), (AXIS,))
        # how every feed, context and carry lies on it: rows over AXIS
        self.sharding = NamedSharding(self.mesh, PSpec(AXIS))
        self.replica_id = replica_id
        self.drain_check = drain_check
        self.last_run: Dict[str, object] = {}
        # preemptive multi-tenancy: the scheduler seat the chunk runner
        # consults at every boundary (runtime/scheduler.py MeshJob),
        # and the work-stealing context ("emit" on a helper replica,
        # "merge" on the failover primary) — both set per-execution by
        # the coordinator
        self.sched_job = None
        self.steal_ctx = None

    # -- public --
    def execute(self, subplan: SubPlan, preempt=None,
                query_span=None) -> List[list]:
        """Run the SubPlan over the mesh. `preempt(done, total)` is the
        coordinator's chunk-boundary hook (deadline / abandonment
        checks); `query_span` roots the mesh stage/task/operator spans.
        The chunked runner splits the plan into prelude / chunk-step /
        flush programs when mesh_chunk_rows > 0, else compiles one
        program — either way preemption checks bracket every program
        boundary."""
        from trino_tpu.parallel.mesh_chunk import ChunkedMeshRunner
        from trino_tpu.runtime.stages import topo_order

        order = topo_order(subplan)
        if len(order) < 2:
            raise MeshUnsupported("single-fragment plan")
        mesh_sps = order[:-1]
        root_sp = order[-1]
        for sp in mesh_sps:
            _check_node(sp.fragment.root)
        root_child_ids = {c.fragment.id for c in root_sp.children}
        repl = _replicated_map(mesh_sps)
        # feed_tables (aligned 1:1 with host_feeds) names each feed's
        # source table — the resident tier's generation-snapshot domain
        # for pinned prelude contexts
        self._feed_tables: List[tuple] = []
        feeds, host_feeds = self._load_scans(mesh_sps)

        runner = ChunkedMeshRunner(
            self, mesh_sps, root_child_ids, repl, feeds, host_feeds,
            feed_tables=tuple(self._feed_tables),
        )
        steal = self.steal_ctx
        if steal is not None and steal[0] == "emit":
            # work-stealing helper: compute chunks [mid, K) from zero
            # carries and publish them for the primary to merge — no
            # root fragment, no client-visible result
            runner.run_steal_helper(steal)
            return []
        sources = runner.run(preempt=preempt, query_span=query_span)
        # count only after the programs have actually produced results —
        # a failure above falls back to the page exchange, which must not
        # register as a mesh-executed query
        bump_mesh_counter("queries")
        self.last_run = dict(runner.info)
        return self._run_root(subplan, root_sp, sources)

    # -- planning helpers --
    def _load_scans(self, mesh_sps):
        """Host side of SOURCE distribution: one RelBatch per ScanNode of
        global shape (n * cap,), shard s's rows in [s * cap, (s + 1) *
        cap). Where the connector deals its rows out by position the
        feed is already on the devices, one shard each, and stays there
        (parallel/mesh_feed.py). Otherwise each shard scans its slice of
        the connector splits and the slices stack into one host RelBatch
        (the SourcePartitionedScheduler assignment collapsed onto the
        mesh); the chunk runner places it, and may re-pad the driver
        feed to a chunk-aligned capacity first."""
        from trino_tpu.exec.operators import TableScanOperator
        from trino_tpu.parallel import mesh_feed

        feeds: Dict[int, int] = {}  # id(node) -> feed position
        host_feeds: List[RelBatch] = []
        for sp in mesh_sps:
            for node in _scan_nodes(sp.fragment.root):
                if id(node) in feeds:
                    # the planner may reuse one ScanNode object in several
                    # plan positions (e.g. the NOT IN rewrite's subquery);
                    # one feed serves them all — a second append would
                    # misalign in_specs with feed_args
                    continue
                conn = self.catalogs.get(node.catalog)
                self._feed_tables.append((
                    str(node.catalog).lower(),
                    str(node.handle.schema).lower(),
                    str(node.handle.table).lower(),
                ))
                dealt = mesh_feed.load(self, conn, node)
                if dealt is not None:
                    # each device holds its shard of what the programs
                    # read (and keeps it for the next statement)
                    feeds[id(node)] = len(host_feeds)
                    host_feeds.append(dealt)
                    continue
                splits = conn.split_manager.get_splits(
                    node.handle, max(self.session.target_splits, self.n)
                )
                schema = [
                    (f.type, conn.metadata.column_dictionary(node.handle, c))
                    for c, f in zip(node.columns, node.fields)
                ]
                shard_batches = []
                for s in range(self.n):
                    my = splits[s:: self.n]
                    op = TableScanOperator(
                        conn.page_source, my, list(node.columns),
                        self.session.batch_rows,
                    )
                    parts = []
                    while not op.is_finished():
                        b = op.get_output()
                        if b is None:
                            break
                        parts.append(b)
                    if parts:
                        shard_batches.append(concat_batches(parts))
                    else:
                        shard_batches.append(_empty_batch(schema))
                feeds[id(node)] = len(host_feeds)
                host_feeds.append(_stack_shards(shard_batches, self.n))
        return feeds, host_feeds

    # -- host boundary --
    def _shard_pages(self, batch: RelBatch, replicated: bool) -> List[Page]:
        from trino_tpu.runtime.tracing import host_sync

        with host_sync("mesh.result"):
            host = jax.device_get(batch)
        global_cap = host.columns[0].data.shape[0] if host.columns else 0
        cap = global_cap // self.n
        shards = range(1) if replicated else range(self.n)
        pages = []
        for s in shards:
            sl = slice(s * cap, (s + 1) * cap)
            live = (
                np.asarray(host.live)[sl].astype(bool)
                if host.live is not None
                else np.ones(cap, dtype=bool)
            )
            cols, valids, dicts, typs = [], [], [], []
            for c in host.columns:
                cols.append(np.asarray(c.data)[sl][live])
                valids.append(
                    np.asarray(c.valid)[sl][live] if c.valid is not None else None
                )
                dicts.append(
                    c.dictionary.values if c.dictionary is not None else None
                )
                typs.append(c.type)
            if int(live.sum()):
                pages.append(Page(typs, cols, valids, dicts, int(live.sum())))
        return pages

    def _run_root(self, subplan, root_sp, sources: Dict[int, List[Page]]):
        """Execute the root (single-partition) fragment on the host local
        pipeline, consuming the mesh results as its remote sources."""
        from trino_tpu.exec import CollectorSink, Driver, Pipeline
        from trino_tpu.runtime.stages import fragment_schema, topo_order
        from trino_tpu.sql.local_planner import LocalPlanner

        schemas: Dict[int, list] = {}
        for sp in topo_order(subplan):
            remote = {c.fragment.id: schemas[c.fragment.id] for c in sp.children}
            schemas[sp.fragment.id] = fragment_schema(
                self.catalogs, self.session, sp, remote
            )
        planner = LocalPlanner(
            self.catalogs,
            batch_rows=self.session.batch_rows,
            remote_schemas={
                c.fragment.id: schemas[c.fragment.id] for c in root_sp.children
            },
            dynamic_filtering=False,
        )
        physical = planner.plan(root_sp.fragment.root)
        ctx = {
            "make_remote_source": lambda fids: _ListSource(
                [p for fid in fids for p in sources[fid]]
            )
        }
        pipelines, chain = physical.instantiate(ctx)
        sink = CollectorSink()
        chain.append(sink)
        for p in pipelines:
            Driver(p).run()
        Driver(Pipeline(chain)).run()
        rows: List[list] = []
        for b in sink.batches:
            rows.extend(b.to_pylists())
        return rows


# ---------------------------------------------------------------------------
# Host-side batch assembly
# ---------------------------------------------------------------------------


def _empty_batch(schema) -> RelBatch:
    cols = [
        Column(
            t,
            jnp.zeros((16, 2) if t.lanes == 2 else (16,), dtype=t.dtype),
            jnp.zeros(16, dtype=jnp.bool_),
            d,
        )
        for t, d in schema
    ]
    return RelBatch(cols, jnp.zeros(16, dtype=jnp.bool_))


def _stack_shards(batches: List[RelBatch], n: int) -> RelBatch:
    """Pad per-shard batches to one capacity, unify dictionaries, and
    stack into host arrays of shape (n * cap,) ready for a sharded
    device_put (leading-dim sharding makes shard s's rows local to
    device s)."""
    assert len(batches) == n
    cap = bucket_capacity(max(b.capacity for b in batches))
    width = batches[0].width
    cols: List[Column] = []
    for i in range(width):
        parts = unify_column_dicts([b.columns[i] for b in batches])
        datas, valids = [], []
        for p in parts:
            d = np.asarray(jax.device_get(p.data))
            v = (
                np.asarray(jax.device_get(p.valid)).astype(bool)
                if p.valid is not None
                else np.ones(d.shape[0], dtype=bool)
            )
            if d.shape[0] < cap:
                pad = np.zeros((cap - d.shape[0],) + d.shape[1:], d.dtype)
                d = np.concatenate([d, pad])
                v = np.concatenate([v, np.zeros(cap - v.shape[0], bool)])
            datas.append(d)
            valids.append(v)
        cols.append(
            Column(
                parts[0].type,
                np.concatenate(datas),
                np.concatenate(valids),
                parts[0].dictionary,
            )
        )
    lives = []
    for b in batches:
        lv = np.asarray(jax.device_get(b.live_mask())).astype(bool)
        if lv.shape[0] < cap:
            lv = np.concatenate([lv, np.zeros(cap - lv.shape[0], bool)])
        lives.append(lv)
    return RelBatch(cols, np.concatenate(lives))
