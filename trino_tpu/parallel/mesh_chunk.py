"""Chunked mesh execution: preemptible SPMD programs over the device mesh.

One monolithic shard_map program per query (the original mesh plane)
keeps the coordinator locked out for the whole device dispatch: deadline
kills, client abandonment and the stuck-task watchdog only fire once the
program returns. This module splits the mesh compiler's output at batch
granularity instead:

- **prelude** — every fragment whose subtree does not depend on the
  driver scan compiles into one program, run once (build sides of joins,
  dimension tables, uncorrelated subqueries). Its exchange outputs stay
  resident on device as sharded global arrays.
- **step** — fragments that stream over the driver scan compile into one
  chunk-step program, jit-compiled once and invoked K times with a chunk
  index. The driver feed is sliced on device per chunk
  (`lax.dynamic_slice_in_dim`); group/join state between steps lives in
  donated device carries (accumulator RelBatches with explicit
  live/valid lanes). Each fragment group — producer, its
  FIXED_HASH/FIXED_BROADCAST exchange, consumer — stays fused inside the
  step, so `lax.all_to_all`/`all_gather` rides inside a single compiled
  program per chunk rather than re-entering Python per fragment.
- **flush** — fragments that need the complete driver relation (final
  aggregations, sorts, limits) compile into one program over the
  accumulated carries, run once after the last chunk.

Between chunk boundaries the host regains control: the coordinator's
preemption hook (deadline / abandonment checks) and the per-chunk
stuck-task watchdog run there, which is what makes the mesh plane safe
to use for deadline-bearing queries.

Chunking engages only when `mesh_chunk_rows > 0` (session property);
with the default 0 the whole plan compiles into a single prelude
program — identical compile cost to the monolithic plane — while
preemption checks still bracket the program.

Static-shape discipline carries over: chunk capacities come off the
capacity ladder, carries use host-chosen capacities with device overflow
flags, and an overflow restarts the chunk loop under doubled capacities
(the tryRehash analogue, now spanning chunks). Program records —
jitted fns plus their host-side metadata — are built under
`jax.eval_shape` (no compilation) and cached in PROGRAM_CACHE keyed by
plan fingerprint, feed schemas and capacities, so a second execution of
the same query shape re-dispatches the already-compiled steps and mints
zero new XLA lowerings.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading as _threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PSpec

from trino_tpu import types as T
from trino_tpu.analysis import threadreg
from trino_tpu.analysis.witness import named_lock
from trino_tpu.block import Column, RelBatch, bucket_capacity
from trino_tpu.compile.cache import (
    PROGRAM_CACHE,
    expr_fingerprint,
    schema_cache_key,
)
from trino_tpu.compile.shapes import CapacityLadder
from trino_tpu.compile.warmup import WarmupEntry, note_classes_warm
from trino_tpu.sql import plan as P
from trino_tpu.parallel import mesh_feed
from trino_tpu.parallel.mesh_feed import chunk_rows_for
from trino_tpu.parallel.mesh_plan import (
    AXIS,
    MeshUnsupported,
    _exchange_hash,
    exchange_block,
    _FragVisitor,
    _local_partition,
    _replicate,
    _salted_exchange_hash,
    _salted_local_partition,
    shard_map,
)
from trino_tpu.runtime.tracing import host_span, host_sync

# Most recent chunked run, for tests and EXPLAIN surfaces: chunk shape,
# fragment classification and attempt count. Observability only, but
# written by chunk loops racing chaos/EXPLAIN readers — the two-step
# clear()+update() must not expose an empty dict mid-publish.
_run_info_lock = named_lock("mesh_chunk._run_info_lock")
LAST_RUN_INFO: Dict[str, object] = {}  # guarded_by: _run_info_lock


def last_run_info() -> Dict[str, object]:
    """Snapshot of the most recent chunked run's info dict."""
    with _run_info_lock:
        return dict(LAST_RUN_INFO)


def publish_run_info(info: Dict[str, object]) -> None:
    """Atomically replace LAST_RUN_INFO with `info`."""
    with _run_info_lock:
        LAST_RUN_INFO.clear()
        LAST_RUN_INFO.update(info)


# WarmupEntry registry for mesh programs (census analogue of the local
# operator registry): the warmup service can AOT-compile chunk steps by
# replaying recorded program thunks. Bounded; oldest entries drop.
# Written at plan time from concurrent query threads, read by warmup.
_warmup_entries_lock = named_lock("mesh_chunk._warmup_entries_lock")
MESH_WARMUP_ENTRIES: List[WarmupEntry] = []  # guarded_by: _warmup_entries_lock
_MAX_WARMUP_ENTRIES = 128


class MeshStuck(RuntimeError):
    """A chunk step exceeded the stuck-task watchdog threshold. Failure
    is treated as retryable — a program hung here may succeed on the
    page plane — so the coordinator falls back rather than failing the
    query."""


class MeshDeviceLost(RuntimeError):
    """A device backing the mesh failed mid-run (or a chaos fault
    simulated one). Retryable like MeshStuck: the checkpointed
    remainder replays on the restored mesh, or the whole query falls
    back to the page plane."""

    # an in-run resume retries on the SAME mesh; subclasses a sibling
    # sub-mesh must take over for (drain) turn this off so the fault
    # escalates straight to the coordinator's replica failover
    in_run_resumable = True


class MeshReplicaDraining(MeshDeviceLost):
    """The replica serving this run started draining mid-query: the
    chunk loop stops at the next boundary so the coordinator can fail
    the query over to a healthy sibling sub-mesh (which resumes from
    the host-portable checkpoint). Resuming in-run would land back on
    the draining replica, so it is disabled for this fault."""

    in_run_resumable = False


# Chaos seam: when set, called as hook(chunk_index, n_chunks) at every
# chunk boundary BEFORE the step dispatch. The chaos harness raises
# MeshStuck / MeshDeviceLost from here to inject deterministic
# mid-chunk faults (runtime/chaos.py).
MESH_FAULT_HOOK: Optional[Callable[[int, int], None]] = None

# Multi-host fabric seam (runtime/fabric.py): when set, called as
# hook(key) right after a checkpoint (or park snapshot) lands in the
# local store, so the fabric can enqueue the bytes for asynchronous
# push to peer coordinators. The hook only offers to a bounded queue —
# shedding never blocks the chunk loop.
CHECKPOINT_PUSH_HOOK: Optional[Callable[[tuple], None]] = None

# Which replica's sub-mesh the calling thread's chunk loop runs on
# (None outside a run, or on the single full-width mesh). THREAD-local:
# under serving load several chunk loops interleave on different
# replicas, and a replica-targeted fault hook must see the replica of
# the loop that invoked it, not whichever run() started last.
_ACTIVE_REPLICA = _threading.local()


def active_replica() -> Optional[int]:
    """Replica id of the sub-mesh the current thread's chunk loop runs
    on, or None. Replica-aware chaos hooks consult this to target one
    fault domain without changing the hook(k, K) signature."""
    return getattr(_ACTIVE_REPLICA, "replica", None)


class _Overflow(Exception):
    """Device overflow flags fired; restart the run with bumped caps."""

    def __init__(self, sites: List[Tuple[str, int]]):
        super().__init__(f"capacity overflow at {sites}")
        self.sites = sites


def register_mesh_warmup(entries: Sequence[WarmupEntry]) -> None:
    with _warmup_entries_lock:
        known = {id(e.fn) for e in MESH_WARMUP_ENTRIES}
        MESH_WARMUP_ENTRIES.extend(e for e in entries if id(e.fn) not in known)
        del MESH_WARMUP_ENTRIES[:-_MAX_WARMUP_ENTRIES]


def mesh_warmup_entries() -> List[WarmupEntry]:
    with _warmup_entries_lock:
        return list(MESH_WARMUP_ENTRIES)


# ---------------------------------------------------------------------------
# Fragment classification: prelude / stream / flush
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChunkPlan:
    """How one SubPlan splits across the three mesh programs."""

    driver_pos: Optional[int]  # feed position of the driver scan (None = unchunked)
    driver_ids: frozenset  # id(ScanNode) values served by that feed
    chunk_cap: int  # per-shard rows per chunk (capacity-ladder rung)
    n_chunks: int
    prelude_fids: frozenset
    stream_fids: frozenset
    flush_fids: frozenset

    @property
    def chunked(self) -> bool:
        return self.driver_pos is not None


def _classify(mesh_sps, root_child_ids, driver_ids):
    """Split fragments by their relationship to the driver scan.

    dep      = subtree reads the driver scan (directly or via a dep
               fragment's exchange)
    stream   = dep AND every operator on the dep path distributes over
               chunk-wise union (safe to run per chunk and accumulate)
    flush    = dep but not stream (needs the complete driver relation)
    prelude  = not dep (driver-independent; runs once, results resident)
    """
    dep_fids: set = set()
    dep_cache: Dict[int, bool] = {}

    def node_dep(node) -> bool:
        r = dep_cache.get(id(node))
        if r is None:
            if isinstance(node, P.ScanNode):
                r = id(node) in driver_ids
            elif isinstance(node, P.RemoteSourceNode):
                r = any(fid in dep_fids for fid in node.fragment_ids)
            else:
                r = any(node_dep(c) for c in node.children())
            dep_cache[id(node)] = r
        return r

    def safe(node, is_root: bool) -> bool:
        # a driver-independent subtree recomputes identically every
        # chunk — always safe (its cost is paid K times, but prelude
        # exchanges keep the heavy driver-independent work out of here)
        if not node_dep(node):
            return True
        if isinstance(node, P.ScanNode):
            return True
        if isinstance(node, (P.FilterNode, P.ProjectNode)):
            return all(safe(c, False) for c in node.children())
        if isinstance(node, P.AggregateNode):
            # only a PARTIAL agg at the fragment root: per-chunk partials
            # are more (but valid) partial rows under the partial/final
            # contract — the final step's merge reducers are associative.
            # Grouped single-step or FINAL aggs need the full input.
            return (
                is_root
                and node.step == "partial"
                and safe(node.child, False)
            )
        if isinstance(node, P.JoinNode):
            ld, rd = node_dep(node.left), node_dep(node.right)
            if ld and rd:
                return False  # chunk x chunk misses cross-chunk pairs
            if node.kind == "cross":
                return safe(node.left if ld else node.right, False)
            if rd:
                # chunked BUILD side: only inner joins distribute over a
                # partition of the build relation (outer/semi/anti/mark
                # verdicts need the whole build side at once)
                return node.kind == "inner" and safe(node.right, False)
            # chunked PROBE side: per-probe-row verdicts against the
            # complete build side are exact for every kind except FULL
            # (whose right-unmatched rows need the whole probe relation)
            return node.kind != "full" and safe(node.left, False)
        if isinstance(node, P.RemoteSourceNode):
            if node.merge_keys:
                return False  # chunk concat breaks merge-sorted runs
            deps = [fid in dep_fids for fid in node.fragment_ids]
            if any(deps) and not all(deps):
                # a union of dep + non-dep sources would replay the
                # non-dep source once per chunk (duplication)
                return False
            return True
        # Sort/TopN/Limit/Window/EnforceSingleRow/UnionAll/Values...:
        # order- or cardinality-global — conservative flush
        return False

    for sp in mesh_sps:
        if node_dep(sp.fragment.root):
            dep_fids.add(sp.fragment.id)

    stream: set = set()
    for sp in mesh_sps:
        fid = sp.fragment.id
        if fid not in dep_fids:
            continue
        if sp.fragment.output_merge_keys:
            # chunk-major accumulation is not merge-sorted; consumers
            # expecting sorted runs must see the full relation
            continue
        if any(
            c.fragment.id in dep_fids and c.fragment.id not in stream
            for c in sp.children
        ):
            continue
        if safe(sp.fragment.root, True):
            stream.add(fid)

    all_fids = {sp.fragment.id for sp in mesh_sps}
    prelude = all_fids - dep_fids
    flush = dep_fids - stream
    return frozenset(prelude), frozenset(stream), frozenset(flush)


def build_chunk_plan(mesh_sps, root_child_ids, feeds, shard_caps, session):
    """Pick a driver scan and classify fragments. Chunking engages when
    the largest feed's shard is past mesh_feed.AUTO_CHUNK_ROWS (one
    program's exchange buffers would not fit beside it) or the session
    asks for it (mesh_chunk_rows > 0), and some feed admits a non-empty
    stream set; otherwise every fragment lands in the prelude
    (single-program execution, preemption checks around it)."""
    all_fids = frozenset(sp.fragment.id for sp in mesh_sps)
    chunk_rows = chunk_rows_for(session, max(shard_caps, default=0))
    if chunk_rows > 0 and feeds:
        ladder = CapacityLadder(
            base=int(session.capacity_ladder_base or 2)
        )
        by_pos: Dict[int, List[int]] = {}
        for key, pos in feeds.items():
            by_pos.setdefault(pos, []).append(key)
        # largest scan first: chunking the biggest relation buys the
        # most preemption granularity per compiled program
        for pos in sorted(by_pos, key=lambda p: -shard_caps[p]):
            driver_ids = frozenset(by_pos[pos])
            prelude, stream, flush = _classify(
                mesh_sps, root_child_ids, driver_ids
            )
            if not stream:
                continue
            chunk_cap = ladder.rung(min(chunk_rows, shard_caps[pos]))
            n_chunks = max(
                1, math.ceil(shard_caps[pos] / chunk_cap)
            )
            return ChunkPlan(
                pos, driver_ids, chunk_cap, n_chunks,
                prelude, stream, flush,
            )
    return ChunkPlan(
        None, frozenset(), 0, 1, all_fids, frozenset(), frozenset()
    )


# Join kinds whose per-probe-row verdict stays exact when hot build
# rows are replicated to every shard and hot probe rows are salted off
# their canonical shard. FULL and MARK need globally consistent
# build-side placement, so they never salt.
_SALTED_JOIN_KINDS = ("inner", "left", "semi", "anti")


def _skew_exchange_map(mesh_sps, root_child_ids):
    """{producer fid: ("build"|"probe", hot_values)} for every exchange
    edge that should trace the salted repartition variant.

    A JoinNode annotated with `skew_hot_keys` (adaptive controller,
    heavy-hitter classification at the build barrier) qualifies only
    when the plan shape guarantees salting changes nothing but row
    routing:

    - kind inner/left/semi/anti with a single integer-like equi key on
      both sides (the classifier only emits plain-int hot values, and
      `_hot_mask` must see a raw 1-D integer lane on BOTH sides or on
      neither — one-sided degradation would reroute probes whose build
      rows were never replicated);
    - both join inputs are RemoteSourceNode leaves (an inline side has
      no exchange to salt) and every producer fragment behind either
      side emits a single-channel FIXED_HASH exchange (a broadcast
      build is already fully replicated — nothing to fix);
    - each producer fragment feeds exactly this consumer edge: another
      consumer of the same exchange output would observe salted
      placement while assuming canonical hash placement;
    - above the join inside the consumer fragment only Filter/Project
      and PARTIAL aggregations appear. Anything partition-reliant (a
      single/final-step grouped aggregate riding the join key's
      partitioning, another join) keeps canonical placement.

    Probe-side salting is only correct when the hot build rows are
    replicated, so the map is all-or-nothing per join: both sides
    resolve, or neither is salted.
    """
    frag_by_id = {sp.fragment.id: sp.fragment for sp in mesh_sps}
    ref_count: Dict[int, int] = {}

    def count_refs(node):
        if isinstance(node, P.RemoteSourceNode):
            for fid in node.fragment_ids:
                ref_count[fid] = ref_count.get(fid, 0) + 1
        for c in node.children():
            count_refs(c)

    for sp in mesh_sps:
        count_refs(sp.fragment.root)

    out: Dict[int, Tuple[str, tuple]] = {}

    def consider(join):
        if join.kind not in _SALTED_JOIN_KINDS:
            return
        if len(join.left_keys) != 1 or len(join.right_keys) != 1:
            return
        for node, ch in ((join.left, join.left_keys[0]),
                         (join.right, join.right_keys[0])):
            t = node.fields[ch].type
            if t.is_nested or t.lanes != 1 or not t.is_integerlike:
                return
        if not (
            isinstance(join.left, P.RemoteSourceNode)
            and isinstance(join.right, P.RemoteSourceNode)
        ):
            return
        probe_fids = tuple(join.left.fragment_ids)
        build_fids = tuple(join.right.fragment_ids)
        for fid in probe_fids + build_fids:
            frag = frag_by_id.get(fid)
            if (
                frag is None
                or ref_count.get(fid, 0) != 1
                or fid in root_child_ids
                or fid in out
                or frag.output_kind != "hash"
                or len(frag.output_channels) != 1
            ):
                return
        hot = tuple(join.skew_hot_keys)
        for fid in build_fids:
            out[fid] = ("build", hot)
        for fid in probe_fids:
            out[fid] = ("probe", hot)

    def walk(node, clean):
        if (
            isinstance(node, P.JoinNode)
            and getattr(node, "skew_hot_keys", ())
            and clean
        ):
            consider(node)
        kid_clean = clean and (
            isinstance(node, (P.FilterNode, P.ProjectNode))
            or (
                isinstance(node, P.AggregateNode)
                and node.step == "partial"
            )
        )
        for c in node.children():
            walk(c, kid_clean)

    for sp in mesh_sps:
        walk(sp.fragment.root, True)
    return out


def static_collective_counts(mesh_sps, root_child_ids, repl) -> Tuple[int, int]:
    """Structural collective census for one compiled pass over the plan:
    each non-replicated hash edge traces one all_to_all, each
    non-replicated broadcast/gather edge one all_gather, plus one
    all_gather per EnforceSingleRow occurrence and one per salted
    non-replicated BUILD edge (hot build rows ride an all_gather on top
    of the cold rows' all_to_all). Static (no execution), so EXPLAIN
    surfaces stay deterministic under program-cache hits."""

    def count_sr(node) -> int:
        own = 1 if isinstance(node, P.EnforceSingleRowNode) else 0
        return own + sum(count_sr(c) for c in node.children())

    skew = _skew_exchange_map(mesh_sps, root_child_ids)
    a2a = ag = 0
    for sp in mesh_sps:
        frag = sp.fragment
        ag += count_sr(frag.root)
        if frag.id in root_child_ids:
            continue
        if repl.get(frag.id):
            continue  # replicated producers exchange without collectives
        if frag.output_kind == "hash":
            a2a += 1
            if skew.get(frag.id, ("", ()))[0] == "build":
                ag += 1
        else:
            ag += 1
    return a2a, ag


# ---------------------------------------------------------------------------
# On-device chunk primitives
# ---------------------------------------------------------------------------


def _slice_chunk(batch: RelBatch, k, cap: int) -> RelBatch:
    """Chunk k of the (padded) driver feed, sliced on device."""
    start = (k * cap).astype(jnp.int32) if hasattr(k, "astype") else k * cap

    def sl(a):
        return jax.lax.dynamic_slice_in_dim(a, start, cap, axis=0)

    cols = [
        Column(
            c.type, sl(c.data),
            None if c.valid is None else sl(c.valid),
            c.dictionary,
        )
        for c in batch.columns
    ]
    live = None if batch.live is None else sl(batch.live)
    return RelBatch(cols, live)


def _accumulate(carry: RelBatch, contrib: RelBatch):
    """Append contrib's live rows to the carry accumulator (per shard).

    The carry keeps live rows densely packed at the front, so appended
    chunks preserve scan order (chunk-major = scan-major after compact).
    Returns (new_carry, overflow_flag): flag carries the exact needed
    capacity when the carry would overflow, 0 otherwise — same protocol
    as the agg/join sites, so the executor's restart ladder handles it.
    """
    cap_c = carry.capacity
    comp = contrib.compact()
    live_in = comp.live_mask()
    count = jnp.sum(carry.live_mask().astype(jnp.int32))

    def put(dst, src):
        # the packed contribution lands as ONE contiguous run behind the
        # carry's rows (its dead tail behind it, dead again); what falls
        # past the capacity is cut, and flagged below
        room = jnp.concatenate(
            [dst, jnp.zeros((comp.capacity,) + dst.shape[1:], dst.dtype)]
        )
        return jax.lax.dynamic_update_slice_in_dim(
            room, src.astype(dst.dtype), count, axis=0
        )[:cap_c]

    cols = []
    for cc, sc in zip(carry.columns, comp.columns):
        cols.append(Column(
            cc.type, put(cc.data, sc.data), put(cc.valid, sc.valid_mask()),
            cc.dictionary,
        ))
    live = put(carry.live, live_in)
    n_new = jnp.sum(live_in.astype(jnp.int32))
    needed = count + n_new
    flag = jnp.where(needed > cap_c, needed, 0).astype(jnp.int32)
    return RelBatch(cols, live), flag


def _carry_template(contrib_sds: RelBatch, cap: int, n: int) -> RelBatch:
    """Global-shape ShapeDtypeStruct pytree for one carry accumulator.
    live and valid lanes are always explicit arrays: a None lane would
    change the pytree structure between the template and _accumulate's
    output, breaking the carry fixed point."""
    cols = []
    for c in contrib_sds.columns:
        if type(c) is not Column:
            raise MeshUnsupported("nested column in mesh carry")
        tail = tuple(c.data.shape[1:])
        cols.append(
            Column(
                c.type,
                jax.ShapeDtypeStruct((n * cap,) + tail, c.data.dtype),
                jax.ShapeDtypeStruct((n * cap,), jnp.bool_),
                c.dictionary,
            )
        )
    return RelBatch(cols, jax.ShapeDtypeStruct((n * cap,), jnp.bool_))


def _pad_shards(batch: RelBatch, n: int, old_cap: int, new_cap: int) -> RelBatch:
    """Re-pad a host-stacked (n * old_cap,) feed to (n * new_cap,) so the
    per-shard extent divides evenly into chunk_cap slices. Padding rows
    are dead (live=False)."""
    if new_cap == old_cap:
        return batch
    pad = new_cap - old_cap
    cols = []
    for c in batch.columns:
        d = np.asarray(c.data)
        d = d.reshape((n, old_cap) + d.shape[1:])
        d = np.pad(d, [(0, 0), (0, pad)] + [(0, 0)] * (d.ndim - 2))
        v = (
            np.asarray(c.valid).astype(bool).reshape(n, old_cap)
            if c.valid is not None
            else np.ones((n, old_cap), dtype=bool)
        )
        v = np.pad(v, [(0, 0), (0, pad)])
        cols.append(
            Column(
                c.type,
                d.reshape((n * new_cap,) + d.shape[2:]),
                v.reshape(-1),
                c.dictionary,
            )
        )
    lv = (
        np.asarray(batch.live).astype(bool).reshape(n, old_cap)
        if batch.live is not None
        else np.ones((n, old_cap), dtype=bool)
    )
    lv = np.pad(lv, [(0, 0), (0, pad)])
    return RelBatch(cols, lv.reshape(-1))


def _merge_out_carry(mine: RelBatch, theirs: RelBatch,
                     n: int) -> Optional[RelBatch]:
    """Append `theirs`'s packed live rows after `mine`'s per-shard live
    count (drain-failover work stealing: `mine` holds chunks [k0, mid),
    `theirs` holds [mid, K) computed from zero carries on a sibling).
    `_accumulate` packs live rows densely at the shard front in chunk
    order, so this concatenation is byte-identical to the sequential
    layout. Returns None when the combined rows overflow the shard
    capacity (a sequential run would have taken the overflow-restart
    ladder, which a merge cannot replay) or the packing precondition
    fails."""
    try:
        if mine.width != theirs.width or mine.capacity != theirs.capacity:
            return None
        cap = mine.capacity // n
        m_live = np.asarray(mine.live_mask()).astype(bool).reshape(n, cap)
        t_live = np.asarray(theirs.live_mask()).astype(bool).reshape(n, cap)
        datas, valids = [], []
        for c in mine.columns:
            d = np.asarray(c.data)
            datas.append(d.reshape((n, cap) + d.shape[1:]).copy())
            valids.append(
                None
                if c.valid is None
                else np.asarray(c.valid).astype(bool).reshape(n, cap).copy()
            )
        new_live = m_live.copy()
        for s in range(n):
            cm = int(m_live[s].sum())
            idx_t = np.nonzero(t_live[s])[0]
            ct = len(idx_t)
            if cm + ct > cap:
                return None
            if (cm and not m_live[s][:cm].all()) or (
                ct and int(idx_t[-1]) != ct - 1
            ):
                return None  # rows not packed at the front
            if ct == 0:
                continue
            for j, c in enumerate(theirs.columns):
                td = np.asarray(c.data)
                td = td.reshape((n, cap) + td.shape[1:])
                datas[j][s, cm:cm + ct] = td[s, idx_t]
                if valids[j] is not None:
                    tv = (
                        np.ones(cap, dtype=bool)
                        if c.valid is None
                        else np.asarray(c.valid).astype(bool).reshape(
                            n, cap
                        )[s]
                    )
                    valids[j][s, cm:cm + ct] = tv[idx_t]
            new_live[s, cm:cm + ct] = True
        cols = [
            Column(
                c.type,
                datas[j].reshape((n * cap,) + datas[j].shape[2:]),
                None if valids[j] is None else valids[j].reshape(-1),
                c.dictionary,
            )
            for j, c in enumerate(mine.columns)
        ]
        return RelBatch(cols, new_live.reshape(-1))
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Program record: jitted prelude/step/flush + host metadata, cacheable
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ExchangeCensus:
    """What ONE run of a mesh program exchanges: its collective
    operations by kind, and the bytes that leave their device in them,
    summed over the mesh (an all_to_all keeps 1/n of each send buffer at
    home; an all_gather sends a shard's rows to the n - 1 others).
    Counted from the program's jaxpr after dead-code elimination, so a
    column that is exchanged in the plan and read by nothing downstream,
    which XLA drops, is not counted. A runner adds it to the `mesh.*`
    counters every time it dispatches the program: those count runs
    (`mesh_plan.MESH_COUNTERS` counts traces)."""

    all_to_all: int = 0
    all_gather: int = 0
    bytes_exchanged: int = 0


def _dce_sort_payloads(used_outputs, eqn):
    """Dead-code rule for `lax.sort`: keep the keys and the payload
    operands whose sorted output something reads."""
    from jax._src.interpreters import partial_eval as pe

    if not any(used_outputs):
        return [False] * len(eqn.invars), None
    keep = [i < eqn.params["num_keys"] or used
            for i, used in enumerate(used_outputs)]
    if all(keep):
        return keep, eqn
    return keep, pe.new_jaxpr_eqn(
        [v for v, k in zip(eqn.invars, keep) if k],
        [v for v, k in zip(eqn.outvars, keep) if k],
        eqn.primitive, eqn.params, eqn.effects, eqn.source_info, eqn.ctx,
    )


def exchange_census(fn, n: int, *args_sds):
    """(census, which flattened arguments the program reads) of
    `fn(*args_sds)`, a shard_map program over an n-wide mesh (also its
    shape check: tracing raises what eval_shape would)."""
    closed = jax.make_jaxpr(fn)(*args_sds)
    jaxpr = closed.jaxpr
    read = [True] * len(jaxpr.invars)
    try:
        from jax._src.interpreters import partial_eval as pe

        # jax drops a sort only whole; XLA drops the payload operands
        # nothing reads, and so must this count (the hash exchange's
        # sort carries every column of its batch)
        had = pe.dce_rules.get(jax.lax.sort_p)
        pe.dce_rules[jax.lax.sort_p] = _dce_sort_payloads
        try:
            jaxpr, read = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
        finally:
            if had is None:
                pe.dce_rules.pop(jax.lax.sort_p, None)
            else:
                pe.dce_rules[jax.lax.sort_p] = had
    except Exception:
        pass  # no DCE in this jax: count what was traced (an upper bound)
    census = ExchangeCensus()

    def walk(jp) -> None:
        for eqn in jp.eqns:
            name = eqn.primitive.name
            if name in ("all_to_all", "all_gather"):
                local = sum(
                    int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                    for v in eqn.invars
                )
                if name == "all_to_all":
                    census.all_to_all += 1
                    census.bytes_exchanged += local * (n - 1)
                else:
                    census.all_gather += 1
                    census.bytes_exchanged += local * n * (n - 1)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    return census, list(read)


@dataclasses.dataclass
class MeshProgramRecord:
    n_chunks: int
    chunk_cap: int
    resolved_caps: Dict[str, int]
    pctx_fids: Tuple[int, ...]
    carry_meta: Tuple[Tuple[str, int], ...]  # ("out"|"ctx", fid)
    carry_sds: tuple  # global ShapeDtypeStruct RelBatch per carry
    prelude_fn: Optional[Callable]
    prelude_sites: List[str]
    prelude_out_meta: List[Tuple[int, bool]]
    step_fn: Optional[Callable]
    step_sites: List[str]
    flush_fn: Optional[Callable]
    flush_sites: List[str]
    flush_out_meta: List[Tuple[int, bool]]
    warmup_entries: List[WarmupEntry]
    class_keys: set
    # shapes of what the prelude leaves on the devices for the others
    pctx_sds: tuple = ()
    # program name -> thread compiling it ahead of its first dispatch
    compiling: Dict[str, object] = dataclasses.field(default_factory=dict)
    compiled_ahead: bool = False
    # per flattened leaf of the feed tuple: does any of the programs
    # read it (a scan lists columns its plan never touches)
    feed_read: Tuple[bool, ...] = ()
    # what one run of each program exchanges
    prelude_census: ExchangeCensus = dataclasses.field(
        default_factory=ExchangeCensus)
    step_census: ExchangeCensus = dataclasses.field(
        default_factory=ExchangeCensus)
    flush_census: ExchangeCensus = dataclasses.field(
        default_factory=ExchangeCensus)


class _ProgramWarmer:
    """WarmupEntry thunk for one mesh program: rebuilds zero-filled
    arguments with the program's exact mesh shardings (jit specializes
    on input shardings — replaying with default placement would warm
    the wrong executable) and dispatches the recorded jitted fn."""

    def __init__(self, fn, mesh, args_sds, scalar_mask):
        self.fn = fn
        self.mesh = mesh
        self.args_sds = args_sds
        self.scalar_mask = scalar_mask

    def __call__(self, _zeros_batch=None):
        sh = NamedSharding(self.mesh, PSpec(AXIS))
        args = []
        for sds, scalar in zip(self.args_sds, self.scalar_mask):
            if scalar:
                args.append(jnp.zeros((), dtype=jnp.int32))
            else:
                args.append(
                    jax.tree_util.tree_map(
                        lambda s: jax.device_put(
                            jnp.zeros(s.shape, s.dtype), sh
                        ),
                        sds,
                    )
                )
        out = self.fn(*args)
        jax.block_until_ready(out)
        return out


def _sds_of(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), tree
    )


def _record_key(ex, mesh_sps, root_child_ids, repl, feed_sigs, cplan, caps):
    """Cache key for a program record. Fragment trees enter by repr
    fingerprint so a structurally identical fresh plan (fresh node
    objects) reuses the record — its bodies address feeds positionally,
    and structural twins trace identically. Falls back to uncached
    builds when any repr leaks object identity."""
    frag_parts = []
    for sp in mesh_sps:
        f = sp.fragment
        frag_parts.append((
            f.id, f.partitioning, f.output_kind,
            tuple(f.output_channels), tuple(f.output_merge_keys),
            f.root, tuple(c.fragment.id for c in sp.children),
        ))
    fp = expr_fingerprint(tuple(frag_parts))
    if fp is None or any(sig is None for sig, _cap in feed_sigs):
        return None
    return (
        "mesh-chunk",
        ex.n,
        tuple(str(d) for d in ex.mesh.devices.flat),
        fp,
        tuple(sorted(root_child_ids)),
        tuple(sorted(repl.items())),
        tuple(feed_sigs),
        cplan.driver_pos,
        cplan.chunk_cap,
        cplan.n_chunks,
        tuple(sorted(caps.items())),
    )


def _build_record(ex, mesh_sps, root_child_ids, repl, feeds, feed_sds,
                  cplan, caps_in) -> MeshProgramRecord:
    """Trace the three programs under jax.eval_shape (populating flag
    sites, output metadata and carry shapes without compiling) and wrap
    them in jit. Compilation happens lazily at the first real dispatch;
    the record keeps everything the executor needs to replay."""
    n = ex.n
    mesh = ex.mesh
    caps = dict(caps_in)

    prelude_sps = [sp for sp in mesh_sps if sp.fragment.id in cplan.prelude_fids]
    stream_sps = [sp for sp in mesh_sps if sp.fragment.id in cplan.stream_fids]
    flush_sps = [sp for sp in mesh_sps if sp.fragment.id in cplan.flush_fids]

    consumer: Dict[int, int] = {}
    for sp in mesh_sps:
        for c in sp.children:
            consumer[c.fragment.id] = sp.fragment.id
    # prelude exchange outputs consumed by later programs stay resident
    pctx_fids = tuple(sorted({
        c.fragment.id
        for sp in stream_sps + flush_sps
        for c in sp.children
        if c.fragment.id in cplan.prelude_fids
    }))
    carry_meta: List[Tuple[str, int]] = []
    for sp in stream_sps:
        fid = sp.fragment.id
        if fid in root_child_ids:
            carry_meta.append(("out", fid))
        elif consumer.get(fid) in cplan.flush_fids:
            carry_meta.append(("ctx", fid))
    carry_meta = tuple(carry_meta)
    carry_index = {fid: i for i, (_k, fid) in enumerate(carry_meta)}

    skew_map = _skew_exchange_map(mesh_sps, root_child_ids)

    def emit_exchange(frag, batch, ctx, flags):
        if frag.output_kind == "hash":
            sk = skew_map.get(frag.id)
            block = exchange_block(batch.capacity, n)
            if sk is None and not repl[frag.id] and block < batch.capacity:
                # a large batch reserves less than its capacity for each
                # destination; the site's flag says when that was short
                site = f"f{frag.id}:xchg"
                block = min(caps.setdefault(site, block), batch.capacity)
                ctx[frag.id], short = _exchange_hash(
                    batch, frag.output_channels, n, block
                )
                flags.append((site, short))
                return
            if sk is not None:
                role, hot = sk
                ctx[frag.id] = (
                    _salted_local_partition(
                        batch, frag.output_channels, n, hot, role
                    )
                    if repl[frag.id]
                    else _salted_exchange_hash(
                        batch, frag.output_channels, n, hot, role
                    )
                )
            else:
                ctx[frag.id] = (
                    _local_partition(batch, frag.output_channels, n)
                    if repl[frag.id]
                    else _exchange_hash(batch, frag.output_channels, n)
                )
        else:  # broadcast, or gather consumed by another mesh fragment
            ctx[frag.id] = batch if repl[frag.id] else _replicate(batch)

    def run_frags(sps, local_feeds, ctx, flags, outputs, out_meta):
        for sp in sps:
            frag = sp.fragment
            vis = _FragVisitor(ex, frag.id, local_feeds, ctx, caps, flags)
            batch = vis.visit(frag.root)
            if frag.id in root_child_ids:
                outputs.append(batch)
                out_meta.append((frag.id, repl[frag.id]))
                continue
            emit_exchange(frag, batch, ctx, flags)

    def flag_array(flags):
        if flags:
            return jnp.stack([f for _s, f in flags])
        return jnp.zeros(1, dtype=jnp.int32)

    # -- prelude -----------------------------------------------------
    prelude_sites: List[str] = []
    prelude_out_meta: List[Tuple[int, bool]] = []

    def prelude_body(feed_batches):
        # host-visible side lists are cleared at trace entry so a
        # re-trace cannot double-append and misalign with the outputs
        prelude_sites.clear()
        prelude_out_meta.clear()
        local_feeds = {key: feed_batches[pos] for key, pos in feeds.items()}
        ctx: Dict[int, RelBatch] = {}
        flags: List[Tuple[str, jnp.ndarray]] = []
        outputs: List[RelBatch] = []
        run_frags(
            prelude_sps, local_feeds, ctx, flags, outputs, prelude_out_meta
        )
        prelude_sites.extend(s for s, _f in flags)
        return (
            tuple(outputs),
            tuple(ctx[fid] for fid in pctx_fids),
            flag_array(flags),
        )

    # -- chunk step --------------------------------------------------
    step_sites: List[str] = []

    def step_core(k, feed_batches, pctx_batches, carry_batches, probing):
        local_feeds = {}
        for key, pos in feeds.items():
            b = feed_batches[pos]
            if pos == cplan.driver_pos:
                b = _slice_chunk(b, k, cplan.chunk_cap)
            local_feeds[key] = b
        ctx: Dict[int, RelBatch] = dict(zip(pctx_fids, pctx_batches))
        flags: List[Tuple[str, jnp.ndarray]] = []
        contribs: List[RelBatch] = []
        new_carries = list(carry_batches) if carry_batches is not None else None
        for sp in stream_sps:
            frag = sp.fragment
            vis = _FragVisitor(ex, frag.id, local_feeds, ctx, caps, flags,
                               streaming=True)
            batch = vis.visit(frag.root)
            if frag.id not in root_child_ids:
                emit_exchange(frag, batch, ctx, flags)
            i = carry_index.get(frag.id)
            if i is None:
                continue  # stream->stream link: flows in-trace
            contrib = batch if carry_meta[i][0] == "out" else ctx[frag.id]
            if probing:
                contribs.append(contrib)
            else:
                new_carries[i], fl = _accumulate(carry_batches[i], contrib)
                flags.append((f"carry:f{frag.id}", fl))
        return flags, contribs, new_carries

    def probe_body(k, feed_batches, pctx_batches):
        # shape probe: what would each carry receive per chunk?
        _flags, contribs, _nc = step_core(
            k, feed_batches, pctx_batches, None, True
        )
        return tuple(contribs)

    def step_body(k, feed_batches, pctx_batches, carry_batches):
        step_sites.clear()
        flags, _contribs, new_carries = step_core(
            k, feed_batches, pctx_batches, carry_batches, False
        )
        step_sites.extend(s for s, _f in flags)
        return tuple(new_carries), flag_array(flags)

    # -- flush -------------------------------------------------------
    flush_sites: List[str] = []
    flush_out_meta: List[Tuple[int, bool]] = []

    def flush_body(feed_batches, pctx_batches, carry_batches):
        flush_sites.clear()
        flush_out_meta.clear()
        local_feeds = {key: feed_batches[pos] for key, pos in feeds.items()}
        ctx: Dict[int, RelBatch] = dict(zip(pctx_fids, pctx_batches))
        for (kind, fid), cb in zip(carry_meta, carry_batches):
            if kind == "ctx":
                ctx[fid] = cb
        flags: List[Tuple[str, jnp.ndarray]] = []
        outputs: List[RelBatch] = []
        run_frags(
            flush_sps, local_feeds, ctx, flags, outputs, flush_out_meta
        )
        flush_sites.extend(s for s, _f in flags)
        return tuple(outputs), flag_array(flags)

    def smap(body, in_specs):
        return shard_map(
            body, mesh=mesh, in_specs=in_specs,
            out_specs=PSpec(AXIS), check_vma=False,
        )

    cpu_mesh = mesh.devices.flat[0].platform == "cpu"
    feed_tuple_sds = tuple(feed_sds)
    k_sds = jax.ShapeDtypeStruct((), jnp.int32)

    prelude_census = step_census = flush_census = ExchangeCensus()
    n_feed = len(jax.tree_util.tree_leaves(feed_tuple_sds))
    feed_read = [False] * n_feed
    prelude_fn = None
    pctx_sds: tuple = ()
    if prelude_sps:
        pf = smap(prelude_body, (PSpec(AXIS),))
        _p_outs, pctx_sds, _p_flags = jax.eval_shape(pf, feed_tuple_sds)
        prelude_census, read = exchange_census(pf, n, feed_tuple_sds)
        feed_read = [a or b for a, b in zip(feed_read, read[:n_feed])]
        prelude_fn = jax.jit(pf)

    step_fn = None
    carry_sds: tuple = ()
    if stream_sps:
        probe = smap(probe_body, (PSpec(), PSpec(AXIS), PSpec(AXIS)))
        contrib_sds = jax.eval_shape(probe, k_sds, feed_tuple_sds, pctx_sds)
        templates = []
        for (kind, fid), csds in zip(carry_meta, contrib_sds):
            contrib_cap = max(
                1, (csds.columns[0].data.shape[0] if csds.columns
                    else csds.live.shape[0]) // n
            )
            # start near the expected total contribution (room for four
            # full chunks), capped so a huge K doesn't pre-allocate the
            # world; the overflow ladder jumps straight to the flagged
            # exact size on a miss
            initial = bucket_capacity(max(
                16,
                min(cplan.n_chunks * contrib_cap,
                    max(4 * contrib_cap, 8192)),
            ))
            cap = caps.setdefault(f"carry:f{fid}", initial)
            templates.append(_carry_template(csds, cap, n))
        carry_sds = tuple(templates)
        sf = smap(
            step_body, (PSpec(), PSpec(AXIS), PSpec(AXIS), PSpec(AXIS))
        )
        step_census, read = exchange_census(
            sf, n, k_sds, feed_tuple_sds, pctx_sds, carry_sds
        )
        feed_read = [a or b for a, b in zip(feed_read, read[1:1 + n_feed])]
        step_fn = jax.jit(
            sf, donate_argnums=() if cpu_mesh else (3,)
        )

    flush_fn = None
    if flush_sps:
        ff = smap(flush_body, (PSpec(AXIS), PSpec(AXIS), PSpec(AXIS)))
        flush_census, read = exchange_census(
            ff, n, feed_tuple_sds, pctx_sds, carry_sds
        )
        feed_read = [a or b for a, b in zip(feed_read, read[:n_feed])]
        flush_fn = jax.jit(ff)

    # -- warmup entries ----------------------------------------------
    sig = (f"frags{len(mesh_sps)}", f"k{cplan.n_chunks}", f"n{n}")
    warm_cap = cplan.chunk_cap or 16
    entries: List[WarmupEntry] = []

    def entry(operator, fn, args_sds, scalar_mask):
        return WarmupEntry(
            operator=operator,
            fn=_ProgramWarmer(fn, mesh, args_sds, scalar_mask),
            in_schema=[(T.BIGINT, None)],
            out_dtypes=sig,
            capacities=(warm_cap,),
        )

    if prelude_fn is not None:
        entries.append(entry(
            "MeshPrelude", prelude_fn, (feed_tuple_sds,), (False,)
        ))
    if step_fn is not None:
        entries.append(entry(
            "MeshChunkStep", step_fn,
            (k_sds, feed_tuple_sds, pctx_sds, carry_sds),
            (True, False, False, False),
        ))
    if flush_fn is not None:
        entries.append(entry(
            "MeshFlush", flush_fn,
            (feed_tuple_sds, pctx_sds, carry_sds),
            (False, False, False),
        ))

    return MeshProgramRecord(
        n_chunks=cplan.n_chunks,
        chunk_cap=cplan.chunk_cap,
        resolved_caps=dict(caps),
        pctx_fids=pctx_fids,
        carry_meta=carry_meta,
        carry_sds=carry_sds,
        prelude_fn=prelude_fn,
        prelude_sites=prelude_sites,
        prelude_out_meta=prelude_out_meta,
        step_fn=step_fn,
        step_sites=step_sites,
        flush_fn=flush_fn,
        flush_sites=flush_sites,
        flush_out_meta=flush_out_meta,
        warmup_entries=entries,
        class_keys=set().union(*(e.keys() for e in entries)) if entries else set(),
        pctx_sds=pctx_sds,
        feed_read=tuple(feed_read),
        prelude_census=prelude_census,
        step_census=step_census,
        flush_census=flush_census,
    )


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


# program identity at empty caps -> the caps its last overflowing run
# ended on; a key is a plan shape over feed shapes, not a statement, and
# the oldest go first
_LEARNED_CAPS_MAX = 256
_learned_caps_lock = named_lock("mesh_chunk._learned_caps_lock")
_LEARNED_CAPS: Dict[tuple, Dict[str, int]] = {}  # guarded_by: _learned_caps_lock


def _count_exchanges(census: ExchangeCensus) -> None:
    """One dispatched program's collectives and bytes, into the plane's
    run-time counters (`mesh_plan.MESH_COUNTERS` counts traces)."""
    from trino_tpu.runtime.metrics import METRICS

    METRICS.increment("mesh.all_to_all", census.all_to_all)
    METRICS.increment("mesh.all_gather", census.all_gather)
    METRICS.increment("mesh.bytes_exchanged", census.bytes_exchanged)



class ChunkedMeshRunner:
    """Drives one query's mesh programs: prelude once, K chunk steps
    with host preemption/watchdog checks at every boundary, flush once;
    restarts the whole loop under bumped capacities on device overflow
    (deterministic ladder — a second execution replays the same
    capacity sequence and hits every cached program)."""

    def __init__(self, ex, mesh_sps, root_child_ids, repl, feeds, host_feeds,
                 feed_tables=()):
        self.ex = ex
        self.session = ex.session
        # source table per feed (resident-tier generation domain)
        self.feed_tables = tuple(feed_tables)
        self.mesh_sps = mesh_sps
        self.root_child_ids = root_child_ids
        self.repl = repl
        self.feeds = feeds
        self.sharding = ex.sharding
        self.skew_map = _skew_exchange_map(mesh_sps, root_child_ids)
        n = ex.n
        # a feed the connector deals out by position comes as its shapes
        # (mesh_feed.Feed.template): its columns go to the devices once
        # a traced program has said which of them it reads
        host_feeds = list(host_feeds)
        shapes = [
            f.template if isinstance(f, mesh_feed.Feed) else f
            for f in host_feeds
        ]
        shard_caps = [b.capacity // n for b in shapes]
        self.cplan = build_chunk_plan(
            mesh_sps, root_child_ids, feeds, shard_caps, self.session
        )
        if self.cplan.chunked:
            pos = self.cplan.driver_pos
            aligned = self.cplan.n_chunks * self.cplan.chunk_cap
            if aligned != shard_caps[pos]:
                if isinstance(host_feeds[pos], mesh_feed.Feed):
                    # a chunk size the feed's capacity is no multiple of
                    # (a ladder base other than 2): re-pad through the host
                    host_feeds[pos] = mesh_feed.place(
                        host_feeds[pos], [True] * len(
                            jax.tree_util.tree_leaves(shapes[pos]))
                    )
                shapes[pos] = host_feeds[pos] = _pad_shards(
                    host_feeds[pos], n, shard_caps[pos], aligned
                )
        self.feed_sigs = tuple(
            (
                schema_cache_key([(c.type, c.dictionary) for c in b.columns]),
                # a placed feed has no validity lane for a column
                # without nulls: another pytree, another program
                (b.capacity, tuple(c.valid is None for c in b.columns)),
            )
            for b in shapes
        )
        self.feed_sds = tuple(_sds_of(b) for b in shapes)
        self._host_feeds = host_feeds
        self._placed_read: Tuple[bool, ...] = ()
        self.feed_args: Optional[tuple] = None
        if not any(isinstance(f, mesh_feed.Feed) for f in host_feeds):
            self.feed_args = tuple(
                jax.device_put(b, self.sharding) for b in host_feeds
            )
        self.info: Dict[str, object] = {}
        self._last_record_key = None
        # recovery bookkeeping for the current run (chaos harness and
        # EXPLAIN ANALYZE read these back through self.info)
        self._run_stats: Dict[str, object] = {
            "executed_chunk_steps": 0,
            "checkpoints": 0,
            "resumes": 0,
            "resumed_from_chunk": None,
            "parks": 0,
            "unparks": 0,
            "steals": 0,
        }

    # -- program record ----------------------------------------------
    def _record_key_for(self, caps):
        return _record_key(
            self.ex, self.mesh_sps, self.root_child_ids, self.repl,
            self.feed_sigs, self.cplan, caps,
        )

    def _record(self, caps) -> MeshProgramRecord:
        def build():
            return _build_record(
                self.ex, self.mesh_sps, self.root_child_ids, self.repl,
                self.feeds, self.feed_sds, self.cplan, caps,
            )

        key = self._record_key_for(caps)
        self._last_record_key = key
        record = None
        if key is not None:
            record = PROGRAM_CACHE.get_or_create(key, build)
        if not isinstance(record, MeshProgramRecord):
            record = build()  # uncacheable, or a foreign entry under the key
        self._place_feeds(record)
        return record

    def _compile_ahead(self, record: MeshProgramRecord) -> None:
        """Cold, a run compiles its programs one after the other as it
        reaches them, and at scale each takes the TPU compiler minutes
        (its sorts). With the persistent compile cache on, start the
        step's and the flush's compiles now, on threads of their own,
        beside the prelude's: `_await_compile` joins each before its
        program's first dispatch, which then finds it in the cache. Off
        the TPU, or without the cache, compiles stay where they were."""
        if (
            record.compiled_ahead
            or not jax.config.jax_compilation_cache_dir
            or self.ex.mesh.devices.flat[0].platform != "tpu"
        ):
            return
        record.compiled_ahead = True

        def placed(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=self.sharding), tree)

        feeds, pctx, carries = (
            placed(self.feed_sds), placed(record.pctx_sds),
            placed(record.carry_sds),
        )
        k = jax.ShapeDtypeStruct((), jnp.int32)
        for name, fn, args in (
            ("step", record.step_fn, (k, feeds, pctx, carries)),
            ("flush", record.flush_fn, (feeds, pctx, carries)),
        ):
            if fn is None or (name == "step" and record.prelude_fn is None):
                continue    # nothing to overlap with: it is dispatched first

            def compile_it(fn=fn, args=args):
                try:
                    fn.lower(*args).compile()
                except Exception:
                    pass    # the dispatch compiles, and says what is wrong

            record.compiling[name] = threadreg.spawn(
                f"mesh-compile-{name}", compile_it, owner="mesh_chunk")

    @staticmethod
    def _await_compile(record: MeshProgramRecord, name: str) -> None:
        t = record.compiling.pop(name, None)
        if t is not None:
            t.join()

    def _place_feeds(self, record: MeshProgramRecord) -> None:
        """`feed_args` for `record`'s programs: every leaf they read is
        on the devices (mesh_feed.place puts the missing columns there)."""
        if self.feed_args is not None and not any(
            r and not p for r, p in zip(record.feed_read, self._placed_read)
        ):
            return
        read = iter(record.feed_read)
        args = []
        for f, sds in zip(self._host_feeds, self.feed_sds):
            flags = [next(read) for _ in jax.tree_util.tree_leaves(sds)]
            args.append(
                mesh_feed.place(f, flags) if isinstance(f, mesh_feed.Feed)
                else jax.device_put(f, self.sharding)
            )
        self.feed_args = tuple(args)
        self._placed_read = tuple(record.feed_read)

    def _ckpt_key(self) -> Optional[tuple]:
        """Checkpoint-store key: the program identity minus the caps
        element (the record key's last component), so a resume after an
        overflow cap bump still finds its checkpoint, and minus the
        DEVICE identity (record-key element 2), so the checkpoint is
        host-portable — a sibling sub-mesh of the same width n (carry
        shapes are (n*cap,)) restores it after a replica failover. The
        shard count n stays in the key: carries from a different-width
        mesh could never be re-placed shape-exactly. None when the
        program itself is uncacheable (repr identity leak) — such plans
        never checkpoint."""
        if self._last_record_key is None:
            return None
        key = self._last_record_key
        return ("mesh-ckpt", key[1]) + tuple(key[3:-1])

    # -- execution ---------------------------------------------------
    def run(self, preempt=None, query_span=None) -> Dict[int, list]:
        from trino_tpu.runtime.tracing import KIND_STAGE, KIND_TASK

        stage_span = task_span = None
        if query_span is not None:
            stage_span = query_span.child(
                "stage mesh", KIND_STAGE,
                data_plane="mesh", fragments=len(self.mesh_sps),
            )
            task_span = stage_span.child(
                "task mesh.0", KIND_TASK,
                chunks=self.cplan.n_chunks, chunk_rows=self.cplan.chunk_cap,
            )
        prev_replica = active_replica()
        _ACTIVE_REPLICA.replica = getattr(self.ex, "replica_id", None)
        try:
            sched_job = getattr(self.ex, "sched_job", None)
            if sched_job is not None:
                # the seat guards DEVICE phases only (prelude, chunk
                # steps, flush): planning and host feed builds already
                # ran before this point, outside the seat, so a fast
                # arrival never queues behind another query's host
                # prep. Typed kills and drain checks fire out of the
                # wait as they do at any boundary.
                sched_job.scheduler.acquire(sched_job)
            # what an earlier run of this plan over feeds of these
            # shapes learned on the capacity ladder: start there, not at
            # the bottom (every rung below is a whole run thrown away)
            learned_key = self._record_key_for({})
            with _learned_caps_lock:
                caps: Dict[str, int] = dict(
                    _LEARNED_CAPS.get(learned_key, ())
                ) if learned_key is not None else {}
            # `caps` holds only what an overflow has widened: every other
            # site starts from the shapes of the trace it is in (a send
            # block follows the join capacity before it, and must not
            # keep the block of a narrower join)
            self._run_stats = {
                "executed_chunk_steps": 0,
                "checkpoints": 0,
                "resumes": 0,
                "resumed_from_chunk": None,
                "parks": 0,
                "unparks": 0,
                "steals": 0,
            }
            resume_budget = int(
                self.session.mesh_resume_attempts or 0
            )
            overflows = 0
            attempt = 0
            while True:
                record = self._record(caps)
                self._compile_ahead(record)
                try:
                    sources = self._execute(
                        record, preempt, task_span, attempt
                    )
                    break
                except _Overflow as ov:
                    for site, _needed in ov.sites:
                        if site.startswith("err:single_row"):
                            raise RuntimeError(
                                "Scalar sub-query has returned multiple rows"
                            ) from None
                    overflows += 1
                    if overflows >= 12:
                        raise RuntimeError(
                            "mesh capacity retry limit exceeded"
                        )
                    # deterministic across executions: the widened
                    # sites and nothing else carry over to the retrace
                    caps = dict(caps)
                    for site, needed in ov.sites:
                        caps[site] = max(
                            record.resolved_caps.get(site, 16) * 2,
                            bucket_capacity(max(needed, 16)),
                        )
                    attempt += 1
                except (MeshStuck, MeshDeviceLost) as e:
                    # in-run resume: only when a live checkpoint exists
                    # and budget remains; otherwise the fault keeps its
                    # type and the coordinator's fallback dispatch (page
                    # plane / QUERY retry) takes over. Typed deadline /
                    # abandonment errors never land here — they
                    # propagate from preempt() uncaught.
                    key = self._ckpt_key()
                    ckpt = None
                    if (
                        key is not None
                        and resume_budget > 0
                        and getattr(e, "in_run_resumable", True)
                    ):
                        from trino_tpu.recovery.checkpoint import (
                            CHECKPOINTS,
                        )

                        ckpt = CHECKPOINTS.get(key)
                    if ckpt is None:
                        # annotate for the coordinator's failover path:
                        # with a live checkpoint under this key, the
                        # unstarted chunk range can be split across two
                        # sibling replicas (work stealing) — but only
                        # when every carry is an append accumulator
                        # (group carries hold cross-chunk state that
                        # cannot merge byte-identically)
                        e.ckpt_key = key
                        e.steal_ok = bool(record.carry_meta) and all(
                            kind == "out"
                            for kind, _fid in record.carry_meta
                        )
                        raise
                    resume_budget -= 1
                    if task_span is not None:
                        task_span.event(
                            "mesh_fault",
                            error=type(e).__name__,
                            resume_from=ckpt.next_chunk,
                        )
                    attempt += 1
            if overflows and learned_key is not None:
                with _learned_caps_lock:
                    _LEARNED_CAPS[learned_key] = dict(caps)
                    while len(_LEARNED_CAPS) > _LEARNED_CAPS_MAX:
                        _LEARNED_CAPS.pop(next(iter(_LEARNED_CAPS)))
            if record.warmup_entries:
                register_mesh_warmup(record.warmup_entries)
                note_classes_warm(record.class_keys)
            if self.skew_map:
                from trino_tpu.runtime.metrics import METRICS

                METRICS.increment(
                    "skew.salted_exchanges", len(self.skew_map)
                )
            stats = self._run_stats
            self.info = {
                "chunked": self.cplan.chunked,
                "chunks": record.n_chunks,
                "chunk_cap": record.chunk_cap,
                "driver_pos": self.cplan.driver_pos,
                "prelude_fragments": sorted(self.cplan.prelude_fids),
                "stream_fragments": sorted(self.cplan.stream_fids),
                "flush_fragments": sorted(self.cplan.flush_fids),
                "attempts": attempt + 1,
                "salted_exchanges": len(self.skew_map),
                "executed_chunk_steps": stats["executed_chunk_steps"],
                "checkpoints": stats["checkpoints"],
                "resumes": stats["resumes"],
                "resumed_from_chunk": stats["resumed_from_chunk"],
                "parks": stats["parks"],
                "unparks": stats["unparks"],
                "steals": stats["steals"],
            }
            key = self._ckpt_key()
            if key is not None:
                # a completed run's checkpoint is spent — a later
                # identical query must start fresh, not resume
                from trino_tpu.recovery.checkpoint import CHECKPOINTS

                CHECKPOINTS.discard(key)
            publish_run_info(self.info)
            self._record_divergences(sources, query_span)
            return sources
        finally:
            _ACTIVE_REPLICA.replica = prev_replica
            if task_span is not None:
                task_span.end()
                stage_span.end()

    def _execute(self, record: MeshProgramRecord, preempt, task_span,
                 attempt: int) -> Dict[int, list]:
        from trino_tpu.runtime.tracing import KIND_OPERATOR

        def op_span(name, **attrs):
            if task_span is None:
                return contextlib.nullcontext()
            return task_span.child(name, KIND_OPERATOR, **attrs)

        n = self.ex.n
        K = record.n_chunks
        watchdog_s = float(self.session.stuck_task_interrupt_s or 0.0)
        outs: Dict[int, Tuple[object, bool]] = {}

        if preempt is not None:
            preempt(0, K)
        pctx: tuple = ()
        if record.prelude_fn is not None:
            p_outs, pctx = self._run_prelude(
                record, task_span, op_span, attempt, n
            )
            for (fid, rep), b in zip(record.prelude_out_meta, p_outs):
                outs[fid] = (b, rep)

        interval = int(self.session.mesh_checkpoint_interval_chunks or 0)
        # park_key: program identity for scheduler parks (and for the
        # resume-on-entry lookup — a parked query failed over by a
        # drain resumes here on the sibling even with periodic
        # checkpointing off); ckpt_key additionally gates the
        # every-N-chunks fault snapshots
        park_key = self._ckpt_key() if self.cplan.chunked else None
        ckpt_key = park_key if interval > 0 else None

        carries: tuple = ()
        if record.step_fn is not None:
            k0 = 0
            carries = None
            if park_key is not None:
                from trino_tpu.recovery.checkpoint import CHECKPOINTS

                ck = CHECKPOINTS.get(park_key)
                if ck is not None and ck.n_chunks == K and 0 < ck.next_chunk <= K:
                    carries = self._restore_carries(ck, record)
                    if carries is not None:
                        k0 = ck.next_chunk
                        CHECKPOINTS.note_resume()
                        self._run_stats["resumes"] = (
                            int(self._run_stats["resumes"]) + 1
                        )
                        self._run_stats["resumed_from_chunk"] = k0
                        # deadline kills during the resumed stretch name
                        # the resume point — and, after a replica
                        # failover, which replica picked the run up
                        # (query_tracker embeds both in the typed
                        # [EXCEEDED_TIME_LIMIT] message)
                        try:
                            preempt.resumed_from = k0
                            preempt.resumed_on = active_replica()
                        except AttributeError:
                            pass  # bare-callable hooks (tests) are fine
                        if task_span is not None:
                            task_span.event("resume", from_chunk=k0, of=K)
            if carries is None:
                carries = tuple(
                    jax.tree_util.tree_map(
                        lambda s: jax.device_put(
                            jnp.zeros(s.shape, s.dtype), self.sharding
                        ),
                        t,
                    )
                    for t in record.carry_sds
                )
            drain_check = getattr(self.ex, "drain_check", None)
            # preemptive scheduler seat (runtime/scheduler.py): consult
            # at every completed boundary whether to keep the mesh,
            # yield in place, or park to the checkpoint store
            sched_job = getattr(self.ex, "sched_job", None)
            # drain-failover work stealing, primary side: at boundary
            # `mid` adopt the helper replica's [mid, K) carries instead
            # of executing those chunks ("merge", mid, key, done_event,
            # caps, timeout_s)
            steal = getattr(self.ex, "steal_ctx", None)
            if steal is not None and steal[0] != "merge":
                steal = None
            from trino_tpu.runtime.metrics import METRICS

            self._await_compile(record, "step")
            with op_span("MeshChunkStep", attempt=attempt, chunks=K):
                for k in range(k0, K):
                    if preempt is not None:
                        preempt(k, K)
                    if drain_check is not None:
                        # replica lifecycle: a drain requested on this
                        # sub-mesh raises MeshReplicaDraining here so
                        # the coordinator fails the run over to a
                        # sibling at this boundary
                        drain_check()
                    if MESH_FAULT_HOOK is not None:
                        MESH_FAULT_HOOK(k, K)
                    t0 = time.monotonic()
                    with host_span("mesh.step", chunk=k,
                                   **dataclasses.asdict(record.step_census)):
                        carries, flags = record.step_fn(
                            jnp.asarray(k, dtype=jnp.int32),
                            self.feed_args, pctx, carries,
                        )
                        # flag readback is the natural device sync point
                        self._check_flags(
                            record.step_sites, flags, n, "step_flags"
                        )
                    _count_exchanges(record.step_census)
                    dt = time.monotonic() - t0
                    self._run_stats["executed_chunk_steps"] = (
                        int(self._run_stats["executed_chunk_steps"]) + 1
                    )
                    # process-wide ledger: a failover spans TWO runners
                    # (the faulted one and the sibling's), so per-run
                    # stats alone cannot say how much work the whole
                    # query re-executed — bench's failover gate diffs
                    # this counter instead
                    METRICS.increment("mesh.chunk_steps")
                    # a completed boundary is a safe snapshot point:
                    # the flag readback synced the device, and the
                    # carries are only donated when passed into the
                    # NEXT step dispatch
                    if (
                        ckpt_key is not None
                        and (k + 1) % interval == 0
                        and (k + 1) < K
                    ):
                        self._checkpoint(
                            ckpt_key, record, carries, k + 1, K,
                            task_span,
                        )
                    if task_span is not None:
                        task_span.event(
                            "chunk", index=k, of=K, wall_s=round(dt, 6)
                        )
                    # chunk 0 pays the cold compile; boundary progress
                    # is only meaningful from the second chunk on
                    if watchdog_s and k >= 1 and dt > watchdog_s:
                        raise MeshStuck(
                            f"mesh chunk {k} made no boundary progress for "
                            f"{dt:.3f}s (stuck_task_interrupt_s="
                            f"{watchdog_s}); retryable on the page plane"
                        )
                    if (
                        steal is not None
                        and (k + 1) == steal[1]
                        and (k + 1) < K
                    ):
                        merged = self._steal_merge(record, carries, steal)
                        if merged is not None:
                            carries = merged
                            self._run_stats["steals"] = (
                                int(self._run_stats["steals"]) + 1
                            )
                            METRICS.increment("scheduler.steals")
                            if task_span is not None:
                                task_span.event(
                                    "steal_merge", at_chunk=k + 1, of=K
                                )
                            break  # helper computed [mid, K)
                        # helper failed: fall through and run the
                        # remainder sequentially (stealing is
                        # opportunistic, never correctness-bearing)
                        steal = None
                    if sched_job is not None and (k + 1) < K:
                        decision = sched_job.boundary(
                            k + 1, K, dt,
                            parkable=park_key is not None,
                        )
                        if decision == "park":
                            carries = self._park(
                                park_key, record, carries, k + 1, K,
                                task_span, sched_job,
                            )

        if preempt is not None:
            preempt(K, K)
        if record.flush_fn is not None:
            self._await_compile(record, "flush")
            with op_span("MeshFlush", attempt=attempt), host_span(
                "mesh.finish", **dataclasses.asdict(record.flush_census)
            ):
                f_outs, flags = record.flush_fn(
                    self.feed_args, pctx, carries
                )
                self._check_flags(
                    record.flush_sites, flags, n, "finish_flags"
                )
            _count_exchanges(record.flush_census)
            for (fid, rep), b in zip(record.flush_out_meta, f_outs):
                outs[fid] = (b, rep)

        for (kind, fid), c in zip(record.carry_meta, carries):
            if kind == "out":
                outs[fid] = (c, self.repl[fid])

        return {
            fid: self.ex._shard_pages(batch, rep)
            for fid, (batch, rep) in outs.items()
        }

    def _record_divergences(self, sources, query_span) -> None:
        """Adaptive-tier observability at the mesh barrier: diff each
        mesh fragment's exported row count (prelude exports + finished
        chunk-stream outputs) against the optimizer's estimate. Instant
        events + adaptive.divergences counters only — the mesh plane
        never re-plans mid-flight; a divergent query's NEXT execution
        re-plans through the controller."""
        try:
            from trino_tpu.adaptive.observer import record_observation
            from trino_tpu.sql.stats import StatsCalculator

            threshold = float(self.session.adaptive_replan_threshold or 4.0)
            from trino_tpu.sql.stats import PlanStats

            frag_rows: Dict[int, float] = {}

            class _FragmentStats(StatsCalculator):
                # producer fragments' estimates feed consumer leaves,
                # same stitching the coordinator's stage diff uses
                def _RemoteSourceNode(self, node):
                    rows = sum(
                        frag_rows.get(fid, 1.0)
                        for fid in node.fragment_ids
                    )
                    return PlanStats(max(rows, 1.0))

            calc = _FragmentStats(self.ex.catalogs)

            def estimate(sp) -> float:
                for c in sp.children:
                    estimate(c)
                fid = sp.fragment.id
                if fid not in frag_rows:
                    frag_rows[fid] = calc.stats(
                        sp.fragment.root
                    ).row_count
                return frag_rows[fid]

            for sp in self.mesh_sps:
                estimate(sp)
            for sp in self.mesh_sps:
                fid = sp.fragment.id
                pages = sources.get(fid)
                if pages is None:
                    continue
                observed = sum(int(p.row_count) for p in pages)
                record_observation(
                    f"mesh-fragment:{fid}", frag_rows.get(fid, 1.0),
                    observed, threshold, span=query_span,
                )
        except Exception:
            pass  # observability must never fail the run

    def _run_prelude(self, record: MeshProgramRecord, task_span, op_span,
                     attempt: int, n: int):
        """Prelude with a resident-tier consult: a warm hit reuses the
        pinned (p_outs, pctx) and skips the dispatch entirely (neither
        is ever donated — step donates only carries — so reuse is
        safe); a miss runs the prelude and pins the exported ctx under
        the feed tables' generation snapshot. Keyed off the program
        record key, so uncacheable plans (repr-identity leaks) never
        pin."""
        rkey = None
        budget_mb = int(self.session.resident_pin_budget_mb or 0)
        if self._last_record_key is not None and budget_mb > 0:
            from trino_tpu.resident import GENERATIONS, RESIDENT

            rkey = (
                "resident-mesh",
                self._last_record_key,
                GENERATIONS.snapshot(self.feed_tables),
            )
            cached = RESIDENT.lookup(rkey)
            if cached is not None:
                if task_span is not None:
                    task_span.event("resident_hit", tier="mesh-prelude")
                self.info["prelude_resident"] = True
                return cached
            # a live entry under a stale generation is unreachable by
            # key; reclaim its device memory eagerly
            for stale in RESIDENT.entries_for_prefix(
                ("resident-mesh", self._last_record_key)
            ):
                if stale != rkey and RESIDENT.evict(stale):
                    if task_span is not None:
                        task_span.event(
                            "resident_evict", tier="mesh-prelude"
                        )
        with op_span("MeshPrelude", attempt=attempt), host_span(
            "mesh.prelude", **dataclasses.asdict(record.prelude_census)
        ):
            p_outs, pctx, flags = record.prelude_fn(self.feed_args)
            self._check_flags(
                record.prelude_sites, flags, n, "prelude_flags"
            )
        _count_exchanges(record.prelude_census)
        if rkey is not None:
            import jax.tree_util as jtu

            from trino_tpu.resident import RESIDENT

            bytes_ = sum(
                int(getattr(x, "nbytes", 0))
                for x in jtu.tree_leaves((p_outs, pctx))
            )
            RESIDENT.configure(budget_mb << 20)
            RESIDENT.pin(
                rkey, (tuple(p_outs), pctx), bytes_,
                set(self.feed_tables),
            )
        return tuple(p_outs), pctx

    def _checkpoint(self, key, record, carries, next_chunk, K,
                    task_span) -> None:
        """Snapshot the device carries to the host checkpoint store as
        of having completed chunks [0, next_chunk). Best-effort: a
        snapshot failure must never fail the run it exists to protect."""
        try:
            from trino_tpu.recovery.checkpoint import (
                CHECKPOINTS,
                MeshCheckpoint,
            )
            from trino_tpu.resident import GENERATIONS

            host = tuple(
                jax.tree_util.tree_map(
                    lambda x: np.asarray(jax.device_get(x)), c
                )
                for c in carries
            )
            CHECKPOINTS.put(key, MeshCheckpoint(
                next_chunk=next_chunk,
                n_chunks=K,
                chunk_cap=record.chunk_cap,
                resolved_caps=dict(record.resolved_caps),
                carries_host=host,
                tables=self.feed_tables,
                generations=GENERATIONS.snapshot(self.feed_tables),
            ))
            self._run_stats["checkpoints"] = (
                int(self._run_stats["checkpoints"]) + 1
            )
            if task_span is not None:
                task_span.event("checkpoint", chunk=next_chunk, of=K)
            if CHECKPOINT_PUSH_HOOK is not None:
                CHECKPOINT_PUSH_HOOK(key)
        except Exception:
            pass

    def _park(self, key, record, carries, next_chunk, K, task_span,
              job) -> tuple:
        """Park this run: snapshot the device carries to the host
        checkpoint store (accounted against park_max_bytes), release
        the device memory, and block in the scheduler until regranted —
        then re-place the same snapshot and continue from `next_chunk`.

        Budget refusal returns the original carries untouched: the
        query keeps the mesh and runs to completion (degradation is
        never query failure). Typed kills (deadline / abandonment)
        raise out of the parked wait with the snapshot discarded — a
        dead query never resumes; mesh faults (drain surfacing while
        parked) keep the snapshot so a sibling replica can restore it
        through the host-portable path."""
        from trino_tpu.recovery.checkpoint import (
            CHECKPOINTS,
            MeshCheckpoint,
        )
        from trino_tpu.resident import GENERATIONS

        host = tuple(
            jax.tree_util.tree_map(
                lambda x: np.asarray(jax.device_get(x)), c
            )
            for c in carries
        )
        ckpt = MeshCheckpoint(
            next_chunk=next_chunk,
            n_chunks=K,
            chunk_cap=record.chunk_cap,
            resolved_caps=dict(record.resolved_caps),
            carries_host=host,
            tables=self.feed_tables,
            generations=GENERATIONS.snapshot(self.feed_tables),
        )
        if not CHECKPOINTS.park(key, ckpt, int(self.session.park_max_bytes)):
            job.park_refused()
            if task_span is not None:
                task_span.event("park_refused", chunk=next_chunk, of=K)
            return carries
        carries = None  # the snapshot is now the only copy
        self._run_stats["parks"] = int(self._run_stats["parks"]) + 1
        if task_span is not None:
            task_span.event("park", chunk=next_chunk, of=K)
        if CHECKPOINT_PUSH_HOOK is not None:
            try:
                CHECKPOINT_PUSH_HOOK(key)
            except Exception:
                pass  # push is best-effort; the park itself succeeded
        try:
            job.park_wait(next_chunk, K)
        except (MeshStuck, MeshDeviceLost):
            # mesh lifecycle fault while parked (drain): keep the
            # snapshot — the coordinator's failover restores it on a
            # sibling via the portable-bytes path
            CHECKPOINTS.unpark(key, keep=True)
            raise
        except BaseException:
            # typed kill (deadline / abandonment) while parked: the
            # query is dead and must never resume
            CHECKPOINTS.unpark(key, keep=False)
            raise
        # regranted: restore from the LOCAL snapshot object (immune to
        # DML generation invalidation — this run's feeds are an
        # immutable device snapshot, so its carries stay exact even if
        # the source tables moved on)
        restored = self._restore_carries(ckpt, record)
        CHECKPOINTS.unpark(key, keep=True)
        if restored is None:
            # cannot happen under an unchanged record (same caps, same
            # shapes) — but if it does, the kept store entry feeds the
            # in-run resume path rather than losing progress
            raise MeshDeviceLost(
                f"parked carries failed to restore at chunk {next_chunk}"
            )
        self._run_stats["unparks"] = (
            int(self._run_stats["unparks"]) + 1
        )
        if task_span is not None:
            task_span.event("unpark", chunk=next_chunk, of=K)
        return restored

    # -- drain-failover work stealing --------------------------------
    def run_steal_helper(self, steal) -> None:
        """Helper side: run chunks [mid, K) of a stolen query on this
        sub-mesh from ZERO carries and publish the resulting carries as
        a checkpoint under the steal key. No store resume on entry, no
        periodic checkpointing (the primary's own key is this program's
        identity — a helper snapshot would collide), no flush, no
        output emission: the primary merges these carries at its `mid`
        boundary and owns the rest of the run. Any failure simply skips
        the publish — the primary times out and continues sequentially."""
        _mode, mid, steal_key, done, caps = steal[:5]
        prev_replica = active_replica()
        _ACTIVE_REPLICA.replica = getattr(self.ex, "replica_id", None)
        try:
            from trino_tpu.recovery.checkpoint import (
                CHECKPOINTS,
                MeshCheckpoint,
            )
            from trino_tpu.resident import GENERATIONS
            from trino_tpu.runtime.metrics import METRICS

            record = self._record(dict(caps))
            n = self.ex.n
            K = record.n_chunks
            if not (0 < mid < K) or record.step_fn is None:
                return
            pctx: tuple = ()
            if record.prelude_fn is not None:
                p_outs, pctx = self._run_prelude(
                    record, None,
                    lambda name, **attrs: contextlib.nullcontext(),
                    0, n,
                )
            carries = tuple(
                jax.tree_util.tree_map(
                    lambda s: jax.device_put(
                        jnp.zeros(s.shape, s.dtype), self.sharding
                    ),
                    t,
                )
                for t in record.carry_sds
            )
            drain_check = getattr(self.ex, "drain_check", None)
            for k in range(mid, K):
                if drain_check is not None:
                    drain_check()
                carries, flags = record.step_fn(
                    jnp.asarray(k, dtype=jnp.int32),
                    self.feed_args, pctx, carries,
                )
                self._check_flags(record.step_sites, flags, n)
                METRICS.increment("mesh.chunk_steps")
            host = tuple(
                jax.tree_util.tree_map(
                    lambda x: np.asarray(jax.device_get(x)), c
                )
                for c in carries
            )
            CHECKPOINTS.put(steal_key, MeshCheckpoint(
                next_chunk=K,
                n_chunks=K,
                chunk_cap=record.chunk_cap,
                resolved_caps=dict(record.resolved_caps),
                carries_host=host,
                tables=self.feed_tables,
                generations=GENERATIONS.snapshot(self.feed_tables),
            ))
        except Exception:
            pass  # opportunistic: the primary covers [mid, K) itself
        finally:
            _ACTIVE_REPLICA.replica = prev_replica
            done.set()

    def _steal_merge(self, record, carries, steal) -> Optional[tuple]:
        """Primary side: adopt the helper's [mid, K) carries. Byte
        identity holds because `_accumulate` packs live rows densely at
        the front of each shard in chunk execution order — appending
        the helper's packed rows after the primary's per-shard live
        count reproduces exactly the layout a sequential run of chunks
        [mid, K) would have written, and both sides ran the same record
        at the same resolved caps so shard shapes agree. Returns None
        on any disagreement (timeout, caps drift, non-append carry,
        combined overflow): the primary continues sequentially."""
        _mode, mid, steal_key, done, caps, timeout_s = steal
        try:
            from trino_tpu.recovery.checkpoint import CHECKPOINTS

            if not done.wait(timeout_s):
                return None
            ck = CHECKPOINTS.get(steal_key)
            CHECKPOINTS.discard(steal_key)
            if (
                ck is None
                or ck.n_chunks != record.n_chunks
                or ck.resolved_caps != dict(record.resolved_caps)
                or len(ck.carries_host) != len(record.carry_sds)
            ):
                return None
            n = self.ex.n
            merged = []
            for (kind, _fid), mine_dev, theirs in zip(
                record.carry_meta, carries, ck.carries_host
            ):
                if kind != "out":
                    return None
                mine = jax.tree_util.tree_map(
                    lambda x: np.asarray(jax.device_get(x)), mine_dev
                )
                m = _merge_out_carry(mine, theirs, n)
                if m is None:
                    return None
                merged.append(m)
            return tuple(
                jax.device_put(b, self.sharding) for b in merged
            )
        except Exception:
            return None

    def _restore_carries(self, ck, record) -> Optional[tuple]:
        """Re-place a checkpoint's host carries onto the mesh, re-padding
        each accumulator whose capacity rung grew since the snapshot
        (overflow restarts bump caps; live rows stay densely packed at
        the front, so tail padding with dead rows is exact). Returns
        None — start fresh — on any shape disagreement."""
        n = self.ex.n
        try:
            if len(ck.carries_host) != len(record.carry_sds):
                return None
            host = []
            for (_kind, fid), batch in zip(
                record.carry_meta, ck.carries_host
            ):
                site = f"carry:f{fid}"
                old_cap = int(ck.resolved_caps.get(site, 0))
                new_cap = int(record.resolved_caps.get(site, old_cap))
                if old_cap and new_cap != old_cap:
                    if new_cap < old_cap:
                        return None  # shrunk rung: rows may not fit
                    batch = _pad_shards(batch, n, old_cap, new_cap)
                host.append(batch)
            for b, t in zip(host, record.carry_sds):
                bl = jax.tree_util.tree_leaves(b)
                tl = jax.tree_util.tree_leaves(t)
                if len(bl) != len(tl) or any(
                    np.shape(x) != s.shape
                    or np.asarray(x).dtype != s.dtype
                    for x, s in zip(bl, tl)
                ):
                    return None
            return tuple(
                jax.tree_util.tree_map(
                    lambda x: jax.device_put(
                        np.asarray(x), self.sharding
                    ),
                    b,
                )
                for b in host
            )
        except Exception:
            return None

    def _check_flags(self, sites, flag_arr, n, site="flags"):
        # where the host waits for the program it has just dispatched
        with host_sync("mesh." + site, nbytes=flag_arr.nbytes):
            vals = np.asarray(jax.device_get(flag_arr))
        if not sites:
            return
        over = vals.reshape(n, -1).max(axis=0)
        sites_over = [
            (site, int(v)) for site, v in zip(sites, over) if v
        ]
        if sites_over:
            raise _Overflow(sites_over)
