"""In-memory connector.

Analogue of plugin/trino-memory (MemoryPagesStore — SURVEY.md §2.12):
tables live as lists of host-side column arrays; supports CREATE TABLE,
INSERT (page sink), and scan. String columns keep one growing
table-wide dictionary so scans stay pipeline-bindable (see spi.py).
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from trino_tpu.analysis.witness import named_condition, named_lock, named_rlock
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.block import Column, Dictionary, RelBatch, bucket_capacity
from trino_tpu.runtime.tracing import host_span
from trino_tpu.connectors.spi import (
    ColumnMetadata,
    Connector,
    ConnectorMetadata,
    ConnectorPageSource,
    ConnectorPageSink,
    ConnectorSplitManager,
    Split,
    TableHandle,
    TableMetadata,
    TableStatistics,
)


def _never_descends(arr: np.ndarray) -> bool:
    """Whether no value of `arr` is below the one before it, a stretch
    of rows at a time: a column in no order says so in its first."""
    step = 1 << 20
    for at in range(0, len(arr) - 1, step):
        part = arr[at:at + step + 1]
        if np.any(part[1:] < part[:-1]):
            return False
    return True


@dataclasses.dataclass
class _StoredColumn:
    type: T.DataType
    data: np.ndarray  # host array, dense (no padding)
    valid: Optional[np.ndarray]
    dictionary: Optional[Dictionary]


@dataclasses.dataclass
class _StoredTable:
    schema: str
    name: str
    columns: List[ColumnMetadata]
    data: Dict[str, _StoredColumn] = dataclasses.field(default_factory=dict)
    row_count: int = 0
    version: int = 0  # bumped on writes; invalidates the device cache
    # device-resident batch cache: the Page/Block layer as persistent SoA
    # device arrays (SURVEY.md §2.5 "the layer that becomes TPU-resident")
    device_cache: Dict[tuple, list] = dataclasses.field(default_factory=dict)
    # declared bucketing: ordered key column names; splits are then 1:1
    # with engine-hash buckets (spi.ConnectorMetadata.table_partitioning)
    bucketed_by: Optional[Tuple[str, ...]] = None
    # (version, n_buckets) -> int32 bucket id per row
    bucket_cache: Dict[tuple, np.ndarray] = dataclasses.field(default_factory=dict)
    # the mesh plane's scan feeds, sharded over its devices, keyed by
    # (version, columns, predicate, placement): parallel/mesh_feed.py
    mesh_feeds: Dict[tuple, object] = dataclasses.field(default_factory=dict)


class _Store:
    """The MemoryPagesStore analogue; guarded for concurrent inserts."""

    def __init__(self):
        self.tables: Dict[Tuple[str, str], _StoredTable] = {}
        self.lock = named_lock("_Store.lock")
        self._ids = itertools.count()


class MemoryMetadata(ConnectorMetadata):
    def __init__(self, store: _Store):
        self.store = store
        # (schema, table) -> (stored-table obj, version, TableStatistics)
        self._stats_cache: Dict[Tuple[str, str], tuple] = {}

    def list_schemas(self) -> List[str]:
        return sorted({s for s, _ in self.store.tables} | {"default"})

    def list_tables(self, schema: str) -> List[str]:
        return sorted(n for s, n in self.store.tables if s == schema)

    def get_table_handle(self, schema: str, table: str) -> Optional[TableHandle]:
        if (schema, table) not in self.store.tables:
            return None
        return TableHandle("memory", schema, table)

    def get_table_metadata(self, handle: TableHandle) -> TableMetadata:
        t = self.store.tables[(handle.schema, handle.table)]
        return TableMetadata(handle.schema, handle.table, tuple(t.columns))

    def column_dictionary(self, handle: TableHandle, column: str) -> Optional[Dictionary]:
        t = self.store.tables[(handle.schema, handle.table)]
        sc = t.data.get(column)
        return sc.dictionary if sc is not None else None

    def table_partitioning(self, handle: TableHandle):
        t = self.store.tables[(handle.schema, handle.table)]
        return t.bucketed_by

    def get_table_statistics(self, handle: TableHandle) -> TableStatistics:
        """Row count + sampled per-column (ndv, null_fraction, min, max).

        The reference's memory connector reports only row counts
        (MemoryMetadata.getTableStatistics), which starves the CBO: join
        orientation then rides on guessed NDVs, and a wrong guess builds
        the lookup on the BIG side (measured: TPC-H Q3 built on lineitem
        instead of orders x customer). We hold the actual arrays, so
        estimate honestly: stride-sample up to 256k rows, Duj1-estimate
        NDV from sample singletons, exact min/max (an integer column's
        are listed in `exact_ranges`: taken over every row, not the
        sample; one whose values never descend from a row to the next
        in `ordered`). Cached per table version (writes invalidate)."""
        t = self.store.tables[(handle.schema, handle.table)]
        key = (handle.schema, handle.table)
        cached = self._stats_cache.get(key)
        if cached is not None and cached[0] is t and cached[1] == t.version:
            return cached[2]
        cols: Dict[str, tuple] = {}
        exact, ordered = set(), set()
        n = t.row_count
        for name, sc in t.data.items():
            if n == 0 or isinstance(sc.data, list):  # empty or ARRAY column
                continue
            arr = sc.data[:n]
            nf = 0.0
            if sc.valid is not None:
                nf = float(1.0 - np.count_nonzero(sc.valid[:n]) / n)
                # null slots hold placeholder payloads (the page sink keeps
                # whatever bytes the source batch had) — they must not leak
                # into ndv/min/max
                arr = arr[sc.valid[:n]]
                if len(arr) == 0:
                    cols[name] = (0.0, nf, None, None)
                    continue
            pop = len(arr)  # non-null population
            sample = arr[:: max(1, pop // 262144)]
            s = len(sample)
            vals, counts = np.unique(sample, return_counts=True)
            d = float(len(vals))
            f1 = float(np.count_nonzero(counts == 1))
            # Duj1: ndv = d / (1 - ((pop-s)/pop) * (f1/s)) — all-singleton
            # samples extrapolate to ~pop, saturated samples stay at d
            denom = 1.0 - ((pop - s) / pop) * (f1 / max(s, 1))
            ndv = min(d / max(denom, 1e-9), float(pop))
            if s < pop:
                # a stride never meets the neighbours that repeat a value,
                # so a clustered column (a fact table's order key) reads
                # as all singletons; its runs of equal neighbours, counted
                # exactly, bound its distinct values whatever the sample
                ndv = min(ndv, float(np.count_nonzero(arr[1:] != arr[:-1]) + 1))
            lo = hi = None
            if not sc.type.is_string and arr.dtype.kind in "iuf":
                least, most = arr.min(), arr.max()
                lo, hi = float(least), float(most)
                # (a float holds an integer exactly up to 2^53)
                if arr.ndim == 1 and arr.dtype.kind in "iu":
                    if lo == int(least) and hi == int(most):
                        exact.add(name)
                    if _never_descends(arr):
                        ordered.add(name)
            cols[name] = (ndv, nf, lo, hi)
        ts = TableStatistics(
            row_count=float(n), columns=cols, exact_ranges=frozenset(exact),
            ordered=frozenset(ordered),
        )
        self._stats_cache[key] = (t, t.version, ts)
        return ts

    def apply_filter(self, handle: TableHandle, constraints):
        """Accept constraints on flat numeric/temporal columns; the page
        source masks the stored arrays before materializing device
        batches (exact enforcement, composed with bucket splits)."""
        from trino_tpu.connectors.pushdown import (
            merge_handle_constraints,
            split_supported,
        )

        t = self.store.tables[(handle.schema, handle.table)]
        types = {c.name: c.type for c in t.columns}
        accepted, residual = split_supported(constraints, types.get)
        if not accepted:
            return None
        return merge_handle_constraints(handle, accepted), tuple(residual)

    def apply_projection(self, handle: TableHandle, columns) -> TableHandle:
        # _materialize already builds only the requested columns
        return handle

    def create_table(self, schema: str, table: str, columns: Sequence[ColumnMetadata]) -> TableHandle:
        with self.store.lock:
            if (schema, table) in self.store.tables:
                raise ValueError(f"table '{schema}.{table}' already exists")
            st = _StoredTable(schema, table, list(columns))
            for c in columns:
                if c.type.kind == T.TypeKind.ARRAY:
                    st.data[c.name] = _StoredColumn(
                        c.type, [], None,
                        Dictionary([]) if c.type.element.is_string else None,
                    )
                    continue
                if c.type.is_nested:  # MAP / ROW: python-object storage
                    st.data[c.name] = _StoredColumn(c.type, [], None, None)
                    continue
                shape = (0, 2) if c.type.lanes == 2 else (0,)
                st.data[c.name] = _StoredColumn(
                    c.type,
                    np.zeros(shape, dtype=c.type.dtype),
                    None,
                    Dictionary([]) if c.type.is_string else None,
                )
            self.store.tables[(schema, table)] = st
        return TableHandle("memory", schema, table)

    def truncate_table(self, handle: TableHandle) -> None:
        with self.store.lock:
            t = self.store.tables[(handle.schema, handle.table)]
            for sc in t.data.values():
                sc.data = sc.data[:0]
                sc.valid = None
            t.row_count = 0
            t.version += 1

    def drop_table(self, handle: TableHandle) -> None:
        with self.store.lock:
            self.store.tables.pop((handle.schema, handle.table), None)
            # the stats cache pins the stored table (host arrays + the
            # device-resident batch cache); a dropped table must free both
            self._stats_cache.pop((handle.schema, handle.table), None)


class MemorySplitManager(ConnectorSplitManager):
    def __init__(self, store: _Store):
        self.store = store

    def get_splits(self, handle: TableHandle, target_split_count: int) -> List[Split]:
        t = self.store.tables[(handle.schema, handle.table)]
        n = t.row_count
        if t.bucketed_by and target_split_count > 1:
            # bucketed table: EXACTLY the requested count, split i = the
            # rows whose engine key-hash lands in partition i of k. The
            # scheduler's task p <- splits[p::tc] rule then puts bucket i
            # on task i, which is what the planner's cancelled exchange
            # assumed (spi.ConnectorMetadata.table_partitioning). A
            # single-task request skips the hash: one full row-range
            # split IS the 1-bucket partitioning
            return [
                Split(handle, i, None, ("bucket", i, target_split_count))
                for i in range(target_split_count)
            ]
        k = max(1, min(target_split_count, max(n, 1)))
        per = -(-max(n, 1) // k)
        return [
            Split(handle, s, (a, min(a + per, n)))
            for s, a in enumerate(range(0, max(n, 1), per))
        ]


class MemoryPageSource(ConnectorPageSource):
    def __init__(self, store: _Store):
        self.store = store

    def batches(self, split: Split, columns: Sequence[str], batch_rows: int,
                stabilizer=None) -> Iterator[RelBatch]:
        t = self.store.tables[(split.table.schema, split.table.table)]
        cs = getattr(split.table, "constraints", ())
        # the stabilizer changes batch capacities, so it must key the
        # device cache (sessions with different ladders cannot share)
        stab_sig = (
            (stabilizer.ladder.base, stabilizer.ladder.min_capacity)
            if stabilizer is not None else None
        )
        if split.payload is not None and split.payload[0] == "bucket":
            _, bi, nb = split.payload
            idx = np.nonzero(self._bucket_ids(t, nb) == bi)[0]
            lo = hi = None
            cache_key = (t.version, tuple(columns), batch_rows, "bucket", bi,
                         nb, cs, stab_sig)
        else:
            lo, hi = split.row_range
            idx = None
            cache_key = (t.version, tuple(columns), batch_rows, lo, hi, cs,
                         stab_sig)
        # leaf spans of a profiler trace (runtime/tracing.py), around the
        # work between the yields: `scan.batches` says whether the
        # split's batches were on the device; where not, the scan pays
        # `scan.host_filter` once and `scan.to_device` per batch
        with host_span("scan.batches", cached=0) as span:
            cached = t.device_cache.get(cache_key)
            if cached is not None:
                span.set_metadata(cached=1)
        if cached is not None:
            yield from cached
            return
        if cs and t.row_count:
            # pushed-down predicate: mask the stored arrays, then route
            # the surviving row indices through the gather path (the
            # same one bucket splits use)
            from trino_tpu.connectors.pushdown import constraint_mask

            n = t.row_count
            with host_span("scan.host_filter", rows=n):
                mask = constraint_mask(
                    cs,
                    lambda name: (
                        np.asarray(t.data[name].data[:n]),
                        None if t.data[name].valid is None
                        else t.data[name].valid[:n],
                    ),
                )
                if idx is None:
                    idx = np.nonzero(mask[lo:hi])[0] + lo
                    lo = hi = None
                else:
                    idx = idx[mask[idx]]
        out = []
        batches = self._materialize(t, columns, batch_rows, lo, hi, idx,
                                    stabilizer=stabilizer)
        while True:
            with host_span("scan.to_device"):
                batch = next(batches, None)
            if batch is None:
                break
            out.append(batch)
            yield batch
        for k in [k for k in t.device_cache if k[0] != t.version]:
            # pop, not del: parallel tasks snapshot the same stale keys
            t.device_cache.pop(k, None)
        t.device_cache[cache_key] = out

    @staticmethod
    def _dealt_columns(t, columns: Sequence[str]):
        """The stored columns, where the table's rows can be dealt out
        by position; None under declared bucketing (its splits are hash
        buckets the planner counted on) or with nested columns."""
        stored = [t.data[name] for name in columns]
        if t.bucketed_by or any(
            isinstance(sc.data, list) or sc.type.is_nested for sc in stored
        ):
            return None
        return stored

    def host_shards(self, handle: TableHandle, columns: Sequence[str],
                    n: int):
        """The rows a scan of `handle` reads, dealt into `n` contiguous
        runs of the host's arrays: what the mesh plane places on its
        devices shard by shard (parallel/mesh_feed.py), so that no
        device ever holds the table. A pushed-down predicate is applied
        here, as `batches` applies it. Returns (rows, fetch): the row
        count of each shard, and `fetch(j, s)`, the (data, valid | None)
        of column j in shard s: a view where the scan reads the whole
        table, a gathered copy under a predicate (safe to call from
        several threads). None where the table cannot be dealt by
        position (`_dealt_columns`)."""
        t = self.store.tables[(handle.schema, handle.table)]
        stored = self._dealt_columns(t, columns)
        if stored is None:
            return None
        cs = getattr(handle, "constraints", ())
        total = t.row_count
        idx = None
        if cs and total:
            from trino_tpu.connectors.pushdown import constraint_mask

            with host_span("scan.host_filter", rows=total):
                idx = np.nonzero(constraint_mask(
                    cs,
                    lambda name: (
                        np.asarray(t.data[name].data[:total]),
                        None if t.data[name].valid is None
                        else t.data[name].valid[:total],
                    ),
                ))[0]
            total = len(idx)
        per = -(-total // n) if total else 0
        bounds = [(min(s * per, total), min((s + 1) * per, total))
                  for s in range(n)]

        def fetch(j: int, s: int):
            lo, hi = bounds[s]
            sel = slice(lo, hi) if idx is None else idx[lo:hi]
            sc = stored[j]
            return sc.data[sel], None if sc.valid is None else sc.valid[sel]

        return [hi - lo for lo, hi in bounds], fetch

    def mesh_feeds(self, handle: TableHandle, columns: Sequence[str]):
        """(the table's cache of what the mesh plane has placed, the key
        prefix that names this scan in it: version and predicate, and
        [(type, dictionary, has nulls)] of `columns`, or None where
        `host_shards` would refuse the table). What was placed of an
        older version is dropped here, with its device memory."""
        t = self.store.tables[(handle.schema, handle.table)]
        for k in [k for k in t.mesh_feeds if k[0] != t.version]:
            t.mesh_feeds.pop(k, None)
        stored = self._dealt_columns(t, columns)
        meta = None if stored is None else [
            (sc.type, sc.dictionary, sc.valid is not None) for sc in stored
        ]
        return t.mesh_feeds, (
            t.version, getattr(handle, "constraints", ()),
        ), meta

    def _bucket_ids(self, t, nb: int) -> np.ndarray:
        """Row -> bucket id with the engine's own exchange hash (the
        lock-step host replica, ops/hashing.hash32_np), so a split of a
        bucketed table holds exactly the rows a runtime repartition on
        the same keys would have routed to that partition. Cached per
        (table version, bucket count)."""
        key = (t.version, nb)
        got = t.bucket_cache.get(key)
        if got is not None:
            return got
        from trino_tpu.ops.hashing import (
            dictionary_lut, hash32_np, partition_of_np,
        )

        n = t.row_count
        lanes, valids = [], []
        for name in t.bucketed_by:
            sc = t.data[name]
            lut = dictionary_lut(sc.dictionary)
            if lut is not None:
                codes = np.clip(np.asarray(sc.data[:n]), 0, len(lut) - 1)
                lanes.append(lut[codes.astype(np.int64)])
            else:
                lanes.append(np.asarray(sc.data[:n]).astype(np.int64))
            valids.append(None if sc.valid is None else sc.valid[:n])
        bids = partition_of_np(hash32_np(lanes, valids), nb)
        for k in [k for k in t.bucket_cache if k[0] != t.version]:
            # pop, not del: parallel tasks snapshot the same stale keys
            t.bucket_cache.pop(k, None)
        t.bucket_cache[key] = bids
        return bids

    def _materialize(self, t, columns: Sequence[str], batch_rows: int,
                     lo, hi, idx: Optional[np.ndarray] = None,
                     stabilizer=None) -> Iterator[RelBatch]:
        """Chunk either a contiguous [lo, hi) row range (plain splits —
        ndarray slicing, one memcpy per column) or an explicit row-index
        array (bucket splits — gathered copy)."""
        from trino_tpu.block import ArrayColumn

        if idx is None:
            total = hi - lo
            sels = (slice(a, min(a + batch_rows, hi))
                    for a in range(lo, hi, batch_rows))
        else:
            total = len(idx)
            sels = (idx[a: a + batch_rows]
                    for a in range(0, total, batch_rows))
        for sel in sels:
            ranged = isinstance(sel, slice)
            n = (sel.stop - sel.start) if ranged else len(sel)
            if stabilizer is None:
                cap = bucket_capacity(n)
            elif ranged:
                # contiguous chunks are unpruned: the slice length IS
                # the span, so main/tail classes match the census
                cap = stabilizer.chunk_capacity(n)
            else:
                # index-gathered chunks (pushdown-pruned rows, bucket
                # splits) have data-dependent sizes; pad to the table's
                # main scan class so pruning never mints a new lowering
                cap = stabilizer.chunk_capacity(min(t.row_count, batch_rows))
            cols = []
            for name in columns:
                sc = t.data[name]
                if sc.type.kind == T.TypeKind.ARRAY:
                    # array columns store python lists host-side; the
                    # batch view flattens the slice (ArrayBlock layout)
                    rows = (list(sc.data[sel]) if ranged
                            else [sc.data[j] for j in sel])
                    cols.append(ArrayColumn.from_pylists(
                        sc.type.element, rows + [None] * (cap - n),
                        capacity=cap, dictionary=sc.dictionary,
                    ))
                    continue
                if sc.type.is_nested:  # MAP / ROW
                    rows = (list(sc.data[sel]) if ranged
                            else [sc.data[j] for j in sel])
                    cols.append(Column.from_pylist(
                        sc.type, rows, capacity=cap,
                    ))
                    continue
                shape = (cap, 2) if sc.type.lanes == 2 else (cap,)
                arr = np.zeros(shape, dtype=sc.type.dtype)
                arr[:n] = sc.data[sel]
                valid = None
                if sc.valid is not None:
                    v = np.zeros(cap, dtype=bool)
                    v[:n] = sc.valid[sel]
                    valid = jnp.asarray(v)
                cols.append(Column(sc.type, jnp.asarray(arr), valid, sc.dictionary))
            live = None
            if n != cap:
                lv = np.zeros(cap, dtype=bool)
                lv[:n] = True
                live = jnp.asarray(lv)
            yield RelBatch(cols, live)
        if total == 0:  # empty split: one empty batch so schemas propagate
            cols = []
            for name in columns:
                sc = t.data[name]
                if sc.type.kind == T.TypeKind.ARRAY:
                    cols.append(ArrayColumn.from_pylists(
                        sc.type.element, [None] * 16, capacity=16,
                        dictionary=sc.dictionary,
                    ))
                    continue
                if sc.type.is_nested:  # MAP / ROW
                    cols.append(Column.from_pylist(
                        sc.type, [None] * 16, capacity=16,
                    ))
                    continue
                from trino_tpu.block import phys_zeros

                cols.append(Column(
                    sc.type, phys_zeros(sc.type, 16),
                    None, sc.dictionary,
                ))
            yield RelBatch(cols, jnp.zeros(16, dtype=jnp.bool_))


class MemoryPageSink(ConnectorPageSink):
    """Appends batches; string columns re-encode into the table's growing
    dictionary (unify) so the table dictionary stays authoritative."""

    def __init__(self, store: _Store, handle: TableHandle):
        self.store = store
        self.handle = handle
        self.rows = 0

    def append(self, batch: RelBatch) -> None:
        from trino_tpu.block import ArrayColumn

        key = (self.handle.schema, self.handle.table)
        live = np.asarray(batch.live_mask())
        with self.store.lock:
            t = self.store.tables[key]
            n = int(live.sum())
            for cm, col in zip(t.columns, batch.columns):
                sc = t.data[cm.name]
                if cm.type.kind == T.TypeKind.ARRAY:
                    if not isinstance(col, ArrayColumn):
                        raise TypeError(
                            f"column {cm.name}: expected ARRAY data"
                        )
                    # decode to the host list-of-lists store (and fold
                    # string elements into the table dictionary)
                    rows = [
                        r for r, k in zip(col.to_pylist(), live) if k
                    ]
                    if cm.type.element.is_string:
                        merged = Dictionary(
                            (sc.dictionary.values if sc.dictionary else ())
                            + tuple(
                                v for r in rows if r is not None
                                for v in r if v is not None
                            )
                        )
                        sc.dictionary = merged
                    sc.data = list(sc.data) + rows
                    continue
                data = np.asarray(col.data)[live]
                valid = np.asarray(col.valid)[live] if col.valid is not None else None
                if cm.type.is_string:
                    incoming = col.dictionary or Dictionary([])
                    merged, remap_old, remap_new = Dictionary.unify(sc.dictionary, incoming)
                    if len(remap_old):
                        sc.data = remap_old[sc.data] if len(sc.data) else sc.data
                    data = remap_new[np.clip(data, 0, max(len(incoming) - 1, 0))] if len(incoming) else data
                    sc.dictionary = merged
                    # back-patch: table dictionary object changes identity;
                    # readers pick up the new one on next scan
                sc.data = np.concatenate([sc.data, data.astype(sc.type.dtype)])
                if valid is not None or sc.valid is not None:
                    old_valid = (
                        sc.valid if sc.valid is not None
                        else np.ones(t.row_count, dtype=bool)
                    )
                    new_valid = valid if valid is not None else np.ones(n, dtype=bool)
                    sc.valid = np.concatenate([old_valid, new_valid])
            t.row_count += n
            t.version += 1
            self.rows += n

    def finish(self) -> int:
        return self.rows


class MemoryTransactionHandle:
    """Buffers writes until commit (read-committed: in-transaction
    scans do NOT see the transaction's own pending writes — a
    documented simplification; the reference's memory connector has no
    cross-statement write transactions at all)."""

    def __init__(self, store: _Store):
        self.store = store
        self._pending: List[tuple] = []  # (handle, batch)

    def stage(self, handle: TableHandle, batch: RelBatch) -> None:
        self._pending.append((handle, batch))

    def commit(self) -> None:
        for handle, batch in self._pending:
            MemoryPageSink(self.store, handle).append(batch)
        self._pending.clear()

    def rollback(self) -> None:
        self._pending.clear()


class _TransactionalMemorySink(ConnectorPageSink):
    def __init__(self, txn: MemoryTransactionHandle, handle: TableHandle):
        self.txn = txn
        self.handle = handle
        self.rows = 0

    def append(self, batch: RelBatch) -> None:
        self.txn.stage(self.handle, batch)
        import jax

        self.rows += int(jax.device_get(batch.live_mask()).sum())

    def finish(self) -> int:
        return self.rows  # publish happens at transaction commit


class MemoryConnector(Connector):
    def __init__(self):
        store = _Store()
        super().__init__(
            "memory",
            MemoryMetadata(store),
            MemorySplitManager(store),
            MemoryPageSource(store),
        )
        self.store = store

    def begin_transaction(self, read_only: bool = False):
        return MemoryTransactionHandle(self.store)

    def replace_rows(self, handle: TableHandle, batches) -> None:
        """Atomically replace the table's rows with `batches` (the
        DELETE/UPDATE rewrite commit): stage into a detached copy of
        the table, then swap under the store lock — a mid-stage failure
        leaves the original untouched."""
        key = (handle.schema, handle.table)
        with self.store.lock:
            t = self.store.tables[key]
            staging = _StoredTable(t.schema, t.name, list(t.columns))
            for cm in t.columns:
                src = t.data[cm.name]
                staging.data[cm.name] = _StoredColumn(
                    cm.type,
                    src.data[:0],
                    None,
                    src.dictionary,  # keep the table dictionary stable
                )
        staging_store = _Store()
        staging_store.tables[key] = staging
        sink = MemoryPageSink(staging_store, handle)
        for b in batches:
            sink.append(b)
        with self.store.lock:
            t = self.store.tables.get(key)
            if t is None:
                raise KeyError(f"table {key} dropped during rewrite")
            t.data = staging.data
            t.row_count = staging.row_count
            t.version += 1
            t.device_cache.clear()
            t.mesh_feeds.clear()

    def page_sink(self, handle: TableHandle, transaction=None) -> ConnectorPageSink:
        if isinstance(transaction, MemoryTransactionHandle):
            return _TransactionalMemorySink(transaction, handle)
        return MemoryPageSink(self.store, handle)

    def load_table(
        self,
        schema: str,
        table: str,
        columns: Sequence[ColumnMetadata],
        arrays: Sequence[np.ndarray],
        valids: Sequence[Optional[np.ndarray]] = None,
        dictionaries: Sequence[Optional[Dictionary]] = None,
        bucketed_by: Optional[Sequence[str]] = None,
    ) -> None:
        """Bulk-load dense host columns (benchmark/fixture path).
        `bucketed_by` declares engine-hash bucketing on the named key
        columns (integer-family or dictionary-string types): splits then
        become hash buckets and co-bucketed joins/aggregations plan
        exchange-free (spi.ConnectorMetadata.table_partitioning)."""
        handle = self.metadata.create_table(schema, table, columns)
        t = self.store.tables[(schema, table)]
        if bucketed_by:
            by_name = {cm.name: cm for cm in columns}
            for c in bucketed_by:
                cm = by_name.get(c)
                if cm is None:
                    raise ValueError(f"bucketed_by column {c!r} not in table")
                ok = cm.type.is_string or (
                    not cm.type.is_nested
                    and cm.type.kind != T.TypeKind.ARRAY
                    and cm.type.lanes == 1
                    and np.issubdtype(np.dtype(cm.type.dtype), np.integer)
                )
                if not ok:
                    # float keys need the 3-lane f64 decomposition and
                    # long decimals the 4-lane limb split; neither has a
                    # host-side replica yet
                    raise ValueError(
                        f"bucketed_by column {c!r}: only integer-family "
                        f"and string types can declare bucketing"
                    )
            t.bucketed_by = tuple(bucketed_by)
        n = len(arrays[0]) if arrays else 0
        for i, (cm, arr) in enumerate(zip(columns, arrays)):
            if cm.type.kind == T.TypeKind.ARRAY:
                # python list-of-lists storage; strings get one
                # table-stable element dictionary
                d = None
                if cm.type.element.is_string:
                    d = Dictionary([
                        v for row in arr if row is not None
                        for v in row if v is not None
                    ])
                t.data[cm.name] = _StoredColumn(cm.type, list(arr), None, d)
                continue
            if cm.type.is_nested:  # MAP / ROW: python-object storage
                t.data[cm.name] = _StoredColumn(cm.type, list(arr), None, None)
                continue
            d = dictionaries[i] if dictionaries else None
            if cm.type.is_string and d is None:
                # convenience: raw python strings -> dictionary + codes
                vals = list(arr)
                d = Dictionary([v for v in vals if v is not None])
                arr = np.asarray(
                    [d.code(v) if v is not None else 0 for v in vals],
                    dtype=np.int32,
                )
            t.data[cm.name] = _StoredColumn(
                cm.type,
                np.asarray(arr, dtype=cm.type.dtype),
                valids[i] if valids else None,
                d if d is not None else (
                    Dictionary([]) if cm.type.is_string else None
                ),
            )
        t.row_count = n
        t.version += 1


def create_memory_connector() -> Connector:
    return MemoryConnector()
