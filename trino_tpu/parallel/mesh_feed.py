"""Scan feeds of the mesh plane: every device holds its shard, placed once.

A mesh program reads each scan as one global array per column, sharded
over the mesh axis (shard s's rows live on device s). Where the
connector can deal a table's rows out by position (`host_shards`: the
memory connector), the feed goes from the host's arrays to each device's
shard directly, and stays there: what is placed of a scan is kept with
the table (`mesh_feeds`), keyed by the table's version, the pushed-down
predicate and the placement, column by column as traced programs come
to read them, so a second statement over the same scan reads what is on
the devices. No device ever holds the table, and nothing passes through
device 0. Other connectors keep the general
path of `MeshExecutor._load_scans` (scan through the operator, stack on
the host, place per query).

Capacity. A shard of at most `AUTO_CHUNK_ROWS` rows is padded to its
power-of-two bucket, as the general path pads, and runs as one program.
A larger one is padded to a whole number of `AUTO_CHUNK_ROWS` chunks:
`mesh_chunk.build_chunk_plan` then streams it chunk by chunk, because
the exchange buffers of one program over the whole shard (sender's
capacity times the mesh width) would not fit beside it. The sizes
decide; `mesh_chunk_rows` only overrides them.

Counters (`runtime/metrics.METRICS`): `mesh.rows_fed` and
`mesh.rows_fed.dev<i>`, the live rows of the feeds a statement's
programs read, per statement and per device (the plane's counterpart of
`rows_scanned`, which only the scan operator moves); `mesh.feed_builds`
and `mesh.bytes_fed`, the placements and their bytes. A placement is a
`mesh.feed` span of a running profiler trace.
"""

from __future__ import annotations

import concurrent.futures
from typing import Dict, Optional

import jax
import numpy as np

from trino_tpu.analysis.witness import named_lock
from trino_tpu.block import Column, RelBatch, bucket_capacity
from trino_tpu.runtime.tracing import host_span

# rows of one shard above which a scan is streamed in chunks of this many
# rows. At 2^22 a chunk's exchange buffers (4 x 2^22 rows a column on a
# four-wide mesh) and its join scratch stay under 2 GB a device.
AUTO_CHUNK_ROWS = 1 << 22

# threads that fetch, pad and transfer the pieces of one feed
PLACE_THREADS = 8

# one placement at a time: two statements that miss on the same scan
# must not both put it on the devices
_place_lock = named_lock("mesh_feed._place_lock")


def chunk_rows_for(session, shard_rows: int) -> int:
    """Rows per chunk of a driver scan whose largest shard has
    `shard_rows` rows; 0 means one program."""
    explicit = int(session.mesh_chunk_rows or 0)
    if explicit > 0:
        return explicit
    return AUTO_CHUNK_ROWS if shard_rows > AUTO_CHUNK_ROWS else 0


def shard_capacity(shard_rows: int) -> int:
    if shard_rows <= AUTO_CHUNK_ROWS:
        return bucket_capacity(max(shard_rows, 1))
    return -(-shard_rows // AUTO_CHUNK_ROWS) * AUTO_CHUNK_ROWS


class _Placed:
    """What of one scan is on the devices: its live mask and, column by
    column as programs come to read them, the columns (the table keeps
    it in `mesh_feeds`, keyed by version, predicate and placement)."""

    def __init__(self, rows, cap):
        self.rows = rows            # live rows per device
        self.cap = cap              # rows of capacity per device
        self.live = None
        self.columns: Dict[str, tuple] = {}   # name -> (data, valid | None)


class Feed:
    """One scan's feed before its columns are placed. `template` has the
    feed's shapes (a RelBatch of ShapeDtypeStructs, global), which is all
    a mesh program needs to be traced; `place(used)` then puts on the
    devices the columns the traced programs read, and stands one shared
    block of zeros in for each column nothing reads (a scan lists every
    column its plan node has, a join-only plan prunes none of them, and
    a column of the fact table is hundreds of megabytes a device)."""

    def __init__(self, ex, source, node):
        self.ex, self.source, self.node = ex, source, node
        self.columns = list(node.columns)
        self.devices = list(ex.mesh.devices.flat)
        self.sharding = ex.sharding
        cache, prefix, self.meta = source.mesh_feeds(node.handle, self.columns)
        if self.meta is None:
            return
        key = prefix + (tuple(d.id for d in self.devices),)
        self.placed = cache.get(key)
        self._dealt = None
        if self.placed is None:
            rows, _fetch = self._deal()
            self.placed = cache[key] = _Placed(
                rows, shard_capacity(max(rows, default=0)))
        n, cap = ex.n, self.placed.cap

        def sds(dtype, tail=()):
            return jax.ShapeDtypeStruct((n * cap,) + tail, dtype)

        self.template = RelBatch([
            Column(typ, sds(typ.dtype, (2,) if typ.lanes == 2 else ()),
                   sds(np.bool_) if has_nulls else None, dictionary)
            for typ, dictionary, has_nulls in self.meta
        ], sds(np.bool_))

    def _deal(self):
        """The host side of the scan (its predicate applied over the
        whole table): only when something has to be fetched."""
        if self._dealt is None:
            self._dealt = self.source.host_shards(
                self.node.handle, self.columns, self.ex.n)
        return self._dealt

    def place(self, used) -> RelBatch:
        """The feed as sharded device arrays; `used` says, leaf by leaf
        of `template` (flattened), which ones a program reads."""
        labels = RelBatch([
            Column(c.type, ("data", j), None if c.valid is None else ("valid", j),
                   c.dictionary)
            for j, c in enumerate(self.template.columns)
        ], ("live", -1))
        wanted = {
            label for label, u in zip(
                jax.tree_util.tree_leaves(
                    labels, is_leaf=lambda x: isinstance(x, tuple)), used)
            if u
        }
        placed = self.placed
        missing = [
            j for j, name in enumerate(self.columns)
            if name not in placed.columns
            and (("data", j) in wanted or ("valid", j) in wanted)
        ]
        nbytes = 0
        if missing or placed.live is None:
            with host_span("mesh.feed", table=str(self.node.handle.table),
                           columns=len(missing)) as span:
                fetch = self._deal()[1] if missing else None
                got, live = _place(
                    [self.meta[j] for j in missing], placed.rows,
                    lambda i, s: fetch(missing[i], s), placed.cap,
                    self.sharding, self.devices, placed.live is None,
                )
                for j, pair in zip(missing, got):
                    placed.columns[self.columns[j]] = pair
                if live is not None:
                    placed.live = live
                nbytes = sum(
                    leaf.nbytes
                    for leaf in jax.tree_util.tree_leaves((got, live))
                )
                span.set_metadata(rows=sum(placed.rows), nbytes=nbytes)
        cols = []
        for name, c in zip(self.columns, self.template.columns):
            data, valid = placed.columns.get(name, (None, None))
            if data is None:
                data = self._zeros(c.data)
                valid = None if c.valid is None else self._zeros(c.valid)
            cols.append(Column(c.type, data, valid, c.dictionary))
        return RelBatch(cols, placed.live), nbytes

    def _zeros(self, like):
        """A block of zeros of `like`'s shape on this mesh, one for all
        the columns of that shape that nothing reads."""
        key = (like.shape, np.dtype(like.dtype).str,
               tuple(d.id for d in self.devices))
        with _zeros_lock:
            got = _ZEROS.get(key)
            if got is None:
                per = (like.shape[0] // len(self.devices),) + like.shape[1:]
                got = _ZEROS[key] = jax.make_array_from_single_device_arrays(
                    like.shape, self.sharding,
                    [jax.device_put(np.zeros(per, like.dtype), d)
                     for d in self.devices],
                )
        return got


# stand-ins for columns no program reads, by (shape, dtype, devices)
_zeros_lock = named_lock("mesh_feed._zeros_lock")
_ZEROS: Dict[tuple, object] = {}  # guarded_by: _zeros_lock


def _place(meta, rows, fetch, cap, sharding, devices, with_live) -> tuple:
    """Pad each shard's host arrays to the feed's capacity, put each on
    its own device and assemble the global arrays. The fetch (a gather
    under a predicate), the padding copy and the transfer of one column
    of one shard are one task of a small thread pool: numpy and the
    transfer release the GIL, and a feed is tens of such pieces of
    hundreds of megabytes. Returns ([(data, valid | None)] per column of
    `meta`, the live mask or None)."""
    n = len(devices)

    def piece(part: np.ndarray, dev):
        padded = np.zeros((cap,) + part.shape[1:], dtype=part.dtype)
        padded[: len(part)] = part
        return jax.device_put(padded, dev)

    def column(j: int, has_nulls: bool, s: int):
        data, valid = fetch(j, s)
        return (
            piece(np.asarray(data), devices[s]),
            piece(np.asarray(valid, dtype=bool), devices[s])
            if has_nulls else None,
        )

    def assemble(bufs):
        return jax.make_array_from_single_device_arrays(
            (n * cap,) + bufs[0].shape[1:], sharding, bufs
        )

    with concurrent.futures.ThreadPoolExecutor(PLACE_THREADS) as pool:
        pending = [
            [pool.submit(column, j, has_nulls, s) for s in range(n)]
            for j, (_t, _d, has_nulls) in enumerate(meta)
        ]
        lives = [pool.submit(piece, np.ones(r, dtype=bool), dev)
                 for r, dev in zip(rows, devices)] if with_live else []
        cols = []
        for (_typ, _dictionary, has_nulls), tasks in zip(meta, pending):
            bufs = [t.result() for t in tasks]
            # a column without nulls gets no validity lane: the programs
            # then neither read nor exchange one
            cols.append((
                assemble([d for d, _v in bufs]),
                assemble([v for _d, v in bufs]) if has_nulls else None,
            ))
        live = assemble([t.result() for t in lives]) if with_live else None
    return cols, live


def load(ex, conn, node) -> Optional[Feed]:
    """The feed of `node`'s scan on `ex`'s mesh, its columns on the
    devices or still to be placed; None where the connector cannot deal
    the table out by position (the caller takes the general path)."""
    source = conn.page_source
    if not hasattr(source, "host_shards"):
        return None
    with _place_lock:
        feed = Feed(ex, source, node)
    return None if feed.meta is None else feed


def place(feed: Feed, used) -> RelBatch:
    """`feed.place(used)` under the placement lock, with the counters."""
    from trino_tpu.runtime.metrics import METRICS

    with _place_lock:
        batch, nbytes = feed.place(used)
    if nbytes:
        METRICS.increment("mesh.feed_builds")
        METRICS.increment("mesh.bytes_fed", nbytes)
    rows = feed.placed.rows
    METRICS.increment("mesh.rows_fed", sum(rows))
    for dev, r in zip(feed.devices, rows):
        METRICS.increment(f"mesh.rows_fed.dev{dev.id}", r)
    return batch
