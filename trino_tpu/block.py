"""Columnar data model: device-resident structure-of-arrays batches.

Analogue of trino-spi's Page/Block layer (spi/Page.java:31 — a Page is
positionCount x Block[]; spi/block/Block.java:25; DictionaryBlock /
RunLengthEncodedBlock / VariableWidthBlock — SURVEY.md §2.5), re-designed
for XLA's static-shape model:

- A ``Column`` is one fixed-capacity device array plus an optional
  validity mask (NULLs) and an optional host-side string dictionary
  (VARCHAR values live on device as int32 codes — the DictionaryBlock
  idea made mandatory, which is the standard TPU answer to varlen data).
- A ``RelBatch`` is N columns sharing a capacity plus a ``live`` row mask.
  Where Trino pages have a dynamic positionCount, we keep static
  capacity (bucketed powers of two) and mask dead rows — filters only
  flip mask bits, and compaction is an explicit (cheap, vectorized)
  operation. This keeps every operator a fixed-shape XLA program.

Both are registered as pytrees so jitted kernels take them directly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T

MIN_CAPACITY = 16


_ONES_CACHE: dict = {}


def ones_mask(n: int) -> jnp.ndarray:
    """Cached all-true mask of length n. valid_mask/live_mask are called
    on the host side of every operator; a fresh jnp.ones per call is one
    device dispatch each. Inside a
    jit trace the created value is a Tracer and MUST NOT be cached (it
    would leak out of its trace); there it folds into the program as a
    constant anyway."""
    a = _ONES_CACHE.get(n)
    if a is not None:
        return a
    a = jnp.ones(n, dtype=jnp.bool_)
    if isinstance(n, int) and not isinstance(a, jax.core.Tracer):
        _ONES_CACHE[n] = a  # unlocked-ok: GIL-atomic setitem of an idempotent value
    return a


def phys_zeros(t, capacity: int):
    """Zero device array in a type's physical shape: (capacity,) for
    flat types, (capacity, 2) int64 limb pairs for decimal(>18) (the
    Int128ArrayBlock analogue — types.DataType.lanes)."""
    if t.lanes == 2:
        return jnp.zeros((capacity, 2), dtype=t.dtype)
    return jnp.zeros(capacity, dtype=t.dtype)


def null_column(t, capacity: int, dictionary=None):
    """All-NULL column of any type at a given capacity — outer-join
    padding (the null-RowBlock the reference builds in LookupOuter
    paths). Nested types get structurally-valid empty layouts, not flat
    zero arrays masquerading as lengths."""
    invalid = jnp.zeros(capacity, dtype=jnp.bool_)
    if t.is_array:
        return ArrayColumn(
            t, jnp.zeros(capacity, jnp.int32), invalid, None,
            jnp.zeros(capacity, jnp.int32), null_column(t.element, 16),
        )
    if t.is_map:
        return MapColumn(
            t, jnp.zeros(capacity, jnp.int32), invalid, None,
            jnp.zeros(capacity, jnp.int32),
            null_column(t.key, 16), null_column(t.element, 16),
        )
    if t.is_row:
        return RowColumn(
            t, jnp.zeros(capacity, jnp.int8), invalid, None,
            [null_column(ft, capacity) for _, ft in t.row_fields],
        )
    return Column(t, phys_zeros(t, capacity), invalid, dictionary)


def bucket_capacity(n: int) -> int:
    """Static-shape discipline: round row counts up to a power of two so
    the set of compiled kernel shapes stays small (the analogue of
    Trino's adaptive page sizes without dynamic shapes)."""
    c = MIN_CAPACITY
    while c < n:
        c <<= 1
    return c


class Dictionary:
    """Host-side sorted string dictionary. Device arrays hold int32 codes.

    Values are sorted, so *within one dictionary* code order == lexical
    order, making <, >=, BETWEEN on strings pure int comparisons on
    device. Cross-dictionary operations go through ``unify``.
    """

    __slots__ = ("values", "_index")

    def __init__(self, values: Sequence[str]):
        vals = sorted(set(values))
        self.values: tuple = tuple(vals)
        self._index = {v: i for i, v in enumerate(vals)}

    def __len__(self) -> int:
        return len(self.values)

    def __hash__(self):
        return hash(self.values)

    def __eq__(self, other):
        return isinstance(other, Dictionary) and self.values == other.values

    def code(self, value: str) -> int:
        """Code for value; -1 if absent (compares unequal to everything)."""
        return self._index.get(value, -1)

    def code_lower_bound(self, value: str) -> int:
        """Smallest code whose value >= `value` (for range predicates)."""
        import bisect

        return bisect.bisect_left(self.values, value)

    def encode(self, values: Sequence[str]) -> np.ndarray:
        return np.asarray([self._index[v] for v in values], dtype=np.int32)

    def decode(self, codes: np.ndarray) -> list:
        return [self.values[c] if c >= 0 else None for c in codes]

    @staticmethod
    def unify(a: "Dictionary", b: "Dictionary"):
        """Merged dictionary plus remap arrays old-code -> new-code."""
        merged = Dictionary(a.values + b.values)
        remap_a = np.asarray([merged._index[v] for v in a.values], dtype=np.int32)
        remap_b = np.asarray([merged._index[v] for v in b.values], dtype=np.int32)
        return merged, remap_a, remap_b


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Column:
    """One column: fixed-capacity device array + validity + dictionary."""

    type: T.DataType
    data: jnp.ndarray  # shape (capacity,), dtype = type.dtype
    valid: Optional[jnp.ndarray] = None  # bool (capacity,), None = all valid
    dictionary: Optional[Dictionary] = None

    # -- pytree --
    def tree_flatten(self):
        return (self.data, self.valid), (self.type, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, valid = children
        return cls(aux[0], data, valid, aux[1])

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def valid_mask(self) -> jnp.ndarray:
        if self.valid is None:
            return ones_mask(self.data.shape[0])
        return self.valid

    def with_data(self, data, valid="__same__") -> "Column":
        return Column(
            self.type,
            data,
            self.valid if isinstance(valid, str) else valid,
            self.dictionary,
        )

    def gather(self, positions: jnp.ndarray, positions_valid=None) -> "Column":
        """Vectorized position copy — the PositionsAppender analogue
        (main/operator/output/PositionsAppender*.java)."""
        pos = jnp.clip(positions, 0, self.data.shape[0] - 1)
        data = jnp.take(self.data, pos, axis=0)
        valid = None
        if self.valid is not None:
            valid = jnp.take(self.valid, pos)
        if positions_valid is not None:
            valid = positions_valid if valid is None else (valid & positions_valid)
        return Column(self.type, data, valid, self.dictionary)

    # -- host conversion (tests / client protocol) --
    @staticmethod
    def from_numpy(
        type_: T.DataType,
        values: np.ndarray,
        valid: Optional[np.ndarray] = None,
        dictionary: Optional[Dictionary] = None,
        capacity: Optional[int] = None,
    ) -> "Column":
        n = len(values)
        cap = capacity if capacity is not None else bucket_capacity(n)
        shape = (cap, 2) if type_.lanes == 2 else (cap,)
        data = np.zeros(shape, dtype=type_.dtype)
        data[:n] = values
        v = None
        if valid is not None:
            v = np.zeros(cap, dtype=bool)
            v[:n] = valid
        return Column(type_, jnp.asarray(data), None if v is None else jnp.asarray(v), dictionary)

    @staticmethod
    def from_pylist(type_: T.DataType, values: Sequence[Any], capacity=None) -> "Column":
        if type_.kind == T.TypeKind.ARRAY:
            return ArrayColumn.from_pylists(type_.element, values, capacity)
        if type_.kind == T.TypeKind.MAP:
            return MapColumn.from_pydicts(
                type_.key, type_.element, values, capacity
            )
        if type_.kind == T.TypeKind.ROW:
            return RowColumn.from_pytuples(type_, values, capacity)
        has_null = any(v is None for v in values)
        if type_.is_string:
            dictionary = Dictionary([v for v in values if v is not None])
            arr = np.asarray(
                [dictionary.code(v) if v is not None else 0 for v in values],
                dtype=np.int32,
            )
        elif type_.is_decimal:
            dictionary = None
            sf = T.decimal_scale_factor(type_)

            def scaled(v):
                from decimal import Decimal

                if isinstance(v, float):
                    return round(v * sf)
                return int(Decimal(str(v)) * sf)

            if type_.is_long_decimal:
                from trino_tpu.ops.int128 import from_python

                pairs = [
                    from_python(scaled(v)) if v is not None else (0, 0)
                    for v in values
                ]
                arr = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
            else:
                arr = np.asarray(
                    [scaled(v) if v is not None else 0 for v in values],
                    dtype=type_.dtype,
                )
        else:
            dictionary = None
            fill = 0
            arr = np.asarray(
                [v if v is not None else fill for v in values], dtype=type_.dtype
            )
        valid = None
        if has_null:
            valid = np.asarray([v is not None for v in values], dtype=bool)
        return Column.from_numpy(type_, arr, valid, dictionary, capacity)

    def to_pylist(self, count: Optional[int] = None, live: Optional[np.ndarray] = None):
        data = np.asarray(self.data)
        valid = np.asarray(self.valid) if self.valid is not None else np.ones(len(data), bool)
        if live is not None:
            keep = np.asarray(live)
            data, valid = data[keep], valid[keep]
        if count is not None:
            data, valid = data[:count], valid[:count]
        dict_values = self.dictionary.values if self.dictionary else None
        return decode_values(self.type, data, valid, dict_values)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ArrayColumn(Column):
    """ARRAY-typed column: per-row views into one flattened element
    column (spi/block/ArrayBlock.java's offsets+values layout, SoA
    form). `data` holds per-row LENGTHS — so generic vectorized code
    that only needs cardinality (the common aggregate/filter case)
    reads an ordinary int32 array — while `starts` + `flat` carry the
    element storage. gather() moves only the per-row views; the flat
    child is shared, never re-laid-out.

    Array columns flow scan -> (filter/project passthrough) -> UNNEST
    within a task, and cross exchanges via the TPG2 nested wire
    encodings (exec/serde.py — offsets + recursively-encoded flat child,
    the ArrayBlockEncoding analogue).
    """

    starts: Optional[jnp.ndarray] = None  # int32 (capacity,)
    flat: Optional[Column] = None  # flattened elements

    def tree_flatten(self):
        return (
            (self.data, self.valid, self.starts, self.flat),
            (self.type, self.dictionary),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, valid, starts, flat = children
        return cls(aux[0], data, valid, aux[1], starts, flat)

    def gather(self, positions: jnp.ndarray, positions_valid=None) -> "ArrayColumn":
        pos = jnp.clip(positions, 0, self.data.shape[0] - 1)
        lengths = jnp.take(self.data, pos)
        starts = jnp.take(self.starts, pos)
        valid = None
        if self.valid is not None:
            valid = jnp.take(self.valid, pos)
        if positions_valid is not None:
            valid = positions_valid if valid is None else (valid & positions_valid)
        return ArrayColumn(
            self.type, lengths, valid, self.dictionary, starts, self.flat
        )

    def with_data(self, data, valid="__same__") -> "ArrayColumn":
        return ArrayColumn(
            self.type,
            data,
            self.valid if isinstance(valid, str) else valid,
            self.dictionary,
            self.starts,
            self.flat,
        )

    @staticmethod
    def from_pylists(element_type: T.DataType, values, capacity=None,
                     dictionary: Optional["Dictionary"] = None) -> "ArrayColumn":
        """values: sequence of python lists (None = NULL array).
        `dictionary`: table-stable element dictionary for string
        elements (keeps plan-time binding valid across batches)."""
        n = len(values)
        cap = capacity if capacity is not None else bucket_capacity(n)
        lengths = np.zeros(cap, dtype=np.int32)
        starts = np.zeros(cap, dtype=np.int32)
        flat_vals: list = []
        valid = None
        if any(v is None for v in values):
            valid = np.zeros(cap, dtype=bool)
        pos = 0
        for i, v in enumerate(values):
            starts[i] = pos
            if v is None:
                continue
            if valid is not None:
                valid[i] = True
            lengths[i] = len(v)
            flat_vals.extend(v)
            pos += len(v)
        if dictionary is not None and element_type.is_string:
            codes = np.asarray(
                [dictionary.code(v) if v is not None else 0 for v in flat_vals],
                dtype=np.int32,
            )
            fvalid = (
                np.asarray([v is not None for v in flat_vals], dtype=bool)
                if any(v is None for v in flat_vals)
                else None
            )
            flat = Column.from_numpy(element_type, codes, fvalid, dictionary)
        else:
            flat = Column.from_pylist(element_type, flat_vals)
        return ArrayColumn(
            T.array_of(element_type),
            jnp.asarray(lengths),
            jnp.asarray(valid) if valid is not None else None,
            None,
            jnp.asarray(starts),
            flat,
        )

    def to_pylist(self, count: Optional[int] = None, live: Optional[np.ndarray] = None):
        lengths = np.asarray(self.data)
        starts = np.asarray(self.starts)
        valid = (
            np.asarray(self.valid)
            if self.valid is not None
            else np.ones(len(lengths), bool)
        )
        flat_vals = self.flat.to_pylist()
        rows = []
        for s, ln, ok in zip(starts, lengths, valid):
            rows.append(
                list(flat_vals[int(s):int(s) + int(ln)]) if ok else None
            )
        if live is not None:
            rows = [r for r, k in zip(rows, np.asarray(live)) if k]
        if count is not None:
            rows = rows[:count]
        return rows


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MapColumn(Column):
    """MAP-typed column: per-row entry views into two flattened child
    columns (spi/block/MapBlock.java's keys+values layout, SoA form).
    `data` holds per-row entry COUNTS so cardinality() reads an ordinary
    int32 array; `starts` + `flat_keys`/`flat_values` carry the entries.
    gather() moves only the per-row views; the flat children are shared."""

    starts: Optional[jnp.ndarray] = None  # int32 (capacity,)
    flat_keys: Optional[Column] = None
    flat_values: Optional[Column] = None

    def tree_flatten(self):
        return (
            (self.data, self.valid, self.starts, self.flat_keys,
             self.flat_values),
            (self.type, self.dictionary),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, valid, starts, fk, fv = children
        return cls(aux[0], data, valid, aux[1], starts, fk, fv)

    def gather(self, positions: jnp.ndarray, positions_valid=None) -> "MapColumn":
        pos = jnp.clip(positions, 0, self.data.shape[0] - 1)
        lengths = jnp.take(self.data, pos)
        starts = jnp.take(self.starts, pos)
        valid = None
        if self.valid is not None:
            valid = jnp.take(self.valid, pos)
        if positions_valid is not None:
            valid = positions_valid if valid is None else (valid & positions_valid)
        return MapColumn(
            self.type, lengths, valid, self.dictionary, starts,
            self.flat_keys, self.flat_values,
        )

    def with_data(self, data, valid="__same__") -> "MapColumn":
        return MapColumn(
            self.type,
            data,
            self.valid if isinstance(valid, str) else valid,
            self.dictionary,
            self.starts,
            self.flat_keys,
            self.flat_values,
        )

    @staticmethod
    def from_pydicts(key_type: T.DataType, value_type: T.DataType, values,
                     capacity=None) -> "MapColumn":
        """values: sequence of python dicts (None = NULL map)."""
        n = len(values)
        cap = capacity if capacity is not None else bucket_capacity(n)
        lengths = np.zeros(cap, dtype=np.int32)
        starts = np.zeros(cap, dtype=np.int32)
        fk: list = []
        fv: list = []
        valid = None
        if any(v is None for v in values):
            valid = np.zeros(cap, dtype=bool)
        pos = 0
        for i, v in enumerate(values):
            starts[i] = pos
            if v is None:
                continue
            if valid is not None:
                valid[i] = True
            lengths[i] = len(v)
            for k, x in v.items():
                fk.append(k)
                fv.append(x)
            pos += len(v)
        return MapColumn(
            T.map_of(key_type, value_type),
            jnp.asarray(lengths),
            jnp.asarray(valid) if valid is not None else None,
            None,
            jnp.asarray(starts),
            Column.from_pylist(key_type, fk),
            Column.from_pylist(value_type, fv),
        )

    def to_pylist(self, count: Optional[int] = None, live: Optional[np.ndarray] = None):
        lengths = np.asarray(self.data)
        starts = np.asarray(self.starts)
        valid = (
            np.asarray(self.valid)
            if self.valid is not None
            else np.ones(len(lengths), bool)
        )
        ks = self.flat_keys.to_pylist()
        vs = self.flat_values.to_pylist()
        rows = []
        for s, ln, ok in zip(starts, lengths, valid):
            if not ok:
                rows.append(None)
            else:
                s, ln = int(s), int(ln)
                rows.append(dict(zip(ks[s:s + ln], vs[s:s + ln])))
        if live is not None:
            rows = [r for r, k in zip(rows, np.asarray(live)) if k]
        if count is not None:
            rows = rows[:count]
        return rows


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RowColumn(Column):
    """ROW-typed column: parallel child columns, one per field
    (spi/block/RowBlock.java). `data` is a per-row presence byte (int8 1)
    so generic code sees an ordinary array; NULL rows ride `valid`."""

    children: Optional[list] = None  # list[Column], same capacity

    def tree_flatten(self):
        return (
            (self.data, self.valid, self.children),
            (self.type, self.dictionary),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, valid, kids = children
        return cls(aux[0], data, valid, aux[1], list(kids))

    def gather(self, positions: jnp.ndarray, positions_valid=None) -> "RowColumn":
        pos = jnp.clip(positions, 0, self.data.shape[0] - 1)
        data = jnp.take(self.data, pos, axis=0)
        valid = None
        if self.valid is not None:
            valid = jnp.take(self.valid, pos)
        if positions_valid is not None:
            valid = positions_valid if valid is None else (valid & positions_valid)
        return RowColumn(
            self.type, data, valid, self.dictionary,
            [c.gather(positions, positions_valid) for c in self.children],
        )

    def with_data(self, data, valid="__same__") -> "RowColumn":
        return RowColumn(
            self.type,
            data,
            self.valid if isinstance(valid, str) else valid,
            self.dictionary,
            self.children,
        )

    @staticmethod
    def from_pytuples(row_type: T.DataType, values, capacity=None) -> "RowColumn":
        """values: sequence of python tuples/lists (None = NULL row)."""
        n = len(values)
        cap = capacity if capacity is not None else bucket_capacity(n)
        presence = np.zeros(cap, dtype=np.int8)
        presence[:n] = 1
        valid = None
        if any(v is None for v in values):
            valid = np.zeros(cap, dtype=bool)
            for i, v in enumerate(values):
                valid[i] = v is not None
        kids = []
        for fi, (_, ft) in enumerate(row_type.row_fields):
            kids.append(
                Column.from_pylist(
                    ft,
                    [None if v is None else v[fi] for v in values],
                    capacity=cap,
                )
            )
        return RowColumn(
            row_type,
            jnp.asarray(presence),
            jnp.asarray(valid) if valid is not None else None,
            None,
            kids,
        )

    def to_pylist(self, count: Optional[int] = None, live: Optional[np.ndarray] = None):
        valid = (
            np.asarray(self.valid)
            if self.valid is not None
            else np.ones(self.capacity, bool)
        )
        kid_vals = [c.to_pylist() for c in self.children]
        rows = []
        for i in range(self.capacity):
            rows.append(
                tuple(kv[i] for kv in kid_vals) if valid[i] else None
            )
        if live is not None:
            rows = [r for r, k in zip(rows, np.asarray(live)) if k]
        if count is not None:
            rows = rows[:count]
        return rows


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RelBatch:
    """A batch of rows: columns share capacity; `live` masks real rows.

    The Page analogue. ``live=None`` means all `capacity` rows are live
    (the common full-batch fast path, like a Page with no mask).
    """

    columns: list  # list[Column]
    live: Optional[jnp.ndarray] = None  # bool (capacity,)

    def tree_flatten(self):
        return (self.columns, self.live), (len(self.columns),)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(list(children[0]), children[1])

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else 0

    @property
    def width(self) -> int:
        return len(self.columns)

    def live_mask(self) -> jnp.ndarray:
        if self.live is None:
            return ones_mask(self.capacity)
        return self.live

    def row_count(self) -> int:
        """Host-synced live-row count (test/protocol use; kernels use masks)."""
        if self.live is None:
            return self.capacity
        return int(jnp.sum(self.live))

    def column(self, i: int) -> Column:
        return self.columns[i]

    def with_columns(self, columns, live="__same__") -> "RelBatch":
        return RelBatch(list(columns), self.live if isinstance(live, str) else live)

    def mask(self, keep: jnp.ndarray) -> "RelBatch":
        """Filter: AND `keep` into the live mask (no data movement)."""
        live = keep if self.live is None else (self.live & keep)
        return RelBatch(self.columns, live)

    def gather(self, positions: jnp.ndarray, positions_live=None) -> "RelBatch":
        """Batch-wide position copy. Random gathers cost ~16 device
        cycles PER ELEMENT on TPU (measured r4: 16.5ms/M for int64,
        index pattern irrelevant), so the validity masks of all flat
        columns are packed into ONE int32 bitmask and gathered once
        instead of one bool gather per nullable column."""
        flat_nullable = [
            i for i, c in enumerate(self.columns)
            if c.valid is not None and not c.type.is_nested
            # consolidation paths carry mixed-capacity columns; only
            # full-capacity ones can share the packed mask + positions
            and c.data.shape[0] == self.capacity
            and c.valid.shape[0] == self.capacity
        ]
        if len(flat_nullable) < 2 or len(flat_nullable) > 32:
            cols = [c.gather(positions) for c in self.columns]
            return RelBatch(cols, positions_live)
        pos = jnp.clip(positions, 0, self.capacity - 1)
        bitpos = {i: k for k, i in enumerate(flat_nullable)}
        bits = None
        for i, k in bitpos.items():
            b = self.columns[i].valid.astype(jnp.int32) << k
            bits = b if bits is None else (bits | b)
        gbits = jnp.take(bits, pos)
        cols = []
        for i, c in enumerate(self.columns):
            k = bitpos.get(i)
            if k is not None:
                data = jnp.take(c.data, pos, axis=0)
                valid = (gbits >> k) & 1 != 0
                cols.append(Column(c.type, data, valid, c.dictionary))
            else:
                cols.append(c.gather(positions))
        return RelBatch(cols, positions_live)

    def compact(self) -> "RelBatch":
        """Front-pack live rows (stable) — Page.compact analogue
        (spi/Page.java:180). Output capacity unchanged; dead tail rows
        get live=False. Pure vectorized: stable argsort on ~live."""
        if self.live is None:
            return self
        order = jnp.argsort(~self.live, stable=True)
        n_live = jnp.sum(self.live)
        idx = jnp.arange(self.capacity)
        new_live = idx < n_live
        cols = [c.gather(order) for c in self.columns]
        return RelBatch(cols, new_live)

    def select(self, indices: Sequence[int]) -> "RelBatch":
        return RelBatch([self.columns[i] for i in indices], self.live)

    # -- host conversion --
    @staticmethod
    def from_pydict(schema, data: dict, capacity=None) -> "RelBatch":
        """schema: list[(name, DataType)] — names are positional only."""
        n = None
        cols = []
        for name, typ in schema:
            vals = data[name]
            n = len(vals) if n is None else n
            assert len(vals) == n
        cap = capacity if capacity is not None else bucket_capacity(n or 0)
        for name, typ in schema:
            cols.append(Column.from_pylist(typ, data[name], capacity=cap))
        live = None
        if (n or 0) != cap:
            lv = np.zeros(cap, dtype=bool)
            lv[: n or 0] = True
            live = jnp.asarray(lv)
        return RelBatch(cols, live)

    def to_pylists(self):
        """Rows as list of python lists, live rows only, in order. The
        whole batch moves device->host in ONE transfer (remote devices
        pay a round trip per fetch)."""
        host = jax.device_get(self)
        live = None
        if host.live is not None:
            live = np.asarray(host.live)
        cols = [c.to_pylist(live=live) for c in host.columns]
        return [list(row) for row in zip(*cols)] if cols else []


def decode_values(type_: T.DataType, data, valid, dict_values) -> list:
    """Physical values -> python values (the single host-side decode rule
    set, shared by Column.to_pylist and the wire-page protocol decode)."""
    out = []
    for x, ok in zip(data, valid):
        if not ok:
            out.append(None)
        elif type_.is_string:
            out.append(dict_values[int(x)] if dict_values else str(int(x)))
        elif type_.is_decimal:
            if type_.is_long_decimal:
                from trino_tpu.ops.int128 import to_python

                v = to_python(int(x[0]), int(x[1]))
                out.append(v / T.decimal_scale_factor(type_))
            else:
                out.append(int(x) / T.decimal_scale_factor(type_))
        elif type_.kind == T.TypeKind.BOOLEAN:
            out.append(bool(x))
        elif type_.is_floating:
            out.append(float(x))
        elif type_.kind == T.TypeKind.TIMESTAMP_TZ:
            from trino_tpu.ops.tz import format_tstz

            out.append(format_tstz(int(x)))
        else:
            out.append(int(x))
    return out


def unify_column_dicts(cols: Sequence[Column]) -> list:
    """Remap a set of same-type string columns onto one merged dictionary
    (no-op when dictionaries already agree, the table-stable fast path)."""
    dicts = [c.dictionary for c in cols]
    present = [d for d in dicts if d is not None]
    if not present or all(d == present[0] for d in dicts if d is not None):
        return list(cols)
    merged = present[0]
    for d in present[1:]:
        merged, _, _ = Dictionary.unify(merged, d)
    out = []
    for c in cols:
        if c.dictionary is None or c.dictionary == merged:
            out.append(Column(c.type, c.data, c.valid, merged))
            continue
        remap = jnp.asarray(
            [merged.code(v) for v in c.dictionary.values], dtype=jnp.int32
        )
        data = jnp.take(remap, jnp.clip(c.data, 0, max(len(c.dictionary) - 1, 0)))
        out.append(Column(c.type, data, c.valid, merged))
    return out


def _concat_valid(parts):
    if any(p.valid is not None for p in parts):
        return jnp.concatenate(
            [
                p.valid
                if p.valid is not None
                else jnp.ones(p.data.shape[0], dtype=jnp.bool_)
                for p in parts
            ]
        )
    return None


def _concat_columns(parts: list):
    """Concatenate column fragments of one schema slot, preserving
    NESTED layouts: array/map flats concatenate with starts rebased by
    the preceding flats' capacities; row children concatenate
    recursively. (A plain data-concat would splice per-row LENGTHS and
    drop the element stores.)"""
    first = parts[0]
    if isinstance(first, (ArrayColumn, MapColumn)):
        data = jnp.concatenate([p.data for p in parts])
        valid = _concat_valid(parts)
        starts = []
        off = 0
        flats1 = []
        flats2 = []
        for p in parts:
            starts.append(p.starts + off)
            if isinstance(p, ArrayColumn):
                off += p.flat.capacity
                flats1.append(p.flat)
            else:
                off += p.flat_keys.capacity
                flats1.append(p.flat_keys)
                flats2.append(p.flat_values)
        starts = jnp.concatenate(starts)
        if isinstance(first, ArrayColumn):
            return ArrayColumn(
                first.type, data, valid, None, starts,
                _concat_columns(flats1),
            )
        return MapColumn(
            first.type, data, valid, None, starts,
            _concat_columns(flats1), _concat_columns(flats2),
        )
    if isinstance(first, RowColumn):
        data = jnp.concatenate([p.data for p in parts])
        valid = _concat_valid(parts)
        kids = [
            _concat_columns([p.children[i] for p in parts])
            for i in range(len(first.children))
        ]
        return RowColumn(first.type, data, valid, None, kids)
    parts = unify_column_dicts(parts)
    data = jnp.concatenate([p.data for p in parts])
    return Column(parts[0].type, data, _concat_valid(parts), parts[0].dictionary)


def concat_batches(batches: Sequence["RelBatch"]) -> "RelBatch":
    """Concatenate batches (PagesIndex-style consolidation —
    main/operator/PagesIndex.java:80 addPage). Output capacity is the sum
    of input capacities (already powers of two stay bucketed enough)."""
    batches = list(batches)
    if len(batches) == 1:
        return batches[0]
    width = batches[0].width
    cols = [
        _concat_columns([b.columns[i] for b in batches])
        for i in range(width)
    ]
    live = jnp.concatenate([b.live_mask() for b in batches])
    return RelBatch(cols, live)


class RuntimeDictionary(Dictionary):
    """Plan-time placeholder for a string column whose dictionary is
    created at EXECUTION time (listagg output: the aggregate builds new
    strings). Pure column references pass the runtime dictionary
    through (operators.make_filter_project_fn); any plan-time-bound
    string operation cannot know the values and must fail loudly at
    bind time rather than treat the column as all-NULL."""

    def __init__(self):
        super().__init__([])
