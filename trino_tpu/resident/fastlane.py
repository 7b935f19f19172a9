"""Serving fast lane over pinned resident tables.

`try_resident_lookup` sits in front of the normal execute path on the
coordinator server: it reuses the micro-batcher's STRICT point-lookup
classifier, and when the probed table is named in the
`resident_tables` session property it serves the lookup from a pinned
`ResidentTable` — a device probe, zero rebuild, zero plan-cache or
scheduler work. A miss (first touch, or a generation bump from DML)
builds the table with ONE oracle scan through the ordinary execute
path, pins it under the current generation snapshot, and serves from
the pin thereafter. Anything surprising — unclassifiable statement,
unconfigured table, nested-typed select list, pin-budget overflow,
per-key fanout past the probe rung — returns None so the caller falls
through to the cold path; the fast lane degrades, it never fails a
query.

Write integration (`table_written`, called from the engine's
invalidation path): INSERTs whose rows were captured by a `DeltaTap`
append to the pinned table's delta side and RE-KEY the entry under the
table's new generation (the table stays warm); UPDATE/DELETE/MERGE/DDL
evict. When the delta crosses half its budget a background compaction
(the warmup-thread idiom: daemon worker, never on the query path)
folds it into the base at a ladder rung.
"""

from __future__ import annotations

import threading
from trino_tpu.analysis.witness import named_condition, named_lock, named_rlock
from typing import List, Optional, Tuple

from trino_tpu.resident.manager import GENERATIONS, RESIDENT, table_key
from trino_tpu.resident.table import ResidentTable

_lock = named_lock("fastlane._lock")
_compaction_pool = None
_pending_compactions: List = []


def _resolve_table(table_sql: str, session) -> Tuple[str, str, str]:
    parts = table_sql.split(".")
    cat, schema = session.catalog, session.schema
    if len(parts) == 2:
        schema = parts[0]
    elif len(parts) == 3:
        cat, schema = parts[0], parts[1]
    return table_key(cat, schema, parts[-1])


def _configured(tkey: Tuple[str, str, str], session) -> bool:
    names = [
        t.strip().lower()
        for t in str(session.resident_tables or "").split(",")
        if t.strip()
    ]
    cat, schema, table = tkey
    return (
        table in names
        or f"{schema}.{table}" in names
        or f"{cat}.{schema}.{table}" in names
    )


def _full_key(tkey, key_col, select_sql, dkind, sig, rung, gen) -> Tuple:
    # convention: the generation snapshot is always the LAST component
    return ("fastlane", tkey, key_col, select_sql, dkind, sig, rung, gen)


def _index_key(tkey, key_col, select_sql, dkind) -> Tuple:
    return ("fastlane", tkey, key_col, select_sql, dkind)


def try_resident_lookup(runner, sql: str, identity=None, prepared=None,
                        query_span=None):
    """MaterializedResult from a pinned table, or None = cold path."""
    from trino_tpu.runtime.metrics import METRICS

    session = getattr(runner, "session", None)
    if session is None or not session.resident_tables:
        return None
    from trino_tpu.serving.batcher import classify

    look = classify(sql, runner=runner, prepared=prepared)
    if look is None:
        return None
    tkey = _resolve_table(look.table_sql, session)
    if not _configured(tkey, session):
        return None
    dkind = look.group_key[3]
    ikey = _index_key(tkey, look.key_col, look.select_sql, dkind)
    gen = GENERATIONS.snapshot([tkey])

    # access control re-checks on every lookup, pinned or not — a pin
    # must never become a bypass
    ac = getattr(runner, "access_control", None)
    if ac is not None:
        from trino_tpu.security import Identity

        ident = identity or Identity(session.user)
        cols = [look.key_col] + [
            c.strip() for c in look.select_sql.split(",")
        ]
        ac.check_can_select(ident, *tkey, cols)

    found = RESIDENT.find(ikey)
    if found is not None:
        key, table = found
        if key[-1] == gen and isinstance(table, ResidentTable):
            rows = table.probe(look.value)
            if rows is None:
                return None  # fanout past the probe rung: cold path
            RESIDENT.lookup(key)  # counts the hit, touches LRU
            if query_span is not None:
                query_span.event("resident_hit", table=".".join(tkey))
            from trino_tpu.engine import MaterializedResult

            return MaterializedResult(
                rows, list(table.names), list(table.types)
            )
        # stale generation that invalidation missed (epoch bump):
        # reclaim the pin and rebuild below
        RESIDENT.evict(key)
    RESIDENT.note_miss()

    # -- cold build: one oracle scan through the ordinary path --------
    try:
        return _build_and_probe(
            runner, session, look, tkey, ikey, gen, dkind, identity,
            query_span,
        )
    except Exception:
        METRICS.increment("resident.skips")
        return None


def _build_and_probe(runner, session, look, tkey, ikey, gen, dkind,
                     identity, query_span):
    from trino_tpu.runtime.metrics import METRICS

    # principled eligibility (the census-satellite rule): nested-typed
    # select columns have no scalar device layout to pin against —
    # counted skip, not a silent one
    if not _eligible_columns(runner, tkey, look, METRICS):
        return None
    oracle_sql = (
        f"SELECT {look.key_col}, {look.select_sql} FROM {look.table_sql}"
    )
    kwargs = {"identity": identity} if identity is not None else {}
    result = runner.execute(oracle_sql, **kwargs)
    names = list(result.column_names[1:])
    types = list(result.column_types[1:])
    table = ResidentTable(
        look.key_col, names, types,
        [r[0] for r in result.rows],
        [r[1:] for r in result.rows],
        string_key=(dkind == "s"),
        delta_max_rows=int(session.resident_delta_max_rows),
    )
    RESIDENT.configure(int(session.resident_pin_budget_mb) << 20)
    key = _full_key(
        tkey, look.key_col, look.select_sql, dkind,
        table.dtype_sig, table.base_cap, gen,
    )
    pinned = RESIDENT.pin(
        key, table, table.device_bytes, [tkey], index_key=ikey
    )
    if not pinned:
        # budget overflow: serve this one lookup from the transient
        # build, but nothing stays pinned (graceful degradation)
        METRICS.increment("resident.skips")
    rows = table.probe(look.value)
    if rows is None:
        return None
    if query_span is not None:
        query_span.event(
            "resident_build", table=".".join(tkey), pinned=pinned
        )
    from trino_tpu.engine import MaterializedResult

    return MaterializedResult(rows, names, types)


def _eligible_columns(runner, tkey, look, METRICS) -> bool:
    # same predicate the census uses for its [nested] classes
    # (sql/validate.nested_column_types) — classification stays
    # principled and in one place
    from trino_tpu.sql.validate import nested_column_types

    try:
        catalogs = getattr(runner, "catalogs", None)
        if catalogs is None:
            return True
        conn = catalogs.get(tkey[0])
        handle = conn.metadata.get_table_handle(tkey[1], tkey[2])
        if handle is None:
            return True  # let the oracle query raise the real error
        meta = conn.metadata.get_table_metadata(handle)
        wanted = {look.key_col.lower()} | {
            c.strip().lower() for c in look.select_sql.split(",")
        }
        if nested_column_types([
            c.type for c in meta.columns if c.name.lower() in wanted
        ]):
            METRICS.increment("resident.skips_nested")
            return False
        return True
    except Exception:
        return True


# -- write-path integration -------------------------------------------


class DeltaTap:
    """Captures the host rows of one INSERT as they stream into the
    connector sink (the engine tees its page sink through this)."""

    def __init__(self, names: List[str]):
        self.names = [n.lower() for n in names]
        self.rows: List[list] = []

    def add_batch(self, batch) -> None:
        self.rows.extend(batch.to_pylists())


class TeeSink:
    """Connector-sink wrapper feeding a DeltaTap (append/finish shim
    compatible with both plain page sinks and ScaledWriterSink)."""

    def __init__(self, inner, tap: DeltaTap):
        self._inner = inner
        self._tap = tap

    def append(self, batch) -> None:
        try:
            self._tap.add_batch(batch)
        except Exception:
            self._tap.rows = None  # poisoned tap: eviction, not bad data
        self._inner.append(batch)

    def finish(self) -> int:
        return self._inner.finish()


def delta_tap(catalog: str, schema: str, table: str,
              column_names) -> Optional[DeltaTap]:
    """A tap when any pinned entry could absorb this table's insert;
    None keeps the write path untouched."""
    tkey = table_key(catalog, schema, table)
    if not RESIDENT.entries_for(tkey):
        return None
    return DeltaTap(list(column_names))


def table_written(catalog: str, schema: str, table: str,
                  appended: bool = False,
                  tap: Optional[DeltaTap] = None) -> None:
    """Engine notification AFTER a write and AFTER the generation bump:
    appends with captured rows ride the delta; everything else
    evicts."""
    tkey = table_key(catalog, schema, table)
    keys = RESIDENT.entries_for(tkey)
    if not keys:
        return
    new_gen = GENERATIONS.snapshot([tkey])
    for key in keys:
        entry_payload = RESIDENT.peek(key)
        if (
            appended
            and tap is not None
            and tap.rows is not None
            and isinstance(entry_payload, ResidentTable)
            and key[0] == "fastlane"
        ):
            t = entry_payload
            rows = _project(tap, t.key_col, t.names)
            if rows is not None and t.delta_room(len(rows)):
                if t.append_delta([r[0] for r in rows],
                                  [r[1:] for r in rows]):
                    new_key = key[:-1] + (new_gen,)
                    RESIDENT.rekey(key, new_key)
                    RESIDENT.set_bytes(new_key, t.device_bytes)
                    if t.wants_compaction():
                        _schedule_compaction(new_key, t)
                    continue
        RESIDENT.evict(key)


def _project(tap: DeltaTap, key_col: str,
             value_names: List[str]) -> Optional[List[list]]:
    """Tap rows (full table schema) -> [key, values...] rows in the
    resident table's column order; None when a column is missing."""
    try:
        pos = {n: i for i, n in enumerate(tap.names)}
        idxs = [pos[key_col.lower()]] + [
            pos[n.lower()] for n in value_names
        ]
    except KeyError:
        return None
    return [[row[i] for i in idxs] for row in tap.rows]


def table_dropped(catalog: str, schema: str, table: str) -> None:
    RESIDENT.drop_table(table_key(catalog, schema, table))


# -- background compaction (the warmup-thread idiom) -------------------


def _schedule_compaction(key: Tuple, table: ResidentTable) -> None:
    global _compaction_pool
    with _lock:
        if _compaction_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            from trino_tpu.analysis.threadreg import THREADS

            # Executor workers are non-daemon on 3.9+; the pool is a
            # process-lifetime singleton, so sanction its one worker
            # with the registry rather than tearing it down per-query.
            _compaction_pool = ThreadPoolExecutor(
                max_workers=1,
                thread_name_prefix="trino-tpu-resident-compact",
                initializer=lambda: THREADS.adopt_current(
                    owner="ResidentManager", long_lived=True),
            )
        fut = _compaction_pool.submit(_compact_one, key, table)
        _pending_compactions[:] = [
            f for f in _pending_compactions if not f.done()
        ]
        _pending_compactions.append(fut)


def _compact_one(key: Tuple, table: ResidentTable) -> None:
    try:
        old_rung = table.base_cap
        table.compact()
        RESIDENT.note_compaction()
        # fold the new rung into the key so the key stays honest
        if key[0] == "fastlane" and table.base_cap != old_rung:
            new_key = key[:6] + (table.base_cap,) + key[7:]
            RESIDENT.rekey(key, new_key)
            key = new_key
        RESIDENT.set_bytes(key, table.device_bytes)
    except Exception:
        # a failed compaction leaves base+delta intact and correct;
        # drop the pin only if the table is now inconsistent — it is
        # not, so just leave it and let DML churn evict eventually
        pass


def drain_compactions(timeout_s: float = 30.0) -> None:
    """Test/bench hook: wait for scheduled compactions to settle."""
    import concurrent.futures as cf

    with _lock:
        pending = list(_pending_compactions)
    if pending:
        cf.wait(pending, timeout=timeout_s)
    with _lock:
        _pending_compactions[:] = [
            f for f in _pending_compactions if not f.done()
        ]
