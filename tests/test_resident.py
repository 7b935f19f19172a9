"""Resident state tier (trino_tpu/resident/): generation clock, pin
manager LRU/budget/pool accounting, the device probe table with delta
maintenance + compaction, and the serving fast lane end-to-end against
the ordinary execute path as oracle."""

import numpy as np
import pytest

from trino_tpu.resident.manager import (
    GENERATIONS,
    RESIDENT,
    ResidentStateManager,
    TableGenerations,
    table_key,
)
from trino_tpu.resident.table import PROBE_OUT_CAP, ResidentTable


# -- TableGenerations ---------------------------------------------------


class TestGenerations:
    def test_bump_changes_snapshot(self):
        g = TableGenerations()
        k = table_key("c", "s", "t")
        s0 = g.snapshot([k])
        g.bump(k)
        assert g.snapshot([k]) != s0
        # an unrelated table's clock is untouched
        other = table_key("c", "s", "u")
        assert g.get(other) == (0, 0)

    def test_epoch_bump_invalidates_every_snapshot(self):
        g = TableGenerations()
        a, b = table_key("c", "s", "a"), table_key("c", "s", "b")
        sa, sb = g.snapshot([a]), g.snapshot([b])
        g.bump_all()
        assert g.snapshot([a]) != sa and g.snapshot([b]) != sb

    def test_snapshot_is_order_insensitive(self):
        g = TableGenerations()
        a, b = table_key("c", "s", "a"), table_key("c", "s", "b")
        assert g.snapshot([a, b]) == g.snapshot([b, a])


# -- ResidentStateManager ----------------------------------------------


class TestManager:
    def test_pin_lookup_evict(self):
        m = ResidentStateManager(budget_bytes=1 << 20)
        t = table_key("c", "s", "t")
        assert m.pin(("k1",), "payload", 100, [t], index_key=("i1",))
        assert m.lookup(("k1",)) == "payload"
        assert m.find(("i1",)) == (("k1",), "payload")
        assert m.evict(("k1",))
        assert m.lookup(("k1",)) is None
        assert m.find(("i1",)) is None
        assert m.stats()["hits"] == 1 and m.stats()["misses"] == 1

    def test_lru_eviction_under_budget(self):
        m = ResidentStateManager(budget_bytes=250)
        t = table_key("c", "s", "t")
        m.pin(("a",), 1, 100, [t])
        m.pin(("b",), 2, 100, [t])
        m.lookup(("a",))  # touch: "b" becomes LRU
        m.pin(("c",), 3, 100, [t])
        assert m.lookup(("b",)) is None
        assert m.lookup(("a",)) == 1 and m.lookup(("c",)) == 3
        assert m.pinned_bytes <= 250

    def test_oversized_pin_refused_not_raised(self):
        m = ResidentStateManager(budget_bytes=50)
        assert not m.pin(("big",), 1, 100, [table_key("c", "s", "t")])
        assert len(m) == 0 and m.stats()["pin_rejects"] == 1

    def test_invalidate_table_is_table_granular(self):
        m = ResidentStateManager(budget_bytes=1 << 20)
        t1, t2 = table_key("c", "s", "t1"), table_key("c", "s", "t2")
        m.pin(("a",), 1, 10, [t1])
        m.pin(("b",), 2, 10, [t2])
        m.pin(("ab",), 3, 10, [t1, t2])  # multi-table entry
        assert m.invalidate_table(t1) == 2
        assert m.lookup(("b",)) == 2
        assert m.lookup(("a",)) is None and m.lookup(("ab",)) is None

    def test_rekey_keeps_entry_warm_and_index_current(self):
        m = ResidentStateManager(budget_bytes=1 << 20)
        t = table_key("c", "s", "t")
        m.pin(("k", 1), "p", 10, [t], index_key=("i",))
        assert m.rekey(("k", 1), ("k", 2))
        assert m.lookup(("k", 1)) is None
        assert m.lookup(("k", 2)) == "p"
        assert m.find(("i",)) == (("k", 2), "p")

    def test_set_bytes_recharges(self):
        m = ResidentStateManager(budget_bytes=1 << 20)
        m.pin(("k",), "p", 100, [table_key("c", "s", "t")])
        m.set_bytes(("k",), 300)
        assert m.pinned_bytes == 300
        m.set_bytes(("k",), 50)
        assert m.pinned_bytes == 50

    def test_pool_charge_and_revocation(self):
        from trino_tpu.runtime.memory import MemoryPool

        pool = MemoryPool(max_bytes=10_000)
        m = ResidentStateManager(budget_bytes=1 << 20)
        m.pin(("k",), "p", 4_000, [table_key("c", "s", "t")])
        m.attach_pool(pool)
        assert pool.reserved_bytes >= 4_000
        # a query wanting more than what's free revokes the pins BEFORE
        # the pool fails the reservation
        pool.reserve(8_000, query_id="q1")
        assert len(m) == 0 and m.stats()["revocations"] == 1
        pool.free(8_000, query_id="q1")
        m.detach_pool()
        assert pool.reserved_bytes == 0


# -- ResidentTable ------------------------------------------------------


def _kv_table(n=40, delta_max=8, string_key=False):
    keys = [f"k{i}" for i in range(n)] if string_key else list(range(n))
    rows = [[i * 10] for i in range(n)]
    return ResidentTable(
        "k", ["v"], ["bigint"], keys, rows,
        string_key=string_key, delta_max_rows=delta_max,
    )


class TestResidentTable:
    def test_probe_int_key(self):
        t = _kv_table()
        assert t.probe(7) == [[70]]
        assert t.probe(39) == [[390]]
        assert t.probe(12345) == []

    def test_probe_string_key(self):
        t = _kv_table(string_key=True)
        assert t.probe("k3") == [[30]]
        # never-encoded key short-circuits on the host dictionary
        assert t.probe("nope") == []

    def test_duplicate_keys_return_all_rows_fanout_bails(self):
        keys = [1] * 3 + [2] * (PROBE_OUT_CAP + 1)
        rows = [[i] for i in range(len(keys))]
        t = ResidentTable("k", ["v"], ["bigint"], keys, rows,
                          string_key=False)
        assert t.probe(1) == [[0], [1], [2]]
        # past the probe rung: None = caller falls to the cold path
        assert t.probe(2) is None

    def test_delta_append_then_compact(self):
        t = _kv_table(n=40, delta_max=8)
        cap0 = t.base_cap
        assert t.delta_room(2)
        assert t.append_delta([100, 101], [[1000], [1010]])
        # probes see base + delta before compaction
        assert t.probe(100) == [[1000]]
        assert t.probe(7) == [[70]]
        assert t.append_delta([102, 103], [[1020], [1030]])
        assert t.wants_compaction()
        t.compact()
        assert t.delta_count == 0
        for k, v in [(100, 1000), (103, 1030), (7, 70)]:
            assert t.probe(k) == [[v]]
        # 44 live rows still fit the original rung: no rekey needed
        assert t.base_cap == cap0 and t.base_live == 44

    def test_delta_overflow_refused(self):
        t = _kv_table(n=4, delta_max=2)
        assert not t.append_delta(list(range(100, 103)),
                                  [[0], [0], [0]])
        assert t.probe(1) == [[10]]  # table unharmed

    def test_device_bytes_tracks_delta(self):
        t = _kv_table()
        b0 = t.device_bytes
        t.append_delta([500], [[5000]])
        assert t.device_bytes > b0


# -- fast lane end-to-end ----------------------------------------------


@pytest.fixture()
def kv_runner():
    from trino_tpu import types as Ty
    from trino_tpu.connectors.memory import create_memory_connector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.engine import LocalQueryRunner, Session

    mem = create_memory_connector()
    r = LocalQueryRunner(Session(
        catalog="memory", schema="s",
        resident_tables="s.kv", resident_delta_max_rows=32,
    ))
    r.register_catalog("memory", mem)
    n = 100
    rng = np.random.default_rng(11)
    mem.load_table(
        "s", "kv",
        [ColumnMetadata("k", Ty.BIGINT), ColumnMetadata("v", Ty.BIGINT)],
        [np.arange(n, dtype=np.int64),
         rng.integers(0, 1 << 20, n).astype(np.int64)],
    )
    RESIDENT.evict_all()
    yield r
    RESIDENT.evict_all()


def _fast(r, k):
    from trino_tpu.resident.fastlane import try_resident_lookup

    res = try_resident_lookup(r, f"select v from kv where k = {k}")
    return None if res is None else res.rows


class TestFastLane:
    def test_build_then_hit(self, kv_runner):
        r = kv_runner
        want = r.execute("select v from kv where k = 7").rows
        assert _fast(r, 7) == want  # cold build
        pins0 = RESIDENT.stats()["pins"]
        assert _fast(r, 7) == want  # pinned hit
        assert _fast(r, 42) == r.execute(
            "select v from kv where k = 42"
        ).rows
        assert RESIDENT.stats()["pins"] == pins0  # no rebuild

    def test_warm_probes_lower_nothing(self, kv_runner):
        """Once the table is pinned and one probe has run, probes of
        other keys mint no XLA lowering and no pin, before and after a
        background compaction of the delta."""
        from trino_tpu.resident.fastlane import drain_compactions
        from trino_tpu.runtime.metrics import METRICS

        r = kv_runner
        assert _fast(r, 7)  # cold build
        assert _fast(r, 8)  # one warm probe
        pins0, hits0 = RESIDENT.stats()["pins"], RESIDENT.stats()["hits"]
        compiles0 = METRICS.counter("xla_compiles")
        for k in range(20, 40):
            assert _fast(r, k) is not None, k
        assert METRICS.counter("xla_compiles") == compiles0
        assert RESIDENT.stats()["pins"] == pins0
        assert RESIDENT.stats()["hits"] == hits0 + 20
        # delta_max_rows=32: compaction from half of it
        for i in range(600, 620):
            r.execute(f"insert into kv values ({i}, {i})")
        drain_compactions()
        assert RESIDENT.stats()["compactions"] >= 1
        assert _fast(r, 619) == [[619]]  # first probe after compaction
        compiles0 = METRICS.counter("xla_compiles")
        for k in range(40, 60):
            assert _fast(r, k) is not None, k
        assert METRICS.counter("xla_compiles") == compiles0
        assert RESIDENT.stats()["pins"] == pins0

    def test_unconfigured_table_declines(self, kv_runner):
        r = kv_runner
        r.session.resident_tables = "s.other"
        assert _fast(r, 7) is None

    def test_non_point_lookup_declines(self, kv_runner):
        from trino_tpu.resident.fastlane import try_resident_lookup

        assert try_resident_lookup(
            kv_runner, "select sum(v) from kv"
        ) is None

    def test_update_invalidates_and_rebuilds(self, kv_runner):
        r = kv_runner
        assert _fast(r, 7)  # pin
        r.execute("update kv set v = -5 where k = 7")
        assert _fast(r, 7) == [[-5]]
        assert _fast(r, 7) == r.execute(
            "select v from kv where k = 7"
        ).rows

    def test_insert_rides_delta_without_repin(self, kv_runner):
        from trino_tpu.resident.fastlane import drain_compactions

        r = kv_runner
        assert _fast(r, 7)  # pin
        pins0 = RESIDENT.stats()["pins"]
        r.execute("insert into kv values (500, 5000)")
        assert _fast(r, 500) == [[5000]]
        assert _fast(r, 7) == r.execute(
            "select v from kv where k = 7"
        ).rows
        # the append re-keyed the live pin; it did not rebuild
        assert RESIDENT.stats()["pins"] == pins0
        # push past half the delta budget -> background compaction
        for i in range(501, 501 + 20):
            r.execute(f"insert into kv values ({i}, {i * 10})")
        drain_compactions()
        assert _fast(r, 510) == [[5100]]
        assert _fast(r, 7) == r.execute(
            "select v from kv where k = 7"
        ).rows

    def test_zero_budget_degrades_to_cold_path(self, kv_runner):
        r = kv_runner
        r.session.resident_pin_budget_mb = 0
        RESIDENT.evict_all()
        want = r.execute("select v from kv where k = 3").rows
        assert _fast(r, 3) == want  # served, transient build
        assert len(RESIDENT) == 0  # nothing stayed pinned
        # restore the default so later tests see a sane budget
        RESIDENT.configure(64 << 20)
