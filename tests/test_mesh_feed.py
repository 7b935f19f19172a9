"""The mesh plane's resident feeds and what PR 28 put around them: each
device holds its shard and keeps it, chunking decided by the sizes, the
sort-dealt hash exchange with send blocks under the sender's capacity and
its overflow ladder, capacities learned across runs, the bounded-domain
group reduce, the run-time exchange census, the fallback log, and the
`tpusql.mesh.*` events of a profiler trace."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.connectors.memory import create_memory_connector
from trino_tpu.connectors.spi import ColumnMetadata
from trino_tpu.engine import LocalQueryRunner, Session
from trino_tpu.parallel import mesh_chunk, mesh_feed, mesh_plan
from trino_tpu.runtime import DistributedQueryRunner
from trino_tpu.runtime.metrics import METRICS

N = 60_000
SUMS = "select g, count(*), sum(v) from t group by g"
JOIN = ("select t.g, count(*), sum(d.w) from t, d where t.k = d.k "
        "group by t.g")


def make_tables(mem, n=N, skew=False):
    rng = np.random.default_rng(28)
    k = np.zeros(n, np.int64) if skew else rng.integers(0, 5000, n)
    mem.load_table(
        "s", "t",
        [ColumnMetadata("k", T.BIGINT), ColumnMetadata("g", T.BIGINT),
         ColumnMetadata("v", T.BIGINT)],
        [k.astype(np.int64), rng.integers(0, 7, n).astype(np.int64),
         rng.integers(0, 1000, n).astype(np.int64)],
    )
    mem.load_table(
        "s", "d", [ColumnMetadata("k", T.BIGINT), ColumnMetadata("w", T.BIGINT)],
        [np.arange(5000, dtype=np.int64), np.arange(5000, dtype=np.int64) % 11],
    )


def runners(skew=False, rows=N, **session):
    mem = create_memory_connector()
    make_tables(mem, n=rows, skew=skew)
    n = len(jax.devices())
    # every join a partitioned one: both sides cross an all_to_all
    dist = DistributedQueryRunner(
        Session(catalog="memory", schema="s", broadcast_join_threshold=100,
                **session),
        n_workers=n, hash_partitions=n,
    )
    dist.register_catalog("memory", mem)
    local = LocalQueryRunner(Session(catalog="memory", schema="s"))
    local.register_catalog("memory", mem)
    return dist, local, mem


def on_mesh(dist, sql):
    result = dist.execute(sql)
    assert result.data_plane == "mesh" and dist.last_mesh_fallback is None
    return sorted(map(tuple, result.rows))


def counters(*names):
    return {k: METRICS.counter(k) for k in names}


# -- feeds ------------------------------------------------------------------------


def test_every_device_holds_its_shard_and_keeps_it():
    dist, local, mem = runners()
    want = sorted(map(tuple, local.execute(SUMS).rows))
    c0 = counters("mesh.feed_builds", "mesh.bytes_fed", "mesh.rows_fed",
                  "rows_scanned")
    assert on_mesh(dist, SUMS) == want
    c1 = counters(*c0)
    assert c1["mesh.feed_builds"] - c0["mesh.feed_builds"] == 1
    assert c1["mesh.rows_fed"] - c0["mesh.rows_fed"] == N
    assert c1["rows_scanned"] == c0["rows_scanned"]     # no scan operator ran
    table = mem.store.tables[("s", "t")]
    (placed,) = table.mesh_feeds.values()
    devices = jax.devices()
    rows = placed.rows
    assert sum(rows) == N and max(rows) - min(rows) <= len(devices)
    assert placed.cap == mesh_feed.shard_capacity(max(rows))
    # the statement reads g and v: k, which the scan also lists, stays
    # on the host
    assert sorted(placed.columns) == ["g", "v"]
    for data, valid in placed.columns.values():
        assert valid is None                     # no nulls, no validity lane
        held = {s.device.id: s.data.shape for s in data.addressable_shards}
        assert held == {d.id: (placed.cap,) for d in devices}
    live = np.asarray(placed.live).reshape(len(devices), placed.cap)
    assert [int(x.sum()) for x in live] == rows
    assert c1["mesh.bytes_fed"] - c0["mesh.bytes_fed"] == (
        len(devices) * placed.cap * (8 + 8 + 1))
    # a second statement over the scan reads what is on the devices
    assert on_mesh(dist, SUMS) == want
    c2 = counters(*c0)
    assert c2["mesh.feed_builds"] == c1["mesh.feed_builds"]
    assert c2["mesh.bytes_fed"] == c1["mesh.bytes_fed"]
    assert c2["mesh.rows_fed"] - c1["mesh.rows_fed"] == N
    per_device = [METRICS.counter(f"mesh.rows_fed.dev{d.id}") for d in devices]
    assert all(per_device) and sum(per_device) >= 2 * N
    # one that reads k as well places k, and nothing twice
    on_mesh(dist, JOIN)
    assert sorted(placed.columns) == ["g", "k"] + (["v"] if "v" in placed.columns else [])
    c3 = counters(*c0)
    assert c3["mesh.bytes_fed"] - c2["mesh.bytes_fed"] >= len(devices) * placed.cap * 8


def test_a_write_drops_the_placed_feed():
    dist, local, mem = runners()
    on_mesh(dist, SUMS)
    table = mem.store.tables[("s", "t")]
    (old_key,) = table.mesh_feeds
    dist.execute("insert into t values (1, 99, 5)")
    got = on_mesh(dist, SUMS)
    assert got == sorted(map(tuple, local.execute(SUMS).rows))
    assert (99, 1, 5) in got
    assert list(table.mesh_feeds) != [old_key] and len(table.mesh_feeds) == 1


def test_a_pushed_down_predicate_keys_its_own_feed():
    dist, local, mem = runners()
    sql = "select g, count(*) from t where v < 100 group by g"
    assert on_mesh(dist, sql) == sorted(map(tuple, local.execute(sql).rows))
    (placed,) = mem.store.tables[("s", "t")].mesh_feeds.values()
    assert sum(placed.rows) < N // 5
    on_mesh(dist, SUMS)
    assert len(mem.store.tables[("s", "t")].mesh_feeds) == 2


def test_nulls_keep_their_validity_lane():
    dist, local, mem = runners()
    dist.execute("insert into t values (null, 3, null)")
    sql = "select g, count(k), sum(v) from t group by g"
    assert on_mesh(dist, sql) == sorted(map(tuple, local.execute(sql).rows))
    (placed,) = mem.store.tables[("s", "t")].mesh_feeds.values()
    assert placed.columns["k"][1] is not None and placed.columns["v"][1] is not None


def test_a_bucketed_table_takes_the_general_path():
    mem = create_memory_connector()
    mem.load_table("s", "b", [ColumnMetadata("k", T.BIGINT)],
                   [np.arange(100, dtype=np.int64)], bucketed_by=["k"])
    from trino_tpu.connectors.spi import TableHandle

    handle = TableHandle("memory", "s", "b")
    assert mem.page_source.host_shards(handle, ["k"], 4) is None
    assert mem.page_source.mesh_feeds(handle, ["k"])[2] is None


# -- chunking from the sizes ----------------------------------------------------------


def test_the_sizes_decide_whether_a_scan_streams(monkeypatch):
    assert mesh_feed.chunk_rows_for(Session(), mesh_feed.AUTO_CHUNK_ROWS) == 0
    assert mesh_feed.chunk_rows_for(
        Session(), mesh_feed.AUTO_CHUNK_ROWS + 1) == mesh_feed.AUTO_CHUNK_ROWS
    assert mesh_feed.chunk_rows_for(Session(mesh_chunk_rows=512), 10) == 512
    assert mesh_feed.shard_capacity(1000) == 1024
    big = 5 * mesh_feed.AUTO_CHUNK_ROWS // 2
    assert mesh_feed.shard_capacity(big) == 3 * mesh_feed.AUTO_CHUNK_ROWS

    monkeypatch.setattr(mesh_feed, "AUTO_CHUNK_ROWS", 2048)
    dist, local, _mem = runners()          # no session property
    for sql in (SUMS, JOIN):
        steps = METRICS.counter("mesh.chunk_steps")
        assert on_mesh(dist, sql) == sorted(map(tuple, local.execute(sql).rows))
        info = mesh_chunk.last_run_info()
        shard = -(-N // len(jax.devices()))
        assert info["chunked"] and info["chunk_cap"] == 2048
        assert info["chunks"] == -(-shard // 2048)
        assert METRICS.counter("mesh.chunk_steps") - steps >= info["chunks"]


# -- the hash exchange ----------------------------------------------------------------


def test_rows_are_dealt_into_blocks_in_scan_order():
    pid = jnp.asarray([2, 0, -1, 2, 1, 0, 2, -1], dtype=jnp.int32)
    data = jnp.arange(8, dtype=jnp.int64) * 10
    pair = jnp.stack([jnp.arange(8), -jnp.arange(8)], axis=1).astype(jnp.int64)
    flag = jnp.asarray([True, False] * 4)
    (d, p, f), live, needed = mesh_plan._scatter_to_blocks([data, pair, flag], pid, 3, 2)
    assert int(needed) == 3
    assert np.asarray(live).tolist() == [[True, True], [True, False], [True, True]]
    assert np.asarray(d)[0].tolist() == [10, 50] and int(d[1, 0]) == 40
    assert np.asarray(d)[2].tolist() == [0, 30]          # the third row for 2 is cut
    assert np.asarray(p)[0].tolist() == [[1, -1], [5, -5]]
    assert f.dtype == jnp.bool_ and np.asarray(f)[2].tolist() == [True, False]
    # more lanes than one sort carries: the rest follow the sorted row ids
    many = [data + i for i in range(mesh_plan._MAX_SORT_PAYLOADS + 3)]
    blocks, _live, _needed = mesh_plan._scatter_to_blocks(many, pid, 3, 3)
    assert [np.asarray(b)[2].tolist() for b in blocks] == [
        [0 + i, 30 + i, 60 + i] for i in range(len(many))]


def test_a_large_batch_reserves_its_share_and_a_quarter():
    assert mesh_plan.exchange_block(1 << 16, 4) == 1 << 16
    assert mesh_plan.exchange_block(1 << 22, 4) == (1 << 20) + (1 << 18)
    assert mesh_plan.exchange_block(1 << 17, 1) == 1 << 17
    assert mesh_plan.exchange_block(100_000, 8) == 16384


def test_a_short_block_is_flagged_widened_and_remembered(monkeypatch):
    """Every row of t has one key: all of a shard's rows go to one
    destination, more than the block a large batch reserves for it."""
    monkeypatch.setattr(mesh_plan, "_FULL_BLOCK_ROWS", 1 << 10)
    monkeypatch.setattr(mesh_plan, "_FULL_JOIN_ROWS", 1 << 10)
    # other shapes than any other test's: a program traced with the
    # full-capacity blocks must not answer from the program cache
    dist, local, _mem = runners(skew=True, rows=N // 2)
    want = sorted(map(tuple, local.execute(JOIN).rows))
    assert on_mesh(dist, JOIN) == want
    first = mesh_chunk.last_run_info()
    assert first["attempts"] > 1
    learned = [caps for caps in mesh_chunk._LEARNED_CAPS.values()
               if any(site.endswith(":xchg") for site in caps)]
    assert learned and any(
        cap > mesh_plan.exchange_block(4096, len(jax.devices()))
        for caps in learned for site, cap in caps.items() if site.endswith(":xchg"))
    assert on_mesh(dist, JOIN) == want
    assert mesh_chunk.last_run_info()["attempts"] == 1      # starts where it ended


# -- the bounded-domain group reduce ----------------------------------------------------


def test_dictionary_keys_reduce_without_a_sort():
    mem = create_memory_connector()
    rng = np.random.default_rng(1)
    flags = ["A", "N", "R"]
    mem.load_table(
        "s", "f", [ColumnMetadata("flag", T.VARCHAR), ColumnMetadata("q", T.BIGINT)],
        [[flags[i] for i in rng.integers(0, 3, 5000)],
         rng.integers(0, 50, 5000).astype(np.int64)],
    )
    n = len(jax.devices())
    dist = DistributedQueryRunner(Session(catalog="memory", schema="s"),
                                  n_workers=n, hash_partitions=n)
    dist.register_catalog("memory", mem)
    local = LocalQueryRunner(Session(catalog="memory", schema="s"))
    local.register_catalog("memory", mem)
    sql = "select flag, count(*), sum(q), count(q) from f group by flag"
    seen = []
    real = mesh_plan.G.dense_group_reduce
    mesh_plan.G.dense_group_reduce = lambda *a, **k: seen.append(a[6]) or real(*a, **k)
    try:
        assert on_mesh(dist, sql) == sorted(map(tuple, local.execute(sql).rows))
    finally:
        mesh_plan.G.dense_group_reduce = real
    assert seen and set(seen) == {(3,)}        # once per trace of the program
    # min is not a sum: the sort path
    sql = "select flag, min(q) from f group by flag"
    assert on_mesh(dist, sql) == sorted(map(tuple, local.execute(sql).rows))


# -- counters, log, spans ------------------------------------------------------------------


def test_the_census_counts_what_a_run_exchanges():
    # streamed: a prelude's outputs may be pinned and then nothing runs
    dist, _local, _mem = runners(mesh_chunk_rows=2048)
    on_mesh(dist, JOIN)         # traced and compiled
    c0 = counters("mesh.all_to_all", "mesh.all_gather", "mesh.bytes_exchanged")
    traced = mesh_plan.mesh_counter("all_to_all")
    on_mesh(dist, JOIN)         # a second run traces nothing and exchanges again
    c1 = counters(*c0)
    assert mesh_plan.mesh_counter("all_to_all") == traced
    assert c1["mesh.all_to_all"] - c0["mesh.all_to_all"] >= 3
    assert c1["mesh.bytes_exchanged"] > c0["mesh.bytes_exchanged"]

    def body(x):
        a = jax.lax.all_to_all(x.reshape(n, -1), mesh_plan.AXIS, 0, 0, tiled=True)
        dead = jax.lax.all_gather(x, mesh_plan.AXIS, tiled=True)  # read by nothing
        return a.reshape(-1)

    n = len(jax.devices())
    from jax.sharding import Mesh, PartitionSpec as P

    f = mesh_plan.shard_map(
        body, mesh=Mesh(np.array(jax.devices()), (mesh_plan.AXIS,)),
        in_specs=(P(mesh_plan.AXIS),), out_specs=P(mesh_plan.AXIS), check_vma=False)
    census, read = mesh_chunk.exchange_census(
        f, n, jax.ShapeDtypeStruct((n * n * 4,), jnp.int64))
    assert (census.all_to_all, census.all_gather) == (1, 0) and read == [True]
    assert census.bytes_exchanged == n * 4 * 8 * (n - 1)


def test_a_fallback_is_logged_with_its_time():
    dist, _local, _mem = runners()
    t0 = time.perf_counter()
    before = METRICS.counter("mesh.fallbacks")
    dist._record_mesh_fallback("window without partition keys")
    assert METRICS.counter("mesh.fallbacks") == before + 1
    at, reason = mesh_plan.FALLBACK_LOG[-1]
    assert t0 <= at <= time.perf_counter() and reason.startswith("window")


def test_mesh_spans_in_the_profilers_trace(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    monkeypatch.setattr(mesh_feed, "AUTO_CHUNK_ROWS", 4096)
    dist, _local, _mem = runners()
    on_mesh(dist, SUMS)         # compiled outside the trace
    mem = dist.catalogs.get("memory")
    mem.store.tables[("s", "t")].mesh_feeds.clear()     # so that a feed is placed
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        on_mesh(dist, SUMS)
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _s, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    events = [e for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith("tpusql.")]
    names = [e.name for e in events]
    assert "tpusql.mesh.feed" in names
    assert names.count("tpusql.mesh.step") == mesh_chunk.last_run_info()["chunks"]
    assert "tpusql.mesh.finish" in names
    assert names.count("tpusql.sync.mesh.step_flags") == names.count("tpusql.mesh.step")
    assert "tpusql.sync.mesh.finish_flags" in names and "tpusql.sync.mesh.result" in names
    step = dict(next(e for e in events if e.name == "tpusql.mesh.step").stats)
    assert {"chunk", "all_to_all", "all_gather", "bytes_exchanged"} <= set(step)
    assert int(step["all_to_all"]) >= 1 and int(step["bytes_exchanged"]) > 0
    from trino_tpu.runtime import tracing

    assert tracing.host_span("mesh.step") is tracing.OFF      # no trace, no event


def test_a_fallback_can_fail_the_statement_instead():
    dist, _local, _mem = runners()
    sql = "select 1"        # no distributed fragment: no mesh form
    dist.execute(sql)
    assert dist.last_mesh_fallback is not None

    def refuse(reason):
        raise RuntimeError(f"left the mesh plane: {reason}")

    dist.on_mesh_fallback = refuse
    before = METRICS.counter("mesh.fallbacks")
    with pytest.raises(RuntimeError, match="left the mesh plane"):
        dist.execute(sql)
    assert METRICS.counter("mesh.fallbacks") == before + 1     # recorded first
    assert on_mesh(dist, SUMS)                                  # the plane still serves


def test_a_column_only_the_exchange_sort_carries_is_not_read():
    """t's scan lists k, g and v; the join plan repartitions the whole
    scan on k and reads v nowhere: v rides the exchange's sort as a
    payload nothing reads, stays on the host, and is not counted."""
    dist, local, mem = runners(rows=N // 3)
    sql = "select t.g, count(*) from t, d where t.k = d.k group by t.g"
    assert on_mesh(dist, sql) == sorted(map(tuple, local.execute(sql).rows))
    (placed,) = mem.store.tables[("s", "t")].mesh_feeds.values()
    assert sorted(placed.columns) == ["g", "k"]
