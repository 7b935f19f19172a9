#!/usr/bin/env python3
"""chip_smoke.py: the served SQL path, once, on the chip.

One process, one chip: TPC-H SF1 is generated from the repo's
deterministic generator, loaded into the memory connector, served by a
`CoordinatorServer` in this process and queried by `trino_tpu.client`
over the HTTP statement protocol. Every statement runs twice (cold,
warm); every answer is compared with a plain numpy reference computed
on the host from the same generated arrays. Any phase that fails raises
and the process exits non-zero; the last line is printed only after
every phase passed.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the mesh plane only, four chips

Lines before the last are one JSON object per phase. Wall times in them
are smoke readings (one cold and one warm execution each), not
benchmark numbers.

The phases are importable functions that take the scale factor and the
runner, so tests/test_chip_smoke.py drives the same code at `tiny` on
the CPU mesh; `main()` itself refuses anything but a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import datetime
import functools
import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

SF1 = 1.0
TINY = 0.01
SCHEMA = "smoke"

Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

Q6 = """
select sum(l_extendedprice * l_discount) as revenue from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07 and l_quantity < 24
"""

Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""

# 8 x 5 x 4 = 160 key slots: past the 64-slot unrolled dense path, inside
# the 2048-slot band the Pallas MXU group-by serves on a TPU. Q1's 12
# slots x 14 value slots reach that kernel too since PR 31; this is the
# statement whose call the smoke's proof compiles.
G3 = """
select l_shipmode, l_shipinstruct, l_returnflag, count(*), sum(l_quantity)
from lineitem group by 1, 2, 3
"""

# TPC-H Q18 at its validation value (QUANTITY 300): 6 M rows into 1.5 M
# groups on the aggregation's sort path, HAVING, the semi-join placed
# on `orders`, two joins, top-100 (chipbench/Q18.md; the cell sf10.q18
# runs it at SF10)
Q18 = """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
  sum(l_quantity)
from customer, orders, lineitem
where o_orderkey in (
    select l_orderkey from lineitem group by l_orderkey
    having sum(l_quantity) > 300)
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate
limit 100
"""

POINT = "select o_custkey, o_totalprice from orders where o_orderkey = {key}"
N_POINT_LOOKUPS = 20

# the columns the statements touch (pruned load, as bench.py does it)
TABLE_COLUMNS = {
    "lineitem": [
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount",
        "l_tax", "l_returnflag", "l_linestatus", "l_shipdate",
        "l_shipmode", "l_shipinstruct",
    ],
    "orders": [
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority",
        "o_totalprice",
    ],
    "customer": ["c_custkey", "c_mktsegment", "c_name"],
}

# statements of the one-chip run, in execution order; Q3 and Q18 are
# the ones whose programs take the TPU compiler minutes when cold
# (CHANGES.md, PR 33, has the seconds): give the call its time
STATEMENTS = (("q6", Q6), ("g3", G3), ("q3", Q3), ("q1", Q1), ("q18", Q18))
# statements of the four-chip run (the mesh plane), cheapest compile
# first. Q1 is not among them: its one mesh program alone takes the TPU
# compiler minutes, and four chips cost four times as much per second
# (CHANGES.md, PR 22)
MESH_STATEMENTS = (("g3", G3), ("q3", Q3))
MESH_CHUNK_ROWS = 1 << 18


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_phase(chips: int) -> dict:
    """Refuse anything but `chips` TPU devices; report versions and the
    compile cache in use. Returns the `device` object of the last line."""
    import jax
    import jaxlib

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform={d0.platform!r}, "
            f"{len(devices)} device(s)); this script only runs on the chip"
        )
    if len(devices) != chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} but JAX reports "
            f"{len(devices)} device(s)"
        )
    from trino_tpu.compile.cache import ACTIVE_PERSISTENT_CACHE

    if ACTIVE_PERSISTENT_CACHE is None:
        raise SystemExit("chip_smoke: no persistent compile cache is active")
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    cache = ACTIVE_PERSISTENT_CACHE.stats()
    emit(
        "device",
        platform=d0.platform, kind=d0.device_kind, count=len(devices),
        jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu_version,
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        compile_cache_entries=cache["entries"],
        compile_cache_bytes=cache["bytes"],
    )
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def native_phase() -> None:
    """Say whether the host runtime's C++ library loaded; either is fine."""
    from trino_tpu import native

    lib = native.get_lib()
    emit("native", pagesplit="c++ library" if lib is not None
         else "numpy fallback")


# ---------------------------------------------------------------------------
# load
# ---------------------------------------------------------------------------


def generate_tables(sf: float) -> Dict[str, Dict[str, tuple]]:
    """{table: {column: (host array, Dictionary | None)}} from the repo's
    deterministic TPC-H generator."""
    from trino_tpu.connectors.tpch import base_row_count, generate_column

    tables = {}
    for table, cols in TABLE_COLUMNS.items():
        base = base_row_count(table, sf)
        tables[table] = {
            name: generate_column(table, name, sf, 0, base) for name in cols
        }
    return tables


def memory_catalog(tables):
    from trino_tpu.connectors.memory import create_memory_connector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.connectors.tpch import TABLES

    mem = create_memory_connector()
    for table, cols in tables.items():
        types = dict(TABLES[table])
        mem.load_table(
            SCHEMA, table,
            [ColumnMetadata(n, types[n]) for n in cols],
            [data for data, _ in cols.values()], None,
            [d for _, d in cols.values()],
        )
    return mem


def load_phase(sf: float):
    """Generate, load into the memory connector behind a LocalQueryRunner
    at the engine's default batch size, and scan every loaded column
    once so the tables are resident on the device.
    Returns (runner, tables)."""
    from trino_tpu.engine import LocalQueryRunner, Session

    t0 = time.perf_counter()
    tables = generate_tables(sf)
    generate_s = time.perf_counter() - t0
    runner = LocalQueryRunner(Session(catalog="memory", schema=SCHEMA))
    runner.register_catalog("memory", memory_catalog(tables))
    t0 = time.perf_counter()
    rows = {}
    for table, cols in tables.items():
        counts = ", ".join(f"count({c})" for c in cols)
        got = runner.execute(f"select {counts} from {table}").rows[0]
        n = len(next(iter(cols.values()))[0])
        if any(v != n for v in got):
            raise AssertionError(f"load: {table} scanned {got}, loaded {n}")
        rows[table] = n
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    in_use = stats.get("bytes_in_use")
    loaded = sum(
        data.nbytes for cols in tables.values() for data, _ in cols.values()
    )
    emit(
        "load", sf=sf, rows=rows, loaded_bytes=loaded,
        batch_rows=runner.session.batch_rows,
        device_bytes_in_use=in_use,
        generate_s=round(generate_s, 3),
        first_scan_s=round(time.perf_counter() - t0, 3),
    )
    if in_use is not None and in_use < loaded:
        raise AssertionError(
            f"load: {in_use} bytes in use on the device after the first "
            f"scan, less than the {loaded} bytes loaded"
        )
    return runner, tables


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def point_keys(tables, seed: int) -> List[int]:
    keys = tables["orders"]["o_orderkey"][0]
    rng = np.random.default_rng(seed)
    return [int(k) for k in rng.choice(keys, N_POINT_LOOKUPS, replace=False)]


def _timed(execute, sql: str):
    """(rows, wall seconds, XLA compiles) of one `execute(sql)`, a client's
    or a runner's, timed with the result in hand."""
    from trino_tpu.runtime.metrics import METRICS

    before = METRICS.counter("xla_compiles")
    t0 = time.perf_counter()
    result = execute(sql)
    wall = time.perf_counter() - t0
    return result, wall, int(METRICS.counter("xla_compiles") - before)


# a cold join statement compiles for minutes before its first row
CLIENT_TIMEOUT_S = 1100.0


def serve_phase(runner, tables, seed: int) -> Dict[str, list]:
    """Serve `runner` over HTTP in this process and run every statement
    twice through `trino_tpu.client.Client`. The warm execution must
    compile nothing. Returns {statement name: rows of the warm run}."""
    from trino_tpu.client import Client
    from trino_tpu.runtime.metrics import install_xla_compile_listener
    from trino_tpu.runtime.server import CoordinatorServer

    if not install_xla_compile_listener():
        raise AssertionError("serve: no XLA compile listener in this jax")
    server = CoordinatorServer(runner, port=0)
    try:
        client = Client(server.uri, timeout=CLIENT_TIMEOUT_S)
        results = {}
        for name, sql in STATEMENTS:
            cold, cold_s, cold_compiles = _timed(client.execute, sql)
            warm, warm_s, warm_compiles = _timed(client.execute, sql)
            rows = warm.rows
            emit(
                "serve", statement=name, rows=len(rows),
                cold_wall_s=round(cold_s, 4), warm_wall_s=round(warm_s, 4),
                cold_compiles=cold_compiles, warm_compiles=warm_compiles,
                note="smoke reading, not a benchmark number",
            )
            if warm_compiles:
                raise AssertionError(
                    f"serve: warm {name} compiled {warm_compiles} programs"
                )
            if rows != cold.rows:
                raise AssertionError(f"serve: {name} warm rows != cold rows")
            results[name] = rows
        # point lookups: one statement shape, twenty keys; the first key
        # is the cold execution, the other nineteen the warm ones
        walls, compiles, point_rows = [], [], []
        for key in point_keys(tables, seed):
            result, wall, n = _timed(client.execute, POINT.format(key=key))
            walls.append(wall)
            compiles.append(n)
            point_rows.append(result.rows)
        emit(
            "serve", statement="point", lookups=len(walls),
            rows=sum(len(r) for r in point_rows),
            cold_wall_s=round(walls[0], 4),
            warm_wall_s_median=round(float(np.median(walls[1:])), 4),
            cold_compiles=compiles[0], warm_compiles=sum(compiles[1:]),
            note="smoke reading, not a benchmark number",
        )
        if sum(compiles[1:]):
            raise AssertionError(
                f"serve: warm point lookups compiled {compiles[1:]}"
            )
        results["point"] = point_rows
        return results
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# compare: the plain reference (numpy on the host, exact integers)
# ---------------------------------------------------------------------------


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - datetime.date(1970, 1, 1)).days


def _group_sums(codes: np.ndarray, n_groups: int, *values: np.ndarray):
    """Exact int64 per-group sums of each value column (codes in
    [0, n_groups)), and the group sizes last."""
    order = np.argsort(codes, kind="stable")
    bounds = np.searchsorted(codes[order], np.arange(n_groups + 1))
    out = []
    for v in values:
        c = np.concatenate([[0], np.cumsum(v[order], dtype=np.int64)])
        out.append(c[bounds[1:]] - c[bounds[:-1]])
    out.append(np.diff(bounds))
    return out


def _dec(unscaled: int, scale: int) -> float:
    """A decimal as the statement protocol renders it: the JSON double
    nearest the exact quotient (block.to_pylists), so equality with the
    served value is exact up to what a double can carry."""
    return int(unscaled) / 10 ** scale


def _avg2(total: int, count: int) -> float:
    """avg over decimal(12,2): rounded half up at scale 2 (non-negative)."""
    return _dec((2 * int(total) + int(count)) // (2 * int(count)), 2)


def _col(tables, table, name):
    return tables[table][name][0]


def _dict_values(tables, table, name):
    return list(tables[table][name][1].values)


def reference_q1(tables):
    rf, ls = _col(tables, "lineitem", "l_returnflag"), _col(tables, "lineitem", "l_linestatus")
    rf_names = _dict_values(tables, "lineitem", "l_returnflag")
    ls_names = _dict_values(tables, "lineitem", "l_linestatus")
    keep = _col(tables, "lineitem", "l_shipdate") <= _days("1998-12-01") - 90
    qty, ep, disc, tax = (
        _col(tables, "lineitem", c)[keep]
        for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
    )
    disc_price = ep * (100 - disc)
    codes = (rf[keep].astype(np.int64) * len(ls_names) + ls[keep])
    s_qty, s_ep, s_dp, s_ch, s_disc, n = _group_sums(
        codes, len(rf_names) * len(ls_names),
        qty, ep, disc_price, disc_price * (100 + tax), disc,
    )
    rows = []
    for g in np.nonzero(n)[0]:
        rows.append([
            rf_names[g // len(ls_names)], ls_names[g % len(ls_names)],
            _dec(s_qty[g], 2), _dec(s_ep[g], 2), _dec(s_dp[g], 4),
            _dec(s_ch[g], 6), _avg2(s_qty[g], n[g]), _avg2(s_ep[g], n[g]),
            _avg2(s_disc[g], n[g]), int(n[g]),
        ])
    return sorted(rows, key=lambda r: (r[0], r[1]))


def reference_q6(tables):
    sd = _col(tables, "lineitem", "l_shipdate")
    disc = _col(tables, "lineitem", "l_discount")
    keep = (
        (sd >= _days("1994-01-01")) & (sd < _days("1995-01-01"))
        & (disc >= 5) & (disc <= 7)
        & (_col(tables, "lineitem", "l_quantity") < 2400)
    )
    total = int(np.sum(
        _col(tables, "lineitem", "l_extendedprice")[keep] * disc[keep],
        dtype=np.int64,
    ))
    return [[_dec(total, 4)]]


def cell_reference(name: str, tables):
    """G3's, Q3's and Q18's expected rows are the benchmark's: the plain
    reference its cells hold every answer against
    (chipbench/references/<name>.py), at the parameters this script's
    text of the statement has (the statement file's `validation`). One
    copy of what the three statements mean, here and in the cells."""
    from chipbench import traffic

    statement = traffic.load_statement(name)
    params = traffic.load_json(os.path.join(
        os.path.dirname(os.path.abspath(traffic.__file__)),
        "statements", f"{name}.json",
    ))["validation"]
    return statement.module.reference(tables, params)


reference_q3 = functools.partial(cell_reference, "q3")
reference_g3 = functools.partial(cell_reference, "g3")
reference_q18 = functools.partial(cell_reference, "q18")


def reference_point(tables, seed: int):
    o_key = _col(tables, "orders", "o_orderkey")
    out = []
    for key in point_keys(tables, seed):
        hit = np.nonzero(o_key == key)[0]
        out.append([
            [int(_col(tables, "orders", "o_custkey")[i]),
             _dec(_col(tables, "orders", "o_totalprice")[i], 2)]
            for i in hit
        ])
    return out


REFERENCES = {
    "q1": reference_q1, "q6": reference_q6, "q3": reference_q3,
    "g3": reference_g3, "q18": reference_q18,
}
# statements whose SQL fixes the row order; the others compare as sets
ORDERED = {"q1", "q3", "q6", "q18"}


def _same_rows(name: str, got: list, want: list) -> bool:
    if name in ORDERED:
        return got == want
    return sorted(map(repr, got)) == sorted(map(repr, want))


def compare_phase(results: Dict[str, list], tables, seed: int) -> None:
    """Every served result equals the numpy reference, exactly."""
    for name, got in results.items():
        if name == "point":
            want = reference_point(tables, seed)
            ok = got == want and all(len(r) == 1 for r in got)
        else:
            want = REFERENCES[name](tables)
            ok = _same_rows(name, got, want)
        emit("compare", statement=name, rows=len(got), equal_to_reference=ok)
        if not ok:
            raise AssertionError(
                f"compare: {name} differs from the numpy reference: "
                f"got {got[:3]} ... want {want[:3]} ..."
            )


# ---------------------------------------------------------------------------
# proof of device: G3 ran the compiled Pallas kernel
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def mxu_spy():
    """Record every call of the Pallas MXU group-by the engine makes
    (ops/groupby.py imports it at the call site, so wrapping the module
    attribute is enough). Yields the list of recorded calls."""
    from trino_tpu.ops import mxu_groupby

    real = mxu_groupby.grouped_sum_mxu
    calls: List[dict] = []

    def spy(gid, values, live, capacity, interpret=False, limbs=None):
        calls.append({
            "n": int(gid.shape[0]), "value_columns": len(values),
            "capacity": int(capacity), "interpret": bool(interpret),
            "limbs": limbs,
        })
        return real(gid, values, live, capacity, interpret=interpret,
                    limbs=limbs)

    mxu_groupby.grouped_sum_mxu = spy
    try:
        yield calls
    finally:
        mxu_groupby.grouped_sum_mxu = real


def proof_of_device_phase(calls: List[dict]) -> None:
    """G3 reached `grouped_sum_mxu`, not interpreted, and the program it
    ran lowers to a Mosaic custom call."""
    import jax
    import jax.numpy as jnp
    from trino_tpu.ops.mxu_groupby import grouped_sum_mxu

    if not calls:
        raise AssertionError("proof: the MXU group-by kernel was never called")
    interpreted = [c for c in calls if c["interpret"]]
    if interpreted:
        raise AssertionError(f"proof: kernel ran interpreted: {interpreted[0]}")
    shape = calls[0]
    n = shape["n"]
    text = grouped_sum_mxu.lower(
        jax.ShapeDtypeStruct((n,), jnp.int32),
        tuple(jax.ShapeDtypeStruct((n,), jnp.int64)
              for _ in range(shape["value_columns"])),
        jax.ShapeDtypeStruct((n,), jnp.bool_),
        capacity=shape["capacity"], interpret=shape["interpret"],
        limbs=shape["limbs"],
    ).as_text()
    emit("proof_of_device", kernel="grouped_sum_mxu", calls=len(calls),
         shape=shape, tpu_custom_call="tpu_custom_call" in text)
    if "tpu_custom_call" not in text:
        raise AssertionError("proof: no tpu_custom_call in the lowered program")


# ---------------------------------------------------------------------------
# --chips 4: the mesh plane
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def feed_spy():
    """Record, per mesh run, the bytes of the scan feeds each device
    holds once the mesh runner has placed them (every mesh query, chunked
    or not, goes through ChunkedMeshRunner). Yields the list of
    {device id: bytes} dicts, one per run."""
    import jax
    from trino_tpu.parallel.mesh_chunk import ChunkedMeshRunner

    real = ChunkedMeshRunner.run
    runs: List[Dict[int, int]] = []

    def run(self, *args, **kwargs):
        try:
            return real(self, *args, **kwargs)
        finally:
            # the feeds are placed once a program says what it reads
            held: Dict[int, int] = {}
            for a in {id(a): a for a in jax.tree_util.tree_leaves(
                    self.feed_args)}.values():
                if a.sharding.is_fully_replicated:
                    continue
                for shard in a.addressable_shards:
                    held[shard.device.id] = (
                        held.get(shard.device.id, 0) + shard.data.nbytes
                    )
            runs.append(held)

    ChunkedMeshRunner.run = run
    try:
        yield runs
    finally:
        ChunkedMeshRunner.run = real


def mesh_phase(tables, expected: Dict[str, list], n_devices: int,
               chunk_rows: int = MESH_CHUNK_ROWS) -> None:
    """A colocated DistributedQueryRunner over `tables` answers
    MESH_STATEMENTS on the mesh plane, with session defaults (one program)
    and chunked, twice each (cold, warm), equal to `expected`: the numpy
    reference's rows on the chips (which the one-chip run's rows equal;
    a LocalQueryRunner beside the mesh would cost four chips its cold
    compiles), a LocalQueryRunner's rows in the tier-1 test."""
    from trino_tpu.engine import Session
    from trino_tpu.parallel.mesh_plan import MESH_COUNTERS
    from trino_tpu.runtime import DistributedQueryRunner
    from trino_tpu.runtime.metrics import install_xla_compile_listener

    if not install_xla_compile_listener():
        raise AssertionError("mesh: no XLA compile listener in this jax")
    runners = {}
    for arm, session_kw in (
        ("default", {}), ("chunked", {"mesh_chunk_rows": chunk_rows}),
    ):
        runners[arm] = DistributedQueryRunner(
            Session(catalog="memory", schema=SCHEMA, **session_kw),
            n_workers=n_devices, hash_partitions=n_devices,
        )
        runners[arm].register_catalog("memory", memory_catalog(tables))
    # statement-major, so the chunked arm is first met after one
    # statement's compiles and not after all of them
    for name, sql in MESH_STATEMENTS:
        for arm, runner in runners.items():
            a2a0 = MESH_COUNTERS["all_to_all"]
            with feed_spy() as feeds:
                result, cold_s, _ = _timed(runner.execute, sql)
            a2a = MESH_COUNTERS["all_to_all"] - a2a0
            held = feeds[-1] if feeds else {}
            warm, warm_s, warm_compiles = _timed(runner.execute, sql)
            equal = _same_rows(name, result.rows, expected[name])
            emit(
                "mesh", arm=arm, statement=name, rows=len(result.rows),
                data_plane=result.data_plane,
                mesh_fallback=runner.last_mesh_fallback,
                all_to_all=a2a, feed_bytes_by_device=held,
                equal_to_expected=equal,
                cold_wall_s=round(cold_s, 4), warm_wall_s=round(warm_s, 4),
                warm_compiles=warm_compiles,
                note="smoke reading, not a benchmark number",
            )
            for r in (result, warm):
                if r.data_plane != "mesh" or runner.last_mesh_fallback:
                    raise AssertionError(
                        f"mesh: {arm} {name} ran on {r.data_plane} "
                        f"(fallback: {runner.last_mesh_fallback})"
                    )
            if a2a < 1:
                raise AssertionError(f"mesh: {arm} {name} ran no all_to_all")
            if len(held) != n_devices or not all(held.values()):
                raise AssertionError(
                    f"mesh: {arm} {name} feeds on devices {sorted(held)} "
                    f"only, not on all {n_devices}"
                )
            if not equal or warm.rows != result.rows:
                raise AssertionError(
                    f"mesh: {arm} {name} differs from the expected rows: got "
                    f"{result.rows[:3]} ... want {expected[name][:3]} ..."
                )
            if warm_compiles:
                raise AssertionError(
                    f"mesh: warm {arm} {name} compiled {warm_compiles} programs"
                )


# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22,
                    help="draws the point-lookup keys")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh plane only, on four chips")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import trino_tpu  # noqa: F401  (x64 + compile cache, before any jax use)

    device = device_phase(args.chips)
    native_phase()
    if args.chips == 1:
        runner, tables = load_phase(SF1)
        with mxu_spy() as calls:
            results = serve_phase(runner, tables, args.seed)
        compare_phase(results, tables, args.seed)
        proof_of_device_phase(calls)
    else:
        tables = generate_tables(SF1)
        mesh_phase(
            tables, {name: REFERENCES[name](tables) for name, _ in MESH_STATEMENTS},
            args.chips,
        )
    from trino_tpu.compile.cache import ACTIVE_PERSISTENT_CACHE
    from trino_tpu.runtime.metrics import METRICS

    emit(
        "done", total_s=round(time.perf_counter() - t_start, 3),
        xla_compiles=int(METRICS.counter("xla_compiles")),
        compile_cache_hits=int(METRICS.counter("compile_cache_hits")),
        compile_cache_misses=int(METRICS.counter("compile_cache_misses")),
        compile_cache_entries=ACTIVE_PERSISTENT_CACHE.entry_count(),
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
