"""The four-chip cell `sf30.mesh4` on the CPU's virtual-device mesh: its
configuration, traffic and runner files, the runner kind `mesh` serving
G3 and Q3 on the mesh plane (no fallback, collectives, answers equal to
the plain references), the partition tied to the whole, and
`chipbench/mesh_trace.py` by hand, on the one-device traces (nothing to
read) and on `trace_mesh4_small.xplane.pb`, recorded on four v5e chips
in PR 28. The chip runs are `python3 chipbench/run.py --workload
sf30.mesh4` on the four-chip host (chipbench/MESH.md)."""

import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from chipbench import harness, mesh_trace, spans, stats, trace, traffic  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "sf30.mesh4"
BENCHMARK, CONFIG, MIX = traffic.load_cell(ROOT, CELL)
RECORDED = os.path.join(HERE, "trace_mesh4_small.xplane.pb")
P = spans.PROGRAM
G3_COLUMNS = ["l_quantity", "l_returnflag", "l_shipmode", "l_shipinstruct"]


def build_runner(tables):
    kind = traffic.load_module(
        os.path.join(ROOT, "chipbench", "runners", f"{CONFIG['runner']}.py"))
    return kind.build(CONFIG, tables)


def same(statement, got, want):
    return harness.same_rows(statement, got, want)


# -- the files -----------------------------------------------------------------


def test_the_configuration_states_its_deployment_cuts_and_guarantees():
    assert CONFIG["name"] == "tpch-sf30-4chip" and CONFIG["scale"] == 30.0
    assert (CONFIG["chips"], CONFIG["runner"], CONFIG["connector"]) == (4, "mesh", "memory")
    sf10 = traffic.load_json(os.path.join(ROOT, "chipbench", "configs", "tpch-sf10-1chip.json"))
    assert CONFIG["guarantees"] == sf10["guarantees"]           # word for word
    assert CONFIG["batch_rows"] == sf10["batch_rows"]
    assert CONFIG["reduced"] == ["scale", "columns", "chips", "streams"]
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert len(CONFIG["source"]) <= 200 and "SF30" in CONFIG["source"]
    assert {"g3", "q3_segment"} <= set(CONFIG["assumed"])
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    cell = {w["name"]: w for w in BENCHMARK["workloads"]}[CELL]
    assert (cell["traffic"], cell["chips"]) == ("mesh.2streams", 4)
    # the one four-chip cell
    assert [w["name"] for w in BENCHMARK["workloads"] if w["chips"] == 4] == [CELL]


def test_the_traffic_is_the_issues():
    assert MIX["statements"] == ["g3", "q3"] and MIX["loop"] == "closed"
    assert (MIX["streams"], MIX["params_per_statement"], MIX["client_poll_ms"]) == (2, 3, 2)


@pytest.mark.parametrize("seed", [0, 1, 28, 2_147_483_659, 4_294_967_295])
def test_any_seed_plans_six_instances_and_two_stream_cycles(seed):
    plan = traffic.plan(MIX, seed)
    assert [i.name for i in plan.instances] == ["g3"] * 3 + ["q3"] * 3
    assert len({i.sql for i in plan.instances if i.name == "q3"}) == 3
    assert len(plan.schedule) == 2
    for k, cycle in enumerate(plan.schedule):
        assert sorted(cycle) == list(range(6))
        # statement kinds alternate, stream k starts at slot k
        assert [plan.instances[i].name for i in cycle] == (
            ["g3", "q3"] * 3 if k % 2 == 0 else ["q3", "g3"] * 3)
    assert traffic.columns_to_load(plan.instances) == {
        "lineitem": G3_COLUMNS + ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"],
        "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
        "customer": ["c_custkey", "c_mktsegment"],
    }


def test_a_program_without_the_feed_module_is_refused_at_once(monkeypatch):
    """What the parent commit does with this runner kind: it exits
    before it loads a table, it does not try for an hour."""
    import builtins

    real = builtins.__import__

    def no_mesh_feed(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "trino_tpu.parallel" and "mesh_feed" in (fromlist or ()):
            raise ImportError("cannot import name 'mesh_feed'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_mesh_feed)
    with pytest.raises(SystemExit) as e:
        build_runner({})
    assert "mesh_feed" in str(e.value)


# -- the runner kind on the mesh plane -------------------------------------------


@pytest.fixture(scope="module", params=[0.01, 0.04], ids=["tiny", "sf0.04"])
def served(request):
    tables = chip_smoke.generate_tables(request.param)
    return tables, build_runner(tables)


@pytest.mark.parametrize("name", MIX["statements"])
def test_the_mesh_runner_answers_on_the_mesh_plane(name, served):
    from trino_tpu.runtime.metrics import METRICS

    tables, runner = served
    plan = traffic.plan(MIX, 28_000_000_001)
    instances = [i for i in plan.instances if i.name == name]
    assert len(instances) == 3
    for inst in {i.sql: i for i in instances}.values():
        before = {k: METRICS.counter(k) for k in (
            "mesh.all_to_all", "mesh.fallbacks", "rows_scanned", "mesh.rows_fed")}
        result = runner.execute(inst.sql)
        assert result.data_plane == "mesh" and runner.last_mesh_fallback is None
        assert same(inst.statement, result.rows,
                    inst.statement.module.reference(tables, inst.params))
        after = {k: METRICS.counter(k) for k in before}
        assert after["mesh.all_to_all"] - before["mesh.all_to_all"] >= 1
        assert after["mesh.fallbacks"] == before["mesh.fallbacks"]
        # the plane feeds its devices itself: the scan operator's
        # counter, which the harness holds against device 0, stands still
        assert after["rows_scanned"] == before["rows_scanned"]
        assert after["mesh.rows_fed"] > before["mesh.rows_fed"]


def test_the_four_partitions_merge_to_the_whole():
    """G3's partial group states over the four shards the plane deals
    lineitem into, merged, are the one-chip runner's answer and the
    reference's: the shards are a partition of the table."""
    from trino_tpu.connectors.spi import TableHandle

    tables = chip_smoke.generate_tables(0.01)
    g3 = traffic.load_statement("g3")
    want = g3.module.reference(tables, {})
    local = traffic.load_module(os.path.join(ROOT, "chipbench", "runners", "local.py"))
    whole = local.build({**CONFIG, "runner": "local"}, tables)
    assert same(g3, whole.execute(g3.sql).rows, want)

    source = whole.catalogs.get("memory").page_source
    handle = TableHandle("memory", CONFIG["schema"], "lineitem")
    rows, fetch = source.host_shards(handle, G3_COLUMNS, 4)
    _cache, _key, meta = source.mesh_feeds(handle, G3_COLUMNS)
    assert sum(rows) == len(tables["lineitem"]["l_quantity"][0])
    assert max(rows) - min(rows) <= 3 and min(rows) > 0
    merged = {}
    for s in range(4):
        part = {"lineitem": {
            name: (np.asarray(fetch(j, s)[0]), dictionary)
            for j, (name, (_type, dictionary, _nulls))
            in enumerate(zip(G3_COLUMNS, meta))}}
        assert len(part["lineitem"]["l_quantity"][0]) == rows[s]
        for *key, count, quantity in local.build(
                {**CONFIG, "runner": "local"}, part).execute(g3.sql).rows:
            n, q = merged.get(tuple(key), (0, 0))
            merged[tuple(key)] = (n + count, q + round(quantity * 100))
    assert same(g3, [[*k, n, q / 100] for k, (n, q) in merged.items()], want)


# -- mesh_trace.py ---------------------------------------------------------------


def test_collectives_are_told_by_xlas_text_for_them():
    assert mesh_trace.collective_of("%all-to-all.3 = (s64[4,8]) all-to-all(%x)") == "all-to-all"
    assert mesh_trace.collective_of(
        "%ag = s64[32] all-gather-start(%p), dimensions={0}") == "all-gather"
    assert mesh_trace.collective_of("%all-reduce-done.1 = s32[] all-reduce-done(%s)") == "all-reduce"
    # as the v5e's trace has them: JAX names the operation after its
    # primitive, the result is a tuple with tilings, operands name others
    assert mesh_trace.collective_of(
        "%all_to_all.3 = (u32[4]{0:T(1024)S(1)}, pred[4]{0:T(1024)(128)(4,1)}) "
        "all-to-all(u32[4] %x), replica_groups={}") == "all-to-all"
    assert mesh_trace.collective_of("%all_to_all.9") == "all-to-all"
    assert mesh_trace.collective_of(
        "%fusion.3 = u32[4]{0} fusion(u32[4] %all_to_all.5), kind=kLoop") is None
    assert mesh_trace.collective_of("%sort.11 = (u64[8]) sort(%a)") is None
    assert mesh_trace.collective_of("%fusion.2") is None


def test_an_asynchronous_collective_is_in_flight_from_start_to_done():
    names = ["%all-gather-start.1 = (s64[8], s64[32]) all-gather-start(%p)",
             "%fusion.7 = s64[8] fusion(%p)",
             "%all-gather-done.1 = s64[32] all-gather-done(%all-gather-start.1)",
             "%all-to-all.4 = s64[4,8] all-to-all(%q)",
             "%fusion.8 = s64[8] fusion(%q)"]
    starts = np.asarray([0.0, 0.1, 2.0, 3.0, 5.0])
    ends = np.asarray([0.1, 1.9, 2.2, 4.0, 6.0])
    lo, hi = mesh_trace.exchange_intervals(names, starts, ends)
    assert list(zip(lo, hi)) == [(0.0, 2.2), (3.0, 4.0)]


def four_chip_trace(mesh_events, busy):
    """A SpanTrace by hand: chip i busy over `busy[i]` [(start, end,
    collective?)], one host line of `mesh_events`, a 10 s window."""
    device_ops = {}
    for i, ops in enumerate(busy):
        names = [("%all-to-all.1 = s64[4,8] all-to-all(%x)" if c else f"%fusion.{j} = f(%x)")
                 for j, (_a, _b, c) in enumerate(ops)]
        device_ops[f"/device:TPU:{i}"] = (
            names, np.asarray([a for a, _b, _c in ops], float),
            np.asarray([b for _a, b, _c in ops], float))
    yardstick = trace.Trace(device_ops, {}, {}, [
        trace.Annotation(trace.WINDOW, 0.0, 10.0, {}),
        trace.Annotation(trace.ENGINE, 0.0, 10.0, {})])
    return spans.SpanTrace(yardstick, [mesh_events], [])


def test_the_five_metrics_by_hand():
    events = [
        spans.Event(P + "mesh.prelude", 0.0, 1.0, {"bytes_exchanged": 1000}),
        spans.Event(P + "mesh.step", 1.0, 2.0, {"bytes_exchanged": 4e9, "chunk": 0}),
        spans.Event(P + "mesh.step", 2.0, 3.0, {"bytes_exchanged": 4e9, "chunk": 1}),
        # half inside the window: counts for half its bytes, and not as a step
        spans.Event(P + "mesh.step", 9.0, 11.0, {"bytes_exchanged": 8e9, "chunk": 2}),
        spans.Event(P + "mesh.finish", 3.0, 3.5, {"bytes_exchanged": 0}),
        spans.Event(P + "sync.mesh.step_flags", 1.5, 2.0, {}),
    ]
    busy = [[(0.0, 2.0, False), (2.0, 3.0, True)],      # 3 s busy, 1 s exchanging
            [(0.0, 1.0, False), (2.0, 3.0, True)],      # 2 s
            [(0.0, 1.0, False), (2.0, 3.0, True)],      # 2 s
            [(2.0, 3.0, True)]]                          # 1 s
    reduced = mesh_trace.reduce(four_chip_trace(events, busy))
    assert reduced["chips"] == 4 and reduced["busy_s"] == [3.0, 2.0, 2.0, 1.0]
    assert reduced["exchange_s"] == [1.0] * 4
    assert reduced["steps"] == 2 and reduced["programs"] == 5
    assert reduced["bytes_exchanged"] == pytest.approx(1000 + 8e9 + 4e9)
    got = mesh_trace.metrics(reduced, statements=4, fallbacks=0)
    assert got["mesh_exchange_share_pct"] == pytest.approx(100 * 4 / 8)
    assert got["mesh_device_skew_pct"] == pytest.approx(100 * (3 / 2 - 1))
    assert got["mesh_chunk_steps_per_stmt"] == pytest.approx(0.5)
    assert got["mesh_fallbacks_in_window"] == 0
    # a chip's share of the bytes against its interconnect, over its second inside
    assert got["mesh_exchange_roofline_pct"] == pytest.approx(
        100 * (12e9 + 1000) / 4 / mesh_trace.ICI_BYTES_PER_S / 1.0)
    assert 0 < got["mesh_exchange_roofline_pct"] < 100
    assert "mesh_fallbacks_in_window" not in mesh_trace.metrics(reduced, 4, None)


def synthetic_run(reduced):
    plan = traffic.plan(MIX, 1)
    samples = [stats.Sample(0, 0, 10.0, 10.030, [[1]]),
               stats.Sample(1, 3, 10.0, 10.012, [[2]])]
    return harness.RunData(
        MIX, CONFIG, plan.instances, stats.account(samples, 10.0, 1.0), [],
        {"xla_compiles": 0.0, "plan_cache.hits": 3.0, "plan_cache.misses": 1.0},
        [0.0] * len(plan.instances), [16] * len(plan.instances),
        10.0, {"hbm_bytes_per_s": 819e9}, reduced, samples,
    )


def put_trace(root, source):
    there = os.path.join(root, CELL, "plugins", "profile", "2026_09_28")
    os.makedirs(there)
    shutil.copy(source, os.path.join(there, "host.xplane.pb"))


@pytest.mark.parametrize("recorded", ["trace_small.xplane.pb", None])
def test_nothing_to_read_on_one_device_without_spans(recorded, tmp_path, monkeypatch, capsys):
    """`trace_small.xplane.pb` (one chip, a program from before the
    spans), found and read, and no trace at all: the metrics are left
    out, they are not zeros."""
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    if recorded:
        put_trace(str(tmp_path), os.path.join(HERE, recorded))
    small = trace.reduce(trace.load(os.path.join(HERE, "trace_small.xplane.pb")))
    run = synthetic_run(small)
    assert mesh_trace.for_run(run) is None
    assert "NO_PROGRAM_SPANS" in capsys.readouterr().out
    run.trace = run.trace_completed = None
    assert mesh_trace.for_run(run) is None


def test_one_chip_with_spans_reads_zero(tmp_path, monkeypatch):
    """`trace_spans_small.xplane.pb`: one chip, the program's spans, no
    mesh program: nothing exchanged, no step, no skew."""
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    one = os.path.join(HERE, "trace_spans_small.xplane.pb")
    put_trace(str(tmp_path), one)
    got = mesh_trace.for_run(synthetic_run(trace.reduce(trace.load(one))))
    assert got == {
        "mesh_exchange_share_pct": 0.0, "mesh_exchange_roofline_pct": 0.0,
        "mesh_device_skew_pct": 0.0, "mesh_chunk_steps_per_stmt": 0.0,
        "mesh_fallbacks_in_window": 0}


def test_the_recorded_four_device_trace(tmp_path, monkeypatch, capsys):
    """`trace_mesh4_small.xplane.pb`: `sf30.mesh4`'s own traced window on
    four v5e chips (my chip run, PR 28), cut to what `trace.py`,
    `spans.py` and `mesh_trace.py` read."""
    st = spans.load(RECORDED)
    assert len(st.yardstick.device_ops) == 4
    reduced = mesh_trace.reduce(st)
    yard = trace.reduce(st.yardstick)
    assert reduced["chips"] == yard["chips"] == 4
    assert sum(reduced["busy_s"]) / 4 == pytest.approx(yard["busy_s"])
    assert reduced["steps"] >= 1 and reduced["programs"] >= reduced["steps"]
    assert reduced["bytes_exchanged"] > 0
    assert all(0 < x <= b for x, b in zip(reduced["exchange_s"], reduced["busy_s"]))
    names = set(spans.reduce(st)["spans"])
    assert {P + "mesh.step", P + "mesh.finish", P + "sync.mesh.step_flags",
            P + "sync.mesh.result"} <= names
    # and as the harness would call a reader
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    put_trace(str(tmp_path), RECORDED)
    got = mesh_trace.for_run(synthetic_run(yard))
    assert set(got) == {
        "mesh_exchange_share_pct", "mesh_exchange_roofline_pct",
        "mesh_device_skew_pct", "mesh_chunk_steps_per_stmt",
        "mesh_fallbacks_in_window"}
    assert 0 < got["mesh_exchange_share_pct"] < 100
    assert 0 < got["mesh_exchange_roofline_pct"] <= 100
    assert 0 <= got["mesh_device_skew_pct"] < 300
    assert got["mesh_chunk_steps_per_stmt"] == reduced["steps"] / 2
    assert got["mesh_fallbacks_in_window"] == 0
    assert mesh_trace.main([CELL]) == 0
    assert '"mesh_exchange_share_pct"' in capsys.readouterr().out
