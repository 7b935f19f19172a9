"""Chunked mesh-plane tests: preemptible ICI execution (PR 10).

The mesh plane (parallel/mesh_chunk.py) splits the driver scan into
per-chunk jit steps with host preemption checks at every chunk
boundary, so deadline kills, client abandonment and the stuck-task
watchdog fire mid-query WITHOUT leaving the mesh. These tests pin the
contract:

  - results are identical across chunk settings (unchunked, K=1, K=2,
    K=many) — the carry/flush machinery must not change answers;
  - a wall deadline preempts BETWEEN chunks with the typed
    EXCEEDED_TIME_LIMIT error and no page-plane fallback;
  - abandonment (cancel) and the watchdog (MeshStuck -> retryable page
    fallback) take their distinct paths;
  - second execution of a chunked query lowers ZERO new XLA programs
    (the record cache + deterministic capacity ladder);
  - chunk capacities land on capacity-ladder rungs and the programs
    register WarmupEntrys / warm classes with the compile regime;
  - a mid-execution MeshUnsupported falls back observably (reason in
    QueryInfo, mesh_fallback trace event) and still answers correctly.
"""

import pytest

from trino_tpu.connectors.tpch import create_tpch_connector
from trino_tpu.engine import Session
from trino_tpu.parallel import mesh_chunk, mesh_plan
from trino_tpu.runtime import DistributedQueryRunner
from trino_tpu.runtime.metrics import METRICS
from trino_tpu.runtime.query_tracker import (
    EXCEEDED_TIME_LIMIT,
    QueryAbandonedError,
    QueryDeadlineError,
)

# exact-valued aggregates only: chunked accumulation changes float
# merge ORDER, so byte-identity asserts stick to ints and
# integral-valued decimal columns
Q_GROUP = (
    "select l_returnflag, l_linestatus, count(*) c, "
    "sum(l_quantity) q, min(l_orderkey) mn, max(l_orderkey) mx "
    "from lineitem group by l_returnflag, l_linestatus "
    "order by l_returnflag, l_linestatus"
)
Q_JOIN = (
    "select o_orderpriority, count(*) c from orders join customer "
    "on o_custkey = c_custkey group by o_orderpriority "
    "order by o_orderpriority"
)


def mk_runner(**session_kw):
    r = DistributedQueryRunner(
        Session(catalog="tpch", schema="tiny", **session_kw),
        n_workers=2, hash_partitions=2,
    )
    r.register_catalog("tpch", create_tpch_connector())
    return r


@pytest.fixture(scope="module")
def baseline_rows(tpch_cluster_mesh_off):
    """Page-plane answers — the oracle every chunk setting must hit.
    Read-only queries on the shared session cluster (tier-1 wall)."""
    return {
        "group": tpch_cluster_mesh_off.execute(Q_GROUP).rows,
        "join": tpch_cluster_mesh_off.execute(Q_JOIN).rows,
    }


# tiny-SF lineitem holds ~7.5k rows per shard on the 8-device mesh:
# 8192 -> one chunk, 4096 -> two, 512 -> many
@pytest.mark.parametrize("chunk_rows", [0, 8192, 4096, 512])
def test_chunked_results_identical(chunk_rows, baseline_rows):
    r = mk_runner(mesh_chunk_rows=chunk_rows)
    before = mesh_plan.MESH_COUNTERS["queries"]
    assert r.execute(Q_GROUP).rows == baseline_rows["group"]
    assert r.execute(Q_JOIN).rows == baseline_rows["join"]
    assert mesh_plan.MESH_COUNTERS["queries"] == before + 2, \
        f"fell back to HTTP: {r.last_mesh_fallback}"
    if chunk_rows:
        assert mesh_chunk.LAST_RUN_INFO["chunked"] is True
    else:
        assert mesh_chunk.LAST_RUN_INFO["chunked"] is False


def test_deadline_preempts_between_chunks(baseline_rows):
    """A wall deadline kills a WARM chunked query at a chunk boundary:
    typed, coded, and WITHOUT falling back to the page plane (the
    pre-PR-10 behavior was to refuse the mesh whenever limits were
    set)."""
    r = mk_runner(mesh_chunk_rows=128)
    assert r.execute(Q_GROUP).rows == baseline_rows["group"]  # warm
    # slow the tracker tick so the chunk-boundary wall check — not the
    # background enforcement thread — is what kills the query
    r.query_tracker.tick_interval_s = 60.0
    r.session.query_max_execution_time_s = 0.05
    with pytest.raises(QueryDeadlineError) as ei:
        r.execute(Q_GROUP)
    msg = str(ei.value)
    assert EXCEEDED_TIME_LIMIT in msg
    assert "mesh chunk" in msg
    assert r.last_mesh_fallback is None, "deadline kill must not fall back"


def test_abandonment_preempts_between_chunks():
    r = mk_runner(mesh_chunk_rows=512)
    r.execute(Q_GROUP)  # warm
    with pytest.raises(QueryAbandonedError, match="abandoned"):
        r.execute(Q_GROUP, cancel=lambda: True)
    assert r.last_mesh_fallback is None


def test_watchdog_falls_back_to_page_plane(baseline_rows):
    """A chunk step slower than stuck_task_interrupt_s raises MeshStuck
    — RETRYABLE, unlike deadline kills — and the coordinator retries
    the query on the page plane: correct answer, reason recorded. The
    property is set after worker construction so the page-plane workers
    keep their 0 (disabled) watchdog."""
    r = mk_runner(mesh_chunk_rows=256)
    r.session.stuck_task_interrupt_s = 1e-9
    before = mesh_plan.MESH_COUNTERS["fallbacks"]
    assert r.execute(Q_GROUP).rows == baseline_rows["group"]
    assert mesh_plan.MESH_COUNTERS["fallbacks"] == before + 1
    assert "stuck" in (r.last_mesh_fallback or "").lower()


def test_second_execution_zero_relowerings(baseline_rows):
    """The program-cache records + deterministic capacity ladder mean a
    repeated chunked query replays entirely from cache: zero new XLA
    lowerings."""
    r = mk_runner(mesh_chunk_rows=512)
    assert r.execute(Q_JOIN).rows == baseline_rows["join"]
    compiles0 = METRICS.snapshot().get("xla_compiles", 0.0)
    assert r.execute(Q_JOIN).rows == baseline_rows["join"]
    delta = METRICS.snapshot().get("xla_compiles", 0.0) - compiles0
    assert delta == 0, f"second execution lowered {delta:g} XLA programs"


def test_chunk_capacity_lands_on_ladder_rung():
    """mesh_chunk_rows is rounded to a capacity-ladder rung so chunk
    programs land on census-predicted shape classes (ladder base 2:
    100 -> 128)."""
    r = mk_runner(mesh_chunk_rows=100)
    r.execute(Q_GROUP)
    assert mesh_chunk.LAST_RUN_INFO["chunk_cap"] == 128


def test_warmup_registration():
    """Successful chunked programs register WarmupEntrys and mark their
    shape classes warm for the compile regime (PR 6)."""
    from trino_tpu.compile.warmup import WARM_CLASSES

    # the registry is the process's: what another file's statements left
    # in it on this worker may have had its classes forgotten since
    # (test_compile_regime and test_fabric reset WARM_CLASSES), so look
    # at what THIS statement's programs register
    with mesh_chunk._warmup_entries_lock:
        del mesh_chunk.MESH_WARMUP_ENTRIES[:]
    r = mk_runner(mesh_chunk_rows=512)
    r.execute(Q_GROUP)
    entries = mesh_chunk.mesh_warmup_entries()
    assert entries, "no mesh WarmupEntrys registered"
    ops = {e.operator for e in entries}
    assert ops <= {"MeshPrelude", "MeshChunkStep", "MeshFlush"}
    assert "MeshChunkStep" in ops
    for e in entries:
        assert e.keys() <= WARM_CLASSES


def test_mid_execution_unsupported_falls_back_observably(
    baseline_rows, monkeypatch
):
    """Regression (PR 10 satellite): a MeshUnsupported raised DURING
    execution used to fall back silently. It must now record the reason
    in QueryInfo, bump the per-reason counter, and drop a mesh_fallback
    instant event on the query span — while still answering via the
    page plane."""
    reason = "synthetic mid-execution refusal"

    def boom(self, preempt=None, query_span=None):
        raise mesh_plan.MeshUnsupported(reason)

    monkeypatch.setattr(mesh_chunk.ChunkedMeshRunner, "run", boom)
    r = mk_runner(query_trace="on")
    before = METRICS.snapshot()
    res = r.execute(Q_JOIN)
    assert res.rows == baseline_rows["join"]
    assert r.last_mesh_fallback == reason
    qi = r.query_info(r.last_query_id)
    assert qi["data_plane"] == "http"
    assert qi["mesh_fallback"] == reason
    after = METRICS.snapshot()
    slug = "mesh_fallbacks.synthetic_mid_execution_refusal"
    assert after.get(slug, 0) == before.get(slug, 0) + 1
    export = r.query_trace_export(r.last_query_id)
    events = [
        e for s in export["spans"] for e in s.get("events", [])
        if e["name"] == "mesh_fallback"
    ]
    assert events and events[0]["attributes"]["reason"] == reason


def test_chunked_span_tree_valid():
    """A chunked mesh query under query_trace=on exports a complete
    span tree: stage/task/operator mesh spans, per-chunk events, and no
    invariant violations."""
    from trino_tpu.runtime.tracing import check_span_invariants

    r = mk_runner(mesh_chunk_rows=512, query_trace="on")
    r.execute(Q_GROUP)
    export = r.query_trace_export(r.last_query_id)
    assert check_span_invariants(export) == []
    names = [s["name"] for s in export["spans"]]
    assert any(n.startswith("stage mesh") for n in names)
    assert any(n.startswith("task mesh") for n in names)
    assert "MeshChunkStep" in names
    chunk_events = [
        e for s in export["spans"] for e in s.get("events", [])
        if e["name"] == "chunk"
    ]
    assert len(chunk_events) >= 2, "expected per-chunk trace events"
