"""Deterministic seeded chaos harness for the cluster resiliency layer.

Analogue of the reference's BaseFailureRecoveryTest matrix run as a
harness instead of hand-written cases: a fixed seed generates a fault
schedule (which partitions crash where, how many exchange fetches drop,
who stalls, who OOMs), the schedule is installed into the shared
FailureInjector, and TPC-H queries run through the fault-tolerant
scheduler. Because every random draw — schedule generation AND the
retry layer's backoff jitter (error_tracker seeds its RNG from the
destination) — is seeded, a failing run replays exactly from its seed.

Fault classes map onto distinct recovery paths:

- task_crash_start: task dies before producing output (clean re-run)
- task_crash_mid:   task dies AFTER its first output page (the
                    partially-spooled path; spool commit manifests keep
                    replayed attempts duplicate-free)
- fetch_loss:       exchange page pulls fail transiently (absorbed by
                    the RequestErrorTracker loop, no task retry at all)
- straggler:        a task stalls; FTE speculation races a duplicate
- oom:              a task raises ExceededMemoryLimitError (memory-
                    classed: the partition memory estimator doubles
                    before re-placement)

Beside the injector schedules stand whole-cluster maneuvers, each a
method or a function here and each called by a tier-1 test: graceful
drains racing a live query and a straggler that speculation must beat
(run_drain_case, run_speculation_case: tests/test_chaos.py), a hung
operator and a vanished client (TIMEBOUND_CLASSES:
tests/test_deadlines.py), and the seeded faults inside the mesh chunk
loop (PREEMPT_CLASSES, FABRIC_CLASSES: tests/test_chaos.py).
"""

from __future__ import annotations

import random
import threading
from trino_tpu.analysis import threadreg
import time
from typing import Dict, List, Optional, Tuple

FAULT_CLASSES = (
    "task_crash_start",
    "task_crash_mid",
    "fetch_loss",
    "straggler",
    "oom",
)

# time-bounding scenarios (PR 4): a hung operator the worker watchdog
# must interrupt (and FTE must retry elsewhere — query still correct),
# and a client that vanishes mid-query (reaper must cancel the query,
# free its resource-group slot, and drain its memory reservations to
# zero). Run via run_hung_operator_case / run_abandoned_client_case.
TIMEBOUND_CLASSES = (
    "hung_operator",
    "abandoned_client",
)

# preemptive multi-tenancy scenarios (PR 18): the chunk-granular mesh
# scheduler (runtime/scheduler.py) under adversity. A fast-lane point
# lookup parks a streaming analytic at a seeded chunk boundary, then a
# device loss lands AFTER the resume — the checkpoint machinery must
# compose with parked state (park -> resume -> fault -> in-run resume,
# all in one run, byte-identical, nothing re-executed). And a replica
# drain surfacing while a query sits PARKED must raise out of the
# parked wait and resume the query from its parked host-portable
# snapshot on the sibling sub-mesh. Run via run_preempt_park_resume_case
# / run_preempt_under_drain_case.
PREEMPT_CLASSES = (
    "preempt_park_resume",
    "preempt_under_drain",
)

# multi-host fabric scenarios (PR 19): the checkpoint transport and
# membership tier (runtime/fabric.py) under adversity. host_lost_mid_
# chunk wipes the local checkpoint store at a seeded boundary (the
# whole "host" dies, not just a sub-mesh) — failover must PULL the last
# pushed snapshot from a fabric peer and resume with zero re-executed
# chunk-steps. membership_flap leaves-and-rejoins the sibling replica
# mid-fault — the membership epoch must advance, a second claim on an
# owned query must be refused (no double placement across epochs), and
# the query still completes oracle-equal. transport_corruption serves
# bit-flipped payloads from the peer — the digest check must reject
# them (fabric.digest_rejects) so failover degrades to a clean restart,
# never a resume from corrupt carries. Run via run_host_lost_case /
# run_membership_flap_case / run_transport_corruption_case.
FABRIC_CLASSES = (
    "host_lost_mid_chunk",
    "membership_flap",
    "transport_corruption",
)


def generate_schedule(
    seed: int,
    fault_class: str,
    n_partitions: int = 2,
    n_rules: int = 2,
    stall_s: float = 1.0,
) -> List[dict]:
    """Deterministic fault schedule: FailureRule kwargs drawn from
    random.Random(seed). Same (seed, fault_class) -> same schedule."""
    if fault_class not in FAULT_CLASSES:
        raise ValueError(f"unknown fault class: {fault_class}")
    rng = random.Random(seed)
    rules: List[dict] = []
    for _ in range(n_rules):
        p = rng.randrange(n_partitions)
        if fault_class == "task_crash_start":
            rules.append(dict(
                where="start", kind="crash", partition=p,
                attempts=(0,), max_hits=1,
            ))
        elif fault_class == "task_crash_mid":
            rules.append(dict(
                where="mid", kind="crash", partition=p,
                attempts=(0,), max_hits=1,
            ))
        elif fault_class == "fetch_loss":
            rules.append(dict(
                where="fetch", kind="fetch_loss", partition=p,
                attempts=(0, 1), max_hits=rng.randint(1, 3),
            ))
        elif fault_class == "straggler":
            # one stall is enough to drive speculation; more would just
            # serialize the test
            if not rules:
                rules.append(dict(
                    where="start", partition=p, attempts=(0,),
                    stall_s=stall_s + rng.random(), max_hits=1,
                ))
        elif fault_class == "oom":
            rules.append(dict(
                where="start", kind="oom", partition=p,
                attempts=(0,), max_hits=1,
            ))
    return rules


def schedule_max_failures(rules: List[dict]) -> int:
    """Upper bound on injected failures a schedule can cause — the
    bounded-attempt assertion compares observed retries against this."""
    return sum(r.get("max_hits", 0) for r in rules if r.get("stall_s", 0) == 0)


def run_preempt_park_resume_case(
    sql: str, seed: int, mesh_chunk_rows: int = 256,
) -> Tuple[List[list], dict]:
    """Park/resume composed with checkpoint recovery in ONE run: a
    fast-lane point lookup arrives at a seeded chunk boundary and parks
    the analytic (device carries snapshot to host, lookup runs, resume
    from chunk k warm); then a MeshDeviceLost lands at a later seeded
    boundary and the run must resume IN-RUN from its last checkpoint.
    Oracle-equal rows, exactly one park/unpark, at least one resume,
    and zero re-executed chunk-steps across the whole maneuver."""
    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.runtime.coordinator import DistributedQueryRunner

    point = (
        "select n_name, r_name from nation join region "
        "on n_regionkey = r_regionkey where n_nationkey = 3"
    )
    runner = DistributedQueryRunner(
        Session(
            catalog="tpch", schema="tiny",
            mesh_chunk_rows=mesh_chunk_rows,
            mesh_checkpoint_interval_chunks=1,
            mesh_resume_attempts=1,
        ),
        n_workers=2, hash_partitions=2,
    )
    runner.register_catalog("tpch", create_tpch_connector())
    expected = runner.execute(sql).rows  # warm run doubles as oracle
    mesh_clean = runner._last_data_plane == "mesh"
    point_expected = runner.execute(point).rows
    rng = random.Random(seed)
    state = {
        "park_target": None, "fault_target": None,
        "parked": 0, "faulted": 0, "point_rows": None,
    }
    case_thread = threading.current_thread()

    def hook(k: int, K: int) -> None:
        if threading.current_thread() is not case_thread:
            return  # the point lookup's own chunk loop
        if state["park_target"] is None:
            # the park lands at park_target+1; the device loss lands
            # strictly after the resume so both maneuvers compose
            state["park_target"] = rng.randrange(max(K - 2, 1))
            state["fault_target"] = (
                state["park_target"] + 1
                + rng.randrange(max(K - state["park_target"] - 2, 1))
            )
        if k == state["park_target"] and not state["parked"]:
            state["parked"] = 1

            def run_point():
                state["point_rows"] = runner.execute(point).rows

            threadreg.spawn("chaos-point-query", run_point, owner="chaos")
            # hold this boundary until the fast seat is queued, so the
            # NEXT boundary deterministically parks
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                sched = runner._mesh_scheduler
                if sched is not None and sched.waiting_count(fast=True):
                    break
                time.sleep(0.002)
            return
        if (
            k == state["fault_target"]
            and state["parked"]
            and not state["faulted"]
        ):
            state["faulted"] = 1
            raise mesh_chunk.MeshDeviceLost(
                f"chaos[preempt_park_resume]: device loss at chunk "
                f"{k}/{K} after the park/resume cycle"
            )

    mesh_chunk.MESH_FAULT_HOOK = hook
    try:
        rows = runner.execute(sql).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    deadline = time.monotonic() + 10.0
    while state["point_rows"] is None and time.monotonic() < deadline:
        time.sleep(0.002)
    info = mesh_chunk.last_run_info()
    report = {
        "mesh_clean_plane": mesh_clean,
        "mesh_fault_plane": runner._last_data_plane,
        "park_chunk": (
            None if state["park_target"] is None
            else state["park_target"] + 1
        ),
        "fault_chunk": state["fault_target"],
        "parked": state["parked"],
        "faulted": state["faulted"],
        "chunks": info.get("chunks"),
        "executed_chunk_steps": info.get("executed_chunk_steps"),
        "parks": info.get("parks"),
        "unparks": info.get("unparks"),
        "resumes": info.get("resumes"),
        "point_ok": state["point_rows"] == point_expected,
        "expected": expected,
    }
    return rows, report


def run_preempt_under_drain_case(
    sql: str, seed: int, mesh_chunk_rows: int = 256,
) -> Tuple[List[list], dict]:
    """A replica drain surfacing while a query sits PARKED: a fast seat
    parks the analytic at a seeded boundary, then the victim replica is
    drained while the query is in the parked wait. The drain must raise
    MeshReplicaDraining OUT of the parked wait, keep the parked
    host-portable snapshot, and resume the query on the sibling
    sub-mesh from exactly the park boundary — oracle-equal, nothing
    re-executed, and the victim quiesces."""
    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.recovery import CHECKPOINTS
    from trino_tpu.runtime.coordinator import DistributedQueryRunner
    from trino_tpu.runtime.metrics import METRICS

    runner = DistributedQueryRunner(
        Session(
            catalog="tpch", schema="tiny",
            mesh_replicas=2,
            mesh_chunk_rows=mesh_chunk_rows,
            mesh_checkpoint_interval_chunks=1,
            mesh_resume_attempts=0,
        ),
        n_workers=2, hash_partitions=2,
    )
    runner.register_catalog("tpch", create_tpch_connector())
    # sequential placements alternate replicas: two rounds warm both
    # sub-meshes, so the sibling resume mints no new lowerings
    expected = runner.execute(sql).rows
    runner.execute(sql)
    mesh_clean = runner._last_data_plane == "mesh"
    rm = runner._replicas
    rng = random.Random(seed)
    state = {
        "target": None, "victim": None, "fake": None,
        "parked": 0, "drained": 0,
    }

    def drain_when_parked(victim: int) -> None:
        vic = rm.replicas[victim]
        parks0 = vic.scheduler.parks
        state["fake"] = vic.scheduler.submit(
            "chaos-fast-seat", fast=True
        )
        # synthetic waiter: never calls acquire, so mark it ready by
        # hand — only ready waiters exert preemption pressure
        state["fake"].ready = True
        deadline = time.monotonic() + 10.0
        while (
            vic.scheduler.parks <= parks0
            and time.monotonic() < deadline
        ):
            time.sleep(0.002)
        if vic.scheduler.parks > parks0:
            state["parked"] = 1
            state["drained"] = 1
            rm.request_drain(victim)

    def hook(k: int, K: int) -> None:
        rep = mesh_chunk.active_replica()
        if rep is None:
            return
        if state["target"] is None:
            state["target"] = rng.randrange(max(K - 2, 1))
        if k == state["target"] and state["victim"] is None:
            state["victim"] = rep
            threadreg.spawn(
                "chaos-drain-when-parked", drain_when_parked, args=(rep,),
                owner="chaos",
            )
            # hold this boundary until the fast seat is queued: the
            # next boundary parks, and the side thread drains the
            # victim while the query sits parked
            vic = rm.replicas[rep]
            deadline = time.monotonic() + 10.0
            while (
                not vic.scheduler.waiting_count(fast=True)
                and time.monotonic() < deadline
            ):
                time.sleep(0.002)

    failovers0 = rm.failovers
    resumed0 = CHECKPOINTS.resumed
    steps0 = METRICS.snapshot().get("mesh.chunk_steps", 0.0)
    mesh_chunk.MESH_FAULT_HOOK = hook
    try:
        rows = runner.execute(sql).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
        if state["fake"] is not None and state["victim"] is not None:
            rm.replicas[state["victim"]].scheduler.finish(state["fake"])
    info = mesh_chunk.last_run_info()
    quiesced = bool(
        state["drained"]
        and state["victim"] is not None
        and rm.drain(state["victim"], timeout_s=30.0)
    )
    if quiesced:
        rm.undrain(state["victim"])
    report = {
        "mesh_clean_plane": mesh_clean,
        "mesh_fault_plane": runner._last_data_plane,
        "park_chunk": (
            None if state["target"] is None else state["target"] + 1
        ),
        "parked": state["parked"],
        "drain_requested": state["drained"],
        "replica_drained": quiesced,
        "failovers": rm.failovers - failovers0,
        "checkpoint_resumes": CHECKPOINTS.resumed - resumed0,
        "chunks": info.get("chunks"),
        "resumed_from_chunk": info.get("resumed_from_chunk"),
        "chunk_steps": int(
            METRICS.snapshot().get("mesh.chunk_steps", 0.0) - steps0
        ),
        "expected": expected,
    }
    return rows, report


def _fabric_case_runner(srv_uri: str, mesh_chunk_rows: int,
                        resume_attempts: int = 1):
    """Replicated runner whose session attaches the checkpoint fabric
    to one peer endpoint (the chaos cases' simulated surviving host)."""
    from trino_tpu.connectors.tpch import create_tpch_connector
    from trino_tpu.engine import Session
    from trino_tpu.runtime.coordinator import DistributedQueryRunner

    runner = DistributedQueryRunner(
        Session(
            catalog="tpch", schema="tiny",
            mesh_chunk_rows=mesh_chunk_rows,
            mesh_checkpoint_interval_chunks=1,
            mesh_replicas=2,
            mesh_resume_attempts=resume_attempts,
            fabric_peers=srv_uri,
        ),
        n_workers=2, hash_partitions=2,
    )
    runner.register_catalog("tpch", create_tpch_connector())
    return runner


def run_host_lost_case(
    sql: str, seed: int, mesh_chunk_rows: int = 256,
) -> Tuple[List[list], dict]:
    """Hard host loss mid-chunk with the fabric attached: at a seeded
    boundary the LOCAL checkpoint store is wiped (the host's memory
    died with it) and the active sub-mesh raises MeshDeviceLost. The
    coordinator's failover must find the local store empty, PULL the
    last pushed snapshot from the fabric peer, and resume the query on
    the sibling from exactly the fault boundary — oracle-equal with
    zero re-executed chunk-steps."""
    import os

    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.recovery.checkpoint import (
        CHECKPOINTS,
        MeshCheckpointStore,
    )
    from trino_tpu.runtime.fabric import (
        HostFabric,
        active_fabric,
        stop_fabric,
    )
    from trino_tpu.runtime.http import FabricServer
    from trino_tpu.runtime.metrics import METRICS

    secret = os.environ.setdefault(
        "TRINO_TPU_INTERNAL_SECRET", "chaos-fabric"
    )
    peer_store = MeshCheckpointStore()
    peer = HostFabric(store=peer_store, host_id="chaos-peer")
    srv = FabricServer(peer, internal_secret=secret)
    stop_fabric()  # fresh attachment: the session below re-binds it
    runner = _fabric_case_runner(srv.uri, mesh_chunk_rows)
    try:
        expected = runner.execute(sql).rows  # warm run doubles as oracle
        mesh_clean = runner._last_data_plane == "mesh"
        rng = random.Random(seed)
        state = {"target": None, "fired": 0}

        def hook(k: int, K: int) -> None:
            if state["target"] is None:
                state["target"] = 1 + rng.randrange(max(K - 1, 1))
            if k == state["target"] and not state["fired"]:
                state["fired"] = 1
                fab = active_fabric()
                if fab is not None:
                    # the host's last push must be on the wire before
                    # it dies — the smoke's victim does the same flush
                    fab.pusher.flush(10.0)
                CHECKPOINTS.clear()  # the store dies with the host
                raise mesh_chunk.MeshDeviceLost(
                    f"chaos[host_lost_mid_chunk]: host lost at "
                    f"chunk {k}/{K}"
                )

        before = METRICS.snapshot()
        mesh_chunk.MESH_FAULT_HOOK = hook
        try:
            rows = runner.execute(sql).rows
        finally:
            mesh_chunk.MESH_FAULT_HOOK = None
        after = METRICS.snapshot()
        info = mesh_chunk.last_run_info()
        report = {
            "mesh_clean_plane": mesh_clean,
            "mesh_fault_plane": runner._last_data_plane,
            "fault_chunk": state["target"],
            "fired": state["fired"],
            "chunks": info.get("chunks"),
            "executed_chunk_steps": info.get("executed_chunk_steps"),
            "resumes": info.get("resumes"),
            "resumed_from_chunk": info.get("resumed_from_chunk"),
            "pushes": int(
                after.get("fabric.pushes", 0) - before.get("fabric.pushes", 0)
            ),
            "pulls": int(
                after.get("fabric.pulls", 0) - before.get("fabric.pulls", 0)
            ),
            "peer_served": peer.served,
            "expected": expected,
        }
        return rows, report
    finally:
        stop_fabric()
        srv.stop()


def run_membership_flap_case(
    sql: str, seed: int, mesh_chunk_rows: int = 256,
) -> Tuple[List[list], dict]:
    """A membership flap racing a failover: at a seeded boundary the
    SIBLING replica leaves and immediately rejoins (epoch advances
    twice), a second claim on the in-flight query is attempted and must
    be REFUSED (exactly one owner per query, across epochs), then the
    active sub-mesh dies. Failover lands on the freshly rejoined
    sibling — whose join epoch matches the post-flap fault epoch, so
    the resume proceeds from checkpoint — and the query completes
    oracle-equal with the ownership map drained."""
    from trino_tpu.parallel import mesh_chunk

    runner = _fabric_case_runner("", mesh_chunk_rows, resume_attempts=0)
    rm = runner._replica_manager()
    expected = runner.execute(sql).rows
    mesh_clean = runner._last_data_plane == "mesh"
    rng = random.Random(seed)
    epoch0 = rm.membership_epoch
    state = {
        "target": None, "fired": 0, "flapped": 0, "double_refused": -1,
    }

    def hook(k: int, K: int) -> None:
        if state["target"] is None:
            state["target"] = 1 + rng.randrange(max(K - 1, 1))
        if k == state["target"] and not state["fired"]:
            state["fired"] = 1
            owners = dict(rm._owners)
            if owners:
                qid, (rid, _ep) = next(iter(owners.items()))
                sib = rm.replicas[1 - rid]
                state["double_refused"] = int(not rm.claim(qid, sib))
            sib_id = 1 - (mesh_chunk.active_replica() or 0)
            rm.leave(sib_id)
            rm.join(sib_id)
            state["flapped"] = 1
            raise mesh_chunk.MeshDeviceLost(
                f"chaos[membership_flap]: sub-mesh lost at chunk {k}/{K} "
                f"with replica {sib_id} mid-flap"
            )

    mesh_chunk.MESH_FAULT_HOOK = hook
    try:
        rows = runner.execute(sql).rows
    finally:
        mesh_chunk.MESH_FAULT_HOOK = None
    info = mesh_chunk.last_run_info()
    report = {
        "mesh_clean_plane": mesh_clean,
        "mesh_fault_plane": runner._last_data_plane,
        "fault_chunk": state["target"],
        "fired": state["fired"],
        "flapped": state["flapped"],
        "double_refused": state["double_refused"],
        "epoch_delta": rm.membership_epoch - epoch0,
        "joins": rm.joins,
        "leaves": rm.leaves,
        "epoch_fences": rm.epoch_fences,
        "owners_at_end": len(rm._owners),
        "chunks": info.get("chunks"),
        "executed_chunk_steps": info.get("executed_chunk_steps"),
        "resumes": info.get("resumes"),
        "expected": expected,
    }
    return rows, report


def run_transport_corruption_case(
    sql: str, seed: int, mesh_chunk_rows: int = 256,
) -> Tuple[List[list], dict]:
    """Transport corruption on the failover pull: the peer serves a
    BIT-FLIPPED payload under the original digest (in-flight
    corruption). The digest check must reject it (fabric.digest_rejects
    grows, fabric.pulls does not), try_pull returns False, and the
    failover degrades to a CLEAN restart on the sibling — oracle-equal
    rows, never a resume from corrupt carries. A truncated payload with
    a matching digest is also pushed at the receive side and must come
    back `imported: False` (undecodable bytes never poison a store)."""
    import os

    from trino_tpu.parallel import mesh_chunk
    from trino_tpu.recovery.checkpoint import (
        CHECKPOINTS,
        MeshCheckpointStore,
    )
    from trino_tpu.runtime.fabric import (
        HostFabric,
        active_fabric,
        checkpoint_digest,
        encode_key,
        stop_fabric,
    )
    from trino_tpu.runtime.http import FabricServer
    from trino_tpu.runtime.metrics import METRICS

    class _CorruptingFabric(HostFabric):
        def serve_checkpoint(self, ekey):
            out = HostFabric.serve_checkpoint(self, ekey)
            if out is None:
                return None
            data, digest = out
            bad = bytearray(data)
            bad[len(bad) // 2] ^= 0xFF
            return bytes(bad), digest  # digest of the ORIGINAL bytes

    secret = os.environ.setdefault(
        "TRINO_TPU_INTERNAL_SECRET", "chaos-fabric"
    )
    peer_store = MeshCheckpointStore()
    peer = _CorruptingFabric(store=peer_store, host_id="chaos-corrupt")
    srv = FabricServer(peer, internal_secret=secret)
    stop_fabric()
    runner = _fabric_case_runner(srv.uri, mesh_chunk_rows)
    try:
        expected = runner.execute(sql).rows
        mesh_clean = runner._last_data_plane == "mesh"
        rng = random.Random(seed)
        state = {"target": None, "fired": 0, "truncated_import": None}

        def hook(k: int, K: int) -> None:
            if state["target"] is None:
                state["target"] = 1 + rng.randrange(max(K - 1, 1))
            if k == state["target"] and not state["fired"]:
                state["fired"] = 1
                fab = active_fabric()
                if fab is not None:
                    fab.pusher.flush(10.0)
                # receive-side truncation probe while the peer holds a
                # live entry: decodes to garbage -> imported False
                for key in list(peer_store._entries):
                    data = peer_store.export_bytes(key)
                    if data is None:
                        continue
                    cut = data[: len(data) // 2]
                    r = peer.receive_checkpoint(
                        encode_key(key), cut, checkpoint_digest(cut)
                    )
                    state["truncated_import"] = r.get("imported")
                    break
                CHECKPOINTS.clear()
                raise mesh_chunk.MeshDeviceLost(
                    f"chaos[transport_corruption]: host lost at "
                    f"chunk {k}/{K}; peer payloads corrupt"
                )

        before = METRICS.snapshot()
        mesh_chunk.MESH_FAULT_HOOK = hook
        try:
            rows = runner.execute(sql).rows
        finally:
            mesh_chunk.MESH_FAULT_HOOK = None
        after = METRICS.snapshot()
        info = mesh_chunk.last_run_info()
        report = {
            "mesh_clean_plane": mesh_clean,
            "mesh_fault_plane": runner._last_data_plane,
            "fault_chunk": state["target"],
            "fired": state["fired"],
            "truncated_import": state["truncated_import"],
            "chunks": info.get("chunks"),
            "executed_chunk_steps": info.get("executed_chunk_steps"),
            "resumes": info.get("resumes"),
            "digest_rejects": int(
                after.get("fabric.digest_rejects", 0)
                - before.get("fabric.digest_rejects", 0)
            ),
            "pulls": int(
                after.get("fabric.pulls", 0) - before.get("fabric.pulls", 0)
            ),
            "expected": expected,
        }
        return rows, report
    finally:
        stop_fabric()
        srv.stop()


class DownableWorker:
    """Proxy handle that can be taken down (every call raises
    ConnectionError) and counts launches — the graylist assertions need
    'zero create_task calls while the breaker is open', and the drain
    assertions need 'zero ACCEPTED launches after the drain landed'
    (accepted_creates is bumped only after the worker took the task, so
    it structurally cannot grow once the worker's state flipped to
    shutting_down — a racing create raises instead)."""

    def __init__(self, inner):
        self._inner = inner
        self.worker_id = inner.worker_id
        self.down = False
        self.create_calls = 0
        self.accepted_creates = 0

    def _check(self) -> None:
        if self.down:
            raise ConnectionError(f"worker {self.worker_id} is down")

    def create_task(self, spec):
        self.create_calls += 1
        self._check()
        out = self._inner.create_task(spec)
        self.accepted_creates += 1
        return out

    def task_state(self, task_id) -> dict:
        self._check()
        return self._inner.task_state(task_id)

    def get_results(self, task_id, partition, token,
                    max_pages=16, wait=0.0):
        self._check()
        return self._inner.get_results(
            task_id, partition, token, max_pages, wait
        )

    def remove_task(self, task_id) -> None:
        self._check()
        self._inner.remove_task(task_id)

    def results_location(self, task_id):
        return self._inner.results_location(task_id)

    def status(self) -> dict:
        self._check()
        return self._inner.status()

    def fail_query(self, query_id, message) -> None:
        self._check()
        self._inner.fail_query(query_id, message)

    def shutdown_gracefully(self) -> None:
        # drain must go through even on a flaky node — request_drain
        # treats delivery as best-effort anyway
        self._inner.shutdown_gracefully()

    # -- stuck-task watchdog passthrough (PR 4 timebound cases) --
    def watchdog_once(self, now=None):
        return self._inner.watchdog_once(now)

    def start_watchdog(self, poll_s: float = 0.01) -> None:
        self._inner.start_watchdog(poll_s)

    def stop_watchdog(self) -> None:
        self._inner.stop_watchdog()

    @property
    def watchdog_interrupts(self):
        return self._inner.watchdog_interrupts

    @property
    def state(self):
        return getattr(self._inner, "state", "active")

    @property
    def memory_pool(self):
        return getattr(self._inner, "memory_pool", None)


def _norm_rows(rows: List[list]) -> List[tuple]:
    """Comparable row form: floats rounded so recomputation noise (a
    retried attempt re-reduces in a different order) doesn't read as
    corruption."""
    out = []
    for r in rows:
        out.append(tuple(
            round(v, 6) if isinstance(v, float) else v for v in r
        ))
    return out


def rows_equal(a: List[list], b: List[list], ordered: bool = False) -> bool:
    na, nb = _norm_rows(a), _norm_rows(b)
    if ordered:
        return na == nb
    key = repr
    return sorted(na, key=key) == sorted(nb, key=key)


class ChaosHarness:
    """One FTE cluster with a shared FailureInjector: run queries under
    generated fault schedules and compare against a clean run.

    The harness owns N in-process workers behind the coordinator's
    worker_handles path (the FTE topology tests use), a NodeManager with
    circuit breakers, and the spooling exchange. `run_case` returns
    (rows, stats) where stats carries the FTE retry counters for
    bounded-attempt assertions.
    """

    def __init__(
        self,
        n_workers: int = 2,
        session=None,
        catalogs: Optional[Dict[str, object]] = None,
        hash_partitions: int = 2,
        memory_pool_bytes: Optional[int] = None,
        stuck_task_interrupt_s: Optional[float] = None,
        stuck_task_interrupt_warm_s: Optional[float] = None,
    ):
        from trino_tpu.engine import Session
        from trino_tpu.runtime.coordinator import DistributedQueryRunner
        from trino_tpu.runtime.failure import FailureInjector
        from trino_tpu.runtime.worker import Worker

        self.injector = FailureInjector()
        self.session = session or Session(
            catalog="tpch", schema="tiny", retry_policy="task"
        )
        from trino_tpu.connectors.spi import CatalogManager

        self._catalogs = CatalogManager()
        # every worker sits behind a DownableWorker proxy so lifecycle
        # cases can count ACCEPTED launches (drain assertions) and take
        # nodes dark (graylist assertions) without touching the engine
        self.workers = [
            DownableWorker(Worker(
                f"chaos-w{i}", self._catalogs,
                failure_injector=self.injector,
                memory_pool_bytes=memory_pool_bytes,
                stuck_task_interrupt_s=stuck_task_interrupt_s,
                stuck_task_interrupt_warm_s=stuck_task_interrupt_warm_s,
            ))
            for i in range(n_workers)
        ]
        # NOTE: workers carry the watchdog threshold but it is NOT
        # armed here — run_hung_operator_case arms it around its own
        # execution, after a warm run has compiled every jit shape the
        # plan needs. Armed from birth, the watchdog would kill healthy
        # COLD tasks (first-use XLA compilation and connector data
        # generation happen inside one batch and dwarf any test-speed
        # threshold), and each retry would re-block on the same warm-up.
        self.stuck_task_interrupt_s = stuck_task_interrupt_s
        self.runner = DistributedQueryRunner(
            self.session,
            worker_handles=self.workers,
            hash_partitions=hash_partitions,
        )
        for name, conn in (catalogs or {}).items():
            self.register_catalog(name, conn)

    def register_catalog(self, name: str, connector) -> None:
        # planner-side AND worker-side (worker_handles topologies load
        # catalogs per node, as the reference does)
        self.runner.register_catalog(name, connector)
        self._catalogs.register(name, connector)

    def run_clean(self, sql: str) -> List[list]:
        self.injector.clear()
        return self.runner.execute(sql).rows

    def run_case(
        self, sql: str, fault_class: str, seed: int,
        n_partitions: int = 2,
    ) -> Tuple[List[list], dict]:
        """Run one query under one generated fault schedule."""
        rules = generate_schedule(seed, fault_class, n_partitions)
        self.injector.clear()
        for r in rules:
            self.injector.inject(**r)
        try:
            rows = self.runner.execute(sql).rows
        finally:
            self.injector.clear()
        stats = dict(self.runner.last_fte_stats or {})
        stats["max_injected_failures"] = schedule_max_failures(rules)
        stats["breakers"] = self.runner.node_manager.breaker_states()
        return rows, stats

    # -- cluster-lifecycle scenarios (graceful drain + speculation) --

    def run_drain_case(
        self, sql: str, seed: int = 0, drain_all_but_one: bool = False,
        stall_s: float = 0.8, drain_timeout_s: float = 60.0,
    ) -> Tuple[List[list], dict]:
        """Gracefully drain worker(s) while `sql` is mid-flight.

        Every first attempt is stretched by `stall_s` so the drain is
        guaranteed to land on a node with running tasks. Returns (rows,
        report); report carries per-victim drain verdicts plus the
        accepted-launch counter at drain time vs end of query — equal
        counters prove the drained node took ZERO post-drain launches.
        """
        rng = random.Random(seed)
        self.injector.clear()
        self.injector.inject(
            where="start", attempts=(0,), stall_s=stall_s,
            max_hits=4 * len(self.workers),
        )
        result: dict = {}

        def run():
            try:
                result["rows"] = self.runner.execute(sql).rows
            except Exception as e:
                result["error"] = e

        t = threadreg.spawn("chaos-query-driver", run, owner="chaos")
        # drain a node that ACTUALLY hosts work: wait for launches
        deadline = time.monotonic() + 10.0
        busy: List[DownableWorker] = []
        while time.monotonic() < deadline and t.is_alive():
            busy = [w for w in self.workers if w.accepted_creates > 0]
            if busy:
                break
            time.sleep(0.002)
        if drain_all_but_one:
            victims = self.workers[:-1]
        else:
            victims = [busy[rng.randrange(len(busy))] if busy
                       else self.workers[0]]
        drained: Dict[str, bool] = {}
        at_drain: Dict[str, int] = {}
        for v in victims:
            drained[v.worker_id] = self.runner.drain(
                v.worker_id, timeout_s=drain_timeout_s
            )
            at_drain[v.worker_id] = v.accepted_creates
        t.join(120.0)
        self.injector.clear()
        if "error" in result:
            raise result["error"]
        report = dict(self.runner.last_fte_stats or {})
        report.update(
            drained=drained,
            launches_at_drain=at_drain,
            launches_at_end={
                v.worker_id: v.accepted_creates for v in victims
            },
            node_states=self.runner.node_manager.all_states(),
        )
        return result.get("rows"), report

    def run_speculation_case(
        self, sql: str, seed: int = 0, stall_s: float = 6.0,
    ) -> Tuple[List[list], dict]:
        """One partition's first attempt stalls hard; the speculative
        duplicate on a spare worker must commit first (stats carry
        speculation_wins/losses and attempts_per_partition).

        stall_s must comfortably exceed the query's REAL per-task wall
        time: the trigger is `age > STRAGGLER_WALL_MULTIPLE * median`, and
        a stalled attempt's age only reaches `stall + wall`, so a stall
        close to the task wall never crosses 2x median and the scenario
        silently degrades to a plain wait. The duplicate wins and
        cancels the stalled loser cooperatively, so a healthy run never
        waits out the full stall."""
        rng = random.Random(seed)
        self.injector.clear()
        # pin the stall to fragment 0 (the leaf stage, one task per
        # worker): speculation needs sibling attempts to commit first so
        # a median exists — a stall on a single-task fragment can never
        # speculate and the scenario would silently degrade to a wait
        self.injector.inject(
            where="start", fragment_id=0, partition=rng.randrange(2),
            attempts=(0,), stall_s=stall_s, max_hits=1,
        )
        try:
            rows = self.runner.execute(sql).rows
        finally:
            self.injector.clear()
        return rows, dict(self.runner.last_fte_stats or {})

    # -- time-bounding scenarios (watchdog + client-abandonment reaper) --

    def run_hung_operator_case(
        self, sql: str, seed: int = 0, stall_s: float = 8.0,
    ) -> Tuple[List[list], dict]:
        """One leaf task WEDGES mid-batch (a hung operator, not a slow
        one: its heartbeat goes stale, where a straggler's keeps
        ticking). The worker watchdog must interrupt it with a
        diagnostic naming the stuck operator; the failure is retryable,
        so FTE re-runs the partition (attempt 1 matches no rule) and the
        query completes correctly — in far less wall time than the
        stall, which is the no-query-may-hang-the-cluster property.

        The conservative threshold must comfortably exceed a cold
        task's honest silence: a fresh shape triggers an XLA lowering
        burst (~0.3s on CPU) INSIDE one operator call, and retries
        perturb batch capacities (dynamic-filter pruning differs per
        surviving attempt) so no warm run covers every shape. But
        operator-internal heartbeats (InstrumentedOperator._beat fires
        at entry AND exit of every add_input/get_output/finish, always
        on since exec/stats.py instrumentation became unconditional)
        mean a WARM task's longest honest silence is one operator call,
        not one batch — so stuck_task_interrupt_warm_s can run at a few
        hundred ms where the old batch-granular beats needed ~1s+."""
        rng = random.Random(seed)
        # warm run first: compiles every jit shape this plan touches, so
        # once the watchdog arms, the only task that can miss a
        # heartbeat for stuck_task_interrupt_s is the genuinely wedged
        # one (a cold compile inside one batch looks identical to a
        # hang at batch granularity). Its duration is the honest-work
        # baseline: the un-wedged proof is elapsed - warm < stall (the
        # injected stall abort-polls, so a killed task wakes early and
        # only a BROKEN watchdog ever waits out the full stall)
        t_warm = time.monotonic()
        self.run_clean(sql)
        warm_clean_s = time.monotonic() - t_warm
        self.injector.inject(
            where="batch", fragment_id=0, partition=rng.randrange(2),
            attempts=(0,), stall_s=stall_s, max_hits=1,
        )
        # speculation would race the watchdog to the rescue (a duplicate
        # attempt commits and cancels the wedged loser) — turn it off so
        # THIS case proves the watchdog path alone unhangs the query
        was_spec = self.session.speculation_enabled
        self.session.speculation_enabled = False
        for w in self.workers:
            w.start_watchdog()
        t0 = time.monotonic()
        try:
            rows = self.runner.execute(sql).rows
        finally:
            for w in self.workers:
                w.stop_watchdog()
            self.session.speculation_enabled = was_spec
            self.injector.clear()
        report = dict(self.runner.last_fte_stats or {})
        report["elapsed_s"] = time.monotonic() - t0
        report["warm_clean_s"] = warm_clean_s
        report["stall_s"] = stall_s
        report["watchdog_interrupts"] = [
            d for w in self.workers for _, d in w.watchdog_interrupts
        ]
        return rows, report

    def run_abandoned_client_case(
        self, sql: str, seed: int = 0, stall_s: float = 4.0,
        client_timeout_s: float = 0.2,
    ) -> Tuple[Optional[List[list]], dict]:
        """Submit through the HTTP server's job path, then VANISH —
        never poll the results page. The reaper must notice within
        client_timeout_s, cancel the query (the runner's `cancel` hook
        unwinds every running task), release the resource-group slot,
        and drain the query's memory reservations back to zero. The
        injected batch stall keeps the query mid-flight (with pages in
        memory) when abandonment lands; it abort-polls, so teardown
        never waits out the full stall."""
        from trino_tpu.runtime.resource_groups import (
            ResourceGroupManager,
            ResourceGroupSpec,
        )
        from trino_tpu.runtime.server import CoordinatorServer

        rg = ResourceGroupManager(
            ResourceGroupSpec("global", max_concurrency=4)
        )
        self.injector.clear()
        self.injector.inject(
            where="batch", attempts=(0,), stall_s=stall_s,
            max_hits=1_000,
        )
        server = CoordinatorServer(
            self.runner,
            resource_groups=rg,
            client_timeout_s=client_timeout_s,
            reap_interval_s=0.05,
        )

        def ledgers() -> Dict[str, Dict[str, int]]:
            return {
                w.worker_id: dict(w.memory_pool.query_reservations())
                for w in self.workers
                if w.memory_pool is not None
            }

        try:
            job = server._submit(sql)
            peak_reserved = 0
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                peak_reserved = max(
                    peak_reserved,
                    sum(sum(l.values()) for l in ledgers().values()),
                )
                if (
                    job.finished_at is not None
                    and rg.total_running() == 0
                    and all(not l for l in ledgers().values())
                ):
                    break
                time.sleep(0.01)
            report = {
                "reaped": job.state == "failed"
                and "abandoned" in (job.error or "").lower(),
                "error": job.error,
                "rg_running": rg.total_running(),
                "ledgers": ledgers(),
                "peak_reserved_bytes": peak_reserved,
            }
            return None, report
        finally:
            self.injector.clear()
            server.stop()
