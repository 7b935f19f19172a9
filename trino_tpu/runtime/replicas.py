"""Replicated serving meshes: carve the device set into sub-meshes.

The mesh plane (parallel/mesh_plan.py) runs one named-axis mesh over
every visible device — one fault domain, one queue. This module is the
GSPMD scale-out half (SNIPPETS [3]: "from 8-chip pods to 6000-chip
superclusters without changing application code"): the device set
becomes a 2-D `replica` x `partition` grid, each row an identical
sub-mesh running the SAME prelude/step/flush `jit(shard_map)` programs
unchanged — the programs only ever see their row's 1-D `shard` axis.

The ReplicaManager is the coordinator's placement layer over that grid:

- **health**: each replica carries a CircuitBreaker (the per-node
  graylist of runtime/discovery.py, applied to a fault domain instead
  of a worker). Mesh-run failures trip it; a later success closes it;
  an open breaker sits out `cooldown_s` before a half-open probe
  placement may try the replica again.
- **placement**: `place()` picks the least-loaded healthy replica
  (round-robin on ties), so admission lanes spread across sub-meshes.
  Plan/program caches are process-global, so a query landing on any
  replica reuses warm rungs — each replica pays its own device-set
  lowering once, then stays warm. A sub-mesh executes ONE mesh program
  at a time (interleaved collectives from two programs on one device
  set deadlock their rendezvous), so replicas are also the serving
  tier's units of mesh concurrency.
- **lifecycle**: `request_drain` flips a replica to shutting_down; new
  placements skip it immediately and its in-flight chunk loops raise
  MeshReplicaDraining at the next boundary, handing the query to the
  coordinator's failover dispatch.
- **failover**: the dying replica's chunked queries resume on a sibling
  from the host-portable checkpoint store (recovery/checkpoint.py) —
  keyed by program identity minus device identity, so the sibling's
  ChunkedMeshRunner finds the snapshot as its own.

Multi-host: `maybe_initialize_distributed()` joins the jax.distributed
pod when the standard coordinator env vars are present; single-process
runs (tests, CPU CI) skip it entirely.
"""

from __future__ import annotations

import os
import threading
from trino_tpu.analysis.witness import named_condition, named_lock, named_rlock
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from trino_tpu.runtime.discovery import CircuitBreaker

# /v1/metrics counter names (registered at zero by
# register_replica_metrics so the surface is visible before the first
# replica event — same protocol as the recovery counters)
PLACEMENTS = "replica.placements"
FAILOVERS = "replica.failovers"
DRAINS = "replica.drains"
BREAKER_OPENS = "replica.breaker_opens"

_COUNTERS = (PLACEMENTS, FAILOVERS, DRAINS, BREAKER_OPENS)

_DISTRIBUTED_INITIALIZED = False


def register_replica_metrics() -> None:
    from trino_tpu.runtime.metrics import METRICS

    for name in _COUNTERS:
        METRICS.increment(name, 0.0)


def maybe_initialize_distributed() -> bool:
    """Join the jax.distributed pod when launched under a multi-host
    coordinator (JAX_COORDINATOR_ADDRESS + process env, the standard
    jax.distributed.initialize() auto-detection inputs). Idempotent and
    deliberately quiet on single-process runs: the CPU CI mesh and
    every test build replicas out of the local device set alone."""
    global _DISTRIBUTED_INITIALIZED
    if _DISTRIBUTED_INITIALIZED:
        return True
    if not os.environ.get("JAX_COORDINATOR_ADDRESS"):
        return False
    try:
        import jax

        jax.distributed.initialize()
        _DISTRIBUTED_INITIALIZED = True
        return True
    except Exception:
        return False


class Replica:
    """One sub-mesh row of the replica x partition grid: its device
    slice, breaker-tracked health, lifecycle state and live depth."""

    def __init__(self, replica_id: int, devices: Sequence,
                 breaker: CircuitBreaker, scheduler_kw=None):
        self.replica_id = replica_id
        self.devices = list(devices)
        self.breaker = breaker
        # a sub-mesh is a single-program resource: two chunk loops
        # interleaving collectives on the SAME device set deadlock the
        # cross-module rendezvous (each program's AllToAll waits for
        # participants the other program occupies). Mesh runs serialize
        # on the seat of this run queue (runtime/scheduler.py), one per
        # replica — REPLICAS are the serving tier's units of mesh
        # concurrency, not threads on one mesh. The holder's chunk loop
        # consults the scheduler at every boundary, so fast-lane
        # arrivals preempt (park) the running analytic instead of
        # queueing behind its whole run.
        from trino_tpu.runtime.scheduler import MeshScheduler

        self.scheduler = MeshScheduler(
            name=f"replica-{replica_id}", **(scheduler_kw or {})
        )
        # active -> shutting_down (drain requested: no new placements,
        # in-flight chunk loops fail over at the next boundary) ->
        # drained (nothing in flight; decommissionable). "left" is the
        # heartbeat tier's verdict (host lost / flapped — see
        # ReplicaManager.leave): out of the placement pool like a
        # drain, but recoverable through join() under a new epoch.
        self.state = "active"
        # membership epoch this replica (re)joined under; a rejoin
        # after a flap moves it, which is what fences stale resumes
        self.join_epoch = 0
        self.inflight = 0
        self.served = 0  # lifetime placements onto this replica


class ReplicaManager:
    """Placement + health + failover bookkeeping over N identical
    sub-meshes. Counters are INSTANCE-scoped (deterministic per runner,
    the EXPLAIN `replicas=` line reads them) and mirrored into the
    process-global METRICS registry for /v1/metrics."""

    def __init__(self, n_replicas: int, devices=None,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 1.0,
                 scheduler_kw=None):
        import jax

        maybe_initialize_distributed()
        devs = list(devices) if devices is not None else list(jax.devices())
        if n_replicas < 1:
            raise ValueError(f"mesh_replicas must be >= 1, got {n_replicas}")
        per = len(devs) // n_replicas
        if per < 1:
            raise ValueError(
                f"mesh_replicas={n_replicas} needs at least one device "
                f"per replica ({len(devs)} visible)"
            )
        # the 2-D replica x partition grid; row r is replica r's
        # sub-mesh. Leftover devices (len % n) stay out of the grid so
        # every replica is identical — identical widths are what make
        # checkpoints portable between them (carry shapes are (n*cap,))
        self.grid = np.array(devs[: n_replicas * per]).reshape(
            n_replicas, per
        )
        self.n_replicas = n_replicas
        self.partition_width = per
        self._lock = named_lock("ReplicaManager._lock")
        self._rr = 0  # guarded_by: _lock — round-robin tiebreak cursor
        self.placements = 0
        self.failovers = 0
        self.drains = 0
        self.breaker_opens = 0
        # -- live membership (runtime/fabric.py drives this) ----------
        # monotonic: every join or leave advances it; resumes carry the
        # epoch their checkpoint context was taken under and
        # require_epoch fences the ones whose target moved on
        self.membership_epoch = 1
        self.joins = 0
        self.leaves = 0
        self.epoch_fences = 0
        # exactly-one-owner ledger: query_id -> (replica_id, epoch) of
        # the single replica allowed to run it right now — a flapped
        # host must never end up racing the sibling that took over
        self._owners: Dict[str, tuple] = {}  # guarded_by: _lock
        self.replicas = [
            Replica(
                r, list(self.grid[r]),
                CircuitBreaker(
                    breaker_threshold, breaker_cooldown_s,
                    on_open=self._on_breaker_open,
                ),
                scheduler_kw=scheduler_kw,
            )
            for r in range(n_replicas)
        ]
        for rep in self.replicas:
            rep.join_epoch = self.membership_epoch
        register_replica_metrics()
        from trino_tpu.runtime.fabric import register_fabric_metrics

        register_fabric_metrics()
        from trino_tpu.runtime.metrics import METRICS

        for rep in self.replicas:
            METRICS.register_gauge(
                f"replica.{rep.replica_id}.queue_depth",
                lambda rep=rep: float(rep.inflight),
            )

    def _on_breaker_open(self) -> None:
        from trino_tpu.runtime.metrics import METRICS

        self.breaker_opens += 1
        METRICS.increment(BREAKER_OPENS)

    def global_mesh(self):
        """The full 2-D named-axis view (`replica` x `partition`-as-
        `shard`) — what a pod-wide collective would address. Sub-mesh
        programs never see it; it exists so the grid carving is
        expressible as one jax Mesh."""
        from jax.sharding import Mesh

        from trino_tpu.parallel.mesh_plan import AXIS, REPLICA_AXIS

        return Mesh(self.grid, (REPLICA_AXIS, AXIS))

    # -- placement ----------------------------------------------------
    def _candidates(self, exclude) -> List[Replica]:
        """Healthy first (active + breaker closed), then cooled-down
        half-open probes, then any active replica — degrade rather than
        refuse, mirroring the coordinator's _schedulable_workers."""
        active = [
            r for r in self.replicas
            if r.state == "active" and r.replica_id not in exclude
        ]
        for r in active:
            r.breaker.mark_probing()
        closed = [r for r in active if not r.breaker.is_open]
        if closed:
            return closed
        probing = [r for r in active if r.breaker.state == "half_open"]
        return probing or active

    def place(self, exclude=()) -> Optional[Replica]:
        """Pick the least-loaded healthy replica not in `exclude` (the
        failover loop excludes replicas it already tried this query).
        None when every replica is excluded or draining — the caller
        falls back to the page plane. Bumps the placement counters and
        the replica's depth; callers MUST release() in a finally."""
        from trino_tpu.runtime.metrics import METRICS

        with self._lock:
            cands = self._candidates(set(exclude))
            if not cands:
                return None
            depth = min(r.inflight for r in cands)
            tied = [r for r in cands if r.inflight == depth]
            rep = tied[self._rr % len(tied)]
            self._rr += 1
            rep.inflight += 1
            rep.served += 1
            self.placements += 1
        METRICS.increment(PLACEMENTS)
        return rep

    def release(self, replica: Replica) -> None:
        with self._lock:
            replica.inflight = max(0, replica.inflight - 1)

    def note_failover(self, from_replica: Replica,
                      to_replica: Optional[Replica] = None) -> None:
        from trino_tpu.runtime.metrics import METRICS

        with self._lock:
            self.failovers += 1
        METRICS.increment(FAILOVERS)

    # -- health (error-tracker listener shape, per fault domain) ------
    def report_failure(self, replica: Replica) -> None:
        replica.breaker.record_failure()

    def report_success(self, replica: Replica) -> None:
        replica.breaker.record_success()

    # -- lifecycle ----------------------------------------------------
    def request_drain(self, replica_id: int) -> Replica:
        """Start draining a replica: placements stop targeting it
        immediately, and every in-flight chunk loop on it raises
        MeshReplicaDraining at its next boundary (the drain_check hook
        below), handing those queries to the failover dispatch."""
        from trino_tpu.runtime.metrics import METRICS

        rep = self.replicas[replica_id]
        with self._lock:
            if rep.state in ("shutting_down", "drained"):
                return rep  # already draining: don't double-count
            rep.state = "shutting_down"
            self.drains += 1
        METRICS.increment(DRAINS)
        return rep

    def drain(self, replica_id: int, timeout_s: float = 30.0,
              poll_s: float = 0.01) -> bool:
        """Graceful drain: request + wait until nothing is in flight on
        the replica (its queries finished or failed over). True once
        drained; False on timeout (the replica stays shutting_down —
        still out of rotation)."""
        rep = self.request_drain(replica_id)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if rep.inflight == 0:
                # state transitions happen under _lock everywhere else
                # (request_drain, undrain, leave); an unlocked write here
                # could race an undrain() and resurrect a dead replica.
                with self._lock:
                    if rep.inflight == 0:
                        rep.state = "drained"
                        return True
                continue
            time.sleep(poll_s)
        return rep.inflight == 0

    def undrain(self, replica_id: int) -> None:
        """Return a drained replica to rotation (chaos harness reuse)."""
        rep = self.replicas[replica_id]
        with self._lock:
            rep.state = "active"

    # -- live membership (heartbeat-driven; runtime/fabric.py) --------
    def leave(self, replica_id: int) -> Replica:
        """Heartbeat-driven departure: the replica leaves the placement
        pool under a NEW membership epoch. The Replica object — breaker
        state, lifetime counters — survives, so a flap (leave + rejoin)
        never resets health history. In-flight chunk loops on it fail
        over through the same drain_check boundary hook a drain uses
        (state left the active set)."""
        from trino_tpu.runtime.fabric import LEAVES
        from trino_tpu.runtime.metrics import METRICS

        rep = self.replicas[replica_id]
        with self._lock:
            if rep.state == "left":
                return rep  # already out: don't double-advance the epoch
            rep.state = "left"
            self.membership_epoch += 1
            self.leaves += 1
        METRICS.increment(LEAVES)
        return rep

    def join(self, replica_id: int, warm=None) -> Replica:
        """(Re)admit a replica under a new membership epoch. `warm`
        runs BEFORE the replica enters the placement pool (the
        joining-host warmup replay of runtime/fabric.py: its first
        placed query must mint zero new lowerings); a warm failure
        still joins — warmup delays availability, never gates it."""
        from trino_tpu.runtime.fabric import JOINS
        from trino_tpu.runtime.metrics import METRICS

        rep = self.replicas[replica_id]
        if rep.state == "active":
            return rep
        if warm is not None:
            try:
                warm()
            except Exception:
                pass
        with self._lock:
            self.membership_epoch += 1
            rep.state = "active"
            rep.join_epoch = self.membership_epoch
            self.joins += 1
        METRICS.increment(JOINS)
        return rep

    # -- ownership ledger (exactly one owner per in-flight query) -----
    def claim(self, query_id: str, replica: Replica) -> bool:
        """Record `replica` as the single owner of `query_id` under the
        current epoch. Refused while ANOTHER replica's claim is live —
        even if that replica has since left (its chunk loop may still
        be unwinding), so a membership flap can never double-place a
        query across epochs. Re-claim by the same replica is a no-op
        refresh."""
        if not query_id:
            return True  # anonymous dispatch: nothing to fence
        with self._lock:
            cur = self._owners.get(query_id)
            if cur is not None and cur[0] != replica.replica_id:
                return False
            self._owners[query_id] = (
                replica.replica_id, self.membership_epoch
            )
            return True

    def unclaim(self, query_id: str, replica: Replica) -> None:
        if not query_id:
            return
        with self._lock:
            cur = self._owners.get(query_id)
            if cur is not None and cur[0] == replica.replica_id:
                del self._owners[query_id]

    def owner_of(self, query_id: str):
        """(replica_id, epoch) of the live claim, or None."""
        with self._lock:
            return self._owners.get(query_id)

    def require_epoch(self, replica: Replica, expected_epoch: int) -> None:
        """Fence a resume: refuse (typed MembershipEpochError) when the
        target replica's epoch moved past the one the resume context
        was taken under, or it is no longer active — it left and
        rejoined in between, so carrying the old resume would hand
        stale state to what is effectively a new host. The caller
        discards the checkpoint and restarts fresh."""
        from trino_tpu.runtime.fabric import (
            EPOCH_FENCES,
            MembershipEpochError,
        )
        from trino_tpu.runtime.metrics import METRICS

        with self._lock:
            moved = (
                replica.join_epoch > expected_epoch
                or replica.state != "active"
            )
            if moved:
                self.epoch_fences += 1
        if moved:
            METRICS.increment(EPOCH_FENCES)
            raise MembershipEpochError(
                f"replica {replica.replica_id} membership epoch moved "
                f"({expected_epoch} -> {replica.join_epoch}, "
                f"state={replica.state}): resume refused, restart fresh",
                replica_id=replica.replica_id,
                expected_epoch=expected_epoch,
                actual_epoch=replica.join_epoch,
            )

    def membership_line(self) -> str:
        """The EXPLAIN ANALYZE membership line (instance-scoped, like
        stats_line, so corpus output stays deterministic)."""
        with self._lock:
            return (
                f"membership= epoch={self.membership_epoch} "
                f"joins={self.joins} leaves={self.leaves} "
                f"epoch_fences={self.epoch_fences} "
                f"owners={len(self._owners)}"
            )

    def drain_check(self, replica: Replica):
        """The chunk-boundary hook a MeshExecutor carries: raises
        MeshReplicaDraining (in-run resume disabled) once this replica
        leaves the active state, so the run fails over instead of
        finishing on capacity that is being decommissioned."""
        def check() -> None:
            if replica.state != "active":
                from trino_tpu.parallel.mesh_chunk import (
                    MeshReplicaDraining,
                )

                raise MeshReplicaDraining(
                    f"replica {replica.replica_id} is "
                    f"{replica.state}; failing over at this chunk "
                    "boundary"
                )

        return check

    # -- observability ------------------------------------------------
    def breaker_states(self) -> Dict[int, str]:
        return {r.replica_id: r.breaker.state for r in self.replicas}

    def healthy_count(self) -> int:
        with self._lock:
            return len([
                r for r in self.replicas
                if r.state == "active" and not r.breaker.is_open
            ])

    def stats(self) -> dict:
        with self._lock:
            return {
                "replicas": self.n_replicas,
                "partition_width": self.partition_width,
                "placements": self.placements,
                "failovers": self.failovers,
                "drains": self.drains,
                "breaker_opens": self.breaker_opens,
                "per_replica": {
                    r.replica_id: {
                        "state": r.state,
                        "breaker": r.breaker.state,
                        "depth": r.inflight,
                        "served": r.served,
                    }
                    for r in self.replicas
                },
            }

    def stats_line(self) -> str:
        s = self.stats()
        states = "".join(
            p["state"][0] for p in s["per_replica"].values()
        )
        return (
            f"replicas= n={s['replicas']}x{s['partition_width']} "
            f"states={states} placements={s['placements']} "
            f"failovers={s['failovers']} drains={s['drains']} "
            f"breaker_opens={s['breaker_opens']}"
        )
