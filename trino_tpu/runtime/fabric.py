"""Multi-host replica fabric: checkpoint transport + membership bridge.

PR 17 made mesh checkpoints host-portable (`MeshCheckpointStore.
export_bytes` / `import_bytes`, generation fencing, the device-identity-
free checkpoint key) but left the wire out: every byte stayed inside one
coordinator process, so a real host loss stranded its in-flight queries
with no sibling able to fetch the last snapshot. This module is that
wire, plus the membership tier that decides who the siblings ARE:

- **checkpoint transport** — `CheckpointPusher` ships `export_bytes`
  payloads to peer coordinators over the HTTP layer (runtime/http.py
  FabricServer/FabricClient), each call wrapped in the PR 2
  RequestErrorTracker backoff/budget loop, with a sha256 content digest
  verified before the receiver's generation-fenced `import_bytes`.
  Pushes ride a bounded queue drained by a daemon thread: the chunk
  loop only ever enqueues, and a full queue SHEDS the push
  (fabric.push_sheds) rather than blocking a chunk boundary. Pulls run
  on demand at failover (`Fabric.try_pull`).
- **membership** — `MembershipDriver` subscribes to the NodeManager
  heartbeat tier (discovery.py state listeners) and drives
  `ReplicaManager.leave` / `.join` under the monotonic membership
  epoch: placement and failover consult live membership, breaker state
  survives flaps (the Replica object persists), and a resume targeting
  a replica whose epoch moved is refused with the typed
  `MembershipEpochError` — then restarted fresh — instead of carrying
  stale state onto what is effectively a new host.
- **warm join** — a joining host replays the peer's warm-class
  manifest (compile/warmup.py `warm_manifest`/`apply_manifest`) and
  the census-driven mesh WarmupEntry registry BEFORE it enters the
  placement pool, so its first placed query mints zero new lowerings.

Counters surface through /v1/metrics under the `fabric.` prefix and
through the EXPLAIN ANALYZE `membership=` line (replicas.py
`membership_line`).
"""

from __future__ import annotations

import base64
import hashlib
import pickle
import queue
import threading
from trino_tpu.analysis import threadreg
from trino_tpu.analysis.witness import named_condition, named_lock, named_rlock
from typing import Callable, List, Optional, Tuple

# /v1/metrics counter names (registered at zero by
# register_fabric_metrics — same surface protocol as the recovery,
# replica and scheduler counters)
PUSHES = "fabric.pushes"
PULLS = "fabric.pulls"
PUSH_SHEDS = "fabric.push_sheds"
DIGEST_REJECTS = "fabric.digest_rejects"
JOINS = "fabric.joins"
LEAVES = "fabric.leaves"
EPOCH_FENCES = "fabric.epoch_fences"

_COUNTERS = (
    PUSHES, PULLS, PUSH_SHEDS, DIGEST_REJECTS, JOINS, LEAVES, EPOCH_FENCES,
)


def register_fabric_metrics() -> None:
    from trino_tpu.runtime.metrics import METRICS

    for name in _COUNTERS:
        METRICS.increment(name, 0.0)


class MembershipEpochError(RuntimeError):
    """A resume targeted a replica whose membership epoch moved past
    the epoch its checkpoint context was taken under (the replica left
    and rejoined in between). Typed so the dispatcher can discard the
    stale context and restart fresh instead of carrying old state onto
    what is effectively a new host."""

    def __init__(self, message: str, replica_id: Optional[int] = None,
                 expected_epoch: Optional[int] = None,
                 actual_epoch: Optional[int] = None):
        super().__init__(message)
        self.replica_id = replica_id
        self.expected_epoch = expected_epoch
        self.actual_epoch = actual_epoch


# -- wire helpers -----------------------------------------------------


def checkpoint_digest(data: bytes) -> str:
    """Content digest of a serialized checkpoint: transport corruption
    (truncation, bit flips) is rejected BEFORE import_bytes ever sees
    the payload, so a corrupt transfer degrades to a clean restart
    rather than a poisoned store."""
    return hashlib.sha256(data).hexdigest()


def encode_key(key: tuple) -> str:
    """URL-safe transport form of a checkpoint key (the device-
    identity-free program tuple). Pickled like the checkpoint payload
    itself — both travel only inside the internal-auth trust domain
    (FabricServer refuses to start networked without a secret)."""
    raw = pickle.dumps(key, protocol=pickle.HIGHEST_PROTOCOL)
    return base64.urlsafe_b64encode(raw).decode("ascii")


def decode_key(ekey: str) -> tuple:
    key = pickle.loads(base64.urlsafe_b64decode(ekey.encode("ascii")))
    if not isinstance(key, tuple):
        raise TypeError(f"fabric key decoded to {type(key).__name__}")
    return key


# -- endpoint logic (behind runtime/http.py FabricServer) -------------


class HostFabric:
    """One host's fabric endpoint state: the receive/serve logic behind
    the FabricServer routes, bound to this process's checkpoint
    store."""

    def __init__(self, store=None, host_id: str = ""):
        if store is None:
            from trino_tpu.recovery.checkpoint import CHECKPOINTS

            store = CHECKPOINTS
        self.store = store
        self.host_id = host_id
        self.received = 0
        self.served = 0
        self.digest_rejects = 0
        register_fabric_metrics()

    def receive_checkpoint(self, ekey: str, data: bytes,
                           digest: str) -> dict:
        """POST /v1/fabric/checkpoint/{ekey}: verify the content digest,
        then land the bytes under the LOCAL generation check
        (import_bytes). Either rejection — digest mismatch or
        undecodable payload — leaves the store untouched; the pusher
        side treats the outcome as advisory (push is best-effort)."""
        from trino_tpu.runtime.metrics import METRICS

        if checkpoint_digest(data) != digest:
            self.digest_rejects += 1
            METRICS.increment(DIGEST_REJECTS)
            return {"imported": False, "reason": "digest_mismatch"}
        try:
            key = decode_key(ekey)
        except Exception:
            self.digest_rejects += 1
            METRICS.increment(DIGEST_REJECTS)
            return {"imported": False, "reason": "bad_key"}
        # rebase_epoch: the sender's global generation epoch is
        # process-local noise across hosts; per-table write counters
        # keep DML fencing live (checkpoint.py import_bytes)
        ok = self.store.import_bytes(key, data, rebase_epoch=True)
        if ok:
            self.received += 1
        return {"imported": bool(ok)}

    def serve_checkpoint(self, ekey: str) -> Optional[Tuple[bytes, str]]:
        """GET /v1/fabric/checkpoint/{ekey}: export the live entry (via
        `get`, so stale generations are never served) with its digest.
        None -> 404."""
        key = decode_key(ekey)
        data = self.store.export_bytes(key)
        if data is None:
            return None
        self.served += 1
        return data, checkpoint_digest(data)

    def status(self) -> dict:
        return {
            "host_id": self.host_id,
            "entries": len(self.store),
            "received": self.received,
            "served": self.served,
            "digest_rejects": self.digest_rejects,
        }


# -- push side --------------------------------------------------------


class CheckpointPusher:
    """Bounded asynchronous push queue over a set of peer clients.

    The chunk loop's checkpoint hook calls `offer(key)` — non-blocking
    by construction: a full queue sheds the push (the NEXT boundary's
    snapshot supersedes this one anyway) and the worker thread does the
    export + HTTP on its own time, inside each client's
    RequestErrorTracker budget. A push failure after the budget is
    spent is dropped: the fabric degrades to pull-on-demand (or a cold
    restart), never to a blocked or failed query."""

    _STOP = object()

    def __init__(self, store, clients: List, depth: int = 8):
        self.store = store
        self.clients = list(clients)
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._busy = 0  # guarded_by: _lock
        self._lock = named_lock("CheckpointPusher._lock")
        self.pushes = 0
        self.sheds = 0
        self.push_failures = 0
        self._thread = threadreg.spawn(
            "trino-tpu-fabric-push", self._run, owner="CheckpointPusher"
        )

    def offer(self, key: tuple) -> bool:
        try:
            self._q.put_nowait(key)
            return True
        except queue.Full:
            from trino_tpu.runtime.metrics import METRICS

            self.sheds += 1
            METRICS.increment(PUSH_SHEDS)
            return False

    def queued(self) -> int:
        with self._lock:
            return self._q.qsize() + self._busy

    def flush(self, timeout_s: float = 10.0) -> bool:
        """Wait for every enqueued push to complete (tests and the
        multihost smoke's pre-kill flush). True when drained."""
        import time

        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.queued() == 0:
                return True
            import time as _t

            _t.sleep(0.005)
        return self.queued() == 0

    def stop(self) -> None:
        self._q.put(self._STOP)
        self._thread.join(timeout=2.0)

    def _run(self) -> None:
        while True:
            key = self._q.get()
            if key is self._STOP:
                return
            with self._lock:
                self._busy += 1
            try:
                self._push(key)
            finally:
                with self._lock:
                    self._busy -= 1

    def _push(self, key: tuple) -> None:
        from trino_tpu.runtime.metrics import METRICS

        data = self.store.export_bytes(key)
        if data is None:
            return  # completed/invalidated since the boundary: nothing to ship
        digest = checkpoint_digest(data)
        for client in self.clients:
            try:
                client.push_checkpoint(key, data, digest=digest)
                self.pushes += 1
                METRICS.increment(PUSHES)
            except Exception:
                # budget spent (RequestFailedError) or protocol error:
                # drop the push — the receiver can still pull on demand
                self.push_failures += 1


# -- process attachment -----------------------------------------------


class Fabric:
    """One coordinator process's fabric attachment: the push queue over
    its peer set plus pull-on-demand for failover."""

    def __init__(self, peer_uris: List[str], store=None,
                 internal_secret: Optional[str] = "__env__",
                 queue_depth: int = 8,
                 max_error_duration_s: float = 5.0):
        from trino_tpu.runtime.error_tracker import RetryPolicy
        from trino_tpu.runtime.http import FabricClient

        if store is None:
            from trino_tpu.recovery.checkpoint import CHECKPOINTS

            store = CHECKPOINTS
        self.store = store
        self.peer_uris = list(peer_uris)
        policy = RetryPolicy(
            max_error_duration_s=float(max_error_duration_s),
            min_backoff_s=0.01, max_backoff_s=0.5,
        )
        self.clients = [
            FabricClient(
                uri, internal_secret=internal_secret, retry_policy=policy,
            )
            for uri in self.peer_uris
        ]
        self.pusher = CheckpointPusher(store, self.clients, depth=queue_depth)
        register_fabric_metrics()

    def push_hook(self) -> Callable[[tuple], None]:
        """The mesh chunk loop's CHECKPOINT_PUSH_HOOK: enqueue-only."""
        def hook(key: tuple) -> None:
            self.pusher.offer(key)

        return hook

    def try_pull(self, key: tuple) -> bool:
        """Failover pull: ask each peer for the key, verify the digest,
        and land the first good payload under the local generation
        check. False when no peer has it (or every transfer failed its
        budget) — the caller restarts cold."""
        from trino_tpu.runtime.metrics import METRICS

        for client in self.clients:
            try:
                data, digest = client.pull_checkpoint(key)
            except Exception:
                continue  # budget spent on this peer: try the next
            if data is None:
                continue
            if digest and checkpoint_digest(data) != digest:
                METRICS.increment(DIGEST_REJECTS)
                continue
            if self.store.import_bytes(key, data, rebase_epoch=True):
                METRICS.increment(PULLS)
                return True
        return False

    def stop(self) -> None:
        self.pusher.stop()


# the process's active attachment (one coordinator, one fabric — set by
# maybe_start_fabric, mirrors recovery.CHECKPOINTS)
_fabric_lock = named_lock("fabric._fabric_lock")
ACTIVE_FABRIC: Optional[Fabric] = None  # guarded_by: _fabric_lock


def active_fabric() -> Optional[Fabric]:
    return ACTIVE_FABRIC  # unguarded-ok: atomic reference read


def maybe_start_fabric(session, store=None) -> Optional[Fabric]:
    """Attach the fabric when `session.fabric_peers` names peers (and
    re-attach when the peer set changed): builds the push queue and
    installs the chunk loop's checkpoint push hook. A session without
    peers leaves any existing attachment alone — SET SESSION on one
    query must not tear down another's transport."""
    global ACTIVE_FABRIC
    peers = [
        p.strip()
        for p in str(session.fabric_peers or "").split(",")
        if p.strip()
    ]
    if not peers:
        return ACTIVE_FABRIC  # unguarded-ok: atomic reference read
    with _fabric_lock:
        if ACTIVE_FABRIC is not None and ACTIVE_FABRIC.peer_uris == peers:
            return ACTIVE_FABRIC
        if ACTIVE_FABRIC is not None:
            ACTIVE_FABRIC.stop()
        fab = Fabric(peers, store=store)
        from trino_tpu.parallel import mesh_chunk

        mesh_chunk.CHECKPOINT_PUSH_HOOK = fab.push_hook()
        ACTIVE_FABRIC = fab
        return fab


def stop_fabric() -> None:
    """Detach and stop the active fabric (tests, process shutdown)."""
    global ACTIVE_FABRIC
    with _fabric_lock:
        if ACTIVE_FABRIC is None:
            return
        from trino_tpu.parallel import mesh_chunk

        mesh_chunk.CHECKPOINT_PUSH_HOOK = None
        ACTIVE_FABRIC.stop()
        ACTIVE_FABRIC = None


def fabric_status() -> dict:
    """The /v1/fabric surface: counter snapshot + attachment state."""
    from trino_tpu.runtime.metrics import METRICS

    s = METRICS.snapshot()
    out = {
        name.split(".", 1)[1]: int(s.get(name, 0.0)) for name in _COUNTERS
    }
    fab = ACTIVE_FABRIC  # unguarded-ok: atomic reference read
    out["attached"] = fab is not None
    if fab is not None:
        out["peers"] = list(fab.peer_uris)
        out["queued"] = fab.pusher.queued()
        out["push_failures"] = fab.pusher.push_failures
    return out


# -- warm join --------------------------------------------------------


def warm_join_manifest() -> dict:
    """What a serving host hands a joining peer: the warm-class census
    (compile/warmup.py) plus the program-cache key fingerprints —
    everything the joiner needs to pre-compile before placement."""
    from trino_tpu.compile.cache import PROGRAM_CACHE
    from trino_tpu.compile.warmup import warm_manifest

    return {
        "classes": warm_manifest(),
        "programs": PROGRAM_CACHE.fingerprints(),
    }


def warm_join_replay(manifest: Optional[dict] = None,
                     mode: str = "block",
                     timeout_s: float = 60.0) -> int:
    """Warm a joining host/replica BEFORE it enters the placement pool:
    register the peer manifest's warm classes, then replay the local
    census-driven mesh WarmupEntry registry so the joiner's first
    placed query dispatches into populated jit caches — zero new
    lowerings. Returns the number of manifest classes applied. Never
    raises: warmup can delay a join, not fail it."""
    from trino_tpu.compile.warmup import WarmupService, apply_manifest
    from trino_tpu.parallel.mesh_chunk import mesh_warmup_entries

    applied = 0
    try:
        if manifest:
            applied = apply_manifest(manifest.get("classes", []))
        entries = mesh_warmup_entries()
        if entries:
            WarmupService(entries, mode=mode).start().wait(timeout_s)
    except Exception:
        pass
    return applied


# -- membership bridge ------------------------------------------------


class MembershipDriver:
    """Bridges the NodeManager heartbeat tier to replica membership:
    node state transitions (discovery.py add_state_listener) drive
    ReplicaManager.leave/join under the monotonic membership epoch.
    `replica_of` maps a worker_id to the replica it backs (None =
    not a replica host); `warm` is the joining-host warmup replay run
    before a rejoin enters the placement pool."""

    def __init__(self, node_manager, replica_manager,
                 replica_of: Optional[Callable[[str], Optional[int]]] = None,
                 warm: Optional[Callable[[], object]] = None):
        self.node_manager = node_manager
        self.replica_manager = replica_manager
        self.replica_of = replica_of or (lambda worker_id: None)
        self.warm = warm if warm is not None else warm_join_replay
        node_manager.add_state_listener(self._on_state)

    def _on_state(self, worker_id: str, old: str, new: str) -> None:
        rid = self.replica_of(worker_id)
        if rid is None:
            return
        if new in ("failed", "shutting_down", "drained") and old == "active":
            self.replica_manager.leave(rid)
        elif new == "active" and old != "active":
            self.replica_manager.join(rid, warm=self.warm)
