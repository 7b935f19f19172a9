"""Config + session-property system.

Analogue of airlift @Config binding (etc/config.properties -> typed
config objects; 353 @Config annotations in trino-main) and the typed
session-property registry (main/SystemSessionProperties.java, ~200
properties — SURVEY.md §5.6). A property is declared once, as a field
of `Session` with its default, description and allowed values;
`SYSTEM_PROPERTIES` is read off those fields at import. SET SESSION
goes through `validate`, and config files bind by the same registry."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class PropertyMetadata:
    name: str
    type: type  # bool | int | float | str
    default: Any
    description: str
    allowed: Optional[tuple] = None  # enum-valued string properties

    def parse(self, text: str) -> Any:
        if self.type is bool:
            if text.lower() in ("true", "1", "on"):
                return True
            if text.lower() in ("false", "0", "off"):
                return False
            raise ValueError(f"{self.name}: expected boolean, got {text!r}")
        return self.type(text)


class PropertyRegistry:
    def __init__(self):
        self._props: Dict[str, PropertyMetadata] = {}

    def register(
        self, name: str, type_: type, default, description: str,
        allowed: Optional[tuple] = None,
    ) -> None:
        self._props[name] = PropertyMetadata(
            name, type_, default, description, allowed
        )

    def validate(self, name: str, value: Any) -> Any:
        meta = self._props.get(name)
        if meta is None:
            raise ValueError(f"unknown session property {name!r}")
        if isinstance(value, str) and meta.type is not str:
            value = meta.parse(value)
        elif meta.type is float and isinstance(value, int):
            value = float(value)
        elif not isinstance(value, meta.type):
            raise ValueError(
                f"{name}: expected {meta.type.__name__}, got {type(value).__name__}"
            )
        if meta.allowed is not None and value not in meta.allowed:
            raise ValueError(
                f"{name}: must be one of {meta.allowed}, got {value!r}"
            )
        return value

    def default(self, name: str) -> Any:
        return self._props[name].default

    def all(self) -> List[PropertyMetadata]:
        return sorted(self._props.values(), key=lambda m: m.name)


def _prop(default, description: str, allowed: Optional[tuple] = None,
          **metadata):
    """A Session field that is a session property: what SHOW SESSION
    describes and SET SESSION validates rides in the field's metadata."""
    return dataclasses.field(
        default=default,
        metadata={"description": description, "allowed": allowed, **metadata},
    )


@dataclasses.dataclass
class Session:
    """Per-query context (main/Session.java analogue) and the one
    declaration of every system session property (SystemSessionProperties
    analogue — the switchboard the executor consults per query): a
    field made by `_prop` is a property, a plain field is not.
    retry_policy mirrors Trino's `retry_policy` session property: "none"
    (pipelined), "query" (whole-query retry inside the pipelined
    scheduler, PipelinedQueryScheduler.scheduleRetryWithDelay:394) or
    "task" (FTE over spooled exchange, SURVEY.md §3.5)."""

    catalog: str = "tpch"
    schema: str = "tiny"
    user: str = "user"
    # session time zone (Session.java getTimeZoneKey): fixes literal
    # parsing, timestamp<->tstz casts, now()/current_date
    timezone: str = "UTC"
    batch_rows: int = _prop(1 << 20, "max rows per device batch")
    target_splits: int = _prop(1, "target connector split count per scan")
    retry_policy: str = _prop(
        "none",
        "none | query | task",
        allowed=("none", "query", "task"),
    )
    query_retry_count: int = _prop(
        2,
        "whole-query retry attempts (retry_policy=query)",
    )
    task_retries: int = _prop(3, "per-task retry attempts (FTE)")
    # None on the session, 0 in SET / SHOW SESSION and a properties
    # file (bind_session); exceeding it triggers revocation/spill, then
    # ExceededMemoryLimitError
    memory_pool_bytes: Optional[int] = _prop(
        None,
        "per-query memory budget (0 = unlimited)",
        registered_default=0,
    )
    enable_dynamic_filtering: bool = _prop(True, "probe-side join pruning")
    broadcast_join_threshold: int = _prop(
        1_000_000,
        "max estimated build rows for a broadcast join",
    )
    mesh_execution: bool = _prop(
        True,
        "run colocated fragments over the device-mesh collective "
        "exchange",
    )
    mesh_chunk_rows: int = _prop(
        0,
        "per-shard rows per mesh chunk-step: the driver scan splits "
        "into ceil(rows/chunk) jit steps with host preemption checks "
        "(deadline/abandonment/watchdog) at every chunk boundary; 0 "
        "compiles the plan as one program (preemption checks only "
        "bracket it)",
    )
    enable_optimizer: bool = _prop(
        True,
        "run the iterative plan-optimizer pipeline",
    )
    enable_pushdown: bool = _prop(
        True,
        "push supported filter conjuncts and projections into "
        "connector scans (apply_filter/apply_projection SPI)",
    )
    join_reordering_strategy: str = _prop(
        "automatic",
        "cost-based join reordering: automatic | none",
        allowed=("automatic", "none"),
    )
    speculation_enabled: bool = _prop(
        True,
        "FTE: duplicate straggler tasks, first finisher wins",
    )
    task_concurrency: int = _prop(
        2,
        "intra-task pipeline parallelism via the local exchange (1 = "
        "off)",
    )
    # -- deadline hierarchy (runtime/query_tracker.py); 0 = unlimited.
    # Breaches are typed NON-RETRYABLE errors: the budget is a property
    # of the query, so neither QUERY retry nor FTE task retry may
    # resubmit past one
    query_max_planning_time_s: float = _prop(
        0.0,
        "kill a query still PLANNING after this long "
        "(EXCEEDED_TIME_LIMIT, non-retryable)",
    )
    query_max_execution_time_s: float = _prop(
        0.0,
        "kill a query EXECUTING (post-planning) after this long "
        "(EXCEEDED_TIME_LIMIT, non-retryable)",
    )
    query_max_run_time_s: float = _prop(
        0.0,
        "end-to-end wall bound: queued + planning + execution "
        "(EXCEEDED_TIME_LIMIT, non-retryable)",
    )
    query_max_cpu_time_s: float = _prop(
        0.0,
        "kill a query whose tasks' aggregated CPU ledgers exceed this "
        "(EXCEEDED_CPU_LIMIT, non-retryable)",
    )
    client_timeout_s: float = _prop(
        300.0,
        "reap a query whose client stopped polling nextUri for this "
        "long: tasks cancelled, resource-group slot and memory "
        "released",
    )
    stuck_task_interrupt_s: float = _prop(
        0.0,
        "worker watchdog: interrupt a task making no batch progress "
        "for this long (failure is RETRYABLE — a hung split may "
        "succeed elsewhere); 0 disables",
    )
    speculation_percentile: float = _prop(
        0.75,
        "FTE speculation bases its per-fragment duration estimate on "
        "this quantile of committed attempt wall times (p75 default)",
    )
    # -- plan validation (sql/validate.py, PlanSanityChecker analogue) --
    plan_validation: str = _prop(
        "passes",
        "run plan sanity checkers: off | passes (after each optimizer "
        "pass + fragmentation) | rules (additionally after every rule "
        "application, plus plan-determinism double-planning — debug "
        "mode)",
        allowed=("off", "passes", "rules"),
    )
    # -- compile regime (compile/: shapes, warmup, cache) --
    shape_stabilization: bool = _prop(
        True,
        "pad scan chunks to the capacity class of their pre-pruning "
        "span so pushdown/dynamic-filter pruning and FTE retries "
        "re-land on census-predicted XLA lowerings",
    )
    capacity_ladder_base: int = _prop(
        2,
        "geometric ratio between capacity-ladder rungs (power of two; "
        "2 = the native bucket_capacity grid, larger = fewer, coarser "
        "capacity classes)",
    )
    warmup_mode: str = _prop(
        "off",
        "census-driven AOT warmup of predicted lowerings: off | "
        "background (compile while the query runs) | block (wait for "
        "warmup before execution)",
        allowed=("off", "background", "block"),
    )
    stuck_task_interrupt_warm_s: float = _prop(
        0.0,
        "aggressive stuck-task watchdog threshold applied once a "
        "task's predicted shape classes are all warm (warmup/cache "
        "hits or a prior completed run); 0 falls back to "
        "stuck_task_interrupt_s",
    )
    # -- serving tier (trino_tpu/serving/) --
    micro_batch_window_ms: float = _prop(
        0.0,
        "inter-query micro-batching: coalesce same-shape point "
        "lookups arriving within this window onto one shared device "
        "step; 0 disables batching",
    )
    # -- resident state tier (trino_tpu/resident/) --
    resident_tables: str = _prop(
        "",
        "comma-separated tables (table, schema.table or "
        "catalog.schema.table) whose point lookups the serving fast "
        "lane serves from pinned device-resident hash tables; empty "
        "disables the fast lane",
    )
    resident_pin_budget_mb: int = _prop(
        64,
        "device-memory budget for resident pins (fast-lane hash "
        "tables and mesh prelude contexts), LRU-evicted and revocable "
        "under memory pressure; 0 disables pinning entirely",
    )
    resident_delta_max_rows: int = _prop(
        4096,
        "capacity of a pinned table's append-only delta side; "
        "background compaction folds the delta into the base once it "
        "crosses half this, and an insert that cannot fit evicts the "
        "pin instead",
    )
    # -- adaptive execution tier (trino_tpu/adaptive/) --
    adaptive_execution: bool = _prop(
        False,
        "mid-query re-planning: materialize pipeline barriers "
        "(completed join build sides), diff observed rows/NDV against "
        "sql/stats.py estimates, and re-optimize the remaining plan "
        "when divergence crosses adaptive_replan_threshold; completed "
        "work is substituted back as literal sources and never redone",
    )
    adaptive_replan_threshold: float = _prop(
        4.0,
        "divergence ratio max(est,obs)/min(est,obs) at or above which "
        "an observation triggers re-planning of the remaining plan "
        "(and is counted in adaptive.divergences regardless of "
        "whether adaptive_execution is on)",
    )
    skewed_join_salting: bool = _prop(
        False,
        "skew-aware join plane: when a build-side barrier's modal key "
        "crosses skew_hot_key_threshold, annotate the join so the "
        "mesh plane replicates hot build rows to every shard and "
        "salts hot probe rows across the all_to_all (requires "
        "adaptive_execution)",
    )
    skew_hot_key_threshold: float = _prop(
        0.2,
        "fraction of observed build rows a single key value must "
        "reach to be classified a heavy hitter",
    )
    skew_spill_min_rows: int = _prop(
        1 << 18,
        "minimum observed build rows before a divergent build-side "
        "barrier re-plans the join into hybrid-hash spill mode "
        "(pre-opened grace partitions)",
    )
    mxu_join_enabled: bool = _prop(
        False,
        "plan high-fanout equi-join + aggregation as the MXU matmul "
        "join-project kernel (ops/mxu_join.py) when profitable",
    )
    mxu_join_min_work: float = _prop(
        16.0,
        "estimated fanout x build-NDV product at or above which the "
        "MXU join-project kernel is selected over the padded-gather "
        "path",
    )
    shared_subtree_materialization: bool = _prop(
        False,
        "materialize identical subtrees (NOT IN rewrites plan the "
        "subquery twice; CTEs referenced twice) once into the "
        "generation-guarded spool and feed every consumer — and the "
        "re-planner — from the same rows",
    )
    # -- recovery tier (trino_tpu/recovery/) --
    mesh_checkpoint_interval_chunks: int = _prop(
        0,
        "snapshot the mesh step loop's device carries to the "
        "host-side generation-guarded checkpoint store every N chunk "
        "boundaries so MeshStuck/device-loss faults resume from the "
        "last checkpoint instead of chunk 0; 0 disables checkpointing",
    )
    mesh_resume_attempts: int = _prop(
        2,
        "max in-run resume attempts from a mesh checkpoint before the "
        "fault escalates to the page-plane fallback / QUERY retry",
    )
    recovery_spool_stages: bool = _prop(
        False,
        "tee completed non-root fragment outputs into the subtree "
        "spool so QUERY-level retry substitutes finished stages as "
        "literal sources instead of recomputing them (FTE settles "
        "lift committed stage spool files into the same store)",
    )
    # -- replicated serving meshes (trino_tpu/runtime/replicas.py) --
    mesh_replicas: int = _prop(
        1,
        "carve the device set into this many identical sub-meshes "
        "(replica x partition named-axis grid); the coordinator "
        "load-balances mesh queries across healthy replicas and each "
        "replica runs the same prelude/step/flush programs unchanged; "
        "1 (or too few devices) keeps the single full-width mesh",
    )
    # -- preemptive multi-tenancy (runtime/scheduler.py) --
    park_max_bytes: int = _prop(
        256 << 20,
        "host-memory budget for parked query snapshots in the mesh "
        "checkpoint store; a park that would exceed it is refused and "
        "the query runs to completion instead (never query failure)",
    )
    mesh_scheduler_weights: str = _prop(
        "",
        "per-resource-group scheduling weights for the mesh "
        "scheduler, 'group=weight,...' (scheduling_weight analogue); "
        "unlisted groups weigh 1",
    )
    mesh_scheduler_group: str = _prop(
        "",
        "resource group this session's mesh queries are accounted to "
        "in the weighted-fair scheduler; empty uses 'default'",
    )
    # -- multi-host replica fabric (runtime/fabric.py) --
    fabric_peers: str = _prop(
        "",
        "comma-separated base URIs of peer coordinator fabric "
        "endpoints (http://host:port); non-empty attaches the "
        "checkpoint push/pull fabric: checkpoints stream "
        "asynchronously to every peer and failover pulls the last "
        "pushed snapshot on demand",
    )
    # -- observability (runtime/tracing.py) --
    query_trace: str = _prop(
        "off",
        "record a full span tree per query (phases, stages, task "
        "attempts, operators; worker spans grafted into the "
        "coordinator's tree) exportable as JSON/Chrome trace-event "
        "via GET /v1/query/{id}/trace",
        allowed=("off", "on"),
    )

    def set_property(self, name: str, value) -> None:
        """SET SESSION entry point — validated through the typed
        registry (SYSTEM_PROPERTIES)."""
        bind_session(self, {name: value})


# the registry is the Session's property fields, read once at import
SYSTEM_PROPERTIES = PropertyRegistry()
for _f in dataclasses.fields(Session):
    if "description" in _f.metadata:
        _default = _f.metadata.get("registered_default", _f.default)
        SYSTEM_PROPERTIES.register(
            _f.name, type(_default), _default,
            _f.metadata["description"], _f.metadata["allowed"],
        )


def load_properties_file(path: str) -> Dict[str, str]:
    """key=value config file (etc/config.properties format: # comments,
    blank lines ignored)."""
    out: Dict[str, str] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if "=" not in line:
                raise ValueError(f"malformed config line: {line!r}")
            k, v = line.split("=", 1)
            out[k.strip()] = v.strip()
    return out


def bind_session(session, overrides: Dict[str, Any]) -> None:
    """Apply validated property values onto a Session (the
    SessionPropertyManager.validate path)."""
    for name, value in overrides.items():
        value = SYSTEM_PROPERTIES.validate(name, value)
        if name == "memory_pool_bytes":
            value = value or None  # 0 means unlimited
        setattr(session, name, value)
