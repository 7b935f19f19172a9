"""Query tracing: explicit-parent spans, cluster-wide, Perfetto-exportable.

Analogue of the reference's OpenTelemetry integration (TracingMetadata,
ScopedSpan, spans per planning phase in SqlQueryExecution, and the
W3C-traceparent propagation coordinator->worker via TaskResource —
SURVEY.md §5.1), reduced to an in-process recorder with the same tree
shape and propagation discipline:

- NO globals and NO thread-local ambient context: a span is created from
  an explicit parent handle (``parent.child(...)`` or
  ``trace.span(..., parent=...)``), so spans opened on scheduler poll
  threads, FTE retry loops, and worker pipelines land under the right
  parent regardless of which thread touches them.
- Span context crosses the coordinator->worker boundary as plain data
  (``wire_context(span)`` -> dict on ``TaskSpec.trace_ctx``); the worker
  records its operator spans against the remote parent id and ships them
  back flat in task status, where ``QueryTrace.graft`` re-attaches them.
- Export is a flat OTel-style span list (``export()``) plus a Chrome
  trace-event rendering (``chrome_trace``) loadable in Perfetto /
  chrome://tracing; annotations (retry, speculation, drain, deadline,
  watchdog, chaos faults) become instant events on the owning span's
  track so a chaos run reads as one timeline.

Span kinds form the tree contract the invariant checker enforces:
``query`` roots the trace; ``phase`` (parse/analyze/optimize/validate/
fragment/schedule) and ``stage`` spans hang off it; ``task`` spans hang
off stages (one per attempt); ``operator`` spans hang off tasks, or off
the ``execute`` phase on the local runner, which has no tasks.

The same spans in the profiler's trace
--------------------------------------

While a ``jax.profiler`` trace is running (``TraceAnnotation.
is_enabled()`` decides, nothing else: no session property, no
environment variable), the program's spans are also events of that
trace, in the ``/host:CPU`` plane, one line per thread, on the clock the
device's events are on. Every event is named ``tpusql.<kind>.<name>``:

- a ``Span`` used as a context manager: ``tpusql.<span kind>.<first word
  of the span's name>`` (``tpusql.query.query``, ``tpusql.phase.execute``,
  ``tpusql.phase.analyze``, ``tpusql.stage.stage``), with the stats
  ``query_id``, ``span_id``, ``parent_id`` and, set at exit, every
  numeric attribute of the span (``phase.execute``: ``cpu_ns``, the
  executing thread's CPU time inside it);
- leaf spans, ``host_span(name, **stats)``: no ``Span``, no id, no lock;
  with no trace running the one shared no-op ``OFF`` (``host_sync`` and
  ``phase_span`` then still time into the running statement's account,
  below). Their names:
  ``phase.parse``, ``phase.plan`` (stat ``hit``: 1 when the plan cache
  answered), ``phase.instantiate``, ``phase.release`` (the operators'
  state and its device buffers are dropped), ``phase.finalize`` (all
  with ``query_id`` where a query span exists);
  ``op.<OperatorClass>.<get_output|add_input|finish>`` around every
  operator call of ``exec/driver.Driver.run`` (with the operator's
  ``span_stats`` where it has any: ``preserved`` 1 on a semi- or
  anti-join that built the side it preserves, ``outer`` 1 on a LEFT or
  FULL join whichever side it built, ``reverse`` 1 on the dynamic
  filter in front of the probe of a join that built its preserved side);
  ``sync.<site>`` (``host_sync``, stat ``nbytes``) around every
  device-to-host readback; ``df.prepare`` around a
  ``DynamicFilterOperator``'s choice of its filter (stats ``path``:
  ``set``, ``bits`` or ``range``; ``build_slots``; where the build
  side's domain was read, ``keys`` and ``domain``; for the bits,
  ``table_bytes``; for a set of over 128 slots, ``slots``, after
  ``sync.join.dynamic_filter_keys`` counted its keys) and, at its
  finish, ``sync.join.dynamic_filter_totals``
  (stats ``rows_in``, ``rows_kept``, ``batches``, ``slots``, ``path``,
  ``key_bytes``: what the filter saw and kept over the scan, counted on
  the device and read back once; ``reverse``: 1 in front of the
  FILTERING side of a semi- or anti-join that built the side it
  preserves); ``sync.join.match_total`` around the read of a probe
  batch's candidate pairs (stats ``rows``, ``probe_slots`` and, under
  such a join, ``first_candidates``: the probe rows that have one);
  ``sync.join.semi_flags``, once at the finish of such a join (stats
  ``pairs_seen`` and ``pairs_kept``: what its residual was shown and
  what it let through; ``build_rows`` and ``build_flagged``; ``kind``);
  ``scan.batches`` (stat ``cached``),
  ``scan.host_filter``, ``scan.to_device`` in the memory connector;
  ``result.fetch`` and ``result.to_rows`` around the result's readback
  and its conversion to rows; ``server.queued`` (stat ``handoff_us`` on
  the executing thread's part) and ``server.respond`` (stats ``pages``,
  ``rows``, and ``since_finished_us`` on the response that delivers the
  last page) in ``runtime/server.py``;
  the mesh plane (``parallel/``): ``mesh.feed`` (a scan's columns go
  from the host to their devices' shards; stats ``table``, ``columns``,
  ``rows``, ``nbytes``), ``mesh.prelude``, ``mesh.step`` (stat
  ``chunk``) and ``mesh.finish``, one dispatch each of a chunked run's
  three programs with its flag readback inside (stats ``all_to_all``,
  ``all_gather``, ``bytes_exchanged``: what one run of the program
  exchanges, ``mesh_chunk.ExchangeCensus``), ``sync.mesh.<site>`` for
  the plane's readbacks (``prelude_flags``, ``step_flags``,
  ``finish_flags``, ``result``), and the coordinator's ``phase.parse``
  and ``phase.execute`` (``cpu_ns``) around a distributed statement.

Leaf spans know their statement by lying inside its ``tpusql.query.*``
event on the same thread line. An operator call is a span whenever the
profiler is on as the call begins (``exec/driver.Driver`` asks per call,
not once a pipeline), so a pipeline that began before the trace has
``op.*`` events, and ``sync.*`` inside them, from the trace's first
instant. A ``sync.*`` span adds itself to the running call's
``OpTally``, and ``record_operators`` turns the tallies into one
``operator`` span each (``calls``, ``batches``, ``host_syncs``,
``host_sync_ms``, ``busy_ms``) in the tree ``GET /v1/query/{id}/trace``
serves. ``chipbench/spans.py`` reduces the events to per-layer metrics.

A statement's own account
-------------------------

Every statement's ``QueryTrace`` carries one ``StmtAccount``, kept
whether or not a profiler runs, from the runner's ``execute`` entered to
the result handed back. The one piece of per-thread state here is the
statement running on this thread (``running_statement()``; both runners
open and close it with ``statement(...)``), and the operator call that
is running hangs off it. The account is fed where the work happens:
``phase_span`` times ``plan`` (and ``plan_hit``), ``instantiate`` and
``release`` into it, the runners ``parse``, ``execute`` and the
executing thread's CPU time inside ``execute``; ``host_sync`` times
every readback on the thread into it (count, wall and bytes, in all and
by site) and is the shared ``OFF`` only where no statement runs on the
thread; ``runtime/metrics.MetricsRegistry.increment`` adds to it every
delta the statement's own thread adds to a counter. Work that other
threads do for a statement (the page plane's worker tasks) is not in
it. ``StmtAccount.stats()`` is the account as flat numbers:

- ``wall_us``, ``parse_us``, ``plan_us``, ``plan_hit``,
  ``instantiate_us``, ``execute_us``, ``release_us`` (walls; a phase a
  runner does not have reads 0), ``cpu_us``;
- ``syncs``, ``sync_us``, ``sync_bytes``, and ``s.<site>.n`` /
  ``s.<site>.us`` for every ``sync.<site>`` the statement reached;
- ``c.<counter>`` for every counter it moved (``c.rows_scanned``,
  ``c.agg_ingest_path.sort``, ``c.join_probe_path.blocked``,
  ``c.mesh.chunk_steps``, ...).

``result.stats["account"]``, ``QueryTrace.export()["account"]`` and the
``account`` key of ``GET /v1/query/{id}/trace`` carry it, and the
completion event takes ``rows_scanned``, ``bytes_scanned`` and
``rows_shuffled`` from it. While a profiler trace runs at the
statement's END, whenever it began, the executing thread writes one
event ``tpusql.stmt.done`` whose stats are ``query_id`` and all of the
above: the statement's numbers over its whole life, at the instant it
ended, on the clock the device's events are on. A statement that starts
inside a trace writes one ``tpusql.stmt.begin`` (``query_id``) as its
account opens. ``chipbench/stmt_account.py`` reads both (STMT.md).

Which of several device paths a batch took is not a span but a counter
of ``runtime/metrics.METRICS``, one increment a batch, trace or no
trace: ``agg_ingest_path.dense`` / ``.mxu`` / ``.slot`` / ``.sort`` (the
bounded reduce a grouped batch got; ``.slot`` the scatter-add of counts
by a key domain past the MXU reduce's), ``df_filter_path.set`` / ``.bits`` /
``.range`` (a dynamic filter's batches; of the bits' batches,
``df_bits_lookup.window`` / ``.gather``: whether the words were picked
out of a window a block, on a scan in key order, or gathered a row, and
``df_bits_window_fallbacks``, added at the filter's finish: the window
program's batches that took its gather after all) and ``join_probe_path.blocked``
/ ``.sorted`` (a join's probe batches by ``ops/join.probe_path``: the
two-level bounds or the two packed sorts), all in ``exec/operators.py``.
Beside them, one increment an OPERATOR (with its first batch):
``agg_key_bound.range`` / ``.dictionary`` / ``.none``, what bounded a
grouped aggregation's table at plan time: an integer key's exact value
range among its keys, dictionaries and booleans alone, or nothing;
``join_semi_side.source`` / ``.filtering``, which side a semi- or
anti-join built (its finish): the side it preserves, as the plan's
``build_left`` says, or the one that filters it. One increment a LAUNCH:
``join_expand_launches.first`` / ``.general`` / ``.fanout1``, the form a
probe batch's pairs took (every row's first candidate beside the batch
as it stands and, of a join that preserves its build side, nothing else
of it; offsets, sorts and gathers of both sides; the fanout-one form of
a batch no row of which has two). Read back once at such a join's finish
and added as counters too: ``semi_pairs_seen``, ``semi_pairs_kept``,
``semi_build_rows``, ``semi_build_flagged``; and ``df_reverse_rows_in``
/ ``df_reverse_rows_kept`` beside ``df_rows_in`` / ``df_rows_kept`` for
the filter in front of its probe. Outer joins: one increment an operator
(its finish), ``join_outer_side.build`` / ``.probe``, the side a LEFT
join preserved (the lookup, as the plan's ``build_left`` says, or the
batches that probe it; a FULL join counts under ``.probe``); read back
once at a LEFT join's finish (span ``sync.join.outer_flags``, stats
``build_rows``, ``unmatched``, ``preserved_rows``, ``build_slots``,
``preserved``: build or probe) and added as counters: ``join_outer_build_rows``, the live build
rows, and ``join_outer_unmatched_rows``, the preserved side's rows that
went out with NULLs (build rows no pair flagged, or probe rows nothing
matched). Of the sort path's batches
(``agg_ingest_path.sort``), ``agg_ordered_input.batches`` counts those
whose reduce found them in key order and skipped the key sort,
``agg_unordered_input.batches`` the others (both as the batch's flag is
read, one batch late). A join's key filter that the plan put UNDER the
aggregation of the side it filters (``JoinNode.filter_under_aggregate``,
PR 48): ``df_under_aggregate``, one increment an operator so placed (as
it is made); its ``op.DynamicFilterOperator.*`` calls and its
``sync.join.dynamic_filter_totals`` carry the stat ``under_aggregate``
(beside ``reverse``, where the keys are a preserved build side's, and
its rows are then in ``df_reverse_rows_in`` / ``_kept`` too);
``agg_filtered_input.batches``, the grouped batches of the aggregation
such a filter feeds, beside ``agg_ingest_batches``; and
``decorrelated_scalar_aggregates``, the correlated scalar aggregates the
analysis turned into a grouped subquery LEFT-joined back, found at plan
time, kept with the cached plan and counted once an execution (as the
plan is instantiated). ``chipbench/corr_trace.py`` reads them.
"""

from __future__ import annotations

import threading
from trino_tpu.analysis.witness import named_condition, named_lock, named_rlock
import time
import uuid
from typing import Any, Dict, List, Optional, Union

from jax.profiler import TraceAnnotation

# every event the program writes into the profiler's trace starts so
PROFILE_PREFIX = "tpusql."

# span kinds, in tree order (parent kind of each child kind)
KIND_QUERY = "query"
KIND_PHASE = "phase"
KIND_STAGE = "stage"
KIND_TASK = "task"
KIND_OPERATOR = "operator"

_PARENT_KIND = {
    KIND_PHASE: (KIND_QUERY,),
    KIND_STAGE: (KIND_QUERY,),
    KIND_TASK: (KIND_STAGE,),
    # the local runner has no tasks: its operators hang off `execute`
    KIND_OPERATOR: (KIND_TASK, KIND_PHASE),
}


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


class Span:
    """One timed node. Created via QueryTrace.span / Span.child only;
    the explicit parent handle IS the propagation mechanism."""

    __slots__ = (
        "name", "kind", "span_id", "trace_id", "parent_id",
        "start_s", "end_s", "attributes", "events", "_trace",
        "_annotation",
    )

    def __init__(self, trace: "QueryTrace", name: str, kind: str,
                 parent_id: Optional[str], **attributes):
        self.name = name
        self.kind = kind
        self.span_id = _new_id()
        self.trace_id = trace.trace_id
        self.parent_id = parent_id
        self.start_s = time.time()
        self.end_s: Optional[float] = None
        self.attributes: Dict[str, Any] = dict(attributes)
        self.events: List[dict] = []
        self._trace = trace
        self._annotation = None  # the profiler event, while entered

    @property
    def query_id(self) -> str:
        return self._trace.query_id

    def child(self, name: str, kind: str, **attributes) -> "Span":
        return self._trace.span(name, kind, parent=self, **attributes)

    def event(self, name: str, **attributes) -> None:
        """Timestamped annotation on this span (otel addEvent)."""
        self.events.append({
            "ts": time.time(), "name": name,
            "attributes": dict(attributes),
        })

    def set(self, **attributes) -> None:
        self.attributes.update(attributes)

    def end(self, end_s: Optional[float] = None) -> None:
        if self.end_s is None:
            self.end_s = time.time() if end_s is None else end_s

    @property
    def ended(self) -> bool:
        return self.end_s is not None

    @property
    def duration_s(self) -> float:
        return (self.end_s or time.time()) - self.start_s

    # `with parent.child("analyze", KIND_PHASE):` — exceptions annotate
    # the span and it still closes, so no failure path leaks open spans.
    # While a profiler trace runs the block is also an event of it,
    # entered and left on this thread.
    def __enter__(self) -> "Span":
        if TraceAnnotation.is_enabled():
            self._annotation = TraceAnnotation(
                f"{PROFILE_PREFIX}{self.kind}.{self.name.partition(' ')[0]}",
                query_id=self.query_id, span_id=self.span_id,
                parent_id=self.parent_id or "",
            )
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None and not self.ended:
            self.event("exception", type=type(exc).__name__,
                       message=str(exc)[:500])
            self.attributes.setdefault("error", True)
        self.end()
        annotation, self._annotation = self._annotation, None
        if annotation is not None:
            annotation.set_metadata(**{
                k: v for k, v in self.attributes.items()
                if isinstance(v, (int, float))
            })
            annotation.__exit__(exc_type, exc, tb)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "kind": self.kind,
            "span_id": self.span_id,
            "trace_id": self.trace_id,
            "parent_id": self.parent_id,
            "start_s": self.start_s,
            "end_s": self.end_s,
            "duration_ms": round(self.duration_s * 1000, 3),
            "attributes": dict(self.attributes),
            "events": [dict(e) for e in self.events],
        }


# -- leaf spans: profiler events only -----------------------------------


class _Off:
    """What every leaf span is while no profiler trace runs."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **stats) -> None:
        pass


OFF = _Off()


def profiling() -> bool:
    """Whether a profiler trace is running in this process."""
    return TraceAnnotation.is_enabled()


def host_span(name: str, **stats):
    """A leaf span for what happens thousands of times a statement: an
    event ``tpusql.<name>`` of the running profiler trace, or `OFF`."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(PROFILE_PREFIX + name, **stats)
    return OFF


# -- a statement's own account ------------------------------------------


# counters that carry a statement's id in their name
PER_QUERY_COUNTERS = "xla_compiles_by_query."


class StmtAccount:
    """What one statement did, counted on the thread that executes it
    whether or not a profiler runs (the module docstring has the
    vocabulary). Slots and no lock: only that thread writes."""

    __slots__ = (
        "query_id", "entered_ns", "wall_ns", "parse_ns", "plan_ns",
        "plan_hit", "instantiate_ns", "execute_ns", "release_ns", "cpu_ns",
        "syncs", "sync_ns", "sync_bytes", "sites", "counters", "tally",
    )

    def __init__(self, query_id: str):
        self.query_id = query_id
        self.entered_ns = time.perf_counter_ns()
        self.wall_ns = self.parse_ns = self.plan_ns = self.plan_hit = 0
        self.instantiate_ns = self.execute_ns = self.release_ns = 0
        self.cpu_ns = self.syncs = self.sync_ns = self.sync_bytes = 0
        self.sites: Dict[str, List[int]] = {}    # site -> [count, ns]
        self.counters: Dict[str, float] = {}
        # the operator call running on the thread, while a trace runs
        self.tally: Optional[OpTally] = None

    def sync(self, site: str, ns: int, nbytes: int) -> None:
        self.syncs += 1
        self.sync_ns += ns
        self.sync_bytes += nbytes
        row = self.sites.get(site)
        if row is None:
            self.sites[site] = [1, ns]
        else:
            row[0] += 1
            row[1] += ns

    def counter(self, name: str) -> float:
        return self.counters.get(name, 0.0)

    def stats(self) -> Dict[str, Union[int, float]]:
        """The account as flat numbers: `tpusql.stmt.done`'s stats."""
        out: Dict[str, Union[int, float]] = {
            "wall_us": self.wall_ns / 1e3, "parse_us": self.parse_ns / 1e3,
            "plan_us": self.plan_ns / 1e3, "plan_hit": self.plan_hit,
            "instantiate_us": self.instantiate_ns / 1e3,
            "execute_us": self.execute_ns / 1e3,
            "release_us": self.release_ns / 1e3, "cpu_us": self.cpu_ns / 1e3,
            "syncs": self.syncs, "sync_us": self.sync_ns / 1e3,
            "sync_bytes": self.sync_bytes,
        }
        for site, (n, ns) in self.sites.items():
            out[f"s.{site}.n"] = n
            out[f"s.{site}.us"] = ns / 1e3
        for name, v in self.counters.items():
            # a counter named after one statement would be a new stat
            # name with every statement
            if not name.startswith(PER_QUERY_COUNTERS):
                out[f"c.{name}"] = int(v) if v == int(v) else v
        return out


class _Thread(threading.local):
    """The one piece of per-thread state: the account of the statement
    this thread is executing. (A class default, because a thread-local's
    missing attribute costs 600 ns to ask for.)"""

    stmt: Optional[StmtAccount] = None


_THREAD = _Thread()


def running_statement() -> Optional[StmtAccount]:
    return _THREAD.stmt


class statement:
    """The runners' one way to open and close a statement's account on
    the thread that executes it: `with statement(trace.account, t):`
    from the trace made to the result handed back, `t` the
    `perf_counter_ns` at which `execute` was entered and the parse
    began. Writes `stmt.begin` and `stmt.done` into a running profiler
    trace."""

    __slots__ = ("_account", "_outer")

    def __init__(self, account: StmtAccount, entered_ns: int,
                 parse_ns: int = 0):
        account.entered_ns = entered_ns
        account.parse_ns = parse_ns
        self._account = account

    def __enter__(self) -> StmtAccount:
        self._outer = running_statement()
        _THREAD.stmt = self._account
        if TraceAnnotation.is_enabled():
            _instant("stmt.begin", query_id=self._account.query_id)
        return self._account

    def __exit__(self, *exc) -> None:
        account = self._account
        _THREAD.stmt = self._outer
        account.wall_ns = time.perf_counter_ns() - account.entered_ns
        if TraceAnnotation.is_enabled():
            _instant("stmt.done", query_id=account.query_id,
                     **account.stats())


def _instant(name: str, **stats) -> None:
    with TraceAnnotation(PROFILE_PREFIX + name, **stats):
        pass


# the phases whose wall `phase_span` adds to the running account
_PHASE_WALLS = {"plan": "plan_ns", "instantiate": "instantiate_ns",
                "release": "release_ns"}


class _Phase:
    """`phase.<name>` timed into the running statement's account, and an
    event of the profiler's trace where `annotation` is one."""

    __slots__ = ("_account", "_slot", "_annotation", "_t0")

    def __init__(self, account: StmtAccount, slot: str, annotation):
        self._account, self._slot = account, slot
        self._annotation = annotation

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        account = self._account
        setattr(account, self._slot, getattr(account, self._slot)
                + time.perf_counter_ns() - self._t0)

    def set_metadata(self, **stats) -> None:
        if "hit" in stats:
            self._account.plan_hit = int(stats["hit"])
        self._annotation.set_metadata(**stats)


def phase_span(query_span: Optional["Span"], name: str, **stats):
    """`host_span("phase.<name>")` carrying the statement's id, and the
    phase's wall in the account of the statement running on the thread
    (`plan`, `instantiate`, `release`); `OFF` where there is neither a
    trace nor such a statement."""
    span = OFF
    if TraceAnnotation.is_enabled():
        if query_span is not None:
            stats["query_id"] = query_span.query_id
        span = host_span("phase." + name, **stats)
    account = running_statement()
    slot = _PHASE_WALLS.get(name)
    if account is None or slot is None:
        return span
    return _Phase(account, slot, span)


class OpTally:
    """What one operator of one `Driver.run` did, counted only while a
    profiler trace runs; `record_operators` makes a span of it."""

    __slots__ = ("name", "stats", "calls", "batches", "host_syncs",
                 "host_sync_ns", "busy_ns", "first_s", "last_s")

    def __init__(self, name: str, stats: Optional[dict] = None):
        self.name = name
        # stats every `op.*` span of this operator carries: what the
        # class name does not say (an operator's `span_stats`)
        self.stats = stats or {}
        self.calls = self.batches = self.host_syncs = 0
        self.host_sync_ns = self.busy_ns = 0
        self.first_s: Optional[float] = None
        self.last_s = 0.0

    def call(self, method: str) -> "_OpCall":
        return _OpCall(self, method)


class _OpCall(TraceAnnotation):
    """`op.<OperatorClass>.<method>`: one operator call, timed into its
    operator's tally; `sync.*` spans inside it find the tally on the
    running statement's account."""

    def __init__(self, tally: OpTally, method: str):
        super().__init__(
            f"{PROFILE_PREFIX}op.{tally.name}.{method}", **tally.stats
        )
        self._tally = tally

    def __enter__(self):
        self._account = account = running_statement()
        if account is not None:
            self._outer = account.tally
            account.tally = self._tally
        if self._tally.first_s is None:
            self._tally.first_s = time.time()
        self._t0 = time.perf_counter_ns()
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        tally = self._tally
        tally.calls += 1
        tally.busy_ns += time.perf_counter_ns() - self._t0
        tally.last_s = time.time()
        if self._account is not None:
            self._account.tally = self._outer


class _Readback:
    """One device-to-host readback of the statement running on this
    thread, timed into its account; no profiler trace runs."""

    __slots__ = ("_account", "_site", "_nbytes", "_t0")

    def __init__(self, account: StmtAccount, site: str, nbytes: int):
        self._account, self._site, self._nbytes = account, site, nbytes

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self._account.sync(
            self._site, time.perf_counter_ns() - self._t0, self._nbytes)
        return False

    def set_metadata(self, **stats) -> None:
        pass


class _Sync(TraceAnnotation):
    """`sync.<site>`: the same readback while a trace runs, an event of
    it, counted into the account and the operator call it happens in."""

    def __init__(self, site: str, nbytes: int):
        super().__init__(f"{PROFILE_PREFIX}sync.{site}", nbytes=nbytes)
        self._site, self._nbytes = site, nbytes

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        account = running_statement()
        if account is None:
            return
        ns = time.perf_counter_ns() - self._t0
        account.sync(self._site, ns, self._nbytes)
        tally = account.tally
        if tally is not None:
            tally.host_syncs += 1
            tally.host_sync_ns += ns


def host_sync(site: str, nbytes: int = 0):
    """Around a device-to-host readback (`int()`, `bool()`,
    `np.asarray`, `device_get` of a device value): where the host waits
    for the device. `nbytes` is what comes back. Timed into the account
    of the statement running on this thread, trace or no trace; `OFF`
    where there is neither."""
    if TraceAnnotation.is_enabled():
        return _Sync(site, nbytes)
    account = running_statement()
    if account is None:
        return OFF
    return _Readback(account, site, nbytes)


def record_operators(parent: Optional[Span], tallies: List[OpTally]) -> None:
    """One `operator` span under `parent` for each operator that ran:
    the leaf spans of a traced statement, aggregated."""
    if parent is None:
        return
    for t in tallies:
        if not t.calls:
            continue
        span = parent.child(
            t.name, KIND_OPERATOR, calls=t.calls, batches=t.batches,
            host_syncs=t.host_syncs,
            host_sync_ms=round(t.host_sync_ns / 1e6, 3),
            busy_ms=round(t.busy_ns / 1e6, 3),
        )
        span.start_s = t.first_s
        span.end(t.last_s)


def wire_context(span: Span) -> dict:
    """Plain-data span context for TaskSpec (traceparent analogue).
    Strings only, so the wire codec ships it with no schema change."""
    return {"trace_id": span.trace_id, "span_id": span.span_id}


class QueryTrace:
    """All spans of one query. Coordinator-side it holds the full tree;
    worker-side (``QueryTrace.remote``) it holds only the spans recorded
    in that process, parented on the remote context, for export back."""

    def __init__(self, query_id: str, trace_id: Optional[str] = None):
        self.query_id = query_id
        self.trace_id = trace_id or _new_id()
        self._lock = named_lock("QueryTrace._lock")
        self._spans: List[Span] = []
        self._grafted: List[dict] = []
        # the statement's own numbers, fed by the thread that runs it
        self.account = StmtAccount(query_id)

    @classmethod
    def remote(cls, ctx: dict, query_id: str = "") -> "QueryTrace":
        """Worker-side recorder attached to a coordinator's context."""
        return cls(query_id, trace_id=ctx.get("trace_id"))

    def span(self, name: str, kind: str,
             parent: Union[Span, str, None] = None, **attributes) -> Span:
        pid = parent.span_id if isinstance(parent, Span) else parent
        s = Span(self, name, kind, pid, **attributes)
        with self._lock:
            self._spans.append(s)
        return s

    def graft(self, span_dicts: List[dict]) -> int:
        """Attach already-exported foreign spans (a worker's operator
        spans) into this trace. They carry their own parent ids — the
        coordinator handed those ids out via wire_context, so the tree
        closes. Duplicate span_ids (a task polled twice) are dropped."""
        with self._lock:
            seen = {s.span_id for s in self._spans}
            seen.update(d.get("span_id") for d in self._grafted)
            added = 0
            for d in span_dicts or []:
                if d.get("span_id") in seen:
                    continue
                seen.add(d.get("span_id"))
                d = dict(d)
                d["trace_id"] = self.trace_id
                self._grafted.append(d)
                added += 1
            return added

    def end_open_spans(self, end_s: Optional[float] = None) -> int:
        """Close every still-open span (abnormal-completion sweep so a
        failed/killed query still exports a fully-closed tree). Grafted
        worker spans are swept too: a task killed mid-stall ships its
        spans before its driver thread's own finally can close them."""
        n = 0
        stamp = time.time() if end_s is None else end_s
        with self._lock:
            spans = list(self._spans)
            for d in self._grafted:
                if d.get("end_s") is None:
                    d["end_s"] = max(stamp, d.get("start_s") or stamp)
                    d["duration_ms"] = round(
                        (d["end_s"] - (d.get("start_s") or d["end_s"]))
                        * 1000, 3,
                    )
                    n += 1
        for s in spans:
            if not s.ended:
                s.end(end_s)
                n += 1
        return n

    def export(self) -> dict:
        with self._lock:
            dicts = [s.to_dict() for s in self._spans]
            dicts += [dict(d) for d in self._grafted]
        dicts.sort(key=lambda d: (d.get("start_s") or 0.0))
        return {
            "trace_id": self.trace_id,
            "query_id": self.query_id,
            "spans": dicts,
            "account": self.account.stats(),
        }


# -- exports ------------------------------------------------------------


def chrome_trace(export: dict) -> List[dict]:
    """Render a QueryTrace.export() as Chrome trace-event JSON (the
    `traceEvents` list — load in Perfetto or chrome://tracing).

    Complete events (ph "X") carry each span; span annotations become
    instant events (ph "i") on the same track. Track (tid) assignment
    keeps the rendering readable: coordinator work (query + phases) on
    tid 0, each stage on its own track, each task attempt (plus its
    operator spans) on its own track — parallel attempts never overlap
    on one row, which "X" nesting cannot express."""
    spans = export.get("spans", [])
    if not spans:
        return []
    t0 = min(s.get("start_s") or 0.0 for s in spans)
    by_id = {s["span_id"]: s for s in spans}
    tids: Dict[str, int] = {}
    names: Dict[int, str] = {0: "coordinator"}
    next_tid = [1]

    def tid_of(span: dict) -> int:
        sid = span["span_id"]
        if sid in tids:
            return tids[sid]
        if span.get("kind") in (KIND_STAGE, KIND_TASK):
            t = next_tid[0]
            next_tid[0] += 1
            names[t] = span.get("name", span.get("kind"))
        else:
            parent = by_id.get(span.get("parent_id") or "")
            t = tid_of(parent) if parent is not None else 0
        tids[sid] = t
        return t

    events: List[dict] = []
    for s in spans:
        tid = tid_of(s)
        start = s.get("start_s") or t0
        end = s.get("end_s") or start
        events.append({
            "name": s.get("name", "?"),
            "cat": s.get("kind", "span"),
            "ph": "X",
            "ts": round((start - t0) * 1e6, 1),
            "dur": round(max(0.0, end - start) * 1e6, 1),
            "pid": 1,
            "tid": tid,
            "args": dict(s.get("attributes") or {},
                         span_id=s["span_id"]),
        })
        for ev in s.get("events") or []:
            events.append({
                "name": ev.get("name", "event"),
                "cat": "annotation",
                "ph": "i",
                "s": "t",
                "ts": round(((ev.get("ts") or start) - t0) * 1e6, 1),
                "pid": 1,
                "tid": tid,
                "args": dict(ev.get("attributes") or {}),
            })
    meta = [
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": t,
         "args": {"name": n}}
        for t, n in sorted(names.items())
    ]
    return meta + events


def check_span_invariants(export: dict) -> List[str]:
    """Structural invariants on an exported trace; returns violations
    (empty == healthy). Enforced by tests/test_tracing.py:

    - exactly one root, and it is the query span
    - every non-root parent_id resolves to a span in the trace
    - kind hierarchy holds: phase/stage under query, task under stage,
      operator under task
    - no span is left open (end_s set, end >= start)
    """
    spans = export.get("spans", [])
    violations: List[str] = []
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if not s.get("parent_id")]
    if len(roots) != 1:
        violations.append(
            f"expected exactly 1 root span, found {len(roots)}: "
            f"{[r.get('name') for r in roots]}"
        )
    for r in roots:
        if r.get("kind") != KIND_QUERY:
            violations.append(
                f"root span {r.get('name')!r} has kind "
                f"{r.get('kind')!r}, expected {KIND_QUERY!r}"
            )
    for s in spans:
        label = f"{s.get('kind')}:{s.get('name')}({s['span_id']})"
        pid = s.get("parent_id")
        parent = by_id.get(pid) if pid else None
        if pid and parent is None:
            violations.append(f"orphan span {label}: parent {pid} "
                              f"not in trace")
        want = _PARENT_KIND.get(s.get("kind"))
        if want is not None and parent is not None \
                and parent.get("kind") not in want:
            violations.append(
                f"span {label} parented on kind "
                f"{parent.get('kind')!r}, expected {' or '.join(want)}"
            )
        if s.get("end_s") is None:
            violations.append(f"unclosed span {label}")
        elif s.get("start_s") is not None \
                and s["end_s"] < s["start_s"] - 1e-6:
            violations.append(f"span {label} ends before it starts")
    return violations
