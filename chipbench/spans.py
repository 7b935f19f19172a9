#!/usr/bin/env python3
"""Reduction of the program's own spans in a profiler trace.

While a `jax.profiler` trace runs, `trino_tpu/runtime/tracing.py` writes
the program's spans into it as host events named `tpusql.<kind>.<name>`
(SPANS.md lists them). They lie in the `/host:CPU` plane of the same
`.xplane.pb` as the device's events, one line per thread, so they are on
the clock `trace.py` already puts the device on (`trace.clock_shift`).
This file reduces them, and the host's `PjitFunction(<program>)` events,
to what the `program_span` readers in `layer_metrics/` return:

- per span name: count, wall and self time (wall minus what its
  children on the same thread line cover), clipped to the traced window
  (the `chipbench.window` annotation);
- per statement (`query_id`): the same sums, a leaf span belonging to
  the `tpusql.query.*` event that encloses it on its line;
- the device's idle time inside the engine (a `chipbench.runner.execute`
  event covers it: the yardstick's `total.in_engine`), split three ways
  by what the program's threads were doing (`idle_split`);
- host dispatches per program and device time per program.

`python3 chipbench/spans.py <cell>` prints all of it for the cell's last
traced run. A reader gets it through `for_run(run)`: one reduction per
run, however many readers ask.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import math
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import trace as trace_mod  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# where `harness.run_cell` leaves a traced run's profile: <cell>/ below
TRACE_ROOT = os.path.join(os.path.dirname(HERE), ".cache", "chipbench", "trace")

PROGRAM = "tpusql."
DISPATCH = "PjitFunction("
QUERY = PROGRAM + "query."
EXECUTE = PROGRAM + "phase.execute"
SYNC = PROGRAM + "sync."
QUEUED = PROGRAM + "server.queued"
RESPOND = PROGRAM + "server.respond"
PLAN = tuple(PROGRAM + f"phase.{p}" for p in ("parse", "plan", "instantiate"))
# a thread inside one of these, and outside any sync, is working
WORK = tuple(PROGRAM + k for k in ("phase.", "op.", "scan.", "result."))
HOST_WORKING, IN_SYNC, UNATTRIBUTED = "host_working", "in_sync", "unattributed"

Intervals = Tuple[np.ndarray, np.ndarray]   # sorted, disjoint (starts, ends)


@dataclasses.dataclass
class Event:
    name: str
    start: float       # seconds, host clock
    end: float
    stats: Dict[str, object]
    # set by `nest`: the part no child on the line covers, the enclosing
    # statement, whether a `phase.execute` encloses it
    self_s: float = 0.0
    query_id: Optional[str] = None
    in_execute: bool = False
    outermost: bool = True     # not a PjitFunction inside its own twin


@dataclasses.dataclass
class SpanTrace:
    yardstick: trace_mod.Trace
    # per host thread line, its `tpusql.*` and `PjitFunction(*)` events
    lines: List[List[Event]]
    # (program name, start, end) of every program run, device clock
    programs: List[Tuple[str, float, float]]


def is_work(name: str) -> bool:
    return name.startswith(WORK) and name != EXECUTE


def program_of(name: str) -> str:
    """`jit_f` from `jit_f(123456)` (XLA Modules) or `PjitFunction(f)`."""
    if name.startswith(DISPATCH):
        return name[len(DISPATCH):].rstrip(")")
    return re.sub(r"\(\d+\)$", "", name)


def newest_xplane(root: str) -> Optional[str]:
    found = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(path: str) -> SpanTrace:
    from jax.profiler import ProfileData

    out = SpanTrace(trace_mod.load(path), [], [])
    for plane in ProfileData.from_file(path).planes:
        if plane.name == trace_mod.HOST_PLANE:
            for line in plane.lines:
                events = [
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
                    for e in line.events
                    if e.name.startswith((PROGRAM, DISPATCH))
                ]
                if events:
                    out.lines.append(events)
        elif plane.name.startswith(trace_mod.DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == trace_mod.MODULES_LINE:
                    out.programs += [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events
                    ]
    return out


# -- interval sets ---------------------------------------------------------------


def intervals(pairs) -> Intervals:
    pairs = list(pairs)
    return trace_mod.union(np.asarray([a for a, _ in pairs], dtype=float),
                           np.asarray([b for _, b in pairs], dtype=float))


def _inside(u: Intervals, x: np.ndarray) -> np.ndarray:
    if len(u[0]) == 0:
        return np.zeros(len(x), dtype=bool)
    i = np.searchsorted(u[0], x, "right") - 1
    return (i >= 0) & (x < u[1][np.maximum(i, 0)])


def combine(a: Intervals, b: Intervals, keep) -> Intervals:
    """The points where `keep(in a, in b)` holds."""
    edges = np.unique(np.concatenate([a[0], a[1], b[0], b[1]]))
    if len(edges) < 2:
        return np.zeros(0), np.zeros(0)
    mid = (edges[:-1] + edges[1:]) / 2
    sel = keep(_inside(a, mid), _inside(b, mid))
    return trace_mod.union(edges[:-1][sel], edges[1:][sel])


def both(a: Intervals, b: Intervals) -> Intervals:
    return combine(a, b, lambda x, y: x & y)


def minus(a: Intervals, b: Intervals) -> Intervals:
    return combine(a, b, lambda x, y: x & ~y)


def either(a: Intervals, b: Intervals) -> Intervals:
    return trace_mod.union(np.concatenate([a[0], b[0]]),
                           np.concatenate([a[1], b[1]]))


def measure(u: Intervals) -> float:
    return float(np.sum(u[1] - u[0]))


# -- nesting ---------------------------------------------------------------------


def nest(line: List[Event], lo: float, hi: float):
    """Clip a thread line's events to [lo, hi] and nest them. Returns
    (the clipped events with `self_s`, `query_id`, `in_execute` and
    `outermost` set, {name: [(start, end) no child covers]})."""
    clipped = [
        dataclasses.replace(e, start=max(e.start, lo), end=min(e.end, hi))
        for e in line if min(e.end, hi) > max(e.start, lo)
    ]
    clipped.sort(key=lambda e: (e.start, -e.end))
    uncovered: Dict[str, List[Tuple[float, float]]] = {}
    stack: List[list] = []     # [event, where its uncovered part resumes]

    def leave():
        e, cursor = stack.pop()
        if e.end > cursor:
            uncovered.setdefault(e.name, []).append((cursor, e.end))
            e.self_s += e.end - cursor

    for e in clipped:
        while stack and stack[-1][0].end <= e.start:
            leave()
        if stack:
            parent, cursor = stack[-1]
            if e.start > cursor:
                uncovered.setdefault(parent.name, []).append((cursor, e.start))
                parent.self_s += e.start - cursor
            # a child that outlasts its parent (clock jitter) is cut to it
            e.end = min(e.end, parent.end)
            stack[-1][1] = max(cursor, e.end)
            e.query_id = parent.query_id
            e.in_execute = parent.in_execute or parent.name == EXECUTE
            e.outermost = not (parent.name == e.name
                               and e.name.startswith(DISPATCH))
        if e.name.startswith(QUERY):
            e.query_id = str(e.stats.get("query_id", ""))
        stack.append([e, e.start])
    while stack:
        leave()
    return clipped, uncovered


# -- the reduction -----------------------------------------------------------------


def idle_split(idle_in_engine: Intervals, lines: List[List[Event]]) -> dict:
    """Seconds of `idle_in_engine` by what the program's threads did: an
    instant is `host_working` if some thread is inside a work span and
    outside any `sync.*`; else `in_sync` if some thread is inside a
    `sync.*`; else `unattributed` (only `query.*` or `phase.execute`, or
    nothing of the program, covers it)."""
    empty = (np.zeros(0), np.zeros(0))
    working, syncing = empty, empty
    for line in lines:
        sync = intervals((e.start, e.end) for e in line
                         if e.name.startswith(SYNC))
        work = intervals((e.start, e.end) for e in line if is_work(e.name))
        working = either(working, minus(work, sync))
        syncing = either(syncing, sync)
    total = measure(idle_in_engine)
    host_working = measure(both(idle_in_engine, working))
    in_sync = measure(both(idle_in_engine, minus(syncing, working)))
    return {HOST_WORKING: host_working, IN_SYNC: in_sync,
            UNATTRIBUTED: max(total - host_working - in_sync, 0.0),
            "in_engine": total}


def reduce(st: SpanTrace) -> dict:
    """Everything the readers and the table take, over the traced window."""
    windows = [a for a in st.yardstick.annotations
               if a.name == trace_mod.WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {trace_mod.WINDOW} span")
    lo, hi = windows[0].start, windows[0].end
    shift = trace_mod.clock_shift(st.yardstick)

    lines, uncovered = [], {}
    for line in st.lines:
        clipped, part = nest(line, lo, hi)
        lines.append(clipped)
        for name, pairs in part.items():
            uncovered.setdefault(name, []).extend(pairs)
    events = [e for line in lines for e in line]

    spans: Dict[str, dict] = {}
    statements: Dict[str, dict] = {}
    for e in events:
        row = spans.setdefault(e.name, {"count": 0, "wall_s": 0.0, "self_s": 0.0})
        if not e.outermost:
            row["self_s"] += e.self_s
            continue
        row["count"] += 1
        row["wall_s"] += e.end - e.start
        row["self_s"] += e.self_s
        if e.query_id and e.name.startswith(PROGRAM):
            s = statements.setdefault(e.query_id, {
                "wall_s": 0.0, "plan_s": 0.0, "syncs": 0, "sync_s": 0.0,
                "op_s": 0.0, "result_s": 0.0})
            if e.name.startswith(QUERY):
                s["wall_s"] += e.end - e.start
            elif e.name in PLAN:
                s["plan_s"] += e.end - e.start
            elif e.name.startswith(SYNC):
                s["syncs"] += 1
                s["sync_s"] += e.end - e.start
            elif e.name.startswith(PROGRAM + "op."):
                s["op_s"] += e.end - e.start
            elif e.name == PROGRAM + "result.fetch":
                s["result_s"] += e.end - e.start

    def wall(pred) -> float:
        return sum(r["wall_s"] for n, r in spans.items() if pred(n))

    # what runs off the executing thread's CPU and outside any readback:
    # per `phase.execute`, wall - cpu_ns - wall of the `sync.*` inside it
    executes = [e for e in events if e.name == EXECUTE]
    execute_s = sum(e.end - e.start for e in executes)
    offcpu_s = execute_s - sum(
        float(e.stats.get("cpu_ns", 0)) * 1e-9 for e in executes
    ) - sum(e.end - e.start for e in events
            if e.name.startswith(SYNC) and e.in_execute)
    totals = {
        "queued_s": wall(lambda n: n == QUEUED) + 1e-6 * sum(
            float(e.stats.get("handoff_us", 0)) for e in events
            if e.name == QUEUED),
        "result_wait_s": 1e-6 * sum(
            float(e.stats["since_finished_us"]) for e in events
            if e.name == RESPOND and "since_finished_us" in e.stats),
        "plan_s": wall(lambda n: n in PLAN),
        "syncs": sum(r["count"] for n, r in spans.items() if n.startswith(SYNC)),
        "sync_s": wall(lambda n: n.startswith(SYNC)),
        "execute_s": execute_s,
        "execute_self_s": spans.get(EXECUTE, {}).get("self_s", 0.0),
        "offcpu_s": max(offcpu_s, 0.0),
        "scan_misses": sum(
            r["count"] for n, r in spans.items()
            if n in (PROGRAM + "scan.host_filter", PROGRAM + "scan.to_device")),
    }

    # the device's idle time inside the engine, as `trace.reduce` has it,
    # averaged over the chips used
    engine = intervals((a.start, a.end) for a in st.yardstick.annotations
                       if a.name == trace_mod.ENGINE)
    window = intervals([(lo, hi)])
    idle = {HOST_WORKING: 0.0, IN_SYNC: 0.0, UNATTRIBUTED: 0.0, "in_engine": 0.0}
    idle_by_span: Dict[str, float] = {}
    planes = sorted(st.yardstick.device_ops.items())
    for _plane, (_names, starts, ends) in planes:
        busy = trace_mod.union(*trace_mod._clip(starts + shift, ends + shift, lo, hi))
        idle_in_engine = both(minus(window, busy), engine)
        for k, v in idle_split(idle_in_engine, lines).items():
            idle[k] += v / len(planes)
        for name, pairs in uncovered.items():
            s = measure(both(idle_in_engine, intervals(pairs)))
            if s:
                idle_by_span[name] = idle_by_span.get(name, 0.0) + s / len(planes)

    dispatches: Dict[str, dict] = {}
    for name, row in spans.items():
        if name.startswith(DISPATCH):
            dispatches[program_of(name)] = {
                "count": row["count"], "host_s": row["wall_s"]}
    programs: Dict[str, dict] = {}
    for name, start, end in st.programs:
        a, b = max(start + shift, lo), min(end + shift, hi)
        if b > a:
            row = programs.setdefault(program_of(name), {"runs": 0, "device_s": 0.0})
            row["runs"] += 1
            row["device_s"] += (b - a) / max(len(planes), 1)
    return {
        "window_s": hi - lo, "clock_shift_s": shift,
        "program_events": sum(1 for e in events if e.name.startswith(PROGRAM)),
        "spans": spans, "statements": statements, "totals": totals,
        "idle": idle, "idle_by_span": idle_by_span,
        "dispatches": dispatches, "programs": programs,
    }


# -- what a reader asks ------------------------------------------------------------


def for_run(run) -> Optional[dict]:
    """The reduction of the traced run `run` (a `harness.RunData` whose
    `trace` is set), made once and kept on `run`. None where no program
    span can be read: no trace under `TRACE_ROOT` whose window has the
    length of `run.trace["window_s"]` (stale, or another run's), or a
    trace that holds no `tpusql.` event (a program from before the
    spans). Either way one `NO_PROGRAM_SPANS` line says so, loudly, so
    that a reader's zero is never taken for a result."""
    if not hasattr(run, "_span_reduction"):
        run._span_reduction = _reduce_for(run.trace["window_s"])
    return run._span_reduction


def _reduce_for(window_s: float) -> Optional[dict]:
    path = newest_xplane(TRACE_ROOT)
    if path is None:
        return _no_spans(path, f"no .xplane.pb under {TRACE_ROOT}", {})
    try:
        reduced = reduce(load(path))
    except ValueError as e:
        return _no_spans(path, str(e), {})
    if not math.isclose(reduced["window_s"], window_s, rel_tol=1e-9):
        return _no_spans(path, (
            f"the newest trace's window is {reduced['window_s']} s, the "
            f"run's {window_s} s: not this run's trace"), {})
    if not reduced["program_events"]:
        return _no_spans(path, f"no {PROGRAM}* event in the run's trace", None)
    return reduced


def _no_spans(path: Optional[str], why: str, answer: Optional[dict]):
    """`answer` is what the readers get: {} reads as 0.0 everywhere (the
    run's trace was not found), None leaves the metrics out (the run's
    trace was read and the program wrote no span into it)."""
    print(json.dumps({
        "phase": "NO_PROGRAM_SPANS", "trace": path, "why": why,
        "note": "this run's program_span metrics are 0.0 or left out",
    }), flush=True)
    return answer


def read_total(run, key: str, scale: float = 1.0) -> Optional[float]:
    """`totals[key]` of the run's traced window over the statements that
    completed in it, times `scale`. None without a trace or a statement,
    and where the run's own trace holds no program span; 0.0 where the
    run's trace cannot be found."""
    if run.trace is None or not run.trace_completed:
        return None
    reduced = for_run(run)
    if reduced is None:
        return None
    found = reduced.get("totals", {}).get(key, 0.0)
    return scale * found / len(run.trace_completed)


def read_idle_share(run, key: str) -> Optional[float]:
    """`idle[key]` as a share of the traced window, %; as `read_total`."""
    if run.trace is None or not run.trace_completed:
        return None
    reduced = for_run(run)
    if reduced is None:
        return None
    return 100.0 * reduced.get("idle", {}).get(key, 0.0) / run.trace["window_s"]


# -- the table -----------------------------------------------------------------------


def table(reduced: dict, top: int = 20) -> str:
    out = [f"window {reduced['window_s']:.6f} s, device clock shifted by "
           f"{reduced['clock_shift_s']:.6f} s, "
           f"{reduced['program_events']} program events"]

    def rows(title, header, items):
        out.append("")
        out.append(title)
        out.append(header)
        out.extend(items)

    spans = sorted(reduced["spans"].items(), key=lambda kv: -kv[1]["self_s"])
    rows("spans by self time", f"{'self_s':>10} {'wall_s':>10} {'count':>7}  name",
         [f"{r['self_s']:10.6f} {r['wall_s']:10.6f} {r['count']:7d}  {n}"
          for n, r in spans[:top]])
    idle = reduced["idle"]
    rows("device idle inside the engine, s",
         f"{'seconds':>10}  what the program's threads did",
         [f"{idle[k]:10.6f}  {k}"
          for k in ("in_engine", HOST_WORKING, IN_SYNC, UNATTRIBUTED)])
    rows("device idle inside the engine by innermost covering span "
         "(each thread's counts)", f"{'seconds':>10}  name",
         [f"{s:10.6f}  {n}" for n, s in sorted(
             reduced["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]])
    rows("host dispatches", f"{'host_s':>10} {'count':>7}  program",
         [f"{r['host_s']:10.6f} {r['count']:7d}  {n}" for n, r in sorted(
             reduced["dispatches"].items(), key=lambda kv: -kv[1]["host_s"])[:top]])
    rows("device time by program", f"{'device_s':>10} {'runs':>7}  program",
         [f"{r['device_s']:10.6f} {r['runs']:7d}  {n}" for n, r in sorted(
             reduced["programs"].items(), key=lambda kv: -kv[1]["device_s"])[:top]])
    rows("totals over the window", f"{'value':>14}  name",
         [f"{v:14.6f}  {k}" for k, v in reduced["totals"].items()])
    return "\n".join(out)


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 chipbench/spans.py <cell>", file=sys.stderr)
        return 2
    path = newest_xplane(os.path.join(TRACE_ROOT, argv[0]))
    if path is None:
        print(f"no traced run of {argv[0]} under {TRACE_ROOT}", file=sys.stderr)
        return 1
    print(path)
    print(table(reduce(load(path))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
