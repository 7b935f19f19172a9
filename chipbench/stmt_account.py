#!/usr/bin/env python3
"""Per-statement numbers from the statements' own accounts.

`trino_tpu/runtime/tracing.py` keeps one account a statement (walls of
its phases, the executing thread's CPU time, every readback by site,
every counter its thread moved) and, while a profiler trace runs as the
statement ENDS, writes it into the trace as one event `tpusql.stmt.done`
on the executing thread: the statement's numbers over its whole life,
whenever it began. A statement that starts inside a trace writes
`tpusql.stmt.begin`. This file reduces the two to seven per-statement
metrics (`METRICS`, STMT.md) from:

- the statements whose `stmt.done` lies inside the traced window (the
  `chipbench.window` annotation), each with its own record: the readers
  take MEDIANS over them, so a window that held one statement and a
  third of the next reads the same as one that held one and two thirds;
- the statement-equivalents in the window, by which a window total (the
  device's busy time, the host's launches) is divided: for every
  `stmt.done` in the trace the part of `[done - wall_us, done]` that
  lies inside the window over `wall_us`, and for a `stmt.begin` inside
  the window with no `stmt.done` in the trace, `(window end - begin)`
  over the median `wall_us` of the statements that ended in the window;
- the host's launches in the window: outermost `PjitFunction(*)`
  events, as `spans.py` counts them.

The seven metrics are NOT entries of `BENCHMARK.json`:
`tests/chipbench/test_dispatch_readers.py` line 52 holds the last two
entries of `per_layer` to be PR 26's, the driver takes an entry put
before them for a change to them, and `test_chipbench.py` wants a file
under `layer_metrics/` for every entry and no other, so nothing can be
added until a `benchmark` PR edits that line (as `mesh_trace.py` and
`agg_trace.py` found). Until then: `python3 chipbench/stmt_account.py
<cell>` after a `--trace 1` run prints all of it for the cell's last
traced run, and `read(run)` is what seven reader files under
`layer_metrics/` would return, one line each.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import sys
from typing import Callable, Dict, List, Optional

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import spans  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402

DONE = spans.PROGRAM + "stmt.done"
BEGIN = spans.PROGRAM + "stmt.begin"
SITE, COUNTER = "s.", "c."
# how far two readings of one instant may lie apart (seconds in doubles)
CLOCKS_S = 1e-5


def overlap(a_lo: float, a_hi: float, b_lo: float, b_hi: float) -> float:
    return max(0.0, min(a_hi, b_hi) - max(a_lo, b_lo))


def reduce(st: spans.SpanTrace) -> dict:
    """The statements that ended in the traced window, with their
    accounts, and what a window total is divided by."""
    windows = [a for a in st.yardstick.annotations
               if a.name == trace_mod.WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {trace_mod.WINDOW} span")
    lo, hi = windows[0].start, windows[0].end
    events = [e for line in st.lines for e in line]
    calls = [a for a in st.yardstick.annotations if a.name == trace_mod.ENGINE]
    done = sorted((e for e in events if e.name == DONE), key=lambda e: e.end)
    ended = {str(e.stats.get("query_id", "")) for e in done}
    statements = [
        {"done_s": e.end - lo, "outside_us": _outside_us(e, calls), **e.stats}
        for e in done if lo <= e.end <= hi
    ]
    equivalents = sum(
        overlap(e.end - 1e-6 * float(e.stats["wall_us"]), e.end, lo, hi)
        / (1e-6 * float(e.stats["wall_us"]))
        for e in done if float(e.stats.get("wall_us", 0)) > 0
    )
    unfinished = [
        e for e in events if e.name == BEGIN and lo <= e.start <= hi
        and str(e.stats.get("query_id", "")) not in ended
    ]
    if unfinished and statements:
        wall_s = 1e-6 * statistics.median(
            float(s["wall_us"]) for s in statements)
        equivalents += sum(min((hi - e.start) / wall_s, 1.0)
                           for e in unfinished)
    launches = sum(
        1 for line in st.lines for e in spans.nest(line, lo, hi)[0]
        if e.name.startswith(spans.DISPATCH) and e.outermost
    )
    return {
        "window_s": hi - lo, "stmt_events": len(done),
        "statements": statements, "unfinished": len(unfinished),
        "equivalents": equivalents, "launches": launches,
    }


def _outside_us(done: spans.Event, calls) -> Optional[float]:
    """The benchmark's own clock around the same statement: the wall of
    the `chipbench.runner.execute` annotation that holds `done` and
    began nearest before the statement did (`done - wall_us`). None
    where that call began before the trace."""
    began = done.end - 1e-6 * float(done.stats.get("wall_us", 0))
    around = [a for a in calls
              if a.start <= began + CLOCKS_S and done.end <= a.end + CLOCKS_S]
    if not around:
        return None
    call = max(around, key=lambda a: a.start)
    return 1e6 * (call.end - call.start)


# -- what a reader would ask -------------------------------------------------------


def for_run(run) -> Optional[dict]:
    """The reduction of the traced run `run` (a `harness.RunData` whose
    `trace` is set); the run's trace is found as `spans.for_run` finds
    it. {} where it cannot be found or is another run's, None where it
    was read and holds no `stmt.done` (a program from before the
    account); either way one `NO_STMT_ACCOUNT` line says so."""
    window_s = run.trace["window_s"]
    path = spans.newest_xplane(spans.TRACE_ROOT)
    if path is None:
        return _no_account(path, f"no .xplane.pb under {spans.TRACE_ROOT}", {})
    try:
        reduced = reduce(spans.load(path))
    except ValueError as e:
        return _no_account(path, str(e), {})
    if not math.isclose(reduced["window_s"], window_s, rel_tol=1e-9):
        return _no_account(path, (
            f"the newest trace's window is {reduced['window_s']} s, the "
            f"run's {window_s} s: not this run's trace"), {})
    if not reduced["stmt_events"]:
        return _no_account(path, f"no {DONE} event in the run's trace", None)
    return reduced


def _no_account(path: Optional[str], why: str, answer: Optional[dict]):
    """`answer` is what `read` gets: {} reads as 0.0 everywhere, None
    leaves the metrics out (`spans._no_spans` has the same two)."""
    print(json.dumps({
        "phase": "NO_STMT_ACCOUNT", "trace": path, "why": why,
        "note": "this run's stmt_* metrics are 0.0 or left out",
    }), flush=True)
    return answer


def offcpu_us(s: dict) -> float:
    """What a statement's `execute` spent neither on the executing
    thread's CPU nor inside a readback: runnable and not running."""
    return max(float(s["execute_us"]) - float(s["cpu_us"])
               - float(s["sync_us"]), 0.0)


# the first five: medians over the statements that ended in the window of
# the statement's own record; the last two: a total of the window over
# the statement-equivalents in it
MEDIANS: Dict[str, Callable[[dict], float]] = {
    "stmt_execute_ms": lambda s: float(s["execute_us"]) / 1e3,
    "stmt_syncs": lambda s: float(s["syncs"]),
    "stmt_sync_ms": lambda s: float(s["sync_us"]) / 1e3,
    "stmt_cpu_ms": lambda s: float(s["cpu_us"]) / 1e3,
    "stmt_offcpu_ms": lambda s: offcpu_us(s) / 1e3,
}
METRICS = (*MEDIANS, "stmt_device_ms", "stmt_launches")


def metrics(reduced: dict, busy_s: Optional[float]) -> Dict[str, float]:
    """The seven of one reduction; `busy_s` is the yardstick's device
    busy time of the same window (the mean over the chips used). A
    metric with nothing to read (no statement ended in the window, no
    equivalents, no device plane) is left out."""
    rows, equivalents = reduced["statements"], reduced["equivalents"]
    out = {}
    if rows:
        out = {name: statistics.median(map(value, rows))
               for name, value in MEDIANS.items()}
    if equivalents:
        if busy_s is not None:
            out["stmt_device_ms"] = 1e3 * busy_s / equivalents
        out["stmt_launches"] = reduced["launches"] / equivalents
    return out


def read(run) -> Dict[str, float]:
    """`metrics` of a traced run, answering as `spans.read_total` does:
    {} without a trace or a completed statement, and where the run's own
    trace holds no `stmt.done`; 0.0 for all seven where the run's trace
    cannot be found or is another run's."""
    if run.trace is None or not run.trace_completed:
        return {}
    reduced = for_run(run)
    if reduced is None:
        return {}
    if not reduced:
        return dict.fromkeys(METRICS, 0.0)
    return metrics(reduced, run.trace["busy_s"])


# -- the table -----------------------------------------------------------------------


def table(reduced: dict, busy_s: Optional[float] = None) -> str:
    rows = reduced["statements"]
    out = [f"window {reduced['window_s']:.6f} s, {reduced['stmt_events']} "
           f"stmt.done in the trace, {len(rows)} inside the window, "
           f"{reduced['unfinished']} begun inside it and not ended"]
    out.append(f"statement-equivalents in the window: "
               f"{reduced['equivalents']:.6f}")
    out.append(f"launches: {reduced['launches']} in the window")
    if busy_s is not None:
        out.append(f"device busy: {busy_s:.6f} s in the window")
    for name, value in metrics(reduced, busy_s).items():
        out.append(f"{value:16.4f}  {name}")
    if not rows:
        return "\n".join(out)

    def median(key: str) -> float:
        return statistics.median(float(s.get(key, 0)) for s in rows)

    out.append("")
    out.append(f"{'done_s':>9} {'wall_ms':>10} {'execute_ms':>10} {'cpu_ms':>9} "
               f"{'sync_ms':>10} {'offcpu_ms':>9} {'syncs':>6}  query_id")
    for s in rows:
        out.append(
            f"{s['done_s']:9.4f} {float(s['wall_us']) / 1e3:10.3f} "
            f"{float(s['execute_us']) / 1e3:10.3f} {float(s['cpu_us']) / 1e3:9.3f} "
            f"{float(s['sync_us']) / 1e3:10.3f} {offcpu_us(s) / 1e3:9.3f} "
            f"{int(s['syncs']):6d}  {s.get('query_id', '')}")
    outside = [float(s["outside_us"]) - float(s["wall_us"]) for s in rows
               if s.get("outside_us") is not None]
    if outside:
        out.append("")
        out.append(
            f"the benchmark's clock around runner.execute minus wall_us: median "
            f"{statistics.median(outside) / 1e3:.3f} ms, most "
            f"{max(outside) / 1e3:.3f} ms, over {len(outside)} statements")
    out.append("")
    out.append("medians over those statements")
    for key in ("wall_us", "parse_us", "plan_us", "plan_hit", "instantiate_us",
                "execute_us", "release_us", "cpu_us", "syncs", "sync_us",
                "sync_bytes"):
        out.append(f"{median(key):16.3f}  {key}")
    keys = sorted({k for s in rows for k in s})
    sites = sorted({k[len(SITE):-2] for k in keys
                    if k.startswith(SITE) and k.endswith(".n")},
                   key=lambda site: -median(f"{SITE}{site}.us"))
    out.append("")
    out.append(f"{'n':>8} {'ms':>12}  readbacks by site, median a statement")
    for site in sites:
        out.append(f"{median(f'{SITE}{site}.n'):8.1f} "
                   f"{median(f'{SITE}{site}.us') / 1e3:12.3f}  {site}")
    out.append("")
    out.append(f"{'value':>16}  counters the statement's thread moved, median")
    for key in keys:
        if key.startswith(COUNTER):
            out.append(f"{median(key):16.1f}  {key[len(COUNTER):]}")
    return "\n".join(out)


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 chipbench/stmt_account.py <cell>", file=sys.stderr)
        return 2
    path = spans.newest_xplane(os.path.join(spans.TRACE_ROOT, argv[0]))
    if path is None:
        print(f"no traced run of {argv[0]} under {spans.TRACE_ROOT}",
              file=sys.stderr)
        return 1
    print(path)
    st = spans.load(path)
    busy_s = None
    if st.yardstick.device_ops:
        busy_s = trace_mod.reduce(st.yardstick)["busy_s"]
    print(table(reduce(st), busy_s))
    return 0


if __name__ == "__main__":
    sys.exit(main())
