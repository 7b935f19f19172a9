#!/usr/bin/env python3
"""The large-state aggregation's and the joins' per-layer readings, from
a traced run of a cell (written for Q18 at SF10, `chipbench/Q18.md`;
any one-chip cell's trace can be read).

`python3 chipbench/agg_trace.py <cell>` after a `--trace 1` run of the
cell, as `spans.py` and `mesh_trace.py` are used. It reads the run's
`.xplane.pb` through `spans.load` / `spans.reduce` and prints one JSON
object with four metrics (`metrics`):

- `agg_merge_ms_per_stmt`: host wall inside the `tpusql.agg.merge` spans
  (one around every launch of `_merge_group_states`: a fold of
  `FOLD_STATES` group states, or an aggregation's last merge) over the
  statements that completed in the traced window;
- `agg_op_share_pct`: wall inside `tpusql.op.HashAggregationOperator.*`
  over the wall inside `tpusql.phase.execute`. A span is recorded only
  if it began and ended while the trace ran, and a Q18 at SF10 (2.13 s)
  outlasts what is left of the traced 3 s beside its neighbours: where
  the trace holds operator spans and no `phase.execute`, the traced
  window's own seconds are the denominator
  (one closed-loop stream is inside `phase.execute` all but the
  protocol's 6 ms a statement), and `agg_op_share_of` says which it was;
- `join_probe_rows_per_stmt`: the slots of the batches the join probes
  took (stat `probe_slots` of `tpusql.sync.join.match_total`, one span a
  probe batch), a statement: a batch the dynamic filter packed counts
  its live rows rounded up to a power of two, one it did not counts the
  scan's whole batch;
- `agg_device_share_pct`: device seconds of the aggregation's programs
  (`jit__agg_ingest*`, `jit__merge_group_states*` on the `XLA Modules`
  line) over the device's busy seconds.

They are NOT entries of `BENCHMARK.json`: three tests pin its
`per_layer` list (`chipbench/Q18.md`), and a PR that may edit no file
that was here can append nothing. A program from before the spans or
stats (the parent of PR 33) gives None for what it cannot show; nothing
here raises on such a trace.
"""

from __future__ import annotations

import json
import os
import sys
from typing import List, Optional

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import spans  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402

MERGE = spans.PROGRAM + "agg.merge"
AGG_OP = spans.PROGRAM + "op.HashAggregationOperator."
MATCH_TOTAL = spans.SYNC + "join.match_total"
DYNAMIC_FILTER = spans.SYNC + "join.dynamic_filter"
RESULT_FETCH = spans.PROGRAM + "result.fetch"
AGG_PROGRAMS = ("jit__agg_ingest", "jit__merge_group_states")


def window_events(st: spans.SpanTrace) -> List[spans.Event]:
    """The program's events that END inside the traced window: a stat
    belongs to the launch or readback that set it, once."""
    windows = [a for a in st.yardstick.annotations
               if a.name == trace_mod.WINDOW]
    if not windows:
        return []
    lo, hi = windows[0].start, windows[0].end
    return [e for line in st.lines for e in line if lo <= e.end <= hi]


def metrics(st: spans.SpanTrace) -> dict:
    reduced = spans.reduce(st)
    yard = trace_mod.reduce(st.yardstick)
    events = window_events(st)
    # the yardstick counts the client's annotations; one that began
    # before the trace did is not in it (a statement of `sf10.q18`
    # outlasts the traced window), so the program's own mark of a
    # statement's end stands in: one `result.fetch` a statement
    statements = yard["statements_in_window"] or sum(
        1 for e in events if e.name == RESULT_FETCH)
    rows = reduced["spans"]

    def per_statement(total: Optional[float]) -> Optional[float]:
        return None if total is None or not statements else total / statements

    merge_s = rows[MERGE]["wall_s"] if MERGE in rows else None
    agg_s = sum(r["wall_s"] for n, r in rows.items() if n.startswith(AGG_OP))
    execute_s, share_of = reduced["totals"]["execute_s"], "phase.execute"
    if not execute_s and agg_s:
        execute_s, share_of = yard["window_s"], "window"
    probes = [e for e in events
              if e.name == MATCH_TOTAL and "probe_slots" in e.stats]
    kept = [e for e in events
            if e.name == DYNAMIC_FILTER and "rows" in e.stats]
    merges = [e for e in events if e.name == MERGE]
    device_s = sum(r["device_s"] for n, r in reduced["programs"].items()
                   if n.startswith(AGG_PROGRAMS))
    return {
        "statements_in_window": statements,
        "agg_merge_ms_per_stmt":
            per_statement(None if merge_s is None else 1e3 * merge_s),
        "agg_op_share_pct": 100.0 * agg_s / execute_s if execute_s else None,
        "agg_op_share_of": share_of if execute_s else None,
        "join_probe_rows_per_stmt": per_statement(
            float(sum(int(e.stats["probe_slots"]) for e in probes))
            if probes else None),
        "agg_device_share_pct":
            100.0 * device_s / yard["busy_s"] if yard["busy_s"] else None,
        # beside them, not metrics: what the spans' stats say
        "merges": [
            {k: int(e.stats[k]) for k in ("states", "slots_in", "cap", "retry")
             if k in e.stats} for e in merges
        ],
        "probe_batches": len(probes),
        "dynamic_filter_rows_kept": sum(int(e.stats["rows"]) for e in kept),
        "agg_programs_device_s": {
            n: r["device_s"] for n, r in reduced["programs"].items()
            if n.startswith(AGG_PROGRAMS)},
        "busy_s": yard["busy_s"], "window_s": yard["window_s"],
    }


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 chipbench/agg_trace.py <cell>", file=sys.stderr)
        return 2
    path = spans.newest_xplane(os.path.join(spans.TRACE_ROOT, argv[0]))
    if path is None:
        print(f"no traced run of {argv[0]} under {spans.TRACE_ROOT}",
              file=sys.stderr)
        return 1
    try:
        found = metrics(spans.load(path))
    except ValueError as e:   # no window, or no device plane
        print(f"{path}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"trace": path, **found}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
