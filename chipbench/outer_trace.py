#!/usr/bin/env python3
"""The readings of an outer join and of the sort-path aggregation over a
key that arrives in no order, from a traced run of a cell (written for
Q13 at SF10, `chipbench/Q13.md`; any one-chip cell's trace can be read).

`python3 chipbench/outer_trace.py <cell>` after a `--trace 1` run of the
cell, as `semi_trace.py` is used. It reads the run's `.xplane.pb` through
`spans.load` / `spans.reduce` and prints one JSON object (`metrics`):

- `outer_op_share_pct`: wall inside the `tpusql.op.LookupJoinOperator.*`
  calls that carry the stat `outer` (a LEFT or FULL join, whichever side
  it built) over the wall inside `tpusql.phase.execute` (over the traced
  window's seconds where the trace holds no whole `phase.execute`;
  `outer_op_share_of` says which);
- `outer_device_share_pct`: device seconds of the outer joins' programs
  (by name on the `XLA Modules` line) over the device's busy seconds.
  Where EVERY join operator call of the window carries `outer` (Q13: the
  statement has one join) these are the probes, the expansions and the
  program that flags the build rows (`OUTER_PROGRAMS` and
  `PROBE_PROGRAMS`); where other joins run beside it the probes cannot
  be told apart on the device's line and only `OUTER_PROGRAMS` count
  (`outer_device_share_of` says which);
- `outer_unmatched_pct`: of the rows of the side the LEFT joins
  preserve, the share that went out with NULLs (stats `unmatched` and
  `preserved_rows` of `tpusql.sync.join.outer_flags`, one span a join
  and statement, or a grace partition; `preserved` says which side);
- `agg_unordered_ms_per_batch`: device milliseconds of one launch of
  `jit__agg_ingest` (the per-batch program of the sort path), the mean
  over the window's launches, where the statements' accounts count more
  batches that paid their key sort (`c.agg_unordered_input.batches`)
  than batches that skipped it; None otherwise (the program is the
  ordered batches' too, and theirs mostly then; `unordered_batches` and
  `ordered_batches` beside it say what the mean is of);
- `agg_merge_ms_per_stmt`: `agg_trace.py`'s, the host wall inside the
  `tpusql.agg.merge` spans a statement; beside it
  `agg_merge_device_ms_per_stmt`, the device milliseconds of
  `jit__merge_group_states` a statement-equivalent;
- `mark_build_rows_roofline_pct`: the least time the chip could take to
  move what the one NEW jitted program, `jit__mark_build_rows`, must
  (`mark_build_rows_bytes`, below) over its device seconds.

They are NOT entries of `BENCHMARK.json` (`chipbench/Q13.md`). A program
from before the spans or stats gives None for what it cannot show;
nothing here raises on such a trace.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
from typing import List, Optional

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import spans  # noqa: E402
from chipbench import stmt_account  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402
from chipbench.agg_trace import MATCH_TOTAL, MERGE, RESULT_FETCH, window_events  # noqa: E402
from chipbench.join_trace import hbm_bytes_per_s  # noqa: E402

OUTER_FLAGS = spans.SYNC + "join.outer_flags"
JOIN_OP = spans.PROGRAM + "op.LookupJoinOperator."
MARK_PROGRAM = "jit__mark_build_rows"
OUTER_PROGRAMS = (MARK_PROGRAM,)
# what a join's probe batches launch whatever its kind (exec/operators.py,
# ops/join.py): theirs where the window's joins are all outer joins
PROBE_PROGRAMS = ("jit_probe_counts", "jit__expand_pairs", "jit__fanout_le_one",
                  "jit__segment_any", "jit__left_unmatched", "jit__flagged_rows")
INGEST_PROGRAM = "jit__agg_ingest"
MERGE_PROGRAM = "jit__merge_group_states"
UNORDERED = stmt_account.COUNTER + "agg_unordered_input.batches"
ORDERED = stmt_account.COUNTER + "agg_ordered_input.batches"


def mark_build_rows_bytes(launches: int, pair_slots: int, build_slots: int) -> int:
    """What `_mark_build_rows` cannot avoid moving in `launches` launches
    over batches of `pair_slots` pairs against `build_slots` flags: a
    pair's build row number (4 B) and whether it held (1 B) read, and a
    flag a build slot read and written (1 B each). (A scatter's own
    passes over its indices are left out: a lower bound.)"""
    return launches * (pair_slots * 5 + build_slots * 2)


def metrics(st: spans.SpanTrace) -> dict:
    reduced = spans.reduce(st)
    yard = trace_mod.reduce(st.yardstick)
    events = window_events(st)
    statements = yard["statements_in_window"] or sum(
        1 for e in events if e.name == RESULT_FETCH)
    account = stmt_account.reduce(st)
    equivalents = account["equivalents"]

    windows = [a for a in st.yardstick.annotations if a.name == trace_mod.WINDOW]
    lo, hi = windows[0].start, windows[0].end
    join_calls = [e for line in st.lines for e in line
                  if e.end > lo and e.start < hi and e.name.startswith(JOIN_OP)]
    outer_calls = [e for e in join_calls if "outer" in e.stats]
    outer_s = sum(min(e.end, hi) - max(e.start, lo) for e in outer_calls)
    execute_s, share_of = reduced["totals"]["execute_s"], "phase.execute"
    if not execute_s and outer_s:
        execute_s, share_of = yard["window_s"], "window"

    counted, device_of = OUTER_PROGRAMS, "the programs only an outer join runs"
    if outer_calls and len(outer_calls) == len(join_calls):
        counted, device_of = OUTER_PROGRAMS + PROBE_PROGRAMS, "every join program"
    programs = {n: r for n, r in reduced["programs"].items()
                if n.startswith(OUTER_PROGRAMS + PROBE_PROGRAMS)
                or n.startswith((INGEST_PROGRAM, MERGE_PROGRAM))}
    device_s = sum(r["device_s"] for n, r in programs.items() if n.startswith(counted))

    flags = [e for e in events if e.name == OUTER_FLAGS and "preserved_rows" in e.stats]
    preserved_rows = sum(int(e.stats["preserved_rows"]) for e in flags)
    unmatched = sum(int(e.stats["unmatched"]) for e in flags)

    # the per-batch program alone, not the trains' (`jit__agg_ingest_train`)
    ingest = programs.get(INGEST_PROGRAM)
    unordered = sum(float(s.get(UNORDERED, 0)) for s in account["statements"])
    ordered = sum(float(s.get(ORDERED, 0)) for s in account["statements"])
    ingest_ms = None
    if ingest and ingest["runs"] and unordered > ordered:
        ingest_ms = 1e3 * ingest["device_s"] / ingest["runs"]
    merge_s = sum(r["device_s"] for n, r in programs.items()
                  if n.startswith(MERGE_PROGRAM))

    mark = programs.get(MARK_PROGRAM)
    probes = [int(e.stats["probe_slots"]) for e in events
              if e.name == MATCH_TOTAL and "probe_slots" in e.stats]
    slots = [int(e.stats["build_slots"]) for e in flags if "build_slots" in e.stats]
    roofline = None
    if mark and mark["device_s"] and probes and slots:
        moved = mark_build_rows_bytes(
            mark["runs"], int(statistics.median(probes)), max(slots))
        roofline = 100.0 * (moved / hbm_bytes_per_s()) / mark["device_s"]
    return {
        "statements_in_window": statements,
        "statement_equivalents": equivalents,
        "outer_op_share_pct":
            100.0 * outer_s / execute_s if execute_s and outer_s else None,
        "outer_op_share_of": share_of if execute_s and outer_s else None,
        "outer_device_share_pct":
            100.0 * device_s / yard["busy_s"] if yard["busy_s"] and device_s else None,
        "outer_device_share_of": device_of if device_s else None,
        "outer_unmatched_pct":
            100.0 * unmatched / preserved_rows if preserved_rows else None,
        "agg_unordered_ms_per_batch": ingest_ms,
        "agg_merge_ms_per_stmt":
            1e3 * reduced["spans"][MERGE]["wall_s"] / statements
            if MERGE in reduced["spans"] and statements else None,
        "agg_merge_device_ms_per_stmt":
            1e3 * merge_s / equivalents if merge_s and equivalents else None,
        "mark_build_rows_roofline_pct": roofline,
        # beside them, not metrics: what the spans' stats say
        "outer_joins": [
            {k: (str(v) if k == "preserved" else int(v)) for k, v in e.stats.items()
             if k in ("preserved", "preserved_rows", "build_rows", "unmatched",
                      "build_slots")} for e in flags],
        "probe_batches": len(probes),
        "unordered_batches": unordered, "ordered_batches": ordered,
        "programs": programs,
        "busy_s": yard["busy_s"], "window_s": yard["window_s"],
    }


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 chipbench/outer_trace.py <cell>", file=sys.stderr)
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
