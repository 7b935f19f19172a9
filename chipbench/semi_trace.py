#!/usr/bin/env python3
"""The readings of a semi- or anti-join that builds the side it
preserves, of the filter in front of its probe and of the scans' device
filter, from a traced run of a cell (written for Q21 at SF10,
`chipbench/Q21.md`; any one-chip cell's trace can be read).

`python3 chipbench/semi_trace.py <cell>` after a `--trace 1` run of the
cell, as `join_trace.py` is used. It reads the run's `.xplane.pb` through
`spans.load` / `spans.reduce` and prints one JSON object (`metrics`):

- `semi_op_share_pct`: wall inside the `tpusql.op.LookupJoinOperator.*`
  calls that carry the stat `preserved` and the
  `op.DynamicFilterOperator.*` calls that carry `reverse` (the two
  joins and the filters in front of their probes) over the wall inside
  `tpusql.phase.execute` (over the traced window's seconds where the
  trace holds no whole `phase.execute`; `semi_op_share_of` says which);
- `semi_device_share_pct`: device seconds of `SEMI_PROGRAMS` (the
  program that flags the build rows and its two small readers, by name
  on the `XLA Modules` line) over the device's busy seconds. The probes'
  `probe_counts` and the filters' programs are the inner joins' too and
  stay in `join_trace.py`'s share;
- `semi_pairs_per_stmt`: pairs the residuals were shown (stat
  `pairs_seen` of `tpusql.sync.join.semi_flags`, one span a join and
  statement), a statement; `semi_pairs_kept_pct`: of those, the share
  the residuals let through (`pairs_kept`);
- `reverse_filter_kept_pct`: of the rows that entered the filters in
  front of the FILTERING sides (stat `reverse` 1 of
  `tpusql.sync.join.dynamic_filter_totals`), the share they kept;
- `scan_filter_roofline_pct`: the least time the chip could take to
  move what the filter/project stages must (`filter_read_bytes`: the
  columns their predicates and computed columns read and a byte of mask
  a slot, counted by the program, `c.filter_read_bytes` of the
  statements' accounts) over the device seconds of their program,
  `jit_FilterProjectOperator`, both a statement;
- `flag_rows_roofline_pct`: the same for the one NEW jitted program,
  `jit__flag_build_rows` (`flag_rows_bytes`, below), over its device
  seconds.

They are NOT entries of `BENCHMARK.json` (`chipbench/Q21.md`). A program
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
from chipbench.agg_trace import MATCH_TOTAL, RESULT_FETCH, window_events  # noqa: E402
from chipbench.join_trace import DF_TOTALS, hbm_bytes_per_s  # noqa: E402

SEMI_FLAGS = spans.SYNC + "join.semi_flags"
JOIN_OP = spans.PROGRAM + "op.LookupJoinOperator."
FILTER_OP = spans.PROGRAM + "op.DynamicFilterOperator."
FLAG_PROGRAM = "jit__flag_build_rows"
SEMI_PROGRAMS = (FLAG_PROGRAM, "jit__probe_row_counts", "jit__flagged_rows")
FILTER_PROGRAM = "jit_FilterProjectOperator"
FILTER_BYTES = stmt_account.COUNTER + "filter_read_bytes"


def flag_rows_bytes(probe_slots: int, pairs: int, key_bytes: int = 8,
                    residual_bytes: int = 8) -> int:
    """What `_flag_build_rows` cannot avoid moving for the probe batches
    of `probe_slots` slots whose `pairs` candidate pairs it looked at:
    every probe slot's key, residual column and run (`lo`, `counts`)
    read, and a pair the build row's number, key and residual column
    gathered and its flag written. (The offsets' sorts of the general
    form, the flags carried from launch to launch and the probe's mask
    are left out: a lower bound.)"""
    return (probe_slots * (key_bytes + residual_bytes + 8)
            + pairs * (4 + key_bytes + residual_bytes + 1))


def metrics(st: spans.SpanTrace) -> dict:
    reduced = spans.reduce(st)
    yard = trace_mod.reduce(st.yardstick)
    events = window_events(st)
    statements = yard["statements_in_window"] or sum(
        1 for e in events if e.name == RESULT_FETCH)
    account = stmt_account.reduce(st)
    equivalents = account["equivalents"]

    def per_statement(total: Optional[float]) -> Optional[float]:
        return None if total is None or not statements else total / statements

    windows = [a for a in st.yardstick.annotations if a.name == trace_mod.WINDOW]
    lo, hi = windows[0].start, windows[0].end
    semi_s = sum(
        min(e.end, hi) - max(e.start, lo)
        for line in st.lines for e in line
        if e.end > lo and e.start < hi and (
            e.name.startswith(JOIN_OP) and "preserved" in e.stats
            or e.name.startswith(FILTER_OP) and "reverse" in e.stats)
    )
    execute_s, share_of = reduced["totals"]["execute_s"], "phase.execute"
    if not execute_s and semi_s:
        execute_s, share_of = yard["window_s"], "window"
    programs = {n: r for n, r in reduced["programs"].items()
                if n.startswith(SEMI_PROGRAMS) or n.startswith(FILTER_PROGRAM)}
    device_s = sum(r["device_s"] for n, r in programs.items()
                   if n.startswith(SEMI_PROGRAMS))
    flags = [e for e in events if e.name == SEMI_FLAGS and "pairs_seen" in e.stats]
    seen = sum(int(e.stats["pairs_seen"]) for e in flags)
    kept = sum(int(e.stats["pairs_kept"]) for e in flags)
    reverse = [e for e in events if e.name == DF_TOTALS
               and int(e.stats.get("reverse", 0)) and "rows_in" in e.stats]
    rows_in = sum(int(e.stats["rows_in"]) for e in reverse)
    rows_kept = sum(int(e.stats["rows_kept"]) for e in reverse)
    probes = [e for e in events
              if e.name == MATCH_TOTAL and "first_candidates" in e.stats]

    def roofline(moved_bytes: Optional[float], program: str) -> Optional[float]:
        """`moved_bytes` (a statement's) at the chip's HBM rate over the
        device seconds a statement spends in `program`."""
        program_s = sum(r["device_s"] for n, r in programs.items()
                        if n.startswith(program))
        if not moved_bytes or not program_s or not equivalents:
            return None
        return 100.0 * (moved_bytes / hbm_bytes_per_s()) / (program_s / equivalents)

    filter_bytes = [float(s[FILTER_BYTES]) for s in account["statements"]
                    if FILTER_BYTES in s]
    flag_bytes = None
    if flags and probes and statements:
        flag_bytes = flag_rows_bytes(
            sum(int(e.stats["probe_slots"]) for e in probes), seen) / statements
    return {
        "statements_in_window": statements,
        "statement_equivalents": equivalents,
        "semi_op_share_pct": 100.0 * semi_s / execute_s if execute_s and semi_s else None,
        "semi_op_share_of": share_of if execute_s and semi_s else None,
        "semi_device_share_pct":
            100.0 * device_s / yard["busy_s"] if yard["busy_s"] and device_s else None,
        "semi_pairs_per_stmt": per_statement(float(seen) if flags else None),
        "semi_pairs_kept_pct": 100.0 * kept / seen if seen else None,
        "reverse_filter_kept_pct": 100.0 * rows_kept / rows_in if rows_in else None,
        "scan_filter_roofline_pct": roofline(
            statistics.median(filter_bytes) if filter_bytes else None, FILTER_PROGRAM),
        "flag_rows_roofline_pct": roofline(flag_bytes, FLAG_PROGRAM),
        # beside them, not metrics: what the spans' stats say
        "joins": [
            {k: (str(v) if k == "kind" else int(v)) for k, v in e.stats.items()
             if k in ("kind", "pairs_seen", "pairs_kept", "build_rows", "build_flagged")}
            for e in flags],
        "reverse_filters": [
            {k: (str(v) if k == "path" else int(v)) for k, v in e.stats.items()
             if k in ("path", "rows_in", "rows_kept", "batches", "slots")}
            for e in reverse],
        "probe_batches": [
            {k: int(e.stats[k]) for k in ("rows", "probe_slots", "first_candidates")}
            for e in probes],
        "programs": programs,
        "filter_read_bytes_per_stmt":
            statistics.median(filter_bytes) if filter_bytes else None,
        "busy_s": yard["busy_s"], "window_s": yard["window_s"],
    }


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 chipbench/semi_trace.py <cell>", file=sys.stderr)
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
