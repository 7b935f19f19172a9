#!/usr/bin/env python3
"""The joins' and the dynamic filters' per-layer readings, from a traced
run of a cell (written for Q9 at SF10, `chipbench/Q9.md`; any one-chip
cell's trace can be read).

`python3 chipbench/join_trace.py <cell>` after a `--trace 1` run of the
cell, as `agg_trace.py` is used. It reads the run's `.xplane.pb` through
`spans.load` / `spans.reduce` and prints one JSON object (`metrics`):

- `join_op_share_pct`: wall inside `tpusql.op.LookupJoinOperator.*`,
  `op.HashBuildSink.*` and `op.DynamicFilterOperator.*` over the wall
  inside `tpusql.phase.execute` (over the traced window's seconds where
  the trace holds no whole `phase.execute`; `join_op_share_of` says
  which);
- `join_device_share_pct`: device seconds of the joins' and the filters'
  programs (`JOIN_PROGRAMS`, by name on the `XLA Modules` line) over the
  device's busy seconds;
- `join_build_rows_per_stmt`: live rows of the build sides that were
  counted (stat `rows` of `tpusql.sync.join.build_rows`: every build
  side of 2^17 slots or more), a statement;
- `join_probe_rows_per_stmt`: the slots of the batches the join probes
  took (stat `probe_slots` of `tpusql.sync.join.match_total`), a
  statement: `agg_trace.py` prints the same number;
- `df_kept_pct`: of the rows that entered the filters that test
  membership (the key set, the key bits), the share they kept (stats
  `rows_in`, `rows_kept` of `tpusql.sync.join.dynamic_filter_totals`,
  one span a filter and statement; a range filter's rows are listed in
  `filters` and left out of the share, since behind a membership filter
  a range keeps every row);
- `df_bits_roofline_pct`: the least time the chip could take to move
  what the key-bits filter must move (`df_bits_bytes`), over the device
  seconds of its program, `jit__df_filter_bits`.

They are NOT entries of `BENCHMARK.json` (`chipbench/Q9.md`). A program
from before the spans or stats gives None for what it cannot show;
nothing here raises on such a trace.
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
from chipbench import traffic  # noqa: E402
from chipbench.agg_trace import (  # noqa: E402
    MATCH_TOTAL, RESULT_FETCH, window_events,
)

HERE = os.path.dirname(os.path.abspath(__file__))
JOIN_OPS = tuple(spans.PROGRAM + f"op.{name}." for name in (
    "LookupJoinOperator", "HashBuildSink", "DynamicFilterOperator"))
BUILD_ROWS = spans.SYNC + "join.build_rows"
DF_TOTALS = spans.SYNC + "join.dynamic_filter_totals"
DF_PREPARE = spans.PROGRAM + "df.prepare"
BITS_PROGRAM = "jit__df_filter_bits"
# exec/operators.py and ops/join.py: what builds, probes, expands, filters
# and packs
JOIN_PROGRAMS = (
    "jit__consolidate_build", "jit_build_lookup", "jit_probe_counts",
    "jit_expand_matches", "jit__expand_pairs", "jit__fanout_le_one",
    "jit__segment_any", "jit__left_unmatched", "jit__right_unmatched",
    "jit__df_", "jit__front_rows", "jit__pack_",
)
MEMBERSHIP = ("set", "bits")


def df_bits_bytes(slots: int, key_bytes: int, batches: int,
                  table_bytes: int) -> int:
    """What the key-bits filter cannot avoid moving: every slot's key
    read and its bit of the mask written, and the table of bits read
    once a batch. (The live mask it reads and the gathered words'
    traffic beyond the table's size are left out: a lower bound.)"""
    return slots * (key_bytes + 1) + batches * table_bytes


def hbm_bytes_per_s() -> float:
    peaks = traffic.load_json(os.path.join(HERE, "peaks.json"))
    return float(next(iter(peaks.values()))["hbm_bytes_per_s"])


def metrics(st: spans.SpanTrace) -> dict:
    reduced = spans.reduce(st)
    yard = trace_mod.reduce(st.yardstick)
    events = window_events(st)
    statements = yard["statements_in_window"] or sum(
        1 for e in events if e.name == RESULT_FETCH)
    rows = reduced["spans"]

    def per_statement(total: Optional[float]) -> Optional[float]:
        return None if total is None or not statements else total / statements

    join_s = sum(r["wall_s"] for n, r in rows.items() if n.startswith(JOIN_OPS))
    execute_s, share_of = reduced["totals"]["execute_s"], "phase.execute"
    if not execute_s and join_s:
        execute_s, share_of = yard["window_s"], "window"
    programs = {n: r["device_s"] for n, r in reduced["programs"].items()
                if n.startswith(JOIN_PROGRAMS)}
    builds = [e for e in events if e.name == BUILD_ROWS and "rows" in e.stats]
    probes = [e for e in events
              if e.name == MATCH_TOTAL and "probe_slots" in e.stats]
    filters = [e for e in events if e.name == DF_TOTALS and "rows_in" in e.stats]
    by_path: dict = {}
    for e in filters:
        row = by_path.setdefault(str(e.stats.get("path")), {
            "filters": 0, "batches": 0, "slots": 0, "rows_in": 0, "rows_kept": 0})
        row["filters"] += 1
        for k in ("batches", "slots", "rows_in", "rows_kept"):
            row[k] += int(e.stats.get(k, 0))
    entered = sum(by_path[p]["rows_in"] for p in MEMBERSHIP if p in by_path)
    kept = sum(by_path[p]["rows_kept"] for p in MEMBERSHIP if p in by_path)
    prepared = [
        {k: (str(v) if k == "path" else int(v)) for k, v in e.stats.items()
         if k in ("path", "keys", "domain", "build_slots", "table_bytes")}
        for e in events if e.name == DF_PREPARE]

    bits_s = sum(s for n, s in programs.items() if n.startswith(BITS_PROGRAM))
    roofline = None
    bits = [e for e in filters if e.stats.get("path") == "bits"]
    tables = [int(p["table_bytes"]) for p in prepared if "table_bytes" in p]
    if bits and bits_s:
        # (every bits filter of the window is taken to hold a table of
        # the largest size seen, one filter a statement in Q9; none
        # where every `df.prepare` fell outside the window: the keys
        # and the mask alone, still a lower bound)
        moved = sum(
            df_bits_bytes(int(e.stats["slots"]), int(e.stats["key_bytes"]),
                          int(e.stats["batches"]), max(tables, default=0))
            for e in bits)
        roofline = 100.0 * (moved / hbm_bytes_per_s()) / bits_s
    return {
        "statements_in_window": statements,
        "join_op_share_pct": 100.0 * join_s / execute_s if execute_s else None,
        "join_op_share_of": share_of if execute_s else None,
        "join_device_share_pct":
            100.0 * sum(programs.values()) / yard["busy_s"]
            if yard["busy_s"] else None,
        "join_build_rows_per_stmt": per_statement(
            float(sum(int(e.stats["rows"]) for e in builds)) if builds else None),
        "join_probe_rows_per_stmt": per_statement(
            float(sum(int(e.stats["probe_slots"]) for e in probes))
            if probes else None),
        "df_kept_pct": 100.0 * kept / entered if entered else None,
        "df_bits_roofline_pct": roofline,
        # beside them, not metrics: what the spans' stats say
        "builds": sorted(int(e.stats["rows"]) for e in builds),
        "probe_batches": len(probes),
        "filters": by_path,
        "prepared": prepared,
        "join_programs_device_s": programs,
        "busy_s": yard["busy_s"], "window_s": yard["window_s"],
    }


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 chipbench/join_trace.py <cell>", file=sys.stderr)
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
