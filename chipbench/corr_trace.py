#!/usr/bin/env python3
"""The readings of a decorrelated scalar aggregate whose join sends its
key filter UNDER the aggregation, from a traced run of a cell (written
for Q17 at SF10, `chipbench/Q17.md`; any one-chip cell's trace can be
read).

`python3 chipbench/corr_trace.py <cell>` after a `--trace 1` run of the
cell, as `outer_trace.py` is used. It reads the run's `.xplane.pb`
through `spans.load` / `spans.reduce` and prints one JSON object
(`metrics`):

- `corr_filter_kept_pct`: of the rows that entered the dynamic filters
  standing under an aggregation (stat `under_aggregate` of
  `tpusql.sync.join.dynamic_filter_totals`, one span a filter and
  statement), the share they kept: what the aggregation was NOT spared;
- `corr_agg_rows_per_stmt`: the rows such a filter handed its
  aggregation, a statement (its `rows_kept`, a filter, times the filters
  a statement's account counts, `c.df_under_aggregate`);
- `corr_filter_device_share_pct`: device seconds of those filters'
  programs over the device's busy seconds. A filter's programs are not
  named after the filter, so every filter program's device seconds (by
  name on the `XLA Modules` line: the set's, the bits', the range's, the
  packers') are divided among the window's filters by the SLOTS each
  sent through that path (stats `path`, `slots`), the packers' by the
  slots of the filters that pack (set and bits); `filters` beside it
  says what stood where;
- `corr_agg_device_share_pct`: device seconds of the aggregations'
  programs (`jit__agg_ingest*`, `jit__merge_group_states*`) over busy:
  `agg_trace.py`'s `agg_device_share_pct`;
- `df_bits_roofline_pct`: `join_trace.py`'s, the least time the chip
  could take to move what the key-bits filters must (`df_bits_bytes`)
  over the device seconds of `jit__df_filter_bits*`.

Beside them, a statement (medians of the accounts that ended in the
window): `c.df_under_aggregate`, `c.decorrelated_scalar_aggregates`,
`c.agg_filtered_input.batches`, `c.df_reverse_rows_in` / `_kept`.

PR 48 added no device program (`NEW_PROGRAMS` is empty: the filter under
the aggregation is the filter that stood in front of a probe, launched
from another place in the plan), so there is no new roofline share and
no function here that counts a new program's bytes.

They are NOT entries of `BENCHMARK.json` (`chipbench/Q17.md`). A program
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

from chipbench import join_trace  # noqa: E402
from chipbench import spans  # noqa: E402
from chipbench import stmt_account  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402
from chipbench.agg_trace import AGG_PROGRAMS, window_events  # noqa: E402
from chipbench.join_trace import DF_TOTALS  # noqa: E402

NEW_PROGRAMS: tuple = ()
# exec/operators.py: the program each filter path launches a batch, and
# what packs the batches behind a set or a bit table
PATH_PROGRAMS = {
    "set": ("jit__df_filter_set", "jit__df_key_set"),
    "bits": ("jit__df_filter_bits", "jit__df_bit_table"),
}
RANGE_PROGRAMS = ("jit__df_filter", "jit__df_domains")     # these names, whole
PACK_PROGRAMS = ("jit__front_rows", "jit__pack_")
PACKING_PATHS = ("set", "bits")
COUNTERS = ("df_under_aggregate", "decorrelated_scalar_aggregates",
            "agg_filtered_input.batches", "df_reverse_rows_in", "df_reverse_rows_kept")


def program_path(name: str) -> Optional[str]:
    """The filter path a program of the `XLA Modules` line belongs to
    ("pack" for the packers), or None for any other program."""
    for path, prefixes in PATH_PROGRAMS.items():
        if name.startswith(prefixes):
            return path
    if name in RANGE_PROGRAMS:
        return "range"
    return "pack" if name.startswith(PACK_PROGRAMS) else None


def slot_share(filters: List[spans.Event], paths) -> Optional[float]:
    """Of the slots the window's filters sent through `paths`, the share
    of the filters under an aggregation; None where none went there."""
    there = [e for e in filters if str(e.stats.get("path")) in paths]
    total = sum(int(e.stats.get("slots", 0)) for e in there)
    under = sum(int(e.stats.get("slots", 0)) for e in there
                if "under_aggregate" in e.stats)
    return under / total if total else None


def metrics(st: spans.SpanTrace) -> dict:
    reduced = spans.reduce(st)
    yard = trace_mod.reduce(st.yardstick)
    events = window_events(st)
    account = stmt_account.reduce(st)
    filters = [e for e in events if e.name == DF_TOTALS and "rows_in" in e.stats]
    under = [e for e in filters if "under_aggregate" in e.stats]
    rows_in = sum(int(e.stats["rows_in"]) for e in under)
    kept = sum(int(e.stats["rows_kept"]) for e in under)

    def counter(name: str) -> Optional[float]:
        seen = [float(s[stmt_account.COUNTER + name]) for s in account["statements"]
                if stmt_account.COUNTER + name in s]
        return statistics.median(seen) if seen else None

    per_stmt = counter("df_under_aggregate")
    programs = reduced["programs"]
    filter_programs, filter_s = {}, 0.0
    for name, row in programs.items():
        path = program_path(name)
        if path is None:
            continue
        filter_programs[name] = row["device_s"]
        share = slot_share(filters, PACKING_PATHS if path == "pack" else (path,))
        filter_s += (share or 0.0) * row["device_s"]
    agg_programs = {n: r["device_s"] for n, r in programs.items()
                    if n.startswith(AGG_PROGRAMS)}
    agg_s = sum(agg_programs.values())
    busy = yard["busy_s"]
    return {
        "statements_in_window": yard["statements_in_window"],
        "statement_equivalents": account["equivalents"],
        "corr_filter_kept_pct": 100.0 * kept / rows_in if rows_in else None,
        "corr_agg_rows_per_stmt":
            (per_stmt or 1.0) * kept / len(under) if under else None,
        "corr_filter_device_share_pct":
            100.0 * filter_s / busy if busy and under and filter_s else None,
        "corr_agg_device_share_pct":
            100.0 * agg_s / busy if busy and under and agg_s else None,
        "df_bits_roofline_pct": join_trace.metrics(st)["df_bits_roofline_pct"],
        # beside them, not metrics: a statement's counters, the filters
        **{stmt_account.COUNTER + name: counter(name) for name in COUNTERS},
        "filters": [
            {k: (str(v) if k == "path" else int(v)) for k, v in e.stats.items()
             if k in ("path", "rows_in", "rows_kept", "batches", "slots", "reverse",
                      "under_aggregate", "key_bytes")} for e in filters],
        "filter_programs_device_s": filter_programs,
        "agg_programs_device_s": agg_programs,
        "new_programs": list(NEW_PROGRAMS),
        "busy_s": busy, "window_s": yard["window_s"],
    }


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 chipbench/corr_trace.py <cell>", file=sys.stderr)
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
