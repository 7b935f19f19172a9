#!/usr/bin/env python3
"""The mesh plane's per-layer metrics, from a traced run of a mesh cell.

`trace.reduce` averages busy time over the chips and
`harness.memory_stats` reads the first one; a mesh cell also wants each
chip's own busy time (skew), the time inside collective operations, and
what the `tpusql.mesh.*` spans say. This file reads all four device
planes and those spans itself, and holds the functions that count
exchanged bytes: the program says, in the stats of each
`tpusql.mesh.prelude|step|finish` span, what one run of that program
exchanges (`trino_tpu/parallel/mesh_chunk.py` `ExchangeCensus`: bytes
that leave their device, from the program's shapes after dead-code
elimination); `reduce` sums the spans of the traced window.

The five metrics (`metrics`) are NOT entries of `BENCHMARK.json` yet:
`tests/chipbench/test_dispatch_readers.py` line 52 holds the last two
entries of `per_layer` to be PR 26's, so nothing can be appended until a
`benchmark` PR edits that line, and this PR may edit no file that was
here (MESH.md). Until then: `python3 chipbench/mesh_trace.py <cell>`
after a `--trace 1` run, as `spans.py` is used.

A cell on one chip runs no mesh program: it reads 0 everywhere (nothing
exchanged, no step, one chip's skew against itself).
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import spans  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402

# one v5e chip's interconnect: Google Cloud documentation, "TPU v5e":
# 1,600 Gbit/s of chip-to-chip interconnect per chip (peaks.json, which
# this PR may not edit, has no such key; the harness refuses any device
# kind but the v5e). Bytes that LEAVE a chip are held against it.
ICI_BYTES_PER_S = 1600e9 / 8

# XLA's names of the operations that move data between chips
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "collective-broadcast")
MESH_PROGRAMS = tuple(
    spans.PROGRAM + f"mesh.{p}" for p in ("prelude", "step", "finish")
)
STEP = spans.PROGRAM + "mesh.step"
_OPERAND = re.compile(r"\((%[\w.\-]+)")
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-_]*)\(")


def opcode_of(text: str) -> str:
    """XLA's opcode in its text for an operation: the first lower-case
    word that opens a parenthesis after the `=` (`%all_to_all.3 =
    (u32[4]{0:T(1024)}, u32[4]{0}) all-to-all(...)` gives `all-to-all`;
    the result's shape may itself be a tuple with tilings in it)."""
    m = _OPCODE.search(text.partition(" = ")[2])
    return m.group(1) if m else ""


def collective_of(text: str) -> Optional[str]:
    """The collective an `XLA Ops` event runs (its `-start` and `-done`
    halves too), or None. By the opcode; by the operation's own name,
    which JAX derives from the primitive (`%all_to_all.3`), where the
    event carries no more than the name."""
    opcode = opcode_of(text)
    head = trace_mod.op_name(text).lstrip("%").replace("_", "-")
    for c in COLLECTIVES:
        if opcode.startswith(c) or (not opcode and head.startswith(c)):
            return c
    return None


def exchange_intervals(names: List[str], starts: np.ndarray,
                       ends: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merged intervals in which one chip was inside a collective. An
    asynchronous one is in flight from the beginning of its `-start` to
    the end of its `-done` (whose operand names the start), not only
    while those two short operations run: bytes over this time can then
    never read above the link's peak."""
    lo, hi = [], []
    started: Dict[str, float] = {}
    for text, a, b in zip(names, starts, ends):
        if collective_of(text) is None:
            continue
        opcode = opcode_of(text)
        if opcode.endswith("-start"):
            started[trace_mod.op_name(text)] = a
        elif opcode.endswith("-done"):
            m = _OPERAND.search(text.partition(" = ")[2])
            a = started.pop(m.group(1), a) if m else a
        lo.append(a)
        hi.append(b)
    return trace_mod.union(np.asarray(lo, dtype=float),
                           np.asarray(hi, dtype=float))


def reduce(st: spans.SpanTrace) -> Optional[dict]:
    """Per chip: busy and in-collective seconds of the traced window;
    over the window: the mesh programs' runs and the bytes they
    exchanged (none of either in a cell that runs on one chip)."""
    windows = [a for a in st.yardstick.annotations
               if a.name == trace_mod.WINDOW]
    if not windows or not st.yardstick.device_ops:
        return None
    lo, hi = windows[0].start, windows[0].end
    shift = trace_mod.clock_shift(st.yardstick)
    runs = [e for line in st.lines for e in line
            if e.name in MESH_PROGRAMS and e.end > lo and e.start < hi]
    busy, exchange = [], []
    for _plane, (names, starts, ends) in sorted(st.yardstick.device_ops.items()):
        s, e = trace_mod._clip(starts + shift, ends + shift, lo, hi)
        busy.append(spans.measure(trace_mod.union(s, e)))
        xs, xe = exchange_intervals(names, starts + shift, ends + shift)
        exchange.append(spans.measure(trace_mod.union(
            *trace_mod._clip(xs, xe, lo, hi))))
    exchanged = 0.0
    for e in runs:
        # a run that straddles an edge of the window counts for the part
        # of it inside, as the device's time does
        inside = (min(e.end, hi) - max(e.start, lo)) / max(e.end - e.start, 1e-12)
        exchanged += inside * float(e.stats.get("bytes_exchanged", 0))
    return {
        "window_s": hi - lo, "chips": len(busy),
        "busy_s": busy, "exchange_s": exchange,
        "bytes_exchanged": exchanged,
        "steps": sum(1 for e in runs if e.name == STEP and lo <= e.end <= hi),
        "programs": len(runs),
    }


def metrics(reduced: dict, statements: int, fallbacks: Optional[int]) -> dict:
    """The mesh plane's five per-layer metrics from `reduce`'s answer,
    the statements that completed in the traced window and the
    fallbacks inside the measured one (`fallbacks_in`).

    - `mesh_exchange_share_pct`: device time inside collective
      operations over device busy time, summed over the chips;
    - `mesh_exchange_roofline_pct`: the least time a chip's interconnect
      could take to send its share of what the window's mesh programs
      exchanged (`ICI_BYTES_PER_S`), over the time a chip was inside
      collectives. Send blocks are mostly padding, and padding travels:
      a low share beside a high exchange share says the blocks are wide;
    - `mesh_device_skew_pct`: the busiest chip's busy time over the
      chips' mean, minus one: how unevenly the partitions load them;
    - `mesh_chunk_steps_per_stmt`: `tpusql.mesh.step` spans (one for each
      increment of the program's `mesh.chunk_steps` counter) a statement;
    - `mesh_fallbacks_in_window`: alarm, 0.
    """
    chips = reduced["chips"]
    busy, inside = sum(reduced["busy_s"]), sum(reduced["exchange_s"])
    least_s = reduced["bytes_exchanged"] / chips / ICI_BYTES_PER_S
    out = {
        "mesh_exchange_share_pct": 100.0 * inside / busy if busy else 0.0,
        "mesh_exchange_roofline_pct":
            100.0 * least_s / (inside / chips) if inside else 0.0,
        "mesh_device_skew_pct":
            100.0 * (max(reduced["busy_s"]) * chips / busy - 1.0) if busy else 0.0,
        "mesh_chunk_steps_per_stmt":
            reduced["steps"] / statements if statements else None,
    }
    if fallbacks is not None:
        out["mesh_fallbacks_in_window"] = fallbacks
    return out


def fallbacks_in(lo: float, hi: float) -> Optional[int]:
    """Statements that left the mesh plane for the page exchange between
    two `time.perf_counter()` readings of this process: the program's
    log of its fallbacks (`trino_tpu/parallel/mesh_plan.FALLBACK_LOG`,
    the times behind its `mesh.fallbacks` counter) held against a
    window. None for a program from before the log."""
    try:
        from trino_tpu.parallel.mesh_plan import FALLBACK_LOG
    except ImportError:
        return None
    return sum(1 for at, _reason in list(FALLBACK_LOG) if lo <= at <= hi)


def for_run(run) -> Optional[dict]:
    """`metrics` of the traced run `run` (a `harness.RunData`): what a
    reader file under `layer_metrics/` would return, once the benchmark
    lists these metrics (MESH.md says what stands in the way). None
    without a trace or a completed statement, and where the run's trace
    holds no program span or cannot be found (`spans.for_run` says so)."""
    if run.trace is None or not run.trace_completed or not spans.for_run(run):
        return None
    reduced = reduce(spans.load(spans.newest_xplane(spans.TRACE_ROOT)))
    if reduced is None:
        return None
    return metrics(reduced, len(run.trace_completed),
                   fallbacks_in(run.t0, run.t0 + run.account.seconds))


def main(argv: Optional[List[str]] = None) -> int:
    """`python3 chipbench/mesh_trace.py <cell>`: the metrics of the
    cell's last traced run, one JSON object. The statements are the
    `chipbench.client.execute` annotations that ended in the traced
    window (`trace.reduce`'s `statements_in_window`); the fallbacks are
    not in a trace, so that line is left out here."""
    import json

    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 chipbench/mesh_trace.py <cell>", file=sys.stderr)
        return 2
    path = spans.newest_xplane(os.path.join(spans.TRACE_ROOT, argv[0]))
    if path is None:
        print(f"no traced run of {argv[0]} under {spans.TRACE_ROOT}",
              file=sys.stderr)
        return 1
    st = spans.load(path)
    reduced = reduce(st)
    if reduced is None:
        print(f"{path}: no window or no device plane", file=sys.stderr)
        return 1
    done = trace_mod.reduce(st.yardstick)["statements_in_window"]
    print(json.dumps({"trace": path, **reduced,
                      "statements_in_window": done,
                      **metrics(reduced, done, None)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
