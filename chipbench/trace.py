"""Reduction of a profiler trace (`*.xplane.pb`) to device metrics.

Read with nothing but JAX (`jax.profiler.ProfileData`). What the chip's
trace holds (seen in PR 24's first chip call): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event per operation that
ran, named by XLA's own text for it (`%sort.11 = (...) sort(...)`), and
whose line `XLA Modules` has one event per program run with a `run_id`;
and the plane `/host:CPU`, whose lines are host threads and hold the
benchmark's own `jax.profiler.TraceAnnotation`s (names starting with
`chipbench.`) and the runtime's `DoEnqueueProgram` events with the same
`run_id`s. The device's clock
runs apart from the host's by about a millisecond, so the reduction
shifts device times by the smallest amount that puts every program's
start after its enqueue.

- busy: the union of the intervals in which an operation ran, clipped to
  the traced window (the `chipbench.window` annotation), averaged
  over the chips used;
- idle share: 1 - busy / window;
- sort share: device time of operations whose XLA name contains `sort`
  over the device time of all operations;
- idle gaps, attributed to what the host was doing: inside the engine
  (a `chipbench.runner.execute` annotation covers it), in the protocol
  (only a `chipbench.client.execute` covers it), or no statement in
  flight.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "chipbench."
WINDOW = "chipbench.window"
ENGINE = "chipbench.runner.execute"
CLIENT = "chipbench.client.execute"
IN_ENGINE, IN_PROTOCOL, NO_STATEMENT = (
    "in_engine", "in_protocol", "no_statement_in_flight",
)


@dataclasses.dataclass
class Annotation:
    name: str
    start: float      # seconds, host clock
    end: float
    stats: Dict[str, str]


@dataclasses.dataclass
class Trace:
    # per device plane: (XLA op names, starts, ends) in seconds, device clock
    device_ops: Dict[str, Tuple[List[str], np.ndarray, np.ndarray]]
    # run_id -> (start, end) of a program on the device, device clock
    modules: Dict[str, Tuple[float, float]]
    # run_id -> start of the host's enqueue of that program
    enqueued: Dict[str, float]
    annotations: List[Annotation]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace({}, {}, {}, [])
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    names, starts, ends = [], [], []
                    for e in line.events:
                        names.append(e.name)
                        starts.append(e.start_ns * 1e-9)
                        ends.append((e.start_ns + e.duration_ns) * 1e-9)
                    trace.device_ops[plane.name] = (
                        names, np.asarray(starts), np.asarray(ends),
                    )
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        run_id = dict(e.stats).get("run_id")
                        if run_id is not None:
                            trace.modules[f"{plane.name}/{run_id}"] = (
                                e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                            )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        trace.annotations.append(Annotation(
                            e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            {k: str(v) for k, v in e.stats},
                        ))
                    elif e.name == "DoEnqueueProgram":
                        stats = dict(e.stats)
                        if "run_id" in stats:
                            key = (f"{DEVICE_PLANE_PREFIX}"
                                   f"{stats.get('device_ordinal', 0)}/{stats['run_id']}")
                            trace.enqueued.setdefault(key, e.start_ns * 1e-9)
    return trace


def clock_shift(trace: Trace) -> float:
    """Seconds to add to a device time to put it on the host's clock:
    the smallest shift that puts every program's start at or after its
    enqueue on the host (0.0 where no program can be matched)."""
    shifts = [
        trace.enqueued[k] - start
        for k, (start, _end) in trace.modules.items() if k in trace.enqueued
    ]
    return max(shifts) if shifts else 0.0


def union(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merged, sorted, disjoint intervals covering the same points."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    first = np.concatenate([[True], s[1:] > e[:-1]])
    last = np.concatenate([first[1:], [True]])
    return s[first], e[last]


def _clip(starts, ends, lo, hi):
    s, e = np.clip(starts, lo, hi), np.clip(ends, lo, hi)
    keep = e > s
    return s[keep], e[keep]


def _covered(lo: float, hi: float, spans: Tuple[np.ndarray, np.ndarray]) -> float:
    s, e = _clip(spans[0], spans[1], lo, hi)
    return float(np.sum(e - s))


def op_name(text: str) -> str:
    """`%sort.11` from `%sort.11 = (u32[...]) sort(...)`."""
    return text.split(" = ", 1)[0].strip()


def is_sort(text: str) -> bool:
    """An operation whose XLA name contains `sort`: its own name, or the
    opcode of its text (`... sort(` after the result shape)."""
    head, _, rest = text.partition(" = ")
    return "sort" in head or " sort(" in rest.split(", calls=")[0]


def reduce(trace: Trace, top: int = 10) -> dict:
    """The numbers the per-layer readers and the result line take."""
    windows = [a for a in trace.annotations if a.name == WINDOW]
    shift = clock_shift(trace)
    if not windows or not trace.device_ops:
        raise ValueError(f"trace holds no {WINDOW} span or no device plane")
    lo, hi = windows[0].start, windows[0].end
    window_s = hi - lo

    def spans_of(name):
        sel = [a for a in trace.annotations if a.name == name]
        return union(np.asarray([a.start for a in sel]),
                     np.asarray([a.end for a in sel]))

    engine, client = spans_of(ENGINE), spans_of(CLIENT)
    busy, op_time, sort_time = [], {}, 0.0
    gaps: List[Tuple[str, float]] = []
    totals = {IN_ENGINE: 0.0, IN_PROTOCOL: 0.0, NO_STATEMENT: 0.0}
    for plane, (names, starts, ends) in sorted(trace.device_ops.items()):
        s, e = _clip(starts + shift, ends + shift, lo, hi)
        us, ue = union(s, e)
        busy.append(float(np.sum(ue - us)))
        inside = (ends + shift > lo) & (starts + shift < hi)
        for text, a, b in zip(
            np.asarray(names, dtype=object)[inside],
            np.clip(starts[inside] + shift, lo, hi),
            np.clip(ends[inside] + shift, lo, hi),
        ):
            op_time[op_name(text)] = op_time.get(op_name(text), 0.0) + (b - a)
            if is_sort(text):
                sort_time += b - a
        # idle gaps of this chip, each split by what the host was doing
        edges_lo = np.concatenate([[lo], ue])
        edges_hi = np.concatenate([us, [hi]])
        for a, b in zip(edges_lo, edges_hi):
            if b <= a:
                continue
            in_engine = _covered(a, b, engine)
            in_client = _covered(a, b, client)
            parts = {
                IN_ENGINE: in_engine,
                IN_PROTOCOL: max(in_client - in_engine, 0.0),
                NO_STATEMENT: max((b - a) - max(in_client, in_engine), 0.0),
            }
            for k, v in parts.items():
                totals[k] += v
            gaps.append((max(parts, key=parts.get), float(b - a)))
    n = len(busy)
    busy_s = sum(busy) / n
    all_ops = sum(op_time.values())
    gaps.sort(key=lambda g: -g[1])
    idle_gaps = [[f"total.{k}", v / n] for k, v in totals.items()]
    idle_gaps += [[f"gap{i + 1}.{k}", v] for i, (k, v) in
                  enumerate(gaps[: top - len(idle_gaps)])]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "op_seconds": all_ops / n,
        "sort_seconds": sort_time / n,
        "clock_shift_s": shift,
        "chips": n,
        "device_ops": [
            [k, v / n] for k, v in
            sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": idle_gaps,
        "statements_in_window": sum(
            1 for a in trace.annotations
            if a.name == CLIENT and lo <= a.end <= hi
        ),
    }
