"""Arithmetic on samples: percentiles and the window's accounting."""

from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List, Optional, Sequence


def exact_percentile(samples: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over the raw sample list (as the
    program's `serving/harness.exact_percentile`, copied so that a later
    PR cannot change it)."""
    if not samples:
        raise ValueError("exact_percentile of no samples")
    s = sorted(samples)
    idx = max(0, min(len(s) - 1, int(round(q * (len(s) - 1)))))
    return s[idx]


@dataclasses.dataclass
class Sample:
    """One statement as its client saw it (seconds on perf_counter)."""

    stream: int
    instance: int          # index into the run's statement instances
    start: float           # POST about to be sent
    end: float             # last page in hand (or the error raised)
    rows: Optional[list]   # None when the statement failed
    error: Optional[str] = None


@dataclasses.dataclass
class WindowAccount:
    completed: List[Sample]   # ended inside the window, with rows
    failed: List[Sample]      # ended inside the window with an error
    in_flight: int            # started inside, ended after: not counted
    seconds: float

    @property
    def attempted(self) -> int:
        return len(self.completed) + len(self.failed)


def account(samples: Sequence[Sample], t0: float, seconds: float) -> WindowAccount:
    """Every statement that ended inside [t0, t0 + seconds] counts, all
    of them and only them: a rate is completed work over the whole
    window, a tail is the tail of every completed statement. A statement
    still in flight when the window closes is finished (the server ends
    quiet) but belongs to no window."""
    end = t0 + seconds
    completed, failed, in_flight = [], [], 0
    for s in samples:
        if s.start < t0:
            raise ValueError("a sample started before the window")
        if s.end > end:
            in_flight += 1
        elif s.error is not None:
            failed.append(s)
        else:
            completed.append(s)
    return WindowAccount(completed, failed, in_flight, seconds)


def end_to_end(acc: WindowAccount) -> Dict[str, float]:
    """stmt_p50_ms, stmt_p95_ms, stmts_per_s over the window."""
    walls_ms = [(s.end - s.start) * 1e3 for s in acc.completed]
    return {
        "stmt_p50_ms": statistics.median(walls_ms),
        "stmt_p95_ms": exact_percentile(walls_ms, 0.95),
        "stmts_per_s": len(acc.completed) / acc.seconds,
    }
