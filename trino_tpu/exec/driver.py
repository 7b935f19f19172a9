"""Driver: the host loop moving device batches through an operator
pipeline.

Analogue of main/operator/Driver.java:65 (processInternal:371 — for each
adjacent operator pair, page = current.getOutput(); next.addInput(page);
finish cascade :417). TPU-first delta: the loop never touches data; it
only launches jitted device programs and handles the (rare) host-sync
points (join fan-out sizing, group-table growth). Trino's 1s-quantum
cooperative scheduling is unnecessary single-pipeline; the multi-driver
form arrives with the task runtime layer.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence

from trino_tpu.exec.operators import Operator
from trino_tpu.runtime import tracing


@dataclasses.dataclass
class Pipeline:
    """An ordered operator chain ending in a sink. Pipelines are executed
    in dependency order (build pipelines before their probe pipelines —
    the ordering Trino derives from LocalExecutionPlanner's pipeline
    DAG)."""

    operators: List[Operator]


class TaskAbortedError(RuntimeError):
    """Raised by Driver.run when the owning task was aborted or failed
    externally (kill, low-memory killer) — cooperative cancellation at
    batch boundaries so a doomed task stops burning device cycles."""


class Driver:
    """Runs one pipeline to completion (Driver.processInternal analogue)."""

    def __init__(self, pipeline: Pipeline, should_stop=None, observer=None,
                 span=None):
        self.ops = pipeline.operators
        # while a profiler trace runs, every operator call is a leaf
        # span of it, and `span` gets one operator span per operator
        self._span = span
        # made by the first operator call that finds the profiler on
        self._tallies = None
        self._finish_signalled = [False] * len(self.ops)
        self._should_stop = should_stop
        # observer(op_name, moved) fires after every batch move (moved=
        # True) and on blocked waits (moved=False) — the stuck-task
        # watchdog's per-batch heartbeat (TaskExecution._on_batch):
        # a task whose heartbeat goes stale past stuck_task_interrupt_s
        # is interrupted through should_stop
        self._observer = observer

    def run(self) -> None:
        try:
            return self._run()
        finally:
            if self._tallies is not None:
                tracing.record_operators(self._span, self._tallies)

    def _call(self, i: int, method: str):
        """Operator `i`'s call of `method` as a span: asked per call, so
        a pipeline that began before a trace is in it from its first
        instant; `tracing.OFF` without a trace."""
        if not tracing.profiling():
            return tracing.OFF
        if self._tallies is None:
            self._tallies = [
                tracing.OpTally(type(o).__name__, getattr(o, "span_stats", None))
                for o in self.ops
            ]
        return self._tallies[i].call(method)

    def _run(self) -> None:
        ops = self.ops
        n = len(ops)
        while not ops[-1].is_finished():
            if self._should_stop is not None and self._should_stop():
                raise TaskAbortedError("task aborted")
            progressed = False
            for i in range(n - 1):
                cur, nxt = ops[i], ops[i + 1]
                if nxt.is_finished():
                    continue
                # move as many batches as the pair allows (Driver.java:389)
                while nxt.needs_input():
                    # cancellation is checked per batch, not just per
                    # sweep: a killed task (low-memory killer, drain
                    # re-placement, speculation loser) must stop inside
                    # a long batch train, not after it
                    if self._should_stop is not None and self._should_stop():
                        raise TaskAbortedError("task aborted")
                    with self._call(i, "get_output"):
                        out = cur.get_output()
                    if out is None:
                        break
                    if self._tallies is not None:
                        # batches an operator put out; the sink's: took in
                        self._tallies[i].batches += 1
                        self._tallies[i + 1].batches += i + 2 == n
                    with self._call(i + 1, "add_input"):
                        nxt.add_input(out)
                    progressed = True
                    if self._observer is not None:
                        self._observer(type(cur).__name__, True)
                # finish cascade (Driver.java:417)
                if cur.is_finished() and not self._finish_signalled[i + 1]:
                    with self._call(i + 1, "finish"):
                        nxt.finish()
                    self._finish_signalled[i + 1] = True
                    progressed = True
            if not progressed and not ops[-1].is_finished():
                blocked = [o for o in ops if o.is_blocked()]
                if blocked:
                    # blocked on remote pages / buffer space: yield the
                    # thread (Driver.java:446 union of blocked futures,
                    # collapsed to a poll-and-sleep). This is NOT "stuck"
                    # — starvation on input is the UPSTREAM task's
                    # problem (its own watchdog names the real culprit),
                    # so the heartbeat stays fresh here
                    if self._observer is not None:
                        self._observer(type(blocked[0]).__name__, False)
                    import time

                    time.sleep(0.001)
                    continue
                raise RuntimeError(
                    "pipeline stalled: "
                    + ", ".join(
                        f"{type(o).__name__}(fin={o.is_finished()})" for o in ops
                    )
                )


def run_pipelines(pipelines: Sequence[Pipeline]) -> None:
    for p in pipelines:
        Driver(p).run()
