"""Layer operators, host loop: wall of `phase.execute` minus its `cpu_ns` (the
executing thread's CPU time) minus the wall of the `sync.*` inside it:
runnable and not running (the GIL, the scheduler), over the statements
that completed in the traced window, ms. Source: the program's
spans in the run's own trace (`chipbench/spans.py`, SPANS.md)."""

from chipbench import spans


def read(run):
    return spans.read_total(run, "offcpu_s", 1e3)
