"""Layer device: of the traced window, the time the device was idle inside
the engine and only `query.*` or `phase.execute`, or nothing of the
program, covered it, %. With the two other shares it sums to the
yardstick's `total.in_engine`. Source: the program's
spans in the run's own trace (`chipbench/spans.py`, SPANS.md)."""

from chipbench import spans


def read(run):
    return spans.read_idle_share(run, spans.UNATTRIBUTED)
