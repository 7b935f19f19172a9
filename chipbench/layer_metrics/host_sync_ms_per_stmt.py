"""Layer operators, host loop: wall inside `sync.*` spans (the host waiting
for a readback) over the statements that completed in the traced window,
ms. Source: the program's
spans in the run's own trace (`chipbench/spans.py`, SPANS.md)."""

from chipbench import spans


def read(run):
    return spans.read_total(run, "sync_s", 1e3)
