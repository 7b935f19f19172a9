"""Layer operators, host loop: number of `sync.*` spans (device-to-host
readbacks) in the traced window over the statements that completed in it. Source: the program's
spans in the run's own trace (`chipbench/spans.py`, SPANS.md)."""

from chipbench import spans


def read(run):
    return spans.read_total(run, "syncs")
