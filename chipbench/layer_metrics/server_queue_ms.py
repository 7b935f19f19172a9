"""Layer client / protocol: wall inside `server.queued` (POST accepted to the runner's
`execute` entered: admission, the pool's hand-off as `handoff_us`, the
fast-lane and batcher probes), over the statements that completed in the
traced window, ms. Source: the program's
spans in the run's own trace (`chipbench/spans.py`, SPANS.md)."""

from chipbench import spans


def read(run):
    return spans.read_total(run, "queued_s", 1e3)
