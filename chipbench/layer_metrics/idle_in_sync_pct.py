"""Layer device: of the traced window, the time the device was idle inside
the engine, no thread was working (see `idle_host_working_pct`) and at
least one was inside a `sync.*`: the host waited for a readback of work
the device had already finished, %. Source: the program's
spans in the run's own trace (`chipbench/spans.py`, SPANS.md)."""

from chipbench import spans


def read(run):
    return spans.read_idle_share(run, spans.IN_SYNC)
