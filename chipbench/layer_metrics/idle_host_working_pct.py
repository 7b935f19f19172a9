"""Layer device: of the traced window, the time the device was idle inside
the engine while some thread was inside a `phase.*` (but
`phase.execute`), `op.*`, `scan.*` or `result.*` span and outside any
`sync.*`: the host was busy and had given the device nothing, %. Source: the program's
spans in the run's own trace (`chipbench/spans.py`, SPANS.md)."""

from chipbench import spans


def read(run):
    return spans.read_idle_share(run, spans.HOST_WORKING)
