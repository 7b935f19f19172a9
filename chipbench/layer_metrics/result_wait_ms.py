"""Layer client / protocol: `since_finished_us` of every `server.respond` that
delivered a last page (the statement finished, the client had not come
for it yet: its poll interval and the GIL), over the statements that
completed in the traced window, ms. Source: the program's
spans in the run's own trace (`chipbench/spans.py`, SPANS.md)."""

from chipbench import spans


def read(run):
    return spans.read_total(run, "result_wait_s", 1e3)
