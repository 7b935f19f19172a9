"""Layer operators, host loop: host wall inside `PjitFunction(*)` events
(the host launching jitted programs) in the traced window over the
statements that completed in it, ms: the wall of what
`dispatches_per_stmt.py` counts, from the same source and with the same
answers where there is nothing to read."""

from chipbench import spans


def read(run):
    if run.trace is None or not run.trace_completed:
        return None
    reduced = spans.for_run(run)
    if reduced is None:
        return None
    host_s = sum(row["host_s"] for row in reduced.get("dispatches", {}).values())
    return 1e3 * host_s / len(run.trace_completed)
