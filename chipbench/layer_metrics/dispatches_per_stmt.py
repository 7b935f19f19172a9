"""Layer operators, host loop: number of `PjitFunction(*)` events (one per
launch of a jitted program from the host) in the traced window over the
statements that completed in it. Source: the profiler's own host events in
the run's trace, as `chipbench/spans.py` reduces them (`dispatches`); what
a reader answers without a trace or without program spans is what
`spans.read_total` answers."""

from chipbench import spans


def read(run):
    if run.trace is None or not run.trace_completed:
        return None
    reduced = spans.for_run(run)
    if reduced is None:
        return None
    launches = sum(row["count"] for row in reduced.get("dispatches", {}).values())
    return launches / len(run.trace_completed)
