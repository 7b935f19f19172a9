"""Layer device: device busy time of the traced window over the
statements that completed in it, ms."""


def read(run):
    if run.trace is None or not run.trace_completed:
        return None
    return 1e3 * run.trace["busy_s"] / len(run.trace_completed)
