"""Layer kernels: device time of operations whose XLA name contains
`sort`, over the device time of all operations in the traced window, %."""


def read(run):
    if run.trace is None or not run.trace["op_seconds"]:
        return None
    return 100.0 * run.trace["sort_seconds"] / run.trace["op_seconds"]
