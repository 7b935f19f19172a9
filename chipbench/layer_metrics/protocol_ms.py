"""Layer client / protocol: median of the client's wall minus the time
inside the runner's `execute` for the same statement, ms. Host clock."""

import statistics


def read(run):
    walls = run.protocol_walls()
    return statistics.median(walls) * 1e3 if walls else None
