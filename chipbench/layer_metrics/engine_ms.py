"""Layer operators, host loop: median time inside the runner's
`execute` (plan-cache lookup, operator loop, device work, result
materialisation), ms. Host clock around the call, from the benchmark's
own wrapper."""

import statistics


def read(run):
    walls = run.engine_walls()
    return statistics.median(walls) * 1e3 if walls else None
