"""Layer parse / plan / cache: wall inside `phase.parse`, `phase.plan`
(canonicalise, key, lookup, access check; analyse and optimise on a miss)
and `phase.instantiate`, over the statements that completed in the traced
window, ms. Source: the program's
spans in the run's own trace (`chipbench/spans.py`, SPANS.md)."""

from chipbench import spans


def read(run):
    return spans.read_total(run, "plan_s", 1e3)
