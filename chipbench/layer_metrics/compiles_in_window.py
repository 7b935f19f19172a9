"""Layer parse / plan / cache: XLA compiles inside the window (the
program's `xla_compiles` counter, fed by jax.monitoring). Anything but 0
means a shape was not warmed, and the tail pays for it."""


def read(run):
    return run.counters["xla_compiles"]
