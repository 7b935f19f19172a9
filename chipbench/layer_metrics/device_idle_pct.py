"""Layer device: 1 - (union of device-operation intervals) / traced
window, %, averaged over the chips used."""


def read(run):
    return None if run.trace is None else run.trace["idle_pct"]
