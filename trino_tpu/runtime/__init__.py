"""Distributed runtime: workers, tasks, buffers, exchange, scheduling.

The coordinator/worker split of the reference (SURVEY.md §1 layers 2–9)
— a Python/host control plane around the XLA device data plane. The
in-process form (threads standing in for worker hosts) is the tier-3
DistributedQueryRunner test topology; the HTTP form runs the same task
runtime behind a real wire.

The names below are imported on first use, not with the package: the
engine's own modules (`exec/`, `connectors/`, `engine.py`) import
`runtime.tracing` and `runtime.metrics`, and the coordinator imports the
engine.
"""

import importlib

_EXPORTS = {
    "OutputBuffer": "buffers",
    "DistributedQueryRunner": "coordinator",
    "Worker": "worker",
}

__all__ = list(_EXPORTS)


def __getattr__(name: str):
    if name in _EXPORTS:
        module = importlib.import_module(f"{__name__}.{_EXPORTS[name]}")
        return getattr(module, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
