"""Serving tier: the high-QPS front of the engine.

Everything below turns the one-query-at-a-time engine into a server:

- `plan_cache`   — prepared-statement plan cache (PREPARE/EXECUTE skips
                   parse→analyze→optimize→fragment on a hit)
- `params`       — typed EXECUTE ... USING parameter binding
- `admission`    — lane-based admission in front of the resource
                   groups, with overload shedding (429 + Retry-After)
- `batcher`      — inter-query micro-batching of point lookups onto
                   one shared device step

The split mirrors the reference's dispatcher layer (DispatchManager +
QueryPreparer + resource-group submit path in front of the execution
engine), which is above all a serving system: the client protocol is
built for thousands of concurrent pollers, not one REPL.
"""

from trino_tpu.serving.admission import (  # noqa: F401
    AdmissionPipeline,
    OverloadSheddedError,
)
from trino_tpu.serving.batcher import MicroBatcher  # noqa: F401
from trino_tpu.serving.params import ParameterBindingError  # noqa: F401
from trino_tpu.serving.plan_cache import PlanCache  # noqa: F401
