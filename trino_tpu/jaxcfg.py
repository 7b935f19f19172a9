"""Central JAX configuration for trino_tpu.

Imported for side effect before any jax.numpy use. We enable x64 because
SQL semantics need BIGINT (int64) and DECIMAL-as-scaled-int64 exactness
(Trino models decimals as Int128/long — spi/type/DecimalType; we use
int64 which covers TPC-H's decimal(12,2) aggregates). Hot kernels
(hashing, probing) deliberately downcast to int32/uint32 lanes so the
TPU VPU runs native-width ops.
"""

import jax

jax.config.update("jax_enable_x64", True)


# Persistent compilation cache: the engine compiles one XLA program per
# (operator, shape) and a TPU compile of a sort over a million rows takes
# minutes, so caching them on disk lets every process after the first
# start from warm executables. Placement (JAX_COMPILATION_CACHE_DIR, else
# a fixed path inside the checkout), startup scrub, LRU eviction and
# counters live in compile/cache.py; the gating (TPU processes only,
# TRINO_TPU_NO_COMPILE_CACHE=1 opt-out) is applied there too. A cache
# that cannot be activated on a TPU process raises here, at import.
from trino_tpu.compile.cache import configure_persistent_cache  # noqa: E402

configure_persistent_cache()
