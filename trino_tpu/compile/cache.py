"""Managed compile caches: in-process program cache + persistent XLA
compilation-cache directory.

Two tiers, different lifetimes:

**ProgramCache** (in-process, cross-query). The planner builds one
`jax.jit` wrapper per fused filter/project stage; identical SQL
replanned later — a dynamic-filter retry, an FTE re-attempt, a
restarted LocalQueryRunner in the same process — rebuilds a
semantically identical wrapper, and jax treats distinct Python
callables as distinct jit caches. The ProgramCache closes that hole:
stages are keyed on their *structural* identity (frozen-dataclass expr
reprs + the input schema signature including dictionary values) and
the planner reuses the exact same jitted callable, so the re-plan
dispatches straight into jax's already-populated C++ fast path with
zero new lowerings.

**PersistentCompileCache** (on-disk, cross-process). Promotes the bare
`jax_compilation_cache_dir` wiring that used to live in jaxcfg.py into
a managed directory: entries live under a versioned salt directory
(`<root>/jax<version>-schema<rev>/`) so a jax upgrade or an engine
schema-rev bump starts a fresh namespace instead of deserializing
stale executables; startup scrubs zero-byte / orphaned-tmp entries
(a process killed mid-write must not poison successors); total size is
LRU-bounded by file mtime; hit/evict/scrub counts feed METRICS. CPU
processes get no persistent cache (XLA:CPU AOT entries can SIGILL on
reload).

Placement: `JAX_COMPILATION_CACHE_DIR`, when set, is the cache — JAX
reads the variable itself, so this module sets no directory in code and
only keeps its scrub/evict/counters on it. Unset, the cache is one
fixed path inside the checkout (`<repo>/.cache/xla/<salt>`): the path
is part of the cache key, so it is never built from a temp name, pid or
time.
"""

from __future__ import annotations

import os
import threading
from trino_tpu.analysis.witness import named_condition, named_lock, named_rlock
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

# Bump when the engine's batch layout / kernel calling conventions
# change in a way that invalidates cached executables' applicability
# (the salt below keys the persistent cache namespace on it).
ENGINE_SCHEMA_REV = 1

_MB = 1 << 20

# where JAX itself looks for the cache directory
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# the root when that variable is unset: fixed, inside the checkout
DEFAULT_CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".cache", "xla",
)


class ProgramCache:
    """Thread-safe LRU of structurally-keyed jitted callables.

    jax.jit returns a C++ PjitFunction that rejects attribute
    assignment, so the reverse mapping (callable -> key, used by the
    planner to key *compositions* of cached stages) is an id() side
    table rather than an attribute."""

    def __init__(self, max_entries: int = 1024):
        self._max_entries = max_entries
        self._lock = named_lock("ProgramCache._lock")
        self._entries: "OrderedDict[Any, Any]" = OrderedDict()  # guarded_by: _lock
        self._keys_by_id: Dict[int, Any] = {}  # guarded_by: _lock
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_create(self, key: Any, builder: Callable[[], Any]) -> Any:
        with self._lock:
            fn = self._entries.get(key)
            if fn is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return fn
        # build outside the lock (jit wrapper construction is cheap but
        # may import); racing builders are benign — first insert wins
        fn = builder()
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                self.hits += 1
                return existing
            self.misses += 1
            self._entries[key] = fn
            self._keys_by_id[id(fn)] = key
            while len(self._entries) > self._max_entries:
                _, old = self._entries.popitem(last=False)
                self._keys_by_id.pop(id(old), None)
                self.evictions += 1
        return fn

    def key_of(self, fn: Any) -> Optional[Any]:
        with self._lock:
            return self._keys_by_id.get(id(fn))

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._keys_by_id.clear()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
            }

    def fingerprints(self) -> list:
        """Stable string forms of every cached program key, in LRU
        order — the joining-host warm manifest (runtime/fabric.py)
        ships these so a new host can see which program identities the
        pod has compiled (observability: keys are structural tuples,
        repr is their canonical printable form)."""
        with self._lock:
            return [repr(k) for k in self._entries]


# the process singleton the planner uses
PROGRAM_CACHE = ProgramCache()


def schema_cache_key(schema) -> Optional[tuple]:
    """Structural signature of a [(DataType, Dictionary|None)] schema,
    dictionary *values* included — two plans over equal-typed columns
    with different string dictionaries bind different device constants
    and must not share a program. Returns None (uncacheable) for
    RuntimeDictionary columns, whose values only exist at execution
    time."""
    from trino_tpu.block import Dictionary

    parts = []
    for typ, d in schema:
        if d is None:
            dk = None
        elif type(d) is Dictionary:
            dk = d.values
        else:  # RuntimeDictionary (or future subclasses): bail out
            return None
        parts.append((str(typ), dk))
    return tuple(parts)


def expr_fingerprint(*parts) -> Optional[str]:
    """Deterministic fingerprint from expr-IR reprs. The IR nodes are
    frozen dataclasses whose repr is purely structural; a defensive
    check rejects anything that leaked an object address (default
    object repr) into the string."""
    fp = repr(parts)
    if " object at 0x" in fp:
        return None
    return fp


class PersistentCompileCache:
    """Managed on-disk XLA compilation cache (see module docstring)."""

    def __init__(self, root: Optional[str] = None,
                 max_bytes: Optional[int] = None):
        import jax

        self.salt = f"jax{jax.__version__}-schema{ENGINE_SCHEMA_REV}"
        # placed from outside: the directory is exactly what the
        # variable says (JAX reads it too, so both agree); otherwise a
        # salted directory under `root` that this class points JAX at
        self.external = root is None and bool(os.environ.get(CACHE_DIR_ENV))
        if self.external:
            self.root = self.dir = os.environ[CACHE_DIR_ENV]
        else:
            self.root = root or DEFAULT_CACHE_ROOT
            self.dir = os.path.join(self.root, self.salt)
        if max_bytes is None:
            max_bytes = int(
                os.environ.get("TRINO_TPU_COMPILE_CACHE_MAX_MB", "1024")
            ) * _MB
        self.max_bytes = max_bytes
        self.scrubbed = 0
        self.evicted = 0

    # -- directory maintenance ------------------------------------------

    def _entries(self):
        """[(path, size, mtime)] for regular files under the salt dir."""
        out = []
        try:
            names = os.listdir(self.dir)
        except OSError:
            return out
        for name in names:
            p = os.path.join(self.dir, name)
            try:
                st = os.stat(p)
            except OSError:
                continue
            if os.path.isfile(p):
                out.append((p, st.st_size, st.st_mtime))
        return out

    def scrub(self) -> int:
        """Corruption-tolerant startup scrub: drop zero-byte entries and
        orphaned temp files (a writer killed mid-rename leaves both).
        jax verifies entry checksums on read, so deeper corruption
        degrades to a cache miss — the scrub just keeps the directory
        from accumulating dead weight."""
        removed = 0
        for p, size, _ in self._entries():
            base = os.path.basename(p)
            if size == 0 or base.endswith(".tmp") or base.startswith("tmp"):
                try:
                    os.remove(p)
                    removed += 1
                except OSError:
                    pass
        self.scrubbed += removed
        if removed:
            _metrics_increment("compile_cache_scrubbed", removed)
        return removed

    def evict(self) -> int:
        """Size-bounded LRU: oldest-mtime entries go first until the
        salt dir fits max_bytes."""
        entries = sorted(self._entries(), key=lambda e: e[2])
        total = sum(size for _, size, _ in entries)
        removed = 0
        for p, size, _ in entries:
            if total <= self.max_bytes:
                break
            try:
                os.remove(p)
            except OSError:
                continue
            total -= size
            removed += 1
        self.evicted += removed
        if removed:
            _metrics_increment("compile_cache_evictions", removed)
        return removed

    def prepare(self) -> None:
        os.makedirs(self.dir, exist_ok=True)
        self.scrub()
        self.evict()

    # -- activation ------------------------------------------------------

    def activate(self) -> None:
        """Make the managed directory JAX's persistent compilation
        cache. Raises when the directory cannot be prepared or JAX
        refuses the settings: on a TPU process a cache that cannot be
        activated costs minutes of compiling per process, so it is an
        error, not a silent downgrade."""
        import jax

        self.prepare()
        if not self.external:
            jax.config.update("jax_compilation_cache_dir", self.dir)
        # cache every program: a query mints many that compile in about
        # a second each (the Pallas group-by among them), and CPU
        # processes, the old reason for a floor, never get here
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        install_cache_event_listener()

    # -- observability ---------------------------------------------------

    def entry_count(self) -> int:
        return len(self._entries())

    def total_bytes(self) -> int:
        return sum(size for _, size, _ in self._entries())

    def stats(self) -> Dict[str, Any]:
        return {
            "dir": self.dir,
            "entries": self.entry_count(),
            "bytes": self.total_bytes(),
            "max_bytes": self.max_bytes,
            "scrubbed": self.scrubbed,
            "evicted": self.evicted,
        }


# the active persistent cache, if configure_persistent_cache enabled one
ACTIVE_PERSISTENT_CACHE: Optional[PersistentCompileCache] = None

_cache_listener_installed = False


def _metrics_increment(name: str, delta: float = 1.0) -> None:
    try:
        from trino_tpu.runtime.metrics import METRICS

        METRICS.increment(name, delta)
    except Exception:
        pass


def install_cache_event_listener() -> bool:
    """Count persistent-cache hits/misses via jax.monitoring (jax
    records `/jax/compilation_cache/cache_hits` style events around
    disk-cache lookups). Idempotent; tolerant of jax builds that emit
    neither event."""
    global _cache_listener_installed
    if _cache_listener_installed:
        return True
    try:
        from jax import monitoring

        def _on_event(event: str, **kw) -> None:
            if "compilation_cache" not in event:
                return
            if "hit" in event:
                _metrics_increment("compile_cache_hits")
            elif "miss" in event:
                _metrics_increment("compile_cache_misses")

        monitoring.register_event_listener(_on_event)
    except Exception:
        return False
    _cache_listener_installed = True
    return True


def cpu_process() -> bool:
    """A CPU process is one whose JAX_PLATFORMS puts the CPU first: in
    "tpu,cpu" the CPU is only where host-side arrays live."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


def tpu_compiler_options(options: dict) -> Optional[dict]:
    """`compiler_options` for a jax.jit whose program needs a TPU
    compiler option: None in a CPU process, whose compiler refuses an
    option it does not know."""
    return None if cpu_process() else options


def configure_persistent_cache() -> Optional[PersistentCompileCache]:
    """jaxcfg entry point, run once at import. TPU-targeted processes
    only (XLA:CPU AOT entries record compile-option pseudo-features the
    loader rejects on reload, and can SIGILL); opt out entirely with
    TRINO_TPU_NO_COMPILE_CACHE=1. Raises if the cache cannot be
    activated."""
    global ACTIVE_PERSISTENT_CACHE
    if ACTIVE_PERSISTENT_CACHE is not None:
        return ACTIVE_PERSISTENT_CACHE
    if os.environ.get("TRINO_TPU_NO_COMPILE_CACHE") == "1" or cpu_process():
        return None
    cache = PersistentCompileCache()
    cache.activate()
    ACTIVE_PERSISTENT_CACHE = cache
    return cache
