"""Native runtime components (C++ via ctypes).

The compute path is XLA; the HOST runtime's inner loops (exchange page
splitting, mask compaction) are C++ — the role the reference fills with
JIT bytecode + Slice buffers (SURVEY.md §2.9). The library is compiled
on first use with the system toolchain and cached next to the source;
every entry point has a numpy fallback, so the engine runs (slower)
without a compiler."""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from trino_tpu.analysis.witness import named_condition, named_lock, named_rlock
from typing import List, Optional

import numpy as np

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "pagesplit.cpp")
_LIB = os.path.join(_DIR, "libpagesplit.so")

_lock = named_lock("native._lock")
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> Optional[str]:
    if os.path.exists(_LIB) and os.path.getmtime(_LIB) >= os.path.getmtime(_SRC):
        return _LIB
    try:
        # compile to a per-pid temp path, then atomically publish: the
        # in-process lock doesn't cover concurrent PROCESSES racing the
        # first build, and dlopen of a half-written .so is undefined
        tmp = f"{_LIB}.{os.getpid()}.tmp"
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, _LIB)
        return _LIB
    except Exception as e:
        # get_lib tries once per process, so this is said once
        compiler_said = (getattr(e, "stderr", None) or b"").decode(
            errors="replace"
        )[-500:]
        print(
            f"trino_tpu.native: could not build {os.path.basename(_LIB)} "
            f"({type(e).__name__}: {e}); using the numpy fallbacks\n"
            f"{compiler_said}",
            file=sys.stderr,
        )
        return None


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        path = _build()
        if path is None:
            return None
        lib = ctypes.CDLL(path)
        lib.partition_counts.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
        ]
        lib.scatter_column.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.mask_gather.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p,
        ]
        lib.mask_gather.restype = ctypes.c_int64
        _lib = lib
        return _lib


def _ptr(a: np.ndarray) -> ctypes.c_void_p:
    return ctypes.c_void_p(a.ctypes.data)


def partition_scatter(
    columns: List[np.ndarray], pids: np.ndarray, n_parts: int
) -> List[List[np.ndarray]]:
    """Split columns by per-row partition id in ONE pass per column.
    Returns [partition][column] arrays. pids: int32, -1 = drop."""
    lib = get_lib()
    pids = np.ascontiguousarray(pids, dtype=np.int32)
    n = len(pids)
    if lib is None:
        out = []
        for p in range(n_parts):
            m = pids == p
            out.append([np.ascontiguousarray(c[m]) for c in columns])
        return out
    counts = np.zeros(n_parts, dtype=np.int64)
    lib.partition_counts(_ptr(pids), n, n_parts, _ptr(counts))
    scratch = np.zeros(n_parts, dtype=np.int64)
    outs: List[List[np.ndarray]] = [[] for _ in range(n_parts)]
    for col in columns:
        col = np.ascontiguousarray(col)
        item = col.dtype.itemsize
        bufs = [np.empty(int(counts[p]), dtype=col.dtype) for p in range(n_parts)]
        ptrs = (ctypes.c_void_p * n_parts)(
            *[b.ctypes.data for b in bufs]
        )
        lib.scatter_column(
            _ptr(col), item, _ptr(pids), n, n_parts,
            ctypes.cast(ptrs, ctypes.c_void_p), _ptr(scratch),
        )
        for p in range(n_parts):
            outs[p].append(bufs[p])
    return outs


def mask_compact(columns: List[np.ndarray], mask: np.ndarray) -> List[np.ndarray]:
    """Extract live rows from each column (Page.from_batch inner loop)."""
    lib = get_lib()
    mask = np.ascontiguousarray(mask, dtype=np.uint8)
    if lib is None:
        m = mask.astype(bool)
        return [np.ascontiguousarray(c[m]) for c in columns]
    n_live = int(mask.sum())
    out = []
    for col in columns:
        col = np.ascontiguousarray(col)
        if col.ndim == 2:
            # long-decimal (n, k) limb rows: one gather of k-wide items
            item = col.dtype.itemsize * col.shape[1]
            buf = np.empty((n_live, col.shape[1]), dtype=col.dtype)
            w = lib.mask_gather(
                _ptr(col), item, _ptr(mask), len(mask), _ptr(buf)
            )
        else:
            buf = np.empty(n_live, dtype=col.dtype)
            w = lib.mask_gather(
                _ptr(col), col.dtype.itemsize, _ptr(mask), len(mask), _ptr(buf)
            )
        assert w == n_live
        out.append(buf)
    return out
