"""Shared arithmetic of the plain references: numpy on the host, exact
integers, nothing of the program imported. `tables` is
{table: {column: (host array, dictionary | None)}}; a dictionary is
only asked for its `.values` (the sorted strings its codes index)."""

import datetime

import numpy as np

_LOW_BITS = 24
_MAX_EXACT_ROWS = 1 << (53 - _LOW_BITS)


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - datetime.date(1970, 1, 1)).days


def col(tables, table, name):
    return tables[table][name][0]


def dict_values(tables, table, name):
    return list(tables[table][name][1].values)


BLOCK_ROWS = 1 << 18


def blocks(n_rows: int):
    """Slices of BLOCK_ROWS rows over [0, n_rows): the references work
    through a table block by block, so that their temporaries stay a few
    megabytes each whatever the scale (a fresh 480 MB array per
    expression is what made them slow at SF10)."""
    for lo in range(0, n_rows, BLOCK_ROWS):
        yield slice(lo, min(lo + BLOCK_ROWS, n_rows))


def add_sums(total, part):
    """Per-group sums of two blocks of rows, added (`total` None: the
    first block). Each keeps its type: int64 stays exact, the control's
    float32 stays float32."""
    return part if total is None else [t + p for t, p in zip(total, part)]


def group_sums(codes: np.ndarray, n_groups: int, *values: np.ndarray):
    """Exact int64 per-group sums of each value column (codes in
    [0, n_groups)), and the group sizes last. float64 `bincount` is
    exact while every partial sum is a whole number under 2**53: a
    column whose largest value times the row count stays under that is
    summed in one pass, any other is split into its low 24 bits and the
    rest and summed in two."""
    if len(codes) >= _MAX_EXACT_ROWS:
        raise ValueError("group_sums: too many rows for exact float sums")
    out = []
    for v in values:
        v = np.asarray(v, dtype=np.int64)
        top = int(v.max()) if len(v) else 0
        if len(v) and (v.min() < 0 or top >= 1 << (2 * _LOW_BITS)):
            raise ValueError("group_sums: value outside [0, 2**48)")
        if top * len(v) < 1 << 53:
            out.append(np.bincount(codes, v, n_groups).astype(np.int64))
            continue
        low = np.bincount(codes, v & ((1 << _LOW_BITS) - 1), n_groups)
        high = np.bincount(codes, v >> _LOW_BITS, n_groups)
        out.append(
            (high.astype(np.int64) << _LOW_BITS) + low.astype(np.int64)
        )
    out.append(np.bincount(codes, minlength=n_groups).astype(np.int64))
    return out


def dec(unscaled: int, scale: int) -> float:
    """A decimal as the statement protocol renders it: the JSON double
    nearest the exact quotient, so equality with the served value is
    exact up to what a double can carry."""
    return int(unscaled) / 10 ** scale


def avg2(total: int, count: int) -> float:
    """avg over decimal(12,2): rounded half up at scale 2 (non-negative)."""
    return dec((2 * int(total) + int(count)) // (2 * int(count)), 2)


def group_sums_float32(codes: np.ndarray, n_groups: int, *values: np.ndarray):
    """The control, never the reference: the same sums accumulated in
    float32, the precision a faster kernel would be tempted by (the
    chip has no native float64 or int64). `correct` has to come out
    false on it (tests/chipbench, PERF.md)."""
    order = np.argsort(codes, kind="stable")
    bounds = np.searchsorted(codes[order], np.arange(n_groups + 1))
    filled = bounds[1:] > bounds[:-1]
    out = []
    for v in values:
        sums = np.zeros(n_groups, dtype=np.float32)
        if filled.any():
            sums[filled] = np.add.reduceat(
                np.asarray(v, dtype=np.float32)[order], bounds[:-1][filled]
            )
        out.append(sums)
    out.append(np.diff(bounds).astype(np.int64))
    return out
