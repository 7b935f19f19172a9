"""Generated TPC-H columns, kept as .npy files inside the checkout.

TPC-H data is fixed by the spec for a scale factor, so nothing here
depends on `--seed`. Columns come from the program's own deterministic
generator (`connectors/tpch.generate_column`), over row ranges in child
processes pinned to the CPU, and are kept under
`<checkout>/.cache/chipbench/data/sf<scale>-<hash>/`, keyed by the scale
and a hash of the generator's source: a run that finds a column there
reads it in well under a second instead of generating it for a minute.
"""

from __future__ import annotations

import hashlib
import json
import multiprocessing
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# rows of the generator's base table per task: small enough that every
# core gets work at SF1, large enough that a task is mostly numpy
CHUNK_ROWS = 250_000
MAX_WORKERS = 12


def generator_hash() -> str:
    """Hash of the generator's source."""
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(
        os.path.dirname(here), "trino_tpu", "connectors", "tpch.py"
    )
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:12]


def cache_dir(root: str, scale: float) -> str:
    return os.path.join(
        root, ".cache", "chipbench", "data", f"sf{scale:g}-{generator_hash()}"
    )


def _generate_chunk(task: Tuple[str, str, float, int, int]):
    """Child-process entry: one column over base rows [lo, hi)."""
    table, column, scale, lo, hi = task
    from trino_tpu.connectors.tpch import generate_column

    data, dictionary = generate_column(table, column, scale, lo, hi)
    values = None if dictionary is None else list(dictionary.values)
    return table, column, lo, np.ascontiguousarray(data), values


def _paths(directory: str, table: str, column: str) -> Tuple[str, str]:
    stem = os.path.join(directory, f"{table}.{column}")
    return stem + ".npy", stem + ".dict.json"


def _write_atomic(path: str, write) -> None:
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        write(f)
    os.replace(tmp, path)


def generate_missing(directory: str, scale: float,
                     missing: Sequence[Tuple[str, str]]) -> None:
    """Generate the (table, column) pairs in `missing` into `directory`.
    The children are spawned with JAX_PLATFORMS=cpu, so they never reach
    for the chip this process will hold."""
    # imported before the environment is touched: the program decides at
    # import whether this process gets the persistent compile cache
    from trino_tpu.connectors.tpch import base_row_count

    from_env = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        tasks = []
        for table, column in missing:
            base = base_row_count(table, scale)
            for lo in range(0, base, CHUNK_ROWS):
                tasks.append(
                    (table, column, scale, lo, min(lo + CHUNK_ROWS, base))
                )
        n = max(1, min(MAX_WORKERS, os.cpu_count() or 1, len(tasks)))
        parts: Dict[Tuple[str, str], List[Tuple[int, np.ndarray]]] = {}
        dicts: Dict[Tuple[str, str], Optional[list]] = {}
        with multiprocessing.get_context("spawn").Pool(n) as pool:
            for table, column, lo, data, values in pool.imap_unordered(
                _generate_chunk, tasks
            ):
                parts.setdefault((table, column), []).append((lo, data))
                dicts[(table, column)] = values
    finally:
        if from_env is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = from_env
    os.makedirs(directory, exist_ok=True)
    for (table, column), chunks in parts.items():
        chunks.sort(key=lambda c: c[0])
        data = np.concatenate([c[1] for c in chunks])
        npy, dict_json = _paths(directory, table, column)
        if dicts[(table, column)] is not None:
            body = json.dumps(dicts[(table, column)]).encode()
            _write_atomic(dict_json, lambda f: f.write(body))
        _write_atomic(npy, lambda f: np.save(f, data))


def ensure_columns(root: str, scale: float,
                   columns: Dict[str, Sequence[str]]) -> Tuple[str, int]:
    """Make sure every column of `columns` ({table: [names]}) is in the
    cache. Returns (directory, number of columns generated now)."""
    directory = cache_dir(root, scale)
    missing = [
        (table, column)
        for table, names in columns.items() for column in names
        if not os.path.exists(_paths(directory, table, column)[0])
    ]
    if missing:
        generate_missing(directory, scale, missing)
    return directory, len(missing)


def load_columns(directory: str, columns: Dict[str, Sequence[str]]):
    """{table: {column: (host array, Dictionary | None)}}, the shape
    `chip_smoke.generate_tables` returns. Imports the program."""
    from trino_tpu.block import Dictionary

    tables = {}
    for table, names in columns.items():
        tables[table] = {}
        for column in names:
            npy, dict_json = _paths(directory, table, column)
            dictionary = None
            if os.path.exists(dict_json):
                with open(dict_json) as f:
                    dictionary = Dictionary(json.load(f))
            tables[table][column] = (np.load(npy), dictionary)
    return tables
