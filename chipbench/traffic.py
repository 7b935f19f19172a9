"""The one traffic generator: statement instances and their schedule
from a traffic file, the statement files it names and `--seed`.

TPC-H's tables are fixed by the spec, so the seed draws what the spec's
qgen draws: each statement's substitution parameters from the ranges in
its statement file, and the order they are sent in, which the mix's loop
kind (`loops/<loop>.py`) lays out. A new mix is a new traffic file; a
new statement is a statement file and its plain reference; a new loop
kind is a file under `loops/`.
"""

from __future__ import annotations

import dataclasses
import datetime
import importlib.util
import json
import os
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Statement:
    name: str
    sql: str                      # template, str.format fields
    draws: Dict[str, dict]
    tables: Dict[str, List[str]]  # columns referenced, to be loaded
    scan_columns: Dict[str, List[str]]  # columns its scans put on the device
    ordered: bool
    module: Any                   # references/<reference>.py


@dataclasses.dataclass
class Instance:
    """One statement with its parameters drawn: what a stream sends."""

    statement: Statement
    params: Dict[str, Any]
    sql: str

    @property
    def name(self) -> str:
        return self.statement.name


@dataclasses.dataclass
class Plan:
    instances: List[Instance]
    loop: Any                     # loops/<loop>.py
    schedule: Any                 # what `loop.schedule` laid out for `loop.run`


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "chipbench_file_" + os.path.relpath(path, HERE).replace(os.sep, "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_statement(name: str) -> Statement:
    spec = load_json(os.path.join(HERE, "statements", f"{name}.json"))
    module = load_module(
        os.path.join(HERE, "references", f"{spec['reference']}.py")
    )
    return Statement(
        name=name, sql=" ".join(spec["sql"]) if isinstance(spec["sql"], list)
        else spec["sql"],
        draws=spec["draws"], tables=spec["tables"],
        scan_columns=spec["scan_columns"], ordered=spec["ordered"],
        module=module,
    )


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(HERE, "traffic", f"{name}.json"))


def draw(rng: np.random.Generator, spec: dict):
    """One parameter. Kinds: `int` (lo..hi inclusive), `choice`
    (values), `date` (lo..hi ISO days inclusive, drawn as an ISO
    string)."""
    kind = spec["draw"]
    if kind == "int":
        return int(rng.integers(spec["lo"], spec["hi"] + 1))
    if kind == "choice":
        return spec["values"][int(rng.integers(len(spec["values"])))]
    if kind == "date":
        lo = datetime.date.fromisoformat(spec["lo"])
        hi = datetime.date.fromisoformat(spec["hi"])
        day = int(rng.integers((hi - lo).days + 1))
        return (lo + datetime.timedelta(days=day)).isoformat()
    raise ValueError(f"unknown draw kind {kind!r}")


def instantiate(statement: Statement, params: Dict[str, Any]) -> Instance:
    """The SQL text for `params`. A reference module may derive template
    fields from the drawn parameters (`fields(params)`)."""
    fields = dict(params)
    if hasattr(statement.module, "fields"):
        fields.update(statement.module.fields(params))
    return Instance(statement, params, statement.sql.format(**fields))


def plan(workload: dict, seed: int) -> Plan:
    """`params_per_statement` instances of each statement, distinct
    where the ranges allow, and the schedule the mix's loop kind makes
    of them. Every seed offers the same work: the same number of
    instances of the same statements, with other values in another
    order."""
    rng = np.random.default_rng(int(seed))
    per = workload["params_per_statement"]
    names = workload["statements"]
    instances: List[Instance] = []
    for name in names:
        statement = load_statement(name)
        seen = []
        for _ in range(per):
            for _attempt in range(64):
                params = {k: draw(rng, d) for k, d in sorted(statement.draws.items())}
                if params not in seen:
                    break
            seen.append(params)
            instances.append(instantiate(statement, params))
    loop = load_module(os.path.join(HERE, "loops", f"{workload['loop']}.py"))
    return Plan(instances, loop, loop.schedule(workload, len(names), per, rng))


def load_cell(root: str, cell: str):
    """(BENCHMARK.json, the cell's configuration file, its traffic file)."""
    benchmark = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if cell not in cells:
        raise SystemExit(f"chipbench: no workload {cell!r} in BENCHMARK.json")
    files = {c["name"]: c["file"] for c in benchmark["configs"]}
    config = load_json(os.path.join(root, files[cells[cell]["config"]]))
    return benchmark, config, load_traffic(cells[cell]["traffic"])


def columns_to_load(instances: List[Instance]) -> Dict[str, List[str]]:
    """{table: [columns]}: the union of what the statements reference."""
    columns: Dict[str, List[str]] = {}
    for inst in instances:
        for table, names in inst.statement.tables.items():
            have = columns.setdefault(table, [])
            have.extend(n for n in names if n not in have)
    return columns
