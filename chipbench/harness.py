"""One run of one cell: set-up, the measured window, the comparison.

`python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` calls `main`, which refuses anything but the TPU the cell
asks for and then calls `run_cell`. Everything that belongs to one cell,
configuration, statement, loop kind, runner kind or per-layer metric is a
file of its own, found by the name `BENCHMARK.json` gives (README.md).

Lines before the last are one JSON object per phase; the last line is
the result object of the benchmark's contract.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import shutil
import statistics
import time
from typing import Dict, List, Optional

from chipbench import data, stats, traffic
from chipbench import trace as trace_mod

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# after every instance has run once alone, the mix itself runs this long
# before the window, so that the window opens on a system in its stride
WARMUP_MIX_S = 1.0
# a traced run starts its trace this long into the window and traces this
# long: every cell's per-layer numbers come from the same kind of window
TRACE_STARTS_AT_S = 1.0
TRACE_S = 3.0
# no statement of any cell takes this long warm; a cold one compiles for
# minutes before its first row
CLIENT_TIMEOUT_S = 1100.0


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


@dataclasses.dataclass
class RunData:
    """What a per-layer reader (`layer_metrics/<metric>.py`) may read."""

    workload: dict
    config: dict
    instances: List[traffic.Instance]
    account: stats.WindowAccount
    # (sql, start, end) of every call of the runner's `execute`
    engine_samples: List[tuple]
    # METRICS counter deltas over the window
    counters: Dict[str, float]
    # rows the scan operators counted for each instance, run alone
    rows_scanned: List[float]
    # bytes one scanned row of each instance occupies on the device
    row_bytes: List[int]
    t0: float              # window start, perf_counter
    peaks: Optional[dict] = None
    # trace.reduce() of the traced part of the window, and the
    # statements that completed inside it
    trace: Optional[dict] = None
    trace_completed: Optional[List[stats.Sample]] = None

    def engine_walls(self) -> List[float]:
        """Seconds inside the runner's `execute`, for calls that ended
        inside the window."""
        lo, hi = self.t0, self.t0 + self.account.seconds
        return [e - s for _sql, s, e in self.engine_samples if lo <= e <= hi]

    def protocol_walls(self) -> List[float]:
        """Client wall minus the time inside `execute` for the same
        statement: per SQL text, the client's samples and the engine's
        pair up in order of start, and a pair counts when the engine's
        interval lies inside the client's."""
        by_sql: Dict[str, List[tuple]] = {}
        for sql, s, e in sorted(self.engine_samples, key=lambda t: t[1]):
            by_sql.setdefault(sql, []).append((s, e))
        out = []
        for sample in sorted(self.account.completed, key=lambda s: s.start):
            calls = by_sql.get(self.instances[sample.instance].sql, [])
            while calls and calls[0][0] < sample.start:
                calls.pop(0)
            if calls and calls[0][1] <= sample.end:
                s, e = calls.pop(0)
                out.append((sample.end - sample.start) - (e - s))
        return out


def scan_row_bytes(statement: traffic.Statement, tables) -> int:
    """Bytes one scanned row of `statement` occupies on the device: the
    item sizes of the columns its scans put there, from the loaded
    arrays' dtypes. Where a statement scans several tables, the
    narrowest table's row: a lower bound, since the program's counter
    does not say which table a row came from."""
    return min(
        sum(tables[table][column][0].dtype.itemsize for column in columns)
        for table, columns in statement.scan_columns.items()
    )


def same_rows(statement: traffic.Statement, got: list, want: list) -> bool:
    """Exact equality; as sets of rows where the SQL fixes no order."""
    if statement.ordered:
        return got == want
    return sorted(map(repr, got)) == sorted(map(repr, want))


def device_phase(chips: int, require_tpu: bool = True) -> dict:
    """The device as JAX reports it; anything but `chips` TPU devices is
    refused (tests drive the phases on the CPU and say so)."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if require_tpu and d0.platform != "tpu":
        raise SystemExit(
            f"chipbench: JAX found no TPU (platform={d0.platform!r}, "
            f"{len(devices)} device(s)); the benchmark only runs on the chip"
        )
    if require_tpu and len(devices) < chips:
        raise SystemExit(
            f"chipbench: the cell asks for {chips} chip(s), JAX reports "
            f"{len(devices)}"
        )
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def load_peaks(kind: str) -> dict:
    peaks = traffic.load_json(os.path.join(HERE, "peaks.json"))
    if kind not in peaks:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r}")
    return peaks[kind]


def memory_stats() -> dict:
    import jax

    return jax.devices()[0].memory_stats() or {}


def wrap_execute(runner, log: list) -> None:
    """Time every call of `runner.execute` from the benchmark's side and
    put it into the profiler's trace."""
    import jax

    inner = runner.execute

    @functools.wraps(inner)
    def execute(sql, *args, **kwargs):
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(trace_mod.ENGINE):
                return inner(sql, *args, **kwargs)
        finally:
            log.append((sql, t0, time.perf_counter()))

    runner.execute = execute


COUNTERS = (
    "xla_compiles", "plan_cache.hits", "plan_cache.misses", "rows_scanned",
    "compile_cache_hits", "compile_cache_misses", "queries.finished",
    "queries.failed",
)


def counters_now() -> Dict[str, float]:
    from trino_tpu.runtime.metrics import METRICS

    return {name: METRICS.counter(name) for name in COUNTERS}


def trace_part(trace_dir: str, marks: dict):
    """The `during` of a traced run: `TRACE_S` seconds of the window
    under the profiler, with the python tracer off (it records every
    call of the host loop and slows it)."""
    import jax

    def during(t0: float) -> None:
        time.sleep(max(0.0, t0 + TRACE_STARTS_AT_S - time.perf_counter()))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            marks["counters_lo"] = counters_now()
            marks["lo"] = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
                time.sleep(TRACE_S)
            marks["hi"] = time.perf_counter()
            marks["counters_hi"] = counters_now()
        finally:
            jax.profiler.stop_trace()

    return during


def read_layer_metrics(benchmark: dict, cell: str, run: RunData) -> dict:
    """Every per-layer metric `BENCHMARK.json` lists for this cell, from
    the reader `layer_metrics/<name>.py`; one that finds nothing to read
    returns None and is left out."""
    out = {}
    for metric in benchmark["per_layer"]:
        if "workloads" in metric and cell not in metric["workloads"]:
            continue
        reader = traffic.load_module(
            os.path.join(HERE, "layer_metrics", f"{metric['name']}.py")
        )
        value = reader.read(run)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             cache_root: str = ROOT, scale: Optional[float] = None,
             t_process: Optional[float] = None, require_tpu: bool = True
             ) -> dict:
    """Drive one run and return the result object. The cell is read from
    this checkout; what the run leaves behind (generated columns, the
    trace) goes under `cache_root`/.cache. `scale` overrides the
    configuration's (tests run the phases at `tiny`)."""
    t_process = time.perf_counter() if t_process is None else t_process
    import trino_tpu  # noqa: F401  (x64 + compile cache, before any jax use)

    benchmark, config, workload = traffic.load_cell(ROOT, cell)
    scale = config["scale"] if scale is None else scale
    plan = traffic.plan(workload, seed)

    # -- device: first, so that a machine without the chip fails at once
    # (the children that generate data are pinned to the CPU and never
    # reach for the chip this process now holds)
    import jax
    from trino_tpu.runtime.metrics import install_xla_compile_listener

    device = device_phase(config["chips"], require_tpu)
    peaks = load_peaks(device["kind"]) if require_tpu else None
    emit("device", **device, jax=jax.__version__,
         compile_cache_dir=jax.config.jax_compilation_cache_dir)
    if not install_xla_compile_listener():
        raise SystemExit("chipbench: no XLA compile listener in this jax")

    # -- data: generated columns, from the cache in the checkout --------
    t = time.perf_counter()
    columns = traffic.columns_to_load(plan.instances)
    directory, generated = data.ensure_columns(cache_root, scale, columns)
    tables = data.load_columns(directory, columns)
    loaded_bytes = sum(
        a.nbytes for cols in tables.values() for a, _ in cols.values()
    )
    data_s = time.perf_counter() - t
    emit("data", scale=scale, dir=os.path.relpath(directory, cache_root),
         columns_generated_now=generated, loaded_bytes=loaded_bytes,
         rows={tb: len(next(iter(c.values()))[0]) for tb, c in tables.items()},
         seconds=data_s)

    # -- engine ------------------------------------------------------------
    t = time.perf_counter()
    from trino_tpu.runtime.server import CoordinatorServer

    runner_kind = traffic.load_module(
        os.path.join(HERE, "runners", f"{config['runner']}.py")
    )
    runner = runner_kind.build(config, tables)
    engine_samples: List[tuple] = []
    wrap_execute(runner, engine_samples)
    server = CoordinatorServer(runner, port=0)
    load_s = time.perf_counter() - t
    poll_s = workload["client_poll_ms"] / 1e3
    try:
        # -- warm-up: every instance once, alone, then the mix itself ------
        t = time.perf_counter()
        from trino_tpu.client import Client

        def new_client():
            return Client(server.uri, timeout=CLIENT_TIMEOUT_S,
                          poll_interval=poll_s)

        client = new_client()
        rows_scanned, warm = [], []
        for inst in plan.instances:
            before = counters_now()
            t1 = time.perf_counter()
            client.execute(inst.sql)
            after = counters_now()
            rows_scanned.append(after["rows_scanned"] - before["rows_scanned"])
            warm.append({
                "statement": inst.name, "params": inst.params,
                "seconds": time.perf_counter() - t1,
                "compiles": after["xla_compiles"] - before["xla_compiles"],
                "rows_scanned": rows_scanned[-1],
            })
        plan.loop.run(plan, new_client, WARMUP_MIX_S, counters_now)
        warm_s = time.perf_counter() - t
        totals = counters_now()
        emit("warm", instances=warm, seconds=warm_s,
             xla_compiles=totals["xla_compiles"],
             persistent_cache_hits=totals["compile_cache_hits"],
             persistent_cache_misses=totals["compile_cache_misses"])

        # -- residency: what the warmed scans read is on the device -------
        resident = sum({
            inst.sql: n * scan_row_bytes(inst.statement, tables)
            for inst, n in zip(plan.instances, rows_scanned)
        }.values())
        mem = memory_stats()
        # the memory connector keeps one filtered copy per warmed
        # predicate value, so bytes in use exceed the loaded bytes
        emit("memory", bytes_in_use=mem.get("bytes_in_use"),
             peak_bytes_in_use=mem.get("peak_bytes_in_use"),
             loaded_bytes=loaded_bytes,
             scanned_bytes_resident_at_least=resident)
        if mem and mem["bytes_in_use"] < resident:
            raise SystemExit(
                f"chipbench: {mem['bytes_in_use']} bytes in use on the "
                f"device, less than the {resident} the warmed scans read"
            )

        # -- the window --------------------------------------------------------
        marks: dict = {}
        during = None
        trace_dir = os.path.join(cache_root, ".cache", "chipbench", "trace", cell)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            during = trace_part(trace_dir, marks)
        engine_samples.clear()
        before = counters_now()
        setup_s = time.perf_counter() - t_process
        samples, t0, at_close = plan.loop.run(
            plan, new_client, seconds, counters_now, during
        )
        counters = {k: at_close[k] - before[k] for k in COUNTERS}
    finally:
        server.stop()
    account = stats.account(samples, t0, seconds)
    emit("setup", setup_s=setup_s, data_s=data_s, load_s=load_s,
         warm_s=warm_s, other_s=setup_s - data_s - load_s - warm_s,
         note="other_s: imports and reaching the chip")
    if counters["xla_compiles"]:
        emit("COMPILES_IN_WINDOW", count=counters["xla_compiles"],
             note="a program compiled inside the measured window")
    emit("window", seconds=seconds, completed=len(account.completed),
         failed=len(account.failed), in_flight_at_close=account.in_flight,
         counters=counters)
    by_name: Dict[str, List[float]] = {}
    for s in account.completed:
        by_name.setdefault(plan.instances[s.instance].name, []).append(
            (s.end - s.start) * 1e3)
    emit("statements", **{
        name: {"count": len(w), "median_ms": statistics.median(w)}
        for name, w in sorted(by_name.items())
    })

    # -- compare: every completed answer against the plain reference ---------
    t = time.perf_counter()
    wants = {
        inst.sql: inst.statement.module.reference(tables, inst.params)
        for inst in {i.sql: i for i in plan.instances}.values()
    }
    reference_s = time.perf_counter() - t
    mismatches: Dict[str, int] = {}
    compared: Dict[str, int] = {}
    for s in account.completed:
        inst = plan.instances[s.instance]
        kind = f"{inst.name}.reference"
        compared[kind] = compared.get(kind, 0) + 1
        if not same_rows(inst.statement, s.rows, wants[inst.sql]):
            mismatches[kind] = mismatches.get(kind, 0) + 1
    for kind in sorted(compared):
        emit("compare", against=kind, compared=compared[kind],
             mismatches=mismatches.get(kind, 0), limit=0)
    emit("compare", failed_statements=len(account.failed), limit=0,
         first_error=account.failed[0].error if account.failed else None,
         reference_s=reference_s, references=len(wants))
    correct = bool(account.completed) and not mismatches and not account.failed

    mem = memory_stats()
    device["memory_peak_bytes"] = mem.get("peak_bytes_in_use")
    emit("memory", bytes_in_use=mem.get("bytes_in_use"),
         peak_bytes_in_use=mem.get("peak_bytes_in_use"),
         loaded_bytes=loaded_bytes)
    result = {
        "correct": correct, "attempted": account.attempted,
        "failed": len(account.failed) + sum(mismatches.values()),
        "metrics": {}, "device": device,
    }
    if not account.completed:
        return result
    units = {m["name"]: m["unit"] for m in benchmark["end_to_end"]}
    if not trace:
        values = {**stats.end_to_end(account), "setup_s": setup_s}
        result["metrics"] = {
            k: {"value": v, "unit": units[k]} for k, v in values.items()
        }
        return result
    run = RunData(workload, config, plan.instances, account, engine_samples,
                  counters, rows_scanned,
                  [scan_row_bytes(i.statement, tables) for i in plan.instances],
                  t0, peaks)
    reduced = trace_mod.reduce(trace_mod.load(trace_mod.find_xplane(trace_dir)))
    run.trace = reduced
    run.trace_completed = [
        s for s in account.completed if marks["lo"] <= s.end <= marks["hi"]
    ]
    emit("trace", window_s=reduced["window_s"], busy_s=reduced["busy_s"],
         clock_shift_s=reduced["clock_shift_s"],
         statements_completed=len(run.trace_completed),
         rows_scanned_counter=marks["counters_hi"]["rows_scanned"]
         - marks["counters_lo"]["rows_scanned"],
         rows_scanned_of_completed=sum(
             rows_scanned[s.instance] for s in run.trace_completed))
    result["metrics"] = read_layer_metrics(benchmark, cell, run)
    device["busy_s"] = reduced["busy_s"]
    device["window_s"] = reduced["window_s"]
    result["breakdown"] = {
        "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
    }
    return result


def main(argv: Optional[List[str]] = None, t_process: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      t_process=t_process)
    print(json.dumps(result), flush=True)
    return 0
