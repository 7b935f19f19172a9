#!/usr/bin/env python3
"""The control at a cell's own size: for each seed, the cell's statement
instances answered by the plain reference with its sums accumulated in
float32 (`references/_common.group_sums_float32`), compared with the
exact reference by the comparison that decides `correct`. It has to come
out as not correct on at least one statement of the cell for every seed.
Host only; the benchmark's own runs do not run it.

    python3 chipbench/control.py --workload sf1.scan_agg --seeds 1 2 3
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import trino_tpu  # noqa: F401
    from chipbench import data, harness, traffic
    from chipbench.references._common import group_sums_float32

    _benchmark, config, mix = traffic.load_cell(harness.ROOT, args.workload)
    all_failed = True
    tables = None
    for seed in args.seeds:
        plan = traffic.plan(mix, seed)
        if tables is None:
            columns = traffic.columns_to_load(plan.instances)
            directory, _ = data.ensure_columns(harness.ROOT, config["scale"], columns)
            tables = data.load_columns(directory, columns)
        wrong = {}
        for inst in {i.sql: i for i in plan.instances}.values():
            want = inst.statement.module.reference(tables, inst.params)
            got = inst.statement.module.reference(
                tables, inst.params, sums=group_sums_float32)
            wrong.setdefault(inst.name, []).append(
                not harness.same_rows(inst.statement, got, want))
        control_correct = not any(any(v) for v in wrong.values())
        all_failed &= not control_correct
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control_correct": control_correct,
            "instances_wrong": {k: f"{sum(v)}/{len(v)}" for k, v in wrong.items()},
        }), flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
