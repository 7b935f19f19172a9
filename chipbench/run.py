#!/usr/bin/env python3
"""Entry point of the benchmark: `python3 chipbench/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>`, from the root of a
checkout, on a machine that holds the chip. See chipbench/README.md."""

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from chipbench import harness

    sys.exit(harness.main(t_process=T_PROCESS))
