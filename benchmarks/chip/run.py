#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout; ``BENCHMARK.json`` names the cells. Any
platform but the TPU, or fewer chips than the cell asks for, exits non-zero
with no result.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

if __name__ == "__main__":
    from chipbench.harness import main

    sys.exit(main(t0=T0))
