#!/usr/bin/env python3
"""Run one cell with the control or a fault planted, to read what the check
compares when the timed path is wrong. Not part of a benchmark run.

    python3 benchmarks/chip/control.py --workload <cell> --seed <n> \\
        --seconds <s> --fault int8|flip|half|unstored|stale [--seed <n> ...]

Several ``--seed`` values run one after another in this one process, so the
chip is set up once. Each prints the result line; ``correct`` should read
false in every one.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv=None) -> int:
    from chipbench import faults, harness, spec

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=faults.NAMES, default="int8")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.compile_cache()
    peaks = spec.peaks(devices[0].device_kind)
    for seed in args.seed:
        with faults.planted(args.fault):
            line = harness.run_cell(cell, seed, args.seconds, False,
                                    t0=time.perf_counter(), peaks=peaks)
        print(json.dumps({"control": args.fault, "seed": seed, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
