#!/usr/bin/env python3
"""Where a traced window's host time goes, span by span.

    python3 benchmarks/chip/split.py --workload <cell> --seed <n> \\
        --seconds <s> [--keep <dir>]

Runs one cell as ``run.py --trace 1`` does, on the TPU, and prints the same
rows and result line. The harness deletes its profiler trace before the
result line; this script reduces the trace's ``ares.*`` and ``chipbench.*``
spans first (``chipbench.spans.reduce``) and, with ``--keep``, copies it to
``<dir>/<cell>.<seed>.xplane.pb``. After the result line comes one more
row, ``{"row": "program_spans", ...}``:

* ``spans``: per span name, the program tracer's calls, inclusive and self
  seconds, bytes and compiles (from the window's open to the end of its
  drain), and the trace's self seconds and idle device seconds inside the
  window (``trace_self_s``, ``idle_s``);
* the window's seconds, its seconds outside every span (``uncovered_s``)
  and the idle device seconds outside every span (``unattributed_idle_s``),
  each also as a percent of the window;
* ``protocol``: the trace's self seconds of ``ares.step``, ``ares.handle``
  and every span inside them, over the whole trace, beside the tracer's
  ``protocol_time`` over the same span of time;
* ``counts``: the tracer's counters.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def program_spans_row(rec: dict, red: dict) -> dict:
    names = {f"ares.{n}" for n in rec["calls"]} | set(red["self_s"])
    rows = {}
    for name in sorted(names):
        short = name[len("ares."):] if name.startswith("ares.") else None
        row = {key: rec[key].get(short, 0) for key in
               ("calls", "incl_s", "self_s", "bytes", "compiles")} if short else {}
        row.update(trace_self_s=red["self_s"].get(name, 0.0),
                   idle_s=red.get("idle_s", {}).get(name, 0.0))
        rows[name] = row
    w = red["window_s"]
    out = {"row": "program_spans", "window_s": w, "spans": rows,
           "uncovered_s": red["uncovered_s"], "uncovered_pct": 100.0 * red["uncovered_s"] / w,
           "protocol": {"trace_self_s": red["protocol_all_s"], "tracer_s": rec["protocol_s"]},
           "counts": rec["counts"]}
    if "busy_s" in red:
        out.update(busy_s=red["busy_s"], unattributed_idle_s=red["unattributed_idle_s"],
                   unattributed_idle_pct=100.0 * red["unattributed_idle_s"] / w)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description="Split a traced window's host time by span.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=Path, default=None)
    args = ap.parse_args()

    from chipbench import harness, spans
    from chipbench import trace as tr

    reduced: dict = {}
    find = tr.find_xplane

    def find_and_reduce(log_dir: str) -> str:
        path = find(log_dir)
        reduced.update(spans.reduce(path))
        if args.keep is not None:
            args.keep.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, args.keep / f"{args.workload}.{args.seed}.xplane.pb")
        return path

    tr.find_xplane = find_and_reduce
    rc = harness.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1"], t0=T0)
    from repro import tracing

    print(json.dumps(program_spans_row(tracing.recording(), reduced)), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
