"""Benchmark driver — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only filesize,...]

Prints ``name,us_per_call,derived`` CSV rows (latencies are virtual-time;
derived carries the figure-specific extras) and writes the full table to
runs/bench_results.json.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

BENCHES = ["kernels", "filesize", "aws", "scalability", "blocksize", "recon",
           "checkpoint", "repair", "readpath"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(BENCHES)
    all_rows = []
    print("name,us_per_call,derived")
    for name in BENCHES:
        if name not in only:
            continue
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        rows = mod.run()
        wall = time.time() - t0
        for r in rows:
            r = dict(r)
            bench = r.pop("bench", name)
            us = None
            for k in ("write_ms", "read_ms", "save_full_ms", "restore_ms",
                      "cpu_ref_MBps", "cpu_MBps"):
                if k in r:
                    us = r[k] * 1e3 if k.endswith("_ms") else r[k]
                    break
            derived = ";".join(f"{k}={v if not isinstance(v, float) else round(v,4)}"
                               for k, v in r.items())
            print(f"{bench},{0.0 if us is None else round(us,2)},{derived}")
            all_rows.append({"bench": bench, **r})
        print(f"# {name}: {len(rows)} rows in {wall:.1f}s wall", file=sys.stderr)
    out = Path("runs/bench_results.json")
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(all_rows, indent=1, default=str))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
