"""Scale-out harness benchmark (ISSUE 7, ROADMAP 5a): 10^3..10^5 sessions.

Drives ``WorkloadGen`` — zipfian file popularity, a 95/5 read/write mix,
session arrival churn — through the Session tier at three population scales,
on both network engines:

* ``fast``   — the one-event-per-fan-out vectorised hot path (default);
* ``legacy`` — the seed's per-destination closures (``fast_net=False``),
  which replays the *same trace* (same seed ⇒ identical rounds, bytes,
  virtual times) while paying the per-message driver costs.

Because both engines execute byte-identical traces, every wall-clock delta
is pure driver overhead. Each row reports end-to-end wall time plus the
**driver / protocol** split (``Network.profile_protocol``): protocol time is
seconds inside op-generator bodies and ``Server.handle`` — storage-system
work identical on both engines — and driver time is everything else the
simulator does (heap, closures, RNG, framing, delivery bookkeeping).
``driver_events_per_sec`` = events / driver seconds is the engine-comparison
headline and the floor gated in ``make bench-smoke``.

Method notes: one small untimed warmup run absorbs one-time JIT/compile cost
(the CDC kernel path), and the collector is frozen around each timed run —
at 10^4+ sessions the live heap is large enough that gen-2 passes otherwise
dominate, more so for the allocation-heavy legacy engine.

    make bench-scale                      # 10^3 + 10^4, both engines
    PYTHONPATH=src python benchmarks/bench_scale.py --sessions 100000 \
        --legacy-at ''                    # the 10^5 run, fast engine only
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

from repro.core import DSS, DSSParams, WorkloadGen, WorkloadSpec  # noqa: E402
from repro.net.sim import LatencyModel  # noqa: E402


def _dss(fast: bool, seed: int) -> DSS:
    # small files / small blocks: protocol work per op stays modest, so the
    # session count — not payload coding — is what the benchmark scales.
    lat = LatencyModel(base_lo=0.1e-3, base_hi=0.3e-3, bandwidth=125e6)
    return DSS(DSSParams(
        algorithm="coaresecf", n_servers=6, parity_m=2, seed=seed,
        min_block=256, avg_block=512, max_block=2048,
        indexed=True, batched=True, latency=lat, fast_net=fast,
    ))


def scale_trial(sessions: int, fast: bool, *, seed: int = 7, files: int = 64,
                read_fraction: float = 0.95, file_size: int = 1024,
                gateway: bool = False, freeze_gc: bool = True) -> dict:
    """One timed run at ``sessions`` population on one engine; returns a
    flat row. Identical (spec, seed) on both engines replays an identical
    trace, so rounds/bytes/virtual columns must match across the pair."""
    dss = _dss(fast, seed)
    dss.net.profile_protocol = True
    gen = WorkloadGen(
        WorkloadSpec(sessions=sessions, files=files, file_size=file_size,
                     read_fraction=read_fraction, ops_per_session=1),
        seed=seed,
    )
    via = dss.gateway() if gateway else None
    gc.collect()
    if freeze_gc:
        gc.freeze()
        gc.disable()
    t0 = time.perf_counter()
    try:
        rep = gen.run(dss, via=via)
    finally:
        dss.net.profile_protocol = False
        if freeze_gc:
            gc.enable()
            gc.unfreeze()
    if via is not None:
        via.stop()
    wall = time.perf_counter() - t0
    proto = dss.net.protocol_time
    driver = max(wall - proto, 1e-9)
    row = {
        "bench": "scale",
        "engine": "fast" if fast else "legacy",
        "sessions": sessions,
        "wall_s": round(wall, 3),
        "protocol_s": round(proto, 3),
        "driver_s": round(driver, 3),
        "events": rep["events"],
        "events_per_sec": round(rep["events"] / wall),
        "driver_events_per_sec": round(rep["events"] / driver),
        "ops_per_sec": round(rep["ops"] / wall),
        "rpc_rounds": rep["rpc_rounds"],
        "msg_count": rep["msg_count"],
        "MB_sent": round(rep["bytes_sent"] / 1e6, 3),
        "ops_done": rep["ops_done"],
        "ops_failed": rep["ops_failed"],
        "ops_stuck": rep["ops_stuck"],
        "virtual_makespan": round(rep["virtual_makespan"], 6),
    }
    for k in ("op_p50", "op_p99", "read_p50", "read_p99"):
        if k in rep:
            row[k] = round(rep[k] * 1e3, 4)  # virtual ms
    return row


def warmup() -> None:
    """Untimed mini-run: pays one-time JIT compilation (CDC/coding kernels)
    so the first timed row is not charged for it."""
    scale_trial(20, True, seed=1, files=4, freeze_gc=False)


def run(sessions: list[int], legacy_at: list[int], *,
        gateway: bool = False, seed: int = 7) -> list[dict]:
    warmup()
    rows = []
    for n in sessions:
        fast_row = scale_trial(n, True, seed=seed, gateway=gateway)
        rows.append(fast_row)
        print(fast_row)
        if n in legacy_at:
            legacy_row = scale_trial(n, False, seed=seed, gateway=gateway)
            rows.append(legacy_row)
            print(legacy_row)
            for k in ("events", "rpc_rounds", "msg_count", "MB_sent",
                      "ops_done", "virtual_makespan"):
                assert fast_row[k] == legacy_row[k], (
                    f"trace divergence at {n} sessions: "
                    f"{k} fast={fast_row[k]} legacy={legacy_row[k]}"
                )
            print({
                "bench": "scale_ratio", "sessions": n,
                "wall_ratio": round(legacy_row["wall_s"] / fast_row["wall_s"], 2),
                "driver_ratio": round(
                    legacy_row["driver_s"] / fast_row["driver_s"], 2),
            })
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sessions", default="1000,10000",
                    help="comma-separated session counts (fast engine)")
    ap.add_argument("--legacy-at", default=None,
                    help="session counts to ALSO run on the legacy engine "
                         "(default: every --sessions count; '' disables)")
    ap.add_argument("--gateway", action="store_true",
                    help="attach every session through a shared Gateway")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--json", metavar="PATH", default=None)
    args = ap.parse_args()
    counts = [int(x) for x in args.sessions.split(",") if x]
    legacy = (counts if args.legacy_at is None
              else [int(x) for x in args.legacy_at.split(",") if x])
    out_rows = run(counts, legacy, gateway=args.gateway, seed=args.seed)
    if args.json:
        p = Path(args.json)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(out_rows, indent=2))
        print(f"scale: wrote {len(out_rows)} rows to {p}", file=sys.stderr)
