#!/usr/bin/env python3
"""Run the store's fragmented erasure-coded write/read path once on one TPU.

    python3 chip_smoke.py [--seed N]

Deployment: the paper's §VII-D Emulab store (``configs/paper_store.EMULAB``:
11 servers, parity_m = 5 so k = 6, CoARESECF) with the indexed genesis, the
"auto" coding backend and the paper's own block sizes (512 KiB minimum,
1 MiB maximum), built through ``DSS``/``DSSParams``. Phases:

  warm-up   compile every kernel width the later phases use (set-up)
  write     5 writer sessions store seeded random objects of 1-512 MiB
  read      5 reader sessions read every object back
  degraded  f = 2 servers crash (s0, a systematic holder, and s10), so every
            read decodes through the GF(256) kernel; everything is read again
  degraded-write
            while they are down, each writer stores one more object
  degraded-mixed
            s0 restarts with its disk and s1 crashes: each read batch now
            holds objects missing fragment 1 and objects missing fragments
            0 and 1, two survivor sets decoded in one block-diagonal launch
  repair    s1 and s10 lose their disks, recover, and everything is repaired
  reread    everything is read once more
  check     the history is linearizable, one stripe's stored parity equals
            the numpy LUT reference, one 64 MiB object's CDC hash stream and
            boundaries equal ``gearhash_ref``'s, and both kernels ran

Every read must be byte-identical to what was written. Earlier lines are
one JSON object per phase: wall time, bytes, kernel launches and compiles,
as ``repro.tracing`` counts them (write..reread are the steady phases and
must compile nothing). The last
line names the device. Any platform but the TPU is refused, and any failed
check exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

KiB, MiB = 1 << 10, 1 << 20
# the paper's file-size range (§VII-D: 1 MB - 512 MB), ragged sizes included
SIZES = tuple(s * MiB for s in (512, 64, 40, 32, 16, 8, 4, 3, 2, 1))
# written while servers are down: one per writer
LATE_SIZES = tuple(s * MiB for s in (16, 8, 4, 2, 1))
BLOCKS = dict(min_block=512 * KiB, avg_block=512 * KiB, max_block=1 * MiB)
WRITERS = READERS = 5
CDC_CHECK_SIZE = 64 * MiB
STEADY = ("write", "read", "degraded", "degraded-write", "degraded-mixed",
          "repair", "reread")
# virtual seconds an operation may take: a 512 MiB object crosses the
# simulated 1 Gbit/s client link in ~8 s
DEADLINE = 600.0


# a GF(256) launch's counter by operand rows: more rows than the code's k is
# a block-diagonal fused decode of several survivor sets
GF_ROWS = "gf256.launch.rows."


def counters(code_k: int) -> dict:
    """JAX compiles and kernel launches so far, from ``repro.tracing``."""
    from repro import tracing

    snap = tracing.snapshot()
    counts = snap["counts"]
    return {
        "compiles": sum(snap["compiles"].values()),
        "compile_s": sum(snap["compile_s"].values()),
        "cache_hits": counts.get("compile_cache_hits", 0),
        "gf256_matmul": counts.get("gf256.launch", 0),
        "gf256_fused": sum(n for name, n in counts.items()
                           if name.startswith(GF_ROWS) and int(name[len(GF_ROWS):]) > code_k),
        "cdc_gearhash": counts.get("cdc_kernel.launch", 0),
    }


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def run_phase(code_k: int, name: str, fn, report: list) -> object:
    """Run one phase; print and keep its wall time and counter deltas."""
    before = counters(code_k)
    t0 = time.perf_counter()
    out = fn()
    row = {"phase": name, "wall_s": time.perf_counter() - t0}
    after = counters(code_k)
    row.update({k: after[k] - before[k] for k in after})
    if isinstance(out, dict):
        row.update(out)
    print(json.dumps(row), flush=True)
    report.append(row)
    return out


def check_device():
    """The chip or nothing: no CPU fallback, no interpret mode."""
    import jax

    from repro.kernels.dispatch import kernel_is_native

    dev = jax.devices()[0]
    if dev.platform != "tpu" or not kernel_is_native():
        raise SystemExit(
            f"chip_smoke needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind})"
        )
    return dev


def build_store(*, seed: int, min_block: int, avg_block: int, max_block: int):
    from repro.configs.paper_store import EMULAB as cfg
    from repro.core import DSS, DSSParams
    from repro.net.sim import LatencyModel

    return DSS(DSSParams(
        algorithm=cfg.algorithm, n_servers=cfg.n_servers, parity_m=cfg.parity_m,
        seed=seed, min_block=min_block, avg_block=avg_block, max_block=max_block,
        indexed=True, coding_backend="auto",
        latency=LatencyModel(base_lo=cfg.base_lo, base_hi=cfg.base_hi,
                             bandwidth=cfg.bandwidth),
    ))


def make_payloads(sizes, seed: int, prefix: str = "obj") -> dict[str, bytes]:
    import numpy as np

    rng = np.random.default_rng(seed)
    return {f"{prefix}{i}-{n}B": rng.bytes(n) for i, n in enumerate(sizes)}


def warm_up(dss, payloads: dict, *, min_block: int, avg_block: int, n_down: int) -> dict:
    """Compile every program the steady phases run: the CDC kernel at each
    object's length bucket, and the GF(256) kernel at each width bucket a
    kernel-sized operand can take, up to the whole load in one batch, for
    encode (m, k), decode (k, k), a two-set fused decode (2k, 2k) and
    repair (n_down, k)."""
    import numpy as np

    from repro.erasure.rs import AUTO_KERNEL_MIN_BYTES
    from repro.kernels.cdc_gearhash.ops import boundary_bitmap
    from repro.kernels.dispatch import width_bucket
    from repro.kernels.gf256_matmul.ops import gf256_matmul

    k, m = dss.c0.k, dss.c0.n - dss.c0.k
    for L in sorted({width_bucket(len(v)) for v in payloads.values()}):
        boundary_bitmap(np.zeros(L, np.uint8), avg_block)
    # the whole load in one batch: each block adds at most one column to
    # its bytes / k, and only an object's last block is under min_block
    total = sum(len(v) for v in payloads.values())
    top = width_bucket(total // k + total // min_block + len(payloads))
    shapes = [(m, k), (k, k), (2 * k, 2 * k), (n_down, k)]
    for rows, cols in shapes:
        # the "auto" backend takes the kernel from AUTO_KERNEL_MIN_BYTES of
        # operand, so a taller operand reaches the kernel at a narrower width
        A = np.ones((rows, cols), np.uint8)
        w = width_bucket(-(-AUTO_KERNEL_MIN_BYTES // cols))
        while w <= top:
            gf256_matmul(A, np.zeros((cols, w), np.uint8))
            w *= 2
    return {"gf256_max_width": top, "gf256_shapes": shapes}


def write_all(dss, payloads: dict) -> dict:
    sessions = [dss.session(f"writer{i}") for i in range(WRITERS)]
    futs = [sessions[i % WRITERS].write(fid, data)
            for i, (fid, data) in enumerate(payloads.items())]
    stats = [f.result(deadline=DEADLINE) for f in futs]
    check(all(s["success"] for s in stats), "every write took effect")
    return {"bytes_written": sum(len(v) for v in payloads.values()),
            "blocks": sum(s["blocks"] for s in stats)}


def read_all(dss, payloads: dict, tag: str) -> dict:
    sessions = [dss.session(f"{tag}-reader{i}") for i in range(READERS)]
    fids = list(payloads)
    futs = [sessions[i % READERS].read(fid) for i, fid in enumerate(fids)]
    got = [f.result(deadline=DEADLINE) for f in futs]
    for fid, data in zip(fids, got):
        check(data == payloads[fid], f"{tag} read of {fid} is byte-identical")
    return {"bytes_read": sum(len(d) for d in got), "identical": True}


def down_servers(dss, n_down: int) -> list[str]:
    """Fragment 0's holder (systematic) and the last parity holder."""
    servers = dss.c0.servers
    return [servers[0]] + list(servers[len(servers) - n_down + 1:])


def repair(dss, down: list[str]) -> dict:
    dss.wipe_servers(down)
    dss.recover_servers(down, wipe=True)
    stats = dss.repair()
    pushed = sum(s["pushed"] for s in stats)
    check(pushed > 0, "repair rebuilt fragments")
    check(all(s["applied"] == s["missing"] for s in stats),
          "every missing fragment was applied")
    return {"objects": len(stats), "fragments_rebuilt": pushed}


def check_parity(dss, fid: str) -> dict:
    """The parity rows the servers hold for one data block of ``fid`` (the
    chip encoded them at write time, and rebuilt one in repair) equal the
    numpy LUT product."""
    import numpy as np

    from repro.core.fragment import SEP, genesis_id
    from repro.erasure.gf import gf_matmul_np
    from repro.erasure.rs import RSCode

    cfg = dss.c0
    blocks = sorted(o for o in dss.ec_objects()
                    if o.startswith(fid + SEP) and o != genesis_id(fid))
    check(bool(blocks), f"{fid} has data blocks")
    rows = []
    for sid in cfg.servers:
        lst = dss.net.servers[sid].ec[(blocks[0], 0)]
        rows.append(np.frombuffer(lst[max(lst)][0], np.uint8))
    check(len({r.size for r in rows}) == 1, "one stripe of equal-length fragments")
    P = RSCode(n=cfg.n, k=cfg.k).parity_matrix
    want = gf_matmul_np(P, np.stack(rows[: cfg.k]))
    check(bool((np.stack(rows[cfg.k:]) == want).all()), "stored parity == gf_matmul_np")
    return {"parity_block": blocks[0], "parity_bytes": int(want.size)}


def check_cdc(data: bytes, avg_block: int) -> dict:
    """The kernel's full 32-bit hash stream and boundary bitmap for one
    object equal gearhash_ref's."""
    import jax
    import numpy as np

    from repro.kernels.cdc_gearhash.ops import _mask_for_avg, gearhash
    from repro.kernels.cdc_gearhash.ref import gearhash_ref

    mask = _mask_for_avg(avg_block)
    h, b = gearhash(data, mask=mask)
    ref_h, ref_b = jax.jit(gearhash_ref, static_argnames=("mask",))(
        np.frombuffer(data, np.uint8), mask=mask)
    check(bool((h == np.asarray(ref_h)).all()), "CDC hash stream == gearhash_ref")
    check(bool((b == np.asarray(ref_b)).all()), "CDC bitmap == gearhash_ref")
    return {"cdc_bytes": len(data), "cdc_candidates": int(b.sum())}


def run(payloads: dict, late: dict, *, seed: int, min_block: int,
        avg_block: int, max_block: int, cdc_fid: str) -> list[dict]:
    """Every phase after the device check, with the tracer on; returns the
    per-phase rows. ``late`` objects are written while servers are down."""
    from repro import tracing

    was = tracing.enabled()
    tracing.enable(True)
    try:
        return _phases(payloads, late, seed=seed, min_block=min_block,
                       avg_block=avg_block, max_block=max_block, cdc_fid=cdc_fid)
    finally:
        tracing.enable(was)


def _phases(payloads: dict, late: dict, *, seed: int, min_block: int,
            avg_block: int, max_block: int, cdc_fid: str) -> list[dict]:
    report: list[dict] = []
    dss = build_store(seed=seed, min_block=min_block, avg_block=avg_block,
                      max_block=max_block)
    k = dss.c0.k
    n_down = (dss.c0.n - k) // 2
    everything = {**payloads, **late}
    run_phase(k, "warm-up", lambda: warm_up(
        dss, everything, min_block=min_block, avg_block=avg_block,
        n_down=n_down), report)
    run_phase(k, "write", lambda: write_all(dss, payloads), report)
    run_phase(k, "read", lambda: read_all(dss, payloads, "read"), report)
    down = down_servers(dss, n_down)
    dss.crash_servers(down)
    run_phase(k, "degraded", lambda: {
        "down": down, **read_all(dss, payloads, "degraded")}, report)
    run_phase(k, "degraded-write", lambda: write_all(dss, late), report)
    # the crashed systematic holder restarts with its disk and the next one
    # fails, so objects written before and during the outage lost different
    # fragments, and each reader's batch mixes the two
    swapped = [dss.c0.servers[1]] + down[1:]
    dss.recover_servers(down[:1])
    dss.crash_servers(swapped[:1])
    run_phase(k, "degraded-mixed", lambda: {
        "down": swapped, **read_all(dss, everything, "mixed")}, report)
    check(report[-1]["gf256_fused"] > 0,
          "a read batch decoded two survivor sets in one fused launch")
    run_phase(k, "repair", lambda: repair(dss, swapped), report)
    run_phase(k, "reread", lambda: read_all(dss, everything, "reread"), report)

    def final_checks() -> dict:
        hist = dss.check_history()
        out = {"history": hist, **check_parity(dss, cdc_fid),
               **check_cdc(payloads[cdc_fid], avg_block)}
        check(sum(r["gf256_matmul"] for r in report) > 0, "the GF(256) kernel ran")
        check(sum(r["cdc_gearhash"] for r in report) > 0, "the CDC kernel ran")
        return out

    run_phase(k, "check", final_checks, report)
    steady = sum(r["compiles"] for r in report if r["phase"] in STEADY)
    check(steady == 0, f"steady phases compiled {steady} programs")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache

    dev = check_device()
    cache_dir = enable_compile_cache()
    t0 = time.perf_counter()
    payloads = make_payloads(SIZES, args.seed)
    late = make_payloads(LATE_SIZES, args.seed + 1, prefix="late")
    print(json.dumps({"phase": "setup", "wall_s": time.perf_counter() - t0,
                      "objects": len(payloads) + len(late),
                      "bytes": sum(SIZES) + sum(LATE_SIZES),
                      "cut": None, "compile_cache": cache_dir, **BLOCKS}),
          flush=True)
    cdc_fid = next(f for f, v in payloads.items() if len(v) == CDC_CHECK_SIZE)
    report = run(payloads, late, seed=args.seed, cdc_fid=cdc_fid, **BLOCKS)
    stats = dev.memory_stats() or {}

    def total(key: str):
        return sum(r[key] for r in report)

    print(json.dumps({
        "phase": "total",
        "steady_wall_s": sum(r["wall_s"] for r in report if r["phase"] in STEADY),
        "steady_compiles": sum(r["compiles"] for r in report if r["phase"] in STEADY),
        "compile_s": total("compile_s"), "cache_hits": total("cache_hits"),
        "launches": {key: total(key) for key in ("gf256_matmul", "gf256_fused", "cdc_gearhash")},
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
