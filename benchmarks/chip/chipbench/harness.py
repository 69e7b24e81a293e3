"""One run of one cell: set-up, the measured window, the check, the line.

``main`` refuses anything but the chips the cell asks for, turns on the
compile cache and prints the result. ``run_cell`` is everything after that
look, so the tests can drive a whole run on the CPU at a tiny size.

Earlier lines of standard output are JSON rows (set-up, window, check);
the last is the contract's result line. The numbers compared for
``correct`` are printed last on standard error, and under ``checks``, the
last key of the result line.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from dataclasses import replace

from chipbench import check, loop, spec
from chipbench import trace as tr
from chipbench.meter import Meter
from chipbench.readers import Reading
from chipbench.traffic import Traffic


def emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


# the simulated network's delays: one stream for every run, so that every
# seed replays the same schedule of ops (see traffic.py)
NETWORK_SEED = 0


def build_store(config: dict):
    from repro.core import DSS, DSSParams
    from repro.net.sim import LatencyModel

    lat = config["latency"]
    return DSS(DSSParams(
        algorithm=config["algorithm"], n_servers=config["n_servers"],
        parity_m=config["parity_m"], seed=NETWORK_SEED,
        min_block=config["min_block"], avg_block=config["avg_block"],
        max_block=config["max_block"], indexed=config["indexed"],
        coding_backend=config["coding_backend"],
        latency=LatencyModel(base_lo=lat["base_lo"], base_hi=lat["base_hi"],
                             bandwidth=lat["bandwidth"]),
    ))


def _split(specs: list, slots: int) -> list:
    """Set-up writes spread over the writer slots, one op each at a time; a
    write that names its session keeps it."""
    return [iter([replace(s, session=s.session or f"writer{i}") for s in specs[i::slots]])
            for i in range(slots) if specs[i::slots]]


def _warm_coding(config: dict, largest: int, warm: dict) -> None:
    """Compile the GF(256) kernel at every shape the window's coding can
    take that the warm-up ops need not reach: the encode of 1 to
    ``encode_blocks`` changed blocks, and the decode of anything up to the
    largest object from 1 to ``decode_groups`` fragment index sets (a
    decode whose blocks heard from different servers fuses them
    block-diagonally). Widths run from the program's kernel threshold up,
    bucket by bucket, as the program pads them."""
    import numpy as np

    from repro.erasure import rs
    from repro.kernels.dispatch import width_bucket
    from repro.kernels.gf256_matmul import ops as gf_ops

    n, m = config["n_servers"], config["parity_m"]
    k = n - m
    blocks = -(-largest // config["min_block"])

    def run(rows: int, cols: int, value_bytes: int, values: int) -> None:
        # a value is its 2-byte header and a chunk, padded to whole rows
        w = width_bucket(-(-rs.AUTO_KERNEL_MIN_BYTES // cols))
        hi = width_bucket(-(-(value_bytes + 2 * values) // k) + values)
        while w <= hi:
            gf_ops.gf256_matmul(np.ones((rows, cols), np.uint8), np.zeros((cols, w), np.uint8))
            w *= 2

    changed = int(warm.get("encode_blocks", 0))
    if changed:
        run(m, k, changed * config["max_block"], changed)
    for g in range(1, int(warm.get("decode_groups", 0)) + 1):
        run(g * k, g * k, largest, blocks)


def _failures(win, what: str) -> int:
    """Set-up ops that failed; they count against ``correct``."""
    bad = [d for d in win.ops if d.error] + win.lost
    if bad:
        emit({"row": "setup_failures", "what": what, "count": len(bad), "first": repr(bad[0])})
    return len(bad)


class _Span:
    """A ``TraceAnnotation`` opened and closed at two points of the loop."""

    def __init__(self, name: str, on: bool) -> None:
        self.ann = None
        if on:
            from jax.profiler import TraceAnnotation

            self.ann = TraceAnnotation(name)
            self.ann.__enter__()

    def close(self) -> None:
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
             t0: float, peaks: dict) -> dict:
    """Everything of a run after the device check; returns the result line
    without ``device``."""
    config, traffic = cell.config, Traffic(cell.traffic, cell.config, seed)
    dss = build_store(config)
    with Meter(code_k=dss.c0.k, annotate=traced) as meter:
        closed = loop.ClosedLoop(dss, traffic, annotate=traced)
        # -- set-up: preload, outage, warm-up of this cell's own shapes -------
        preload = traffic.preload()
        failed = (_failures(closed.run(_split(preload, config["writers"]), None), "preload")
                  if preload else 0)
        down = [dss.c0.servers[i] for i in traffic.fragments("down_fragments")]
        dss.crash_servers(down)
        failed += _failures(closed.run([iter(traffic.warmup())], None), "warm-up")
        if "warm_coding" in traffic.params:
            _warm_coding(config, max(traffic.sizes), traffic.params["warm_coding"])
        setup_s = time.perf_counter() - t0
        emit({"row": "setup", "setup_s": setup_s, "preload_objects": len(preload),
              "preload_bytes": sum(s.size for s in preload), "down": down, **meter.snapshot()})

        # -- the window ---------------------------------------------------------
        with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as log_path:
            if traced:
                import jax

                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(log_path, profiler_options=opts)
                dss.net.profile_protocol = True
            before, counters = meter.snapshot(), _counters(dss)
            dss.net.protocol_time = 0.0
            span = _Span(tr.WINDOW_SPAN, traced)
            at_close: dict = {}

            def on_close() -> None:
                span.close()
                at_close.update(meter=Meter.delta(meter.snapshot(), before),
                                protocol_s=dss.net.protocol_time)

            streams = [traffic.stream(kind, slot)
                       for kind in ("write", "edit", "read")
                       for slot in range(traffic.slots(kind))]
            win = closed.run(streams, seconds, on_close=on_close)
            if traced:
                jax.profiler.stop_trace()
                dss.net.profile_protocol = False
                trace = tr.load(tr.find_xplane(log_path))
            else:
                trace = None
        memory_peak = _memory_peak()
        delta = at_close["meter"]
        reading = Reading(
            window=win, setup_s=setup_s, calls=delta["calls"], wall_s=delta["wall_s"],
            needed=delta["bytes"], peaks=peaks,
            protocol_s=at_close["protocol_s"] if traced else None, trace=trace)
        emit(_window_row(win, delta, counters, dss))

    # -- the check, after the window and outside the meter --------------------
    t_check = time.perf_counter()
    numbers, notes = check.run_checks(dss, config, traffic, win)
    numbers["failed_ops"] += failed
    compared = {k: {"value": v, "limit": check.LIMITS[k]} for k, v in numbers.items()}
    emit({"row": "check", "check_s": time.perf_counter() - t_check, **notes, **compared})
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = spec.reader(m["name"])(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {
        "correct": all(c["value"] <= c["limit"] for c in compared.values()),
        "attempted": len(win.ops) + len(win.lost),
        "failed": sum(d.error is not None for d in win.ops) + len(win.lost),
        "metrics": metrics,
        "device": {"memory_peak_bytes": memory_peak},
    }
    if trace is not None:
        line["device"].update(busy_s=tr.busy_s(trace) or 0.0, window_s=trace.window_s)
        line["breakdown"] = {"device_ops": tr.top_ops(trace), "idle_gaps": tr.idle_gaps(trace)}
    line["checks"] = compared
    return line


def _counters(dss) -> list[int]:
    return [sum(c[i] for c in dss.net.client_counters.values()) for i in range(3)]


def _memory_peak() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _window_row(win, delta: dict, counters: list[int], dss) -> dict:
    import numpy as np

    row = {"row": "window", "seconds": win.seconds, "harness_s": win.harness_s,
           "compiles_in_window": delta["compiles"], "cache_hits": delta["cache_hits"],
           "kernel_calls": delta["calls"], "device_call_s": delta["wall_s"],
           "needed_bytes": delta["bytes"], "lost": len(win.lost)}
    for kind in ("write", "read"):
        ops = [d for d in win.ops if d.kind == kind]
        if not ops:
            continue
        lat = [d.done - d.issued for d in ops if d.error is None]
        inside = [d for d in win.in_window() if d.kind == kind]
        row[kind] = {"issued": len(ops), "done_in_window": len(inside),
                     "bytes_in_window": sum(d.nbytes for d in inside),
                     "failed": sum(d.error is not None for d in ops),
                     "latency_p50_s": float(np.percentile(lat, 50)) if lat else None,
                     "latency_p95_s": float(np.percentile(lat, 95)) if lat else None,
                     "latency_n": len(lat)}
    after = _counters(dss)
    row["rounds"], row["msgs"], row["wire_bytes"] = (a - b for a, b in zip(after, counters))
    return row


def compile_cache() -> str:
    """JAX's persistent compile cache in ``.jax_cache`` at the root of the
    checkout: a fixed path, so only a cell's first run there compiles, and
    one that two checkouts never share, whatever the environment names."""
    import jax

    path = str(spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # kernels compile in well under JAX's default 1 s floor; cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_chips(chips: int):
    """The chip or nothing: no CPU fallback, no interpret mode."""
    import jax

    from repro.kernels.dispatch import kernel_is_native

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or not kernel_is_native():
        raise SystemExit(f"this benchmark needs a TPU; JAX found {dev.platform!r} "
                         f"({dev.device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices


def main(argv=None, *, t0: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    # where set-up goes before the store is built: imports, then the TPU
    # runtime's start inside the first ``jax.devices()``
    t_imports = time.perf_counter()
    import jax  # noqa: F401

    t_jax = time.perf_counter()
    devices = require_chips(cell.chips)
    t_devices = time.perf_counter()
    emit({"row": "start", "t_s": t_devices - t0, "imports_s": t_imports - t0,
          "jax_import_s": t_jax - t_imports, "devices_s": t_devices - t_jax,
          "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
          "trace": args.trace, "compile_cache": compile_cache()})
    dev = devices[0]
    try:
        peaks = spec.peaks(dev.device_kind)
    except KeyError as e:
        raise SystemExit(str(e)) from None
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0=t0, peaks=peaks)
    line["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), **line["device"]}
    checks = line.pop("checks")
    line["checks"] = checks  # the last key
    print(json.dumps(line), flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    return 0
