"""What a metric's reader is given, and the arithmetic readers share.

Each ``metrics/<name>.py`` has ``read(r: Reading) -> float | None``. A
reader that finds nothing to read returns None, and the harness leaves the
metric out of the line; a share of a roofline is never made up as 0.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from chipbench import trace as tr

MiB, GiB = 1 << 20, 1 << 30


@dataclass
class Reading:
    window: object          # loop.Window: every op, the start and the close
    setup_s: float
    calls: dict             # over the window: kernel -> host calls
    wall_s: dict            # kernel -> host seconds inside its entry point
    needed: dict            # kernel -> bytes its work needs
    peaks: dict
    protocol_s: float | None = None  # traced runs: seconds in protocol code
    trace: tr.Trace | None = None


def done_bytes(r: Reading) -> int:
    return sum(d.nbytes for d in r.window.in_window())


def rate_MiBps(r: Reading, kind: str) -> float | None:
    ops = [d for d in r.window.in_window() if d.kind == kind]
    return sum(d.nbytes for d in ops) / MiB / r.window.seconds if ops else None


def p95_s(r: Reading, kind: str) -> float | None:
    """95th percentile of the latency of every op of ``kind`` issued in the
    window that completed, those that finished after the close included."""
    lat = [d.done - d.issued for d in r.window.ops if d.kind == kind and d.error is None]
    return float(np.percentile(lat, 95)) if lat else None


def per_gib(r: Reading, seconds: float | None) -> float | None:
    b = done_bytes(r)
    return None if seconds is None or not b else seconds / (b / GiB)


def device_call_s(r: Reading) -> float:
    return sum(r.wall_s.values())


def roofline_pct(r: Reading, kernel: str) -> float | None:
    """Least time for the bytes the kernel's work needs, at the HBM peak,
    over the summed device time of its events: a share of 100 or less."""
    if r.trace is None or not r.needed.get(kernel):
        return None
    spent = tr.kernel_s(r.trace, kernel)
    if not spent:
        return None
    return 100.0 * r.needed[kernel] / r.peaks["hbm_bytes_per_s"] / spent


def idle_pct(r: Reading) -> float | None:
    if r.trace is None or not r.trace.window_s:
        return None
    busy = tr.busy_s(r.trace)
    return None if busy is None else 100.0 * (1.0 - busy / r.trace.window_s)
