"""Reduce a profiler trace (``*.xplane.pb``) to what the metrics read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the chip. Host spans are the harness's own
``TraceAnnotation`` events, all named ``chipbench.*``: the window, each
device call, each payload made. The window span bounds every interval, so
what ran before or after it is not counted.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
# what the host was doing when no harness span covers an idle gap
HOST_DEFAULT = "protocol_or_driver"


@dataclass
class Event:
    name: str
    start: float  # seconds
    end: float
    kernel: str | None = None  # the kernel this op runs, when it is one


@dataclass
class Trace:
    devices: dict[str, list[Event]] = field(default_factory=dict)
    spans: list[Event] = field(default_factory=list)
    window: tuple[float, float] | None = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0] if self.window else 0.0


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _kernel_of(name: str, stats, kernels) -> str | None:
    for k in kernels:
        if k in name:
            return k
    for _key, value in stats:
        if isinstance(value, str):
            for k in kernels:
                if k in value:
                    return k
    return None


def load(path: str, kernels=("gf256_matmul", "cdc_gearhash")) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    # an op's event is named by its HLO text: "%copy.1 = u8[...] copy(...)"
                    ops.append(Event(e.name.split(" = ", 1)[0].lstrip("%"),
                                     e.start_ns * 1e-9, e.end_ns * 1e-9,
                                     _kernel_of(e.name, e.stats, kernels)))
            trace.devices[plane.name] = sorted(ops, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        ev = Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        if e.name == WINDOW_SPAN:
                            trace.window = (ev.start, ev.end)
                        else:
                            trace.spans.append(ev)
    trace.spans.sort(key=lambda e: e.start)
    return trace


def _clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events if e.end > lo and e.start < hi]


def merged(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float | None:
    """Seconds in which an operation ran, in the window, averaged over the
    devices that ran any."""
    if not trace.window:
        return None
    lo, hi = trace.window
    per = [sum(b - a for a, b in merged(_clip(ev, lo, hi)))
           for ev in trace.devices.values() if ev]
    return sum(per) / len(per) if per else None


def kernel_s(trace: Trace, kernel: str) -> float | None:
    """Summed device time of the kernel's events in the window."""
    if not trace.window:
        return None
    lo, hi = trace.window
    spans = [iv for ev in trace.devices.values()
             for iv in _clip([e for e in ev if e.kernel == kernel], lo, hi)]
    return sum(b - a for a, b in spans) if spans else None


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    if not trace.window:
        return []
    lo, hi = trace.window
    total: dict[str, float] = defaultdict(float)
    for ev in trace.devices.values():
        for e in ev:
            for a, b in _clip([e], lo, hi):
                total[e.kernel or e.name] += b - a
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """Idle device seconds in the window by what the host was doing: the
    harness span that covers each idle instant, else ``HOST_DEFAULT``.
    Taken on the first device that ran anything."""
    if not trace.window:
        return []
    lo, hi = trace.window
    ops = next((ev for ev in trace.devices.values() if ev), [])
    busy = merged(_clip(ops, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    # the harness's spans are disjoint: a payload or a device call, one at
    # a time on one thread; give each gap's overlap with them to their name
    spans = [e for e in trace.spans if e.end > lo and e.start < hi]
    starts = [e.start for e in spans]
    total: dict[str, float] = defaultdict(float)
    for ga, gb in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, ga) - 1, 0)
        while i < len(spans) and spans[i].start < gb:
            over = min(gb, spans[i].end) - max(ga, spans[i].start)
            if over > 0:
                total[spans[i].name[len(SPAN_PREFIX):]] += over
                covered += over
            i += 1
        total[HOST_DEFAULT] += (gb - ga) - covered
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]
