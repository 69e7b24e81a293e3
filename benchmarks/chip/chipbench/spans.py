"""The program's own spans: what its tracer recorded, and where they lie in
a profiler trace.

The store times itself with ``repro.tracing``: spans named ``ares.<name>``
at each layer boundary, and counters. Two views of them:

* ``recording()``: the tracer's totals over the traced window, per name
  (calls, inclusive and self seconds, bytes, compiles) and its counters.
  The harness switches the tracer on just before the window
  (``Network.profile_protocol``) and off after the window's in-flight ops
  have finished, so the totals run from the window's open to the end of
  that drain; per GiB they are divided by the bytes done in the window, as
  every ``readers.per_gib`` metric is. The program of a commit without the
  tracer gives None, and so does a recording without the names asked for.
* ``reduce(path)``: the ``ares.*`` and ``chipbench.*`` host events of a
  trace file. Nested spans are the point: each instant of the window
  belongs to the innermost span covering it (its self time), and each idle
  device instant to that span, or to no span. ``trace.idle_gaps`` assumes
  disjoint harness spans and stays as it is.
"""
from __future__ import annotations

from collections import defaultdict

from chipbench import trace as tr
from chipbench.readers import per_gib

PREFIX = "ares."
# the spans whose self time, and that of every span inside them, makes up
# the program's ``protocol_time``
PROTOCOL = ("ares.step", "ares.handle")


def recording() -> dict | None:
    try:
        from repro import tracing
    except ImportError:  # a program without the tracer
        return None
    rec = tracing.recording()
    return rec if rec["calls"] else None


def self_s_per_gib(r, names: tuple[str, ...]) -> float | None:
    """Self seconds of the named spans (without ``ares.``) per GiB done."""
    rec = recording()
    if rec is None or not any(n in rec["calls"] for n in names):
        return None
    return per_gib(r, sum(rec["self_s"].get(n, 0.0) for n in names))


def padding_pct() -> float | None:
    """Zero padding's share of the bytes the kernel wrappers sent to the
    device: padded less unpadded bytes over padded bytes."""
    rec = recording()
    if rec is None:
        return None
    counts = rec["counts"]
    padded = sum(v for k, v in counts.items() if k.endswith(".bytes_in_padded"))
    if not padded:
        return None
    unpadded = sum(v for k, v in counts.items() if k.endswith(".bytes_in"))
    return 100.0 * (padded - unpadded) / padded


# -- the trace file ---------------------------------------------------------
def _base(name: str) -> str:
    """A span's name without the metadata TraceMe appends (``name#k=v#``)."""
    return name.split("#", 1)[0]


def load_spans(path: str) -> list[tr.Event]:
    """Every ``ares.*`` and ``chipbench.*`` host event but the window's, by
    start, on any host line."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = _base(e.name)
                if (name.startswith((PREFIX, tr.SPAN_PREFIX))
                        and name != tr.WINDOW_SPAN):
                    out.append(tr.Event(name, e.start_ns * 1e-9, e.end_ns * 1e-9))
    out.sort(key=lambda e: (e.start, -e.end))
    return out


def owners(spans: list[tr.Event], lo: float, hi: float) -> list[tuple[float, float, str, str]]:
    """The window ``[lo, hi]`` cut into the pieces each span owns, as
    ``(start, end, name, outermost)``: a span owns what it covers less what
    the spans inside it cover. ``outermost`` names the span's outermost
    ancestor (itself at the top). Spans start in order and nest; one that
    outlives its parent is cut at the parent's end."""
    pieces: list[tuple[float, float, str, str]] = []
    # open spans: [name, end, owned-from, outermost]
    stack: list[list] = []

    def close_until(t: float) -> None:
        while stack and stack[-1][1] <= t:
            name, end, frm, top = stack.pop()
            if end > frm:
                pieces.append((frm, end, name, top))
            if stack:
                stack[-1][2] = end

    for e in spans:
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        close_until(a)
        if stack:
            b = min(b, stack[-1][1])
            parent = stack[-1]
            if a > parent[2]:
                pieces.append((parent[2], a, parent[0], parent[3]))
            top = parent[3]
        else:
            top = e.name
        stack.append([e.name, b, a, top])
    close_until(float("inf"))
    pieces.sort()
    return pieces


def _overlap(pieces, intervals) -> list[float]:
    """Per piece, its overlap with a sorted list of disjoint intervals."""
    out, j = [], 0
    for a, b, *_ in pieces:
        while j < len(intervals) and intervals[j][1] <= a:
            j += 1
        k, s = j, 0.0
        while k < len(intervals) and intervals[k][0] < b:
            s += min(b, intervals[k][1]) - max(a, intervals[k][0])
            k += 1
        out.append(s)
    return out


def reduce(path: str) -> dict:
    """The trace's spans inside its window: per name the self seconds and
    the idle device seconds it owns; the window's seconds that no span
    covers; the idle device seconds that no span covers. Besides, over the
    whole trace, the self seconds of ``ares.step``, ``ares.handle`` and the
    spans inside them (``protocol_all_s``), which the tracer's
    ``protocol_time`` should equal. Idle is taken on the first device that
    ran anything; a trace with no device leaves the idle numbers out."""
    trace = tr.load(path)
    if trace.window is None:
        raise ValueError(f"{path} has no {tr.WINDOW_SPAN} span")
    lo, hi = trace.window
    events = load_spans(path)
    pieces = owners(events, lo, hi)
    self_s: dict[str, float] = defaultdict(float)
    for a, b, name, _top in pieces:
        self_s[name] += b - a
    covered = sum(self_s.values())
    whole = owners(events, float("-inf"), float("inf"))
    out = {"window_s": hi - lo, "self_s": dict(self_s), "uncovered_s": (hi - lo) - covered,
           "protocol_all_s": sum(b - a for a, b, _n, top in whole if top in PROTOCOL)}
    ops = next((ev for ev in trace.devices.values() if ev), None)
    if ops is None:
        return out
    busy = tr.merged(tr._clip(ops, lo, hi))
    idle, t = [], lo
    for a, b in busy:
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    if t < hi:
        idle.append((t, hi))
    idle_s: dict[str, float] = defaultdict(float)
    for (_a, _b, name, _top), s in zip(pieces, _overlap(pieces, idle)):
        idle_s[name] += s
    total_idle = sum(b - a for a, b in idle)
    out.update(busy_s=sum(b - a for a, b in busy), idle_s=dict(idle_s),
               unattributed_idle_s=total_idle - sum(idle_s.values()))
    return out
