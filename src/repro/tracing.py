"""One tracer for the store's hot path: named spans and counters, off by default.

The store calls ``span(name, nbytes=0, op=None)`` at each layer boundary
(op-generator steps, server handlers, framing, chunking, coding, CRC, the
kernel wrappers' pad / put / run / get / slice) and ``count(name, n)`` for
what those boundaries move (launches, bytes to and from the device, CRC
bytes, handled messages by type).

Off (the default), ``span`` returns one shared no-op context and ``count``
returns at once: a flag test, no allocation. On (``enable(True)``, or
``Network.profile_protocol = True``):

* each span is also a ``jax.profiler.TraceAnnotation`` named
  ``ares.<name>`` (with ``op``, the id of the ``OpFuture`` whose work it
  does, as metadata), so a profiler trace shows it on the host plane, on
  the clock of the device's ops;
* per name the tracer keeps calls, inclusive and self seconds (self is the
  span's duration less that of the spans opened inside it), bytes, and the
  JAX compiles that ran while it was the innermost open span;
* counters accumulate.

Totals are cumulative over the process: ``snapshot()`` reads them and
``delta(after, before)`` takes the difference of two snapshots, as
``chipbench.meter.Meter`` does; ``recording()`` is the delta since the
tracer was last switched on. ``protocol_s()`` is the inclusive seconds of
the outermost ``step`` and ``handle`` spans, what ``Network.protocol_time``
reads.

The tracer is process-wide, like the profiler, and assumes the store's one
thread. It reads the host clock only: it draws no randomness and schedules
nothing, so the virtual-time trace is the same with it on or off. A span
never stays open across a ``yield`` of an op generator.
"""
from __future__ import annotations

from time import perf_counter_ns

PREFIX = "ares."
# spans whose outermost instances make up ``protocol_s``
PROTOCOL = frozenset(("step", "handle"))
# the key of compiles that ran with no span open
NO_SPAN = ""
_COMPILE = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_PREFIX = "/jax/core/compile/"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_on = False
_listening = False
_annotation = None          # jax.profiler.TraceAnnotation, bound at enable
_stack: list = []           # open spans, innermost last
_proto_depth = 0
_protocol_ns = 0
_calls: dict[str, int] = {}
_incl_ns: dict[str, int] = {}
_self_ns: dict[str, int] = {}
_bytes: dict[str, int] = {}
_compiles: dict[str, int] = {}
_compile_s: dict[str, float] = {}
_counts: dict[str, int] = {}
_at_enable: dict | None = None


class _Off:
    """The span of a tracer that is off: enters and leaves, records nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


OFF = _Off()


class _Span:
    __slots__ = ("name", "nbytes", "ann", "t0", "child_ns")

    def __init__(self, name: str, nbytes: int, op) -> None:
        self.name = name
        self.nbytes = nbytes
        self.child_ns = 0
        self.ann = (_annotation(PREFIX + name) if op is None
                    else _annotation(PREFIX + name, op=op))

    # The clock is read just inside the annotation at both ends, and the
    # bookkeeping is done outside it, so the tracer and a profiler trace
    # time the same interval; the bookkeeping lands in the parent's self.
    def __enter__(self) -> "_Span":
        global _proto_depth
        _stack.append(self)
        if self.name in PROTOCOL:
            _proto_depth += 1
        self.ann.__enter__()
        self.t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        global _proto_depth, _protocol_ns
        dur = perf_counter_ns() - self.t0
        self.ann.__exit__(*exc)
        _stack.pop()
        name = self.name
        _calls[name] = _calls.get(name, 0) + 1
        _incl_ns[name] = _incl_ns.get(name, 0) + dur
        _self_ns[name] = _self_ns.get(name, 0) + dur - self.child_ns
        if self.nbytes:
            _bytes[name] = _bytes.get(name, 0) + self.nbytes
        if _stack:
            _stack[-1].child_ns += dur
        if name in PROTOCOL:
            _proto_depth -= 1
            if not _proto_depth:
                _protocol_ns += dur
        return False


def span(name: str, nbytes: int = 0, op=None):
    """A context that times ``name`` while the tracer is on."""
    if not _on:
        return OFF
    return _Span(name, nbytes, op)


def count(name: str, n: int = 1) -> None:
    if _on:
        _counts[name] = _counts.get(name, 0) + n


def enabled() -> bool:
    return _on


def enable(on: bool) -> None:
    """Switch the tracer on or off. Switching it on starts a new
    ``recording()``; totals carry on."""
    global _on, _annotation, _at_enable, _listening
    on = bool(on)
    if on and not _on:
        import jax.monitoring
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
        _at_enable = snapshot()
    _on = on


def _on_duration(event: str, secs: float, **_kw) -> None:
    if not _on or not event.startswith(_COMPILE_PREFIX):
        return
    where = _stack[-1].name if _stack else NO_SPAN
    _compile_s[where] = _compile_s.get(where, 0.0) + secs
    if event == _COMPILE:
        _compiles[where] = _compiles.get(where, 0) + 1


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        count("compile_cache_hits")


def protocol_s() -> float:
    return _protocol_ns * 1e-9


def snapshot() -> dict:
    return {
        "calls": dict(_calls),
        "incl_s": {n: v * 1e-9 for n, v in _incl_ns.items()},
        "self_s": {n: v * 1e-9 for n, v in _self_ns.items()},
        "bytes": dict(_bytes),
        "compiles": dict(_compiles),
        "compile_s": dict(_compile_s),
        "counts": dict(_counts),
        "protocol_s": protocol_s(),
    }


def delta(after: dict, before: dict) -> dict:
    """``after`` less ``before``, per name; names that did not move are left
    out."""
    out: dict = {"protocol_s": after["protocol_s"] - before["protocol_s"]}
    for key, now in after.items():
        if isinstance(now, dict):
            was = before.get(key, {})
            out[key] = {n: v - was.get(n, 0) for n, v in now.items()
                        if v != was.get(n, 0)}
    return out


def recording() -> dict:
    """What the tracer recorded since it was last switched on (until now, or
    until it was switched off)."""
    return snapshot() if _at_enable is None else delta(snapshot(), _at_enable)
