"""What the harness counts around the program while it runs.

``Meter`` wraps the two kernel entry points where the store's data path
looks them up at call time (``gf256_matmul`` in its ops module, which
``RSCode`` reaches through the module; ``boundary_bitmap``, which
``split_chunks`` reaches through its module's globals), and listens to
JAX's compile events. Per entry point it keeps the calls, the host wall
seconds inside them (pad, transfer, launch, wait, copy back, slice) and the
bytes the work needs (``needed``). With ``annotate`` each call is also a
``TraceAnnotation`` span, so a device trace can say what the host was doing
in an idle gap.
"""
from __future__ import annotations

import time
from contextlib import nullcontext

from chipbench import needed

GF = "gf256_matmul"
CDC = "cdc_gearhash"


class Meter:
    def __init__(self, code_k: int, *, annotate: bool = False) -> None:
        self.code_k = code_k
        self.annotate = annotate
        self.calls = {GF: 0, CDC: 0}
        self.wall_s = {GF: 0.0, CDC: 0.0}
        self.bytes = {GF: 0, CDC: 0}
        self.compiles = 0
        self.cache_hits = 0
        self._undo: list = []

    def __enter__(self) -> "Meter":
        import jax.monitoring

        from repro.kernels.cdc_gearhash import ops as cdc_ops
        from repro.kernels.gf256_matmul import ops as gf_ops

        gf_fn, cdc_fn = gf_ops.gf256_matmul, cdc_ops.boundary_bitmap
        gf_ops.gf256_matmul = self._timed(
            GF, gf_fn, lambda a, kw: needed.gf256_bytes(a[0], a[1], self.code_k))
        cdc_ops.boundary_bitmap = self._timed(
            CDC, cdc_fn, lambda a, kw: needed.cdc_bytes(len(a[0])))
        self._undo = [(gf_ops, "gf256_matmul", gf_fn), (cdc_ops, "boundary_bitmap", cdc_fn)]
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        for mod, attr, fn in self._undo:
            setattr(mod, attr, fn)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _timed(self, name: str, fn, count_bytes):
        span = f"chipbench.device_call.{name}"

        def call(*args, **kw):
            if self.annotate:
                from jax.profiler import TraceAnnotation

                ctx = TraceAnnotation(span)
            else:
                ctx = nullcontext()
            t0 = time.perf_counter()
            with ctx:
                out = fn(*args, **kw)
            self.wall_s[name] += time.perf_counter() - t0
            self.calls[name] += 1
            self.bytes[name] += count_bytes(args, kw)
            return out

        return call

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "calls": dict(self.calls), "wall_s": dict(self.wall_s),
                "bytes": dict(self.bytes)}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {
            "compiles": after["compiles"] - before["compiles"],
            "cache_hits": after["cache_hits"] - before["cache_hits"],
            **{key: {n: after[key][n] - before[key][n] for n in after[key]}
               for key in ("calls", "wall_s", "bytes")},
        }
