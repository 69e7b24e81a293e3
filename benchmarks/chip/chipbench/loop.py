"""Closed-loop clients, timed by the host's clock.

The store is one Python process that steps a virtual-time network, so the
wall clock is what a user of this emulation feels. ``ClosedLoop`` keeps one
op outstanding per slot: it steps ``dss.net`` one event at a time, after
each step polls every slot's ``OpFuture.done()``, stamps a completion with
``time.perf_counter()`` and issues the slot's next op. An op's latency runs
from its issue to the first poll that sees it done. Making a write's
content is timed apart (``harness_s``) and is not part of its latency.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field

clock = time.perf_counter
# how long past the close in-flight ops may take before they count as lost
DRAIN_S = 60.0


@dataclass
class Done:
    kind: str
    fid: str
    size: int
    issued: float
    done: float = 0.0
    nbytes: int = 0       # user bytes written or read back
    error: str | None = None
    answer: bytes | None = None  # a kept read's bytes
    index: tuple = ()
    result: dict | None = None   # what the program returned for a write


@dataclass
class Window:
    start: float
    close: float = 0.0
    ops: list[Done] = field(default_factory=list)
    lost: list[Done] = field(default_factory=list)  # never completed
    harness_s: float = 0.0

    @property
    def seconds(self) -> float:
        return self.close - self.start

    def in_window(self) -> list[Done]:
        return [d for d in self.ops if d.error is None and d.done <= self.close]


class ClosedLoop:
    def __init__(self, dss, traffic, *, annotate: bool = False) -> None:
        self.dss = dss
        self.traffic = traffic
        self.annotate = annotate
        self._sessions: dict[str, object] = {}

    def _session(self, cid: str):
        s = self._sessions.get(cid)
        if s is None:
            s = self.dss.session(cid)
            if cid.startswith("writer"):  # writers live on; readers are one-shot
                self._sessions[cid] = s
        return s

    def _issue(self, spec, win: Window):
        t0 = clock()
        if spec.kind == "write":
            span = nullcontext()
            if self.annotate:
                from jax.profiler import TraceAnnotation

                span = TraceAnnotation("chipbench.payload")
            with span:
                data = self.traffic.payload(spec)
            fut = self._session(spec.session).write(spec.fid, data)
        else:
            fut = self._session(spec.session).read(spec.fid)
        now = clock()
        win.harness_s += now - t0
        return spec, fut, Done(spec.kind, spec.fid, spec.size, now, index=spec.index)

    @staticmethod
    def _finish(spec, fut, rec: Done, now: float) -> Done:
        rec.done = now
        err = fut.exception()
        if err is not None:
            rec.error = f"{type(err).__name__}: {err}"
            return rec
        answer = fut.result()
        if spec.kind == "read":
            rec.nbytes = len(answer)
            if spec.keep:
                rec.answer = answer
            return rec
        rec.result = answer
        if answer.get("success"):
            rec.nbytes = spec.size
        else:
            rec.error = f"write not applied: {answer}"
        return rec

    def run(self, streams: list, seconds: float | None, on_close=None) -> Window:
        """Drive ``streams`` (one iterator of ``OpSpec`` per slot) for
        ``seconds`` of wall time, or until every stream ends when None.
        ``on_close()`` runs at the close. Ops in flight then finish before
        this returns (their latency counts the wait); none are issued after
        it."""
        net = self.dss.net
        win = Window(start=clock())
        deadline = float("inf") if seconds is None else win.start + seconds
        live: list = []
        for it in streams:
            spec = next(it, None)
            live.append((it, *self._issue(spec, win)) if spec else None)
        closed = False
        while any(live):
            if not net.step():
                raise RuntimeError("the network went idle with ops in flight")
            for i, slot in enumerate(live):
                if slot is None or not slot[2].done():
                    continue
                it, spec, fut, rec = slot
                now = clock()
                win.ops.append(self._finish(spec, fut, rec, now))
                nxt = None if closed or now >= deadline else next(it, None)
                live[i] = (it, *self._issue(nxt, win)) if nxt else None
            if not closed and clock() >= deadline:
                closed, win.close = True, clock()
                if on_close is not None:
                    on_close()
                live = self._drain(live, win)
        if not closed:
            win.close = clock()
        return win

    def _drain(self, live: list, win: Window) -> list:
        net, end = self.dss.net, win.close + DRAIN_S
        while any(live) and clock() < end:
            if not net.step():
                break
            for i, slot in enumerate(live):
                if slot is not None and slot[2].done():
                    win.ops.append(self._finish(slot[1], slot[2], slot[3], clock()))
                    live[i] = None
        win.lost = [slot[3] for slot in live if slot is not None]
        return []

