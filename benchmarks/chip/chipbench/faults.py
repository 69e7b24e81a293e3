"""The control, and faults planted under the timed path, that the check
must catch. None is ever on in a benchmark run; ``control.py`` and the
tests switch one on around ``run_cell``.

``int8``
    the control: the GF(256) product computed in plain 8-bit integer
    arithmetic (multiply-add mod 256), the precision a uint8 matmul unit
    gives, in the kernel's place. It breaks the stated guarantee that an
    acknowledged write stays readable with f servers down.
``flip``
    an answer altered where it is produced: one byte of every GF(256)
    product flipped.
``half``
    half of the batch left out: the right half of every GF(256) product's
    columns (the later blocks laid side by side) left at zero.
``unstored``
    a step that returns its state unchanged: servers acknowledge the coded
    fragments of writes without storing them.
``stale``
    an answer altered where it is produced, in the block diff: an update
    leaves one changed block of the file's old chain unwritten (the plan
    keeps its old data), and the op still acknowledges.
"""
from __future__ import annotations

from contextlib import contextmanager

import numpy as np

NAMES = ("int8", "flip", "half", "unstored", "stale")


def _int8(A, B, **_kw):
    A = np.asarray(A, dtype=np.uint32)
    B = np.asarray(B, dtype=np.uint32)
    return ((A @ B) & 0xFF).astype(np.uint8)


def _altered(fn, how: str):
    def call(A, B, **kw):
        out = np.array(fn(A, B, **kw), dtype=np.uint8)
        if out.size and how == "flip":
            out[0, 0] ^= 0x01
        elif how == "half":
            out[:, out.shape[1] // 2:] = 0
        return out

    return call


def _stale(plan):
    def call(self, fid, old_blocks, content):
        final, chunks = plan(self, fid, old_blocks, content)
        old = {bid: data for bid, _nxt, data in old_blocks}
        for i, (bid, data) in enumerate(final):
            if old.get(bid, data) != data:
                final[i] = (bid, old[bid])
                break
        return final, chunks

    return call


@contextmanager
def planted(name: str):
    from repro.core.fragment import FragmentationModule
    from repro.core.server import StorageServer
    from repro.kernels.gf256_matmul import ops as gf_ops

    if name not in NAMES:
        raise ValueError(f"unknown fault {name!r}; expected one of {NAMES}")
    undo = []

    def patch(owner, attr, new) -> None:
        undo.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, new)

    if name == "int8":
        patch(gf_ops, "gf256_matmul", _int8)
    elif name in ("flip", "half"):
        patch(gf_ops, "gf256_matmul", _altered(gf_ops.gf256_matmul, name))
    elif name == "stale":
        patch(FragmentationModule, "_plan_blocks", _stale(FragmentationModule._plan_blocks))
    else:
        patch(StorageServer, "_h_ec_put", lambda self, sender, msg: ("ack",))
    try:
        yield
    finally:
        for owner, attr, old in reversed(undo):
            setattr(owner, attr, old)
