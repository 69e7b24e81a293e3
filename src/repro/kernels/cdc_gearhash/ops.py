"""Public wrappers: gear-hash stream, boundary bitmap, and chunk splitting.

``split_chunks`` is what the Fragmentation Module calls: kernel-computed
boundary candidates + a cheap host pass enforcing min/avg/max chunk sizes
(the paper's rabin-fingerprint parameters).

Streams are zero-padded on the host to a power-of-two length before they
reach the device and sliced back on the host: the hash at i depends only on
bytes i-31..i, so trailing padding leaves positions < L bit-identical, and a
stream of mixed object sizes compiles O(log L) programs instead of one per
length."""
from __future__ import annotations

import functools

import jax
import numpy as np

from repro.kernels import dispatch
from repro.kernels.cdc_gearhash.kernel import gearhash_pallas


def _mask_for_avg(avg_size: int) -> int:
    """Boundary mask with P(boundary) = 1/avg -> expected chunk ~= avg."""
    bits = max(1, int(np.log2(max(2, avg_size))))
    return (1 << bits) - 1


@functools.partial(jax.jit, static_argnames=("mask",))
def _ref_jit(data, *, mask):
    from repro.kernels.cdc_gearhash.ref import gearhash_ref

    return gearhash_ref(data, mask=mask)


def _gearhash_device(
    data: np.ndarray | bytes, mask: int, block_l: int, interpret: bool | None
) -> tuple[jax.Array, jax.Array, int]:
    """Hash and bitmap of ``data`` padded to its power-of-two bucket, still
    on the device, and the unpadded length. ``interpret=None`` runs the
    native kernel on TPU and the jit'd ref elsewhere (interpret-mode Pallas
    is for validation only); ``interpret=True`` forces the interpreter."""
    buf = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) \
        else np.asarray(data, dtype=np.uint8)
    L = buf.shape[0]
    Lp = dispatch.width_bucket(L)
    padded = np.zeros(Lp, dtype=np.uint8)
    padded[:L] = buf
    if interpret is None and not dispatch.kernel_is_native():
        h, b = _ref_jit(padded, mask=mask)
    else:
        h, b = gearhash_pallas(padded, block_l=block_l, mask=mask,
                               interpret=bool(interpret))
    return h, b, L


def gearhash(
    data: np.ndarray | bytes, *, mask: int = 0xFFFF, block_l: int = 1 << 17,
    interpret: bool | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Rolling gear hash + boundary bitmap for a byte stream (host arrays)."""
    h, b, L = _gearhash_device(data, mask, block_l, interpret)
    return np.asarray(h)[:L], np.asarray(b)[:L]


def boundary_bitmap(
    data: np.ndarray | bytes, avg_size: int, *, block_l: int = 1 << 17,
    interpret: bool | None = None,
) -> np.ndarray:
    """Boundary candidates only: the hash stream never leaves the device."""
    _h, b, L = _gearhash_device(data, _mask_for_avg(avg_size), block_l, interpret)
    return np.asarray(b)[:L]


def split_chunks(
    data: bytes,
    *,
    min_size: int,
    avg_size: int,
    max_size: int,
    interpret: bool | None = None,
) -> list[bytes]:
    """Content-defined chunking with min/avg/max enforcement.

    Kernel emits boundary candidates in parallel; the host pass walks only
    the candidate positions (|candidates| ~= L/avg) applying min/max rules —
    O(L) on device, O(L/avg) on host.
    """
    if not data:
        return [b""]
    bitmap = boundary_bitmap(data, avg_size, interpret=interpret)
    cand = np.nonzero(bitmap)[0]
    chunks: list[bytes] = []
    start = 0
    L = len(data)
    ci = 0
    while start < L:
        lo = start + min_size
        hi = start + max_size
        # first candidate >= lo (strictly inside the chunk) and < hi
        while ci < len(cand) and cand[ci] < lo:
            ci += 1
        if ci < len(cand) and cand[ci] < hi and cand[ci] + 1 < L:
            end = int(cand[ci]) + 1  # boundary position is *inclusive* end
            ci += 1
        else:
            end = min(hi, L)
        chunks.append(data[start:end])
        start = end
    return chunks
