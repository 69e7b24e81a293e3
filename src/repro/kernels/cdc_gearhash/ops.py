"""Public wrappers: gear-hash stream, boundary bitmap, and chunk splitting.

``split_chunks`` is what the Fragmentation Module calls: kernel-computed
boundary candidates + a cheap host pass enforcing min/avg/max chunk sizes
(the paper's rabin-fingerprint parameters).

Streams are zero-padded on the host to a power-of-two length before they
reach the device and sliced back on the host: the hash at i depends only on
bytes i-31..i, so trailing padding leaves positions < L bit-identical, and a
stream of mixed object sizes compiles O(log L) programs instead of one per
length. ``boundary_bitmap`` hashes a stream longer than
``dispatch.CDC_SLICE_BYTES`` in slices of that size (the last bucketed),
each with the 128 bytes before it as its first row's tail, so the bitmap is
bit-identical to the whole stream's; slice j + 1 is launched before slice
j's bitmap is fetched, so the fetch overlaps the next slice's work.

The host steps of each launch are ``repro.tracing`` spans:
``cdc_kernel.pad``, ``.put`` (the stream to the device), ``.run`` (launch to
ready) and ``.get`` (a result back). Only while the tracer is on is the
stream put on the device before the launch, and do ``put`` and ``run`` end
by waiting for it; off, the launch moves the stream as it always has.
Launches that are one slice of a longer stream are counted
(``cdc_kernel.slices``) with their unpadded bytes
(``cdc_kernel.bytes_in_sliced``). ``split_chunks`` is the ``cdc`` span,
whose self time is the min/max walk and the chunk slicing."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.kernels import dispatch
from repro.kernels.cdc_gearhash.kernel import LANES, gearhash_pallas


def _mask_for_avg(avg_size: int) -> int:
    """Boundary mask with P(boundary) = 1/avg -> expected chunk ~= avg."""
    bits = max(1, int(np.log2(max(2, avg_size))))
    return (1 << bits) - 1


@functools.partial(jax.jit, static_argnames=("mask",))
def _ref_jit(data, head, *, mask):
    from repro.kernels.cdc_gearhash.ref import gearhash_ref

    if head is None:
        return gearhash_ref(data, mask=mask)
    h, b = gearhash_ref(jnp.concatenate([head, data]), mask=mask)
    return h[head.shape[0]:], b[head.shape[0]:]


def _as_array(data: np.ndarray | bytes) -> np.ndarray:
    return (np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray))
            else np.asarray(data, dtype=np.uint8))


def _gearhash_device(
    buf: np.ndarray, mask: int, block_l: int, interpret: bool | None,
    head: np.ndarray | None = None,
) -> tuple[jax.Array, jax.Array, int]:
    """One launch: hash and bitmap of ``buf`` padded to its power-of-two
    bucket, still on the device, and the unpadded length. ``head`` is the
    ``LANES`` bytes before ``buf`` where it is a slice of a longer stream.
    ``interpret=None`` runs the native kernel on TPU and the jit'd ref
    elsewhere (interpret-mode Pallas is for validation only);
    ``interpret=True`` forces the interpreter."""
    L = buf.shape[0]
    Lp = dispatch.width_bucket(L)
    traced = tracing.enabled()
    with tracing.span("cdc_kernel.pad", nbytes=Lp):
        padded = np.zeros(Lp, dtype=np.uint8)
        padded[:L] = buf
    if traced:
        with tracing.span("cdc_kernel.put", nbytes=Lp):
            padded = jax.device_put(padded).block_until_ready()
    with tracing.span("cdc_kernel.run"):
        if interpret is None and not dispatch.kernel_is_native():
            h, b = _ref_jit(padded, head, mask=mask)
        else:
            h, b = gearhash_pallas(padded, head, block_l=block_l, mask=mask,
                                   interpret=bool(interpret))
        if traced:
            b.block_until_ready()
    if traced:
        tracing.count("cdc_kernel.launch")
        tracing.count("cdc_kernel.bytes_in", L)
        tracing.count("cdc_kernel.bytes_in_padded", Lp)
    return h, b, L


def _fetch(x: jax.Array, L: int, out: np.ndarray | None = None) -> np.ndarray:
    """A device result back on the host (the ``cdc_kernel.get`` span), cut
    to the unpadded length; with ``out``, written into it."""
    with tracing.span("cdc_kernel.get", nbytes=x.nbytes):
        host = np.asarray(x)[:L]
        if out is not None:
            out[:] = host
            host = out
    tracing.count("cdc_kernel.bytes_out", x.nbytes)
    return host


def gearhash(
    data: np.ndarray | bytes, *, mask: int = 0xFFFF, block_l: int = 1 << 17,
    interpret: bool | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Rolling gear hash + boundary bitmap for a byte stream (host arrays),
    in one launch."""
    h, b, L = _gearhash_device(_as_array(data), mask, block_l, interpret)
    return _fetch(h, L), _fetch(b, L)


def boundary_bitmap(
    data: np.ndarray | bytes, avg_size: int, *, block_l: int = 1 << 17,
    interpret: bool | None = None,
) -> np.ndarray:
    """Boundary candidates only: the hash stream never leaves the device.
    A stream longer than ``dispatch.CDC_SLICE_BYTES`` is hashed in slices."""
    buf = _as_array(data)
    mask = _mask_for_avg(avg_size)
    parts = dispatch.slices(buf.shape[0], dispatch.CDC_SLICE_BYTES)
    if len(parts) == 1:
        _h, b, L = _gearhash_device(buf, mask, block_l, interpret)
        return _fetch(b, L)
    out = np.empty(buf.shape[0], dtype=np.uint8)
    fetch = None  # the bitmap of the slice launched last, still on the device
    for lo, hi in parts:
        head = buf[lo - LANES:lo] if lo else None
        _h, b, L = _gearhash_device(buf[lo:hi], mask, block_l, interpret, head)
        tracing.count("cdc_kernel.slices")
        tracing.count("cdc_kernel.bytes_in_sliced", L)
        if fetch is not None:
            fetch()
        fetch = functools.partial(_fetch, b, L, out[lo:hi])
    fetch()
    return out


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
    with tracing.span("cdc", nbytes=len(data)):
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
