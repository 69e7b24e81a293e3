"""Public host wrappers for the bitsliced GF(256) matmul kernel.

``gf256_matmul(A, B)`` — the GF(256) matrix product the storage data path
dispatches to (RSCode backend "kernel"/"auto", see ``repro.erasure.rs``):
host-side prep (bit-matrix expansion of the tiny A, width padding) + the
Pallas kernel where it compiles natively (TPU), the jit'd XLA LUT
formulation elsewhere. ``interpret=True`` runs the kernel in the Pallas
interpreter, a correctness harness orders of magnitude slower than either
and never a production path.
``rs_encode_parity(parity_matrix, data)`` — the RS encode hot path.

All paths are bit-identical to ``ref.gf256_matmul_ref`` (and to the numpy
LUT reference ``erasure.gf.gf_matmul_np``).
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from repro import tracing
from repro.erasure.gf import gf_matrix_to_bitmatrix
from repro.kernels import dispatch
from repro.kernels.gf256_matmul.kernel import _round_up, gf2_bitsliced_matmul

# f32 VMEM tile is (8, 128); pad the bit-matrix to it.
_SUBLANE, _LANE = 8, 128


def _validate_shapes(A: np.ndarray, B) -> None:
    # ValueError, not assert: shape bugs must not vanish under ``python -O``
    # and surface later as wrong-shaped kernel output.
    if A.ndim != 2:
        raise ValueError(f"A must be a 2-D (m, k) matrix, got shape {A.shape}")
    if getattr(B, "ndim", None) != 2:
        raise ValueError(f"B must be a 2-D (k, L) matrix, got shape {getattr(B, 'shape', None)}")
    if B.shape[0] != A.shape[1]:
        raise ValueError(
            f"inner dimensions disagree: A is {A.shape}, B is {tuple(B.shape)}"
        )


@functools.lru_cache(maxsize=128)
def _abits_cached(a_bytes: bytes, m: int, k: int) -> np.ndarray:
    A = np.frombuffer(a_bytes, dtype=np.uint8).reshape(m, k)
    bits = gf_matrix_to_bitmatrix(A).astype(np.float32)  # (8m, 8k)
    mp = _round_up(8 * m, _SUBLANE)
    kp = _round_up(8 * k, _LANE)
    out = np.zeros((mp, kp), dtype=np.float32)
    out[: 8 * m, : 8 * k] = bits
    return out


@functools.lru_cache(maxsize=1)
def _jit_ref():
    from repro.kernels.gf256_matmul.ref import gf256_matmul_ref

    return jax.jit(gf256_matmul_ref)


def gf256_matmul(
    A: np.ndarray,
    B: np.ndarray | jax.Array,
    *,
    block_l: int = 2048,
    interpret: bool | None = None,
) -> np.ndarray:
    """GF(256) matrix product C = A (x) B as a host array. A: (m, k) uint8
    (small); B: (k, L) uint8 (large). Returns (m, L) uint8.

    ``interpret=None`` runs the native kernel on TPU and the jit'd ref
    elsewhere; ``interpret=True`` forces the Pallas interpreter. L is
    zero-padded on the host to ``dispatch.width_bucket(L)`` and the product
    sliced back on the host — GF matmul is column-wise, so padding columns
    is bit-identical — which bounds compiles across ragged widths to
    O(log L) per (m, k) and keeps ragged shapes off the device.

    Each host step is a ``repro.tracing`` span: ``gf256.pad`` (to the
    bucket), ``gf256.put`` (the operand to the device), ``gf256.run``
    (launch to ready), ``gf256.get`` (the product back) and ``gf256.slice``
    (back to L). Only while the tracer is on is the operand put on the
    device before the launch, and do ``put`` and ``run`` end by waiting
    for it, so each step is timed alone; the path already waited in
    ``get``, so no wait moves. Off, the launch moves the operand as it
    always has. Launches are counted by operand rows (more than the code's
    k: a block-diagonal fused decode), with the bytes sent unpadded and
    padded and the bytes fetched.
    """
    A = np.asarray(A, dtype=np.uint8)
    B = np.asarray(B, dtype=np.uint8)
    _validate_shapes(A, B)
    m, k = A.shape
    L = B.shape[1]
    if m == 0 or L == 0 or k == 0:
        # degenerate shapes the storage path can produce (m == 0 codes,
        # empty values): the product is an empty/zero matrix — don't hand
        # a zero-sized grid to Pallas.
        return np.zeros((m, L), dtype=np.uint8)
    Lp = dispatch.width_bucket(L)
    traced = tracing.enabled()
    with tracing.span("gf256.pad", nbytes=k * Lp):
        if Lp != L:
            Bp = np.zeros((k, Lp), dtype=np.uint8)
            Bp[:, :L] = B
            B = Bp
    if traced:
        with tracing.span("gf256.put", nbytes=k * Lp):
            B = jax.device_put(B).block_until_ready()
    with tracing.span("gf256.run"):
        if interpret is None and not dispatch.kernel_is_native():
            out = _jit_ref()(A, B)
        else:
            abits = _abits_cached(A.tobytes(), m, k)
            out = gf2_bitsliced_matmul(abits, B, m=m, k=k, block_l=min(block_l, Lp),
                                       interpret=bool(interpret))
        if traced:
            out.block_until_ready()
    with tracing.span("gf256.get", nbytes=m * Lp):
        host = np.asarray(out)
    with tracing.span("gf256.slice"):
        host = host[:, :L]
    if traced:
        tracing.count("gf256.launch")
        tracing.count(f"gf256.launch.rows.{k}")
        tracing.count("gf256.bytes_in", k * L)
        tracing.count("gf256.bytes_in_padded", k * Lp)
        tracing.count("gf256.bytes_out", m * Lp)
    return host


def rs_encode_parity(
    parity_matrix: np.ndarray, data: np.ndarray | jax.Array, **kw
) -> np.ndarray:
    """Parity rows for a systematic RS code: P = parity_matrix (x) data."""
    return gf256_matmul(parity_matrix, data, **kw)
