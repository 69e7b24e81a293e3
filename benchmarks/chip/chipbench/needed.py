"""Bytes each kernel's work needs to move through HBM, from its operands.

Both kernels are bound by bytes: neither has a published integer vector
peak, and their arithmetic per byte is small. So a kernel's least time is
its needed bytes over the HBM bandwidth, whatever implements it:

* CDC (``boundary_bitmap``): the stream read once, and one boundary bit
  written per input byte. The hash stream the kernel also writes, and the
  padding to a power-of-two length, are not needed.
* GF(256) (``gf256_matmul(A, B)``): the k input rows and the rows out, at
  the unpadded width. A block-diagonal fused decode of G survivor sets
  needs, per set, k rows in and k rows out at that set's own width; its
  zero blocks and zero-padded columns are not needed.
"""
from __future__ import annotations

import numpy as np


def cdc_bytes(length: int) -> int:
    return length + -(-length // 8)


def _diagonal_groups(A: np.ndarray, k: int) -> int:
    """G when A is block-diagonal with G > 1 blocks of k x k, else 1."""
    rows, cols = A.shape
    if k <= 0 or rows != cols or rows <= k or rows % k:
        return 1
    G = rows // k
    mask = np.kron(np.eye(G, dtype=bool), np.ones((k, k), dtype=bool))
    return G if not A[~mask].any() else 1


def gf256_bytes(A: np.ndarray, B: np.ndarray, code_k: int) -> int:
    A = np.asarray(A)
    rows_out, rows_in = A.shape
    width = B.shape[1]
    G = _diagonal_groups(A, code_k)
    if G == 1:
        return (rows_in + rows_out) * width
    total = 0
    for g in range(G):
        block = np.asarray(B[g * code_k:(g + 1) * code_k])
        used = np.nonzero(block.any(axis=0))[0]
        total += 2 * code_k * (int(used[-1]) + 1 if used.size else 0)
    return total
