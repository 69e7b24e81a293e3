"""Policy shared by both kernels' host wrappers.

``kernel_is_native()`` says where the Pallas kernels compile for real
hardware; elsewhere the wrappers run their jit'd pure-jnp references
(interpret-mode Pallas is a correctness harness, never a production path).
``width_bucket(L)`` is the one padding policy: every wrapper zero-pads its
operand on the host to this width and slices the result back on the host,
so a stream of mixed sizes compiles O(log L) programs, not one per size.
Above a slice size a call launches in slices (``slices``): every slice but
the last is exactly that size, and the last is bucketed as a whole call
would be, so no program is wider than a slice. That bounds a program's
width, not a call's device memory: a GF(256) call launches every slice
before it fetches any. Callers look all of these up through this module, so a test
can patch them once.
"""
from __future__ import annotations

import jax

_LANE = 128

# Slice sizes, powers of two: a CDC stream longer than CDC_SLICE_BYTES is
# hashed CDC_SLICE_BYTES at a time (a 64 MiB stream launches whole), and a
# GF(256) operand wider than GF_SLICE_COLS columns is multiplied
# GF_SLICE_COLS columns at a time. RSCode's batched calls pack and unpack in
# windows of the same width, so no host buffer of a coding call is wider.
# 2^21 is the largest power of two that keeps every window's operand and
# product at or under 16 MiB ((5, 6) encode 12 / 10 MiB, (4, 2) 4 / 8 MiB,
# (6, 6) decode 12 / 12 MiB). glibc serves a request above its mmap
# threshold, which rises with the buffers freed but never past 32 MiB, with
# a fresh mapping that it unmaps on free, so every page of a larger buffer
# faults again on each call: on a TPU v5e host a fresh product of 32 MiB or
# more came back at under 0.9 GB/s, one of 16 MiB at 6.1 GB/s.
CDC_SLICE_BYTES = 1 << 26
GF_SLICE_COLS = 1 << 21


def kernel_is_native() -> bool:
    """True when the Pallas kernels compile for real hardware (TPU). Gates
    production dispatch of both kernels and the block-diagonal group fusion
    in RSCode."""
    return jax.default_backend() == "tpu"


def width_bucket(L: int) -> int:
    """The padded width an L-column operand or L-byte stream is computed
    at: the next power of two, at least one lane row."""
    return max(_LANE, 1 << (L - 1).bit_length())


def slices(L: int, size: int) -> list[tuple[int, int]]:
    """The ``[lo, hi)`` bounds an L-long operand is launched in: one
    ``(0, L)`` up to ``size``, else slices of ``size`` and a ragged last."""
    return [(lo, min(lo + size, L)) for lo in range(0, L, size)] if L > size else [(0, L)]
