"""Policy shared by both kernels' host wrappers.

``kernel_is_native()`` says where the Pallas kernels compile for real
hardware; elsewhere the wrappers run their jit'd pure-jnp references
(interpret-mode Pallas is a correctness harness, never a production path).
``width_bucket(L)`` is the one padding policy: every wrapper zero-pads its
operand on the host to this width and slices the result back on the host,
so a stream of mixed sizes compiles O(log L) programs, not one per size.
Callers look both up through this module, so a test can patch them once.
"""
from __future__ import annotations

import jax

_LANE = 128


def kernel_is_native() -> bool:
    """True when the Pallas kernels compile for real hardware (TPU). Gates
    production dispatch of both kernels and the block-diagonal group fusion
    in RSCode."""
    return jax.default_backend() == "tpu"


def width_bucket(L: int) -> int:
    """The padded width an L-column operand or L-byte stream is computed
    at: the next power of two, at least one lane row."""
    return max(_LANE, 1 << (L - 1).bit_length())
