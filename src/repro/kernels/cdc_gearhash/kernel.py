"""Pallas TPU kernel: content-defined-chunking gear hash + boundary bitmap.

The paper's Fragmentation Module splits files with Rabin fingerprints — a
rolling hash that looks sequential. We use the gear/FastCDC form

    h_i = sum_{j=0..W-1} gear(x_{i-j}) << j      (mod 2^32, W = 32)

where left-shifted-out bits vanish, so h_i depends on a *fixed 32-byte
window*: a windowed weighted sum, data-parallel over every position i.
``gear()`` is an arithmetic byte mixer (no LUT — TPU-friendly).

Layout. The stream is viewed as ``(R, width)`` rows, ``width`` a multiple
of the 128-lane vreg width, and the grid walks blocks of ``block_rows``
rows (a multiple of the 32-row uint8 sublane tile, or all rows). Position
``c`` of a row needs bytes ``c-31..c``; for ``c < 31`` some of them sit at
the end of the PREVIOUS row. Blocks cannot overlap, so the wrapper passes
each row's predecessor tail — the previous row's last 128 bytes; for row 0
the 128 bytes before the stream (``head``, when the stream is one slice of
a longer one), else zeros — as a second ``(R, 128)`` operand. In-kernel,
``pltpu.roll`` along the lanes shifts the gear stream by j: correct
everywhere except the first 31 lanes, which the first lane-tile recomputes
with the tail spliced in (``lane < j`` selects the rolled tail). Every block is tile-aligned, no
slice is unaligned, and the output is the uint32 hash stream and a uint8
boundary bitmap (h & mask == 0).

The W shifted adds are vector ALU work: ~W ops/byte with zero HBM
re-reads — memory-bound at 1 byte/position in (+128/width for the tails),
5 bytes/position out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WINDOW = 32
LANES = 128
ROW_WIDTH = 1024   # bytes per row of the 2-D view (lane multiple)
ROW_TILE = 32      # uint8 sublane tile: block rows must be a multiple


def gear_mix(x: jnp.ndarray) -> jnp.ndarray:
    """Deterministic byte -> uint32 mixer (splitmix-ish; no table lookup)."""
    v = x.astype(jnp.uint32)
    v = (v + jnp.uint32(0x9E3779B9)) * jnp.uint32(0x85EBCA6B)
    v = v ^ (v >> 15)
    v = v * jnp.uint32(0xC2B2AE35)
    v = v ^ (v >> 13)
    return v


def _gearhash_kernel(tail_ref, cur_ref, h_ref, b_ref, *, mask: int):
    g = gear_mix(cur_ref[...].astype(jnp.int32))    # (BR, width) uint32
    gt = gear_mix(tail_ref[...].astype(jnp.int32))  # (BR, 128): prev row's tail
    g0 = g[:, :LANES]
    lane = jax.lax.broadcasted_iota(jnp.int32, g0.shape, 1)
    h, h0 = g, g0
    # h[c] = sum_j g[c - j] << j; roll(x, j)[c] = x[c - j] (wrapping), and
    # the wrapped lanes c < j of the first tile take the previous row's tail.
    for j in range(1, WINDOW):
        sh = jnp.uint32(j)
        h = h + (pltpu.roll(g, j, 1) << sh)
        h0 = h0 + (jnp.where(lane < j, pltpu.roll(gt, j, 1), pltpu.roll(g0, j, 1)) << sh)
    m = jnp.uint32(mask)
    h_ref[:, :LANES] = h0
    b_ref[:, :LANES] = ((h0 & m) == 0).astype(jnp.int32).astype(jnp.uint8)
    if g.shape[1] > LANES:
        hr = h[:, LANES:]
        h_ref[:, LANES:] = hr
        b_ref[:, LANES:] = ((hr & m) == 0).astype(jnp.int32).astype(jnp.uint8)


def layout(L: int, block_l: int) -> tuple[int, int]:
    """``(width, block_rows)`` of the 2-D view for an L-byte stream with
    ~``block_l`` positions per grid step; raises ``ValueError`` when L has
    no tile-aligned view (callers pad L to a power of two >= 128)."""
    width = min(ROW_WIDTH, L)
    if L <= 0 or L % LANES or L % width:
        raise ValueError(
            f"stream length {L} must be a positive multiple of {LANES} "
            f"(and of {ROW_WIDTH} above it)"
        )
    rows = L // width
    block_rows = min(rows, max(ROW_TILE, block_l // width))
    if rows % block_rows or (block_rows % ROW_TILE and block_rows != rows):
        raise ValueError(
            f"{rows} rows of {width} bytes do not tile into blocks of "
            f"{block_rows} rows (need a multiple of {ROW_TILE})"
        )
    return width, block_rows


@functools.partial(jax.jit, static_argnames=("block_l", "mask", "interpret"))
def gearhash_pallas(
    data: jax.Array, head: jax.Array | None = None, *, block_l: int = 1 << 17,
    mask: int = 0xFFFF, interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """data: (L,) uint8 with a tile-aligned view (see ``layout``). Returns
    (hash (L,) uint32, boundary bitmap (L,) uint8). ``head``: the (128,)
    bytes just before ``data`` in a longer stream, row 0's tail, so the
    result equals that stream's own at these positions; without it,
    positions < W-1 hash a window zero-padded in byte space."""
    L = data.shape[0]
    width, block_rows = layout(L, block_l)
    rows = L // width
    d2 = data.reshape(rows, width)
    first = (jnp.zeros((1, LANES), jnp.uint8) if head is None
             else head.astype(jnp.uint8).reshape(1, LANES))
    tails = jnp.concatenate([first, d2[:-1, width - LANES:]], axis=0)
    h, b = pl.pallas_call(
        functools.partial(_gearhash_kernel, mask=mask),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, width), jnp.uint32),
            jax.ShapeDtypeStruct((rows, width), jnp.uint8),
        ],
        interpret=interpret,
        name="cdc_gearhash",
    )(tails, d2)
    return h.reshape(L), b.reshape(L)
