"""Pallas TPU kernel: GF(256) matmul as a bitsliced GF(2) MXU matmul.

Problem. RS encode/decode is ``C[m, L] = A[m, k] (x) B[k, L]`` over GF(256)
(XOR-accumulate of LUT products). Per-byte LUTs are hostile to the TPU vector
unit (no fast gather); instead we exploit that GF(256) is an 8-dim GF(2)
vector space: multiplication by each constant ``A[r, c]`` is an 8x8 bit
matrix, so

    bits(C)[8m, L] = ( Abits[8m, 8k] @ bits(B)[8k, L] ) mod 2,

an ordinary 0/1 f32 matmul (exact: row sums <= 8k << 2^24) followed by a
parity extraction — which the MXU eats at full rate.

Layout / tiling.
 * ``Abits`` is tiny (8m x 8k, m,k <= 32) and precomputed host-side
   (``erasure.gf.gf_matrix_to_bitmatrix``); it is padded up to the sublane
   tile (8,128 for f32) and kept whole in VMEM for every grid step.
 * ``B`` crosses between host and device as a flat ``(k*L,)`` uint8
   buffer, and the product as a flat ``(m*L,)`` one: a (rows, L) uint8
   array with a few rows sits in a sparse or byte-packed tile and crosses
   1.3-2.5 times slower than the same bytes flat (TPU v5e, 2-6 rows of
   1-16 Mi). The kernel reads and writes the flat buffers'
   lane-dense ``(rows, L/128, 128)`` views, which are bitcasts of them:
   no relayout runs outside the kernel. (An XLA reshape to ``(k, L)``
   would cost a device copy each way and a second or two of compile per
   shape.)
 * The views are blocked along L only: block (k, BL/128, 128), the same
   BL positions of each operand row. In-kernel the block is reshaped to
   (k, BL) and its bits unpacked (shift+mask, 8x expansion along the tiny
   k axis — never along L), so HBM traffic is 1 byte per input byte, not 8.
 * Output block (m, BL/128, 128) uint8 is packed in-kernel.

Grid: (L // BL,). VMEM per step ~= BL*(k + 8k*4 + 8m*4 + m) bytes; with
BL=2048, k=n-k=16: ~1.3 MB — comfortably inside the ~16 MB v5e VMEM budget,
leaving room for double buffering. BL/128 = 16 rows is under the uint8
(32, 128) tile; Mosaic accepts it for these blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _gf2_matmul_kernel(abits_ref, b_ref, out_ref, *, m: int, k: int, kpad: int):
    """One (k, BR, 128) -> (m, BR, 128) block of the bitsliced product: BL =
    BR * 128 positions of each operand row, lane-dense."""
    br = b_ref.shape[1]
    bl = br * LANES
    b = b_ref[...].astype(jnp.int32).reshape(k, bl)  # (k, BL) bytes as int32
    # Unpack bits little-endian along a new axis folded into the k axis:
    # Dbits[8r + j, :] = (B[r, :] >> j) & 1   -> (8k, BL)
    shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
    dbits = ((b[:, None, :] >> shifts) & 1).reshape(8 * k, bl).astype(jnp.float32)
    if kpad > 8 * k:
        dbits = jnp.pad(dbits, ((0, kpad - 8 * k), (0, 0)))
    # MXU matmul; f32 accumulation is exact for 0/1 operands at these depths.
    acc = jax.lax.dot_general(
        abits_ref[...],
        dbits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (8m_pad, BL)
    # mod-2 parity of the integer-valued accumulator.
    par = acc.astype(jnp.int32) & 1  # (8m_pad, BL)
    par = par[: 8 * m]
    # Pack bits back to bytes: C[r, :] = sum_j par[8r + j, :] << j
    weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32)).reshape(1, 8, 1)
    packed = (par.reshape(m, 8, bl) * weights).sum(axis=1)
    out_ref[...] = packed.reshape(m, br, LANES).astype(jnp.uint8)


def _round_up(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


@functools.partial(jax.jit, static_argnames=("m", "k", "block_l", "interpret"))
def gf2_bitsliced_matmul(
    abits_padded: jax.Array,
    b: jax.Array,
    *,
    m: int,
    k: int,
    block_l: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """C = A (x) B over GF(256), with A given as its padded GF(2) bit matrix.

    abits_padded: (8m_pad, 8k_pad) f32 0/1 (pad rows/cols zero).
    b:            (k*L,) uint8, the row-major (k, L) operand flat;
                  L % block_l == 0 (caller pads), block_l % 128 == 0.
    returns:      (m*L,) uint8, the row-major (m, L) product flat.
    """
    if b.ndim != 1 or b.shape[0] % k:
        raise ValueError(f"operand must be a flat buffer of k={k} rows, got shape {b.shape}")
    L = b.shape[0] // k
    if L % block_l or block_l % LANES:
        raise ValueError(f"operand width {L} is not a multiple of block_l={block_l}, "
                         f"or block_l is not a multiple of {LANES}")
    mpad8, kpad8 = abits_padded.shape
    rows, block_rows = L // LANES, block_l // LANES
    out = pl.pallas_call(
        functools.partial(_gf2_matmul_kernel, m=m, k=k, kpad=kpad8),
        grid=(rows // block_rows,),
        in_specs=[
            # A-bits: whole matrix every step (tiny, stays resident in VMEM).
            pl.BlockSpec((mpad8, kpad8), lambda i: (0, 0)),
            # B: the same BL positions of each of the k rows per step.
            pl.BlockSpec((k, block_rows, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((m, block_rows, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, rows, LANES), jnp.uint8),
        interpret=interpret,
        name="gf256_matmul",
    )(abits_padded, b.reshape(k, rows, LANES))
    return out.reshape(m * L)
