"""Plain reference of what the store keeps for a write, in numpy alone.

It imports nothing of the program. The semantics are the store's stated
ones, written out in the simplest form:

* GF(2^8) over the polynomial 0x11D, by log/antilog tables;
* the systematic Cauchy RS code: parity row i, column j is
  1 / (i ^ (m + j)); a value is zero-padded to k rows of ceil(len / k)
  bytes, fragment r < k is data row r, fragment k + i is parity row i;
* the gear hash h_i = sum_{j<32} gear(x_{i-j}) << j (mod 2^32), with
  bytes before the stream read as zero, and a boundary candidate where
  ``h & mask == 0``, mask = 2^floor(log2(avg)) - 1;
* the chunker: from each chunk start, the first candidate at least
  ``min`` and under ``max`` bytes in (and not the last byte) ends the
  chunk inclusively, else the chunk is ``max`` bytes or the rest;
* a block value is a 2-byte big-endian pointer length (0 for the indexed
  layout) followed by the chunk; a file's genesis value is the same header
  followed by a pickled list of its block ids.
"""
from __future__ import annotations

import pickle

import numpy as np

POLY = 0x11D
WINDOW = 32
ID_SEP = "\x01"


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


EXP, LOG = _tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(256)")
    return int(EXP[255 - LOG[a]])


# MUL[a] is the 256-entry table of a * x
MUL = np.array([[gf_mul(a, b) for b in range(256)] for a in range(256)], dtype=np.uint8)


def cauchy_parity(n: int, k: int) -> np.ndarray:
    m = n - k
    return np.array([[gf_inv(i ^ (m + j)) for j in range(k)] for i in range(m)],
                    dtype=np.uint8)


def gf_matmul(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """C[i] = XOR_c A[i, c] * B[c] over GF(256), by table lookups."""
    out = np.zeros((A.shape[0], B.shape[1]), dtype=np.uint8)
    for i in range(A.shape[0]):
        for c in range(A.shape[1]):
            if A[i, c]:
                out[i] ^= MUL[A[i, c]][B[c]]
    return out


def value_rows(value: bytes, k: int) -> np.ndarray:
    L = -(-len(value) // k) if value else 1
    buf = np.zeros(k * L, dtype=np.uint8)
    buf[: len(value)] = np.frombuffer(value, dtype=np.uint8)
    return buf.reshape(k, L)


def fragments(value: bytes, n: int, k: int, which=None) -> dict[int, bytes]:
    """The coded fragments of ``value`` (all n, or the indices in ``which``)."""
    rows = value_rows(value, k)
    which = range(n) if which is None else which
    parity_rows = [i - k for i in which if i >= k]
    parity = gf_matmul(cauchy_parity(n, k)[parity_rows], rows) if parity_rows else None
    out = {}
    for i in which:
        out[i] = (rows[i] if i < k else parity[parity_rows.index(i - k)]).tobytes()
    return out


def gear(x: np.ndarray) -> np.ndarray:
    v = x.astype(np.uint32)
    v = (v + np.uint32(0x9E3779B9)) * np.uint32(0x85EBCA6B)
    v ^= v >> np.uint32(15)
    v *= np.uint32(0xC2B2AE35)
    v ^= v >> np.uint32(13)
    return v


def gear_hash(data: bytes) -> np.ndarray:
    """The 32-bit gear hash at every position, by doubling the window:
    H_2w(i) = H_w(i) + H_w(i - w) << w."""
    x = np.concatenate([np.zeros(WINDOW - 1, np.uint8), np.frombuffer(data, np.uint8)])
    h = gear(x)
    w = 1
    while w < WINDOW:
        nxt = h.copy()
        nxt[w:] += h[:-w] << np.uint32(w)
        h = nxt
        w *= 2
    return h[WINDOW - 1:]


def boundary_mask(avg_block: int) -> int:
    return (1 << max(1, int(np.log2(max(2, avg_block))))) - 1


def chunk_lengths(data: bytes, min_block: int, avg_block: int, max_block: int) -> list[int]:
    L = len(data)
    if L == 0:
        return []
    cand = np.nonzero((gear_hash(data) & np.uint32(boundary_mask(avg_block))) == 0)[0]
    out, start, ci = [], 0, 0
    while start < L:
        lo, hi = start + min_block, start + max_block
        while ci < len(cand) and cand[ci] < lo:
            ci += 1
        if ci < len(cand) and cand[ci] < hi and cand[ci] + 1 < L:
            end = int(cand[ci]) + 1
            ci += 1
        else:
            end = min(hi, L)
        out.append(end - start)
        start = end
    return out


def block_values(data: bytes, min_block: int, avg_block: int, max_block: int) -> list[bytes]:
    """The value of each data block a write of ``data`` stores, in order."""
    out, off = [], 0
    for n in chunk_lengths(data, min_block, avg_block, max_block):
        out.append(b"\x00\x00" + data[off: off + n])
        off += n
    return out


def genesis_id(fid: str) -> str:
    return f"{fid}{ID_SEP}g"


def parse_genesis(value: bytes) -> list[str]:
    """Block ids listed by a genesis value."""
    plen = int.from_bytes(value[:2], "big")
    index = pickle.loads(value[2 + plen:])
    if not isinstance(index, list) or not all(isinstance(b, str) for b in index):
        raise ValueError("genesis value holds no block index")
    return index
