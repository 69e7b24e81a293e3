"""Systematic [n, k] Reed-Solomon (Cauchy) codes over GF(256).

``RSCode`` is the object-level API used by the EC DAPs (``repro.core.dap.ec*``),
the repair subsystem (``repro.core.repair``) and the EC checkpoint store
(``repro.train.checkpoint``):

* ``encode(data)``      — (k, L) uint8 -> (n, L) coded fragments (systematic:
                          fragments [0, k) are the data rows themselves).
* ``decode(frs, idxs)`` — any k fragments (+ their indices) -> (k, L) data.

Coding backends (ISSUE 6)
-------------------------
``backend`` selects where the GF(256) matmul runs:

* ``"numpy"``  — the byte-LUT reference (``erasure.gf.gf_matmul_np``).
* ``"kernel"`` — the hardware path (``repro.kernels.gf256_matmul.ops.
  gf256_matmul``): the Pallas bitsliced kernel where it compiles natively
  (TPU), the jit'd XLA LUT formulation on CPU.
* ``"auto"``   — size-based dispatch: operands at or above
  ``AUTO_KERNEL_MIN_BYTES`` (measured crossover on the reference container,
  see ``benchmarks/bench_kernels.py``) take the kernel path; tiny
  single-block products stay on the LUT path, whose fixed overhead is lower.

All backends are bit-identical (property-tested in
``tests/test_coding_backend.py``).

Batched byte paths
------------------
``encode_bytes_batch`` / ``decode_bytes_batch`` fuse many ragged byte values
into as few matmuls as possible: values are laid side by side column-wise
(GF(256) matmul acts per column, so no per-value padding is needed), decode
groups sharing a surviving-fragment index set share one cached inverted
generator (``_decoder_cached``), and on the native kernel multiple groups
fuse into ONE block-diagonal operand. That operand is never built whole: it
is packed straight from the value or fragment bytes, multiplied and unpacked
one column window of ``dispatch.GF_SLICE_COLS`` at a time, so no host
buffer of a call is wider than a window (``_by_window``); a call no wider
than one is one launch of its whole operand. Fragments carry an optional CRC-32
computed/verified in the same traversal that materialises the bytes
(``with_crc=True`` / a per-item crc dict), so integrity checking never costs
a second pass over the data.
"""
from __future__ import annotations

import bisect
import functools
import itertools
import zlib
from dataclasses import dataclass, field

import numpy as np

from repro import tracing
from repro.erasure.gf import gf_matmul_np
from repro.erasure.matrix import cauchy_parity_matrix, gf_invert_matrix

BACKENDS = ("numpy", "kernel", "auto")

# "auto" crossover: operand (B) bytes at which the kernel backend overtakes
# the numpy LUT path. Measured on the reference container (CPU/XLA): the
# jit'd formulation wins from ~16 KiB; 64 KiB leaves headroom for dispatch
# and shape-bucket recompiles. See benchmarks/bench_kernels.py.
AUTO_KERNEL_MIN_BYTES = 1 << 16

# Block-diagonal group fusion bound: G groups of a k-row code fuse into one
# (G*k, G*k) launch only while the expanded bit-matrix stays VMEM-friendly.
_FUSE_MAX_ROWS = 128


def element_crc_ok(elem) -> bool:
    """Integrity check for a stored/shipped coded element.

    Elements are ``(fragment_bytes, orig_len)`` or, since ISSUE 6,
    ``(fragment_bytes, orig_len, crc32)``. Returns False only when a carried
    checksum does not match the fragment bytes — legacy 2-tuples (and the
    server's ``("", 0)`` sentinel) always pass.
    """
    if not isinstance(elem, tuple) or len(elem) < 3 or elem[2] is None:
        return True
    return fragment_crc(elem[0]) == elem[2]


def fragment_crc(data: bytes) -> int:
    """CRC-32 of a fragment's bytes, as the ``crc`` span (with its bytes)."""
    with tracing.span("crc", nbytes=len(data)):
        return zlib.crc32(data)


def bytes_to_rows(data: bytes, k: int) -> tuple[np.ndarray, int]:
    """Pad ``data`` to a multiple of k and reshape to (k, L). Returns the
    original length so ``rows_to_bytes`` can strip the padding."""
    orig = len(data)
    L = (orig + k - 1) // k if orig else 1
    buf = np.zeros(k * L, dtype=np.uint8)
    buf[:orig] = np.frombuffer(data, dtype=np.uint8)
    return buf.reshape(k, L), orig


def rows_to_bytes(rows: np.ndarray, orig_len: int) -> bytes:
    return rows.reshape(-1).tobytes()[:orig_len]


class _Columns:
    """Ragged members laid side by side: member b holds the columns
    ``[offsets[b], offsets[b] + widths[b])`` of one ``width``-wide operand."""

    def __init__(self, widths: list[int]) -> None:
        self.widths = widths
        self.offsets = [0, *itertools.accumulate(widths)][:-1]
        self.width = sum(widths)

    def within(self, lo: int, hi: int):
        """``(b, j0, j1, at)`` for each member b that meets the columns
        ``[lo, hi)``: its own columns ``[j0, j1)`` sit at column ``at`` of
        that window."""
        b = bisect.bisect_right(self.offsets, lo) - 1
        while b < len(self.widths) and self.offsets[b] < hi:
            off = self.offsets[b]
            j0, j1 = max(lo - off, 0), min(hi - off, self.widths[b])
            if j1 > j0:
                yield b, j0, j1, off + j0 - lo
            b += 1


def _rows_into(dst: np.ndarray, data: np.ndarray, L: int, j0: int, j1: int) -> None:
    """Columns ``[j0, j1)`` of ``data`` as k rows of L bytes (zero past its
    end, as ``bytes_to_rows`` pads it) into the ``(k, j1 - j0)`` ``dst``."""
    full = len(data) // L
    if full:
        dst[:full] = data[: full * L].reshape(full, L)[:, j0:j1]
    if full < len(dst):
        tail = data[full * L + j0 : full * L + j1]
        dst[full, : len(tail)] = tail
        dst[full, len(tail) :] = 0
        dst[full + 1 :] = 0


def _row_bytes(data: np.ndarray, L: int, i: int) -> bytes:
    """Row i of ``data`` as k rows of L bytes, zero past its end."""
    row = data[i * L : (i + 1) * L].tobytes()
    return row + bytes(L - len(row)) if len(row) < L else row


@functools.lru_cache(maxsize=128)
def _parity_cached(n: int, k: int) -> np.ndarray:
    P = cauchy_parity_matrix(n, k)
    P.setflags(write=False)
    return P


@functools.lru_cache(maxsize=4096)
def _decoder_cached(n: int, k: int, idxs: tuple[int, ...]) -> np.ndarray:
    """Inverted generator for fragment index-set ``idxs`` of the [n, k] code.

    Cached per index-set the way ``ops._abits_cached`` caches bit-matrices:
    batched reads keep hitting the same few surviving-quorum subsets, so the
    k x k Gauss-Jordan runs once per subset, not once per decode."""
    P = _parity_cached(n, k)
    gen = np.zeros((k, k), dtype=np.uint8)
    for r, idx in enumerate(idxs):
        if idx < k:
            gen[r, idx] = 1
        else:
            gen[r] = P[idx - k]
    D = gf_invert_matrix(gen)
    D.setflags(write=False)
    return D


@dataclass
class RSCode:
    """Systematic Cauchy-RS erasure code over GF(256)."""

    n: int
    k: int
    backend: str = "numpy"  # "numpy" | "kernel" | "auto"
    # Block-diagonal fusion of multi-group decode_bytes_batch calls into one
    # kernel launch: None = only where the Pallas kernel is native (the MXU
    # eats the zero blocks at full rate; the CPU LUT path would pay G x the
    # dense work). Tests force True/False.
    fuse_groups: bool | None = None
    _parity: np.ndarray = field(init=False, repr=False)

    def __post_init__(self) -> None:
        if not (0 < self.k <= self.n <= 256):
            raise ValueError(f"need 0 < k <= n <= 256, got n={self.n} k={self.k}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown coding backend {self.backend!r}; expected one of {BACKENDS}"
            )
        self._parity = _parity_cached(self.n, self.k)

    # -- properties ---------------------------------------------------------
    @property
    def m(self) -> int:
        return self.n - self.k

    @property
    def parity_matrix(self) -> np.ndarray:
        return self._parity

    def generator_row(self, idx: int) -> np.ndarray:
        """Row of the full systematic generator [I; P] for fragment ``idx``."""
        if idx < self.k:
            row = np.zeros(self.k, dtype=np.uint8)
            row[idx] = 1
            return row
        return self._parity[idx - self.k].copy()

    # -- core ops ------------------------------------------------------------
    def _use_kernel(self, A: np.ndarray, rows: int, cols: int) -> bool:
        """Whether ``A (x) B`` for a ``(rows, cols)`` operand B takes the
        kernel backend."""
        if self.backend == "numpy" or A.size == 0 or rows * cols == 0:
            return False
        if self.backend == "kernel":
            return cols >= 8
        return rows * cols >= AUTO_KERNEL_MIN_BYTES  # "auto"

    def _matmul(self, A: np.ndarray, B: np.ndarray) -> np.ndarray:
        if self._use_kernel(np.asarray(A), *np.shape(B)):
            from repro.kernels.gf256_matmul import ops as gf_ops

            return gf_ops.gf256_matmul(A, B)
        return gf_matmul_np(A, B)

    @staticmethod
    def _systematic_rows(indices, nrows: int, k: int) -> list[int] | None:
        """Row positions holding fragments 0..k-1 (in that order), or None
        when the supplied indices don't cover the full systematic set."""
        pos: dict[int, int] = {}
        for p, idx in enumerate(list(indices)[:nrows]):
            pos.setdefault(int(idx), p)
        if all(i in pos for i in range(k)):
            return [pos[i] for i in range(k)]
        return None

    def encode(self, data: np.ndarray) -> np.ndarray:
        """(k, L) uint8 -> (n, L) uint8 coded fragments (systematic)."""
        data = np.asarray(data, dtype=np.uint8)
        if data.shape[0] != self.k:
            raise ValueError(f"expected {self.k} data rows, got {data.shape}")
        if self.m == 0:
            return data.copy()
        parity = self._matmul(self._parity, data)
        return np.concatenate([data, parity], axis=0)

    def decode(self, fragments: np.ndarray, indices: list[int]) -> np.ndarray:
        """Reconstruct (k, L) data from any k fragments.

        ``fragments``: (k, L) uint8 rows; ``indices``: their fragment ids in
        [0, n). Raises if fewer than k distinct fragments are supplied. When
        the supplied rows cover all k systematic fragments — in any order,
        at any position — they are returned directly (no inversion, no
        matmul); otherwise the first k rows decode through the cached
        inverted generator.
        """
        fragments = np.asarray(fragments, dtype=np.uint8)
        if len(indices) != len(set(indices)):
            raise ValueError("duplicate fragment indices")
        if fragments.shape[0] < self.k or len(indices) < self.k:
            raise ValueError(
                f"need {self.k} fragments to decode, got {fragments.shape[0]}"
            )
        rows = self._systematic_rows(indices, fragments.shape[0], self.k)
        if rows is not None:
            return np.ascontiguousarray(fragments[rows])
        idxs = [int(i) for i in list(indices)[: self.k]]
        frs = fragments[: self.k]
        dec = _decoder_cached(self.n, self.k, tuple(idxs))
        return np.asarray(self._matmul(dec, frs))

    def reconstruct_fragment(
        self, target_idx: int, fragments: np.ndarray, indices: list[int]
    ) -> np.ndarray:
        """Rebuild a single lost fragment (server repair path)."""
        data = self.decode(fragments, indices)
        if target_idx < self.k:
            return data[target_idx]
        return self._matmul(self._parity[target_idx - self.k : target_idx - self.k + 1], data)[0]

    def reconstruct_fragments(
        self, target_idxs: list[int], fragments: np.ndarray, indices: list[int]
    ) -> np.ndarray:
        """Rebuild several lost fragments with one decode + one fused matmul.

        Returns (len(target_idxs), L) rows in target order. Used by the
        repair controller, which typically replaces every fragment a set of
        recovered servers lost at once."""
        with tracing.span("rs.decode"):
            data = self.decode(fragments, indices)
            if not target_idxs:
                return np.zeros((0, data.shape[1]), dtype=np.uint8)
            gen = np.stack([self.generator_row(i) for i in target_idxs], axis=0)
            return np.asarray(self._matmul(gen, data))

    # -- batched coding (single fused GF(256) matmul over many blocks) -------
    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        """(B, k, L) uint8 -> (B, n, L) coded blocks via ONE matmul.

        GF(256) matmul acts column-wise, so the B blocks are laid side by
        side as one (k, B*L) operand; the product splits back into per-block
        parity bit-identically to B separate ``encode`` calls. On the kernel
        backend this is one launch instead of B."""
        data = np.asarray(data, dtype=np.uint8)
        if data.ndim != 3 or data.shape[1] != self.k:
            raise ValueError(f"expected (B, {self.k}, L) blocks, got {data.shape}")
        B, _, L = data.shape
        if B == 0:
            return np.zeros((0, self.n, L), dtype=np.uint8)
        if self.m == 0:
            return data.copy()
        flat = np.ascontiguousarray(data.transpose(1, 0, 2)).reshape(self.k, B * L)
        parity = np.asarray(self._matmul(self._parity, flat))
        parity = parity.reshape(self.m, B, L).transpose(1, 0, 2)
        return np.concatenate([data, parity], axis=1)

    def decode_batch(self, fragments: np.ndarray, indices: list[int]) -> np.ndarray:
        """(B, k, L) fragment blocks sharing ONE index set -> (B, k, L) data.

        The common case for batched reads: every block lost the same servers,
        so one inverted generator serves the whole batch in a single matmul."""
        fragments = np.asarray(fragments, dtype=np.uint8)
        if fragments.ndim != 3:
            raise ValueError(f"expected (B, k, L) fragment blocks, got {fragments.shape}")
        if len(indices) != len(set(indices)):
            raise ValueError("duplicate fragment indices")
        if fragments.shape[1] < self.k or len(indices) < self.k:
            raise ValueError(
                f"need {self.k} fragments per block to decode, got {fragments.shape[1]}"
            )
        B, R, L = fragments.shape
        if B == 0:
            return fragments[:, : self.k, :].copy()
        rows = self._systematic_rows(indices, R, self.k)
        if rows is not None:
            return np.ascontiguousarray(fragments[:, rows, :])
        idxs = [int(i) for i in list(indices)[: self.k]]
        frs = fragments[:, : self.k, :]
        dec = _decoder_cached(self.n, self.k, tuple(idxs))
        flat = np.ascontiguousarray(frs.transpose(1, 0, 2)).reshape(self.k, B * L)
        out = np.asarray(self._matmul(dec, flat))
        return np.ascontiguousarray(out.reshape(self.k, B, L).transpose(1, 0, 2))

    # -- bytes-level convenience (object values in the DAPs) -----------------
    def encode_bytes(self, value: bytes, *, with_crc: bool = False):
        """``([fragment bytes] * n, orig_len)``; with ``with_crc`` also a
        parallel list of per-fragment CRC-32s (one-element batch)."""
        return self.encode_bytes_batch([value], with_crc=with_crc)[0]

    def encode_bytes_batch(self, values: list[bytes], *, with_crc: bool = False):
        """Batch ``encode_bytes`` over many byte strings with fused matmuls.

        The values' (k, L_b) row blocks are laid side by side column-wise —
        the GF matmul acts per column, so ragged lengths fuse with NO
        padding and the result is bit-identical to per-value encoding. The
        operand is never built whole: each column window is packed straight
        from the value bytes and multiplied on its own (``_by_window``), and
        each value's parity is cut from the one or more window products it
        spans. Returns ``[(fragments, orig_len)]`` aligned with ``values``,
        or ``[(fragments, orig_len, crcs)]`` with ``with_crc=True`` — the
        CRC-32 of each fragment, computed in the same pass that materialises
        its bytes (the integrity tags the EC DAP ships inside coded
        elements)."""
        if not values:
            return []
        k, m = self.k, self.m
        with tracing.span("rs.encode"):
            data = [np.frombuffer(v, dtype=np.uint8) for v in values]
            layout = _Columns([-(-len(d) // k) or 1 for d in data])
            # each value's parity rows, as the pieces of the windows it spans
            parity: list[list[list[bytes]]] = [[[] for _ in range(m)] for _ in values]
            if m:
                def pack(lo: int, hi: int) -> np.ndarray:
                    B = np.empty((k, hi - lo), dtype=np.uint8)
                    for b, j0, j1, at in layout.within(lo, hi):
                        _rows_into(B[:, at : at + j1 - j0], data[b], layout.widths[b], j0, j1)
                    return B

                for lo, hi, product in self._by_window(self._parity, k, layout.width, pack):
                    with tracing.span("rs.unpack"):
                        for b, j0, j1, at in layout.within(lo, hi):
                            for j in range(m):
                                parity[b][j].append(product[j, at : at + j1 - j0].tobytes())
            out = []
            with tracing.span("rs.unpack"):
                for d, L, pieces in zip(data, layout.widths, parity):
                    frags = [_row_bytes(d, L, i) for i in range(k)]
                    frags += [b"".join(p) for p in pieces]
                    if with_crc:
                        out.append((frags, len(d), [fragment_crc(f) for f in frags]))
                    else:
                        out.append((frags, len(d)))
            return out

    def _by_window(self, A: np.ndarray, rows: int, width: int, pack):
        """Yield ``(lo, hi, A (x) B[:, lo:hi])`` over the column windows of a
        ``(rows, width)`` operand ``B`` that is never built whole:
        ``pack(lo, hi)`` returns its columns ``[lo, hi)``. The windows are
        ``dispatch.slices`` at ``dispatch.GF_SLICE_COLS``, so no operand or
        product of a call is wider than one. The kernel-or-LUT choice is
        made once, on the whole operand; on the kernel each window is one
        ``gf256_matmul`` call, and where a call has several, each counts as
        one slice of a larger call (``gf256.slices``, with its unpadded
        bytes in ``gf256.bytes_in_sliced``)."""
        from repro.kernels import dispatch

        windows = dispatch.slices(width, dispatch.GF_SLICE_COLS)
        kernel = self._use_kernel(A, rows, width)
        for lo, hi in windows:
            with tracing.span("rs.pack"):
                B = pack(lo, hi)
            if kernel:
                from repro.kernels.gf256_matmul import ops as gf_ops

                product = gf_ops.gf256_matmul(A, B)
                if len(windows) > 1:
                    tracing.count("gf256.slices")
                    tracing.count("gf256.bytes_in_sliced", B.size)
            else:
                product = gf_matmul_np(A, B)
            yield lo, hi, product

    def _choose_idxs(self, fragments: dict) -> tuple[int, ...]:
        """The k-subset of fragment indices to decode from: the all-systematic
        subset whenever every data fragment is present (the no-matmul fast
        path), the lowest k indices otherwise."""
        if len(fragments) < self.k:
            raise ValueError(f"need {self.k} fragments, have {len(fragments)}")
        if all(i in fragments for i in range(self.k)):
            return tuple(range(self.k))
        return tuple(int(i) for i in sorted(fragments)[: self.k])

    def _decode_groups(self, groups: list[tuple[np.ndarray, list]], out: list) -> None:
        """Decode each ``(decoder, members)`` group into ``out``; a member is
        ``(pos, rows, L, orig)``, its k fragment rows of L bytes. A group's
        members lie side by side as one ``(k, W)`` operand, multiplied
        window by window. On the native kernel several groups fuse into
        ONE block-diagonal ``(G*k, G*k)`` product over the widest group's
        columns, each group's rows zero past its own width (zero blocks are
        free on the MXU; the CPU LUT path keeps one product per group, where
        a block-diagonal one would cost G x the dense work)."""
        k = self.k
        fuse = self.fuse_groups
        if fuse is None and self.backend != "numpy" and len(groups) > 1:
            from repro.kernels import dispatch

            fuse = dispatch.kernel_is_native()
        if (
            not fuse
            or len(groups) <= 1
            or self.backend == "numpy"
            or len(groups) * k > _FUSE_MAX_ROWS
        ):
            passes = [(dec, [members]) for dec, members in groups]
        else:
            A = np.zeros((len(groups) * k, len(groups) * k), dtype=np.uint8)
            for g, (dec, _) in enumerate(groups):
                A[g * k : (g + 1) * k, g * k : (g + 1) * k] = dec
            passes = [(A, [members for _, members in groups])]
        for A, stack in passes:
            layouts = [_Columns([L for _, _, L, _ in members]) for members in stack]
            values = [[np.empty((k, L), dtype=np.uint8) for _, _, L, _ in members]
                      for members in stack]

            def pack(lo: int, hi: int) -> np.ndarray:
                B = np.empty((len(stack) * k, hi - lo), dtype=np.uint8)
                for g, (members, layout) in enumerate(zip(stack, layouts)):
                    dst = B[g * k : (g + 1) * k]
                    for b, j0, j1, at in layout.within(lo, hi):
                        for r, row in enumerate(members[b][1]):
                            dst[r, at : at + j1 - j0] = row[j0:j1]
                    dst[:, max(layout.width - lo, 0) :] = 0
                return B

            wmax = max(layout.width for layout in layouts)
            for lo, hi, product in self._by_window(A, len(stack) * k, wmax, pack):
                with tracing.span("rs.unpack"):
                    for g, layout in enumerate(layouts):
                        for b, j0, j1, at in layout.within(lo, hi):
                            values[g][b][:, j0:j1] = product[g * k : (g + 1) * k,
                                                             at : at + j1 - j0]
            with tracing.span("rs.unpack"):
                for members, decoded in zip(stack, values):
                    for (pos, _, _, orig), value in zip(members, decoded):
                        out[pos] = value.reshape(-1)[:orig].tobytes()

    def decode_bytes_batch(self, items: list[tuple]) -> list[bytes]:
        """Decode many byte values with as few GF(256) matmuls as possible.

        Each item is ``(fragments, orig_len)`` or ``(fragments, orig_len,
        crcs)`` — ``fragments`` maps fragment index -> fragment bytes (any
        number >= k; the decode subset is chosen here, preferring the
        all-systematic one), ``crcs`` optionally maps index -> CRC-32 to
        verify while the rows are gathered. Items whose chosen index subset
        coincides (the common case for a batched read: every block heard
        from the same quorum) share one cached inverted generator and fuse
        column-wise into one operand regardless of ragged lengths, packed
        and multiplied one column window at a time; distinct subsets
        additionally fuse block-diagonally on the native kernel. Raises
        ``ValueError`` when an item's chosen fragments disagree in length
        (a short/truncated fragment would otherwise silently decode to
        garbage) or fail their checksum. Returns the decoded bytes aligned
        with ``items``."""
        out: list[bytes | None] = [None] * len(items)
        sys_idxs = tuple(range(self.k))
        groups: dict[tuple[int, ...], list[tuple[int, list, int, int]]] = {}
        with tracing.span("rs.decode"):
            for pos, item in enumerate(items):
                fragments, orig = item[0], item[1]
                crcs = item[2] if len(item) > 2 else None
                idxs = self._choose_idxs(fragments)
                L = len(fragments[idxs[0]])
                for i in idxs:
                    if len(fragments[i]) != L:
                        raise ValueError(
                            f"fragment length mismatch in item {pos}: index {i} "
                            f"has {len(fragments[i])} bytes, index {idxs[0]} has {L}"
                        )
                    if (
                        crcs is not None
                        and crcs.get(i) is not None
                        and fragment_crc(fragments[i]) != crcs[i]
                    ):
                        raise ValueError(
                            f"fragment {i} of item {pos} failed its checksum"
                        )
                if self.k * L < orig:
                    raise ValueError(
                        f"item {pos}: {self.k} fragments of {L} bytes cannot hold "
                        f"a {orig}-byte value"
                    )
                if idxs == sys_idxs:
                    # systematic fast path: the data rows ARE the fragments
                    with tracing.span("rs.unpack"):
                        out[pos] = b"".join(bytes(fragments[i]) for i in idxs)[:orig]
                else:
                    rows = [np.frombuffer(fragments[i], dtype=np.uint8) for i in idxs]
                    groups.setdefault(idxs, []).append((pos, rows, L, orig))
            self._decode_groups(
                [(_decoder_cached(self.n, self.k, idxs), members)
                 for idxs, members in groups.items()],
                out,
            )
        return out  # type: ignore[return-value]

    def decode_bytes(
        self, fragments: dict[int, bytes], orig_len: int, crcs: dict | None = None
    ) -> bytes:
        item = (fragments, orig_len) if crcs is None else (fragments, orig_len, crcs)
        return self.decode_bytes_batch([item])[0]
