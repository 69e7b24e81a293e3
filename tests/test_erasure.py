"""GF(256) field + RS code correctness (unit + hypothesis property tests)."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # seeded fallback shim — see tests/_propfallback.py
    from _propfallback import given, settings
    from _propfallback import strategies as st

from repro.erasure import (
    RSCode,
    bytes_to_rows,
    cauchy_parity_matrix,
    gf_inv,
    gf_invert_matrix,
    gf_matmul_np,
    gf_mul,
    rows_to_bytes,
    vandermonde_matrix,
)
from repro.erasure.gf import (
    bits_to_bytes_np,
    bytes_to_bits_np,
    gf_const_to_bitmatrix,
    gf_matrix_to_bitmatrix,
)

els = st.integers(min_value=0, max_value=255)
nz_els = st.integers(min_value=1, max_value=255)


# ---------------------------------------------------------------- field axioms
@given(els, els, els)
def test_gf_mul_associative(a, b, c):
    assert gf_mul(gf_mul(a, b), c) == gf_mul(a, gf_mul(b, c))


@given(els, els)
def test_gf_mul_commutative(a, b):
    assert gf_mul(a, b) == gf_mul(b, a)


@given(els, els, els)
def test_gf_distributive(a, b, c):
    assert gf_mul(a, b ^ c) == gf_mul(a, b) ^ gf_mul(a, c)


@given(nz_els)
def test_gf_inverse(a):
    assert gf_mul(a, gf_inv(a)) == 1


@given(els)
def test_gf_identity_and_zero(a):
    assert gf_mul(a, 1) == a
    assert gf_mul(a, 0) == 0


# ------------------------------------------------------------- bitslice algebra
@given(els, els)
def test_bitmatrix_multiplication(c, d):
    """bits(c*d) == M_c @ bits(d) mod 2 — the core bitslicing identity."""
    M = gf_const_to_bitmatrix(c)
    dbits = np.array([(d >> j) & 1 for j in range(8)], dtype=np.uint8)
    pbits = (M @ dbits) % 2
    p = sum(int(pbits[i]) << i for i in range(8))
    assert p == gf_mul(c, d)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 8), st.integers(2, 8), st.integers(1, 64), st.integers(0, 2**32 - 1))
def test_bitsliced_matmul_matches_lut(m, k, L, seed):
    rng = np.random.default_rng(seed)
    A = rng.integers(0, 256, (m, k), dtype=np.uint8)
    B = rng.integers(0, 256, (k, L), dtype=np.uint8)
    want = gf_matmul_np(A, B)
    Abits = gf_matrix_to_bitmatrix(A).astype(np.int64)
    Bbits = bytes_to_bits_np(B).astype(np.int64)
    got = bits_to_bytes_np(((Abits @ Bbits) % 2).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------- matrix layer
def test_gf_matrix_inverse_roundtrip():
    rng = np.random.default_rng(0)
    for k in (1, 2, 5, 10):
        # Cauchy-derived square matrices are always invertible
        A = cauchy_parity_matrix(2 * k, k)[:k]
        Ainv = gf_invert_matrix(A)
        np.testing.assert_array_equal(gf_matmul_np(A, Ainv), np.eye(k, dtype=np.uint8))


def test_singular_matrix_raises():
    A = np.zeros((3, 3), dtype=np.uint8)
    with pytest.raises(np.linalg.LinAlgError):
        gf_invert_matrix(A)


def test_vandermonde_systematic_mds_small():
    # every k-subset of generator rows of [I; P] must be invertible
    import itertools

    n, k = 7, 4
    P = vandermonde_matrix(n, k)
    G = np.concatenate([np.eye(k, dtype=np.uint8), P], axis=0)
    for rows in itertools.combinations(range(n), k):
        gf_invert_matrix(G[list(rows)])  # must not raise


def test_cauchy_mds_small():
    import itertools

    n, k = 8, 5
    P = cauchy_parity_matrix(n, k)
    G = np.concatenate([np.eye(k, dtype=np.uint8), P], axis=0)
    for rows in itertools.combinations(range(n), k):
        gf_invert_matrix(G[list(rows)])


# ------------------------------------------------------------------- RS codes
@settings(max_examples=20, deadline=None)
@given(
    st.integers(2, 10),      # k
    st.integers(0, 6),       # m
    st.integers(1, 200),     # L
    st.integers(0, 2**32 - 1),
)
def test_rs_roundtrip_random_erasures(k, m, L, seed):
    n = k + m
    rng = np.random.default_rng(seed)
    code = RSCode(n=n, k=k)
    data = rng.integers(0, 256, (k, L), dtype=np.uint8)
    coded = code.encode(data)
    assert coded.shape == (n, L)
    np.testing.assert_array_equal(coded[:k], data)  # systematic
    keep = rng.permutation(n)[:k]
    got = code.decode(coded[keep], list(keep))
    np.testing.assert_array_equal(got, data)


def test_rs_decode_insufficient_fragments():
    code = RSCode(n=6, k=4)
    data = np.arange(4 * 8, dtype=np.uint8).reshape(4, 8)
    coded = code.encode(data)
    with pytest.raises(ValueError):
        code.decode(coded[:3], [0, 1, 2])


def test_rs_reconstruct_single_fragment():
    rng = np.random.default_rng(7)
    code = RSCode(n=8, k=5)
    data = rng.integers(0, 256, (5, 33), dtype=np.uint8)
    coded = code.encode(data)
    for lost in range(8):
        keep = [i for i in range(8) if i != lost][:5]
        rebuilt = code.reconstruct_fragment(lost, coded[keep], keep)
        np.testing.assert_array_equal(rebuilt, coded[lost])


@settings(max_examples=20, deadline=None)
@given(st.binary(min_size=0, max_size=4096), st.integers(2, 9), st.integers(1, 4))
def test_rs_bytes_roundtrip(blob, k, m):
    code = RSCode(n=k + m, k=k)
    frags, orig = code.encode_bytes(blob)
    assert len(frags) == k + m
    # drop the m largest-index fragments, decode from an arbitrary k-subset
    rng = np.random.default_rng(len(blob))
    keep = sorted(rng.permutation(k + m)[:k].tolist())
    got = code.decode_bytes({i: frags[i] for i in keep}, orig)
    assert got == blob


def test_rs_decode_duplicate_indices_raises():
    code = RSCode(n=6, k=3)
    data = np.arange(3 * 8, dtype=np.uint8).reshape(3, 8)
    coded = code.encode(data)
    with pytest.raises(ValueError):
        code.decode(np.stack([coded[0], coded[0], coded[1]]), [0, 0, 1])
    with pytest.raises(ValueError):
        code.decode_batch(coded[None, [0, 0, 1]], [0, 0, 1])


def test_rs_reconstruct_systematic_and_parity_targets():
    rng = np.random.default_rng(21)
    code = RSCode(n=7, k=4)
    data = rng.integers(0, 256, (4, 19), dtype=np.uint8)
    coded = code.encode(data)
    keep = [1, 3, 4, 6]  # mixed systematic + parity survivors
    # single-target: one systematic (0, 2) and one parity (5) rebuild
    for lost in (0, 2, 5):
        got = code.reconstruct_fragment(lost, coded[keep], keep)
        np.testing.assert_array_equal(got, coded[lost])
    # multi-target fused path matches, in target order
    multi = code.reconstruct_fragments([5, 0, 2], coded[keep], keep)
    np.testing.assert_array_equal(multi, coded[[5, 0, 2]])
    assert code.reconstruct_fragments([], coded[keep], keep).shape == (0, 19)


# ------------------------------------------------------- batched coding
@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(0, 4), st.integers(1, 40),
       st.integers(1, 12), st.integers(0, 2**32 - 1))
def test_encode_decode_batch_bit_identical_to_per_block(k, m, L, B, seed):
    n = k + m
    rng = np.random.default_rng(seed)
    code = RSCode(n=n, k=k)
    data = rng.integers(0, 256, (B, k, L), dtype=np.uint8)
    batch = code.encode_batch(data)
    per = np.stack([code.encode(data[b]) for b in range(B)])
    np.testing.assert_array_equal(batch, per)
    keep = sorted(rng.permutation(n)[:k].tolist())
    got = code.decode_batch(batch[:, keep, :], keep)
    np.testing.assert_array_equal(got, data)
    per_dec = np.stack([code.decode(batch[b][keep], keep) for b in range(B)])
    np.testing.assert_array_equal(got, per_dec)


def test_encode_batch_shape_and_insufficient_checks():
    code = RSCode(n=6, k=4)
    with pytest.raises(ValueError):
        code.encode_batch(np.zeros((2, 3, 8), dtype=np.uint8))  # wrong k
    with pytest.raises(ValueError):
        code.encode_batch(np.zeros((4, 8), dtype=np.uint8))     # not 3-D
    with pytest.raises(ValueError):
        code.decode_batch(np.zeros((2, 3, 8), dtype=np.uint8), [0, 1, 2])
    assert code.encode_batch(np.zeros((0, 4, 8), dtype=np.uint8)).shape == (0, 6, 8)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.binary(min_size=0, max_size=600), min_size=1, max_size=6),
       st.integers(2, 6), st.integers(1, 3))
def test_encode_bytes_batch_matches_encode_bytes(values, k, m):
    code = RSCode(n=k + m, k=k)
    got = code.encode_bytes_batch(values)
    assert len(got) == len(values)
    for v, (frags, orig) in zip(values, got):
        f_ref, o_ref = code.encode_bytes(v)
        assert frags == f_ref and orig == o_ref
    assert code.encode_bytes_batch([]) == []


def test_encode_batch_single_kernel_call(monkeypatch):
    """Acceptance (ISSUE 1): >= 32 blocks on the kernel backend issue exactly
    ONE kernel matmul, bit-identical to per-block numpy encode. The kernel
    backend dispatches through ``gf256_matmul``, so that is the seam counted
    here."""
    from repro.kernels.gf256_matmul import ops as gf_ops

    calls = []
    real = gf_ops.gf256_matmul

    def counting(A, B, **kw):
        calls.append(np.asarray(B).shape)
        return real(A, B, **kw)

    monkeypatch.setattr(gf_ops, "gf256_matmul", counting)
    rng = np.random.default_rng(3)
    code = RSCode(n=6, k=4, backend="kernel")
    data = rng.integers(0, 256, (32, 4, 16), dtype=np.uint8)
    coded = code.encode_batch(data)
    assert len(calls) == 1, f"expected one fused kernel call, saw {len(calls)}"
    ref = np.stack([RSCode(n=6, k=4).encode(data[b]) for b in range(32)])
    np.testing.assert_array_equal(coded, ref)


def test_bytes_rows_padding():
    rows, orig = bytes_to_rows(b"hello world", 4)
    assert rows.shape[0] == 4 and orig == 11
    assert rows_to_bytes(rows, orig) == b"hello world"
    rows0, o0 = bytes_to_rows(b"", 3)
    assert rows0.shape == (3, 1) and rows_to_bytes(rows0, o0) == b""


# --------------------------------------------------- ISSUE 6 regressions
def test_decode_bytes_rejects_truncated_fragment():
    """Regression (ISSUE 6): a short/truncated fragment used to be silently
    zero-padded into the decode operand and produce garbage bytes; a length
    mismatch within an item's chosen fragments must raise."""
    code = RSCode(n=6, k=4)
    frags, orig = code.encode_bytes(b"x" * 4000)
    good = {i: frags[i] for i in (0, 1, 2, 4)}
    bad = dict(good)
    bad[1] = bad[1][:-3]
    with pytest.raises(ValueError, match="length mismatch"):
        code.decode_bytes_batch([(bad, orig)])
    with pytest.raises(ValueError, match="length mismatch"):
        code.decode_bytes(bad, orig)
    assert code.decode_bytes_batch([(good, orig)]) == [b"x" * 4000]


def test_decode_prefers_systematic_subset(monkeypatch):
    """Regression (ISSUE 6): when the k systematic fragments are all present
    — in any order, or alongside parity fragments — every decode path must
    take the copy fast path and perform NO GF matmul."""
    import repro.erasure.rs as rs_mod

    code = RSCode(n=6, k=4)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (4, 96), dtype=np.uint8)
    coded = code.encode(data)
    frags, orig = code.encode_bytes(b"hello" * 100)

    calls = []
    real = rs_mod.gf_matmul_np

    def counting(A, B):
        calls.append((np.asarray(A).shape, np.asarray(B).shape))
        return real(A, B)

    monkeypatch.setattr(rs_mod, "gf_matmul_np", counting)
    # shuffled systematic indices, plus a parity row riding along
    keep = [3, 0, 2, 1, 5]
    np.testing.assert_array_equal(code.decode(coded[keep], keep), data)
    batch = np.stack([coded[keep], coded[keep]])
    np.testing.assert_array_equal(
        code.decode_batch(batch, keep), np.stack([data, data])
    )
    # bytes form: all systematic present + a parity fragment in the reply
    sub = {i: frags[i] for i in (0, 1, 2, 3, 5)}
    assert code.decode_bytes_batch([(sub, orig)]) == [b"hello" * 100]
    assert code.decode_bytes(sub, orig) == b"hello" * 100
    assert calls == [], f"systematic replies must not matmul: {calls}"
