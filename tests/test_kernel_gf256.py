"""Pallas gf256_matmul kernel vs pure-jnp oracle: shape/dtype sweeps."""
import numpy as np
import pytest

from repro.erasure import RSCode, gf_matmul_np
from repro.kernels.gf256_matmul.ops import gf256_matmul, rs_encode_parity
from repro.kernels.gf256_matmul.ref import gf256_matmul_ref

SHAPES = [
    (1, 2, 8),
    (2, 4, 128),
    (4, 10, 1000),     # unaligned L -> pad path
    (3, 16, 2048),
    (8, 24, 4096),     # multi-block grid
    (16, 32, 2048),
    (2, 2, 1),         # degenerate L
    (12, 20, 8192),
]


@pytest.mark.parametrize("m,k,L", SHAPES)
def test_kernel_matches_ref(m, k, L):
    rng = np.random.default_rng(m * 1000 + k * 10 + L)
    A = rng.integers(0, 256, (m, k), dtype=np.uint8)
    B = rng.integers(0, 256, (k, L), dtype=np.uint8)
    got = np.asarray(gf256_matmul(A, B, interpret=True))
    want = np.asarray(gf256_matmul_ref(A, B))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m,k,L", [(4, 8, 512), (5, 11, 777)])
def test_ref_matches_numpy_lut(m, k, L):
    rng = np.random.default_rng(0)
    A = rng.integers(0, 256, (m, k), dtype=np.uint8)
    B = rng.integers(0, 256, (k, L), dtype=np.uint8)
    np.testing.assert_array_equal(np.asarray(gf256_matmul_ref(A, B)), gf_matmul_np(A, B))


def test_kernel_edge_values():
    """All-zero, all-ones, and identity corners."""
    k, L = 6, 256
    A = np.eye(k, dtype=np.uint8)
    B = np.arange(k * L, dtype=np.uint8).reshape(k, L)
    np.testing.assert_array_equal(np.asarray(gf256_matmul(A, B, interpret=True)), B)
    Z = np.zeros((3, k), dtype=np.uint8)
    np.testing.assert_array_equal(
        np.asarray(gf256_matmul(Z, B, interpret=True)), np.zeros((3, L), np.uint8)
    )
    F = np.full((2, k), 255, dtype=np.uint8)
    np.testing.assert_array_equal(
        np.asarray(gf256_matmul(F, B, interpret=True)), np.asarray(gf256_matmul_ref(F, B))
    )


def test_block_size_sweep():
    """Same result for every VMEM block size (tiling invariance)."""
    rng = np.random.default_rng(42)
    A = rng.integers(0, 256, (4, 10), dtype=np.uint8)
    B = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
    want = np.asarray(gf256_matmul_ref(A, B))
    for bl in (128, 256, 512, 1024, 2048, 4096):
        got = np.asarray(gf256_matmul(A, B, block_l=bl, interpret=True))
        np.testing.assert_array_equal(got, want, err_msg=f"block_l={bl}")


def test_rs_kernel_backend_matches_numpy_backend():
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (10, 2048), dtype=np.uint8)
    c_np = RSCode(n=14, k=10, backend="numpy")
    c_kr = RSCode(n=14, k=10, backend="kernel")
    np.testing.assert_array_equal(c_np.encode(data), c_kr.encode(data))
    coded = c_kr.encode(data)
    keep = [1, 3, 5, 7, 9, 10, 11, 12, 13, 0]
    np.testing.assert_array_equal(c_kr.decode(coded[keep], keep), data)


def test_shape_validation_raises_valueerror():
    """Regression (ISSUE 6): shape mismatches must raise ValueError — an
    ``assert`` disappears under ``python -O`` and the mismatch would surface
    as wrong-shaped kernel output."""
    A = np.zeros((2, 4), dtype=np.uint8)
    with pytest.raises(ValueError):
        gf256_matmul(A, np.zeros((5, 16), dtype=np.uint8), interpret=True)
    with pytest.raises(ValueError):
        gf256_matmul(A, np.zeros(16, dtype=np.uint8), interpret=True)
    with pytest.raises(ValueError):
        gf256_matmul(A, np.zeros((5, 16), dtype=np.uint8))
    with pytest.raises(ValueError):
        gf256_matmul(np.zeros(4, dtype=np.uint8), np.zeros((4, 16), dtype=np.uint8))


def test_degenerate_shapes():
    """m == 0 / L == 0 / k == 0 products the storage path can produce
    (parity-free codes, empty values) return empty matrices, not crashes."""
    for ma, ka, L in [(0, 4, 16), (2, 4, 0), (0, 0, 0), (2, 0, 5)]:
        A = np.zeros((ma, ka), dtype=np.uint8)
        B = np.zeros((ka, L), dtype=np.uint8)
        for fn in (
            lambda a, b: gf256_matmul(a, b, interpret=True),
            gf256_matmul,
        ):
            out = np.asarray(fn(A, B))
            assert out.shape == (ma, L) and out.dtype == np.uint8


def test_coding_matmul_matches_lut():
    """The production dispatcher (whatever backend it picks on this host) is
    bit-identical to the numpy LUT reference across L sizes."""
    rng = np.random.default_rng(11)
    A = rng.integers(0, 256, (3, 7), dtype=np.uint8)
    for L in (1, 7, 128, 1000, 5000):
        B = rng.integers(0, 256, (7, L), dtype=np.uint8)
        np.testing.assert_array_equal(
            gf256_matmul(A, B), gf_matmul_np(A, B)
        )


def test_rs_encode_parity_wrapper():
    rng = np.random.default_rng(9)
    code = RSCode(n=12, k=8)
    data = rng.integers(0, 256, (8, 1024), dtype=np.uint8)
    par = np.asarray(rs_encode_parity(code.parity_matrix, data, interpret=True))
    np.testing.assert_array_equal(par, code.encode(data)[8:])


def _block_diagonal(rng, k: int, groups: int) -> np.ndarray:
    """A fused decode's matrix: one k x k block per survivor set."""
    A = np.zeros((groups * k, groups * k), dtype=np.uint8)
    for g in range(groups):
        A[g * k:(g + 1) * k, g * k:(g + 1) * k] = rng.integers(0, 256, (k, k), dtype=np.uint8)
    return A


@pytest.mark.parametrize("path", ["interpret", "jit_ref"])
@pytest.mark.parametrize("L", [1, 127, 128, 2048, 2049, 4097])
@pytest.mark.parametrize("m,k", [(5, 6), (6, 6), (4, 2), (12, 12)],
                         ids=["encode-emulab", "decode-emulab", "encode-aws", "fused-decode"])
def test_flat_boundary_matches_lut(m, k, L, path, monkeypatch):
    """The device program takes the padded operand as one flat (k*Lp,)
    buffer and returns a flat (m*Lp,) product; the wrapper's (m, L) result
    is bit-identical to the numpy LUT at every bucket edge."""
    from repro.kernels import dispatch
    from repro.kernels.gf256_matmul import ops

    rng = np.random.default_rng(m * 7919 + k * 131 + L)
    A = (_block_diagonal(rng, 6, 2) if m == 12
         else rng.integers(0, 256, (m, k), dtype=np.uint8))
    B = rng.integers(0, 256, (k, L), dtype=np.uint8)
    Lp = dispatch.width_bucket(L)
    seen = []

    def spy(fn):
        def call(*args, **kw):
            out = fn(*args, **kw)
            seen.append((args[1].shape, out.shape))
            return out
        return call

    if path == "interpret":
        monkeypatch.setattr(ops, "gf2_bitsliced_matmul", spy(ops.gf2_bitsliced_matmul))
        got = gf256_matmul(A, B, interpret=True)
    else:
        monkeypatch.setattr(dispatch, "kernel_is_native", lambda: False)
        ref = spy(ops._jit_ref())
        monkeypatch.setattr(ops, "_jit_ref", lambda: ref)
        got = gf256_matmul(A, B)
    assert seen == [((k * Lp,), (m * Lp,))]
    assert got.shape == (m, L) and got.dtype == np.uint8
    np.testing.assert_array_equal(got, gf_matmul_np(A, B))


# -- operands wider than a slice ---------------------------------------------
SLICE = 2048  # dispatch.GF_SLICE_COLS patched down: 2^21 on the chip


@pytest.mark.parametrize("path", ["interpret", "jit_ref"])
@pytest.mark.parametrize("L", [SLICE - 1, SLICE, SLICE + 1, 3 * SLICE + 5])
@pytest.mark.parametrize("m,k", [(5, 6), (6, 6), (12, 12)],
                         ids=["encode-emulab", "decode-emulab", "fused-decode"])
def test_sliced_product_matches_lut(m, k, L, path, monkeypatch):
    """An operand wider than a slice is multiplied slice by slice, the last
    bucketed, every slice launched before any product is fetched; the
    (m, L) result is bit-identical to the numpy LUT, for a block-diagonal
    fused decode too."""
    from repro import tracing
    from repro.kernels import dispatch
    from repro.kernels.gf256_matmul import ops

    monkeypatch.setattr(dispatch, "GF_SLICE_COLS", SLICE)
    rng = np.random.default_rng(m * 7919 + k * 131 + L)
    A = (_block_diagonal(rng, 6, 2) if m == 12
         else rng.integers(0, 256, (m, k), dtype=np.uint8))
    B = rng.integers(0, 256, (k, L), dtype=np.uint8)
    events = []
    launch, get = ops._launch, ops._get

    def spy_launch(A, B, *a):
        events.append(("launch", B.shape[1]))
        return launch(A, B, *a)

    def spy_get(out, Lp, m):
        events.append(("get", Lp))
        return get(out, Lp, m)

    monkeypatch.setattr(ops, "_launch", spy_launch)
    monkeypatch.setattr(ops, "_get", spy_get)
    if path == "jit_ref":
        monkeypatch.setattr(dispatch, "kernel_is_native", lambda: False)
    tracing.enable(True)
    try:
        got = gf256_matmul(A, B, interpret=True if path == "interpret" else None)
        counts = tracing.recording()["counts"]
    finally:
        tracing.enable(False)
    assert got.shape == (m, L) and got.dtype == np.uint8
    np.testing.assert_array_equal(got, gf_matmul_np(A, B))
    widths = [min(SLICE, L - lo) for lo in range(0, L, SLICE)]
    buckets = [dispatch.width_bucket(w) for w in widths]
    assert events == [("launch", w) for w in widths] + [("get", b) for b in buckets]
    assert counts["gf256.launch"] == len(widths)
    assert counts["gf256.bytes_in_padded"] == k * sum(buckets)
    if len(widths) == 1:
        assert "gf256.slices" not in counts
    else:
        assert counts["gf256.slices"] == len(widths)
        assert counts["gf256.bytes_in_sliced"] == counts["gf256.bytes_in"] == k * L


def test_sliced_fused_decode_through_the_code(monkeypatch):
    """``decode_bytes_batch`` over two survivor sets, fused block-diagonally
    into one product wider than a slice, returns every value."""
    from repro.kernels import dispatch

    monkeypatch.setattr(dispatch, "GF_SLICE_COLS", SLICE)
    code = RSCode(n=11, k=6, backend="kernel", fuse_groups=True)
    rng = np.random.default_rng(21)
    values = [rng.bytes(n) for n in (6 * SLICE + 9, 3 * SLICE, 40_000)]
    items = []
    for j, (frags, orig, crcs) in enumerate(code.encode_bytes_batch(values, with_crc=True)):
        lost = {0} if j % 2 else {0, 1}  # two survivor sets
        keep = {i: f for i, f in enumerate(frags) if i not in lost}
        items.append((keep, orig, {i: crcs[i] for i in keep}))
    from repro import tracing

    tracing.enable(True)
    try:
        assert code.decode_bytes_batch(items) == values
        counts = tracing.recording()["counts"]
    finally:
        tracing.enable(False)
    # one fused (12, 12) product, cut into slices
    assert counts["gf256.launch.rows.12"] == counts["gf256.slices"] > 1
