"""Pallas cdc_gearhash kernel vs pure-jnp oracle + chunking invariants."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # seeded fallback shim — see tests/_propfallback.py
    from _propfallback import given, settings
    from _propfallback import strategies as st

from repro.kernels.cdc_gearhash.ops import boundary_bitmap, gearhash, split_chunks
from repro.kernels.cdc_gearhash.ref import gearhash_ref


@pytest.mark.parametrize("L", [32, 128, 4096, 5000, 12288, 100_000])
@pytest.mark.parametrize("mask", [0xFF, 0xFFF])
def test_kernel_matches_ref(L, mask):
    rng = np.random.default_rng(L + mask)
    data = rng.integers(0, 256, L, dtype=np.uint8)
    h_k, b_k = gearhash(data, mask=mask, block_l=1024, interpret=True)
    import jax.numpy as jnp

    h_r, b_r = gearhash_ref(jnp.asarray(data), mask=mask)
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))
    np.testing.assert_array_equal(np.asarray(b_k), np.asarray(b_r))


def test_locality_of_hash():
    """Hash at position i depends only on bytes (i-31..i) — the CDC property
    that makes chunk boundaries stable under local edits."""
    rng = np.random.default_rng(0)
    a = rng.integers(0, 256, 2048, dtype=np.uint8)
    b = a.copy()
    b[100] ^= 0xFF  # flip one byte
    ha, _ = gearhash(a, interpret=True)
    hb, _ = gearhash(b, interpret=True)
    diff = np.nonzero(np.asarray(ha) != np.asarray(hb))[0]
    assert diff.min() >= 100 and diff.max() <= 100 + 31


@settings(max_examples=15, deadline=None)
@given(st.binary(min_size=1, max_size=8192), st.integers(0, 3))
def test_split_chunks_partition(blob, sz):
    mins, avgs, maxs = [64, 128, 256, 512][sz], [128, 256, 512, 1024][sz], [512, 1024, 2048, 4096][sz]
    chunks = split_chunks(blob, min_size=mins, avg_size=avgs, max_size=maxs, interpret=True)
    assert b"".join(chunks) == blob            # partition: lossless
    for i, c in enumerate(chunks[:-1]):
        assert mins <= len(c) <= maxs or i == len(chunks) - 1
    assert all(len(c) <= maxs for c in chunks)


def test_split_chunks_stability_under_edit():
    """Editing bytes in one region must not move far-away chunk boundaries
    (rsync insight the paper's FM builds on)."""
    rng = np.random.default_rng(5)
    blob = rng.integers(0, 256, 64 * 1024, dtype=np.uint8).tobytes()
    edited = bytearray(blob)
    edited[1000:1100] = rng.integers(0, 256, 100, dtype=np.uint8).tobytes()
    kw = dict(min_size=512, avg_size=1024, max_size=4096, interpret=True)
    c1 = split_chunks(blob, **kw)
    c2 = split_chunks(bytes(edited), **kw)
    # the chunking re-synchronizes after the edit: suffix chunk lists match
    s1 = [bytes(c) for c in c1[-5:]]
    s2 = [bytes(c) for c in c2[-5:]]
    assert s1 == s2
    # and most chunks are shared overall (rsync-style dedup works)
    shared = len(set(c1) & set(c2))
    assert shared >= len(c1) - 4


def test_boundary_density_tracks_avg():
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, 1 << 18, dtype=np.uint8).tobytes()
    bm = boundary_bitmap(data, avg_size=1024, interpret=True)
    density = bm.mean()
    assert 0.3 / 1024 < density < 3.0 / 1024  # ~1/avg within 3x


def test_empty_and_tiny_inputs():
    assert split_chunks(b"", min_size=4, avg_size=8, max_size=16, interpret=True) == [b""]
    out = split_chunks(b"abc", min_size=4, avg_size=8, max_size=16, interpret=True)
    assert b"".join(out) == b"abc"


# -- streams longer than a slice ----------------------------------------------
SLICE = 4096  # dispatch.CDC_SLICE_BYTES patched down: 2^26 on the chip


def _spied(monkeypatch, path):
    """Route the wrapper through ``path`` and record its launches and
    fetches in order."""
    from repro.kernels import dispatch
    from repro.kernels.cdc_gearhash import ops

    monkeypatch.setattr(dispatch, "CDC_SLICE_BYTES", SLICE)
    if path == "interpret":
        monkeypatch.setattr(dispatch, "kernel_is_native", lambda: True)
        pallas = ops.gearhash_pallas
        monkeypatch.setattr(ops, "gearhash_pallas",
                            lambda *a, **kw: pallas(*a, **{**kw, "interpret": True}))
    events = []
    launch, fetch = ops._gearhash_device, ops._fetch

    def spy_launch(buf, *a, **kw):
        events.append(("launch", buf.shape[0]))
        return launch(buf, *a, **kw)

    def spy_fetch(x, L, out=None):
        events.append(("fetch", L))
        return fetch(x, L, out)

    monkeypatch.setattr(ops, "_gearhash_device", spy_launch)
    monkeypatch.setattr(ops, "_fetch", spy_fetch)
    return events


def _gear(x: np.ndarray) -> np.ndarray:
    """The kernel's byte mixer in numpy (uint32 arithmetic wraps)."""
    v = (x.astype(np.uint32) + np.uint32(0x9E3779B9)) * np.uint32(0x85EBCA6B)
    v ^= v >> np.uint32(15)
    v *= np.uint32(0xC2B2AE35)
    return v ^ (v >> np.uint32(13))


def _edge_stream(L: int, bits: int) -> np.ndarray:
    """Random bytes with boundary candidates (mask 2^bits - 1) planted at
    the last byte of every slice and the first ``bits`` bytes after it, the
    positions whose low hash bits read the bytes before the slice. Each is
    planted by choosing its own byte, in order, so later bytes leave the
    earlier candidates as they are."""
    data = np.random.default_rng(L + bits).integers(0, 256, L, dtype=np.uint8)
    mask = np.uint32((1 << bits) - 1)
    every = _gear(np.arange(256, dtype=np.uint8))
    for lo in range(SLICE, L, SLICE):
        for p in range(lo - 1, min(lo + bits, L)):
            window = _gear(data[p - 31:p][::-1])  # x[p-1], x[p-2], ...
            rest = (window << np.arange(1, 32, dtype=np.uint32)).sum(dtype=np.uint32)
            data[p] = np.nonzero(((every + rest) & mask) == 0)[0][0]
    return data


@pytest.mark.parametrize("path", ["interpret", "jit_ref"])
@pytest.mark.parametrize("L", [SLICE, SLICE + 1, 3 * SLICE, 3 * SLICE + 1000, 4 * SLICE - 1])
@pytest.mark.parametrize("bits", [4, 6])
def test_sliced_bitmap_equals_the_whole_stream_ref(L, bits, path, monkeypatch):
    """A stream longer than a slice is hashed slice by slice, each with the
    bytes before it as its first row's tail: the bitmap equals the whole
    stream's, with candidates at slice edges and within 31 bytes after
    them, and for a ragged last slice. Slice j + 1 is launched before
    slice j is fetched."""
    from repro import tracing

    events = _spied(monkeypatch, path)
    data = _edge_stream(L, bits)
    want = np.asarray(gearhash_ref(data, mask=(1 << bits) - 1)[1])
    for lo in range(SLICE, L, SLICE):
        assert want[lo - 1:lo + bits].all()
    tracing.enable(True)
    try:
        got = boundary_bitmap(data.tobytes(), avg_size=1 << bits)
        counts = tracing.recording()["counts"]
    finally:
        tracing.enable(False)
    np.testing.assert_array_equal(got, want)
    for lo in range(SLICE, L, SLICE):  # every slice's first 31 positions
        np.testing.assert_array_equal(got[lo:lo + 31], want[lo:lo + 31])
    n = -(-L // SLICE)
    if n == 1:
        assert events == [("launch", L), ("fetch", L)]
        assert "cdc_kernel.slices" not in counts
        return
    sizes = [min(SLICE, L - lo) for lo in range(0, L, SLICE)]
    order = [("launch", sizes[0])]
    for j in range(1, n):
        order += [("launch", sizes[j]), ("fetch", sizes[j - 1])]
    assert events == order + [("fetch", sizes[-1])]
    assert counts["cdc_kernel.slices"] == n
    assert counts["cdc_kernel.bytes_in_sliced"] == counts["cdc_kernel.bytes_in"] == L


@pytest.mark.parametrize("path", ["interpret", "jit_ref"])
@pytest.mark.parametrize("lo", [128, 1000, SLICE])
def test_a_slice_with_its_head_hashes_as_the_whole_stream(lo, path, monkeypatch):
    """The full 32-bit hash of one slice, given the 128 bytes before it,
    equals the whole stream's at the same positions."""
    from repro.kernels.cdc_gearhash import ops

    _spied(monkeypatch, path)
    data = np.random.default_rng(lo).integers(0, 256, lo + 3000, dtype=np.uint8)
    want = np.asarray(gearhash_ref(data, mask=0xFFF)[0])[lo:]
    h, _b, L = ops._gearhash_device(data[lo:], 0xFFF, 1024, None, data[lo - 128:lo])
    np.testing.assert_array_equal(np.asarray(h)[:L], want)


def test_sliced_chunks_equal_whole_stream_chunks(monkeypatch):
    from repro.kernels import dispatch

    data = np.random.default_rng(8).integers(0, 256, 9 * SLICE + 77, dtype=np.uint8).tobytes()
    kw = dict(min_size=512, avg_size=1024, max_size=2048)
    whole = split_chunks(data, **kw)
    monkeypatch.setattr(dispatch, "CDC_SLICE_BYTES", SLICE)
    assert split_chunks(data, **kw) == whole
    assert len(whole) > 20
