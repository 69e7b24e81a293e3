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
