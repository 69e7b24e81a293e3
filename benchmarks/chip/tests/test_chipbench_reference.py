"""The benchmark's plain reference agrees with the program on small data,
so a wrong answer on the chip is the program's and not the reference's."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import reference  # noqa: E402


@pytest.mark.parametrize("n,k", [(11, 6), (6, 2), (11, 10)])
def test_rs_fragments_equal_the_program_code(n, k):
    from repro.erasure.rs import RSCode

    value = np.random.default_rng(n * k).bytes(4099)
    frags, orig = RSCode(n=n, k=k).encode_bytes(value)
    assert orig == len(value)
    assert reference.fragments(value, n, k) == dict(enumerate(frags))


def test_gear_hash_and_chunks_equal_the_program_chunker():
    from repro.kernels.cdc_gearhash.ops import split_chunks
    from repro.kernels.cdc_gearhash.ref import gearhash_ref

    data = np.random.default_rng(5).bytes(50_000)
    h, _b = gearhash_ref(np.frombuffer(data, np.uint8), mask=0xFF)
    assert (reference.gear_hash(data) == np.asarray(h)).all()
    chunks = split_chunks(data, min_size=512, avg_size=1024, max_size=2048)
    assert reference.chunk_lengths(data, 512, 1024, 2048) == [len(c) for c in chunks]
    assert len(chunks) > 20


def test_block_values_and_genesis_parse_follow_the_program_layout():
    from repro.core.fragment import encode_block_value, encode_genesis_meta, genesis_id

    data = np.random.default_rng(6).bytes(9000)
    values = reference.block_values(data, 512, 1024, 2048)
    assert b"".join(v[2:] for v in values) == data
    assert all(v[:2] == encode_block_value(None, b"") for v in values)
    index = ["f\x01c\x011", "f\x01c\x012"]
    assert reference.parse_genesis(encode_block_value(index[0], encode_genesis_meta(index))) == index
    assert reference.genesis_id("f") == genesis_id("f")
