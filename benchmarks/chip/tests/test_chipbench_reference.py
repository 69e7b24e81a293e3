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


def _edited_store(history: list[bytes]):
    """A tiny store of the edit cell's deployment after ``history``, each
    content saved in turn by one session, as an edit saves it."""
    from _tiny import tiny_cell
    from chipbench import harness

    config = tiny_cell("emulab_k6.edit").config
    dss = harness.build_store(config)
    session = dss.session("writer0")
    for content in history:
        assert session.write("f", content).result()["success"]
    dss.net.run()
    return dss, config


def _without_third_chunk(data: bytes) -> bytes:
    a, b = np.cumsum(reference.chunk_lengths(data, 2048, 2048, 4096))[1:3]
    return data[:a] + data[b:]


def _histories() -> dict:
    """Edit histories that all end at one content: seeded one-byte flips
    only, or with a chunk deleted first or last (which leaves a
    tombstone in the block index)."""
    from _tiny import tiny_cell
    from chipbench.traffic import Traffic

    cell = tiny_cell("emulab_k6.edit")
    t = Traffic(cell.traffic, cell.config, 2**33 + 9)
    flips = [t.version(0, 2, v) for v in range(6)]
    final = _without_third_chunk(flips[-1])
    return {
        "flips": (flips, flips[-1], 0),
        "delete_then_flip": ([flips[0], _without_third_chunk(flips[0]), final], final, 1),
        "flip_then_delete": ([*flips, final], final, 1),
        "at_once": ([flips[0], final], final, 1),
    }


@pytest.mark.parametrize("history", ["flips", "delete_then_flip", "flip_then_delete", "at_once"])
def test_live_blocks_after_edits_equal_a_fresh_chunking(history):
    """Chunking depends on content alone, so whatever the edits, the
    blocks left live hold the reference's blocks of the final content;
    tombstones are skipped by the check and stay in the index."""
    from chipbench import check

    steps, final, tombstones = _histories()[history]
    dss, config = _edited_store(steps)
    raw = check._stored_value(dss, reference.genesis_id("f"))
    values = [check._stored_value(dss, bid) for bid in reference.parse_genesis(raw)]
    live = [v for v in values if v != check.TOMBSTONE]
    assert live == reference.block_values(final, 2048, 2048, 4096)
    assert len(values) - len(live) == tombstones
    assert check.stored_blocks_wrong(dss, config, {"f": final}) == 0
    # the same comparison against the content before the last edit fails
    assert check.stored_blocks_wrong(dss, config, {"f": steps[-2]}) > 0
