"""The large-object cell at a tiny size on the CPU. ``_tiny`` cuts the
objects and blocks by 256; both slice sizes are cut alike here, so an
object crosses as many slices as it does on the chip. With the control or
a fault in the sliced path planted, the check comes out false."""
from contextlib import contextmanager

import pytest

from _tiny import run

CELL = "emulab_k6.large_object"
SCALE = 256  # 512 MiB -> 2 MiB, 512 KiB blocks -> 2 KiB


@pytest.fixture
def sliced(monkeypatch):
    from repro.erasure import rs
    from repro.kernels import dispatch

    monkeypatch.setattr(dispatch, "CDC_SLICE_BYTES", dispatch.CDC_SLICE_BYTES // SCALE)
    monkeypatch.setattr(dispatch, "GF_SLICE_COLS", dispatch.GF_SLICE_COLS // SCALE)
    monkeypatch.setattr(rs, "AUTO_KERNEL_MIN_BYTES", 1024)


def test_the_configuration_is_the_emulab_store_at_its_largest_files():
    """The file-size deployment is the Emulab store itself; only what it
    holds differs, and the mix's largest object is the largest it holds."""
    from chipbench import spec

    large, emulab = spec.load_cell(CELL), spec.load_cell("emulab_k6.ingest")
    assert large.config["name"] != emulab.config["name"]
    store = set(emulab.config) - {"name", "source", "deployment", "assumed"}
    assert {k: large.config[k] for k in store} == {k: emulab.config[k] for k in store}
    assert max(large.traffic["object_sizes_mib"]) << 20 == large.config["max_object_bytes"]


def test_a_sound_run_is_correct_and_reports_its_metrics(sliced, monkeypatch):
    from _tiny import tiny_cell

    line = run(CELL, monkeypatch, seed=3_000_000_019, seconds=2.0)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in tiny_cell(CELL).end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_a_traced_run_reads_most_bytes_sliced_and_little_padding(sliced, monkeypatch):
    line = run(CELL, monkeypatch, seed=3_000_000_021, seconds=2.0, traced=True)
    assert line["correct"], line["checks"]
    m = line["metrics"]
    # three of the four sizes slice, and carry 896 of every 960 bytes; the
    # rows of a 64 MiB object pad by half a slice, those of larger ones by
    # at most a quarter
    assert m["sliced_share_pct"]["value"] >= 85
    assert m["padding_share_pct"]["value"] < 10


def test_objects_across_slices_are_stored_as_the_reference_stores_them(sliced):
    """Writes of objects that span 3 or more slices of both kernels: every
    data block's value and all of its fragments equal the plain
    reference's, and each reads back whole with two holders down."""
    from _tiny import tiny_cell
    from chipbench import check, harness
    from chipbench.traffic import Payloads

    from repro import tracing
    from repro.kernels import dispatch

    config = tiny_cell(CELL).config
    dss = harness.build_store(config)
    payloads = Payloads(11, 1 << 21)
    # CDC slices of 256 KiB and encode slices of 64 Ki columns, a row being a
    # sixth of the object: 5 / 3, 8 / 6 and 4 / 3 slices, the last ragged
    sizes = [(1 << 20) + 333, 1 << 21, (1 << 20) - 4000]
    files = {f"big{i}": payloads.make(n, i) for i, n in enumerate(sizes)}
    tracing.enable(True)
    try:
        for fid, content in files.items():
            assert dss.session("writer0").write(fid, content).result()["success"]
        counts = tracing.recording()["counts"]
    finally:
        tracing.enable(False)
    dss.net.run()
    assert counts["cdc_kernel.slices"] == sum(-(-n // dispatch.CDC_SLICE_BYTES) for n in sizes)
    assert counts["gf256.slices"] >= 3 * len(sizes)
    assert check.stored_blocks_wrong(dss, config, files) == 0
    assert check.readback_wrong(dss, files, [0, 1]) == 0


@contextmanager
def _slice_falls_short(monkeypatch):
    """A slice bound off by one: every GF(256) slice but the last stops a
    column short, so that column of the product is never written."""
    from repro.kernels import dispatch

    whole = dispatch.slices

    def slices(L, size):
        parts = whole(L, size)
        if size != dispatch.GF_SLICE_COLS:
            return parts
        return [(lo, hi - 1) for lo, hi in parts[:-1]] + parts[-1:]

    with monkeypatch.context() as m:
        m.setattr(dispatch, "slices", slices)
        yield


@pytest.mark.parametrize("fault", ["int8", "slice_falls_short"])
def test_the_check_catches_it(fault, sliced, monkeypatch):
    """A CDC slice hashed without its head is not among these: it moves a
    candidate only within 31 bytes after a slice edge, which a window's
    check almost never samples; ``tests/test_kernel_cdc.py`` plants
    candidates there and catches it."""
    from chipbench import faults

    plant = _slice_falls_short(monkeypatch) if fault == "slice_falls_short" \
        else faults.planted(fault)
    with plant:
        line = run(CELL, monkeypatch, seed=3_000_000_023, seconds=2.0)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
