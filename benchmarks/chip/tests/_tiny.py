"""A cell at a tiny size on the CPU, for the tests: KiB objects and blocks,
and the GF(256) kernel path taken from 1 KiB of operand, so every block
reaches the kernel entry point as it does on the chip."""
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from chipbench import harness, spec  # noqa: E402

PEAKS = {"hbm_bytes_per_s": 819e9}


def tiny_cell(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    cell.config.update(min_block=2048, avg_block=2048, max_block=4096)
    key = "file_sizes_mib" if "file_sizes_mib" in cell.traffic else "object_sizes_mib"
    cell.traffic[key] = [s / 256 for s in cell.traffic[key]]
    if cell.traffic.get("preload_per_size"):
        cell.traffic["preload_per_size"] = 2
    return cell


def run(name: str, monkeypatch, *, seed: int = 7, seconds: float = 0.3,
        traced: bool = False) -> dict:
    from repro.erasure import rs

    monkeypatch.setattr(rs, "AUTO_KERNEL_MIN_BYTES", 1024)
    return harness.run_cell(tiny_cell(name), seed, seconds, traced,
                            t0=time.perf_counter(), peaks=PEAKS)
