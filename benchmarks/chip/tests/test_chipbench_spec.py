"""BENCHMARK.json resolves, cell by cell, to the files the harness reads."""
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import spec  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = spec.load_cell(cell)
    assert c.chips in (1, 4)
    assert c.config["n_servers"] > c.config["n_servers"] - c.config["parity_m"] > 0
    assert c.end_to_end and c.per_layer
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.reader(m["name"]))
    # each per-layer metric moves an end-to-end metric this cell reports
    assert {m["moves"] for m in c.per_layer} <= names


def test_names_and_paths_keep_the_contract():
    entries = (BENCHMARK["configs"] + BENCHMARK["workloads"]
               + BENCHMARK["end_to_end"] + BENCHMARK["per_layer"])
    assert all(NAME.match(e["name"]) for e in entries)
    for c in BENCHMARK["configs"]:
        assert c["file"].startswith("benchmarks/chip/") and (ROOT / c["file"]).is_file()
        on_file = json.loads((ROOT / c["file"]).read_text())
        assert sorted(on_file["reduced"]) == sorted(c["reduced"])
        assert on_file["name"] == c["name"] and on_file["source"]
    assert len({c["source"] for c in BENCHMARK["configs"]}) == len(BENCHMARK["configs"])


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v0 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        spec.load_cell("no_such.cell")


def test_the_entry_refuses_the_cpu_and_prints_nothing():
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(ROOT)}
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr  # it got past every import to the look


def test_the_compile_cache_lies_inside_the_checkout(monkeypatch, tmp_path):
    import jax

    from chipbench import harness

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    try:
        path = harness.compile_cache()
        assert path == str(ROOT / ".jax_cache") == jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
