"""``chip_smoke.py``'s phases at a tiny size on the CPU, with both Pallas
kernels in interpret mode, so the script's control flow is checked on every
run of the suite; its ``main()`` still refuses any platform but the TPU."""
import importlib.util
from pathlib import Path

import pytest

from repro import tracing
from repro.erasure import rs
from repro.kernels import dispatch
from repro.kernels.cdc_gearhash import ops as cdc_ops
from repro.kernels.gf256_matmul import ops as gf_ops

KiB = 1 << 10


@pytest.fixture
def smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_the_cpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert "'cpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""  # no result line


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_location(env_dir, monkeypatch, tmp_path):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro import compile_cache

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.CHECKOUT_CACHE)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert compile_cache.enable_compile_cache() == want
        # an environment path is JAX's own to read: the code sets none
        configured = want if env_dir is None else saved[0]
        assert jax.config.jax_compilation_cache_dir == configured
        assert compile_cache.CHECKOUT_CACHE.parent == Path(__file__).resolve().parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
        compilation_cache.reset_cache()


def _interpreted(fn):
    return lambda *a, **kw: fn(*a, **{**kw, "interpret": True})


def test_phases_run_end_to_end_in_interpret_mode(smoke, monkeypatch):
    monkeypatch.setattr(dispatch, "kernel_is_native", lambda: True)
    # scaled with the blocks (2 KiB here, 512 KiB on the chip), so single
    # repaired blocks reach the kernel as they do on the chip, and a repair
    # shape the warm-up missed shows as a steady compile
    monkeypatch.setattr(rs, "AUTO_KERNEL_MIN_BYTES", 1 * KiB)
    # and the slice with them (2^21 columns on the chip), so the batched
    # calls launch in windows as they do there
    monkeypatch.setattr(dispatch, "GF_SLICE_COLS", dispatch.GF_SLICE_COLS // 256)
    monkeypatch.setattr(gf_ops, "gf2_bitsliced_matmul",
                        _interpreted(gf_ops.gf2_bitsliced_matmul))
    monkeypatch.setattr(cdc_ops, "gearhash_pallas",
                        _interpreted(cdc_ops.gearhash_pallas))
    sizes = (96 * KiB, 40 * KiB, 24 * KiB, 8 * KiB, 3 * KiB, 1 * KiB)
    late_sizes = (24 * KiB, 16 * KiB, 8 * KiB, 4 * KiB, 2 * KiB)
    payloads = smoke.make_payloads(sizes, seed=3)
    late = smoke.make_payloads(late_sizes, seed=4, prefix="late")
    cdc_fid = next(iter(payloads))
    report = smoke.run(payloads, late, seed=3, min_block=2 * KiB,
                       avg_block=2 * KiB, max_block=8 * KiB, cdc_fid=cdc_fid)
    rows = {r["phase"]: r for r in report}
    assert list(rows) == ["warm-up", *smoke.STEADY, "check"]
    assert all(rows[p]["compiles"] == 0 for p in smoke.STEADY)
    assert rows["warm-up"]["compiles"] > 0
    assert rows["write"]["bytes_written"] == sum(sizes)
    assert rows["degraded-write"]["bytes_written"] == sum(late_sizes)
    for p in ("read", "degraded"):
        assert rows[p]["bytes_read"] == sum(sizes) and rows[p]["identical"]
    for p in ("degraded-mixed", "reread"):
        assert rows[p]["bytes_read"] == sum(sizes) + sum(late_sizes)
        assert rows[p]["identical"]
    assert rows["degraded"]["down"] == ["s0", "s10"]
    assert rows["degraded-mixed"]["down"] == ["s1", "s10"]
    assert rows["degraded"]["gf256_matmul"] > 0  # the decode ran on the kernel
    assert rows["degraded"]["gf256_fused"] == 0  # one survivor set
    assert rows["degraded-mixed"]["gf256_fused"] > 0  # two, in one launch
    assert rows["write"]["cdc_gearhash"] == len(sizes)
    assert rows["repair"]["fragments_rebuilt"] > 0
    assert rows["repair"]["gf256_matmul"] > 0  # one block at a time, on the kernel
    # the counts come from the tracer, which is off again after the run
    assert not tracing.enabled()
