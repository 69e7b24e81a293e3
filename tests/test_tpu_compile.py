"""The store's two Pallas kernels compile for a described TPU v5e.

Nothing runs: the TPU compiler that ships with JAX lowers each kernel for a
chip that is described, not attached, and raises what the chip's compiler
would raise (unaligned blocks, VMEM overuse, programs larger than HBM). The
topology is described inside fixtures, never while a module is imported, and
every test skips where it cannot be described. The shapes are the main
path's: the Emulab store's encode (5, 6), decode (6, 6), two-set fused
decode (12, 12) and largest fused decode (128, 128), and the AWS store's
encode (4, 2), over a 1 MiB block and over one slice
(``dispatch.GF_SLICE_COLS`` columns, the widest launch); and the CDC kernel
over a 1 MiB block and over one slice (``dispatch.CDC_SLICE_BYTES``, the
longest launch), alone and with the bytes before it as its head. Both
kernels' programs take and return flat buffers, as the wrappers move them.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels.cdc_gearhash.kernel import LANES, gearhash_pallas
from repro.kernels.gf256_matmul.kernel import _round_up, gf2_bitsliced_matmul

MiB = 1 << 20
SLICE = dispatch.GF_SLICE_COLS
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Programs compiled for a described chip cannot be read back without
    one; keep them out of any persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _fits_hbm(compiled) -> bool:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    return used < HBM_BYTES


@pytest.mark.parametrize(
    "m,k,L",
    [
        (5, 6, 1 * MiB),       # Emulab encode
        (6, 6, 1 * MiB),       # Emulab decode
        (128, 128, 1 * MiB),   # largest block-diagonal fused decode
        (4, 2, 1 * MiB),       # AWS encode
        (5, 6, SLICE),         # Emulab encode window of a 16-512 MiB object
        (6, 6, SLICE),         # Emulab decode window
        (12, 12, SLICE),       # two-set fused decode window
        (4, 2, SLICE),         # AWS encode window of a 4-16 MiB object
    ],
)
def test_gf256_kernel_compiles_for_v5e(m, k, L, one_chip, no_persistent_cache):
    abits = jax.ShapeDtypeStruct(
        (_round_up(8 * m, 8), _round_up(8 * k, 128)), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((k * L,), jnp.uint8, sharding=one_chip)  # flat (k, L)
    compiled = _compile(
        lambda a, x: gf2_bitsliced_matmul(a, x, m=m, k=k, block_l=2048), abits, b)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert compiled.out_info.shape == (m * L,)
    # the flat buffers are bitcast to the kernel's views: no byte relayout
    u8_ops = set(re.findall(r"= u8\[[^\]]*\]\S* ([\w-]+)\(", text))
    assert u8_ops == {"parameter", "bitcast", "custom-call"}
    assert _fits_hbm(compiled)


@pytest.mark.parametrize(
    "L,head",
    [
        (1 * MiB, False),                        # one block
        (dispatch.CDC_SLICE_BYTES, False),       # a 64 MiB object, or a first slice
        (dispatch.CDC_SLICE_BYTES, True),        # a later slice, with its head
    ],
)
def test_cdc_kernel_compiles_for_v5e(L, head, one_chip, no_persistent_cache):
    data = jax.ShapeDtypeStruct((L,), jnp.uint8, sharding=one_chip)
    if head:
        tail = jax.ShapeDtypeStruct((LANES,), jnp.uint8, sharding=one_chip)
        compiled = _compile(lambda d, h: gearhash_pallas(d, h, mask=(1 << 19) - 1), data, tail)
    else:
        compiled = _compile(lambda d: gearhash_pallas(d, mask=(1 << 19) - 1), data)
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits_hbm(compiled)
