"""Production mesh construction (function, not module-level constant — meshes
must never touch jax device state at import time)."""
from __future__ import annotations

import jax

# ``MeshCtx.constrain`` uses ``with_sharding_constraint``, which only accepts
# Auto axes; ``jax.make_mesh`` defaults to Explicit ones.
AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e pod). Multi-pod:
    (pod=2, data=16, model=16) = 512 chips; "pod" is the outermost
    data-parallel axis (gradients reduce hierarchically: in-pod ICI first,
    then cross-pod DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AUTO,) * len(axes))


def make_host_mesh():
    """1-device mesh for CPU smoke usage of mesh-parameterized code paths."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(AUTO,) * 2)
