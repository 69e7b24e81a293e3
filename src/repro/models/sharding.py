"""Mesh context + best-effort sharding plans.

Production meshes (DESIGN.md §5): single-pod (data=16, model=16) and
multi-pod (pod=2, data=16, model=16). Logical axes:

  batch  -> ("pod", "data") or ("data",)     activations' batch dim
  seq    -> the batch axes, used instead of batch when global_batch is too
            small to fill them (long_500k: batch=1 -> shard sequence)
  model  -> "model"                           TP/EP axis

Dims not divisible by the model-axis size are handled by *axis fallback*
(shard a different dim that is divisible) rather than XLA padding wherever
possible; the chosen plan is recorded for the dry-run report.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass
class MeshCtx:
    mesh: Mesh
    notes: list = field(default_factory=list)

    @property
    def has_pod(self) -> bool:
        return "pod" in self.mesh.axis_names

    @property
    def batch_axes(self) -> tuple[str, ...]:
        return ("pod", "data") if self.has_pod else ("data",)

    @property
    def n_batch(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    @property
    def n_model(self) -> int:
        return int(self.mesh.shape["model"])

    # ----------------------------------------------------------- specs
    def ns(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def token_spec(self, global_batch: int, extra_dims: int = 0) -> tuple:
        """(B, S, ...) activation spec: shard batch if it fills the batch
        axes, otherwise shard the sequence dim (context/sequence parallel)."""
        if global_batch >= self.n_batch and global_batch % self.n_batch == 0:
            return (self.batch_axes, None) + (None,) * extra_dims
        return (None, self.batch_axes) + (None,) * extra_dims

    def constrain(self, x, *spec):
        return jax.lax.with_sharding_constraint(x, self.ns(*spec))

    def model_dim_choice(self, *dim_sizes: int) -> int:
        """Index of the first dim divisible by the model axis, else -1."""
        for i, d in enumerate(dim_sizes):
            if d % self.n_model == 0:
                return i
        return -1


def shard_map_compat(f, *, mesh, in_specs, out_specs, **kw):
    """``jax.shard_map`` with the mesh and specs passed by keyword."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kw)


def spec_with_model_on(shape: tuple[int, ...], ctx: MeshCtx, candidates: list[int]) -> tuple:
    """Build a spec placing "model" on the first candidate dim divisible by
    the model-axis size (fallback: replicated)."""
    spec: list = [None] * len(shape)
    for dim in candidates:
        if shape[dim] % ctx.n_model == 0:
            spec[dim] = "model"
            return tuple(spec)
    return tuple(spec)
