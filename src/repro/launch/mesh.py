"""Production mesh construction.

Every mesh the program builds comes from `make_mesh` here: its axes
are `AxisType.Auto`, the sharding mode the model code is written for
(`jax.make_mesh` defaults to `Explicit` axes, under which the
embedding gather refuses to infer its output sharding).

Functions, not module-level constants — importing this module never
touches jax device state (required so smoke tests see 1 CPU device
while the dry-run sees 512 forced host devices).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

from repro.configs.base import MULTI_POD_MESH, SINGLE_POD_MESH, MeshConfig


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """`jax.make_mesh` with `Auto` axes, over `devices` (default: all
    local devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    return make_mesh_from_config(mesh_config(multi_pod=multi_pod))


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD_MESH if multi_pod else SINGLE_POD_MESH


def make_mesh_from_config(cfg: MeshConfig):
    return make_mesh(cfg.shape, cfg.axes)


def make_hybrid_mesh(plan_or_factorization):
    """3-axis (data, model, pipe) mesh for a HybridPlan / Factorization.

    Accepts a `core.hybrid.HybridPlan`, a `core.hybrid.Factorization`,
    or anything else exposing `.mesh_config()`.
    """
    return make_mesh_from_config(plan_or_factorization.mesh_config())


def make_cluster_mesh(spec, model_parallel: int = 1,
                      pipeline_parallel: int = 1):
    """Mesh whose axis order mirrors a `ClusterSpec`'s hierarchy: one
    axis per (ways > 1) level, outermost first, then `model` (and
    `pipe` when pipelined) — so jax's device order walks the innermost
    (fastest) level fastest and every level-k ZDP axis lands on the
    physical links the cost model priced it against.
    """
    return make_mesh_from_config(spec.mesh_config(
        model_parallel=model_parallel, pipeline_parallel=pipeline_parallel))


def make_host_mesh():
    """1x1 mesh on the real local device (smoke tests / examples)."""
    return make_mesh((1, 1), ("data", "model"))
