"""Production mesh construction.

Deliberately a FUNCTION (no module-level jax device access) so importing
this module never locks jax's device count — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax init.

Every mesh is built with Auto (GSPMD) axes: the round functions vmap over
'data'-sharded batches with replicated keys and leave the partitioning to
the compiler, which Explicit axes (jax.make_mesh's default) reject.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: 'pod' (slow inter-pod links) × 'data' (client/batch parallelism +
    FSDP) × 'model' (tensor parallelism).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """Arbitrary Auto-axis mesh (tests use small CPU meshes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
