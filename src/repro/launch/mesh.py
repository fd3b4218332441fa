"""Production mesh construction (multi-pod dry-run spec).

A function — not a module-level constant — so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def _auto(n: int) -> tuple:
    """Auto axis types: ``jax.make_mesh`` defaults to Explicit axes, under
    which the model's sharding annotations (``pshard.logical``) and the
    embedding gather raise instead of letting GSPMD propagate."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_small_mesh(devices: int = 8, model: int = 2):
    """CPU-test mesh (requires XLA_FLAGS host device count >= devices)."""
    return jax.make_mesh((devices // model, model), ("data", "model"),
                         axis_types=_auto(2))


def make_replica_mesh(devices, tp: int, pp: int = 1):
    """Mesh for ONE serving replica: shape (pp, tp), axes ("pipe", "model").

    ``devices`` is this replica's slice of the device set (len == tp * pp);
    the cluster runtime carves ``jax.devices()`` into per-replica slices so
    heterogeneous deployments place each replica on its own sub-mesh.
    Tensor parallelism shards heads / d_ff / vocab over ``model`` (the
    serving ``ShardingPlan`` rules); pipeline parallelism shards the
    layer-stacked parameter (and paged-pool) leading axis over ``pipe``.
    """
    import numpy as np

    devices = list(devices)
    if len(devices) != tp * pp:
        raise ValueError(
            f"replica mesh needs tp*pp={tp * pp} devices, got {len(devices)}")
    return jax.sharding.Mesh(
        np.asarray(devices, dtype=object).reshape(pp, tp), ("pipe", "model"))
