"""Production mesh construction.

Single pod: 16 x 16 = 256 chips (data x model).
Multi-pod:  2 x 16 x 16 = 512 chips (pod x data x model); the `pod` axis is
the slow (DCN/ICI-inter-pod) dimension — params replicate across it and the
gradient all-reduce over it is where compression applies.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """Mesh whose axes are all ``Auto``: the model code places activations
    and gradients with ``with_sharding_constraint``, which only accepts Auto
    axes (``jax.make_mesh`` defaults to Explicit ones)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1, devices=None):
    """Small mesh over ``devices`` (default: every device of the process)."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    mp = max(1, min(model_parallel, n))
    return _auto_mesh((n // mp, mp), ("data", "model"), devices)


def shard_leading_axis(tree, mesh, axis: str = "data"):
    """Shard every leaf of a pytree along its leading axis over one mesh axis.

    Used by the fleet engine to spread the K-slice batch axis of stacked
    ``SliceParams`` / ``SchedulerState`` pytrees across devices
    (``NamedSharding(mesh, P(axis, None, ...))``); all trailing axes stay
    replicated. K must be divisible by the mesh axis size.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    def put(leaf):
        spec = PartitionSpec(axis, *([None] * (leaf.ndim - 1)))
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(put, tree)
