"""Production mesh construction (multi-pod dry-run spec).

``make_production_mesh`` is a FUNCTION (not module state) so importing
this module never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax use.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (16, 16) ("data", "model") = 256 chips.
    Multi-pod: (2, 16, 16) ("pod", "data", "model") = 512 chips; the pod
    axis is an outer data axis (batch shards over pod x data, gradient
    all-reduce crosses pods once per step)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = data * model
    devs = jax.devices()[:n]
    assert len(devs) == n, f"need {n} devices, have {len(jax.devices())}"
    return jax.sharding.Mesh(
        __import__("numpy").array(devs).reshape(data, model),
        ("data", "model"))


def batch_axes_for(mesh: jax.sharding.Mesh, batch: int
                   ) -> Optional[Tuple[str, ...]]:
    """Largest prefix of (pod, data) that divides ``batch``; None if no
    non-empty prefix divides (then the batch stays replicated)."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    # Longest dividing prefix first: (pod, data), then (pod,) / (data,)
    # for a batch divisible by the outer axis but not the full product.
    for end in range(len(axes), 0, -1):
        prefix = axes[:end]
        size = 1
        for a in prefix:
            size *= mesh.shape[a]
        if batch % size == 0:
            return tuple(prefix)
    return None
