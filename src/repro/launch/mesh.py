"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and everything else (smoke tests, benches) must keep seeing 1 CPU
device.

Axis semantics:
  pod    — inter-pod data parallelism (DCN-ish; gradients cross it once)
  data   — intra-pod data parallelism + FSDP shard axis
  model  — TP / EP / SP axis (ICI all-to-all-heavy)
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto(n_axes: int) -> tuple:
    return (AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model: int = 1) -> Mesh:
    """Tiny mesh over however many (fake or real) local devices exist —
    used by tests (8 host devices) and CPU examples (1 device)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"), axis_types=_auto(2))


def make_serving_mesh(n_shards: int | None = None, axis: str = "shard",
                      replicas: int = 1) -> Mesh:
    """Mesh for the sharded ``KNNIndex`` (DESIGN.md §5/§7).

    ``replicas == 1`` (default) keeps the original 1-D shape:
    ``n_shards`` devices along ``axis``.  ``replicas > 1`` builds the
    2-D (replica × shard) serving mesh: shard groups for corpus
    capacity, replica groups for QPS/fault tolerance — index state is
    sharded along ``axis`` and *replicated* along ``"replica"`` (the
    collective top-K merge stays confined to the shard axis; query
    routing spreads across replicas).  On a CPU host, fake devices come
    from ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set
    before the first jax import."""
    devs = jax.devices()
    r = int(replicas)
    if r < 1:
        raise ValueError(f"replicas must be >= 1, got {r}")
    n = (len(devs) // r) if n_shards is None else int(n_shards)
    if r * n > len(devs):
        raise ValueError(
            f"serving mesh wants {r}x{n}={r * n} devices but only "
            f"{len(devs)} exist "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count "
            "before the first jax import to fake more on CPU)"
        )
    if r == 1:
        return jax.make_mesh((n,), (axis,), axis_types=_auto(1))
    return jax.make_mesh((r, n), ("replica", axis), axis_types=_auto(2))


def mesh_chip_count(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
