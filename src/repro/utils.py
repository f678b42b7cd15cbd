"""Small shared utilities: padding, pytree dataclasses."""
from __future__ import annotations

import dataclasses
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

INT32_SENTINEL = np.int32(2**31 - 1)  # padding value for sorted id arrays
FLOAT_INF = jnp.inf

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    honours it and nothing changes; otherwise the cache goes to the
    fixed ``<repo>/.jax_cache`` — a fixed path, since the path is part
    of what a cache hit matches.  Entry points (``chip_smoke.py``,
    ``benchmarks/run.py``, ``examples/``) call this first; tests do
    not."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_host_devices(n: int) -> None:
    """Give XLA's CPU backend ``n`` virtual devices (the dry-run tools'
    fake production mesh).  Appends to ``XLA_FLAGS``, keeps a device
    count already there, and acts only when ``JAX_PLATFORMS`` is
    ``cpu``.  Call it before JAX initializes a backend."""
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def pytree_dataclass(cls):
    """Register a (frozen is fine) dataclass as a JAX pytree.

    Fields whose declared type is marked ``static`` via ``metadata={'static': True}``
    are treated as auxiliary (hashable, not traced).
    """
    cls = dataclasses.dataclass(cls)
    fields = dataclasses.fields(cls)
    dyn = [f.name for f in fields if not f.metadata.get("static", False)]
    sta = [f.name for f in fields if f.metadata.get("static", False)]

    def flatten(obj):
        return tuple(getattr(obj, n) for n in dyn), tuple(getattr(obj, n) for n in sta)

    def unflatten(aux, children):
        kwargs = dict(zip(dyn, children))
        kwargs.update(dict(zip(sta, aux)))
        return cls(**kwargs)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


def static_field(**kw):
    return dataclasses.field(metadata={"static": True}, **kw)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def pow2_bucket(n: int, block: int) -> int:
    """Smallest pow2 multiple of ``block`` that holds ``n`` rows — THE
    shape bucket for engine-cache keys (query-id vectors and foreign
    query arrays must round identically, or the zero-compile
    steady-state guarantee silently breaks)."""
    n = max(int(n), 1)
    target = block
    while target < n:
        target *= 2
    return round_up(target, block)


def pad_to(x: jnp.ndarray, size: int, axis: int = 0, value=0):
    """Pad ``x`` along ``axis`` up to ``size`` with ``value``."""
    cur = x.shape[axis]
    if cur == size:
        return x
    if cur > size:
        raise ValueError(f"cannot pad axis of size {cur} down to {size}")
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, size - cur)
    return jnp.pad(x, widths, constant_values=value)


def tree_bytes(tree) -> int:
    """Total bytes of all array leaves (works on ShapeDtypeStruct too)."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def human_bytes(n: float) -> str:
    for unit in ["B", "KiB", "MiB", "GiB", "TiB"]:
        if abs(n) < 1024:
            return f"{n:.2f}{unit}"
        n /= 1024
    return f"{n:.2f}PiB"
