"""Seeded point clouds for the benchmark's deployments, made on the device.

A copy, kept with the benchmark, of the synthetic analogue of the paper's
datasets (dense Gaussian clusters, a uniform background, low-variance tail
dims, rows shuffled): the same parameters, drawn with JAX's generator in
one jitted call instead of NumPy's on the host.  The cloud is built
transposed, (dims, rows), so the device does not pad 18 or 32 features
to 128 lanes, and handed to the host row-major.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A NumPy generator for any whole-number seed, 64-bit and negative
    ones included, kept apart per ``salt``."""
    return np.random.default_rng([seed % (1 << 64), *salt])


def jax_key(seed: int, salt: int) -> jax.Array:
    """A JAX key drawn from ``seed``: two 32-bit words, so seeds past
    2**31 do not overflow the key's integer."""
    hi, lo = seed_rng(seed, salt).integers(0, 1 << 32, 2, dtype=np.uint64)
    return jax.random.wrap_key_data(
        jnp.asarray([hi, lo], dtype=jnp.uint32), impl="threefry2x32")


@functools.partial(jax.jit, static_argnames=("n", "n_cl"))
def _cloud_t(key, centers_t, bounds, scale, sigma, *, n: int, n_cl: int):
    d = centers_t.shape[0]
    k_cl, k_bg, k_perm = jax.random.split(key, 3)
    label = jnp.searchsorted(bounds, jnp.arange(n_cl), side="right")
    clustered = centers_t[:, label] + sigma * jax.random.normal(
        k_cl, (d, n_cl), jnp.float32)
    background = jax.random.uniform(k_bg, (d, n - n_cl), jnp.float32)
    pts = jnp.concatenate([clustered, background], axis=1) * scale[:, None]
    return pts[:, jax.random.permutation(k_perm, n)]


def make_cloud(gen: dict, n: int, d: int, seed: int):
    """(n, d) float32 cloud with the generator parameters ``gen``
    (``n_clusters``, ``cluster_frac``, ``cluster_sigma``,
    ``intrinsic_dims``), the same for the same ``seed``, and its per-dim
    scale (1, or 0.02 on the low-variance tail dims)."""
    rng = seed_rng(seed, 0)
    n_clusters = int(gen["n_clusters"])
    n_cl = int(n * gen["cluster_frac"])
    centers = rng.uniform(0.15, 0.85, (n_clusters, d))
    # Exponential cluster sizes: a few very dense cores, many small ones.
    sizes = rng.exponential(1.0, n_clusters)
    sizes = np.maximum((sizes / sizes.sum() * n_cl).astype(np.int64), 1)
    sizes[-1] = max(sizes[-1] + n_cl - sizes.sum(), 1)
    bounds = np.cumsum(sizes)[:-1]
    scale = np.ones(d)
    if gen["intrinsic_dims"] < d:
        scale[rng.permutation(d)[gen["intrinsic_dims"]:]] = 0.02
    pts_t = _cloud_t(
        jax_key(seed, 1), jnp.asarray(centers.T, jnp.float32),
        jnp.asarray(bounds, jnp.int32), jnp.asarray(scale, jnp.float32),
        jnp.float32(gen["cluster_sigma"]), n=n, n_cl=n_cl)
    return np.ascontiguousarray(np.asarray(pts_t).T), scale.astype(np.float32)
