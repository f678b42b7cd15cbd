"""Pure-jnp oracle for the fused streaming KNN top-K kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import f32_precision


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def knn_topk_ref(
    queries: jnp.ndarray,     # (Q, D)
    candidates: jnp.ndarray,  # (C, D)
    query_ids: jnp.ndarray,   # (Q,) i32
    cand_ids: jnp.ndarray,    # (C,) i32, −1 = invalid
    *,
    k: int,
    metric: str = "l2",
):
    """Exact K nearest candidates per query: (dists (Q,k) f32 ascending,
    ids (Q,k) i32, −1 where fewer than k valid candidates exist).
    ``metric="ip"`` scores are the negated inner product −q·c (may be
    negative); the default is squared L2."""
    q = queries.astype(jnp.float32)
    c = candidates.astype(jnp.float32)
    if metric == "ip":
        d = -jnp.matmul(q, c.T, precision=f32_precision(q.dtype))
    else:
        diff = q[:, None, :] - c[None, :, :]
        d = jnp.sum(diff * diff, axis=-1)
    invalid = (cand_ids[None, :] < 0) | (query_ids[:, None] == cand_ids[None, :])
    d = jnp.where(invalid, jnp.inf, d)
    neg, idx = jax.lax.top_k(-d, k)
    dk = -neg
    ids = jnp.where(jnp.isinf(dk), -1, cand_ids[idx])
    return dk, ids


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk_ref(dists: jnp.ndarray, ids: jnp.ndarray, *, k: int):
    """Reduce (R, Q, k) partial top-Ks over axis 0 -> exact (Q, k)."""
    r, q, kk = dists.shape
    flat_d = jnp.moveaxis(dists, 0, 1).reshape(q, r * kk)
    flat_i = jnp.moveaxis(ids, 0, 1).reshape(q, r * kk)
    neg, pos = jax.lax.top_k(-flat_d, k)
    return -neg, jnp.take_along_axis(flat_i, pos, axis=1)
