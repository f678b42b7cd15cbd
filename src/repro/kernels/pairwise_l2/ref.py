"""Pure-jnp oracle for the pairwise squared-L2 kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import f32_precision


@jax.jit
def pairwise_sq_l2_ref(queries: jnp.ndarray, candidates: jnp.ndarray) -> jnp.ndarray:
    """(Q, D) × (C, D) -> (Q, C) squared L2, float32, numerically direct
    (difference-then-square — the stable form the kernel is tested against)."""
    q = queries.astype(jnp.float32)
    c = candidates.astype(jnp.float32)
    diff = q[:, None, :] - c[None, :, :]
    return jnp.sum(diff * diff, axis=-1)


@jax.jit
def pairwise_neg_ip_ref(queries: jnp.ndarray, candidates: jnp.ndarray) -> jnp.ndarray:
    """(Q, D) × (C, D) -> (Q, C) negated inner product −q·c, float32
    (ascending = best-first, matching the L2 score convention)."""
    q = queries.astype(jnp.float32)
    c = candidates.astype(jnp.float32)
    return -jnp.matmul(q, c.T, precision=f32_precision(q.dtype))
