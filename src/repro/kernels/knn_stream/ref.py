"""Pure-jnp oracle for the fused streaming distance+top-K engine.

Deliberately materialize-then-sort: the full (Q, C) distance matrix via
the broadcast-subtract formulation (same rounding as the dense engine's
``"ref"`` backend), ε-masked, then one native ``top_k``.  The streaming
kernel must agree with this modulo last-ulp ε²-boundary rounding between
the two distance formulations (DESIGN.md §2.5 boundary caveat).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import f32_precision


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def knn_stream_topk_ref(
    queries: jnp.ndarray,     # (Q, D)
    candidates: jnp.ndarray,  # (C, D)
    query_ids: jnp.ndarray,   # (Q,) i32
    cand_ids: jnp.ndarray,    # (C,) i32, −1 = invalid
    eps2: jnp.ndarray,        # () f32
    *,
    k: int,
    metric: str = "l2",
):
    """ε-filtered exact K nearest candidates per query.

    Returns (dists (Q, k) f32 ascending inf-padded, ids (Q, k) i32
    −1-padded, found (Q,) i32).  ``metric="ip"`` scores are −q·c (pass
    eps2=+inf to disable the score-threshold filter)."""
    q = queries.astype(jnp.float32)
    c = candidates.astype(jnp.float32)
    if metric == "ip":
        d = -jnp.matmul(q, c.T, precision=f32_precision(q.dtype))  # (Q, C)
    else:
        diff = q[:, None, :] - c[None, :, :]
        d = jnp.sum(diff * diff, axis=-1)                      # (Q, C)
    keep = (
        (cand_ids[None, :] >= 0)
        & (query_ids[:, None] != cand_ids[None, :])
        & (d <= eps2)
    )
    dm = jnp.where(keep, d, jnp.inf)
    neg, sel = jax.lax.top_k(-dm, k)
    kd = -neg
    ki = jnp.where(jnp.isinf(kd), -1, cand_ids[sel])
    found = jnp.sum(keep, axis=1).astype(jnp.int32)
    return kd, ki, found


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_c", "metric"))
def knn_stream_topk_prefetch_ref(
    queries: jnp.ndarray,      # (T·block_q, D)
    corpus: jnp.ndarray,       # (C, D), C % block_c == 0
    block_table: jnp.ndarray,  # (T, nblk) i32
    query_ids: jnp.ndarray,    # (T·block_q,) i32 exclusion ids
    cand_ids: jnp.ndarray,     # (T, nblk·block_c) i32, −1 = masked row
    eps2: jnp.ndarray,         # () f32
    *,
    k: int,
    block_q: int = 128,
    block_c: int = 128,
    metric: str = "l2",
):
    """Oracle for the scalar-prefetch kernel: materialize each tile's
    block-aligned candidate operand by an explicit gather — the exact
    data movement the prefetch kernel's index maps perform via DMA — and
    run the materialize-then-sort oracle per tile."""
    n_tiles, nblk = block_table.shape
    dim = queries.shape[1]
    q_t = queries.astype(jnp.float32).reshape(n_tiles, block_q, dim)
    qid_t = query_ids.reshape(n_tiles, block_q)

    def one(args):
        q, qid, blk, cid = args
        rows = blk[:, None] * block_c + jnp.arange(block_c, dtype=jnp.int32)
        cand = corpus[rows.reshape(-1)].astype(jnp.float32)    # (nblk·bc, D)
        return knn_stream_topk_ref(q, cand, qid, cid, eps2, k=k, metric=metric)

    kd, ki, found = jax.lax.map(one, (q_t, qid_t, block_table, cand_ids))
    return (kd.reshape(-1, k), ki.reshape(-1, k), found.reshape(-1))
