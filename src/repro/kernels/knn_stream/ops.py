"""Public wrapper for the fused streaming distance+top-K engine:
padding + dispatch (same mode policy as the other kernel packages).

Unlike ``knn_topk.ops`` there is no post-kernel merge pass: the kernel
carries the running top-K across candidate sub-blocks in VMEM scratch,
so the kernel outputs ARE the final (Q, k) results.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from repro.kernels import use_pallas
from repro.utils import round_up
from repro.kernels.knn_stream import kernel as _kernel
from repro.kernels.knn_stream import ref as _ref
from repro.kernels.knn_topk.ops import warn_oversized_k

_log = logging.getLogger(__name__)


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_c", "mode", "metric")
)
def knn_stream_topk(
    queries: jnp.ndarray,      # (Q, D)
    candidates: jnp.ndarray,   # (C, D)
    query_ids: jnp.ndarray,    # (Q,) i32
    cand_ids: jnp.ndarray,     # (C,) i32, −1 = invalid row
    eps2: jnp.ndarray,         # () f32 — traced ε² (runtime operand)
    *,
    k: int,
    block_q: int = 128,
    block_c: int = 128,
    mode: str = "auto",
    metric: str = "l2",
):
    """One-pass ε-filtered top-K over arbitrary (unpadded) shapes.

    Returns (dists (Q, k) ascending inf-padded, ids (Q, k) −1-padded,
    found (Q,) i32 — in-range candidates, self/invalid excluded).

    Oversized K falls back to the ref oracle, mirroring
    ``knn_topk.ops`` (the unrolled merge network stops paying for
    itself past ``MAX_UNROLLED_K``); the first such reroute per process
    logs a warning so the cliff is visible."""
    if not use_pallas(mode) or k > _kernel.MAX_UNROLLED_K:
        if use_pallas(mode):
            warn_oversized_k(_log, "knn_stream", k,
                             "the materialize-then-sort ref oracle")
        return _ref.knn_stream_topk_ref(
            queries, candidates, query_ids, cand_ids, eps2, k=k, metric=metric
        )

    q_n, dim = queries.shape
    c_n, _ = candidates.shape
    qp = round_up(max(q_n, 1), block_q)
    cp = round_up(max(c_n, 1), block_c)
    q = jnp.zeros((qp, dim), queries.dtype).at[:q_n].set(queries)
    c = jnp.zeros((cp, dim), candidates.dtype).at[:c_n].set(candidates)
    qid = jnp.full((qp,), -1, jnp.int32).at[:q_n].set(query_ids.astype(jnp.int32))
    cid = jnp.full((cp,), -1, jnp.int32).at[:c_n].set(cand_ids.astype(jnp.int32))

    kd, ki, found = _kernel.knn_stream_topk_padded(
        q, c, qid, cid, eps2, k=k, block_q=block_q, block_c=block_c,
        metric=metric, interpret=(mode == "interpret"),
    )
    return kd[:q_n], ki[:q_n], found[:q_n]


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_c", "mode", "metric")
)
def knn_stream_topk_prefetch(
    queries: jnp.ndarray,      # (T·block_q, D)
    corpus: jnp.ndarray,       # (C, D), C % block_c == 0
    block_table: jnp.ndarray,  # (T, nblk) i32 — scalar-prefetch DMA schedule
    query_ids: jnp.ndarray,    # (T·block_q,) i32 exclusion ids
    cand_ids: jnp.ndarray,     # (T, nblk·block_c) i32, −1 = masked row
    eps2: jnp.ndarray,         # () f32
    *,
    k: int,
    block_q: int = 128,
    block_c: int = 128,
    mode: str = "auto",
    metric: str = "l2",
):
    """Dispatch for the scalar-prefetch streaming kernel (operands are
    pre-padded by the dense engine — the block table fixes the shapes).

    ``"ref"`` mode materializes the same block-aligned candidate operand
    by an explicit gather (the oracle); oversized k raises — callers
    route oversized k through the gathered path instead, where the
    budget-shaped operand the oracle needs already exists."""
    if not use_pallas(mode):
        return _ref.knn_stream_topk_prefetch_ref(
            queries, corpus, block_table, query_ids, cand_ids, eps2,
            k=k, block_q=block_q, block_c=block_c, metric=metric,
        )
    return _kernel.knn_stream_topk_prefetch(
        queries, corpus, block_table, query_ids, cand_ids, eps2,
        k=k, block_q=block_q, block_c=block_c, metric=metric,
        interpret=(mode == "interpret"),
    )
