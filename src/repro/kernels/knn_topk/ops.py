"""Public wrapper for the fused streaming KNN top-K: padding, dispatch, and
the log-depth merge that finishes the per-tile partial top-Ks."""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from repro.kernels import use_pallas
from repro.utils import round_up
from repro.kernels.knn_topk import kernel as _kernel
from repro.kernels.knn_topk import ref as _ref

_log = logging.getLogger(__name__)

# Engines whose oversized-k reroute has been logged in this process.
_oversized_k_warned: set = set()


def warn_oversized_k(log: logging.Logger, engine: str, k: int,
                     fallback: str) -> None:
    """Log, once per ``engine`` per process, that ``k`` above
    ``MAX_UNROLLED_K`` rerouted a kernel call to its jnp ``fallback``.
    The reroute is an exact but silent cliff, so it is worth one loud
    line — and only one: it fires per jit trace, and a k sweep would
    otherwise log a line per shape."""
    if engine in _oversized_k_warned:
        return
    _oversized_k_warned.add(engine)
    log.warning(
        "%s: k=%d exceeds MAX_UNROLLED_K=%d — routing to %s (exact, but "
        "not the Pallas kernel; further oversized-k traces fall back "
        "silently)", engine, k, _kernel.MAX_UNROLLED_K, fallback,
    )


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_c", "mode", "metric")
)
def knn_topk(
    queries: jnp.ndarray,      # (Q, D)
    candidates: jnp.ndarray,   # (C, D)
    query_ids: jnp.ndarray,    # (Q,) i32
    cand_ids: jnp.ndarray,     # (C,) i32, −1 = invalid row
    *,
    k: int,
    block_q: int = 128,
    block_c: int = 256,
    mode: str = "auto",
    metric: str = "l2",
):
    """Exact K nearest candidates per query (self/invalid excluded).

    Returns (dists (Q, k) f32 ascending — squared L2, or −q·c under
    ``metric="ip"`` — and ids (Q, k) i32, −1 where fewer than k
    candidates exist)."""
    # Oversized K: the kernel's unrolled min-pass extraction stops paying
    # for itself (see kernel.MAX_UNROLLED_K) — take the ref merge path.
    if not use_pallas(mode) or k > _kernel.MAX_UNROLLED_K:
        if use_pallas(mode):
            warn_oversized_k(_log, "knn_topk", k,
                             "the full-tile jnp reference merge")
        return _ref.knn_topk_ref(queries, candidates, query_ids, cand_ids,
                                 k=k, metric=metric)

    q_n, d = queries.shape
    c_n, _ = candidates.shape
    qp = round_up(max(q_n, 1), block_q)
    cp = round_up(max(c_n, 1), block_c)
    q = jnp.zeros((qp, d), queries.dtype).at[:q_n].set(queries)
    c = jnp.zeros((cp, d), candidates.dtype).at[:c_n].set(candidates)
    qid = jnp.full((qp,), -1, jnp.int32).at[:q_n].set(query_ids.astype(jnp.int32))
    cid = jnp.full((cp,), -1, jnp.int32).at[:c_n].set(cand_ids.astype(jnp.int32))

    pd, pi = _kernel.knn_tile_topk(
        q, c, qid, cid, k=k, block_q=block_q, block_c=block_c,
        metric=metric, interpret=(mode == "interpret"),
    )                                                   # (nC, Qp, k) each
    dists, ids = _ref.merge_topk_ref(pd, pi, k=k)
    return dists[:q_n], ids[:q_n]


@functools.partial(jax.jit, static_argnames=("k",))
def merge_running_topk(
    run_d: jnp.ndarray, run_i: jnp.ndarray,
    new_d: jnp.ndarray, new_i: jnp.ndarray, *, k: int,
):
    """Merge two (Q, k) top-K buffers into one (used by the ring join —
    each ppermute step merges the incoming shard's local top-K)."""
    d = jnp.concatenate([run_d, new_d], axis=1)
    i = jnp.concatenate([run_i, new_i], axis=1)
    neg, pos = jax.lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(i, pos, axis=1)
