"""jit'd public wrapper for the pairwise-L2 kernel: padding + dispatch
(``repro.kernels.use_pallas`` policy)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_pallas
from repro.utils import round_up
from repro.kernels.pairwise_l2 import kernel as _kernel
from repro.kernels.pairwise_l2 import ref as _ref


def pairwise_sq_l2(
    queries: jnp.ndarray,
    candidates: jnp.ndarray,
    *,
    block_q: int = 128,
    block_c: int = 128,
    block_d: int = 128,
    shortc_eps2=None,
    metric: str = "l2",
    mode: str = "auto",
) -> jnp.ndarray:
    """Squared L2 distances (Q, C) float32 for arbitrary (unpadded) shapes
    (negated inner product −q·c under ``metric="ip"``).

    Padded query/candidate rows never reach the caller (sliced off); padded
    feature columns are zero so they contribute nothing to distances.

    ``shortc_eps2`` may be a Python float (baked into the kernel as a
    compile-time constant) or a traced jax scalar (passed as a runtime
    operand, so ε sweeps reuse one executable).  SHORTC is L2-only —
    partial ip sums are not monotone, so ``metric="ip"`` requires
    ``shortc_eps2=None``.  This outer function is a trace-time
    dispatcher; the per-path workers below carry the jit caches.
    """
    if metric == "ip":
        if shortc_eps2 is not None:
            raise ValueError(
                "pairwise_sq_l2(metric='ip') cannot take shortc_eps2: "
                "the SHORTC cutoff assumes monotone partial distances "
                "(L2 only) — pass shortc_eps2=None"
            )
        return _pairwise_static(
            queries, candidates, block_q=block_q, block_c=block_c,
            block_d=block_d, shortc_eps2=None, metric="ip", mode=mode,
        )
    if shortc_eps2 is None or isinstance(shortc_eps2, (int, float)):
        return _pairwise_static(
            queries, candidates, block_q=block_q, block_c=block_c,
            block_d=block_d, shortc_eps2=shortc_eps2, metric="l2", mode=mode,
        )
    return _pairwise_dynamic(
        queries, candidates, shortc_eps2, block_q=block_q, block_c=block_c,
        block_d=block_d, mode=mode,
    )


def _pad_operands(queries, candidates, block_q, block_c, block_d):
    """Zero-pad to tile multiples.  Rows of at most ``block_d`` features
    stay one unpadded chunk (a block equal to the array's last dim needs
    no lane padding, and the L2 kernel loops over every column).
    Returns (q, c, the feature chunk)."""
    q_n, d = queries.shape
    c_n, _ = candidates.shape
    block_d = min(block_d, max(d, 1))
    qp = round_up(max(q_n, 1), block_q)
    cp = round_up(max(c_n, 1), block_c)
    dp = round_up(max(d, 1), block_d)
    q = jnp.zeros((qp, dp), queries.dtype).at[:q_n, :d].set(queries)
    c = jnp.zeros((cp, dp), candidates.dtype).at[:c_n, :d].set(candidates)
    return q, c, block_d


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_c", "block_d", "shortc_eps2",
                     "metric", "mode"),
)
def _pairwise_static(
    queries, candidates, *, block_q, block_c, block_d, shortc_eps2,
    metric="l2", mode,
):
    q_n, _ = queries.shape
    c_n, _ = candidates.shape
    if not use_pallas(mode):
        if metric == "ip":
            return _ref.pairwise_neg_ip_ref(queries, candidates)
        return _ref.pairwise_sq_l2_ref(queries, candidates)
    q, c, block_d = _pad_operands(queries, candidates, block_q, block_c,
                                  block_d)
    out = _kernel.pairwise_sq_l2(
        q, c,
        block_q=block_q, block_c=block_c, block_d=block_d,
        shortc_eps2=shortc_eps2, metric=metric,
        interpret=(mode == "interpret"),
    )
    return out[:q_n, :c_n]


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_c", "block_d", "mode")
)
def _pairwise_dynamic(
    queries, candidates, shortc_eps2, *, block_q, block_c, block_d, mode,
):
    q_n, _ = queries.shape
    c_n, _ = candidates.shape
    if not use_pallas(mode):
        # The ref oracle computes exact distances; SHORTC only ever clamps
        # values already above the cutoff, so exact is a valid refinement.
        return _ref.pairwise_sq_l2_ref(queries, candidates)
    q, c, block_d = _pad_operands(queries, candidates, block_q, block_c,
                                  block_d)
    out = _kernel.pairwise_sq_l2_dyn_shortc(
        q, c, shortc_eps2,
        block_q=block_q, block_c=block_c, block_d=block_d,
        interpret=(mode == "interpret"),
    )
    return out[:q_n, :c_n]
