"""Pallas TPU kernel: fused streaming distance + tile-local top-K.

This is the beyond-paper optimization that replaces the paper's batching
scheme (§IV-B) on TPU (DESIGN.md §2.3): instead of materializing an
unbounded range-query result set in HBM (which forced the paper into a
result-size estimator + n_b staged batches), each (query tile × candidate
tile) step computes the distance tile in VMEM (``sq_l2_direct``; the MXU
for inner products) and immediately reduces it
to the tile's K smallest (distance, index) pairs in VMEM.  HBM traffic
drops from O(Q·C) to O(Q·(C/TC)·K), and a log-depth top-K reduction in
ops.py finishes the job — memory is statically bounded, no failure/restart.

The K-smallest extraction is K passes of (min, first-argmin-via-min-iota,
one-hot mask) — branch-free, VPU-friendly, no unsupported sort/topk
primitives inside the kernel.  ``topk_min_passes`` is shared with the
streaming kernel (``knn_stream``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import f32_precision

_INF = np.float32(np.inf)

# ``topk_min_passes`` unrolls K serial min-passes into straight-line kernel
# code.
# Past this ceiling the unrolled loop stops being a win: compile time and
# kernel size grow linearly while the per-pass VPU reductions dominate the
# MXU matmul they amortize.  ``ops.knn_topk`` falls back to the jnp ref
# (full distance tile + native top_k merge) instead of silently compiling
# a huge kernel; calling the kernel directly with k above the ceiling is a
# usage error.
MAX_UNROLLED_K = 32


def topk_min_passes(d: jnp.ndarray, ids: jnp.ndarray, k: int):
    """K smallest per row of ``d`` (TQ, M) with their ``ids`` (TQ, M):
    K passes of (min, first-argmin-via-min-iota, one-hot knockout).  Ids
    are gathered by one-hot sum — branch-free, and Mosaic lowers it
    (it has no lowering for ``take_along_axis`` inside a kernel)."""
    tq, m = d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, m), 1)
    vals, outs = [], []
    for _ in range(k):
        mn = jnp.min(d, axis=1)                                    # (TQ,)
        amn = jnp.min(jnp.where(d == mn[:, None], col, m), axis=1)
        hit = col == amn[:, None]
        vals.append(mn)
        outs.append(jnp.sum(jnp.where(hit, ids, 0), axis=1).astype(jnp.int32))
        d = jnp.where(hit, _INF, d)
    return jnp.stack(vals, axis=1), jnp.stack(outs, axis=1)


def sq_l2_direct(q: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """(TQ, D) × (TC, D) f32 -> (TQ, TC) squared L2 from direct
    differences, one VPU pass per dimension.  The MXU expansion
    ‖q‖² + ‖c‖² − 2·q·c cancels away f32 digits: its absolute error is
    ~1e-7·‖x‖², which on unit-scale data is ~1e-3 relative on a close
    neighbor's distance — too coarse for an exact index.  Differences
    keep every distance within f32 rounding of the true one."""
    ct = c.T                                                        # (D, TC)
    d = jnp.zeros((q.shape[0], ct.shape[1]), jnp.float32)
    for j in range(q.shape[1]):
        diff = q[:, j:j + 1] - ct[j:j + 1, :]
        d = d + diff * diff
    return d


def _knn_topk_kernel(q_ref, c_ref, qid_ref, cid_ref, outd_ref, outi_ref,
                     *, k: int, metric: str):
    q = q_ref[...].astype(jnp.float32)                              # (TQ, D)
    c = c_ref[...].astype(jnp.float32)                              # (TC, D)
    if metric == "ip":
        # Negated inner product on the MXU: no cancellation, and no
        # max-0 clamp — ip scores are legitimately negative.
        d = -jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())),
            precision=f32_precision(q.dtype),
            preferred_element_type=jnp.float32,
        )                                                           # (TQ, TC)
    else:
        d = sq_l2_direct(q, c)                                      # (TQ, TC)

    qids = qid_ref[...]                                             # (TQ, 1) i32
    cids = cid_ref[...]                                             # (1, TC) i32
    # Invalid candidates are id-tagged < 0 by ops.py; self-pairs excluded.
    invalid = (cids < 0) | (qids == cids)
    d = jnp.where(invalid, _INF, d)

    vals, ids = topk_min_passes(d, jnp.broadcast_to(cids, d.shape), k)
    outd_ref[0] = vals
    outi_ref[0] = jnp.where(jnp.isinf(vals), -1, ids)


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_c", "metric", "interpret")
)
def knn_tile_topk(
    queries: jnp.ndarray,      # (Q, D) padded: Q % block_q == 0
    candidates: jnp.ndarray,   # (C, D) padded: C % block_c == 0
    query_ids: jnp.ndarray,    # (Q,) i32 (−1 for padding rows)
    cand_ids: jnp.ndarray,     # (C,) i32 (−1 for padding rows)
    *,
    k: int,
    block_q: int = 128,
    block_c: int = 256,
    metric: str = "l2",
    interpret: bool = False,
):
    """Per (query, candidate-tile) top-K.

    Returns (distances (nC, Q, k) f32, indices (nC, Q, k) i32) where
    nC = C // block_c; a log-depth merge in ops.py reduces axis 0.
    """
    if k > MAX_UNROLLED_K:
        raise ValueError(
            f"knn_tile_topk unrolls k min-passes; k={k} exceeds the "
            f"MAX_UNROLLED_K={MAX_UNROLLED_K} ceiling — use "
            "ops.knn_topk, which falls back to the ref merge path"
        )
    q_n, d = queries.shape
    c_n, _ = candidates.shape
    assert q_n % block_q == 0 and c_n % block_c == 0
    n_c = c_n // block_c
    grid = (q_n // block_q, n_c)

    kernel = functools.partial(_knn_topk_kernel, k=k, metric=metric)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, k), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, block_q, k), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_c, q_n, k), jnp.float32),
            jax.ShapeDtypeStruct((n_c, q_n, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(queries, candidates, query_ids[:, None], cand_ids[None, :])
