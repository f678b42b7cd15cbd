"""Pallas TPU kernel: sampled pairwise-distance histogram (ε selection).

Implements the paper's §V-C2 sampling kernel: for S sampled query points vs
the full database, bin each distance d < n_bins·bin_width into
floor(d / bin_width).  Distance tiles come off the MXU (matmul form):
its f32 cancellation (DESIGN.md §11.2) only blurs bin edges, and the ε
it selects steers the work split, never which answers are exact.  The
per-tile histogram is a branch-free chunked one-hot reduction; grid steps
accumulate into a single (1, n_bins) output block ("arbitrary" semantics ⇒
sequential revisiting, no race).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import f32_precision


def _hist_kernel(q_ref, c_ref, qid_ref, cid_ref, bw_ref, out_ref, *, n_bins: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = q_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    qq = jnp.sum(q * q, axis=1, keepdims=True)
    cc = jnp.sum(c * c, axis=1, keepdims=True).T
    qc = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        precision=f32_precision(q.dtype),
        preferred_element_type=jnp.float32,
    )
    d2 = jnp.maximum(qq + cc - 2.0 * qc, 0.0)
    d = jnp.sqrt(d2)

    qids = qid_ref[...]                            # (TQ, 1)
    cids = cid_ref[...]                            # (1, TC)
    bw = bw_ref[0, 0]
    valid = (cids >= 0) & (qids >= 0) & (qids != cids)
    bins = jnp.floor(d / bw).astype(jnp.int32)     # (TQ, TC)
    in_range = valid & (bins >= 0) & (bins < n_bins)
    bins = jnp.where(in_range, bins, n_bins)       # n_bins = discard slot

    # Chunked one-hot reduction: (TQ, TC) bins -> (n_bins,) counts.
    tq, tc = d.shape
    flat = bins.reshape(1, tq * tc)
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (n_bins, 1), 0)
    onehot = (flat == bin_iota).astype(jnp.float32)      # (n_bins, TQ*TC)
    counts = jnp.sum(onehot, axis=1)[None, :]            # (1, n_bins)
    out_ref[...] += counts


@functools.partial(
    jax.jit, static_argnames=("n_bins", "block_q", "block_c", "interpret")
)
def distance_bin_histogram(
    queries: jnp.ndarray,    # (S, D) padded: S % block_q == 0
    points: jnp.ndarray,     # (N, D) padded: N % block_c == 0
    query_ids: jnp.ndarray,  # (S,) i32 original ids (−1 padding)
    point_ids: jnp.ndarray,  # (N,) i32 original ids (−1 padding)
    bin_width: jnp.ndarray,  # () f32
    *,
    n_bins: int,
    block_q: int = 128,
    block_c: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Counts (n_bins,) f32 of pair distances per bin (self-pairs excluded)."""
    s, d = queries.shape
    n, _ = points.shape
    assert s % block_q == 0 and n % block_c == 0
    grid = (s // block_q, n // block_c)
    kernel = functools.partial(_hist_kernel, n_bins=n_bins)
    bw = jnp.reshape(bin_width.astype(jnp.float32), (1, 1))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, n_bins), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_bins), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(queries, points, query_ids[:, None], point_ids[None, :], bw)
    return out[0]
