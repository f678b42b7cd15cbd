"""Pallas TPU kernel: fused one-pass streaming distance + running top-K.

This is the dense engine's streaming backend (DESIGN.md §2.6).  The
cell-tiled path (`pairwise_l2` + `lax.top_k`) materializes the full
`(TQ, C)` distance tile in HBM and then runs top-K as a *second* pass
over it — exactly the materialize-then-sort structure whose memory wall
caps the batch size (ISSUE 3 motivation; Garcia et al.'s GPU brute
force).  Here the candidate axis is an *inner grid dimension* instead:

  grid = (query tiles, candidate sub-blocks), semantics ("parallel",
  "arbitrary") — for a fixed query tile the candidate axis iterates
  sequentially, so VMEM scratch persists across steps and Pallas's
  pipeline machinery double-buffers the next candidate sub-block's DMA
  behind the current step's compute (the FlashAttention streaming
  structure).

Each step computes one `(TQ, TCsub)` distance sub-tile in VMEM (direct
differences for L2, ``knn_topk.sq_l2_direct``; the MXU for inner
products) and merges it into a per-query running top-K — distances *and*
candidate ids — carried in VMEM scratch.  Nothing of shape `(TQ, C)`
ever exists in any memory: HBM traffic is O(Q·D + C·D + Q·K) and the
candidate budget stops being a peak-memory knob.

Folded into the same pass (no second sweep over distances):
  * SHORTC ε² as a *runtime operand* — a (1, 1) block the kernel reads,
    so ε sweeps never recompile (paper §IV-E).  Candidates beyond ε²
    are masked to +inf before the merge, and a sub-block contributing
    no in-range candidate skips its merge network entirely (the
    tile-level short circuit: masked minima only ever grow);
  * `found` bookkeeping — the per-query count of in-range candidates
    (self excluded) accumulates in scratch, so the dense engine's §V-E
    failure test (`found < K`) needs no second distance sweep.

The running merge is the branch-free K min-passes network shared with
``knn_topk`` (``topk_min_passes``: min, first-argmin via min-iota, one-hot
knockout) applied to the running buffer concatenated with the fresh
sub-tile along lanes — no in-kernel sort/top_k primitives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import f32_precision
from repro.kernels.knn_topk.kernel import (  # shared with the tile kernel
    MAX_UNROLLED_K,
    sq_l2_direct,
    topk_min_passes,
)

_INF = np.float32(np.inf)


def _stream_kernel(
    eps_ref, q_ref, c_ref, qid_ref, cid_ref,
    outd_ref, outi_ref, outf_ref,
    run_d, run_i, run_f,
    *, k: int, metric: str,
):
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    eps2 = eps_ref[0, 0]

    @pl.when(j == 0)
    def _init():
        run_d[...] = jnp.full(run_d.shape, _INF, jnp.float32)
        run_i[...] = jnp.full(run_i.shape, -1, jnp.int32)
        run_f[...] = jnp.zeros(run_f.shape, jnp.int32)

    # Operands arrive at their STORED dtype (f32, or bf16 under
    # distance_dtype="bf16" — half the candidate-DMA bytes).  bf16→f32
    # is exact, so every distance is an exact-f32 function of the
    # (possibly bf16-cast) inputs.
    q_raw = q_ref[...]                                             # (TQ, D)
    c_raw = c_ref[...]                                             # (TC, D)
    if metric == "ip":
        # Negated inner product on the MXU (bf16 operands take its
        # native pass): no cancellation, no max-0 clamp (ip scores are
        # legitimately negative).
        d = -jax.lax.dot_general(
            q_raw, c_raw, (((1,), (1,)), ((), ())),
            precision=f32_precision(q_raw.dtype),
            preferred_element_type=jnp.float32,
        )                                                          # (TQ, TC)
    else:
        d = sq_l2_direct(q_raw.astype(jnp.float32),
                         c_raw.astype(jnp.float32))                # (TQ, TC)

    qids = qid_ref[...]                                            # (TQ, 1)
    cids = cid_ref[...]                                            # (1, TC)
    keep = (cids >= 0) & (qids != cids) & (d <= eps2)
    run_f[...] += jnp.sum(keep, axis=1, keepdims=True).astype(jnp.int32)
    d = jnp.where(keep, d, _INF)

    # Tile-level SHORTC: a sub-block with no in-range candidate cannot
    # change the running minima — skip its merge network entirely.
    @pl.when(jnp.any(keep))
    def _merge():
        alld = jnp.concatenate([run_d[...], d], axis=1)            # (TQ, k+TC)
        alli = jnp.concatenate(
            [run_i[...], jnp.broadcast_to(cids, d.shape)], axis=1
        )
        vals, ids = topk_min_passes(alld, alli, k)
        run_d[...] = vals
        run_i[...] = ids

    @pl.when(j == nj - 1)
    def _flush():
        vals = run_d[...]
        outd_ref[...] = vals
        outi_ref[...] = jnp.where(jnp.isinf(vals), -1, run_i[...])
        outf_ref[...] = run_f[...]


def _prefetch_kernel(blk_ref, *refs, k: int, metric: str):
    """Scalar-prefetch wrapper: the block-table ref arrives first (Pallas
    passes scalar-prefetch operands ahead of the tensor refs) and is
    consumed ONLY by the BlockSpec index maps — the compute body is the
    unchanged streaming kernel."""
    del blk_ref
    _stream_kernel(*refs, k=k, metric=metric)


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_c", "metric", "interpret")
)
def knn_stream_topk_prefetch(
    queries: jnp.ndarray,      # (T·block_q, D) cell-sorted query rows
    corpus: jnp.ndarray,       # (C, D) HBM-resident cell-sorted corpus,
                               #        C % block_c == 0 (read in place)
    block_table: jnp.ndarray,  # (T, nblk) i32 — corpus block DMA'd at (i, j)
    query_ids: jnp.ndarray,    # (T·block_q,) i32 exclusion ids (−2 ⇒ none)
    cand_ids: jnp.ndarray,     # (T, nblk·block_c) i32 aligned candidate ids;
                               #        −1 ⇒ row not in the tile's union
    eps2: jnp.ndarray,         # () f32 — traced ε² (runtime operand)
    *,
    k: int,
    block_q: int = 128,
    block_c: int = 128,
    metric: str = "l2",
    interpret: bool = False,
):
    """Scalar-prefetch streaming top-K: the kernel pulls its own candidates.

    One ``pallas_call`` over grid (tiles, candidate steps).  The int32
    ``block_table`` rides as a scalar-prefetch operand
    (``PrefetchScalarGridSpec``), so the corpus BlockSpec's index map reads
    ``block_table[i, j]`` and the pipeline DMAs exactly that ``block_c``-row
    corpus block out of HBM for step (i, j) — no gathered per-tile candidate
    copy ever exists, the corpus is read in place, and the per-tile working
    set is one sub-block regardless of the candidate budget.

    Block-aligned DMA over-fetches rows outside the tile's deduped cell
    ranges; ``cand_ids`` marks those rows −1, which the kernel's existing
    keep-predicate masks — the scored candidate set is EXACTLY the union
    ``grid.tile_shared_candidates`` would have gathered, for any metric.

    Returns (dists (T·block_q, k) f32 ascending inf-padded, ids i32
    −1-padded, found (T·block_q,) i32).
    """
    if k > MAX_UNROLLED_K:
        raise ValueError(
            f"knn_stream_topk_prefetch unrolls k merge passes; k={k} "
            f"exceeds MAX_UNROLLED_K={MAX_UNROLLED_K}"
        )
    q_n, dim = queries.shape
    c_n, _ = corpus.shape
    n_tiles, nblk = block_table.shape
    assert q_n == n_tiles * block_q, (queries.shape, block_table.shape, block_q)
    assert c_n % block_c == 0 and c_n >= block_c, (corpus.shape, block_c)
    assert cand_ids.shape == (n_tiles, nblk * block_c), cand_ids.shape

    kernel = functools.partial(_prefetch_kernel, k=k, metric=metric)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, nblk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, blk: (0, 0)),
            pl.BlockSpec((block_q, dim), lambda i, j, blk: (i, 0)),
            # The data-driven DMA: which corpus block step (i, j) streams
            # is a runtime value, not a grid coordinate.
            pl.BlockSpec((block_c, dim), lambda i, j, blk: (blk[i, j], 0)),
            pl.BlockSpec((block_q, 1), lambda i, j, blk: (i, 0)),
            # Step (i, j)'s ids as a (1, block_c) block of a
            # (T, nblk, 1, block_c) array: Mosaic needs a block's last two
            # dims to be (8, 128) multiples or the array's own, which a
            # (1, block_c) slice of a (T, nblk·block_c) array is not.
            pl.BlockSpec((None, None, 1, block_c),
                         lambda i, j, blk: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j, blk: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j, blk: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j, blk: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),    # running top-K dists
            pltpu.VMEM((block_q, k), jnp.int32),      # running top-K ids
            pltpu.VMEM((block_q, 1), jnp.int32),      # running found count
        ],
    )
    outd, outi, outf = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((q_n, k), jnp.float32),
            jax.ShapeDtypeStruct((q_n, k), jnp.int32),
            jax.ShapeDtypeStruct((q_n, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32),
        jnp.reshape(eps2, (1, 1)).astype(jnp.float32),
        queries, corpus,
        query_ids.astype(jnp.int32)[:, None],
        cand_ids.astype(jnp.int32).reshape(n_tiles, nblk, 1, block_c),
    )
    return outd, outi, outf[:, 0]


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_c", "metric", "interpret")
)
def knn_stream_topk_padded(
    queries: jnp.ndarray,      # (Q, D) padded: Q % block_q == 0
    candidates: jnp.ndarray,   # (C, D) padded: C % block_c == 0
    query_ids: jnp.ndarray,    # (Q,) i32 (−1 for padding rows)
    cand_ids: jnp.ndarray,     # (C,) i32 (−1 for padding rows)
    eps2: jnp.ndarray,         # () f32 — traced ε² (runtime operand)
    *,
    k: int,
    block_q: int = 128,
    block_c: int = 128,
    metric: str = "l2",
    interpret: bool = False,
):
    """One-pass streaming ε-filtered top-K (pre-padded operands).

    Returns (dists (Q, k) f32 ascending inf-padded, ids (Q, k) i32
    −1-padded, found (Q,) i32 in-range candidate count, self excluded).
    """
    if k > MAX_UNROLLED_K:
        raise ValueError(
            f"knn_stream_topk_padded unrolls k merge passes; k={k} exceeds "
            f"MAX_UNROLLED_K={MAX_UNROLLED_K} — use ops.knn_stream_topk, "
            "which falls back to the ref oracle"
        )
    q_n, dim = queries.shape
    c_n, _ = candidates.shape
    assert q_n % block_q == 0 and c_n % block_c == 0
    grid = (q_n // block_q, c_n // block_c)

    kernel = functools.partial(_stream_kernel, k=k, metric=metric)
    outd, outi, outf = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_q, dim), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, dim), lambda i, j: (j, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        # Output blocks are revisited across j (index maps ignore j) and
        # written once at the final candidate step.
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_n, k), jnp.float32),
            jax.ShapeDtypeStruct((q_n, k), jnp.int32),
            jax.ShapeDtypeStruct((q_n, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),    # running top-K dists
            pltpu.VMEM((block_q, k), jnp.int32),      # running top-K ids
            pltpu.VMEM((block_q, 1), jnp.int32),      # running found count
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.reshape(eps2, (1, 1)).astype(jnp.float32),
        queries, candidates,
        query_ids[:, None], cand_ids[None, :],
    )
    return outd, outi, outf[:, 0]
