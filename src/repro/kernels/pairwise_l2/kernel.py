"""Pallas TPU kernel: tiled pairwise squared-L2 distances (the paper's
"filtering" hot spot).

Each (TQ×TD)·(TC×TD) step adds the chunk's squared differences to the
output block, one VPU pass per dimension (``knn_topk.sq_l2_direct``):
the MXU expansion ``‖q‖² + ‖c‖² − 2·q·cᵀ`` cancels away f32 digits and
is too coarse for an exact index (DESIGN.md §11.2).  The inner-product
metric stays one MXU matmul per chunk.  The grid is (query tiles ×
candidate tiles × d-chunks); the d-chunk axis accumulates into the
output block, so the full (Q, C) matrix is built tile-by-tile with
VMEM-resident operands.

TSTATIC/TDYNAMIC (paper §V-G) map to the (block_q, block_c) tile shape —
``block_c`` plays "threads per query point" (candidates processed per step
per query).  ``benchmarks/table3_granularity.py`` sweeps it.

SHORTC (paper §IV-E) appears as an optional *tile-level* short circuit:
when every partial distance in the tile already exceeds ε², remaining
d-chunk accumulation for that tile is skipped.  Partial sums only grow, so
a consumer that filters at ε² is unaffected (DESIGN.md §2.3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import f32_precision
from repro.kernels.knn_topk.kernel import sq_l2_direct


def _pairwise_kernel(*refs, shortc_eps2: float | None, shortc_dynamic: bool,
                     metric: str):
    if shortc_dynamic:
        eps_ref, q_ref, c_ref, out_ref = refs
        shortc_eps2 = eps_ref[0, 0]
    else:
        q_ref, c_ref, out_ref = refs
    kd = pl.program_id(2)

    @pl.when(kd == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def _accumulate():
        q = q_ref[...].astype(jnp.float32)                 # (TQ, TD)
        c = c_ref[...].astype(jnp.float32)                 # (TC, TD)
        if metric == "ip":
            # The d-chunk axis accumulates the negated inner product,
            # one MXU matmul per chunk.
            out_ref[...] += -jax.lax.dot_general(
                q, c, (((1,), (1,)), ((), ())),
                precision=f32_precision(q.dtype),
                preferred_element_type=jnp.float32,
            )                                              # (TQ, TC)
        else:
            out_ref[...] += sq_l2_direct(q, c)             # (TQ, TC)

    if shortc_eps2 is None and not shortc_dynamic:
        _accumulate()
    else:
        # Tile-level SHORTC: partial sums are monotone non-decreasing, so if
        # the smallest partial distance already exceeds ε² the whole tile is
        # rejected by any ε-filtering consumer — skip the remaining chunks.
        alive = jnp.logical_or(kd == 0, jnp.min(out_ref[...]) <= shortc_eps2)
        pl.when(alive)(_accumulate)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_c", "block_d", "shortc_eps2",
                     "metric", "interpret"),
)
def pairwise_sq_l2(
    queries: jnp.ndarray,     # (Q, D) — Q % block_q == 0, D % block_d == 0
    candidates: jnp.ndarray,  # (C, D) — C % block_c == 0
    *,
    block_q: int = 128,
    block_c: int = 128,
    block_d: int = 128,
    shortc_eps2: float | None = None,
    metric: str = "l2",
    interpret: bool = False,
) -> jnp.ndarray:
    """Squared L2 distances (Q, C) in float32 (−q·c under
    ``metric="ip"``, which forbids SHORTC: partial ip sums are not
    monotone).  Inputs must be pre-padded to tile multiples (see ops.py
    for the padding wrapper)."""
    if metric == "ip" and shortc_eps2 is not None:
        raise ValueError(
            "SHORTC requires monotone non-decreasing partial sums; "
            "metric='ip' partial scores can shrink — call with "
            "shortc_eps2=None"
        )
    return _pallas_pairwise(
        queries, candidates, None,
        block_q=block_q, block_c=block_c, block_d=block_d,
        shortc_eps2=shortc_eps2, metric=metric, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_c", "block_d", "interpret"),
)
def pairwise_sq_l2_dyn_shortc(
    queries: jnp.ndarray,
    candidates: jnp.ndarray,
    shortc_eps2: jnp.ndarray,     # () f32 — traced ε² (no recompile per ε)
    *,
    block_q: int = 128,
    block_c: int = 128,
    block_d: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """SHORTC variant taking ε² as a runtime operand: the cutoff rides in a
    (1, 1) block the kernel reads, so sweeping ε never forces a recompile
    (the engines trace ε as a device scalar).  L2 only — SHORTC's
    monotone-partial-sum premise does not hold for ip."""
    return _pallas_pairwise(
        queries, candidates, jnp.reshape(shortc_eps2, (1, 1)).astype(jnp.float32),
        block_q=block_q, block_c=block_c, block_d=block_d,
        shortc_eps2=None, metric="l2", interpret=interpret,
    )


def _pallas_pairwise(
    queries, candidates, eps2_arr, *, block_q, block_c, block_d,
    shortc_eps2, metric, interpret,
):
    q_n, d = queries.shape
    c_n, d2 = candidates.shape
    assert d == d2, (d, d2)
    assert q_n % block_q == 0 and c_n % block_c == 0 and d % block_d == 0

    dynamic = eps2_arr is not None
    grid = (q_n // block_q, c_n // block_c, d // block_d)
    kernel = functools.partial(
        _pairwise_kernel, shortc_eps2=shortc_eps2, shortc_dynamic=dynamic,
        metric=metric,
    )
    in_specs = [
        pl.BlockSpec((block_q, block_d), lambda i, j, k: (i, k)),
        pl.BlockSpec((block_c, block_d), lambda i, j, k: (j, k)),
    ]
    operands = [queries, candidates]
    if dynamic:
        in_specs.insert(0, pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)))
        operands.insert(0, eps2_arr)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_q, block_c), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q_n, c_n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
