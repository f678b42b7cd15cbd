"""Sparse engine — TPU-native replacement for the paper's CPU EXACT-ANN.

The paper hands low-density queries to a kd-tree (work-efficient, branchy —
exactly what a TPU cannot run well).  We keep the *work bound* and drop the
branches with a multi-resolution grid pyramid (DESIGN.md §2.2):

  level ℓ = ε·2^ℓ grid, ℓ = 0..L−1.  A query reads its 3^m-neighborhood
  population at every level (vectorized binary searches — regular), picks
  the finest level with ≥ sel_factor·(K+1) candidates (a branch-free
  ``argmax of first-true``), gathers that level's candidates under a fixed
  budget, and runs one small distance+top-K.

Exactness certificate: the 3^m neighborhood of a level-ℓ grid covers every
point within cert_r(ℓ) = min_j cell_edge_ℓ_j of the query, so
``found ≥ K ∧ kth_dist ≤ cert_r(ℓ) ∧ ¬overflow ⇒ exact KNN``.
Queries missing the certificate fall back to the streamed brute scan
(core/brute.py) — the result is always exact, like EXACT-ANN in exact mode.

The engine serves self-joins and foreign (R≠S) queries alike: with
``queries_r`` the ids index an arbitrary query cloud (reference-
reordered), per-level cell coords are computed on the fly, and
candidates always gather from the indexed reference (DESIGN.md §3).

``backend=`` selects the candidate pass (DESIGN.md §2.5, §2.6): every
backend but ``"fused"`` gathers the (B, budget, n) per-query candidate
operand and scores it in one go (candidate sets here are per-query by
design, so the dense engine's shared-candidate Pallas tiling does not
apply); ``"fused"`` streams the candidate budget in chunks through a
scan that carries a per-query running top-K (the ``knn_topk`` merge
helper), so neither the (B, budget, n) gathered operand nor the
(B, budget) distance tile is ever materialized — the jnp-level analogue
of the dense engine's streaming kernel.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import dense_join as dense_lib
from repro.core import grid as grid_lib
from repro.kernels import f32_precision
from repro.kernels.knn_topk import ops as topk_ops
from repro.utils import round_up

# Candidate-chunk width of the fused streaming scan (lane-aligned).
STREAM_CHUNK = 128


class Pyramid(NamedTuple):
    levels: tuple                 # tuple[GridIndex] (no materialized points)
    cert_radii: jnp.ndarray       # (L,) f32 — certified coverage radius per level


@functools.partial(jax.jit, static_argnames=("m", "n_levels", "level_scale"))
def build_pyramid(
    points_r: jnp.ndarray, epsilon: jnp.ndarray, m: int, n_levels: int = 6,
    level_scale: float = 2.0,
) -> Pyramid:
    """L stacked ε·scale^ℓ grids over the (already variance-reordered) data."""
    levels = []
    radii = []
    for lvl in range(n_levels):
        eps_l = jnp.asarray(epsilon, points_r.dtype) * (level_scale**lvl)
        g = grid_lib.build_grid(points_r, eps_l, m, materialize_points=False)
        levels.append(g)
        radii.append(jnp.min(g.cell_edge))
    return Pyramid(levels=tuple(levels), cert_radii=jnp.stack(radii))


class SparseKNNResult(NamedTuple):
    dists: jnp.ndarray        # (Q, K) f32 squared L2 ascending, inf-padded
    ids: jnp.ndarray          # (Q, K) i32, −1-padded
    certified: jnp.ndarray    # (Q,) bool — exactness proven at chosen level
    level: jnp.ndarray        # (Q,) i32 — pyramid level used
    total_candidates: jnp.ndarray  # (Q,) i32 — work proxy (T₁ numerator)
    n_overflow: jnp.ndarray   # () i32 — real rows left uncertified whose
                              # budget overflowed at their final level


def _gathered_sq_l2(qpts, cand_pts, metric="l2"):
    """(B, n) queries vs per-query (B, C, n) candidates -> (B, C) scores
    (squared L2, or −q·c under ``metric="ip"``).

    L2 is summed from direct differences: the candidate operands differ
    per query (this engine exists for irregular low-density work), so
    they are gathered at this shape anyway, and the matmul expansion
    ‖q‖² + ‖c‖² − 2·q·c would cancel away the f32 digits of close
    neighbors' distances.  Inner products take a batched MXU
    dot_general.  Either way every score is an exact-f32 function of
    the (possibly bf16-cast) operands."""
    if metric == "ip":
        return -jax.lax.dot_general(
            qpts, cand_pts, (((1,), (2,)), ((0,), (0,))),
            precision=f32_precision(qpts.dtype),
            preferred_element_type=jnp.float32,
        )                                                      # (B, C)
    diff = (qpts[:, None, :].astype(jnp.float32)
            - cand_pts.astype(jnp.float32))
    return jnp.sum(diff * diff, axis=-1)


def _streamed_topk(points_r, qpts, cand_ids, keep, k, metric="l2"):
    """One-pass streaming top-K for per-query candidate sets (the
    ``"fused"`` sparse path): scan the budget in ``STREAM_CHUNK``-wide
    chunks, gathering / computing / merging per chunk.  The carry is the
    (B, k) running top-K (``knn_topk.merge_running_topk``), so peak
    intermediates are O(B·chunk·n) instead of O(B·budget·n) and no
    (B, budget) distance tile exists in the jaxpr."""
    b, budget = cand_ids.shape
    cpad = round_up(budget, STREAM_CHUNK)
    ids_p = jnp.zeros((b, cpad), cand_ids.dtype).at[:, :budget].set(cand_ids)
    keep_p = jnp.zeros((b, cpad), bool).at[:, :budget].set(keep)
    # (n_chunks, B, chunk) scan layout.
    ids_s = jnp.moveaxis(ids_p.reshape(b, -1, STREAM_CHUNK), 1, 0)
    keep_s = jnp.moveaxis(keep_p.reshape(b, -1, STREAM_CHUNK), 1, 0)

    def step(carry, xs):
        run_d, run_i = carry
        ids_c, keep_c = xs                                     # (B, chunk)
        # The chunk inherits the query dtype: under the bf16 trade the
        # caller passes bf16 queries and the gathered rows cast to match.
        pts_c = points_r[ids_c].astype(qpts.dtype)             # (B, chunk, n)
        d2 = _gathered_sq_l2(qpts, pts_c, metric)
        d2m = jnp.where(keep_c, d2, jnp.inf)
        idm = jnp.where(keep_c, ids_c, -1)
        return topk_ops.merge_running_topk(
            run_d, run_i, d2m, idm, k=k
        ), None

    init = (
        jnp.full((b, k), jnp.inf, jnp.float32),
        jnp.full((b, k), -1, jnp.int32),
    )
    (kd, ki), _ = jax.lax.scan(step, init, (ids_s, keep_s))
    return kd, jnp.where(jnp.isinf(kd), -1, ki)


def _query_level(pyr: Pyramid, points_r, queries, orders, starts, counts,
                 qids, excl, safe, sel, k, budget, backend, metric="l2",
                 distance_dtype="fp32"):
    """Gather + distance + top-K at per-query pyramid level ``sel`` (B,).

    ``orders`` (L, |D|) and ``starts``/``counts`` (L, B, R) are hoisted by
    the caller — both passes (and the level selection) reuse one sweep of
    binary searches instead of recomputing the stacks three times.
    ``queries`` is the cloud the ids index (the indexed points for a
    self-join, the foreign R cloud otherwise); candidates always gather
    from ``points_r``.  ``excl`` is the per-query excluded reference id
    (−2 ⇒ none — see ``dense_join._exclusion_ids``).

    Returns (kd, ki, certified, overflow, total) — the certificate is
    kth ≤ cert_r(sel)² with ≥ K found and no budget truncation."""
    sel_starts = jnp.take_along_axis(starts, sel[None, :, None], axis=0)[0]
    sel_counts = jnp.take_along_axis(counts, sel[None, :, None], axis=0)[0]

    pos, valid, total, overflow = grid_lib.gather_candidates(
        pyr.levels[0], sel_starts, sel_counts, budget
    )                                            # positions in SELECTED level's order

    cand_ids = orders[sel[:, None], pos]                      # (B, budget)
    qpts = queries[safe]
    keep = valid & (cand_ids != excl[:, None])

    # Low-precision scoring pass (DESIGN.md §10): score in bf16 at
    # k + overfetch, then rescore the survivors in exact fp32 — the
    # certificate below is evaluated on exact distances.  The ref
    # backend stays the fp32 oracle.
    lowp = distance_dtype == "bf16" and backend != "ref"
    k_run = min(k + dense_lib.BF16_OVERFETCH, budget) if lowp else k
    qk = qpts.astype(jnp.bfloat16) if lowp else qpts

    if backend == "fused":
        kd, ki = _streamed_topk(points_r, qk, cand_ids, keep, k_run, metric)
    else:
        cand_pts = points_r[cand_ids]                         # (B, budget, n)
        if lowp:
            cand_pts = cand_pts.astype(jnp.bfloat16)
        d2 = _gathered_sq_l2(qk, cand_pts, metric)
        d2m = jnp.where(keep, d2, jnp.inf)
        neg, selk = jax.lax.top_k(-d2m, k_run)
        kd = -neg
        ki = jnp.where(
            jnp.isinf(kd), -1, jnp.take_along_axis(cand_ids, selk, axis=1)
        )
    if lowp:
        kd, ki, _ = dense_lib._rescore_fp32(
            points_r, qpts, ki, jnp.inf, k, metric
        )

    found = jnp.sum(jnp.isfinite(kd), axis=1)
    cert_r = pyr.cert_radii[sel]
    if metric == "ip":
        # Inner product has no triangle inequality: a grid neighborhood
        # certifies NOTHING about ip neighbors.  Every query stays
        # uncertified, so the caller's brute backstop keeps exactness.
        certified = jnp.zeros_like(qids >= 0)
    else:
        certified = (
            (found >= k) & (kd[:, k - 1] <= cert_r**2) & ~overflow
            & (qids >= 0)
        )
    return kd, ki, certified, overflow, total.astype(jnp.int32)


def _block_fn(pyr: Pyramid, points_r, k, budget, sel_factor, backend,
              queries_r=None, exclude_self=True, metric="l2",
              distance_dtype="fp32"):
    """Two-pass adaptive level search (the TPU kd-tree descent analogue).

    Pass 1 picks the finest level whose *projected* 3^m-neighborhood holds
    ≥ sel_factor·(K+1) candidates.  With m < n indexed dims that level can
    under-cover the *full-dimension* KNN radius, so pass 2 escalates: the
    pass-1 kth distance upper-bounds the true kth, and the first level
    whose certified radius exceeds it provably contains the exact KNN —
    one extra gather certifies it (absent budget overflow).

    ``queries_r`` decouples the query cloud from the indexed one (R≠S):
    per-level cell coords are then computed on the fly against each
    pyramid level's geometry instead of read from the build-time
    ``point_coords`` caches.
    """
    n_levels = len(pyr.levels)
    npts = pyr.levels[0].n_points
    queries = points_r if queries_r is None else queries_r
    # Hoisted per-level constants: everything below is loop-invariant
    # across the lax.map over query blocks, so computing it inside
    # ``fn`` would re-broadcast it every block (and, for the 3^m offset
    # table, once more per level).  The closure keeps it out of the
    # scan body entirely.
    cert_r2 = pyr.cert_radii**2                     # (L,) ascending
    orders = jnp.stack([g.order for g in pyr.levels])         # (L, |D|)
    offs = jnp.asarray(grid_lib.neighbor_offsets(pyr.levels[0].m))
    target = sel_factor * (k + 1)                   # selectivity constant

    def fn(qids):
        safe = jnp.clip(qids, 0, queries.shape[0] - 1)
        excl = dense_lib._exclusion_ids(qids, exclude_self)
        qproj = None if queries_r is None else queries[safe][:, : pyr.levels[0].m]

        # All-level candidate ranges, computed ONCE per block: the level
        # selection and both _query_level passes read these same stacks
        # (3× fewer binary-search sweeps than per-pass recomputation).
        starts_l, counts_l = [], []
        for g in pyr.levels:
            coords = (
                g.point_coords[safe] if qproj is None
                else grid_lib.compute_cell_coords(g, qproj)
            )
            s, c = grid_lib.neighbor_ranges(g, coords, offs)
            starts_l.append(s)
            counts_l.append(c)
        starts = jnp.stack(starts_l)                 # (L, B, R)
        counts = jnp.stack(counts_l)                 # (L, B, R)

        # Level selection by projected candidate counts (cheap, regular).
        totals = jnp.sum(counts, axis=-1)            # (L, B)
        enough = totals >= target
        first = jnp.argmax(enough, axis=0).astype(jnp.int32)
        sel1 = jnp.where(jnp.any(enough, axis=0), first, n_levels - 1)

        kd1, ki1, cert1, _, tot1 = _query_level(
            pyr, points_r, queries, orders, starts, counts, qids, excl,
            safe, sel1, k, budget, backend, metric, distance_dtype
        )

        # Escalation level: first ℓ with cert_r(ℓ)² ≥ pass-1 kth (∞ → coarsest).
        kth1 = kd1[:, k - 1]
        sel2 = jnp.searchsorted(cert_r2, kth1).astype(jnp.int32)
        sel2 = jnp.clip(jnp.maximum(sel2, sel1), 0, n_levels - 1)

        kd2, ki2, cert2, ovf2, tot2 = _query_level(
            pyr, points_r, queries, orders, starts, counts, qids, excl,
            safe, sel2, k, budget, backend, metric, distance_dtype
        )

        use1 = cert1[:, None]
        kd = jnp.where(use1, kd1, kd2)
        ki = jnp.where(use1, ki1, ki2)
        certified = cert1 | cert2
        level = jnp.where(cert1, sel1, sel2)
        overflowed = ~certified & ovf2 & (qids >= 0)
        return (kd, ki, certified, level, tot1 + jnp.where(cert1, 0, tot2),
                overflowed)

    return fn


def sparse_knn(
    pyr: Pyramid,
    points_r: jnp.ndarray,
    query_ids: jnp.ndarray,
    queries_r: jnp.ndarray = None,
    *,
    k: int,
    budget: int = 512,
    query_block: int = 128,
    sel_factor: int = 4,
    backend: str = "ref",
    exclude_self: bool = True,
    metric: str = "l2",
    distance_dtype: str = "fp32",
) -> SparseKNNResult:
    """Resolving wrapper (see ``dense_join.dense_join``): collapses
    ``backend`` outside the jit boundary so the executable cache is
    keyed on the concrete path."""
    return sparse_knn_jit(
        pyr, points_r, query_ids, queries_r,
        k=k, budget=budget, query_block=query_block, sel_factor=sel_factor,
        backend=dense_lib.resolve_backend(backend), exclude_self=exclude_self,
        metric=metric, distance_dtype=distance_dtype,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "budget", "query_block", "sel_factor", "backend", "exclude_self",
        "metric", "distance_dtype",
    ),
)
def sparse_knn_jit(
    pyr: Pyramid,
    points_r: jnp.ndarray,
    query_ids: jnp.ndarray,   # (Qpad,) i32, −1 padding
    queries_r: jnp.ndarray = None,  # foreign (R≠S) query cloud, reference-
                                    # reordered; None ⇒ self-join
    *,
    k: int,
    budget: int = 512,
    query_block: int = 128,
    sel_factor: int = 4,
    backend: str = "ref",
    exclude_self: bool = True,
    metric: str = "l2",
    distance_dtype: str = "fp32",
) -> SparseKNNResult:
    if backend == "auto":
        # Same staleness guard as dense_join_jit: "auto" in the jit
        # cache key would freeze the trace-time REPRO_BACKEND reading.
        raise ValueError(
            "sparse_knn_jit requires a concrete backend; resolve "
            "\"auto\" first (use sparse_knn or resolve_backend)"
        )
    backend = dense_lib.resolve_backend(backend)
    if distance_dtype not in dense_lib.DISTANCE_DTYPES:
        raise ValueError(
            f"distance_dtype must be one of {dense_lib.DISTANCE_DTYPES}, "
            f"got {distance_dtype!r}"
        )
    qpad = round_up(query_ids.shape[0], query_block)
    qids = jnp.full((qpad,), -1, jnp.int32).at[: query_ids.shape[0]].set(query_ids)
    blocks = qids.reshape(-1, query_block)
    out = jax.lax.map(
        _block_fn(pyr, points_r, k, budget, sel_factor, backend,
                  queries_r, exclude_self, metric, distance_dtype),
        blocks,
    )
    kd, ki, cert, lvl, total, ovf = jax.tree_util.tree_map(
        lambda x: x.reshape((qpad,) + x.shape[2:]), out
    )
    n = query_ids.shape[0]
    return SparseKNNResult(kd[:n], ki[:n], cert[:n], lvl[:n], total[:n],
                           jnp.sum(ovf, dtype=jnp.int32))
