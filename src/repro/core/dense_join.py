"""Dense engine — the paper's GPU-JOIN (§V-B/§V-E) adapted to TPU.

Range-queries the ε-grid around each assigned query point, filters the
3^m-cell candidate set with full-dimension distances, and keeps the K
nearest within ε.  Faithful semantics:

  * a single, fixed ε for every query (no per-query expansion — the paper
    explicitly rejects divergent search radii, §V-B);
  * a query FAILS iff it finds < K neighbors within ε — failures are
    reassigned to the sparse engine (§V-E).  Our fixed candidate budget
    adds a second failure cause (budget overflow ⇒ the neighborhood was
    not fully examined ⇒ exactness cannot be certified), folding the
    paper's buffer-management concern into the same mechanism;
  * batching (§IV-B): queries stream through in fixed blocks, so peak
    memory is block × budget regardless of |Q^dense|;
  * foreign (R≠S) queries (DESIGN.md §3): ``queries_r`` decouples the
    query cloud from the indexed one — ids then index query rows, home
    cells are computed against the reference grid on the fly, and
    ``exclude_self`` controls the positional-identity exclusion.

Three execution backends share those semantics (DESIGN.md §2.5, §2.6):

  * ``"ref"`` — per-query gather + broadcast-subtract (the original jnp
    path; VPU-bound, kept as the correctness oracle);
  * ``"pallas"`` / ``"interpret"`` — the cell-tiled path: queries are
    sorted by home cell (``grid.group_queries_by_cell``) so each tile
    shares ONE deduplicated 3^m candidate block
    (``grid.tile_shared_candidates``), and the (TQ, TC) distance tile
    comes from the ``pairwise_l2`` kernel (direct differences) with
    the SHORTC ε² tile short-circuit, followed by a second top-K pass
    over the materialized (TQ, TC) tile;
  * ``"fused"`` — the streaming one-pass engine (``kernels/knn_stream``):
    the candidate axis is an inner kernel grid dimension — each
    (TQ×D)·(D×TCsub) distance sub-tile merges into a per-query running
    top-K carried in VMEM scratch, with ε/found bookkeeping folded into
    the same pass, so no (block, budget) distance tile ever exists in
    HBM.  Since ISSUE 10 the kernel also pulls its own candidates: the
    tile's deduped cell ranges become a scalar-prefetch DMA schedule
    (``_fused_prefetch_join``) driving block reads straight from the
    HBM-resident cell-sorted corpus, so no gathered (tiles, budget, D)
    candidate copy exists either — the corpus is read in place and the
    budget bounds only metadata.  Runs the Pallas kernel compiled on
    TPU and in interpret mode elsewhere (CPU CI).  ``distance_dtype``
    ("fp32"/"bf16") selects the kernel accumulation dtype here.

``"auto"`` resolves once per process state to fused on TPU and ref
elsewhere; the ``REPRO_BACKEND`` env var overrides the auto resolution
for benchmarking without code edits.

Correctness invariant (used by tests): if ``found ≥ K`` and no overflow,
the returned K neighbors are the *exact* global KNN, because the 3^m
neighborhood of an edge-≥ε grid covers every point within distance ε, and
all K reported neighbors lie within ε.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import grid as grid_lib
from repro.kernels import f32_precision
from repro.kernels.knn_stream import kernel as stream_kernel
from repro.kernels.knn_stream import ops as stream_ops
from repro.kernels.pairwise_l2 import ops as pairwise_ops
from repro.utils import INT32_SENTINEL, round_up

BACKENDS = ("ref", "pallas", "interpret", "fused", "auto")

# Distance-accumulation dtype (DESIGN.md §10).  "fp32" is the exact
# path.  "bf16" computes kernel distance tiles from bf16-cast operands
# (halving candidate-DMA bytes and engaging the MXU's native
# low-precision path), over-fetches BF16_OVERFETCH extra slots, and
# restores exact fp32 distances by rescoring the survivors; the ε
# keep-threshold is inflated by BF16_EPS_SLACK so cast rounding near
# the ε² boundary drops (almost) nothing the exact filter would keep —
# the rescore then applies the exact ε² and any capture shortfall is a
# conservative §V-E failure, never a silent wrong answer.
DISTANCE_DTYPES = ("fp32", "bf16")
BF16_OVERFETCH = 8
BF16_EPS_SLACK = 0.125

# Extra corpus-block slots past ceil(budget/block_c) in the prefetch
# path's per-tile DMA schedule: the deduped cell ranges are rounded to
# block_c-aligned corpus blocks, so fragmentation (many small ranges
# straddling block edges) can touch a few more blocks than the budget's
# worth of rows.  Exceeding the padded schedule is a per-tile overflow
# failure, exactly like exceeding the row budget.
PREFETCH_BLOCK_SLACK = 2


def resolve_backend(backend: str) -> str:
    """Collapse ``"auto"`` on the host: the streaming fused engine on
    TPU, ref elsewhere.  The ``REPRO_BACKEND`` env var overrides the
    auto resolution (benchmark sweeps without code edits); an explicit
    non-auto ``backend`` always wins over the env.

    Resolution always happens OUTSIDE the jit boundary (the public
    ``dense_join``/``sparse_knn`` wrappers resolve before calling their
    ``*_jit`` bodies), so the executable cache is keyed on the concrete
    path and a changed env can never silently hit a stale ``"auto"``
    trace.  Callers that dispatch repeatedly (sessions, benchmark
    drivers) still resolve ONCE up front so one run never mixes paths.
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    if backend == "auto":
        env = os.environ.get("REPRO_BACKEND", "").strip().lower()
        if env:
            if env not in BACKENDS or env == "auto":
                raise ValueError(
                    f"REPRO_BACKEND must be one of {tuple(b for b in BACKENDS if b != 'auto')}, "
                    f"got {env!r}"
                )
            return env
        return "fused" if jax.default_backend() == "tpu" else "ref"
    return backend


def _stream_kernel_mode() -> str:
    """The fused backend's kernel execution mode: compiled Pallas on
    TPU, interpret elsewhere (the CPU CI path)."""
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


class DenseJoinResult(NamedTuple):
    dists: jnp.ndarray     # (Q, K) f32 squared L2, ascending, inf-padded
    ids: jnp.ndarray       # (Q, K) i32, −1-padded
    found: jnp.ndarray     # (Q,) i32 neighbors within ε (self excluded)
    failed: jnp.ndarray    # (Q,) bool — < K within ε, or candidate overflow
    total_candidates: jnp.ndarray  # (Q,) i32 — filtering workload (T₂ proxy)


def _exclusion_ids(qids, exclude_self: bool):
    """Reference id each query must not match.  Self-join exclusion
    compares against the query id itself (Q = R shares one id space);
    with ``exclude_self=False`` the constant −2 never matches a real
    candidate id (≥ 0) nor the −1 invalid marker, so nothing is
    excluded and no kernel needs a flag."""
    return qids if exclude_self else jnp.full_like(qids, -2)


def _block_fn(index: grid_lib.GridIndex, points_r, eps2, k, budget,
              queries_r=None, qcoords=None, exclude_self=True, metric="l2"):
    """Process one block of query ids (−1 = padding).

    ``queries_r`` decouples the query cloud from the indexed one (R≠S):
    ids then index ``queries_r`` rows and ``qcoords`` — the query
    cloud's reference-grid cell coords — replaces the build-time
    ``point_coords`` cache.  Defaults keep the self-join fast path."""
    queries = points_r if queries_r is None else queries_r
    coords_all = index.point_coords if qcoords is None else qcoords

    def fn(qids):
        nq = qids.shape[0]
        safe = jnp.clip(qids, 0, queries.shape[0] - 1)
        coords = coords_all[safe]                                 # (B, m)
        starts, counts = grid_lib.neighbor_ranges(index, coords)  # (B, R)
        pos, valid, total, overflow = grid_lib.gather_candidates(
            index, starts, counts, budget
        )                                                          # (B, budget)
        cand_ids = index.order[pos]                                # original ids
        cand_pts = index.points_sorted[pos]                        # (B, budget, n)
        qpts = queries[safe]                                       # (B, n)

        if metric == "ip":
            d2 = -jnp.einsum("bn,bcn->bc", qpts, cand_pts,
                             precision=f32_precision(qpts.dtype))                # (B, budget)
        else:
            diff = qpts[:, None, :] - cand_pts
            d2 = jnp.sum(diff * diff, axis=-1)                     # (B, budget)

        self_pair = cand_ids == _exclusion_ids(qids, exclude_self)[:, None]
        keep = valid & ~self_pair & (d2 <= eps2)
        d2m = jnp.where(keep, d2, jnp.inf)

        neg, sel = jax.lax.top_k(-d2m, k)
        kdists = -neg
        kids = jnp.where(
            jnp.isinf(kdists), -1, jnp.take_along_axis(cand_ids, sel, axis=1)
        )
        found = jnp.sum(keep, axis=1).astype(jnp.int32)
        failed = (found < k) | overflow
        return kdists, kids, found, failed, total.astype(jnp.int32)

    return fn


def _shared_tile_candidates(index: grid_lib.GridIndex, points_r, qids,
                            cand_budget, queries_r=None, qcoords=None):
    """The cell-tiled backends' common gather: one deduplicated shared
    candidate block per query tile (−1 = padding row).  ``queries_r`` /
    ``qcoords`` carry the foreign query cloud and its reference-grid
    cell coords (see ``_block_fn``); candidate ranges always come from
    the reference index."""
    queries = points_r if queries_r is None else queries_r
    coords_all = index.point_coords if qcoords is None else qcoords
    safe = jnp.clip(qids, 0, queries.shape[0] - 1)
    coords = coords_all[safe]                                 # (TQ, m)
    starts, counts = grid_lib.neighbor_ranges(index, coords)  # (TQ, R)
    # Padding rows clip to point 0 — zero their ranges so a partial
    # tile's shared union holds only REAL queries' neighborhoods
    # (otherwise point 0's cells could crowd out, or overflow, the
    # tile's budget and spuriously fail every query in it).
    counts = jnp.where((qids >= 0)[:, None], counts, 0)
    pos, valid, _, tile_overflow = grid_lib.tile_shared_candidates(
        index, starts, counts, cand_budget
    )                                                          # (TC,)
    cand_ids = jnp.where(valid, index.order[pos], -1)
    cand_pts = index.points_sorted[pos]                        # (TC, n)
    qpts = queries[safe]                                       # (TQ, n)
    # T₂ proxy stays per-query (own 3^m total), matching the ref
    # backend so the queue's Eq.-6 rebalance sees identical workloads.
    own_total = jnp.sum(counts, axis=1).astype(jnp.int32)
    return qpts, cand_ids, cand_pts, own_total, tile_overflow


def _tile_block_tables(index: grid_lib.GridIndex, coords_all, queries,
                       tiles, nblk, n_cb, budget, block_c):
    """The prefetch path's XLA-side metadata pass: per query tile, turn
    the deduped 3^m cell ranges into (a) the list of ``block_c``-aligned
    corpus blocks the kernel must DMA and (b) a block-aligned candidate-id
    operand whose rows OUTSIDE the deduped union carry −1.

    The id masking makes block rounding exact: the kernel's keep
    predicate drops the over-fetched rows, so the scored candidate set
    equals ``tile_shared_candidates``'s union bit-for-bit, independent of
    metric or ε.  Only int32 metadata is built here — no (budget, D)
    candidate copy, which is the whole point.

    Returns (block_table (T, nblk) i32, cand_ids (T, nblk·block_c) i32,
    own_total (T, TQ) i32, tile_overflow (T,) bool).  Overflow covers
    both failure modes: union rows > budget (the row budget, same as the
    gather path) and touched blocks > nblk (block fragmentation past the
    padded DMA schedule)."""
    npts = index.n_points

    def one(qids):
        safe = jnp.clip(qids, 0, queries.shape[0] - 1)
        coords = coords_all[safe]                                  # (TQ, m)
        starts, counts = grid_lib.neighbor_ranges(index, coords)   # (TQ, R)
        # Padding rows clip to point 0 — zero their ranges (same guard
        # as _shared_tile_candidates).
        counts = jnp.where((qids >= 0)[:, None], counts, 0)
        own_total = jnp.sum(counts, axis=1).astype(jnp.int32)

        flat_s = starts.reshape(-1)
        flat_c = counts.reshape(-1)
        # Dedup by range start (a start uniquely keys its cell): sort,
        # mark repeats — identical to tile_shared_candidates' dedup.
        key = jnp.where(flat_c > 0, flat_s, INT32_SENTINEL)
        order = jnp.argsort(key)
        key_s = key[order]
        s_sorted = flat_s[order]
        c_sorted = flat_c[order]
        dup = jnp.concatenate([jnp.zeros((1,), bool), key_s[1:] == key_s[:-1]])
        uniq = (key_s != INT32_SENTINEL) & ~dup
        total = jnp.sum(jnp.where(uniq, c_sorted, 0))

        # Touched corpus blocks by interval stabbing: +1 at each unique
        # range's first block, −1 after its last, running-sum > 0.
        first = jnp.clip(s_sorted // block_c, 0, n_cb - 1)
        last = jnp.clip((s_sorted + c_sorted - 1) // block_c, 0, n_cb - 1)
        marks = jnp.zeros((n_cb + 1,), jnp.int32)
        marks = marks.at[jnp.where(uniq, first, n_cb)].add(
            jnp.where(uniq, 1, 0))
        marks = marks.at[jnp.where(uniq, last + 1, n_cb)].add(
            jnp.where(uniq, -1, 0))
        touched = jnp.cumsum(marks[:-1]) > 0                       # (n_cb,)
        n_touched = jnp.sum(touched.astype(jnp.int32))
        # Stable argsort of ~touched lists touched blocks first, in
        # ascending block order; unused slots re-DMA block 0 with
        # all-masked ids (the kernel skips their merge entirely).
        blk = jnp.argsort(~touched, stable=True).astype(jnp.int32)[:nblk]
        slot_ok = jnp.arange(nblk, dtype=jnp.int32) < n_touched
        blk = jnp.where(slot_ok, blk, 0)

        # Membership of each aligned row: cell slices are disjoint, so
        # row p belongs to the union iff the last range with start ≤ p
        # still covers it.  Duplicate ranges share identical (start,
        # count) — searching the UNdeduped sorted ranges means the
        # rightmost hit always carries the full extent.
        pos = (blk[:, None] * block_c
               + jnp.arange(block_c, dtype=jnp.int32)[None, :]).reshape(-1)
        j = jnp.searchsorted(key_s, pos, side="right") - 1
        js = jnp.clip(j, 0, key_s.shape[0] - 1)
        member = (
            (j >= 0)
            & (key_s[js] != INT32_SENTINEL)
            & (pos < s_sorted[js] + c_sorted[js])
            & jnp.repeat(slot_ok, block_c)
        )
        cand = jnp.where(
            member, index.order[jnp.clip(pos, 0, npts - 1)], -1
        ).astype(jnp.int32)
        overflow = (total > budget) | (n_touched > nblk)
        return blk, cand, own_total, overflow

    return jax.vmap(one)(tiles)


def _rescore_fp32(points_r, qpts, ki, eps2, k, metric="l2"):
    """Exact fp32 rescore of the low-precision pass's over-fetched
    survivors: gather the (Q, k_run, n) candidate rows BY ID (k_run ≤
    MAX_UNROLLED_K — tiny, nothing budget-shaped), recompute distances
    at full precision, re-apply the exact ε² filter, and keep the k
    best.  Returns (kd (Q, k) f32, ki (Q, k) i32, n_true (Q,) i32 —
    survivors within the exact ε², the §V-E failure evidence)."""
    safe = jnp.clip(ki, 0, points_r.shape[0] - 1)
    cand = points_r[safe]                                  # (Q, k_run, n)
    q = qpts.astype(jnp.float32)
    if metric == "ip":
        d = -jnp.einsum("qn,qcn->qc", q, cand, precision=f32_precision(q.dtype))
    else:
        diff = q[:, None, :] - cand
        d = jnp.sum(diff * diff, axis=-1)
    keep = (ki >= 0) & (d <= eps2)
    dm = jnp.where(keep, d, jnp.inf)
    neg, sel = jax.lax.top_k(-dm, k)
    kd = -neg
    kid = jnp.where(jnp.isinf(kd), -1, jnp.take_along_axis(ki, sel, axis=1))
    return kd, kid, jnp.sum(keep, axis=1).astype(jnp.int32)


def _fused_prefetch_join(index: grid_lib.GridIndex, points_r, qids, eps2, k,
                         budget, query_block, block_c, kernel_mode,
                         queries_r=None, qcoords=None, exclude_self=True,
                         metric="l2", distance_dtype="fp32"):
    """The fused backend's scalar-prefetch path (DESIGN.md §10): ONE
    kernel launch over every tile, with the per-tile DMA schedule from
    ``_tile_block_tables`` riding as a scalar-prefetch operand so the
    kernel pulls its own candidates from the HBM-resident cell-sorted
    corpus.  No gathered (tiles, budget, D) candidate copy exists at any
    layer.  Returns (kd, ki, found, failed, total), already scattered
    back to original query order."""
    queries = points_r if queries_r is None else queries_r
    coords_all = index.point_coords if qcoords is None else qcoords
    tiles, perm = grid_lib.group_queries_by_cell(
        index, qids, query_block, qcoords
    )

    n_cb = max(1, -(-index.n_points // block_c))       # corpus blocks
    c_pad = n_cb * block_c
    nblk = min(
        round_up(budget, block_c) // block_c + PREFETCH_BLOCK_SLACK, n_cb
    )
    blk, cand, own_total, tile_ovf = _tile_block_tables(
        index, coords_all, queries, tiles, nblk, n_cb, budget, block_c
    )

    flat = tiles.reshape(-1)                           # (Qpad,) cell-sorted
    safe = jnp.clip(flat, 0, queries.shape[0] - 1)
    qpts = queries[safe]                               # queries read once
    excl = _exclusion_ids(flat, exclude_self)
    corpus = index.points_sorted                       # read in place
    if c_pad != corpus.shape[0]:
        corpus = jnp.zeros(
            (c_pad, corpus.shape[1]), corpus.dtype
        ).at[: corpus.shape[0]].set(corpus)

    bf16 = distance_dtype == "bf16"
    k_run = k + (BF16_OVERFETCH if bf16 else 0)
    # ε slack is multiplicative on the runtime operand, so the recall
    # ladder's eps_scale sweeps reuse this executable unchanged; abs()
    # keeps the inflation an inflation for ip's negative thresholds.
    eps_keep = eps2 + BF16_EPS_SLACK * jnp.abs(eps2) if bf16 else eps2
    qk = qpts.astype(jnp.bfloat16) if bf16 else qpts
    ck = corpus.astype(jnp.bfloat16) if bf16 else corpus

    kd, ki, found = stream_ops.knn_stream_topk_prefetch(
        qk, ck, blk, excl, cand, eps_keep,
        k=k_run, block_q=query_block, block_c=block_c,
        mode=kernel_mode, metric=metric,
    )
    if bf16:
        kd, ki, n_true = _rescore_fp32(points_r, qpts, ki, eps2, k, metric)
        # found counts at the inflated threshold (an over-estimate near
        # the boundary); n_true < k proves the exact-ε survivors fall
        # short, so the failure test stays conservative.
        failed_rows = (found < k) | (n_true < k)
    else:
        failed_rows = found < k
    failed = failed_rows | jnp.repeat(tile_ovf, query_block)
    out = (kd, ki, found, failed, own_total.reshape(-1))
    return tuple(jnp.zeros_like(x).at[perm].set(x) for x in out)


def _tile_fn(index: grid_lib.GridIndex, points_r, eps2, k, budget, block_c,
             kernel_mode, queries_r=None, qcoords=None, exclude_self=True,
             metric="l2"):
    """Process one cell-sorted query tile against its shared candidate
    block (−1 = padding).  The distance tile is one kernel call."""
    cand_budget = round_up(budget, block_c)

    def fn(qids):
        nq = qids.shape[0]
        qpts, cand_ids, cand_pts, own_total, tile_overflow = (
            _shared_tile_candidates(index, points_r, qids, cand_budget,
                                    queries_r, qcoords)
        )

        d2 = pairwise_ops.pairwise_sq_l2(
            qpts, cand_pts,
            block_q=nq, block_c=block_c,
            # SHORTC's monotone-partial-sum premise is L2-only; under ip
            # the ε² cutoff still applies below, as a plain score filter.
            shortc_eps2=None if metric == "ip" else eps2,
            metric=metric, mode=kernel_mode,
        )                                                          # (TQ, TC)

        excl = _exclusion_ids(qids, exclude_self)
        keep = (
            (cand_ids[None, :] >= 0)
            & (cand_ids[None, :] != excl[:, None])
            & (d2 <= eps2)
        )
        d2m = jnp.where(keep, d2, jnp.inf)
        neg, sel = jax.lax.top_k(-d2m, k)
        kdists = -neg
        kids = jnp.where(
            jnp.isinf(kdists),
            -1,
            jnp.take_along_axis(
                jnp.broadcast_to(cand_ids[None, :], d2m.shape), sel, axis=1
            ),
        )
        found = jnp.sum(keep, axis=1).astype(jnp.int32)
        # The shared block holds the tile's union, so truncation hits every
        # query in the tile at once — a per-tile §V-E failure.
        failed = (found < k) | tile_overflow
        return kdists, kids, found, failed, own_total

    return fn


def _fused_tile_fn(index: grid_lib.GridIndex, points_r, eps2, k, budget,
                   block_c, kernel_mode, queries_r=None, qcoords=None,
                   exclude_self=True, metric="l2"):
    """Streaming one-pass tile processor (DESIGN.md §2.6): the shared
    candidate block streams through the fused kernel in ``block_c``
    sub-blocks; distance, ε filter, top-K, and ``found`` all happen in
    one kernel pass — no (TQ, TC) distance tile is ever materialized."""
    cand_budget = round_up(budget, block_c)

    def fn(qids):
        nq = qids.shape[0]
        qpts, cand_ids, cand_pts, own_total, tile_overflow = (
            _shared_tile_candidates(index, points_r, qids, cand_budget,
                                    queries_r, qcoords)
        )
        # The kernel's "query id" operand exists solely for the id
        # inequality test, so the exclusion ids ride in its place —
        # R≠S needs no kernel change.
        kdists, kids, found = stream_ops.knn_stream_topk(
            qpts, cand_pts, _exclusion_ids(qids, exclude_self), cand_ids,
            eps2, k=k, block_q=nq, block_c=block_c, mode=kernel_mode,
            metric=metric,
        )
        # Same per-tile §V-E overflow semantics as the two-pass tiled path.
        failed = (found < k) | tile_overflow
        return kdists, kids, found, failed, own_total

    return fn


def dense_join(
    index: grid_lib.GridIndex,
    points_r: jnp.ndarray,
    query_ids: jnp.ndarray,
    epsilon: jnp.ndarray,
    queries_r: jnp.ndarray = None,
    *,
    k: int,
    budget: int = 1024,
    query_block: int = 128,
    block_c: int = 128,
    backend: str = "ref",
    exclude_self: bool = True,
    metric: str = "l2",
    distance_dtype: str = "fp32",
) -> DenseJoinResult:
    """Run GPU-JOIN over the given query ids (see ``dense_join_jit``).

    Resolves ``backend`` OUTSIDE the jit boundary so the executable
    cache is keyed on the concrete path: ``"auto"`` (and a changed
    ``REPRO_BACKEND``) can never silently hit a stale entry traced
    under a different resolution."""
    return dense_join_jit(
        index, points_r, query_ids, epsilon, queries_r,
        k=k, budget=budget, query_block=query_block, block_c=block_c,
        backend=resolve_backend(backend), exclude_self=exclude_self,
        metric=metric, distance_dtype=distance_dtype,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "budget", "query_block", "block_c", "backend", "exclude_self",
        "metric", "distance_dtype",
    ),
)
def dense_join_jit(
    index: grid_lib.GridIndex,
    points_r: jnp.ndarray,     # (|D|, n) variance-reordered database
    query_ids: jnp.ndarray,    # (Qpad,) i32, −1 padding — Q^dense, compacted
    epsilon: jnp.ndarray,      # () f32 — range-query radius (= grid target edge)
    queries_r: jnp.ndarray = None,  # (|Q|, n) foreign query cloud (R≠S), in
                                    # the reference's reordered space; None ⇒
                                    # queries ARE the indexed points
    *,
    k: int,
    budget: int = 1024,
    query_block: int = 128,
    block_c: int = 128,
    backend: str = "ref",
    exclude_self: bool = True,
    metric: str = "l2",
    distance_dtype: str = "fp32",
) -> DenseJoinResult:
    """Run GPU-JOIN over the given query ids.  Results are aligned with
    ``query_ids`` (row i ↔ query_ids[i]); padding rows are failed.

    ``distance_dtype`` (module constants, DESIGN.md §10) selects the
    kernel accumulation dtype on the fused backend: ``"bf16"`` halves
    candidate-DMA bytes and over-fetches, then an exact fp32 rescore of
    the survivors restores exact distances and the exact ε² filter.
    The ref/tiled backends always serve fp32 (more precision is never
    wrong); the knob is part of every engine-cache key regardless.

    ``metric`` selects the kernel score space (``"l2"`` squared L2 —
    which cosine indexes reuse over unit rows — or ``"ip"`` the negated
    inner product, where ε² acts as a plain score threshold and SHORTC
    is disabled); it is part of every engine-cache key.

    ``backend`` must be a concrete (already-resolved) execution path
    (module docstring) — AOT callers (``KNNIndex``/``JoinSession``)
    lower this directly with their session-resolved backend; everyone
    else goes through the resolving ``dense_join`` wrapper.  ``block_c``
    is the candidate-tile width in the fused kernels — the paper's
    TDYNAMIC "threads per query point" knob — and is ignored by
    ``"ref"``.

    With ``queries_r`` the join is a foreign (R≠S) join: ids index
    ``queries_r`` rows, home cells are computed on the fly against the
    reference grid, and ``exclude_self`` decides whether query i may
    report reference point i (positional identity — only meaningful
    when the query cloud aliases the indexed one).
    """
    if backend == "auto":
        # Re-resolving here would key the executable cache on the
        # literal "auto" and freeze whatever REPRO_BACKEND said at
        # trace time — the exact staleness the wrapper exists to avoid.
        raise ValueError(
            "dense_join_jit requires a concrete backend; resolve "
            "\"auto\" first (use dense_join or resolve_backend)"
        )
    backend = resolve_backend(backend)
    if distance_dtype not in DISTANCE_DTYPES:
        raise ValueError(
            f"distance_dtype must be one of {DISTANCE_DTYPES}, "
            f"got {distance_dtype!r}"
        )
    qpad = round_up(query_ids.shape[0], query_block)
    qids = jnp.full((qpad,), -1, jnp.int32).at[: query_ids.shape[0]].set(query_ids)
    eps2 = jnp.asarray(epsilon, jnp.float32) ** 2
    # Foreign queries carry no build-time coords cache — compute the
    # whole cloud's reference-grid cell coords once (a floor + clip).
    qcoords = (
        None if queries_r is None
        else grid_lib.compute_cell_coords(index, queries_r[:, : index.m])
    )
    # The fused backend's streaming kernel unrolls k (+ the bf16
    # over-fetch) merge passes; past the ceiling the gathered tile path
    # below takes over and its stream op reroutes to the ref oracle
    # (ops logs the cliff once) — always at fp32.
    fused_k_run = k + (BF16_OVERFETCH if distance_dtype == "bf16" else 0)
    use_prefetch = (
        backend == "fused" and fused_k_run <= stream_kernel.MAX_UNROLLED_K
    )

    if backend == "ref":
        blocks = qids.reshape(-1, query_block)
        out = jax.lax.map(
            _block_fn(index, points_r, eps2, k, budget,
                      queries_r, qcoords, exclude_self, metric),
            blocks,
        )
        kd, ki, found, failed, total = jax.tree_util.tree_map(
            lambda x: x.reshape((qpad,) + x.shape[2:]), out
        )
    elif use_prefetch:
        kd, ki, found, failed, total = _fused_prefetch_join(
            index, points_r, qids, eps2, k, budget, query_block, block_c,
            _stream_kernel_mode(), queries_r, qcoords, exclude_self,
            metric, distance_dtype,
        )
    else:
        if backend == "fused":
            tile_fn = _fused_tile_fn(
                index, points_r, eps2, k, budget, block_c,
                _stream_kernel_mode(), queries_r, qcoords, exclude_self,
                metric,
            )
        else:
            tile_fn = _tile_fn(
                index, points_r, eps2, k, budget, block_c, backend,
                queries_r, qcoords, exclude_self, metric,
            )
        tiles, perm = grid_lib.group_queries_by_cell(
            index, qids, query_block, qcoords
        )
        out = jax.lax.map(tile_fn, tiles)
        kd, ki, found, failed, total = jax.tree_util.tree_map(
            lambda x: jnp.zeros_like(x.reshape((qpad,) + x.shape[2:]))
            .at[perm]
            .set(x.reshape((qpad,) + x.shape[2:])),
            out,
        )
    n = query_ids.shape[0]
    pad_row = jnp.arange(qpad) >= n
    failed = failed | pad_row | (qids < 0)
    return DenseJoinResult(kd[:n], ki[:n], found[:n], failed[:n], total[:n])
