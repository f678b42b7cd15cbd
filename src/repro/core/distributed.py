"""Distributed-memory KNN join — the paper's stated future work (§VII),
delivered as the *collective layer* under the sharded serving pipeline
(DESIGN.md §5) plus the ring-systolic exact join.

After the placement refactor (ISSUE 5) this module holds exactly three
things:

  * ``build_shard_indices`` — the shard-local index build: one
    ``shard_map`` program that constructs every shard's ε-grid and
    pyramid in parallel on its owning device (via the ``repro.utils``
    shims, so it lowers on jax 0.4.x and newer alike);

  * the collective top-K merge — ``collective_topk_merge`` combines the
    P shard-local candidate sets ``runtime.sharded_index`` produces
    into the exact global KNN, either by an all-gather + fold of
    ``knn_topk.merge_running_topk`` (small P: one collective launch,
    P·Q·k bytes per device) or by a ``ppermute`` butterfly tree-merge
    (large pow2 P: log₂P rounds of neighbor-to-neighbor (Q, k)
    traffic — the wire never carries more than one running buffer);

  * ``ring_self_join`` / ``ring_self_join_bf16`` — the corpus-rotation
    exact join (each device joins its query shard against every corpus
    shard as it rotates around the ring).

The hybrid density *routing* that used to live here (a private
re-implementation of the ρ split inside ``hybrid_join_spmd``) is gone:
``hybrid_join_spmd`` now routes through ``splitter.split_from_counts``,
so the β/γ/ρ arithmetic has exactly one implementation, shared with the
single-device pipeline and the sharded serving path.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import brute as brute_lib
from repro.core import dense_join as dense_lib
from repro.core import grid as grid_lib
from repro.core import sparse_knn as sparse_lib
from repro.core import splitter as split_lib
from repro.kernels.knn_topk import ops as topk_ops
from repro import utils


def _axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


# --------------------------------------------------------------------------
# Shard-local index build (one SPMD program for all shards)
# --------------------------------------------------------------------------

def build_shard_indices(
    mesh: Mesh,
    axis_names: Sequence[str],
    points_stacked: jnp.ndarray,       # (P, shard_n, n) f32, reference-reordered
    epsilon,
    m: int,
    *,
    n_levels: int = 6,
    level_scale: float = 2.0,
):
    """Build every shard's ε-grid + pyramid under ``shard_map``.

    ``points_stacked`` carries shard p's points in block p of the
    leading axis; each device builds the index state for ITS resident
    shard only (grid sort + pyramid stack, all jittable), so build cost
    is one |D|/P-sized index build per device instead of P sequential
    ones.  Returns ``(grids, pyramids)`` — stacked pytrees whose array
    leaves keep the leading P axis (sharded over ``axis_names``); slice
    leaf ``[p]`` to obtain shard p's host-side ``GridIndex``/``Pyramid``.

    All shards share one ε (grid geometry then differs only through
    each shard's extent), so every shard's engines have the same shapes
    — one compile per shard device, no shape-driven recompiles.
    """
    axes = tuple(axis_names)
    eps = jnp.float32(epsilon)

    def local(pts):
        p = pts[0]                                      # (shard_n, n)
        g = grid_lib.build_grid(p, eps, m)
        pyr = sparse_lib.build_pyramid(
            p, eps, m, n_levels=n_levels, level_scale=level_scale
        )
        return jax.tree_util.tree_map(lambda x: x[None], (g, pyr))

    spec = P(axes)
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False,
    )
    return jax.jit(fn)(points_stacked)


# --------------------------------------------------------------------------
# Collective top-K merge (the serving path's only cross-shard step)
# --------------------------------------------------------------------------

#: Shard count at which the ppermute butterfly overtakes the all-gather
#: fold: the fold materializes P·Q·k per device and runs a P-deep merge
#: chain, the butterfly runs log₂P rounds of one (Q, k) buffer each.
TREE_MERGE_MIN_SHARDS = 8

MERGE_STRATEGIES = ("allgather", "tree", "auto")


def merge_strategy(n_shards: int, strategy: str = "auto") -> str:
    """Resolve the collective-merge strategy (DESIGN.md §5.3).

    ``"auto"`` picks the ``ppermute`` butterfly for pow2 shard counts ≥
    ``TREE_MERGE_MIN_SHARDS`` and the all-gather fold otherwise (the
    butterfly needs pow2 P; below the crossover one collective launch
    beats log₂P rounds)."""
    if strategy not in MERGE_STRATEGIES:
        raise ValueError(
            f"merge strategy must be one of {MERGE_STRATEGIES}, got {strategy!r}"
        )
    pow2 = n_shards & (n_shards - 1) == 0
    if strategy == "auto":
        return "tree" if pow2 and n_shards >= TREE_MERGE_MIN_SHARDS \
            else "allgather"
    if strategy == "tree" and not pow2:
        raise ValueError(
            f"tree merge needs a pow2 shard count, got {n_shards}"
        )
    return strategy


def collective_topk_merge(
    mesh: Mesh,
    axis_names: Sequence[str],
    *,
    k: int,
    strategy: str = "auto",
    dedup: bool = False,
):
    """Build the jitted collective merge for ``mesh``:

        fn(dists (P, Q, k_in), ids (P, Q, k_in), excl (Q,))
            -> (dists (Q, k), ids (Q, k))        # replicated

    Block p of the leading axis is shard p's local top-``k_in``
    candidate set — Euclidean (or any monotone) keys ascending, global
    ids, (−1, inf) where a shard had fewer candidates.  ``excl`` is the
    reference id each query must not match (−2 ⇒ none — the same
    exclusion-id trick the engines use, ``dense_join._exclusion_ids``),
    which is how a sharded self-join masks "myself" without any shard
    knowing the global query↔shard-row correspondence.

    ``dedup`` drops repeated global ids within a shard's block before
    merging — the uneven-|D| case, where the last rows of some shards
    duplicate a resident point so every shard keeps the same static
    shape (``runtime.sharded_index``).  Duplicates never cross shards
    (a pad row clones a point of its own shard), so per-block dedup is
    complete.

    Strategies (``merge_strategy``): ``"allgather"`` all-gathers the P
    masked blocks and folds them through ``knn_topk.merge_running_topk``;
    ``"tree"`` reduces each block to (Q, k) locally, then runs a
    log₂P-round ``ppermute`` butterfly whose merge op is the same
    running-top-K merge — every device ends with the full reduction, so
    the output is replicated either way.
    """
    axes = tuple(axis_names)
    n_shards = _axis_size(mesh, axes)
    strategy = merge_strategy(n_shards, strategy)
    if strategy == "tree" and len(axes) != 1:
        raise ValueError("tree merge runs over a single mesh axis")

    def mask_block(d, i, excl):
        # (Q, k_in) block: drop excluded ids and (optionally) in-block
        # duplicate ids BEFORE any reduction to k, so a masked slot can
        # never displace a real candidate.
        valid = (i >= 0) & (i != excl[:, None])
        if dedup:
            k_in = i.shape[1]
            eq = i[:, :, None] == i[:, None, :]          # (Q, k_in, k_in)
            earlier = jnp.tril(jnp.ones((k_in, k_in), bool), -1)
            valid &= ~jnp.any(eq & earlier[None] & (i[:, :, None] >= 0),
                              axis=-1)
        return (
            jnp.where(valid, d, jnp.inf),
            jnp.where(valid, i, -1),
        )

    def local(d, i, excl):
        dm, im = mask_block(d[0], i[0], excl)
        q = dm.shape[0]
        run_d = jnp.full((q, k), jnp.inf, jnp.float32)
        run_i = jnp.full((q, k), -1, jnp.int32)
        # Local reduction to k first: the wire then carries (Q, k), not
        # (Q, k_in), in both strategies.
        run_d, run_i = topk_ops.merge_running_topk(run_d, run_i, dm, im, k=k)
        if strategy == "allgather":
            dg = jax.lax.all_gather(run_d, axes)         # (P, Q, k)
            ig = jax.lax.all_gather(run_i, axes)
            run_d, run_i = dg[0], ig[0]
            for p in range(1, n_shards):
                run_d, run_i = topk_ops.merge_running_topk(
                    run_d, run_i, dg[p], ig[p], k=k
                )
        else:
            stride = 1
            while stride < n_shards:
                perm = [(r, r ^ stride) for r in range(n_shards)]
                pd = jax.lax.ppermute(run_d, axes, perm)
                pi = jax.lax.ppermute(run_i, axes, perm)
                run_d, run_i = topk_ops.merge_running_topk(
                    run_d, run_i, pd, pi, k=k
                )
                stride *= 2
        return run_d, run_i

    spec = P(axes)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


# --------------------------------------------------------------------------
# Ring-systolic exact join
# --------------------------------------------------------------------------

def _even_chunk(corpus_chunk: int, c_loc: int) -> int:
    """Largest divisor of ``c_loc`` that is ≤ ``corpus_chunk``:
    ``dynamic_slice`` clamps at the array edge, which would re-read (and
    double-count) corpus rows, so only even chunking is sound — and
    snapping to a divisor keeps the O(q_loc × chunk) streaming bound
    instead of collapsing to one full-shard tile.  With the default
    pow2 ``pad_block``/``corpus_chunk`` this is just ``min``."""
    chunk = min(corpus_chunk, c_loc)
    while c_loc % chunk:
        chunk -= 1
    return chunk


def _pad_ring_rows(n: int, n_shards: int, pad_block: int) -> int:
    """Padded row count for the ring join: every shard gets the same
    ``utils.pow2_bucket`` row bucket the serving path uses for its
    query shapes, so ring and sharded-index runs land on the same
    compiled-shape keys (and uneven |D| just works — padding rows carry
    id −1, which ``knn_topk`` treats as invalid)."""
    return n_shards * utils.pow2_bucket(utils.cdiv(n, n_shards), pad_block)


def ring_self_join(
    mesh: Mesh,
    axis_names: Sequence[str],
    *,
    k: int,
    kernel_mode: str = "auto",
    corpus_chunk: int = 4096,
    pad_block: int = 128,
):
    """Build the jitted ring join for ``mesh``; returns fn(points) ->
    (dists (|D|, k) squared-L2, ids (|D|, k)).

    ``points`` is logically global; rows are padded to ``n_shards`` ×
    ``pow2_bucket(|D|/n_shards, pad_block)`` (see ``_pad_ring_rows``)
    and split over ``axis_names`` (all other mesh axes replicate).
    Within each hop the resident corpus shard streams through the fused
    top-K in ``corpus_chunk`` slices, bounding the distance working set
    at O(q_loc × corpus_chunk) (the Pallas kernel additionally tiles
    that into VMEM on real hardware).
    """
    axes = tuple(axis_names)
    n_shards = _axis_size(mesh, axes)
    ring = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def local(qpts, qids, cpts, cids):
        # qpts (q_loc, n); cpts (c_loc, n) — resident shard, rotates.
        # pcast: the running buffers are device-varying from step 1 on.
        run_d = jax.lax.pcast(
            jnp.full((qpts.shape[0], k), jnp.inf, jnp.float32), axes, to="varying"
        )
        run_i = jax.lax.pcast(
            jnp.full((qpts.shape[0], k), -1, jnp.int32), axes, to="varying"
        )
        c_loc = cpts.shape[0]
        chunk = _even_chunk(corpus_chunk, c_loc)
        n_chunks = c_loc // chunk

        def hop(_, carry):
            rd, ri, cp, ci = carry

            def inner(j, acc):
                rd, ri = acc
                cj = jax.lax.dynamic_slice_in_dim(cp, j * chunk, chunk, 0)
                ij = jax.lax.dynamic_slice_in_dim(ci, j * chunk, chunk, 0)
                nd, ni = topk_ops.knn_topk(
                    qpts, cj, qids, ij, k=k, mode=kernel_mode)
                return topk_ops.merge_running_topk(rd, ri, nd, ni, k=k)

            rd, ri = jax.lax.fori_loop(0, n_chunks, inner, (rd, ri))
            # Rotate the corpus shard one hop; XLA overlaps this transfer
            # with the next hop's compute (no data dependence until use).
            cp = jax.lax.ppermute(cp, axes, ring)
            ci = jax.lax.ppermute(ci, axes, ring)
            return rd, ri, cp, ci

        rd, ri, _, _ = jax.lax.fori_loop(
            0, n_shards, hop, (run_d, run_i, cpts, cids)
        )
        return rd, ri

    spec = P(axes)
    shard_fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec),
    )

    @jax.jit
    def join(points: jnp.ndarray):
        n = points.shape[0]
        total = _pad_ring_rows(n, n_shards, pad_block)
        pts = utils.pad_to(points, total)
        ids = utils.pad_to(
            jnp.arange(n, dtype=jnp.int32), total, value=-1
        )
        d, i = shard_fn(pts, ids, pts, ids)
        return d[:n], i[:n]

    return join


def ring_self_join_bf16(
    mesh: Mesh,
    axis_names: Sequence[str],
    *,
    k: int,
    corpus_chunk: int = 4096,
    pad_block: int = 128,
):
    """Ring join with bf16 corpus shards on the wire (§Perf lever).

    The rotating corpus shard is the only inter-device traffic; casting
    it to bf16 halves the collective term.  Distances are accumulated in
    f32 from bf16 coordinates (the knn_topk oracle upcasts), so ordering
    error is bounded by bf16 key precision — the same trade the kNN-LM
    datastore makes, and exactness-critical callers keep the f32 ring.

    The loop carry is *bitcast to int16* so XLA cannot hoist the f32
    upconversion above the ppermute (it otherwise folds the convert into
    the carry and silently puts f32 back on the wire — observed, §Perf).

    Rows share the serving path's ``pow2_bucket`` padding (see
    ``ring_self_join``).
    """
    axes = tuple(axis_names)
    n_shards = _axis_size(mesh, axes)
    ring = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def local(qpts, qids, cpts, cids):
        run_d = jax.lax.pcast(
            jnp.full((qpts.shape[0], k), jnp.inf, jnp.float32), axes,
            to="varying")
        run_i = jax.lax.pcast(
            jnp.full((qpts.shape[0], k), -1, jnp.int32), axes, to="varying")
        wire = jax.lax.bitcast_convert_type(
            cpts.astype(jnp.bfloat16), jnp.int16)     # opaque wire format
        c_loc = cpts.shape[0]
        chunk = _even_chunk(corpus_chunk, c_loc)
        n_chunks = c_loc // chunk

        def hop(_, carry):
            rd, ri, cw, ci = carry
            cp = jax.lax.bitcast_convert_type(cw, jnp.bfloat16) \
                .astype(jnp.float32)

            def inner(j, acc):
                rd, ri = acc
                cj = jax.lax.dynamic_slice_in_dim(cp, j * chunk, chunk, 0)
                ij = jax.lax.dynamic_slice_in_dim(ci, j * chunk, chunk, 0)
                nd, ni = topk_ops.knn_topk(qpts, cj, qids, ij, k=k,
                                           mode="ref")
                return topk_ops.merge_running_topk(rd, ri, nd, ni, k=k)

            rd, ri = jax.lax.fori_loop(0, n_chunks, inner, (rd, ri))
            cw = jax.lax.ppermute(cw, axes, ring)     # int16 on the wire
            ci = jax.lax.ppermute(ci, axes, ring)
            return rd, ri, cw, ci

        rd, ri, _, _ = jax.lax.fori_loop(
            0, n_shards, hop, (run_d, run_i, wire, cids))
        return rd, ri

    spec = P(axes)
    shard_fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec))

    @jax.jit
    def join(points: jnp.ndarray):
        n = points.shape[0]
        total = _pad_ring_rows(n, n_shards, pad_block)
        pts = utils.pad_to(points, total)
        ids = utils.pad_to(
            jnp.arange(n, dtype=jnp.int32), total, value=-1
        )
        d, i = shard_fn(pts, ids, pts, ids)
        return d[:n], i[:n]

    return join


# --------------------------------------------------------------------------
# Static-shape SPMD hybrid join (dry-run / serving form of the paper)
# --------------------------------------------------------------------------

class SPMDJoinResult(NamedTuple):
    dists: jnp.ndarray        # (Q, k) squared L2
    ids: jnp.ndarray          # (Q, k)
    source: jnp.ndarray       # (Q,) 0=dense, 1=sparse, 2=fail-lane, 3=unresolved
    n_unresolved: jnp.ndarray  # () i32 — driver re-issues these queries


def hybrid_join_spmd(
    mesh: Mesh,
    query_axes: Sequence[str],
    *,
    k: int,
    m: int = 6,
    rho: float = 0.5,
    gamma: float = 0.0,
    dense_budget: int = 1024,
    sparse_budget: int = 512,
    query_block: int = 128,
    n_levels: int = 3,
    fail_lane_factor: float = 0.25,
    brute_lane_factor: float = 0.25,
    brute_chunk: int = 2048,
):
    """Build fn(points, epsilon) -> SPMDJoinResult for the production mesh.

    The corpus (== query set; self-join) is replicated; query *processing*
    is sharded over ``query_axes``.  The β/γ/ρ density split is
    ``splitter.split_from_counts`` — the SAME implementation the
    single-device pipeline and the sharded index use — evaluated on each
    device's local queries.  Shapes stay static by carrying the split's
    *dynamic membership* as −1 id masking: queries are ordered
    dense-first (densest leading, the paper's §V-B work order), both
    engine lanes are ``pow2_bucket``-padded to the serving path's shape
    buckets, and slots outside a lane's dynamic extent hold qid −1,
    which both engines already treat as padding.

    The price of exact splitter routing under static shapes: both lanes
    (and the fail lane) are sized for q_loc rows regardless of where
    the dynamic cut lands, so per-step engine row-work is ~2× the old
    disjoint static split.  This is the dry-run/serving form — the
    sharded index (``runtime.sharded_index``) is the performance path.
    """
    axes = tuple(query_axes)

    def local(points, qids, epsilon):
        # points replicated (|D|, n); qids (q_loc,) this device's queries.
        points_r = points  # reordering is done by the caller (host, once)
        index = grid_lib.build_grid(points_r, epsilon, m)
        pyramid = sparse_lib.build_pyramid(points_r, epsilon, m, n_levels=n_levels)

        q_loc = qids.shape[0]
        lane = utils.pow2_bucket(q_loc, query_block)

        # The ρ split — one implementation (splitter), shared everywhere.
        home = index.cell_counts[index.point_cell_pos[qids]]
        split = split_lib.split_from_counts(home, k, m, gamma, rho)

        # Dense-first ordering: splitter-dense queries first (densest
        # leading; their key −home < 1 ≤ any sparse key since a dense
        # cell holds ≥ n_min ≥ 1 points), the rest after.  The cut at
        # the splitter's dynamic n_dense rides in the id masks.
        order = jnp.argsort(
            jnp.where(split.to_dense, -home, 1), stable=True
        ).astype(jnp.int32)
        sorted_ids = qids[order]
        rank = jnp.arange(q_loc, dtype=jnp.int32)
        in_dense = rank < split.n_dense
        dense_ids = utils.pad_to(
            jnp.where(in_dense, sorted_ids, -1), lane, value=-1)
        sparse_ids = utils.pad_to(
            jnp.where(in_dense, -1, sorted_ids), lane, value=-1)
        # Result row r ↔ original query position order[r]; q_loc is the
        # scatter drop target for masked/padding rows.
        rows = utils.pad_to(order, lane, value=q_loc)

        out_d = jnp.full((q_loc, k), jnp.inf, jnp.float32)
        out_i = jnp.full((q_loc, k), -1, jnp.int32)
        out_s = jnp.full((q_loc,), 3, jnp.int32)

        dres = dense_lib.dense_join(
            index, points_r, dense_ids, epsilon,
            k=k, budget=dense_budget, query_block=query_block,
        )
        ok = (dense_ids >= 0) & ~dres.failed
        tgt = jnp.where(ok, rows, q_loc)
        out_d = out_d.at[tgt].set(dres.dists, mode="drop")
        out_i = out_i.at[tgt].set(dres.ids, mode="drop")
        out_s = out_s.at[tgt].set(0, mode="drop")

        sres = sparse_lib.sparse_knn(
            pyramid, points_r, sparse_ids,
            k=k, budget=sparse_budget, query_block=query_block,
        )
        oks = (sparse_ids >= 0) & sres.certified
        tgt = jnp.where(oks, rows, q_loc)
        out_d = out_d.at[tgt].set(sres.dists, mode="drop")
        out_i = out_i.at[tgt].set(sres.ids, mode="drop")
        out_s = out_s.at[tgt].set(1, mode="drop")

        # Fixed-capacity fail lane: dense failures re-tried on the pyramid.
        flane = utils.pow2_bucket(
            max(int(fail_lane_factor * q_loc), 1), query_block)
        dfail = (dense_ids >= 0) & dres.failed
        frank = jnp.cumsum(dfail.astype(jnp.int32)) - 1
        # Compact failed queries into the lane; the (flane+1)-th slot is
        # an out-of-bounds drop target for non-failed entries.
        slot = jnp.where(dfail & (frank < flane), frank, flane)
        lane_ids = jnp.full((flane,), -1, jnp.int32).at[slot].set(
            dense_ids, mode="drop"
        )
        lane_rows = jnp.full((flane,), q_loc, jnp.int32).at[slot].set(
            rows, mode="drop"
        )
        fres = sparse_lib.sparse_knn(
            pyramid, points_r, lane_ids,
            k=k, budget=sparse_budget, query_block=query_block,
        )
        good = fres.certified & (lane_ids >= 0)
        safe_rows = jnp.where(good, lane_rows, q_loc)
        out_d = out_d.at[safe_rows].set(fres.dists, mode="drop")
        out_i = out_i.at[safe_rows].set(fres.ids, mode="drop")
        out_s = out_s.at[safe_rows].set(2, mode="drop")

        # Brute lane: fixed-capacity exact backstop for whatever the grid
        # engines could not certify (overflow/uncovered queries).
        if brute_lane_factor > 0.0:
            blane = utils.pow2_bucket(
                max(int(brute_lane_factor * q_loc), 1), query_block)
            pending = out_s == 3
            prank = jnp.cumsum(pending.astype(jnp.int32)) - 1
            slot = jnp.where(pending & (prank < blane), prank, blane)
            rows_all = jnp.arange(q_loc, dtype=jnp.int32)
            blane_ids = jnp.full((blane,), -1, jnp.int32).at[slot].set(
                qids, mode="drop"
            )
            blane_rows = jnp.full((blane,), -1, jnp.int32).at[slot].set(
                rows_all, mode="drop"
            )
            bq = points_r[jnp.clip(blane_ids, 0, points_r.shape[0] - 1)]
            bd, bi = brute_lib.brute_knn(
                points_r, bq, blane_ids, k=k, corpus_chunk=brute_chunk,
            )
            good = blane_ids >= 0
            safe_rows = jnp.where(good, blane_rows, q_loc)
            out_d = out_d.at[safe_rows].set(bd, mode="drop")
            out_i = out_i.at[safe_rows].set(bi, mode="drop")
            out_s = out_s.at[safe_rows].set(2, mode="drop")

        unresolved = jax.lax.psum(
            jnp.sum(out_s == 3).astype(jnp.int32), axes
        )
        return SPMDJoinResult(out_d, out_i, out_s, unresolved)

    spec_q = P(axes)
    shard_fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), spec_q, P()),
        out_specs=SPMDJoinResult(spec_q, spec_q, spec_q, P()),
        check_vma=False,
    )

    @jax.jit
    def join(points: jnp.ndarray, epsilon: jnp.ndarray):
        qids = jnp.arange(points.shape[0], dtype=jnp.int32)
        return shard_fn(points, qids, jnp.asarray(epsilon, jnp.float32))

    return join
