"""Sharded KNNIndex: one hybrid pipeline from single chip to mesh
(DESIGN.md §5).

``KNNIndex`` (single device) and ``core.distributed`` (SPMD) used to be
disconnected universes — the SPMD join re-implemented the ρ routing,
bypassed the engine cache, and could not serve R≠S queries.  This
module makes *placement* a layer instead of a fork:

  * ``ShardedKNNIndex.build(points, config, mesh=...)`` partitions the
    reference cloud into P equal per-device shards along the
    cell-sorted order of a global ε-grid over the REORDERed points —
    row-range shards of that order cover compact cell ranges, so each
    shard's local grid stays dense (Gowanlock's grid-partitioned
    self-join, applied to serving).  Shard-local grid+pyramid state is
    built in one ``shard_map`` program (``distributed
    .build_shard_indices``); each shard is then a plain ``KNNIndex``
    over its sub-cloud, its state committed to its own device.

  * ``index.query(queries, k, exclude_self)`` runs the EXISTING hybrid
    dense/sparse/brute pipeline per shard — AOT engine cache, pow2
    query buckets, and all four backends unchanged; every shard has the
    same static shapes, so each device compiles one set of engines and
    no shard recompiles per batch — and merges the P shard-local top-K
    candidate sets with a collective merge
    (``distributed.collective_topk_merge``: all-gather +
    ``knn_topk.merge_running_topk`` fold, or the ``ppermute`` tree-merge
    for large pow2 P).  The merge executable lives in the
    same AOT engine cache under kind ``"merge"``, so the zero-compile
    steady-state guarantee covers the collective step too.

Exactness bookkeeping: the true global KNN of a query is distributed
over shards, so each shard answers with ``k_eff = k (+1 if
exclude_self) (+1 if the shard count padded |D|)`` candidates —
self-exclusion happens at merge time by global id (the engines'
exclusion-id trick, no shard needs the query↔shard-row map), and an
uneven |D| pads each of the first ``n_pad`` shards with ONE duplicated
resident row whose repeated global id the merge dedups.  Either way a
shard's block always holds its k nearest *distinct, non-excluded*
points (or its entire sub-cloud), so the merged top-k is exact.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.core.hybrid as hybrid_lib
from repro.core import dense_join as dense_lib
from repro.core import distributed as dist_lib
from repro.core import grid as grid_lib
from repro.core import queue as queue_lib
from repro.core import splitter as split_lib
from repro.retrieval import metrics as met_lib
from repro.runtime import mutation as mut_lib
from repro.runtime.faults import FaultInjector
from repro.runtime.knn_index import (
    _ENGINE_CACHE, KNNIndex, _engine_key, executable_memory_analysis,
    pad_rows_pow2, run_engine, select_epsilon, traced_query, validate_k,
    validate_points,
)
from repro.runtime.serving import ServingConfig, ServingSupervisor
from repro.runtime.stragglers import OnlineRho
from repro.utils import cdiv, pow2_bucket

#: Mesh axis name reserved for replica groups (launch.make_serving_mesh):
#: index state is replicated along it, so it is never a shard axis.
REPLICA_AXIS = "replica"


def _block_devices(stacked: jax.Array, n_blocks: int) -> List:
    """The device holding each leading-axis block of a ``shard_map``
    output — replica 0's copy where the mesh replicates shard state."""
    out = [None] * n_blocks
    index_map = stacked.sharding.devices_indices_map(stacked.shape)
    for dev, idx in sorted(index_map.items(), key=lambda kv: kv[0].id):
        lo, hi, _ = idx[0].indices(n_blocks)
        for p in range(lo, hi):
            if out[p] is None:
                out[p] = dev
    return out


def _resolve_axes(mesh: Mesh, mesh_axis) -> Tuple[str, ...]:
    if mesh_axis is None:
        axes = tuple(a for a in mesh.axis_names if a != REPLICA_AXIS)
        return axes if axes else tuple(mesh.axis_names)
    if isinstance(mesh_axis, str):
        return (mesh_axis,)
    return tuple(mesh_axis)


@dataclasses.dataclass
class _ShardedGeneration:
    """One immutable built snapshot of the sharded reference cloud —
    the sharded analogue of ``knn_index._Generation``: the index holds
    ``self._live = (generation, mutations)`` and ``compact()`` swaps
    that one reference atomically (DESIGN.md §6)."""

    points_ref: object
    points_r: jnp.ndarray
    dim_perm: Optional[jnp.ndarray]
    eps: float
    eps_beta: float
    shards: List[KNNIndex]
    gids: np.ndarray                  # (P, shard_n) i32 global ids
    n_pad: int

    @property
    def n_base(self) -> int:
        return int(self.points_r.shape[0])

    @property
    def shard_n(self) -> int:
        return int(self.gids.shape[1])


class ShardedKNNIndex:
    """A reference cloud sharded over a device mesh, served by P
    shard-local hybrid pipelines plus one collective top-K merge.

    >>> mesh = make_serving_mesh(4)                  # launch.mesh
    >>> index = KNNIndex.build(db, cfg, mesh=mesh)   # -> ShardedKNNIndex
    >>> r = index.query(batch)                       # R≠S, exact
    >>> r = index.query(exclude_self=True)           # sharded self-join
    >>> index.compile_counts                         # incl. "merge"
    """

    def __init__(
        self,
        config: "hybrid_lib.HybridConfig",
        *,
        backend: str,
        mesh: Mesh,
        axes: Tuple[str, ...],
        merge: str,
        points_ref: object,
        points_r: jnp.ndarray,
        dim_perm: Optional[jnp.ndarray],
        eps: float,
        eps_beta: float,
        shards: List[KNNIndex],
        gids: np.ndarray,
        n_pad: int,
        t_select_eps: float = 0.0,
        t_build: float = 0.0,
        compile_counts: Optional[Dict[str, int]] = None,
        executables: Optional[Dict[str, object]] = None,
        epsilon_arg: Optional[float] = None,
    ):
        self.config = config
        self.backend = backend
        self.mesh = mesh
        self.axes = axes
        self.n_shards = len(shards)
        self.merge = dist_lib.merge_strategy(self.n_shards, merge)
        # Replica groups: every mesh axis NOT in the shard axes (the
        # REPLICA_AXIS of a 2-D serving mesh) multiplies into serving
        # lanes over the same shard state — routing/health/hedging run
        # per (replica, shard) lane (DESIGN.md §7).
        self.n_replicas = int(np.prod(
            [mesh.shape[a] for a in mesh.axis_names if a not in axes]
        )) if set(mesh.axis_names) - set(axes) else 1
        # Fault-tolerant serving state (configure_serving): lazily
        # auto-enabled on the first query when replica groups exist.
        self._supervisor: Optional[ServingSupervisor] = None
        self._faults: FaultInjector = FaultInjector()
        self._serve_step = 0
        self._rho_online = OnlineRho(alpha=0.3, warmup=1)
        gen = _ShardedGeneration(
            points_ref=points_ref,
            points_r=points_r,
            dim_perm=dim_perm,
            eps=eps,
            eps_beta=eps_beta,
            shards=shards,
            gids=gids,
            n_pad=n_pad,
        )
        # The atomic (generation, mutations) pair — see _ShardedGeneration.
        self._live: Tuple[_ShardedGeneration, mut_lib.MutationState] = (
            gen, mut_lib.MutationState.empty(int(points_r.shape[1]))
        )
        self.generation = 0
        self._epsilon_arg = epsilon_arg
        self.t_select_eps = t_select_eps
        self.t_build = t_build
        if compile_counts is None:
            compile_counts = {"dense": 0, "sparse": 0, "brute": 0}
        compile_counts.setdefault("merge", 0)
        self.compile_counts = compile_counts
        self.executables = executables if executables is not None else {}
        # Keyed (k_out, dedup): dedup depends on the live generation's
        # n_pad, which compaction may change.
        self._merge_jits: Dict[Tuple[int, bool], object] = {}

    # -- construction ------------------------------------------------------

    @classmethod
    def build(
        cls,
        points,
        config: "hybrid_lib.HybridConfig",
        epsilon: Optional[float] = None,
        *,
        mesh: Mesh,
        mesh_axis: Union[str, Sequence[str], None] = None,
        merge: str = "auto",
        backend: Optional[str] = None,
        compile_counts: Optional[Dict[str, int]] = None,
        executables: Optional[Dict[str, object]] = None,
        _prebuilt: Optional[tuple] = None,
    ) -> "ShardedKNNIndex":
        """Per-database steps, placement-aware: global REORDER + ε
        selection (one geometry for every shard), cell-sorted row-range
        partition, then the ``shard_map`` grid+pyramid build.
        ``_prebuilt`` replays a saved generation's REORDER + ε
        (``runtime.persistence``) so restarts recompute neither."""
        cfg = config
        if cfg.projection_dim > 0:
            raise ValueError(
                "projection_dim > 0 is single-device in this release — "
                "the projection front stage and the sharded cell-order "
                "partition do not compose yet.  Build without a mesh, "
                "or drop the projection."
            )
        axes = _resolve_axes(mesh, mesh_axis)
        n_shards = int(np.prod([mesh.shape[a] for a in axes]))
        # Metric contract on the corpus (DESIGN.md §9.2) — same check
        # as the single-device build, before anything is partitioned.
        pts = jnp.asarray(met_lib.prepare_rows(
            validate_points(points, None, what="indexed points"),
            cfg.metric, "indexed points", context="KNNIndex.build",
        ))
        npts, ndim = pts.shape
        validate_k(cfg.k, npts - 1, what="config.k",
                   context=" (build needs k < |D|)")
        assert n_shards >= 1
        # The ≤1-pad-row-per-shard invariant (merge dedup + k_eff
        # headroom) needs every shard to own at least one real point.
        assert npts >= n_shards, (
            f"|D|={npts} cannot shard over {n_shards} devices "
            "(need at least one reference point per shard)"
        )
        m = min(cfg.m, ndim)

        if _prebuilt is not None:
            points_r, dim_perm, eps, eps_beta = _prebuilt
            points_r = jnp.asarray(points_r, jnp.float32)
            t_select = 0.0
        else:
            # (1) REORDER — once, globally: every shard shares the perm.
            if cfg.reorder:
                points_r, dim_perm = grid_lib.reorder_by_variance(pts)
            else:
                points_r, dim_perm = pts, None

            # (2) ε selection — once, globally: one grid geometry class,
            # so P equal-shape shards compile the same engine shapes.
            eps, eps_beta, t_select = select_epsilon(
                points_r, cfg, epsilon, npts)

        t0 = time.perf_counter()
        # (3) partition: row ranges of the cell-sorted order of a global
        # ε-grid.  Consecutive rows of that order share (adjacent) grid
        # cells, so each shard covers a compact cell range and its local
        # grid stays dense — the grid-partitioned self-join layout.
        pgrid = grid_lib.build_grid(
            points_r, jnp.float32(eps), m, materialize_points=False
        )
        cell_order = np.asarray(pgrid.order)

        shard_n = cdiv(npts, n_shards)
        n_pad = shard_n * n_shards - npts
        # Uneven |D|: at most ONE duplicated row per shard — shards
        # 0..n_pad−1 take shard_n−1 real rows and repeat their last one,
        # so per-shard top-(k+1) still yields k distinct global ids and
        # the collective merge dedups the repeat.
        gids = np.empty((n_shards, shard_n), np.int32)
        off = 0
        for p in range(n_shards):
            real = shard_n - (1 if p < n_pad else 0)
            rows = cell_order[off:off + real]
            if real < shard_n:
                rows = np.concatenate([rows, rows[-1:]])
            gids[p] = rows
            off += real
        assert off == npts

        # (4) shard-local grid + pyramid, one shard_map program.
        pts_stacked = jax.device_put(                          # (P, s, n)
            np.asarray(points_r)[gids], NamedSharding(mesh, P(axes)))
        grids, pyramids = dist_lib.build_shard_indices(
            mesh, axes, pts_stacked, eps, m,
            n_levels=cfg.n_levels, level_scale=cfg.level_scale,
        )
        jax.block_until_ready(grids.unique_cells)

        bk = (backend if backend is not None
              else dense_lib.resolve_backend(cfg.backend))
        counts = (compile_counts if compile_counts is not None
                  else {"dense": 0, "sparse": 0, "brute": 0})
        execs = executables if executables is not None else {}

        # (5) each shard is a plain KNNIndex over its sub-cloud: REORDER
        # already applied, ε pinned, grid/pyramid prebuilt, counters and
        # executables shared so P shards look like one serving engine.
        # Each shard's state is committed to the device that built it, so
        # its engines compile for and run on that chip (the engine-cache
        # key carries the devices).
        shard_cfg = dataclasses.replace(cfg, reorder=False)
        devices = _block_devices(grids.unique_cells, n_shards)
        shards = []
        for p in range(n_shards):
            def local(x, p=p):
                return jax.device_put(x[p], devices[p])

            g = jax.tree_util.tree_map(local, grids)
            pyr = jax.tree_util.tree_map(local, pyramids)
            spts = local(pts_stacked)
            shards.append(KNNIndex(
                shard_cfg, backend=bk,
                points_ref=spts, points_r=spts, dim_perm=None,
                eps=eps, eps_beta=eps_beta, grid=g, pyramid=pyr,
                home_counts=np.asarray(g.cell_counts[g.point_cell_pos]),
                compile_counts=counts, executables=execs,
            ))
        t_build = time.perf_counter() - t0

        return cls(
            cfg, backend=bk, mesh=mesh, axes=axes, merge=merge,
            points_ref=points, points_r=points_r, dim_perm=dim_perm,
            eps=eps, eps_beta=eps_beta, shards=shards, gids=gids,
            n_pad=n_pad, t_select_eps=t_select, t_build=t_build,
            compile_counts=counts, executables=execs, epsilon_arg=epsilon,
        )

    # -- introspection -----------------------------------------------------

    # Generation-owned state under the pre-mutability attribute names
    # (reads the LIVE generation; compact() swaps it).
    @property
    def points_ref(self):
        return self._live[0].points_ref

    @property
    def points_r(self):
        return self._live[0].points_r

    @property
    def dim_perm(self):
        return self._live[0].dim_perm

    @property
    def eps(self) -> float:
        return self._live[0].eps

    @property
    def eps_beta(self) -> float:
        return self._live[0].eps_beta

    @property
    def shards(self) -> List[KNNIndex]:
        return self._live[0].shards

    @property
    def gids(self) -> np.ndarray:
        return self._live[0].gids

    @property
    def shard_n(self) -> int:
        return self._live[0].shard_n

    @property
    def n_pad(self) -> int:
        return self._live[0].n_pad

    @property
    def points(self):
        return self.points_ref

    @property
    def n_base(self) -> int:
        return self._live[0].n_base

    @property
    def n_points(self) -> int:
        """LIVE corpus size (= ``n_base`` on a clean index)."""
        gen, mut = self._live
        return mut.n_live(gen.n_base)

    @property
    def n_delta(self) -> int:
        return self._live[1].n_delta_live

    @property
    def n_tombstones(self) -> int:
        return self._live[1].n_base_tombs

    @property
    def is_clean(self) -> bool:
        return self._live[1].is_clean

    @property
    def n_dims(self) -> int:
        return int(self._live[0].points_r.shape[1])

    @property
    def mesh_shape(self) -> Tuple[int, ...]:
        return tuple(self.mesh.shape[a] for a in self.axes)

    @property
    def total_compiles(self) -> int:
        return sum(self.compile_counts.values())

    def cache_info(self) -> Dict[str, int]:
        return {"global_entries": len(_ENGINE_CACHE), **self.compile_counts}

    def memory_analysis(self):
        return executable_memory_analysis(self.executables)

    @property
    def placement_shape(self) -> Tuple[int, int]:
        """(replicas, shards) — the serving placement, independent of
        how the mesh spells its axes."""
        return (self.n_replicas, self.n_shards)

    # -- fault-tolerant serving (DESIGN.md §7) -----------------------------

    def configure_serving(
        self,
        serving: Optional[ServingConfig] = None,
        faults: Optional[FaultInjector] = None,
    ) -> ServingSupervisor:
        """Install (or replace) the fault policy for this index's query
        path: straggler-driven hedging, retry across replicas, health
        marking, degraded coverage.  ``faults`` plugs a deterministic
        ``FaultInjector`` in front of every sub-query (tests/benches).
        Returns the ``ServingSupervisor`` for introspection."""
        self._supervisor = ServingSupervisor(
            self.n_replicas, self.n_shards, serving)
        if faults is not None:
            self._faults = faults
        return self._supervisor

    @property
    def supervisor(self) -> Optional[ServingSupervisor]:
        """The active fault policy — auto-created on first query when
        the mesh has replica groups, else None until
        ``configure_serving``."""
        if self._supervisor is None and self.n_replicas > 1:
            self.configure_serving()
        return self._supervisor

    @property
    def rho_suggestion(self) -> Optional[float]:
        """Online Eq. 6 re-suggestion from the serve-time EWMA of the
        per-engine times (the paper's load-balance lever, reused as the
        straggler mitigation §V-F) — None before the first serve.  The
        EWMA + warmup gating lives in ``stragglers.OnlineRho``."""
        return self._rho_online.suggestion

    def _note_engine_times(self, t1: float, t2: float) -> None:
        self._rho_online.note(t1, t2)

    def _rho_override(self) -> Optional[float]:
        sup = self._supervisor
        if sup is None or not sup.cfg.adapt_rho:
            return None
        return self.rho_suggestion

    # -- persistence (DESIGN.md §7) ----------------------------------------

    def save(self, directory: str, *, manager=None) -> int:
        """Checkpoint the live *global* generation (placement is a
        load-time choice): ``KNNIndex.load(dir, mesh=...)`` rebuilds it
        onto any mesh shape with bit-identical answers — see
        ``runtime.persistence``."""
        from repro.runtime import persistence
        return persistence.save_index(self, directory, manager=manager)

    # -- collective merge engine -------------------------------------------

    def _merge(self, k_out: int, dists: np.ndarray, ids: np.ndarray,
               excl: np.ndarray, n_pad: int):
        """Run the collective merge through the AOT engine cache (kind
        ``"merge"``): same zero-compile steady-state contract as the
        dense/sparse/brute engines.  ``n_pad`` is the LIVE generation's
        pad count (dedup is only needed when a shard carries a
        duplicated pad row)."""
        dedup = n_pad > 0
        jitted = self._merge_jits.get((k_out, dedup))
        if jitted is None:
            jitted = dist_lib.collective_topk_merge(
                self.mesh, self.axes, k=k_out, strategy=self.merge,
                dedup=dedup,
            )
            self._merge_jits[(k_out, dedup)] = jitted
        args = (dists, ids, excl)
        kwargs = dict(k=k_out, strategy=self.merge, dedup=dedup,
                      axes=self.axes, mesh=self.mesh)
        key = _engine_key("merge", args, kwargs)
        ex = _ENGINE_CACHE.get(key)
        if ex is None:
            t0 = time.perf_counter()
            ex = jitted.lower(*args).compile()
            queue_lib.charge_compile(time.perf_counter() - t0)
            _ENGINE_CACHE[key] = ex
            self.compile_counts["merge"] += 1
        self.executables["merge"] = ex
        return jax.block_until_ready(ex(*args))

    # -- mutations (DESIGN.md §6) ------------------------------------------
    # Mutations live at the sharded level: shards stay clean single-
    # device indexes, the delta buffer / tombstones fold in after the
    # collective merge, and compact() re-partitions the net corpus.

    def insert(self, points) -> np.ndarray:
        """Add points (delta buffer).  Returns their global ids, valid
        as of this call's return (post-compaction ids if the insert
        tripped the auto-compact threshold)."""
        points = met_lib.prepare_rows(
            validate_points(points, self.n_dims, what="inserted points"),
            self.config.metric, "inserted points",
            context="KNNIndex.insert",
        )
        gen, mut = self._live
        new_mut, gids = mut.with_insert(points, gen.n_base, self.n_dims)
        self._live = (gen, new_mut)
        remap = self._maybe_autocompact()
        if remap is not None:
            gids = remap[gids]
        return gids

    def delete(self, ids) -> None:
        """Remove points by global id (tombstones).  Raises ValueError
        on unknown or already-deleted ids."""
        gen, mut = self._live
        self._live = (gen, mut.with_delete(ids, gen.n_base))
        self._maybe_autocompact()

    def net_points(self) -> np.ndarray:
        """The LIVE corpus in original dim order, ascending global id."""
        gen, mut = self._live
        return mut.net_corpus(np.asarray(gen.points_ref, np.float32))[0]

    def _maybe_autocompact(self) -> Optional[np.ndarray]:
        gen, mut = self._live
        frac = self.config.mutation_compact_frac
        if (mut.n_delta_rows > frac * gen.n_base
                or mut.n_base_tombs > frac * gen.n_base):
            return self.compact()
        return None

    def compact(self) -> np.ndarray:
        """Rebuild the sharded index over the net corpus — global
        REORDER + ε (replaying build()'s ε argument), re-partition,
        shard_map grid/pyramid build — into a fresh generation, swapped
        atomically.  Returns the old-id → new-id remap (−1 deleted).
        Same mesh/axes/merge strategy; the compile counters and
        executables carry over, and same-bucket shard shapes reuse every
        cached engine."""
        gen, mut = self._live
        if mut.is_clean:
            return np.arange(gen.n_base, dtype=np.int64)
        net, _ = mut.net_corpus(np.asarray(gen.points_ref, np.float32))
        assert self.config.k < len(net), (
            f"cannot compact: k={self.config.k} needs more than the "
            f"{len(net)} live points"
        )
        assert len(net) >= self.n_shards, (
            f"cannot compact: {len(net)} live points cannot shard over "
            f"{self.n_shards} devices"
        )
        remap = mut.remap_after_compact(gen.n_base)
        fresh = ShardedKNNIndex.build(
            net, self.config, self._epsilon_arg,
            mesh=self.mesh, mesh_axis=self.axes, merge=self.merge,
            backend=self.backend,
            compile_counts=self.compile_counts,
            executables=self.executables,
        )
        self._live = (
            fresh._live[0], mut_lib.MutationState.empty(self.n_dims)
        )
        self.generation += 1
        self.t_select_eps = fresh.t_select_eps
        self.t_build = fresh.t_build
        return remap

    # -- the query pipeline ------------------------------------------------

    @traced_query
    def query(
        self,
        queries=None,
        k: Optional[int] = None,
        exclude_self: bool = False,
        *,
        _serve_shards: Optional[Tuple[int, ...]] = None,
    ) -> "hybrid_lib.KNNResult":
        """Hybrid KNN of ``queries`` against the sharded reference cloud
        — the single-device ``KNNIndex.query`` contract, mesh-placed.

        Every shard serves the full batch as an R≠S join against its
        resident sub-cloud (the per-shard pipeline IS ``KNNIndex.query``
        — density split against the shard's grid, work queue, failure
        lanes, brute certification), then the P shard-local top-k_eff
        candidate sets meet in the collective merge.  ``exclude_self``
        masks global reference id i for query row i at merge time.
        With mutations pending the delta buffer and tombstones fold in
        after the collective merge (``_query_mutated``).

        ``_serve_shards`` is internal (the overload server's partial-
        answer degrade rung, DESIGN.md §8): only the listed shard ids
        run their sub-query; the rest contribute nothing and the result
        is the exact top-K over the SERVED shards, flagged via
        ``coverage`` (skipped columns False) and
        ``stats.shards_skipped`` — the same degraded-result contract as
        a lost shard, entered deliberately."""
        gen, mut = self._live
        if not mut.is_clean:
            return self._query_mutated(gen, mut, queries, k, exclude_self,
                                       _serve_shards=_serve_shards)
        cfg = self.config
        npts = gen.n_base
        max_k = npts - 1 if exclude_self else npts
        kq = validate_k(
            cfg.k if k is None else k, max_k,
            context=" after self-exclusion" if exclude_self else "",
        )
        compiles_before = self.total_compiles

        is_self = queries is None or queries is gen.points_ref
        if is_self:
            queries_r = gen.points_r
            n_q = npts
        else:
            q = jnp.asarray(met_lib.prepare_rows(
                validate_points(queries, self.n_dims),
                cfg.metric, "queries", context="KNNIndex.query",
            ))
            n_q = int(q.shape[0])
            queries_r = q[:, gen.dim_perm] if gen.dim_perm is not None else q

        # Candidate head-room: +1 when the merge masks the self id, +1
        # when a shard may carry one duplicated pad row (module
        # docstring) — capped at the shard size, where a shard returns
        # its whole sub-cloud and nothing can be lost.
        k_extra = (1 if exclude_self else 0) + (1 if gen.n_pad else 0)
        k_eff = min(kq + k_extra, gen.shard_n)

        excl = (np.arange(n_q, dtype=np.int32) if exclude_self
                else np.full((n_q,), -2, np.int32))
        md, mi, sources, shard_stats, t_merge, serve, skipped, ests = \
            self._shard_serve(
                gen, kq, k_eff, n_q, queries_r, excl,
                serve_shards=_serve_shards,
            )
        md = md[:n_q]
        mi = mi[:n_q]

        stats = self._stats(
            gen, shard_stats, t_merge, compiles_before, serve=serve,
            skipped=skipped,
        )
        return hybrid_lib.KNNResult(
            dists=md,
            ids=mi,
            # Per-query source over P pipelines: report the most
            # expensive path any shard took (0 dense < 1 sparse <
            # 2 brute) — the serving-latency-relevant label.
            source=np.max(sources, axis=0),
            stats=stats,
            coverage=self._coverage(n_q, serve, skipped),
            # Approximate shards (recall_target < 1.0) bound the merged
            # result from below by the weakest shard's measurement.
            recall_estimate=min(ests) if ests else 1.0,
        )

    def _query_mutated(
        self, gen: _ShardedGeneration, mut: "mut_lib.MutationState",
        queries, k: Optional[int], exclude_self: bool,
        _serve_shards: Optional[Tuple[int, ...]] = None,
    ) -> "hybrid_lib.KNNResult":
        """The dirty sharded query path: per-shard pipelines + the
        collective merge run over the BASE corpus at tombstone-
        headroomed k (exclusion deferred), then the same delta-buffer
        top-K and merge-time fold as the single-device path
        (``knn_index.KNNIndex._query_mutated``) mask tombstones/self by
        global id and fold the inserts in — exact for any mutation
        state.  Shards stay clean; mutations live at this level only."""
        cfg = self.config
        n_base = gen.n_base
        n_live = mut.n_live(n_base)
        max_k = n_live - 1 if exclude_self else n_live
        kq = validate_k(
            cfg.k if k is None else k, max_k,
            context=(" (live, after self-exclusion)" if exclude_self
                     else " (live)"),
        )
        compiles_before = self.total_compiles

        if queries is None:
            net, net_gids = mut.net_corpus(
                np.asarray(gen.points_ref, np.float32)
            )
            q = jnp.asarray(net)
            excl = (net_gids.astype(np.int32) if exclude_self
                    else np.full((len(net),), -2, np.int32))
        else:
            q = jnp.asarray(met_lib.prepare_rows(
                validate_points(queries, self.n_dims),
                cfg.metric, "queries", context="KNNIndex.query",
            ))
            excl = (np.arange(q.shape[0], dtype=np.int32) if exclude_self
                    else np.full((int(q.shape[0]),), -2, np.int32))
        n_q = int(q.shape[0])
        queries_r = q[:, gen.dim_perm] if gen.dim_perm is not None else q

        # Net-density correction per shard: every shard's split sees all
        # live delta points plus its OWN tombstoned rows (other shards'
        # tombstones are not in its grid).
        pts_r = np.asarray(gen.points_r)
        delta_live_r = mut.delta_r(gen.dim_perm)[mut.delta_live]
        shard_net_cells = []
        for p in range(self.n_shards):
            own = mut.base_tombs[np.isin(mut.base_tombs, gen.gids[p])]
            shard_net_cells.append((delta_live_r, pts_r[own]))

        # Headroom so merge-time masking cannot starve the top-k; the
        # collective runs at k_out with no exclusion (deferred to the
        # fold), each shard at k_out + the usual pad-row slack.
        k_out = min(
            kq + mut_lib.headroom_bucket(mut.n_base_tombs, exclude_self),
            n_base,
        )
        k_eff = min(k_out + (1 if gen.n_pad else 0), gen.shard_n)
        md, mi, sources, shard_stats, t_merge, serve, skipped, ests = \
            self._shard_serve(
                gen, k_out, k_eff, n_q, queries_r,
                np.full((n_q,), -2, np.int32), shard_net_cells,
                serve_shards=_serve_shards,
            )
        qb = int(md.shape[0])

        # Delta top-K + fold, through the shared AOT engine kinds
        # ("delta", "merge") — see runtime.mutation.
        t0 = time.perf_counter()
        queries_rp = pad_rows_pow2(queries_r, cfg.query_block)
        delta_pts_p, delta_gids = mut.padded_delta(gen.dim_perm, n_base)
        k_delta = min(kq, delta_pts_p.shape[0])
        excl_p = np.full((qb,), -2, np.int32)
        excl_p[:n_q] = excl
        dargs = (queries_rp, jnp.asarray(delta_pts_p),
                 jnp.asarray(excl_p), jnp.asarray(delta_gids))
        dkw = dict(k=k_delta, mode=cfg.kernel_mode,
                   metric=met_lib.kernel_metric(cfg.metric))
        dd, di = run_engine(
            self, "delta", mut_lib.delta_topk, dargs, dkw
        )(*dargs)
        # Shard distances are FINALIZED while the delta engine returns
        # raw scores — bring the delta block into the merged space
        # before folding (finalize is monotone per metric, so the fold
        # compares like with like).
        dd = met_lib.finalize(np.asarray(dd), cfg.metric)
        fargs = (jnp.asarray(md), jnp.asarray(mi), jnp.asarray(dd),
                 jnp.asarray(np.asarray(di)),
                 jnp.asarray(mut.tombstone_table()), jnp.asarray(excl_p))
        fkw = dict(k=kq)
        fd, fi = jax.block_until_ready(run_engine(
            self, "merge", mut_lib.fold_topk, fargs, fkw
        )(*fargs))
        t_delta = time.perf_counter() - t0

        stats = self._stats(
            gen, shard_stats, t_merge, compiles_before, t_delta=t_delta,
            serve=serve, skipped=skipped,
        )
        return hybrid_lib.KNNResult(
            dists=np.asarray(fd)[:n_q],
            ids=np.asarray(fi)[:n_q],
            source=np.max(sources, axis=0),
            stats=stats,
            coverage=self._coverage(n_q, serve, skipped),
            recall_estimate=min(ests) if ests else 1.0,
        )

    def _shard_serve(self, gen: _ShardedGeneration, k_out: int,
                     k_eff: int, n_q: int, queries_r, excl: np.ndarray,
                     shard_net_cells=None,
                     serve_shards: Optional[Tuple[int, ...]] = None):
        """Per-shard hybrid serves + the collective top-K merge: shard
        p answers k_eff candidates over its sub-cloud on its own device
        (equal shapes ⇒ one engine set per device), local ids map to
        global, and the collective reduces the P blocks to k_out over
        the query-shape bucket (same pow2
        rounding as the per-shard engines, so batch-size sweeps share
        merge executables too).  Returns the merged (qb, k_out) block
        (post-√ distances), per-shard sources/stats, the merge time,
        and the serve record (fault accounting; None when the index has
        no fault policy — single replica, never configured).

        With a ``ServingSupervisor`` active every sub-query runs
        through its retry/hedge loop (``serving.run_subquery``); a
        shard no replica could serve stays (+inf, −1) in the merge and
        is reported in ``serve["shards_lost"]`` — the degrade path."""
        cfg = self.config
        sup = self.supervisor
        rho_over = self._rho_override()
        step = self._serve_step
        self._serve_step += 1
        # (+inf, −1) baseline: a lost shard's block is already "no
        # candidates" for the merge.
        shard_d = np.full((self.n_shards, n_q, k_eff), np.inf, np.float32)
        shard_i = np.full((self.n_shards, n_q, k_eff), -1, np.int32)
        sources = np.zeros((self.n_shards, n_q), np.int32)
        shard_stats = []
        estimates = []
        serve = None if sup is None else {
            "n_hedged": 0, "n_hedge_wins": 0, "n_subquery_retries": 0,
            "n_subquery_failures": 0, "shards_lost": [],
            "t_effective": 0.0,
        }
        lane_times: Dict[int, float] = {}
        if serve_shards is not None:
            want = set(int(p) for p in serve_shards)
            if not want or not want <= set(range(self.n_shards)):
                raise ValueError(
                    f"_serve_shards={serve_shards!r}: need a non-empty "
                    f"subset of shard ids 0..{self.n_shards - 1}")
        skipped = [] if serve_shards is None else sorted(
            set(range(self.n_shards)) - want)

        def take(p, res):
            shard_d[p] = res.dists
            gid = gen.gids[p]
            li = res.ids
            shard_i[p] = np.where(li >= 0, gid[np.clip(li, 0, None)], -1)
            sources[p] = res.source
            shard_stats.append(res.stats)
            estimates.append(res.recall_estimate)

        for p, shard in enumerate(gen.shards):
            if p in skipped:
                # Deliberate partial serve: the (+inf, −1) baseline
                # already is "no candidates" for the merge.
                continue
            nc = None if shard_net_cells is None else shard_net_cells[p]
            if sup is None:
                take(p, shard.query(queries_r, k=k_eff, _net_cells=nc,
                                    _rho=rho_over))
                continue

            def attempt(replica, p=p, shard=shard, nc=nc):
                extra = self._faults.subquery(replica, p, step)
                t0 = time.perf_counter()
                res = shard.query(queries_r, k=k_eff, _net_cells=nc,
                                  _rho=rho_over)
                return res, time.perf_counter() - t0 + extra

            out = sup.run_subquery(p, step, attempt)
            serve["n_hedged"] += int(out.hedged)
            serve["n_hedge_wins"] += int(out.hedge_won)
            serve["n_subquery_retries"] += out.retries
            serve["n_subquery_failures"] += out.failures
            # A sub-query that compiled engines timed the compiler, not
            # the replica: keep it out of the straggler detector.
            if not (out.served and out.result.stats.n_engine_compiles):
                lane_times.update(out.times)
            if not out.served:
                serve["shards_lost"].append(p)
                continue
            serve["t_effective"] += out.t_effective
            take(p, out.result)

        if sup is not None:
            sup.observe(lane_times)
        if shard_stats:
            self._note_engine_times(
                float(np.mean([s.t1_per_query for s in shard_stats])),
                float(np.mean([s.t2_per_query for s in shard_stats])),
            )

        qb = pow2_bucket(n_q, cfg.query_block)
        dpad = np.full((self.n_shards, qb, k_eff), np.inf, np.float32)
        ipad = np.full((self.n_shards, qb, k_eff), -1, np.int32)
        epad = np.full((qb,), -2, np.int32)
        dpad[:, :n_q] = shard_d
        ipad[:, :n_q] = shard_i
        epad[:n_q] = excl

        t0 = time.perf_counter()
        md, mi = self._merge(k_out, dpad, ipad, epad, gen.n_pad)
        t_merge = time.perf_counter() - t0
        return (np.asarray(md), np.asarray(mi), sources, shard_stats,
                t_merge, serve, tuple(skipped), estimates)

    def _coverage(self, n_q: int, serve,
                  skipped: Tuple[int, ...] = ()) -> Optional[np.ndarray]:
        """The degraded-result contract: (|Q|, n_shards) bool, column s
        False iff shard s contributed nothing — all replicas failed it
        (``shards_lost``) or the caller skipped it deliberately
        (``_serve_shards``, the overload degrade rung).  None when no
        fault policy is active and nothing was skipped — coverage is
        then total by construction."""
        if serve is None and not skipped:
            return None
        cov = np.ones((n_q, self.n_shards), bool)
        for p in (serve["shards_lost"] if serve is not None else ()):
            cov[:, p] = False
        for p in skipped:
            cov[:, p] = False
        return cov

    def _stats(self, gen: _ShardedGeneration, shard_stats, t_merge: float,
               compiles_before: int, t_delta: float = 0.0, serve=None,
               skipped: Tuple[int, ...] = ()):
        if not shard_stats:
            # Every shard lost: no engine ran; report only the serve
            # accounting so the caller still sees an honest record.
            return hybrid_lib.JoinStats(
                epsilon=gen.eps, epsilon_beta=gen.eps_beta,
                t_merge=t_merge, t_delta=t_delta,
                t_wall=t_merge + t_delta,
                n_engine_compiles=self.total_compiles - compiles_before,
                n_hedged=serve["n_hedged"],
                n_hedge_wins=serve["n_hedge_wins"],
                n_subquery_retries=serve["n_subquery_retries"],
                n_subquery_failures=serve["n_subquery_failures"],
                shards_lost=tuple(serve["shards_lost"]),
                shards_skipped=skipped,
                t_effective=t_merge + t_delta,
            )
        t1 = float(np.mean([s.t1_per_query for s in shard_stats]))
        t2 = float(np.mean([s.t2_per_query for s in shard_stats]))
        t_wall = (sum(s.t_wall for s in shard_stats) + t_merge + t_delta)
        if serve is None:
            serve_kw = dict(t_effective=t_wall, shards_skipped=skipped)
        else:
            serve_kw = dict(
                n_hedged=serve["n_hedged"],
                n_hedge_wins=serve["n_hedge_wins"],
                n_subquery_retries=serve["n_subquery_retries"],
                n_subquery_failures=serve["n_subquery_failures"],
                shards_lost=tuple(serve["shards_lost"]),
                shards_skipped=skipped,
                t_effective=serve["t_effective"] + t_merge + t_delta,
            )
        return hybrid_lib.JoinStats(
            epsilon=gen.eps,
            epsilon_beta=gen.eps_beta,
            # Engine-assignment counts sum over shards (each shard
            # classifies the full batch against ITS grid): totals are
            # P·|Q|, the actual work dispatched.
            n_dense=sum(s.n_dense for s in shard_stats),
            n_sparse=sum(s.n_sparse for s in shard_stats),
            n_failed=sum(s.n_failed for s in shard_stats),
            n_uncertified=sum(s.n_uncertified for s in shard_stats),
            n_thresh=shard_stats[0].n_thresh,
            t_dense=sum(s.t_dense for s in shard_stats),
            t_sparse=sum(s.t_sparse for s in shard_stats),
            t_brute=sum(s.t_brute for s in shard_stats),
            t_delta=t_delta,
            t_wall=t_wall,
            t_merge=t_merge,
            t1_per_query=t1,
            t2_per_query=t2,
            rho_model=split_lib.rho_model(t1, t2),
            n_batches=sum(s.n_batches for s in shard_stats),
            batch_sizes=[b for s in shard_stats for b in s.batch_sizes],
            t_dense_batches=[t for s in shard_stats
                             for t in s.t_dense_batches],
            n_rebalanced=sum(s.n_rebalanced for s in shard_stats),
            n_sparse_rounds=sum(s.n_sparse_rounds for s in shard_stats),
            n_sparse_engine_total=sum(
                s.n_sparse_engine_total for s in shard_stats),
            n_sparse_gated=sum(s.n_sparse_gated for s in shard_stats),
            n_dense_overflow=sum(s.n_dense_overflow for s in shard_stats),
            dense_pairs=sum(s.dense_pairs for s in shard_stats),
            dense_cand_rows=sum(s.dense_cand_rows for s in shard_stats),
            n_sparse_overflow=sum(s.n_sparse_overflow for s in shard_stats),
            rho_online=float(np.mean(
                [s.rho_online for s in shard_stats])),
            n_engine_compiles=self.total_compiles - compiles_before,
            **serve_kw,
        )
