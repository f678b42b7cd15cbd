"""Index/query serving API (DESIGN.md §3): build once, query many.

``HybridKNNJoin.join`` fuses index construction and query execution
into one monolithic self-join — the right shape for the paper's batch
experiments, the wrong one for the serving workloads the ROADMAP
targets (many query batches against a static database, foreign R≠S
query sets).  ``KNNIndex`` splits Algorithm 1 at its natural seam:

  * ``KNNIndex.build(points, config)`` runs the *per-database* steps
    once — REORDER by variance (§IV-D), ε selection (§V-C), ε-grid +
    pyramid construction (§IV-A, DESIGN.md §2.2) — and owns the AOT
    engine-executable cache;
  * ``index.query(queries, k=None, exclude_self=False)`` runs the
    hybrid dense/sparse/brute pipeline (§V-D split by *reference-grid*
    density, §V-A work queue, §V-E failure reassignment, brute
    certification) for an arbitrary query set against the indexed
    reference cloud.  The classic self-join is the special case
    ``index.query(exclude_self=True)`` (or passing the indexed array
    itself), which is exactly what ``JoinSession.join`` now does.

Buffer k-d trees (Gieseke et al.) and Garcia et al.'s GPU brute force
expose the same build-once/query-many shape; here both engines serve
it from one index.

Engine-cache keys and the query-shape bucket: executables are lowered
per (pytree structure, leaf avals, static params).  Query-id vectors
are pow2-padded (``hybrid._pad_ids``) and foreign query *arrays* are
row-padded to pow2 multiples of ``query_block`` (``pad_rows_pow2``),
so a stream of variable-sized query batches collapses onto a handful
of cache keys — steady-state ``index.query`` calls in one bucket
compile **zero** new engines (the probe tests assert this).

The executable cache is process-global (indexes with identical configs
and shapes share compilations, like jit's internal cache); each index
counts only the misses it caused, into a counter dict a ``JoinSession``
may share across the indexes it builds.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.hybrid as hybrid_lib
from repro.core import brute as brute_lib
from repro.core import dense_join as dense_lib
from repro.core import epsilon as eps_lib
from repro.core import grid as grid_lib
from repro.core import queue as queue_lib
from repro.core import sparse_knn as sparse_lib
from repro.core import splitter as split_lib
from repro.kernels import f32_precision
from repro.retrieval import metrics as met_lib
from repro.retrieval import projection as proj_lib
from repro.runtime import mutation as mut_lib
from repro.runtime import trace
from repro.utils import pad_to, pow2_bucket

# Process-global AOT executable cache: key -> jax.stages.Compiled.
_ENGINE_CACHE: Dict[tuple, object] = {}


def clear_engine_cache() -> None:
    """Drop all cached executables (tests / memory pressure)."""
    _ENGINE_CACHE.clear()


def _engine_key(kind: str, args: tuple, kwargs: dict) -> tuple:
    """Cache key: pytree structure (static fields ride in the treedef),
    leaf avals (shape, dtype), the devices committed arguments live on
    (an executable runs on the devices it was compiled for, and each
    shard of a ``ShardedKNNIndex`` lives on its own), and the static
    kwargs."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    avals = tuple(
        (tuple(np.shape(leaf)), str(jnp.result_type(leaf))) for leaf in leaves
    )
    devices = tuple(sorted({
        d.id for leaf in leaves
        if isinstance(leaf, jax.Array) and leaf.committed
        for d in leaf.sharding.device_set
    }))
    return (kind, treedef, avals, devices, tuple(sorted(kwargs.items())))


def run_engine(owner, kind: str, jitted, args: tuple, kwargs: dict):
    """Lower/compile through the process-global AOT cache, charging the
    miss to ``owner.compile_counts[kind]`` — the one engine-dispatch
    path shared by ``KNNIndex`` and ``ShardedKNNIndex`` (tolerant of
    kinds the owner's counter dict has not seen, e.g. the mutation
    engines ``"delta"``/``"merge"``)."""
    key = _engine_key(kind, args, kwargs)
    ex = _ENGINE_CACHE.get(key)
    if ex is None:
        t0 = time.perf_counter()
        ex = jitted.lower(*args, **kwargs).compile()
        queue_lib.charge_compile(time.perf_counter() - t0)
        _ENGINE_CACHE[key] = ex
        owner.compile_counts[kind] = owner.compile_counts.get(kind, 0) + 1
    owner.executables[kind] = ex
    return ex


def traced_query(query):
    """Wrap an index's ``query``: one ``knn.query`` span over the call,
    and the programs JAX compiled or loaded during it (any thread; see
    ``runtime/trace.py``) in the result's stats."""

    @functools.wraps(query)
    def traced(*args, **kwargs):
        before = trace.snapshot()
        with trace.span("knn.query"):
            res = query(*args, **kwargs)
        compiles = trace.since(before)
        res.stats.n_programs_compiled = compiles.programs
        res.stats.t_compile = compiles.seconds
        res.stats.compiles_by_span = compiles.by_span
        return res

    return traced


def validate_points(arr, n_dims: Optional[int], what: str = "queries"):
    """Serving-surface input validation: reject dtype/shape mismatches
    with an actionable ``ValueError`` *before* anything reaches the
    engine stack (where they would surface as cryptic shape errors from
    deep inside a compiled kernel).  Returns the validated array
    unconverted — callers keep their own ``jnp.asarray`` casts."""
    try:
        a = np.asarray(arr)
    except Exception as e:
        raise ValueError(f"{what} must be an array-like of numbers "
                         f"({type(arr).__name__} is not)") from e
    if a.dtype.kind not in "iuf":
        raise ValueError(
            f"{what} must have a real numeric dtype (int or float), got "
            f"{a.dtype} — the index stores float32 coordinates")
    if a.ndim != 2:
        raise ValueError(
            f"{what} must be a 2-D (rows, dims) array, got shape {a.shape}")
    if n_dims is not None and a.shape[1] != n_dims:
        raise ValueError(
            f"{what} have {a.shape[1]} dims but the index was built over "
            f"{n_dims}-dim points — shape must be (rows, {n_dims})")
    return a


def validate_k(k, available: int, *, what: str = "k",
               context: str = "") -> int:
    """Serving-surface ``k`` validation, the ``validate_points``
    counterpart: reject non-int / non-positive / larger-than-the-net-
    corpus ``k`` with an actionable ``ValueError`` before anything
    reaches the engine stack.  ``available`` is the number of reference
    points a query can actually return (post self-exclusion, post
    tombstones); ``context`` is appended to the too-large message.
    Returns ``k`` as a plain int."""
    if isinstance(k, bool) or not isinstance(k, (int, np.integer)):
        raise ValueError(
            f"{what} must be an int, got {type(k).__name__} ({k!r})")
    k = int(k)
    if k < 1:
        raise ValueError(f"{what} must be >= 1, got {k}")
    if k > available:
        raise ValueError(
            f"{what}={k} exceeds the {available} reference points "
            f"available{context}")
    return k


def pad_rows_pow2(arr: jnp.ndarray, block: int) -> jnp.ndarray:
    """Pad an array's leading axis to a pow2 multiple of ``block`` (zero
    fill) — the query-shape bucket: engine-cache keys see the padded
    aval, so variable-sized query batches share compiled executables.
    Uses the same ``utils.pow2_bucket`` rounding as ``hybrid._pad_ids``."""
    return pad_to(arr, pow2_bucket(arr.shape[0], block))


def select_epsilon(points_r, cfg, epsilon, npts):
    """Step 2 of Algorithm 1 (§V-C2), shared by the single-device and
    sharded builds: returns ``(eps, eps_beta, t_select)``, skipping the
    sampling sweep when the caller pins ``epsilon``."""
    t0 = time.perf_counter()
    if epsilon is None:
        sel = eps_lib.select_epsilon(
            points_r, jax.random.PRNGKey(cfg.seed), cfg.k, cfg.beta,
            n_query_sample=min(cfg.n_query_sample, npts),
            n_bins=cfg.n_bins,
            n_pair_sample=cfg.n_pair_sample,
        )
        eps = float(jax.block_until_ready(sel.epsilon))
        eps_beta = float(sel.epsilon_beta)
    else:
        eps, eps_beta = float(epsilon), float(epsilon) / 2.0
    return eps, eps_beta, time.perf_counter() - t0


def executable_memory_analysis(executables: Dict[str, object]):
    """Compiler memory analysis per engine kind (bytes), for the
    benchmark JSON's peak-HBM trajectory.  ``None`` where the backend's
    ``Compiled.memory_analysis()`` is unavailable (e.g. some CPU
    builds)."""
    out: Dict[str, Optional[Dict[str, int]]] = {}
    fields = (
        "temp_size_in_bytes", "argument_size_in_bytes",
        "output_size_in_bytes", "generated_code_size_in_bytes",
    )
    for kind, ex in executables.items():
        try:
            ma = ex.memory_analysis()
            rec = {
                f: int(getattr(ma, f))
                for f in fields if hasattr(ma, f)
            }
            out[kind] = rec or None
        except Exception:
            out[kind] = None
    return out


@functools.partial(
    jax.jit,
    static_argnames=("k", "corpus_chunk", "kernel_mode", "exclude_self",
                     "metric"),
)
def _brute_engine(points_r, query_ids, queries_r=None, *, k, corpus_chunk,
                  kernel_mode, exclude_self=True, metric="l2"):
    """Brute lane with the query gather fused in, so the AOT signature is
    (corpus, padded ids[, padded foreign queries]) only."""
    queries = points_r if queries_r is None else queries_r
    safe = jnp.clip(query_ids, 0, queries.shape[0] - 1)
    return brute_lib.brute_knn(
        points_r, queries[safe],
        dense_lib._exclusion_ids(query_ids, exclude_self),
        k=k, corpus_chunk=corpus_chunk, kernel_mode=kernel_mode,
        metric=metric,
    )


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _rescore_engine(points_full, queries_f, cand_ids, excl, *, k, metric):
    """Full-dimension exact rescore of the projection front stage's
    surviving candidates (engine kind ``"rescore"``, DESIGN.md §9.3):
    gather each query's candidate rows from the full-dim corpus,
    compute true-metric scores as one batched MXU dot_general, and keep
    the K best.  Returns raw scores (squared L2 / −q·c) aligned with
    the padded query rows; invalid candidates (−1 ids from the
    candidate stage) and the per-query excluded id are masked."""
    safe = jnp.clip(cand_ids, 0, points_full.shape[0] - 1)
    cand_pts = points_full[safe]                       # (Qp, kc, d)
    if metric == "ip":
        d = -jax.lax.dot_general(
            queries_f, cand_pts, (((1,), (2,)), ((0,), (0,))),
            precision=f32_precision(queries_f.dtype),
            preferred_element_type=jnp.float32,
        )                                              # (Qp, kc)
    else:
        diff = queries_f[:, None, :] - cand_pts
        d = jnp.sum(diff * diff, axis=-1)
    valid = (cand_ids >= 0) & (cand_ids != excl[:, None])
    dm = jnp.where(valid, d, jnp.inf)
    neg, sel = jax.lax.top_k(-dm, k)
    kd = -neg
    ki = jnp.where(
        jnp.isinf(kd), -1, jnp.take_along_axis(cand_ids, sel, axis=1)
    )
    return kd, ki


@dataclasses.dataclass
class _Generation:
    """One immutable built snapshot of the reference cloud — everything
    ``query`` reads that ``compact()`` replaces.  The index holds
    ``self._live = (generation, mutations)`` and swaps that ONE
    reference atomically, so an in-flight query (which snapshots the
    pair once at entry) is unharmed by a concurrent compaction
    (DESIGN.md §6)."""

    points_ref: object
    points_r: jnp.ndarray
    dim_perm: Optional[jnp.ndarray]
    eps: float
    eps_beta: float
    grid: grid_lib.GridIndex
    pyramid: sparse_lib.Pyramid
    home_counts: np.ndarray                 # (|D|,) self-cloud densities
    # Projection front stage (DESIGN.md §9.3): when set, ``points_r``/
    # grid/pyramid live in PROJECTED (m ≤ 8 dim) space and
    # ``points_full`` holds the full-dim corpus the rescore engine
    # reads.  None on a direct (unprojected) index.
    projection: Optional[proj_lib.Projection] = None
    points_full: Optional[jnp.ndarray] = None
    # Self-split cache per (k, ρ): (dense_ids, sparse_ids, threshold) —
    # generation-owned because it derives from this grid's densities.
    # ρ keys the cache because serving may override the config floor
    # online (straggler-driven Eq. 6 re-suggestion, DESIGN.md §7).
    self_splits: Dict[Tuple[int, float],
                      Tuple[np.ndarray, np.ndarray, float]] = (
        dataclasses.field(default_factory=dict)
    )
    # Calibration cache (DESIGN.md §9.4): key -> (tier, recall_estimate)
    # measured once per generation against a held-out corpus sample.
    calib: Dict[tuple, Tuple[Optional[float], float]] = (
        dataclasses.field(default_factory=dict)
    )

    @property
    def n_base(self) -> int:
        return int(self.points_r.shape[0])


class KNNIndex:
    """A built reference cloud plus everything needed to serve queries.

    >>> index = KNNIndex.build(db_points, HybridConfig(k=10))
    >>> r = index.query(batch)                     # R≠S join, k=10
    >>> r = index.query(batch, k=3)                # per-call k override
    >>> r = index.query(exclude_self=True)         # the classic self-join
    >>> index.compile_counts                       # AOT cache misses so far

    ``exclude_self`` masks, for query row i, the reference point at the
    same position i — meaningful when the query set aliases (a prefix
    of) the indexed cloud.  Without it, a point queried against its own
    index reports itself at distance 0 as its first neighbor.

    The index is *mutable* (DESIGN.md §6): ``insert(points)`` /
    ``delete(ids)`` absorb corpus changes into a delta buffer +
    tombstone set that queries fold in exactly, and ``compact()``
    rebuilds into a fresh generation (auto-triggered when either side
    outgrows ``config.mutation_compact_frac·|D|``).  Global ids: build
    row i is id i; the j-th insert since the last compaction is
    ``n_base + j``; compaction renumbers (it returns the remap).
    """

    def __init__(
        self,
        config: "hybrid_lib.HybridConfig",
        *,
        backend: str,
        points_ref: object,
        points_r: jnp.ndarray,
        dim_perm: Optional[jnp.ndarray],
        eps: float,
        eps_beta: float,
        grid: grid_lib.GridIndex,
        pyramid: sparse_lib.Pyramid,
        home_counts: np.ndarray,
        t_select_eps: float = 0.0,
        t_build: float = 0.0,
        compile_counts: Optional[Dict[str, int]] = None,
        executables: Optional[Dict[str, object]] = None,
        epsilon_arg: Optional[float] = None,
        projection: Optional[proj_lib.Projection] = None,
        points_full: Optional[jnp.ndarray] = None,
    ):
        self.config = config
        self.backend = backend
        gen = _Generation(
            points_ref=points_ref,
            points_r=points_r,
            dim_perm=dim_perm,
            eps=eps,
            eps_beta=eps_beta,
            grid=grid,
            pyramid=pyramid,
            home_counts=home_counts,
            projection=projection,
            points_full=points_full,
        )
        # Delta rows arrive in the corpus' ORIGINAL (full) dim order.
        mut_dims = (projection.in_dim if projection is not None
                    else int(points_r.shape[1]))
        # The atomic (generation, mutations) pair — see _Generation.
        self._live: Tuple[_Generation, mut_lib.MutationState] = (
            gen, mut_lib.MutationState.empty(mut_dims)
        )
        self.generation = 0
        # The ε *argument* build() was given (None = re-select), replayed
        # by compact() so a rebuilt generation is bit-identical to
        # KNNIndex.build(net_corpus, config, epsilon_arg).
        self._epsilon_arg = epsilon_arg
        self.t_select_eps = t_select_eps
        self.t_build = t_build
        # Shared with the owning session when one exists, so serving
        # dashboards see one counter across index rebuilds.
        self.compile_counts = (
            compile_counts if compile_counts is not None
            else {"dense": 0, "sparse": 0, "brute": 0}
        )
        self.executables = executables if executables is not None else {}

    # -- construction ------------------------------------------------------

    @classmethod
    def build(
        cls,
        points,
        config: "hybrid_lib.HybridConfig",
        epsilon: Optional[float] = None,
        *,
        backend: Optional[str] = None,
        compile_counts: Optional[Dict[str, int]] = None,
        executables: Optional[Dict[str, object]] = None,
        mesh=None,
        mesh_axis=None,
        merge: str = "auto",
        _prebuilt: Optional[tuple] = None,
    ):
        """Steps 1–3 of Algorithm 1, once per database: REORDER,
        ε selection (skipped when the caller pins ``epsilon``), grid +
        pyramid construction.  ``backend``/counter kwargs let a
        ``JoinSession`` share its resolved backend and compile
        accounting; standalone callers omit them.

        ``mesh`` makes placement a build parameter instead of a fork
        (DESIGN.md §5): with a ``jax.sharding.Mesh`` the reference cloud
        is partitioned into per-device shards and a ``ShardedKNNIndex``
        is returned — same ``query()`` contract, shard-local hybrid
        pipelines plus a collective top-K merge (``mesh_axis`` names
        the shard axis/axes, default all; ``merge`` picks the collective
        strategy, see ``core.distributed.merge_strategy``).

        ``_prebuilt`` is internal (checkpoint restore): a
        ``(points_r, dim_perm, eps, eps_beta[, projection])`` tuple
        replaying a saved generation's REORDER + ε (+ fitted projection)
        verbatim, so ``load`` never recomputes any of them."""
        if mesh is not None:
            if config.projection_dim > 0:
                raise ValueError(
                    "projection_dim > 0 is single-device in this release "
                    "— the projection front stage and the sharded "
                    "cell-order partition do not compose yet.  Build "
                    "without a mesh, or drop the projection."
                )
            from repro.runtime.sharded_index import ShardedKNNIndex

            return ShardedKNNIndex.build(
                points, config, epsilon,
                mesh=mesh, mesh_axis=mesh_axis, merge=merge,
                backend=backend, compile_counts=compile_counts,
                executables=executables, _prebuilt=_prebuilt,
            )
        cfg = config
        # Metric contract on the corpus (DESIGN.md §9.2): cosine demands
        # unit rows — reject, with a pointer to normalize_rows, before
        # anything is indexed.
        pts_np = met_lib.prepare_rows(
            validate_points(points, None, what="indexed points"),
            cfg.metric, "indexed points", context="KNNIndex.build",
        )
        npts, ndim = pts_np.shape
        # k < |D| at build time: the self-join must find k OTHER points.
        validate_k(cfg.k, npts - 1, what="config.k",
                   context=" (build needs k < |D|)")

        projection = None
        points_full = None
        if _prebuilt is not None:
            points_r, dim_perm, eps, eps_beta = _prebuilt[:4]
            if len(_prebuilt) > 4:
                projection = _prebuilt[4]
            points_r = jnp.asarray(points_r, jnp.float32)
            t_select = 0.0
        else:
            if cfg.projection_dim > 0:
                # Projection front stage (DESIGN.md §9.3): grid/pyramid
                # over the m-dim projected corpus; REORDER is skipped
                # (the PCA fit already orders directions by variance,
                # and a random map has none to exploit).
                # An ip index fits over the MIPS→L2 augmented corpus so
                # projected-L2 candidate ranking tracks inner-product
                # ranking (see retrieval.projection.Projection).
                projection = proj_lib.fit_projection(
                    pts_np, cfg.projection_dim,
                    kind=cfg.projection_kind, seed=cfg.seed,
                    mips=(cfg.metric == "ip"),
                )
                points_r = jnp.asarray(projection.apply(pts_np,
                                                        corpus=True))
                dim_perm = None
            # (1) REORDER — distances are dim-perm invariant (§IV-D).
            elif cfg.reorder:
                points_r, dim_perm = grid_lib.reorder_by_variance(
                    jnp.asarray(pts_np))
            else:
                points_r, dim_perm = jnp.asarray(pts_np), None

            # (2) ε selection (§V-C2) — skipped when the caller pins ε.
            eps, eps_beta, t_select = select_epsilon(
                points_r, cfg, epsilon, npts)
        if projection is not None:
            points_full = jnp.asarray(pts_np)
        m = min(cfg.m, int(points_r.shape[1]))

        # (3) grid + pyramid indices (owned by this object).
        t0 = time.perf_counter()
        grid = grid_lib.build_grid(points_r, jnp.float32(eps), m)
        pyramid = sparse_lib.build_pyramid(
            points_r, jnp.float32(eps), m,
            n_levels=cfg.n_levels, level_scale=cfg.level_scale,
        )
        jax.block_until_ready(grid.unique_cells)
        t_build = time.perf_counter() - t0

        home_counts = np.asarray(grid.cell_counts[grid.point_cell_pos])
        return cls(
            cfg,
            backend=(backend if backend is not None
                     else dense_lib.resolve_backend(cfg.backend)),
            points_ref=points,
            points_r=points_r,
            dim_perm=dim_perm,
            eps=eps,
            eps_beta=eps_beta,
            grid=grid,
            pyramid=pyramid,
            home_counts=home_counts,
            t_select_eps=t_select,
            t_build=t_build,
            compile_counts=compile_counts,
            executables=executables,
            epsilon_arg=epsilon,
            projection=projection,
            points_full=points_full,
        )

    # -- introspection -----------------------------------------------------

    # Generation-owned state, exposed under the pre-mutability attribute
    # names: these read the LIVE generation, so they move when compact()
    # swaps it.
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
    def grid(self):
        return self._live[0].grid

    @property
    def pyramid(self):
        return self._live[0].pyramid

    @property
    def home_counts(self):
        return self._live[0].home_counts

    @property
    def points(self):
        """The live generation's base cloud in original dim order (the
        array passed to ``build``, or the net corpus of the last
        compaction).  ``index.query(index.points, exclude_self=True)``
        is the classic self-join; with mutations pending, prefer
        ``net_points()``."""
        return self.points_ref

    @property
    def n_base(self) -> int:
        """Base-corpus size of the live generation (grid/pyramid rows)."""
        return self._live[0].n_base

    @property
    def n_points(self) -> int:
        """LIVE corpus size: |base| − tombstones + live delta rows —
        equals ``n_base`` on a clean index."""
        gen, mut = self._live
        return mut.n_live(gen.n_base)

    @property
    def n_delta(self) -> int:
        """Live (non-tombstoned) delta-buffer rows."""
        return self._live[1].n_delta_live

    @property
    def n_tombstones(self) -> int:
        """Tombstoned BASE rows (deleted delta rows just vanish from the
        buffer's live set and are not counted here)."""
        return self._live[1].n_base_tombs

    @property
    def is_clean(self) -> bool:
        """True iff no mutations are pending against the live generation
        — queries take the original zero-overhead path."""
        return self._live[1].is_clean

    @property
    def n_dims(self) -> int:
        """Query-facing dimensionality: what ``query``/``insert`` rows
        must have — the FULL corpus dim even when the grid lives in
        projected space."""
        gen = self._live[0]
        if gen.projection is not None:
            return gen.projection.in_dim
        return int(gen.points_r.shape[1])

    @property
    def projection(self) -> Optional[proj_lib.Projection]:
        """The live generation's fitted projection front stage (None on
        a direct index)."""
        return self._live[0].projection

    @property
    def total_compiles(self) -> int:
        return sum(self.compile_counts.values())

    def cache_info(self) -> Dict[str, int]:
        return {"global_entries": len(_ENGINE_CACHE), **self.compile_counts}

    def memory_analysis(self) -> Dict[str, Optional[Dict[str, int]]]:
        """Compiler memory analysis per engine kind (bytes) — see
        ``executable_memory_analysis``."""
        return executable_memory_analysis(self.executables)

    # -- persistence (DESIGN.md §7) ----------------------------------------

    def save(self, directory: str, *, manager=None) -> int:
        """Checkpoint the live generation (points, REORDER permutation,
        ε, mutation state) through the atomic tmp+rename format of
        ``checkpoint.CheckpointManager``; returns the step number
        written (auto-incremented, so repeated saves keep a generation
        history).  ``KNNIndex.load`` round-trips onto any mesh shape
        with bit-identical answers."""
        from repro.runtime import persistence
        return persistence.save_index(self, directory, manager=manager)

    @classmethod
    def load(cls, directory: str, *, mesh=None, mesh_axis=None,
             merge: str = "auto", step: Optional[int] = None,
             backend: Optional[str] = None,
             compile_counts: Optional[Dict[str, int]] = None,
             executables: Optional[Dict[str, object]] = None):
        """Rebuild a served index from a saved generation — the restart
        path.  REORDER and ε selection are NOT recomputed (the stored
        permutation and ε are replayed), and ``mesh`` routes exactly
        like ``build``: None rebuilds a single-device ``KNNIndex``, a
        ``jax.sharding.Mesh`` repartitions the same generation into a
        ``ShardedKNNIndex`` — any shape, answers bit-identical to the
        saved index."""
        from repro.runtime import persistence
        return persistence.load_index(
            directory, mesh=mesh, mesh_axis=mesh_axis, merge=merge,
            step=step, backend=backend, compile_counts=compile_counts,
            executables=executables,
        )

    # -- engine cache ------------------------------------------------------

    def _engine(self, kind: str, jitted, args: tuple, kwargs: dict):
        return run_engine(self, kind, jitted, args, kwargs)

    # -- engine callables for the work queue -------------------------------
    # Each closure binds one _Generation explicitly (NOT self.grid etc.)
    # so a compact() mid-query cannot mix generations' state.

    def _grid_metric(self, gen: _Generation) -> str:
        """The metric the grid-space engines run in: cosine collapses
        onto the l2 kernels (pre-normalized rows), and a projected grid
        is ALWAYS l2 space — the true metric returns at rescore time."""
        if gen.projection is not None:
            return "l2"
        return met_lib.kernel_metric(self.config.metric)

    def _dense_fn(self, gen: _Generation, k: int, queries_rp,
                  exclude_self: bool, report: queue_lib.QueueReport,
                  eps_scale: Optional[float] = None):
        """The dense engine over query ids; each harvest adds the
        engine's work counters to ``report``."""
        cfg = self.config
        # ε is a RUNTIME operand: the approximate mode's scaled ε
        # (DESIGN.md §9.4) reuses the exact path's executable.
        eps_arg = jnp.float32(
            gen.eps if eps_scale is None else gen.eps * eps_scale)

        def dense_fn(ids: np.ndarray, compile_only: bool = False):
            qp = hybrid_lib._pad_ids(ids, cfg.query_block)
            args = (gen.grid, gen.points_r, qp, eps_arg)
            if queries_rp is not None:
                args = args + (queries_rp,)
            kwargs = dict(
                k=k, budget=cfg.dense_budget, query_block=cfg.query_block,
                block_c=cfg.block_c, backend=self.backend,
                exclude_self=exclude_self, metric=self._grid_metric(gen),
                distance_dtype=cfg.distance_dtype,
            )
            ex = self._engine("dense", dense_lib.dense_join_jit, args, kwargs)
            if compile_only:
                return None
            t0 = time.perf_counter()
            with trace.span("knn.dense.wait"):
                res = jax.block_until_ready(ex(*args))
            dt = time.perf_counter() - t0
            n = len(ids)
            with trace.span("knn.dense.copy"):
                out = (
                    np.asarray(res.dists[:n]),
                    np.asarray(res.ids[:n]),
                    np.asarray(res.failed[:n]),
                    dt,
                )
                ovf, pairs, cand_rows = jax.device_get(
                    (res.n_overflow, res.pairs, res.cand_rows))
            report.n_dense_overflow += int(ovf)
            report.dense_pairs += int(pairs)
            report.dense_cand_rows += int(cand_rows)
            return out

        return dense_fn

    def _sparse_fn(self, gen: _Generation, k: int, queries_rp,
                   exclude_self: bool, report: queue_lib.QueueReport):
        """The sparse engine over query ids, dispatched async; each
        harvest adds its overflow count to ``report``."""
        cfg = self.config

        def sparse_fn(ids: np.ndarray, compile_only: bool = False):
            qp = hybrid_lib._pad_ids(ids, cfg.query_block)
            args = (gen.pyramid, gen.points_r, qp)
            if queries_rp is not None:
                args = args + (queries_rp,)
            kwargs = dict(
                k=k, budget=cfg.sparse_budget,
                query_block=cfg.query_block, sel_factor=cfg.sel_factor,
                backend=self.backend, exclude_self=exclude_self,
                metric=self._grid_metric(gen),
                distance_dtype=cfg.distance_dtype,
            )
            ex = self._engine("sparse", sparse_lib.sparse_knn_jit, args, kwargs)
            if compile_only:
                return None
            raw = ex(*args)     # async dispatch: returns un-blocked arrays
            n = len(ids)

            def finalize(r):
                report.n_sparse_overflow += int(r.n_overflow)
                return (
                    np.asarray(r.dists[:n]),
                    np.asarray(r.ids[:n]),
                    np.asarray(r.certified[:n]),
                )

            return queue_lib.AsyncEngineCall(raw, finalize)

        return sparse_fn

    def _brute_fn(self, gen: _Generation, k: int, queries_rp,
                  exclude_self: bool):
        cfg = self.config

        def brute_fn(ids: np.ndarray):
            qp = hybrid_lib._pad_ids(ids, cfg.query_block)
            args = (gen.points_r, qp)
            if queries_rp is not None:
                args = args + (queries_rp,)
            kwargs = dict(
                k=k, corpus_chunk=cfg.brute_chunk,
                kernel_mode=cfg.kernel_mode, exclude_self=exclude_self,
                metric=self._grid_metric(gen),
            )
            return self._brute_call(args, kwargs, len(ids))

        return brute_fn

    def _brute_call(self, args: tuple, kwargs: dict, n: int):
        """Run the brute engine and harvest its first ``n`` rows."""
        ex = self._engine("brute", _brute_engine, args, kwargs)
        with trace.span("knn.brute.wait"):
            d, i = jax.block_until_ready(ex(*args))
        with trace.span("knn.brute.copy"):
            return np.asarray(d[:n]), np.asarray(i[:n])

    def _full_brute_fn(self, gen: _Generation, k: int, queries_fp,
                       exclude_self: bool):
        """Brute engine over the FULL-dimension corpus in the true
        kernel metric — the projected path's exact fallback and its
        calibration reference.  (The projected grid's own brute lane
        runs in projected l2 space; this one answers in the index's
        real geometry.)"""
        cfg = self.config

        def brute_fn(ids: np.ndarray):
            qp = hybrid_lib._pad_ids(ids, cfg.query_block)
            args = (gen.points_full, qp)
            if queries_fp is not None:
                args = args + (queries_fp,)
            kwargs = dict(
                k=k, corpus_chunk=cfg.brute_chunk,
                kernel_mode=cfg.kernel_mode, exclude_self=exclude_self,
                metric=met_lib.kernel_metric(cfg.metric),
            )
            return self._brute_call(args, kwargs, len(ids))

        return brute_fn

    # -- work split --------------------------------------------------------

    def _self_split(
        self, gen: _Generation, k: int, rho: float
    ) -> Tuple[np.ndarray, np.ndarray, float]:
        """Dense/sparse assignment of the indexed cloud itself (cached
        per (k, ρ) on the generation — home-cell densities never change
        between compactions; ρ may be overridden online)."""
        hit = gen.self_splits.get((k, rho))
        if hit is not None:
            return hit
        cfg = self.config
        split = split_lib.split_from_counts(
            jnp.asarray(gen.home_counts), k, gen.grid.m, cfg.gamma, rho
        )
        to_dense = np.asarray(split.to_dense)
        out = (
            np.nonzero(to_dense)[0].astype(np.int32),
            np.nonzero(~to_dense)[0].astype(np.int32),
            float(split.threshold),
        )
        gen.self_splits[(k, rho)] = out
        return out

    # -- mutations (DESIGN.md §6) ------------------------------------------

    def insert(self, points) -> np.ndarray:
        """Add points to the corpus (delta buffer).  Returns the global
        ids assigned to them, valid as of this call's return (i.e.
        post-compaction ids when the insert tripped the auto-compact
        threshold).  O(1) amortized; queries stay exact."""
        self._check_mutable()
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
        on unknown or already-deleted ids — a silent double-delete is a
        silent recall bug."""
        self._check_mutable()
        gen, mut = self._live
        self._live = (gen, mut.with_delete(ids, gen.n_base))
        self._maybe_autocompact()

    def _check_mutable(self) -> None:
        if self._live[0].projection is not None:
            raise ValueError(
                "insert/delete are not supported on a projection-fronted "
                "index (the fitted projection would go stale against a "
                "drifting corpus) — rebuild with KNNIndex.build(...) on "
                "the updated points, or set projection_dim=0"
            )

    def net_points(self) -> np.ndarray:
        """The LIVE corpus in original dim order, ascending global id —
        ``KNNIndex.build(index.net_points(), config)`` is the index
        ``compact()`` swaps in."""
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
        """Fold all pending mutations into a fresh generation: rebuild
        REORDER, ε selection (replaying build()'s ε argument), and the
        grid/pyramid over the net corpus, then swap the (generation,
        mutations) pair atomically — in-flight queries that already
        snapshotted the old pair finish against it unharmed.

        Returns the id remap: ``remap[old_gid]`` is the point's id in
        the new generation, −1 if deleted.  Post-compaction queries are
        bit-identical to ``KNNIndex.build(net_points, config, ε_arg)``
        — same clean path over the same built state — and, because the
        engine-cache keys see only pow2-bucketed shapes, a net corpus
        in the same buckets recompiles nothing."""
        gen, mut = self._live
        if mut.is_clean:
            return np.arange(gen.n_base, dtype=np.int64)
        net, _ = mut.net_corpus(np.asarray(gen.points_ref, np.float32))
        assert self.config.k < len(net), (
            f"cannot compact: k={self.config.k} needs more than the "
            f"{len(net)} live points"
        )
        remap = mut.remap_after_compact(gen.n_base)
        fresh = KNNIndex.build(
            net, self.config, self._epsilon_arg,
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

    def _drain(self, gen: _Generation, kq: int, n_q: int, queries_rp,
               dense_ids, sparse_ids, home_counts, exclude_self: bool,
               rho: Optional[float] = None):
        """Steps 5–8 of Algorithm 1: the §V-A work queue over the three
        engines.  Returns SQUARED distances (√ happens after any
        merge-time folding, so folds compare like with like)."""
        cfg = self.config
        rho_floor = cfg.rho if rho is None else rho
        report = queue_lib.QueueReport()
        dense_fn = self._dense_fn(gen, kq, queries_rp, exclude_self, report)
        sparse_fn = self._sparse_fn(gen, kq, queries_rp, exclude_self,
                                    report)
        # The first round's timings seed the online ρ rebalance, so they
        # must not depend on the engine cache: compile that round's
        # engines before the queue dispatches any.  (Were the dense
        # engine compiled while the first sparse round runs, the device
        # would finish that round unobserved, a cold query would demote
        # differently from a warm one, and the warm one would compile
        # the buckets its demotions land in.)
        t0 = time.perf_counter()
        with trace.span("knn.prepare"):
            if len(sparse_ids):
                sparse_fn(sparse_ids, compile_only=True)
            first = -(-len(dense_ids) // cfg.n_batches)
            if first:
                dense_fn(dense_ids[:first], compile_only=True)
        t_prepare = time.perf_counter() - t0
        *answers, report = queue_lib.run_work_queue(
            npts=n_q,
            k=kq,
            dense_ids=dense_ids,
            sparse_ids=sparse_ids,
            home_counts=home_counts,
            dense_fn=dense_fn,
            sparse_fn=sparse_fn,
            brute_fn=self._brute_fn(gen, kq, queries_rp, exclude_self),
            n_batches=cfg.n_batches,
            online_rebalance=cfg.online_rebalance,
            sync_t1_after=cfg.rebalance_sync_batches,
            min_sparse=int(math.ceil(rho_floor * n_q)),
            demote_quantum=cfg.query_block,
            report=report,
        )
        report.t_wall += t_prepare      # the query's wall holds its compiles
        return (*answers, report)

    def _stats(self, gen: _Generation, n_dense: int, n_sparse: int,
               threshold: float, report, compiles_before: int,
               t_delta: float = 0.0) -> "hybrid_lib.JoinStats":
        return hybrid_lib.JoinStats(
            epsilon=gen.eps,
            epsilon_beta=gen.eps_beta,
            n_dense=n_dense,
            n_sparse=n_sparse,
            n_failed=report.n_failed,
            n_uncertified=report.n_uncertified,
            n_thresh=threshold,
            t_select_eps=0.0,
            t_build=0.0,
            t_dense=report.t_dense,
            t_sparse=report.t_sparse,
            t_brute=report.t_brute,
            t_delta=t_delta,
            t_wall=report.t_wall + t_delta,
            t1_per_query=report.t1_per_query,
            t2_per_query=report.t2_per_query,
            rho_model=split_lib.rho_model(
                report.t1_per_query, report.t2_per_query
            ),
            n_batches=report.n_dense_batches,
            batch_sizes=list(report.batch_sizes),
            t_dense_batches=list(report.t_batches),
            n_rebalanced=report.n_rebalanced,
            n_sparse_rounds=report.n_sparse_rounds,
            n_sparse_engine_total=report.n_sparse_engine_total,
            rho_online=report.rho_online,
            n_engine_compiles=self.total_compiles - compiles_before,
            n_dense_overflow=report.n_dense_overflow,
            dense_pairs=report.dense_pairs,
            dense_cand_rows=report.dense_cand_rows,
            n_sparse_overflow=report.n_sparse_overflow,
        )

    @traced_query
    def query(
        self,
        queries=None,
        k: Optional[int] = None,
        exclude_self: bool = False,
        *,
        _net_cells=None,
        _rho: Optional[float] = None,
    ) -> "hybrid_lib.KNNResult":
        """Hybrid KNN of ``queries`` against the indexed reference cloud.

        ``queries`` is an (|Q|, n) array in the reference cloud's
        original dim order (REORDER is applied internally with the
        reference permutation); ``None`` — or the indexed array object
        itself — selects the self-join fast path, which reuses the
        build-time coordinate caches.  ``k`` overrides the config's K
        for this call.  ``exclude_self`` masks reference point i for
        query row i (positional identity — which is global-id identity;
        with ``queries=None`` on a mutated index, each live point's own
        global id is excluded).

        Steps 4–9 of Algorithm 1 run per call: the §V-D density split
        classifies queries by the *reference grid's* population around
        them, the §V-A work queue drains both engines, §V-E failures
        reassign, and the brute lane certifies the residue — results
        are exact for arbitrary R≠S query sets.  With mutations pending
        the delta buffer and tombstones fold in at merge time
        (``_query_mutated``); a clean index takes this original path
        untouched.

        ``_net_cells`` is internal (sharded serving): raw reordered
        (delta, tombstone) point arrays whose home cells adjust this
        grid's density classification to the net corpus.  ``_rho``
        overrides the config's ρ floor for this call (the sharded
        serving layer's online Eq. 6 re-suggestion) — pure work routing,
        results are exact either way.

        Metric/approximation routing (DESIGN.md §9): cosine runs the
        l2 machinery over pre-normalized rows; raw ip (no projection)
        serves every query through the exact brute lane (ip admits no
        triangle inequality, so the grid cannot bound it); a
        projection-fronted index runs the candidate stage in projected
        space and rescores full-dim (``_query_projected``); and
        ``recall_target < 1.0`` swaps the work queue for the calibrated
        lean candidate stage (``_query_approx``) — ``recall_target=1.0``
        takes this exact path bit-identically.
        """
        gen, mut = self._live
        if not mut.is_clean:
            assert _net_cells is None
            return self._query_mutated(gen, mut, queries, k, exclude_self)
        cfg = self.config
        rho = cfg.rho if _rho is None else float(np.clip(_rho, 0.0, 1.0))
        npts_ref = gen.n_base
        max_k = npts_ref - 1 if exclude_self else npts_ref
        kq = validate_k(
            cfg.k if k is None else k, max_k,
            context=" after self-exclusion" if exclude_self else "",
        )
        compiles_before = self.total_compiles

        is_self = queries is None or queries is gen.points_ref
        q_np = None
        if is_self:
            n_q = npts_ref
        else:
            # Metric contract on the query side (DESIGN.md §9.2): cosine
            # demands unit rows, with a pointer to normalize_rows.
            q_np = met_lib.prepare_rows(
                validate_points(queries, self.n_dims),
                cfg.metric, "queries", context="KNNIndex.query",
            )
            n_q = int(q_np.shape[0])

        if gen.projection is not None:
            return self._query_projected(
                gen, kq, n_q, q_np, exclude_self, rho, compiles_before)
        if cfg.metric == "ip":
            return self._query_brute_all(
                gen, kq, n_q, q_np, exclude_self, compiles_before)

        with trace.span("knn.split"):
            if is_self:
                queries_rp = None
                dense_ids, sparse_ids, threshold = self._self_split(
                    gen, kq, rho)
                home_counts = gen.home_counts
            else:
                q = jnp.asarray(q_np)
                queries_r = (q[:, gen.dim_perm] if gen.dim_perm is not None
                             else q)
                # The query-shape bucket: engine-cache keys see this padded
                # aval, so variable batch sizes share executables.
                queries_rp = pad_rows_pow2(queries_r, cfg.query_block)
                q_coords = grid_lib.compute_cell_coords(
                    gen.grid, queries_r[:, : gen.grid.m]
                )
                net_adjust = None
                if _net_cells is not None:
                    q_cells = np.asarray(
                        grid_lib.linearize(q_coords, gen.grid.radices)
                    )
                    net_adjust = jnp.asarray(mut_lib.net_cell_adjustment(
                        gen.grid, q_cells, *_net_cells
                    ))
                split = split_lib.split_queries(
                    gen.grid, q_coords, kq, cfg.gamma, rho,
                    net_adjust=net_adjust,
                )
                to_dense = np.asarray(split.to_dense)
                dense_ids = np.nonzero(to_dense)[0].astype(np.int32)
                sparse_ids = np.nonzero(~to_dense)[0].astype(np.int32)
                home_counts = np.asarray(split.home_counts)
                threshold = float(split.threshold)

        if cfg.recall_target < 1.0 and _net_cells is None:
            return self._query_approx(
                gen, kq, n_q, queries_rp, dense_ids, sparse_ids,
                home_counts, threshold, exclude_self, rho, compiles_before,
            )

        final_d, final_i, source, report = self._drain(
            gen, kq, n_q, queries_rp, dense_ids, sparse_ids, home_counts,
            exclude_self, rho=rho,
        )
        with trace.span("knn.finalize"):
            stats = self._stats(
                gen, len(dense_ids), len(sparse_ids), threshold, report,
                compiles_before,
            )
            return hybrid_lib.KNNResult(
                dists=met_lib.finalize(final_d, cfg.metric),
                ids=final_i,
                source=source,
                stats=stats,
            )

    # -- metric / approximation query paths (DESIGN.md §9) -----------------

    def _query_brute_all(
        self, gen: _Generation, kq: int, n_q: int, q_np,
        exclude_self: bool, compiles_before: int,
    ) -> "hybrid_lib.KNNResult":
        """Raw inner-product serving (§9.2): ip admits no triangle
        inequality, so neither the grid's geometric routing nor the
        sparse certificates can bound it — every query serves through
        the exact brute lane (one padded batch).  Approximate ip wants
        the projection front stage."""
        cfg = self.config
        if q_np is None:
            queries_rp = None
        else:
            q = jnp.asarray(q_np)
            queries_r = q[:, gen.dim_perm] if gen.dim_perm is not None else q
            queries_rp = pad_rows_pow2(queries_r, cfg.query_block)
        t0 = time.perf_counter()
        d, i = self._brute_fn(gen, kq, queries_rp, exclude_self)(
            np.arange(n_q, dtype=np.int32))
        dt = time.perf_counter() - t0
        stats = hybrid_lib.JoinStats(
            epsilon=gen.eps,
            epsilon_beta=gen.eps_beta,
            t_brute=dt,
            t_wall=dt,
            n_engine_compiles=self.total_compiles - compiles_before,
        )
        return hybrid_lib.KNNResult(
            dists=met_lib.finalize(d, cfg.metric),
            ids=i,
            source=np.full((n_q,), 2, np.int32),
            stats=stats,
        )

    def _query_full_brute(
        self, gen: _Generation, kq: int, n_q: int, q_np,
        exclude_self: bool, compiles_before: int,
    ) -> "hybrid_lib.KNNResult":
        """The projected path's exact fallback (§9.4): no candidate rung
        met ``recall_target`` on the held-out sample, so serve exact
        full-dimension brute (estimate 1.0 by construction) — the same
        executable calibration used for its reference — rather than
        quietly under-serving the contract."""
        cfg = self.config
        qfp = (None if q_np is None
               else pad_rows_pow2(jnp.asarray(q_np), cfg.query_block))
        t0 = time.perf_counter()
        d, i = self._full_brute_fn(gen, kq, qfp, exclude_self)(
            np.arange(n_q, dtype=np.int32))
        dt = time.perf_counter() - t0
        stats = hybrid_lib.JoinStats(
            epsilon=gen.eps,
            epsilon_beta=gen.eps_beta,
            t_brute=dt,
            t_wall=dt,
            n_engine_compiles=self.total_compiles - compiles_before,
        )
        return hybrid_lib.KNNResult(
            dists=met_lib.finalize(d, cfg.metric),
            ids=i,
            source=np.full((n_q,), 2, np.int32),
            stats=stats,
        )

    def _lean_pass(
        self, gen: _Generation, kq: int, n_q: int, queries_rp,
        dense_ids: np.ndarray, sparse_ids: np.ndarray,
        exclude_self: bool, eps_scale: float,
    ):
        """One-shot approximate candidate stage (§9.4): sparse engine
        dispatched async, dense engine once at scaled ε (a runtime
        operand — the exact path's executable, zero recompiles), then
        NO failure reassignment and NO brute certification — the missing
        backstops are what the calibrated tier's measured recall pays
        for.  The report's engine counters are filled as on the exact
        path; ``n_uncertified`` counts rows that no brute lane serves."""
        d_out = np.full((n_q, kq), np.inf, np.float32)
        i_out = np.full((n_q, kq), -1, np.int32)
        source = np.zeros((n_q,), np.int32)
        report = queue_lib.QueueReport()
        t0 = time.perf_counter()
        call = None
        if len(sparse_ids):
            call = self._sparse_fn(gen, kq, queries_rp, exclude_self,
                                   report)(sparse_ids)
        if len(dense_ids):
            dd, di, dfail, t_dense = self._dense_fn(
                gen, kq, queries_rp, exclude_self, report,
                eps_scale=eps_scale,
            )(dense_ids)
            d_out[dense_ids] = dd
            i_out[dense_ids] = di
            report.n_failed = int(np.sum(dfail))
            report.batch_sizes = [len(dense_ids)]
            report.t_batches = [t_dense]
            report.n_dense_batches = 1
            report.t_dense = t_dense
        if call is not None:
            sd, si, cert = call.get()
            d_out[sparse_ids] = sd
            i_out[sparse_ids] = si
            source[sparse_ids] = 1
            report.n_sparse_rounds = 1
            report.n_uncertified = int(np.sum(~cert))
            report.n_sparse_engine_total = len(sparse_ids)
            report.t_sparse = call.elapsed or 0.0
        report.t_wall = time.perf_counter() - t0
        return d_out, i_out, source, report

    def _query_approx(
        self, gen: _Generation, kq: int, n_q: int, queries_rp,
        dense_ids, sparse_ids, home_counts, threshold: float,
        exclude_self: bool, rho: float, compiles_before: int,
    ) -> "hybrid_lib.KNNResult":
        """recall_target < 1.0 (§9.4): serve the calibrated lean tier —
        or fall back to the exact pipeline (estimate 1.0) when no lean
        tier met the target on the held-out sample."""
        from repro.retrieval import calibrate as cal_lib

        cfg = self.config
        eps_scale, est = cal_lib.grid_tier(self, gen, kq)
        if eps_scale is None:
            final_d, final_i, source, report = self._drain(
                gen, kq, n_q, queries_rp, dense_ids, sparse_ids,
                home_counts, exclude_self, rho=rho,
            )
        else:
            final_d, final_i, source, report = self._lean_pass(
                gen, kq, n_q, queries_rp, dense_ids, sparse_ids,
                exclude_self, eps_scale,
            )
        stats = self._stats(
            gen, len(dense_ids), len(sparse_ids), threshold, report,
            compiles_before,
        )
        return hybrid_lib.KNNResult(
            dists=met_lib.finalize(final_d, cfg.metric),
            ids=final_i,
            source=source,
            stats=stats,
            recall_estimate=est,
        )

    def _projected_pass(
        self, gen: _Generation, kq: int, k_cand: int, n_q: int,
        queries_rp, qf, exclude_self: bool, rho: float,
    ):
        """Projection front stage (§9.3), one batch: the FULL exact
        pipeline (work queue + brute certification) in projected space
        at ``k_cand``, then the full-dim true-metric rescore engine
        (kind ``"rescore"``) reduces each candidate pool to the k best.
        ``queries_rp`` is the padded PROJECTED batch (None = self-join
        over the projected corpus); ``qf`` the full-dim query rows the
        rescore reads."""
        cfg = self.config
        if queries_rp is None:
            dense_ids, sparse_ids, threshold = self._self_split(
                gen, k_cand, rho)
            home_counts = gen.home_counts
        else:
            q_coords = grid_lib.compute_cell_coords(
                gen.grid, queries_rp[:n_q, : gen.grid.m]
            )
            split = split_lib.split_queries(
                gen.grid, q_coords, k_cand, cfg.gamma, rho)
            to_dense = np.asarray(split.to_dense)
            dense_ids = np.nonzero(to_dense)[0].astype(np.int32)
            sparse_ids = np.nonzero(~to_dense)[0].astype(np.int32)
            home_counts = np.asarray(split.home_counts)
            threshold = float(split.threshold)
        cd, ci, source, report = self._drain(
            gen, k_cand, n_q, queries_rp, dense_ids, sparse_ids,
            home_counts, exclude_self, rho=rho,
        )
        t0 = time.perf_counter()
        qb = pow2_bucket(n_q, cfg.query_block)
        qfp = pad_rows_pow2(jnp.asarray(qf), cfg.query_block)
        ci_p = np.full((qb, k_cand), -1, np.int32)
        ci_p[:n_q] = ci
        excl_p = np.full((qb,), -2, np.int32)
        if exclude_self:
            excl_p[:n_q] = np.arange(n_q, dtype=np.int32)
        rargs = (gen.points_full, qfp, jnp.asarray(ci_p),
                 jnp.asarray(excl_p))
        rkw = dict(k=kq, metric=met_lib.kernel_metric(cfg.metric))
        rd, ri = jax.block_until_ready(
            self._engine("rescore", _rescore_engine, rargs, rkw)(*rargs)
        )
        t_rescore = time.perf_counter() - t0
        return (
            np.asarray(rd)[:n_q], np.asarray(ri)[:n_q], source, report,
            threshold, len(dense_ids), len(sparse_ids), t_rescore,
        )

    def _query_projected(
        self, gen: _Generation, kq: int, n_q: int, q_np,
        exclude_self: bool, rho: float, compiles_before: int,
    ) -> "hybrid_lib.KNNResult":
        """Projection-fronted query (§9.3): candidate pool size comes
        from the calibrated tier ladder (``retrieval.calibrate``); when
        no rung met the target on the held-out sample (``cand_mult``
        None), serve exact full-dimension brute instead — the projected
        twin of the grid path's exact fallback."""
        from repro.retrieval import calibrate as cal_lib

        cfg = self.config
        cand_mult, est = cal_lib.projected_tier(self, gen, kq)
        if cand_mult is None:
            return self._query_full_brute(
                gen, kq, n_q, q_np, exclude_self, compiles_before)
        if q_np is None:
            queries_rp = None
            qf = gen.points_full
        else:
            qproj = gen.projection.apply(q_np)
            queries_rp = pad_rows_pow2(
                jnp.asarray(qproj), cfg.query_block)
            qf = jnp.asarray(q_np)
        max_k = gen.n_base - 1 if exclude_self else gen.n_base
        k_cand = max(kq, min(cand_mult * kq, max_k))
        rd, ri, source, report, threshold, n_dense, n_sparse, t_rescore = (
            self._projected_pass(
                gen, kq, k_cand, n_q, queries_rp, qf, exclude_self, rho)
        )
        stats = self._stats(
            gen, n_dense, n_sparse, threshold, report, compiles_before)
        stats.t_merge += t_rescore
        stats.t_wall += t_rescore
        return hybrid_lib.KNNResult(
            dists=met_lib.finalize(rd, cfg.metric),
            ids=ri,
            source=source,
            stats=stats,
            recall_estimate=est,
        )

    def _query_mutated(
        self, gen: _Generation, mut: "mut_lib.MutationState",
        queries, k: Optional[int], exclude_self: bool,
    ) -> "hybrid_lib.KNNResult":
        """The dirty-index query path: main hybrid pipeline over the
        base corpus at tombstone-headroomed k (no engine-level
        exclusion), a brute top-K over the delta buffer (engine kind
        ``"delta"``), then one merge-time fold (kind ``"merge"``) that
        masks tombstones/self by global id and folds the delta block in
        — exact for any mutation state, recompiling only when a pow2
        bucket (query batch, delta buffer, tombstone headroom) grows."""
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
            q_np = met_lib.prepare_rows(
                validate_points(queries, self.n_dims),
                cfg.metric, "queries", context="KNNIndex.query",
            )
            q = jnp.asarray(q_np)
            excl = (np.arange(q.shape[0], dtype=np.int32) if exclude_self
                    else np.full((int(q.shape[0]),), -2, np.int32))
        n_q = int(q.shape[0])
        queries_r = q[:, gen.dim_perm] if gen.dim_perm is not None else q
        queries_rp = pad_rows_pow2(queries_r, cfg.query_block)
        qb = int(queries_rp.shape[0])

        # Main pipeline, widened so merge-time masking cannot starve the
        # top-k: engine-level exclusion is OFF (exclusion is by global
        # id in the fold; the base engines' positional identity is
        # meaningless against net-corpus queries).
        k_main = min(
            kq + mut_lib.headroom_bucket(mut.n_base_tombs, exclude_self),
            n_base,
        )
        if cfg.metric == "ip":
            # Raw ip (DESIGN.md §9.2): grid routing cannot bound inner
            # product — the widened main pipeline IS the brute lane.
            dense_ids = np.empty((0,), np.int32)
            sparse_ids = np.empty((0,), np.int32)
            threshold = 0.0
            t0 = time.perf_counter()
            final_d, final_i = self._brute_fn(
                gen, k_main, queries_rp, False
            )(np.arange(n_q, dtype=np.int32))
            dt = time.perf_counter() - t0
            source = np.full((n_q,), 2, np.int32)
            report = queue_lib.QueueReport(t_brute=dt, t_wall=dt)
        else:
            # §V-D split against the NET density: base grid counts
            # corrected by the delta/tombstone cell populations
            # (splitter.net_adjust).
            pts_r = np.asarray(gen.points_r)
            delta_live_r = mut.delta_r(gen.dim_perm)[mut.delta_live]
            tomb_pts_r = pts_r[mut.base_tombs]
            q_coords = grid_lib.compute_cell_coords(
                gen.grid, queries_r[:, : gen.grid.m]
            )
            q_cells = np.asarray(
                grid_lib.linearize(q_coords, gen.grid.radices))
            net_adjust = jnp.asarray(mut_lib.net_cell_adjustment(
                gen.grid, q_cells, delta_live_r, tomb_pts_r
            ))
            split = split_lib.split_queries(
                gen.grid, q_coords, kq, cfg.gamma, cfg.rho,
                net_adjust=net_adjust,
            )
            to_dense = np.asarray(split.to_dense)
            dense_ids = np.nonzero(to_dense)[0].astype(np.int32)
            sparse_ids = np.nonzero(~to_dense)[0].astype(np.int32)
            home_counts = np.asarray(split.home_counts)
            threshold = float(split.threshold)
            final_d, final_i, source, report = self._drain(
                gen, k_main, n_q, queries_rp, dense_ids, sparse_ids,
                home_counts, False,
            )

        # Delta top-K + fold, through the same AOT engine cache.
        t0 = time.perf_counter()
        delta_pts_p, delta_gids = mut.padded_delta(gen.dim_perm, n_base)
        k_delta = min(kq, delta_pts_p.shape[0])
        excl_p = np.full((qb,), -2, np.int32)
        excl_p[:n_q] = excl
        dargs = (queries_rp, jnp.asarray(delta_pts_p),
                 jnp.asarray(excl_p), jnp.asarray(delta_gids))
        dkw = dict(k=k_delta, mode=cfg.kernel_mode,
                   metric=met_lib.kernel_metric(cfg.metric))
        dd, di = self._engine("delta", mut_lib.delta_topk, dargs, dkw)(*dargs)

        md = np.full((qb, k_main), np.inf, np.float32)
        mi = np.full((qb, k_main), -1, np.int32)
        md[:n_q] = final_d
        mi[:n_q] = final_i
        fargs = (jnp.asarray(md), jnp.asarray(mi), dd, di,
                 jnp.asarray(mut.tombstone_table()), jnp.asarray(excl_p))
        fkw = dict(k=kq)
        fd, fi = jax.block_until_ready(
            self._engine("merge", mut_lib.fold_topk, fargs, fkw)(*fargs)
        )
        t_delta = time.perf_counter() - t0
        fd = np.asarray(fd)[:n_q]
        fi = np.asarray(fi)[:n_q]

        stats = self._stats(
            gen, len(dense_ids), len(sparse_ids), threshold,
            report, compiles_before, t_delta=t_delta,
        )
        return hybrid_lib.KNNResult(
            dists=met_lib.finalize(fd, cfg.metric),
            ids=fi,
            # Source labels the main-pipeline engine; delta-buffer hits
            # don't relabel (the fold is uniform merge work).
            source=source,
            stats=stats,
        )
