"""HYBRIDKNN-JOIN — the paper's Algorithm 1, orchestrating the two engines.

Host-level control flow mirrors the paper's MPI master/worker structure
(one GPU rank + CPU ranks) as two jitted device pipelines plus a brute
fallback lane:

  1.  REORDER by variance                     (§IV-D)
  2.  select ε from sampled histogram          (§V-C,  β)
  3.  build the ε-grid index, m ≤ n dims       (§IV-A, §IV-C)
  4.  split work: density + ρ floor            (§V-D,  γ, ρ)
  5.  dense engine on Q^dense, dequeued in
      n_batches work-queue batches             (§V-A/§V-B, GPU-JOIN)
  6.  collect failures Q^fail                  (§V-E)
  7.  sparse engine drains Q^sparse async;
      online ρ rebalance demotes from the
      queue tail between rounds                (§V-B/§V-F, EXACT-ANN)
  8.  brute-certify the residue                (exactness backstop)
  9.  merge + report T₁/T₂ and ρ^Model         (§VI-E2, Eq. 6)

Execution lives in ``repro.runtime.knn_index.KNNIndex`` (build-once
index + compiled-engine caching; ``query()`` serves arbitrary R≠S query
sets) driving ``repro.core.queue`` (the multi-round work-queue
scheduler); ``repro.runtime.session.JoinSession`` owns index reuse
across joins and ``HybridKNNJoin`` is kept as the thin, stable
self-join entry point.  The per-engine wall times recorded here are what the paper
calls T₁ and T₂; ``stats.rho_model`` reproduces Table V's analytic
load-balance point.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.utils import pow2_bucket


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """All paper parameters (Table II) plus TPU execution knobs."""

    k: int
    m: int = 6                    # indexed dims (paper uses m=6 everywhere)
    beta: float = 0.0             # ε inflation (§V-C2)
    gamma: float = 0.0            # density threshold (§V-D)
    rho: float = 0.0              # min sparse-engine fraction (§V-F)
    reorder: bool = True          # REORDER (§IV-D)
    # ε-selection sampling (§V-C2)
    n_bins: int = 256
    n_query_sample: int = 256
    n_pair_sample: int = 4096
    # dense engine (GPU-JOIN analogue).  Defaults sized for the fused
    # streaming backend (DESIGN.md §2.6): with no (block, budget)
    # distance tile in HBM the candidate budget stops being the memory
    # cap, so the default budget doubles and the dense assignment is
    # dequeued in fewer, larger batches (the paper's opt. i — maximize
    # accelerator batch size).  Re-swept in benchmarks/table3.
    dense_budget: int = 2048      # candidate budget per query (batching, §IV-B)
    query_block: int = 128        # queries per streamed block (TSTATIC tile)
    block_c: int = 128            # candidate-tile width in the fused kernel
                                  # (TDYNAMIC, §V-G; tiled backends only)
    # work-queue scheduler (§V-A, Table III granularity)
    n_batches: int = 2            # dense batches dequeued per join
    online_rebalance: bool = True # Eq. 6-driven demotion between rounds
    rebalance_sync_batches: int = 1  # force a T₁ harvest after this many
                                     # dense batches (0: poll only)
    # sparse engine (EXACT-ANN analogue)
    n_levels: int = 6
    level_scale: float = 2.0
    sparse_budget: int = 512
    sel_factor: int = 4
    # fallback + kernels
    brute_chunk: int = 2048
    kernel_mode: str = "auto"     # auto|pallas|interpret|ref (brute-lane kernels)
    # engine execution backend (DESIGN.md §2.5, §2.6): "ref" per-query
    # gather oracle; "pallas"/"interpret" the cell-tiled two-pass MXU
    # path; "fused" the streaming one-pass distance+top-K engine; "auto"
    # resolves to fused on TPU, ref elsewhere (REPRO_BACKEND env
    # overrides).  Part of the AOT engine-cache key; resolved ONCE per
    # session (dense_join.resolve_backend).
    backend: str = "auto"
    # distance accumulation dtype (DESIGN.md §10): "fp32" exact; "bf16"
    # computes kernel distance tiles from bf16-cast operands (half the
    # candidate-DMA bytes, the MXU's native low-precision path),
    # over-fetches k+8 slots, and restores exact fp32 distances by
    # rescoring the survivors — ids stay identical to fp32 away from
    # the ε² boundary, and boundary shortfalls fail conservatively into
    # the sparse/brute reassignment.  Honored by the fused dense engine
    # and the kernel-formulation sparse backends; ref/tiled paths and
    # the brute lane always serve fp32.  Part of every engine-cache key.
    distance_dtype: str = "fp32"  # fp32 | bf16
    # mutable index (DESIGN.md §6): auto-compact when the delta buffer
    # or the tombstone set exceeds this fraction of the base corpus
    # (0.0 compacts after every mutation; math.inf never auto-compacts).
    mutation_compact_frac: float = 0.25
    # retrieval subsystem (DESIGN.md §9): distance metric, recall target,
    # and the projection front stage.  metric is part of every engine-
    # cache key; cosine demands pre-normalized rows (retrieval.metrics);
    # raw ip (no projection) serves through the exact brute lane.
    metric: str = "l2"            # l2 | ip | cosine
    # recall_target < 1.0 engages the calibrated approximate candidate
    # stage: a tier ladder of (eps_scale, cand_mult) knobs is measured
    # against an exact reference on a held-out corpus sample and the
    # fastest tier meeting the target wins (KNNResult.recall_estimate
    # reports the measured value).  1.0 = the exact path, bit-identical
    # to a config without the knob.
    recall_target: float = 1.0
    calib_queries: int = 128      # held-out sample size for calibration
    # projection front stage (retrieval/projection.py): project d-dim
    # rows to projection_dim ≤ 8 dims, grid/search in projected space,
    # exact full-dimension rescore of the surviving candidates.
    # 0 disables the stage.
    projection_dim: int = 0
    projection_kind: str = "pca"  # pca | random (seeded)
    rescore_mult: int = 8         # projected candidates per output slot
    seed: int = 0

    def __post_init__(self):
        assert 0.0 <= self.beta <= 1.0 and 0.0 <= self.gamma <= 1.0
        assert 0.0 <= self.rho <= 1.0 and self.k >= 1 and self.m >= 1
        assert self.n_batches >= 1 and self.rebalance_sync_batches >= 0
        assert self.mutation_compact_frac >= 0.0
        from repro.core.dense_join import BACKENDS, DISTANCE_DTYPES
        from repro.retrieval.metrics import validate_metric

        assert self.backend in BACKENDS, self.backend
        assert self.block_c >= 1
        if self.distance_dtype not in DISTANCE_DTYPES:
            raise ValueError(
                f"distance_dtype must be one of {DISTANCE_DTYPES}, "
                f"got {self.distance_dtype!r}"
            )
        validate_metric(self.metric, "HybridConfig.metric")
        if not 0.0 < self.recall_target <= 1.0:
            raise ValueError(
                f"recall_target must be in (0, 1], got {self.recall_target}"
            )
        if not 0 <= self.projection_dim <= 8:
            raise ValueError(
                "projection_dim must be 0 (off) or 1..8 (the grid's "
                f"low-dim sweet spot), got {self.projection_dim}"
            )
        if self.projection_kind not in ("pca", "random"):
            raise ValueError(
                f"projection_kind must be 'pca' or 'random', "
                f"got {self.projection_kind!r}"
            )
        assert self.rescore_mult >= 1 and self.calib_queries >= 1


@dataclasses.dataclass
class JoinStats:
    epsilon: float = 0.0
    epsilon_beta: float = 0.0
    n_dense: int = 0
    n_sparse: int = 0
    n_failed: int = 0             # dense-engine failures reassigned (§V-E)
    n_uncertified: int = 0        # sparse results needing the brute backstop
    n_thresh: float = 0.0
    t_select_eps: float = 0.0
    t_build: float = 0.0
    t_dense: float = 0.0
    t_sparse: float = 0.0
    t_brute: float = 0.0
    t_merge: float = 0.0          # collective top-K merge (sharded serving)
    t_delta: float = 0.0          # delta-buffer top-K + mutation fold
                                  # (mutable index, DESIGN.md §6)
    t_wall: float = 0.0           # scheduler wall time (engines overlap)
    t1_per_query: float = 0.0     # paper T₁ (sparse engine, per query)
    t2_per_query: float = 0.0     # paper T₂ (dense engine, per query)
    rho_model: float = 0.5        # Eq. 6
    # work-queue scheduler accounting (§V-A/§V-F)
    n_batches: int = 0            # dense batches actually dequeued
    batch_sizes: List[int] = dataclasses.field(default_factory=list)
    t_dense_batches: List[float] = dataclasses.field(default_factory=list)
    n_rebalanced: int = 0         # queries demoted online beyond the ρ floor
    n_sparse_rounds: int = 0
    n_sparse_engine_total: int = 0  # all queries the sparse engine processed
    rho_online: float = 0.0       # last Eq. 6 estimate the scheduler applied
    n_engine_compiles: int = 0    # engine compilations triggered by this join
    # engine counters, reduced on the device (0 where an engine did not run)
    n_dense_overflow: int = 0     # real dense rows failed by candidate-
                                  # budget overflow (the rest of n_failed
                                  # found < K within ε)
    dense_pairs: int = 0          # query–candidate pairs the dense kernel
                                  # scored
    dense_cand_rows: int = 0      # candidate rows the dense kernel read
    n_sparse_overflow: int = 0    # uncertified sparse rows whose budget
                                  # overflowed (the rest missed the level's
                                  # certified radius)
    # programs JAX compiled or loaded from its cache during the call,
    # helpers and engines alike (runtime/trace.py)
    n_programs_compiled: int = 0
    t_compile: float = 0.0        # their seconds
    compiles_by_span: Dict[str, int] = dataclasses.field(
        default_factory=dict)     # innermost knn.* span -> programs
    # fault-tolerant serving accounting (DESIGN.md §7) — populated by the
    # sharded replica-group path; zero/empty on single-device queries.
    n_hedged: int = 0             # slow sub-queries re-issued to a sibling
    n_hedge_wins: int = 0         # hedges whose effective latency won
    n_subquery_retries: int = 0   # failed sub-queries retried on siblings
    n_subquery_failures: int = 0  # sub-query attempts that raised
    shards_lost: Tuple[int, ...] = ()   # shards no replica could serve
    shards_skipped: Tuple[int, ...] = ()  # shards deliberately skipped
                                  # (overload partial-answer rung, §8)
    t_effective: float = 0.0      # serve wall under the hedging policy
                                  # (== t_wall when nothing hedged)

    @property
    def response_time(self) -> float:
        """Main-operation response time (paper excludes data load / index
        construction; we additionally report t_build separately).  The
        scheduler overlaps the engines, so this is the measured wall time
        of the query phase — NOT the sum of per-engine times, which
        double-counts the overlap window."""
        if self.t_wall > 0.0:
            return self.t_wall
        return self.t_dense + self.t_sparse + self.t_brute


@dataclasses.dataclass
class KNNResult:
    dists: np.ndarray     # (|D|, K) finalized distance, ascending: Euclidean
                          # (l2), cosine distance 1 − cos (cosine), or −q·c
                          # (ip — may be negative)
    ids: np.ndarray       # (|D|, K) neighbor ids
    source: np.ndarray    # (|D|,) 0=dense engine, 1=sparse engine, 2=brute lane
    stats: JoinStats
    # Degraded-result contract (DESIGN.md §7): per-query per-shard
    # served mask, (|Q|, n_shards) bool.  Column s is False when no
    # replica could serve shard s — the result rows are then the exact
    # top-K over the SURVIVING shards (never silently wrong, never an
    # exception).  None on single-device queries (coverage is total).
    coverage: Optional[np.ndarray] = None
    # Approximate-mode contract (DESIGN.md §9): the calibration-measured
    # recall@k estimate of the serving tier.  1.0 on every exact path
    # (recall_target=1.0, which is bit-identical to the pre-knob code).
    recall_estimate: float = 1.0

    @property
    def fully_covered(self) -> bool:
        """True iff every shard contributed to every query (always True
        for single-device results)."""
        return self.coverage is None or bool(self.coverage.all())


def _pad_ids(ids: np.ndarray, block: int) -> jnp.ndarray:
    """Pad a query-id list to a pow2 multiple of ``block`` (bounds the
    number of distinct compiled shapes across parameter sweeps)."""
    out = np.full((pow2_bucket(len(ids), block),), -1, np.int32)
    out[: len(ids)] = ids
    return jnp.asarray(out)


class HybridKNNJoin:
    """Reusable joiner: ``HybridKNNJoin(cfg).join(points)``.

    Thin self-join compatibility wrapper over the index/query API
    (DESIGN.md §3): ``join(points)`` is exactly
    ``KNNIndex.build(points, cfg).query(exclude_self=True)``, routed
    through ``repro.runtime.session.JoinSession`` so repeated joins
    reuse the built index and compiled engines.  Serving workloads
    (foreign R≠S query batches against a static database) should hold
    the ``KNNIndex`` directly."""

    def __init__(self, config: HybridConfig):
        self.config = config
        # Imported here: runtime.session imports this module's dataclasses.
        from repro.runtime.session import JoinSession

        self.session = JoinSession(config)

    def join(self, points, epsilon: Optional[float] = None) -> KNNResult:
        return self.session.join(points, epsilon)
