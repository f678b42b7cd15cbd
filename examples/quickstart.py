"""Quickstart: build a KNN index once, serve self-join and R≠S queries.

    PYTHONPATH=src python examples/quickstart.py

Walks the index/query serving API (DESIGN.md §3) on a synthetic cloud:
``KNNIndex.build`` runs the per-database steps of Algorithm 1 once —
REORDER, ε selection, grid + pyramid construction — then ``query``
runs the hybrid pipeline (γ/ρ work split by reference-grid density,
the §V-A work queue feeding the dense MXU-tile engine in batches while
the sparse pyramid engine drains asynchronously, §V-E failure
reassignment, brute certification) for any query set:

  * the classic self-join is ``index.query(exclude_self=True)``;
  * foreign (R≠S) batches against the same index need no rebuild;
  * steady-state batches reuse every compiled engine (zero compiles).

Both results are verified exact against a float64 oracle.
"""
import time

import numpy as np

from repro.core import HybridConfig
from repro.runtime import KNNIndex

from repro.data import pointclouds
from repro.utils import init_compile_cache


def main():
    init_compile_cache()
    # A cloud with the paper's density structure: dense cores (the GPU's
    # work in the paper; the MXU tile join here) + sparse background (the
    # CPU's work; the pyramid engine here).
    pts = pointclouds.load("chist", n_override=4000)
    k = 5

    # online_rebalance off: demotion round shapes are timing-dependent
    # (README caveat), and a serving demo wants the deterministic
    # zero-compile steady state from the very first warm batch.
    cfg = HybridConfig(k=k, m=6, beta=0.0, gamma=0.4, rho=0.2, n_batches=4,
                       online_rebalance=False)

    # -- build once --------------------------------------------------------
    t0 = time.perf_counter()
    index = KNNIndex.build(pts, cfg)
    t_build = time.perf_counter() - t0
    print("KNNIndex on a CHist-like cloud "
          f"(|D|={index.n_points}, n={index.n_dims}, K={k})")
    print(f"  build (reorder+ε+grids): {t_build:.3f}s "
          f"(ε = {index.eps:.4f}, backend = {index.backend})")

    # -- self-join: the classic HYBRIDKNN-JOIN -----------------------------
    t0 = time.perf_counter()
    result = index.query(exclude_self=True)
    t_cold = time.perf_counter() - t0
    s = result.stats
    print(f"  self-join work split  : {s.n_dense} dense / {s.n_sparse} sparse "
          f"(threshold {s.n_thresh:.1f} pts/cell)")
    print(f"  queue                 : {s.n_batches} dense batches {s.batch_sizes}, "
          f"{s.n_sparse_rounds} sparse rounds, "
          f"{s.n_rebalanced} demoted online (ρ^online {s.rho_online:.3f})")
    print(f"  dense-engine failures : {s.n_failed} (reassigned, §V-E)")
    print(f"  uncertified -> brute  : {s.n_uncertified}")
    print(f"  response time         : {s.response_time:.3f}s "
          f"(dense {s.t_dense:.3f} / sparse {s.t_sparse:.3f} / "
          f"brute {s.t_brute:.3f})")
    print(f"  ρ^Model (Eq. 6)       : {s.rho_model:.3f} "
          f"(T1={s.t1_per_query:.2e}s, T2={s.t2_per_query:.2e}s)")

    # verify self-join exactness against the float64 oracle
    d2 = ((pts[:, None, :].astype(np.float64) - pts[None]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    want = np.sqrt(np.sort(d2, axis=1)[:, :k])
    err = np.abs(np.sort(result.dists, axis=1) - want).max()
    print(f"  max |dist - oracle|   : {err:.2e}  "
          f"{'EXACT' if err < 1e-3 else 'MISMATCH'}")
    by_engine = np.bincount(result.source, minlength=3)
    print(f"  resolved by engine    : dense={by_engine[0]} "
          f"sparse={by_engine[1]} brute={by_engine[2]}")

    # -- serving: foreign (R≠S) query batches against the same index -------
    rng = np.random.default_rng(7)
    batch = (pts[rng.integers(0, len(pts), 512)]
             + 0.02 * rng.normal(size=(512, pts.shape[1]))).astype(np.float32)
    t0 = time.perf_counter()
    qr = index.query(batch)                   # cold: compiles R≠S engines
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    qr2 = index.query(batch.copy())           # steady state
    t_steady = time.perf_counter() - t0
    d2q = ((batch[:, None, :].astype(np.float64) - pts[None]) ** 2).sum(-1)
    wantq = np.sqrt(np.sort(d2q, axis=1)[:, :k])
    errq = np.abs(np.sort(qr.dists, axis=1) - wantq).max()
    print(f"  R≠S batch (512 q)     : {t_first:.3f}s cold, "
          f"{t_steady:.3f}s steady ({512 / t_steady:.0f} q/s), "
          f"{qr2.stats.n_engine_compiles} new engine compiles "
          f"(cache: {index.compile_counts})")
    print(f"  max |dist - oracle|   : {errq:.2e}  "
          f"{'EXACT' if errq < 1e-3 else 'MISMATCH'}")
    assert err < 1e-3 and errq < 1e-3, "oracle mismatch"
    assert qr2.stats.n_engine_compiles == 0, "steady-state query recompiled"


if __name__ == "__main__":
    main()
