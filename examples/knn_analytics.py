"""Paper end-to-end driver: parameter search -> ρ^Model -> production run.

Mirrors the paper's §VI-E methodology on one dataset:
  1. grid-search (β, γ) on a SAMPLE of the data (Table VI's trick),
  2. measure T1/T2 at ρ=0.5, derive ρ^Model (Eq. 6, Table V),
  3. run the full join with the tuned parameters,
  4. compare against REFIMPL and the brute-force lower bound (Fig 11).

    PYTHONPATH=src python examples/knn_analytics.py [dataset] [k]
"""
import sys
import time

import numpy as np

from repro.core import HybridConfig, HybridKNNJoin, refimpl_knn, \
    self_join_brute
from repro.data import pointclouds
from repro.utils import init_compile_cache


def main():
    init_compile_cache()
    ds = sys.argv[1] if len(sys.argv) > 1 else "susy"
    k = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    pts = pointclouds.load(ds, n_override=6000)
    m = min(6, pts.shape[1])
    print(f"dataset={ds} |D|={len(pts)} n={pts.shape[1]} K={k}\n")

    # -- 1. sampled parameter search (f = 10%) ---------------------------
    sub = pts[np.random.default_rng(0).permutation(len(pts))[:len(pts) // 10]]
    best, best_t = None, float("inf")
    for beta in (0.0, 1.0):
        for gamma in (0.0, 0.8):
            cfg = HybridConfig(k=k, m=m, beta=beta, gamma=gamma, rho=0.5)
            r = HybridKNNJoin(cfg).join(sub)
            print(f"  sample grid β={beta} γ={gamma}: "
                  f"{r.stats.response_time:.3f}s")
            if r.stats.response_time < best_t:
                best, best_t = (beta, gamma), r.stats.response_time
    beta, gamma = best
    print(f"  -> selected β={beta} γ={gamma}\n")

    # -- 2. ρ^Model from a ρ=0.5 probe ------------------------------------
    probe = HybridKNNJoin(HybridConfig(
        k=k, m=m, beta=beta, gamma=gamma, rho=0.5)).join(pts)
    rho = probe.stats.rho_model
    print(f"  T1={probe.stats.t1_per_query:.2e}s "
          f"T2={probe.stats.t2_per_query:.2e}s -> ρ^Model={rho:.3f}")
    print(f"  t(ρ=0.5) = {probe.stats.response_time:.3f}s")

    # -- 3. tuned production run ------------------------------------------
    tuned = HybridKNNJoin(HybridConfig(
        k=k, m=m, beta=beta, gamma=gamma, rho=rho)).join(pts)
    t_hybrid = tuned.stats.response_time
    print(f"  t(ρ^Model) = {t_hybrid:.3f}s "
          f"({probe.stats.response_time / t_hybrid:.2f}× vs ρ=0.5)\n")

    # -- 4. baselines -------------------------------------------------------
    ref, _ = refimpl_knn(pts, k=k)
    t_ref = ref.stats.t_sparse
    t0 = time.perf_counter()
    self_join_brute(pts, k=k, kernel_mode="ref")
    t_brute = time.perf_counter() - t0
    print(f"  REFIMPL        : {t_ref:.3f}s")
    print(f"  GPU-JOINLINEAR : {t_brute:.3f}s")
    print(f"  HYBRIDKNN-JOIN : {t_hybrid:.3f}s "
          f"-> {t_ref / t_hybrid:.2f}× vs REFIMPL, "
          f"{t_brute / t_hybrid:.2f}× vs brute")

    # exactness
    np.testing.assert_allclose(
        np.sort(tuned.dists, axis=1), np.sort(ref.dists, axis=1),
        rtol=1e-4, atol=1e-4)
    print("  hybrid == refimpl results: EXACT")


if __name__ == "__main__":
    main()
