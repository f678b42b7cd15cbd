"""§Perf hillclimb for the paper's own workload: the distributed KNN
join on the production mesh.

Variants of the ring-systolic self-join (core/distributed.py), lowered
and compiled on the single-pod (16,16) mesh with the corpus sharded over
"model" (256-device roofline from the same three terms as the LM cells):

  baseline     f32 points, ring over the model axis
  bf16_wire    corpus shards rotate in bf16 (distances accumulated f32):
               hypothesis — collective term halves, exactness preserved
               to bf16 key precision (re-ranked f32 on the local shard)
  replicated   corpus replicated, no ring: collective term ~0 but
               per-device memory × n_shards — the paper's in-memory
               single-GPU assumption, for contrast
  hybrid_spmd  the full hybrid algorithm (density split + fail lanes) as
               one SPMD program — the faithful-paper cell

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.perf_knn --variant baseline
"""
from repro.utils import force_host_devices

force_host_devices(512)

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import hybrid_join_spmd, ring_self_join  # noqa: E402
from repro.core.distributed import ring_self_join_bf16   # noqa: E402
from repro.core import brute as brute_lib                # noqa: E402
from repro.launch import hlo_analysis                    # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_chip_count  # noqa: E402

PERF_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results", "perf")

# Production workload: 16.7M points × 32 dims (SuSy-scale ×3), K=8 —
# corpus sharded over the 16-way model axis, queries over data.
N_POINTS = 1 << 24
N_DIMS = 32
K = 8


def build(variant: str, mesh):
    pts = jax.ShapeDtypeStruct((N_POINTS, N_DIMS), jnp.float32)
    if variant == "baseline":
        fn = ring_self_join(mesh, ("model",), k=K, kernel_mode="ref",
                            corpus_chunk=1024)
        return fn, (pts,)
    if variant == "bf16_wire":
        fn = ring_self_join_bf16(mesh, ("model",), k=K, corpus_chunk=1024)
        return fn, (pts,)
    if variant == "replicated":
        from jax.sharding import NamedSharding, PartitionSpec as P

        def fn(points):
            # corpus replicated, queries sharded over every mesh axis
            q = jax.lax.with_sharding_constraint(
                points, NamedSharding(mesh, P(("data", "model"))))
            ids = jnp.arange(points.shape[0], dtype=jnp.int32)
            return brute_lib.brute_knn(points, q, ids, k=K,
                                       corpus_chunk=1024,
                                       kernel_mode="ref")
        return jax.jit(fn), (pts,)
    if variant == "hybrid_spmd":
        fn = hybrid_join_spmd(mesh, ("data",), k=K, rho=0.5,
                              dense_budget=1024, sparse_budget=512)
        eps = jax.ShapeDtypeStruct((), jnp.float32)
        return fn, (pts, eps)
    raise ValueError(variant)


HYPOTHESES = {
    "baseline": "ring join: collective = |D|·n·4B rotated through every "
                "device; compute = |D|²·n/P MXU work",
    "bf16_wire": "halving wire bytes halves the collective term at "
                 "unchanged compute — free when compute-bound",
    "replicated": "no ring traffic at all, but |D|·n bytes live per "
                  "device (memory ceiling) — the paper's single-GPU form",
    "hybrid_spmd": "the paper's full algorithm: grid-pruned candidate "
                   "sets cut compute ~|D|/cell-occupancy vs brute ring",
    "session_serving": "persistent JoinSession amortizes engine "
                       "compiles: steady-state joins pay query work "
                       "only (zero retrace on the response path)",
}


def run_session_serving(n_batches: int, backend: str = "auto"):
    """Executed (not lowered) serving measurement: cold vs steady-state
    join latency through the work-queue scheduler on a scaled workload.
    ``backend`` picks the engine path (cell-tiled MXU vs per-query ref),
    so the tiled hot loop is measured on whatever host runs this."""
    import time

    import numpy as np

    from repro.core import HybridConfig
    from repro.runtime import JoinSession

    n, dim, k = 4096, 16, 8
    r = np.random.default_rng(0)
    pts = np.concatenate([
        r.normal(0, 0.05, (n // 2, dim)),
        r.uniform(-3, 3, (n - n // 2, dim)),
    ]).astype(np.float32)
    session = JoinSession(HybridConfig(
        k=k, m=min(6, dim), gamma=0.2, rho=0.2, n_batches=n_batches,
        backend=backend))

    t0 = time.perf_counter()
    cold = session.join(pts)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    steady = session.join(pts.copy())       # same shapes, fresh values
    t_steady = time.perf_counter() - t0
    return {
        "arch": "knn_join", "shape": f"serving_{n}x{dim}d",
        "variant": "session_serving",
        "hypothesis": HYPOTHESES["session_serving"],
        "backend": session.backend,
        "n_batches": n_batches,
        "t_cold_s": t_cold,
        "t_steady_s": t_steady,
        "compiles_cold": cold.stats.n_engine_compiles,
        "compiles_steady": steady.stats.n_engine_compiles,
        "steady_batch_sizes": steady.stats.batch_sizes,
        "steady_t_batches": steady.stats.t_dense_batches,
        "n_rebalanced": steady.stats.n_rebalanced,
        "rho_online": steady.stats.rho_online,
        "response_s": steady.stats.response_time,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", nargs="+", default=["baseline"],
                    choices=sorted(HYPOTHESES))
    ap.add_argument("--n-batches", type=int, default=4,
                    help="work-queue granularity for session_serving")
    from repro.core.dense_join import BACKENDS

    ap.add_argument("--backend", default="auto", choices=sorted(BACKENDS),
                    help="engine backend for session_serving (cell-tiled "
                         "MXU path vs per-query ref)")
    args = ap.parse_args()
    mesh = make_production_mesh()
    chips = mesh_chip_count(mesh)
    os.makedirs(PERF_DIR, exist_ok=True)
    path = os.path.join(PERF_DIR, "knn_join__ring.json")
    hist = json.load(open(path)) if os.path.exists(path) else []
    for variant in args.variant:
        if variant == "session_serving":
            rec = run_session_serving(args.n_batches, args.backend)
            hist = [h for h in hist if h["variant"] != variant] + [rec]
            print(f"[perf-knn] {variant}: backend={rec['backend']} cold "
                  f"{rec['t_cold_s']:.3f}s "
                  f"({rec['compiles_cold']} engine compiles) steady "
                  f"{rec['t_steady_s']:.3f}s ({rec['compiles_steady']} "
                  f"compiles) nb={rec['n_batches']} "
                  f"rebalanced={rec['n_rebalanced']}")
            continue
        fn, specs = build(variant, mesh)
        with mesh:
            lowered = jax.jit(fn).lower(*specs) if variant == "replicated" \
                else fn.lower(*specs) if hasattr(fn, "lower") \
                else jax.jit(fn).lower(*specs)
            compiled = lowered.compile()
        hlo = compiled.as_text()
        coll = hlo_analysis.collective_bytes_weighted(hlo)
        ma = hlo_analysis.memory_analysis_dict(compiled)
        # Analytic terms for the TARGET (Pallas fused-top-K) execution:
        # q-tiles of 8192 rows (1 MiB VMEM at 32-d) stream the corpus, so
        # HBM traffic = corpus re-read once per resident q-tile.
        Q_TILE = 8192
        if variant in ("baseline", "bf16_wire"):
            q_loc = N_POINTS // 16                 # queries stay resident
            flops = 2.0 * q_loc * N_POINTS * N_DIMS
            hbm = N_POINTS * N_DIMS * 4.0 * (q_loc / Q_TILE)
        elif variant == "replicated":
            q_loc = N_POINTS // chips
            flops = 2.0 * q_loc * N_POINTS * N_DIMS
            hbm = N_POINTS * N_DIMS * 4.0 * max(q_loc / Q_TILE, 1.0)
        else:  # hybrid_spmd: grid-pruned — ≤ dense_budget cands/query,
            # gathered (no tile reuse: candidates differ per query)
            q_loc = N_POINTS // 16
            flops = 2.0 * q_loc * 1024 * N_DIMS
            hbm = q_loc * 1024 * N_DIMS * 4.0
        roof = hlo_analysis.Roofline(
            flops_per_device=flops,
            hbm_bytes_per_device=hbm,
            collective_bytes_per_device=coll["total"],
            chips=chips)
        rec = {
            "arch": "knn_join", "shape": "ring_16M_32d",
            "variant": variant, "hypothesis": HYPOTHESES[variant],
            "roofline": roof.as_dict(), "collective_bytes": coll,
            "memory_analysis": ma,
            "arg_gib_per_dev": ma.get("argument_size_in_bytes", 0) / 2**30,
            "temp_gib_per_dev": ma.get("temp_size_in_bytes", 0) / 2**30,
        }
        hist = [h for h in hist if h["variant"] != variant] + [rec]
        rl = rec["roofline"]
        print(f"[perf-knn] {variant}: compute {rl['t_compute_s']:.3e}s "
              f"memory {rl['t_memory_s']:.3e}s collective "
              f"{rl['t_collective_s']:.3e}s ({rl['dominant']}) "
              f"arg {rec['arg_gib_per_dev']:.2f}GiB "
              f"temp {rec['temp_gib_per_dev']:.2f}GiB")
    with open(path, "w") as f:
        json.dump(hist, f, indent=1)


if __name__ == "__main__":
    main()
