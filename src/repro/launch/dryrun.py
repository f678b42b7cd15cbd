"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is how the distribution config is proven coherent without hardware:
``jax.jit(step, in_shardings=...).lower(*specs).compile()`` must succeed
under the single-pod (16,16) mesh AND the multi-pod (2,16,16) = 512-chip
mesh for every applicable cell; memory_analysis / cost_analysis /
collective-byte parsing feed EXPERIMENTS.md §Dry-run and §Roofline.

Usage::

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.dryrun \
        --arch olmo_1b --shape train_4k --mesh both
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.dryrun --all
"""
# Before anything initializes a JAX backend (the device count locks
# then):
from repro.utils import force_host_devices

force_host_devices(512)

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs.base import (  # noqa: E402
    ARCH_IDS, SHAPES, applicable_shapes, get_config)
from repro.launch import analytic, hlo_analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_chip_count  # noqa: E402
from repro.launch.steps import build_cell  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "results", "dryrun")


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chip_count(mesh)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.shape.values()),
        "multi_pod": multi_pod, "chips": chips, "ok": False,
    }
    t0 = time.perf_counter()
    try:
        fn, in_specs, in_shardings = build_cell(cfg, shape, mesh)
        with mesh:
            jitted = jax.jit(fn, in_shardings=in_shardings)
            lowered = jitted.lower(*in_specs)
            rec["t_lower_s"] = time.perf_counter() - t0
            t1 = time.perf_counter()
            compiled = lowered.compile()
            rec["t_compile_s"] = time.perf_counter() - t1

        rec["memory_analysis"] = hlo_analysis.memory_analysis_dict(compiled)
        rec["cost_analysis"] = {
            k: v for k, v in hlo_analysis.cost_analysis_dict(compiled).items()
            if k in ("flops", "bytes accessed", "transcendentals",
                     "optimal_seconds")}
        hlo = compiled.as_text()
        rec["collective_bytes"] = hlo_analysis.collective_bytes(hlo)
        rec["collective_bytes_weighted"] = \
            hlo_analysis.collective_bytes_weighted(hlo)
        rec["collective_counts"] = hlo_analysis.collective_counts(hlo)
        rec["hlo_lines"] = hlo.count("\n")

        # Roofline terms: analytic model (trip-count-correct); as-compiled
        # cost_analysis kept alongside (XLA counts while bodies once).
        costs = analytic.cell_costs(cfg, shape, mesh)
        rec["analytic"] = {
            "flops_per_device": costs.flops_per_device,
            "hbm_bytes_per_device": costs.hbm_bytes_per_device,
            "breakdown": costs.breakdown,
        }
        roof = hlo_analysis.Roofline(
            flops_per_device=costs.flops_per_device,
            hbm_bytes_per_device=costs.hbm_bytes_per_device,
            collective_bytes_per_device=(
                rec["collective_bytes_weighted"]["total"]),
            chips=chips)
        rec["roofline"] = roof.as_dict()
        mf = hlo_analysis.model_flops(cfg, shape)
        rec["model_flops_global"] = mf
        rec["model_flops_ratio"] = mf / max(
            costs.flops_per_device * chips, 1.0)
        rec["ok"] = True
        if verbose:
            ma = rec["memory_analysis"]
            rl = rec["roofline"]
            print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: OK  "
                  f"lower {rec['t_lower_s']:.1f}s compile "
                  f"{rec['t_compile_s']:.1f}s  "
                  f"argbytes/dev {ma.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
                  f"temp/dev {ma.get('temp_size_in_bytes', 0)/2**30:.2f}GiB  "
                  f"coll/dev {rec['collective_bytes_weighted']['total']/2**20:.1f}MiB")
            print(f"  roofline: compute {rl['t_compute_s']:.2e}s  memory "
                  f"{rl['t_memory_s']:.2e}s  collective "
                  f"{rl['t_collective_s']:.2e}s  -> {rl['dominant']}-bound; "
                  f"model/analytic flops ratio "
                  f"{rec['model_flops_ratio']:.2f}")
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["error"] = repr(e)
        rec["traceback"] = traceback.format_exc()
        if verbose:
            print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: "
                  f"FAILED — {e!r}")
    return rec


def save(rec: dict, out_dir: str) -> str:
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default all)")
    ap.add_argument("--shape", default=None, help="one shape (default all)")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(RESULTS_DIR))
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = [args.shape] if args.shape else applicable_shapes(cfg)
        for shape_name in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape_name, multi_pod=mp)
                save(rec, args.out)
                n_ok += rec["ok"]
                n_fail += not rec["ok"]
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
