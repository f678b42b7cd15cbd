"""§Perf hillclimb driver: hypothesis -> change -> re-lower -> record.

Each iteration re-runs the dry-run for a chosen cell with a config
override, records the three roofline terms before/after, and appends to
results/perf/<cell>.json.  The EXPERIMENTS.md §Perf log is generated
from these records.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.perf_iter --arch olmo_1b \
        --shape prefill_32k --variant causal_skip
"""
from repro.utils import force_host_devices

force_host_devices(512)

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import os            # noqa: E402

from repro.configs.base import SHAPES, get_config  # noqa: E402
from repro.launch import analytic, hlo_analysis    # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_chip_count  # noqa: E402
from repro.launch.steps import build_cell          # noqa: E402

import jax  # noqa: E402

PERF_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results", "perf")

# Named config variants = the §Perf levers.  Each is (description,
# hypothesis, config-override dict).
VARIANTS = {
    "baseline": ("paper-faithful / naive baseline", "reference point", {}),
    "causal_skip": (
        "diagonal-blocked causal attention",
        "causal masks waste half the attention FLOPs; static skipping of "
        "above-diagonal kv chunks should cut the attention share of the "
        "compute term ~2x with zero accuracy change",
        {"causal_skip": True}),
    "micro8": (
        "8-way gradient accumulation",
        "activation memory scales with per-device microbatch; 8 microsteps "
        "should cut temp bytes ~8x on the memory term at ~equal FLOPs",
        {"micro_steps": 8}),
    "micro16": (
        "16-way gradient accumulation",
        "further activation-memory reduction; diminishing returns expected "
        "once params+opt dominate",
        {"micro_steps": 16}),
    "no_remat": (
        "disable rematerialization",
        "remat adds a full forward recompute (compute term x4/3 -> x1); "
        "only viable when activations fit — trade memory for compute",
        {"remat": False}),
    "no_seq_shard": (
        "disable sequence sharding (SP)",
        "SP saves activation memory but adds all-gathers around attention; "
        "for short sequences the collective term should drop",
        {"seq_shard": False}),
    "attn_chunk_512": (
        "smaller flash attention chunk",
        "smaller tiles reduce peak VMEM-resident logits at slightly more "
        "loop overhead",
        {"attn_chunk": 512}),
    "attn_chunk_2048": (
        "larger flash attention chunk",
        "larger tiles amortize softmax/rescale overhead; memory term rises",
        {"attn_chunk": 2048}),
    "bf16_opt": (
        "bf16 optimizer moments",
        "opt-state traffic halves -> memory term drops on update-bound "
        "train cells",
        {"opt_state_dtype": "bfloat16"}),
    "fsdp": (
        "FSDP param+opt sharding over the data axis",
        "param memory /16 at the cost of per-layer all-gathers "
        "(collective term rises, memory term falls)",
        {"fsdp": True}),
    "moe_sharded": (
        "per-data-shard MoE dispatch (EP all-to-all)",
        "baseline MoE scatters into a replicated (e·cap, d) buffer, "
        "all-reduced across 16 data shards every layer — ~e·cap·d·4B of "
        "collective per layer.  Per-shard capacity buffers keep the "
        "scatter local; only the tokens·k·d expert exchange crosses the "
        "mesh: predict ~100–1000× lower collective term",
        {"moe_sharded_dispatch": True}),
    "moe_sharded_micro8": (
        "sharded MoE dispatch + 8-way grad accumulation",
        "compose the collective fix with the activation-memory fix",
        {"moe_sharded_dispatch": True, "micro_steps": 8}),
    "causal_skip_micro8": (
        "diagonal-blocked attention + 8-way grad accumulation",
        "compose the compute fix with the activation-memory fix",
        {"causal_skip": True, "micro_steps": 8}),
    "causal_skip_micro16": (
        "diagonal-blocked attention + 16-way grad accumulation",
        "same, deeper accumulation",
        {"causal_skip": True, "micro_steps": 16}),
    "micro32": (
        "32-way gradient accumulation",
        "push activation memory below the f32 grad-accumulator floor",
        {"micro_steps": 32}),
    "dots_micro16": (
        "selective remat (save dots) + 16-way accumulation",
        "full remat re-runs the whole forward (compute ×4/3); saving "
        "matmul outputs and recomputing only elementwise ops cuts the "
        "compute term ~22% for the memory the micro-steps freed up",
        {"remat_policy": "dots", "causal_skip": True, "micro_steps": 16}),
    "dots_micro32": (
        "selective remat (save dots) + 32-way accumulation",
        "same compute win, deepest memory reduction",
        {"remat_policy": "dots", "causal_skip": True, "micro_steps": 32}),
    "dots_skip": (
        "selective remat (save dots) + diagonal-blocked attention",
        "kill the remat recompute tax (−22% compute) and the masked "
        "attention waste without touching microbatching (FSDP params are "
        "re-gathered per microbatch, so accumulation raises the "
        "collective term on FSDP models — avoid it when memory allows)",
        {"remat_policy": "dots", "causal_skip": True}),
}


def run_variant(arch: str, shape_name: str, variant: str,
                multi_pod: bool = False) -> dict:
    desc, hypothesis, overrides = VARIANTS[variant]
    cfg = dataclasses.replace(get_config(arch), **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    fn, specs, shardings = build_cell(cfg, shape, mesh)
    with mesh:
        lowered = jax.jit(fn, in_shardings=shardings).lower(*specs)
        compiled = lowered.compile()
    hlo = compiled.as_text()
    coll = hlo_analysis.collective_bytes_weighted(hlo)
    costs = analytic.cell_costs(cfg, shape, mesh)
    roof = hlo_analysis.Roofline(
        flops_per_device=costs.flops_per_device,
        hbm_bytes_per_device=costs.hbm_bytes_per_device,
        collective_bytes_per_device=coll["total"],
        chips=mesh_chip_count(mesh))
    ma = hlo_analysis.memory_analysis_dict(compiled)
    return {
        "arch": arch, "shape": shape_name, "variant": variant,
        "description": desc, "hypothesis": hypothesis,
        "overrides": overrides,
        "roofline": roof.as_dict(),
        "collective_bytes": coll,
        "memory_analysis": ma,
        "temp_gib_per_dev": ma.get("temp_size_in_bytes", 0) / 2**30,
        "arg_gib_per_dev": ma.get("argument_size_in_bytes", 0) / 2**30,
    }


def append(rec: dict):
    os.makedirs(PERF_DIR, exist_ok=True)
    path = os.path.join(PERF_DIR, f"{rec['arch']}__{rec['shape']}.json")
    hist = []
    if os.path.exists(path):
        with open(path) as f:
            hist = json.load(f)
    hist = [h for h in hist if h["variant"] != rec["variant"]] + [rec]
    with open(path, "w") as f:
        json.dump(hist, f, indent=1)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", required=True,
                    choices=sorted(VARIANTS), nargs="+")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    for v in args.variant:
        rec = run_variant(args.arch, args.shape, v,
                          multi_pod=args.multi_pod)
        path = append(rec)
        rl = rec["roofline"]
        print(f"[perf] {args.arch}×{args.shape} {v}: "
              f"compute {rl['t_compute_s']:.3e}s "
              f"memory {rl['t_memory_s']:.3e}s "
              f"collective {rl['t_collective_s']:.3e}s "
              f"({rl['dominant']}-bound) "
              f"temp {rec['temp_gib_per_dev']:.1f}GiB -> {path}")


if __name__ == "__main__":
    main()
