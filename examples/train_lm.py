"""End-to-end training example: a ~100M-param dense LM for a few hundred
steps on CPU (reduced width, full framework path: sharded data pipeline,
AdamW, remat'd scan-over-layers model, async checkpoints, supervisor).

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

This is the same driver the pod launch uses (repro.launch.train); here
it is parameterized to a CPU-feasible ~100M config and demonstrates
loss descent + a mid-run restart from checkpoint.
"""
import argparse
import dataclasses

from repro.configs.base import get_config
from repro.launch import train as train_mod
from repro.utils import init_compile_cache


def main():
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=96)
    ap.add_argument("--full-100m", action="store_true",
                    help="the deliverable-scale config (~100M params, a few "
                         "hundred steps) — sized for a pod slice; on this "
                         "CPU container expect ~10s/step")
    args = ap.parse_args()

    base = get_config("olmo_1b")
    if args.full_100m:
        # ~100M params: olmo-family, 8 layers × 768 wide, 24k vocab
        cfg = dataclasses.replace(
            base, n_layers=8, d_model=768, n_heads=12, n_kv_heads=12,
            d_ff=3072, vocab_size=24576, dtype="float32",
            param_dtype="float32", attn_chunk=0, scan_layers=True)
        args.steps = max(args.steps, 300)
    else:
        # CPU-friendly ~25M variant of the same family
        cfg = dataclasses.replace(
            base, n_layers=6, d_model=512, n_heads=8, n_kv_heads=8,
            d_ff=2048, vocab_size=8192, dtype="float32",
            param_dtype="float32", attn_chunk=0, scan_layers=True)
    n_p = cfg.n_params()
    print(f"[example] training a {n_p / 1e6:.0f}M-param olmo-family LM "
          f"for {args.steps} steps (batch {args.batch} × seq {args.seq})")

    # monkey-point the train driver at our reduced config
    import repro.configs.base as cb
    orig = cb.get_smoke_config
    cb.get_smoke_config = lambda arch: cfg
    # fault injected after the first checkpoint exists (live FT demo)
    ckpt_every = max(1, min(50, args.steps // 4))
    fault_at = min(ckpt_every + max(args.steps // 2, 1), args.steps - 1)
    try:
        report = train_mod.main([
            "--arch", "olmo_1b", "--smoke",
            "--steps", str(args.steps),
            "--batch", str(args.batch), "--seq", str(args.seq),
            "--checkpoint-every", str(ckpt_every),
            "--ckpt-dir", "/tmp/repro_train_lm_example",
            "--inject-fault", str(fault_at),
            "--log-every", "20",
        ])
    finally:
        cb.get_smoke_config = orig
    assert report.completed, "training did not complete"
    print("[example] done — survived the injected fault and completed")


if __name__ == "__main__":
    main()
