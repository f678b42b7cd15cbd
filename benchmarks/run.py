"""Benchmark driver: one run per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale 0.25] [--quick]

Writes results/bench/*.json, prints each table, and ends with a summary
of the paper's headline claims vs what this run measured."""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import common  # noqa: E402
from benchmarks import (  # noqa: E402
    fig6_refimpl_scaling, fig7_brute, fig11_vs_k, overload, recall,
    serving, table3_granularity, table4_param_grid, table5_rho_model,
    table6_sampled_params)
from repro.utils import init_compile_cache  # noqa: E402


def main():
    init_compile_cache()
    ap = common.parser("benchmarks.run")
    ap.add_argument("--quick", action="store_true",
                    help="tiny datasets (CI smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help="kernel-path CI smoke: one tiny dataset, Table III "
                         "only — pair with --backend interpret|fused so the "
                         "Pallas kernel paths run end-to-end on CPU")
    ap.add_argument("--serving", action="store_true",
                    help="serving mode only: steady-state index.query "
                         "batches against a built KNNIndex (R≠S path; "
                         "asserts zero steady-state compiles)")
    ap.add_argument("--recall", action="store_true",
                    help="recall mode only: the recall@k-vs-queries/s "
                         "frontier sweep (exact baseline + recall_target "
                         "grid, per metric) with oracle-measured recall "
                         "(DESIGN.md §9.4)")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="emit the machine-readable BENCH_<tag>.json "
                         "perf-trajectory record (per-variant wall time, "
                         "queries/s, compile counts, peak-HBM memory "
                         "analysis).  With no PATH, writes "
                         "results/bench/BENCH_<tag>.json")
    ap.add_argument("--tag", default=None,
                    help="tag for the BENCH json (default: backend name, "
                         "prefixed smoke- under --smoke)")
    args = ap.parse_args()
    if args.quick:
        args.scale = 0.08
    t0 = time.time()

    if args.serving:
        # Serving default is smaller than the table default (CI path);
        # an explicit --scale always wins.
        scale_explicit = any(
            a == "--scale" or a.startswith("--scale=") for a in sys.argv
        )
        if not scale_explicit:
            args.scale = 0.1
        n_rep, n_shards = common.parse_mesh(args.mesh)
        mesh_part = (f"mesh{n_rep}x{n_shards}-"
                     if n_rep * n_shards > 1 else "")
        mut_part = "mutate-" if args.mutate else ""
        fault_part = "faults-" if args.faults else ""
        load_part = "load-" if args.load is not None else ""
        print(f"[bench] SERVING backend={args.backend} "
              f"mesh={n_rep}x{n_shards} mutate={args.mutate} "
              f"faults={args.faults} load={args.load} "
              f"datasets={args.datasets} scale={args.scale}")
        rec = serving.run(args)
        assert rec, "serving mode produced no records"
        if args.mutate:
            assert all("mutation" in v for v in rec.values()), (
                "--mutate produced no churn records")
        if args.faults:
            assert all("faults" in v for v in rec.values()), (
                "--faults produced no drill records")
            for v in rec.values():
                on = v["faults"]["with_hedging"]
                assert on["n_hedged"] > 0, (
                    "fault drill never hedged — spikes below threshold?")
        tables = {"serving": rec}
        if args.load is not None:
            over = overload.run(args)
            assert over, "--load produced no overload records"
            for name, v in over.items():
                # at-or-over capacity the server must keep every served
                # request within deadline (admission shed, never a
                # silent miss) — the drill's hard acceptance invariant
                assert v["n_deadline_misses"] == 0, (
                    f"overload {name}: {v['n_deadline_misses']} served "
                    "requests missed their deadline")
                if v["load_factor"] >= 2.0:
                    assert v["n_shed"] and sum(v["n_shed"].values()) > 0, (
                        f"overload {name}: >=2x capacity shed nothing")
            tables["overload"] = over
        _emit_json(args, tables,
                   tag_default=(f"serving-{mesh_part}{mut_part}"
                                f"{fault_part}{load_part}{args.backend}"))
        print(f"[bench] serving ok ({time.time() - t0:.0f}s, "
              f"{len(rec)} datasets)")
        return

    if args.recall:
        scale_explicit = any(
            a == "--scale" or a.startswith("--scale=") for a in sys.argv
        )
        if not scale_explicit:
            args.scale = 0.1
        print(f"[bench] RECALL backend={args.backend} "
              f"datasets={args.datasets} scale={args.scale}")
        rec = recall.run(args)
        assert rec, "recall mode produced no records"
        for name, v in rec.items():
            # the subsystem's contract: measured recall@k on held-out
            # queries meets the target within the acceptance margin
            assert v["recall"] >= v["recall_target"] - 0.01, (
                f"recall {name}: measured {v['recall']:.3f} below "
                f"target {v['recall_target']} - 0.01")
        _emit_json(args, {"recall": rec},
                   tag_default=f"recall-{args.backend}")
        print(f"[bench] recall ok ({time.time() - t0:.0f}s, "
              f"{len(rec)} points)")
        return

    if args.smoke:
        args.scale = 0.05
        args.datasets = ["chist"]
        args.trials = 1
        print(f"[bench] SMOKE backend={args.backend} "
              f"datasets={args.datasets} scale={args.scale}")
        rec = table3_granularity.run(args)
        assert rec, "table3 smoke produced no records"
        # (zero-compile steady state is asserted by the test suite under a
        # deterministic scheduler; online rebalance makes it timing-
        # dependent here, so the smoke only gates on the runs completing)
        _emit_json(args, {"table3": rec})
        print(f"[bench] smoke ok ({time.time() - t0:.0f}s, "
              f"{len(rec)} configs)")
        return

    print(f"[bench] datasets={args.datasets} scale={args.scale}")
    results = {}
    results["serving"] = serving.run(args)
    results["table3"] = table3_granularity.run(args)
    results["table4"] = table4_param_grid.run(args)
    results["table5"] = table5_rho_model.run(args)
    results["table6"] = table6_sampled_params.run(args)
    results["fig6"] = fig6_refimpl_scaling.run(args)
    results["fig7"] = fig7_brute.run(args)
    results["fig11"] = fig11_vs_k.run(args)

    # ---- headline claim check (paper §VI) -------------------------------
    print("\n== paper claims vs this run ==")
    claims = []
    t5 = results["table5"]
    best_t5 = max(v["speedup"] for v in t5.values())
    claims.append(("ρ^Model speeds up vs ρ=0.5 (paper: up to 1.62×)",
                   f"max {best_t5:.2f}×", best_t5 > 1.0))
    t6 = results["table6"]
    rec_ok = all(v["match"] for v in t6.values())
    claims.append(("best params recoverable from a sample (Table VI)",
                   "all recovered" if rec_ok else "some missed", rec_ok))
    f11 = results["fig11"]
    sp = [v["speedup_vs_refimpl"] for v in f11.values()]
    claims.append(("hybrid beats REFIMPL (paper: 1.03×–2.56×)",
                   f"range {min(sp):.2f}×–{max(sp):.2f}×",
                   max(sp) > 1.0))
    # brute-vs-hybrid is a scale-dependent claim (the paper runs 5M-point
    # datasets on a GP100); we check it on the largest cloud we run
    big = [v for kk, v in f11.items() if kk.startswith("susy")]
    brute_slower = all(v["t_brute_s"] > v["t_hybrid_s"] for v in big) \
        if big else False
    claims.append(("brute slower than hybrid on the largest cloud (Fig 11)",
                   "yes" if brute_slower else
                   "no at this --scale (expected at paper scale)",
                   brute_slower))
    for desc, got, ok in claims:
        print(f"  [{'ok' if ok else '!!'}] {desc}: {got}")

    os.makedirs(common.RESULTS_DIR, exist_ok=True)
    with open(os.path.join(common.RESULTS_DIR, "summary.json"), "w") as f:
        json.dump({"claims": [(d, g, bool(o)) for d, g, o in claims],
                   "wall_s": time.time() - t0}, f, indent=1)
    _emit_json(args, results)
    print(f"\n[bench] total {time.time() - t0:.0f}s; "
          f"results in {common.RESULTS_DIR}")


def _emit_json(args, tables, tag_default=None):
    """--json: write the BENCH_<tag>.json trajectory record.  The knobs
    that produced each number live in the per-variant ``config`` embeds
    (every benchmark builds its own HybridConfig, so there is no honest
    run-wide config beyond the resolved backend, which rides at the
    record's top level)."""
    if args.json is None:
        return
    tag = args.tag or tag_default or (
        f"smoke-{args.backend}" if args.smoke else args.backend)
    path = args.json or os.path.join(args.out, f"BENCH_{tag}.json")
    common.emit_bench_json(path, tag, args.backend, tables)


if __name__ == "__main__":
    main()
