"""Benchmark entry point — one function per paper table/figure plus the
framework benchmarks. Prints
``name,us_per_call,compile_ms,steady_ms,backend,interpret,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run             # CI-sized (~15 min)
  PYTHONPATH=src python -m benchmarks.run --standard  # m up to 150 (~2 h)
  PYTHONPATH=src python -m benchmarks.run --paper     # published scale

The committed `benchmarks/results/*.json` + `bench_standard.log` +
`full_scale.json` hold the --standard and published-scale sweeps quoted in
EXPERIMENTS.md; the default profile re-validates every benchmark at a
CPU-minutes budget.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="(default profile)")
    ap.add_argument("--standard", action="store_true")
    ap.add_argument("--paper", action="store_true",
                    help="published workload scale (longest)")
    ap.add_argument("--only", default=None,
                    help="comma list: figs,online,beta,rsd,planner,kernels,"
                         "bna_batch,roofline,scenarios,plan_pipeline,serve,"
                         "analysis")
    ap.add_argument("--scenario", default=None,
                    help="comma list of scenario-registry keys for the "
                         "scenario x scheduler matrix (default: all "
                         "registered; implies the 'scenarios' section)")
    ap.add_argument("--alpha-backend", default=None,
                    choices=("auto", "numpy", "pallas"),
                    help="route merge_and_fix alphas through this backend "
                         "(default: REPRO_ALPHA_BACKEND or auto)")
    ap.add_argument("--bna-backend", default=None,
                    choices=("auto", "numpy", "pallas"),
                    help="route the batched BNA step through this backend "
                         "(default: REPRO_BNA_BACKEND or auto)")
    ap.add_argument("--plan-backend", default=None,
                    choices=("auto", "python", "jit"),
                    help="route the planning pipeline (order/decompose/"
                         "merge_and_fix) through this backend "
                         "(default: REPRO_PLAN_BACKEND or auto)")
    ap.add_argument("--matrix-seeds", type=int, default=1,
                    help="seeds per scenario in the scenario matrix; > 1 "
                         "batches the decomposition prefetch across the "
                         "whole seed set (one jit trace amortized)")
    ap.add_argument("--backfill-exec", default="packet",
                    choices=("packet", "ledger"),
                    help="backfill executor for the *_bf schedulers in the "
                         "scenario matrix (packet: timed-matching re-"
                         "execution, never worse than the plan; ledger: "
                         "historical uniform-rate sweep)")
    ap.add_argument("--driver", default="session",
                    choices=("session", "batch"),
                    help="online-protocol driver for the scenario matrix's "
                         "online rows (session: event-driven "
                         "SchedulerSession with frontier-append repair; "
                         "batch: historical closed loop — results-identical)")
    args = ap.parse_args()
    args.fast = not (args.standard or args.paper)

    from repro.core import configure_compile_cache
    configure_compile_cache()

    if args.alpha_backend:
        from repro.core import set_alpha_backend
        set_alpha_backend(args.alpha_backend)
    if args.bna_backend:
        from repro.core import set_bna_backend
        set_bna_backend(args.bna_backend)
    if args.plan_backend:
        from repro.core import set_plan_backend
        set_plan_backend(args.plan_backend)

    if args.fast:
        scale, seeds, ms, mus, factors = 0.12, 2, (10, 30, 50), (2, 5, 10), (2, 25)
    elif args.paper:
        scale, seeds, ms, mus, factors = 1.0, 3, (10, 30, 50, 100, 150), \
            (2, 5, 10, 20), (1, 2, 10, 25, 100)
    else:
        scale, seeds, ms, mus, factors = 0.35, 2, (10, 30, 50, 100, 150), \
            (2, 5, 10), (2, 10, 100)

    want = set((args.only or
                "figs,online,beta,rsd,planner,kernels,roofline,scenarios,"
                "plan_pipeline,serve,analysis").split(","))
    if args.scenario:
        want.add("scenarios")
    from . import (analysis_bench, common, kernels_bench, paper_figs,
                   plan_pipeline, planner_ab, roofline_report,
                   scenario_matrix, serve_stream)

    if "figs" in want:
        paper_figs.workload_calibration(scale)
        paper_figs.fig_a(rooted=False, scale=scale, seeds=seeds, ms=ms)
        paper_figs.fig_a(rooted=True, scale=scale, seeds=seeds, ms=ms)
        paper_figs.fig_b(rooted=False, scale=scale, seeds=seeds, mus=mus)
        paper_figs.fig_b(rooted=True, scale=scale, seeds=seeds, mus=mus)
    online_m = 150 if args.paper else 50
    if "online" in want:
        paper_figs.fig_c(rooted=False, scale=min(scale, 0.2), factors=factors,
                         m=online_m)
        paper_figs.fig_c(rooted=True, scale=min(scale, 0.2), factors=factors,
                         m=online_m)
    if "beta" in want:
        paper_figs.fig4_beta(scale=min(scale, 0.25),
                             ms=(30, 150) if not args.fast else (30,))
    if "rsd" in want:
        paper_figs.rsd(scale=min(scale, 0.15), m=50)
    if "scenarios" in want:
        profile = "paper" if args.paper else ("standard" if args.standard
                                              else "fast")
        scenario_matrix.run(
            args.scenario.split(",") if args.scenario else None,
            profile=profile, backfill_exec=args.backfill_exec,
            driver=args.driver, seeds=args.matrix_seeds)
    if "plan_pipeline" in want:
        plan_pipeline.run(fast=args.fast)
    if "serve" in want:
        serve_stream.run(fast=args.fast)
    if "planner" in want:
        planner_ab.run()
    if "kernels" in want:
        kernels_bench.run(fast=args.fast)
    elif "bna_batch" in want:
        kernels_bench.run_bna_batch(fast=args.fast)
    if "analysis" in want:
        analysis_bench.run(fast=args.fast)
    if "roofline" in want:
        roofline_report.bna_batch_roofline()
        try:
            roofline_report.render()
        except FileNotFoundError:
            print("roofline: dryrun.json missing (run repro.launch.dryrun --all)")
    common.flush_csv()


if __name__ == "__main__":
    main()
