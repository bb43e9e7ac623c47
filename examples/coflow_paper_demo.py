"""Paper walkthrough: BNA optimality, the Lemma 2 gap instance, the FSP
NP-hardness reduction, and the collective planner on a synthetic train step.

  PYTHONPATH=src python examples/coflow_paper_demo.py
"""
import numpy as np

from repro.core import (bna, configure_compile_cache, dma_srt,
                        fsp_to_coflow_job, gap_bounds, gap_instance,
                        gap_optimal_schedule_length, verify_schedule,
                        effective_size)


def main() -> None:
    configure_compile_cache()
    # 1) BNA schedules any coflow in exactly its effective size (Lemma 1)
    rng = np.random.default_rng(0)
    d = rng.integers(0, 50, size=(6, 6)).astype(np.int64)
    pieces = bna(d, validate=True)
    print(f"BNA: effective size {effective_size(d)}, schedule length "
          f"{sum(t for t, _ in pieces)}, {len(pieces)} matchings")

    # 2) Lemma 2: a DAG whose optimal makespan is Omega(sqrt(mu)) above the
    #    simple lower bounds Delta and T
    K = 4
    inst = gap_instance(K, d=3)
    delta, T = gap_bounds(inst)
    print(f"gap instance: mu={inst.jobs[0].mu}, Delta={delta}, T={T}, "
          f"optimal makespan {gap_optimal_schedule_length(K, 3)} "
          f"(= {gap_optimal_schedule_length(K, 3) / (delta + T):.2f} x (Delta+T))")

    # 3) Theorem 1: flow-shop instances embed as rooted-tree coflow jobs
    p = np.array([[3, 1, 4], [2, 4, 1], [5, 2, 2]])
    fsp = fsp_to_coflow_job(p)
    sched = dma_srt(fsp.jobs[0], fsp.m, rng=np.random.default_rng(0))
    verify_schedule(fsp, sched)
    print(f"FSP reduction: {fsp.jobs[0].mu} coflows, DMA-SRT makespan "
          f"{sched.makespan:.0f}")

    # 4) the collective planner: multi-tenant pod fabric (heterogeneous
    #    port usage — the regime where delay-and-merge wins; see
    #    EXPERIMENTS.md §Planner for the single-step regime analysis)
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.planner_ab import multi_tenant_instance
    from repro.dist.planner import plan
    res = plan(multi_tenant_instance(seed=2))
    print(f"planner (multi-tenant): order {res.order}, makespan "
          f"{res.planner_makespan:.0f} vs naive {res.naive_makespan:.0f} "
          f"({100 * res.makespan_gain:.1f}% shorter)")

    # 5) the unified engine's incremental online path (§VII-C.2 protocol):
    #    same completions as from-scratch rescheduling, with the bytes-keyed
    #    BNA cache hitting across arrivals
    from repro.core import (clear_caches, paper_workload, plan_online,
                            poisson_releases, theta0)
    base = paper_workload(m=12, mu_bar=3, seed=0, scale=0.05)
    online = poisson_releases(base, theta=3 * theta0(base), seed=0)
    clear_caches()
    r = plan_online(online, "gdm", seed=0)
    print(f"online (engine, incremental): twct {r.twct():.0f}, "
          f"{r.reschedules} reschedules, "
          f"BNA cache hit rate {100 * r.stats['bna']['hit_rate']:.0f}%")


if __name__ == "__main__":
    main()
