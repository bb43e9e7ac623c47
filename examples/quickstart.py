"""Quickstart: build a workload from the scenario registry, schedule it
with the paper's G-DM algorithm, compare against the prior-art O(m)Alg
baseline, then drive the same engine event-by-event through the stateful
SchedulerSession (the §VII-C.2 online protocol as an API).

  PYTHONPATH=src python examples/quickstart.py
"""
from repro import scenarios
from repro.core import (SchedulerSession, available_schedulers,
                        configure_compile_cache, plan, verify_schedule,
                        workload_stats)


def main() -> None:
    configure_compile_cache()
    # a Facebook-trace-calibrated workload: ~5 coflows per job, rooted-tree
    # dependencies (Hive/MapReduce-style stages). Gains grow with port count
    # and job count (paper Fig 6a) — benchmarks/run.py sweeps the full range,
    # and `--scenario` runs the whole zoo (incast, shuffle-heavy, ...).
    built = scenarios.build("fb_like_rt", m=24, seed=3, scale=0.08)
    inst = built.instance
    print("registered scenarios:", ", ".join(scenarios.names()))
    print("scenario:", built.meta.name, "| DAG family:", built.meta.dag_family,
          "| arrivals:", built.meta.arrival)
    print("workload:", workload_stats(inst))
    print("registered schedulers:", ", ".join(available_schedulers()))

    sched = plan(inst, "gdm_rt", beta=2.0, seed=0, decompose=True)
    verify_schedule(inst, sched.schedule)  # capacity + precedence + conservation
    base = plan(inst, "om_alg")

    print(f"G-DM-RT   TWCT = {sched.twct():12.0f}   makespan = {sched.makespan:10.0f}")
    print(f"O(m)Alg   TWCT = {base.twct():12.0f}   makespan = {base.makespan:10.0f}")
    print(f"improvement: {100 * (1 - sched.twct() / base.twct()):.1f}%  "
          "(tiny demo instance — gains grow with m and job count; "
          "benchmarks/run.py reproduces the paper's Fig 5/6 sweeps)")

    bf_g, bf_o = sched.backfilled(), base.backfilled()
    print(f"with backfilling: G-DM-RT-BF {bf_g.twct():.0f} "
          f"vs O(m)Alg-BF {bf_o.twct():.0f}")

    # the event-driven session: submit arrivals, advance wall-clock, read
    # the live frontier — simulate_online/plan_online are thin drivers over
    # exactly this loop (see README "The session API")
    online = scenarios.build("online_poisson", m=12, seed=0, scale=0.04)
    session = SchedulerSession(online.instance.m, "gdm", seed=0)
    for job in sorted(online.instance.jobs, key=lambda j: j.release):
        session.advance(until=job.release)
        session.submit(job)
        f = session.frontier()
        print(f"t={session.now:6.0f}  submit job {job.jid:2d}  "
              f"active={len(f.completions):2d}  busy_until={f.busy_until:.0f}")
    session.advance()
    res = session.result()
    s = res.stats["session"]
    print(f"session drained: twct={res.twct():.0f} "
          f"reschedules={res.reschedules} "
          f"(full={s['full_replans']}, repaired={s['repairs']})")


if __name__ == "__main__":
    main()
