"""Offline full replans of one backlog, back to back.

Set-up feeds the stream's first ``backlog_jobs`` arrivals through
``StreamDriver.feed`` at their releases, as the arrivals driver's warm-up
does; the residual left after the last feed (every unfinished coflow's
remaining demand at that release) is what every request plans.  A
request clears the result caches (``core.clear_caches()``: the BNA,
order, group-block and edge caches; compiled programs stay), then plans
the residual from scratch through the session's own engine scheduler and
entry, ``plan_full``, the call a full replan of the session makes; it is
answered when the plan and its transcript (for a backfilled scheduler,
the backfilled one) are on the host.  The traffic's ``warmup_plans``
untimed plans come first; requests then follow at once.

The plain reference (``bench/reference``: G-DM's ledger, or G-DM with
packet backfilling) builds its own residual after the window: it replays
the same arrivals, planning every job in the system from scratch at each
release and sending that plan until the next one (``online.execute``),
then plans what is left once.  Checks, each with limit 0:

- ``residual_mismatch``: coflows whose remaining demand or completion
  time in the session's residual differs from the reference's, so the
  session's execution of the plans it made while being fed (for
  ``gdm_bf``, the backfilled transcripts) is checked too;
- ``jobs_unanswered``: fed jobs neither finished by the residual's time
  nor completed by a plan, most over the answered plans;
- ``completion_gap_max``: the largest gap between a job's completion in
  any answered plan and in the reference's plan of its own residual
  (fabric time units, the residual's clock);
- ``twct_rel_gap``: the largest relative gap of the plans' total weighted
  completion time.

Only ``gamma="residual"`` plans, the paper's per-plan grouping scale,
which the configurations state.  A backfilled scheduler is run only on a
program that marks its backfill layer (``plan.backfill`` among
``repro.core.spans.NAMES``), whose span the cell's per-layer metrics read:
a program without it decomposes every merged interval with scalar BNA on
the host, minutes a plan at the cell's size, past a run's time limit, so
such a run exits at once with status 1 and no result.
"""
from __future__ import annotations

import gc
import resource
import time

import numpy as np

from bench.lib.workload import JobStream
from bench.reference import backfill as ref_backfill
from bench.reference.model import Coflow, Job
from bench.reference.online import execute, sub_instance
from bench.reference.plan import Pieces, gdm


def reference_plan(sub, scheduler: str, pieces: Pieces) -> list:
    """The plain reference's plan of ``sub``: entries with ``jid``,
    ``cid``, ``t0``, ``t1``, ``srcs``, ``dsts`` and ``units``."""
    if scheduler == "gdm":
        return gdm(sub, False, pieces)
    if scheduler == "gdm_bf":
        return ref_backfill.gdm_bf(sub, pieces)
    raise ValueError(f"no reference plan for {scheduler!r}")


def reference_residual(specs, m: int, scheduler: str, pieces: Pieces):
    """(jobs, remaining, done) at the last spec's release, by the plain
    reference alone: at every earlier release it plans every job in the
    system from scratch and sends that plan until the next release, as
    ``online.simulate`` does; ``remaining[(jid, cid)]`` is the demand left,
    ``done[(jid, cid)]`` the absolute completion time."""
    jobs = sorted((Job(s.jid, [Coflow(s.jid, k, np.asarray(d, np.int64))
                               for k, d in enumerate(s.demands)],
                       list(s.edges), weight=s.weight, release=s.release)
                   for s in specs), key=lambda j: (j.release, j.jid))
    remaining = {(j.jid, c.cid): c.demand.copy()
                 for j in jobs for c in j.coflows}
    done = {(j.jid, c.cid): float(j.release) for j in jobs
            for c in j.coflows if not c.demand.any()}
    active: list = []
    i = 0
    while i < len(jobs):
        t = float(jobs[i].release)
        while i < len(jobs) and jobs[i].release <= t:
            active.append(jobs[i])
            i += 1
        if i == len(jobs):
            break
        sub, cid_maps = sub_instance(active, remaining, done, m)
        if sub.jobs:
            execute(reference_plan(sub, scheduler, pieces),
                    float(jobs[i].release) - t, t, cid_maps, remaining, done)
    return jobs, remaining, done


def residual_mismatch(snap, remaining: dict, done: dict) -> int:
    """Coflows whose remaining demand or completion time differs between
    the session's residual ``snap`` and the reference's."""
    apart = 0
    for key in set(remaining) | set(snap.remaining):
        a, b = snap.remaining.get(key), remaining.get(key)
        if a is None or b is None:
            apart += bool((a if b is None else b).any())
        else:
            apart += not np.array_equal(a, b)
    return apart + sum(snap.done.get(key) != done.get(key)
                       for key in set(done) | set(snap.done))


def reference_completions(jobs, remaining: dict, done: dict, m: int,
                          scheduler: str, pieces: Pieces) -> dict:
    """Each residual job's completion in the plain reference's plan of
    the residual (``reference_residual``'s)."""
    sub, _ = sub_instance(jobs, remaining, done, m)
    out: dict = {}
    for e in reference_plan(sub, scheduler, pieces):
        out[e.jid] = max(out.get(e.jid, 0.0), float(e.t1))
    return out


def compare(specs, finished: set, plans: list, ref: dict):
    """(checks, failed) of every answered plan against the reference."""
    weight = {s.jid: s.weight for s in specs}
    twct_ref = sum(weight[j] * t for j, t in ref.items())
    unanswered, gap, twct_gap, failed = 0, 0.0, 0.0, 0
    for comp in plans:
        missing = [s.jid for s in specs
                   if s.jid not in finished and s.jid not in comp]
        gaps = [abs(t - ref[j]) if j in ref else abs(t)
                for j, t in comp.items()]
        twct = sum(weight[j] * comp.get(j, t) for j, t in ref.items())
        unanswered = max(unanswered, len(missing))
        gap = max(gap, max(gaps, default=0.0))
        twct_gap = max(twct_gap, abs(twct - twct_ref) / max(twct_ref, 1.0))
        failed += bool(missing) or any(g > 0 for g in gaps)
    checks = [("jobs_unanswered", float(unanswered), 0.0),
              ("completion_gap_max", float(gap), 0.0),
              ("twct_rel_gap", float(twct_gap), 0.0)]
    return checks, failed


def run(ctx):
    import jax

    import repro.core as core
    from repro.core import spans
    from repro.core.session import sub_instance as residual_of

    cfg, traffic, rec = ctx.config, ctx.traffic, ctx.run
    opts = dict(cfg["options"])
    if opts.get("gamma", "residual") != "residual":
        raise ValueError("offline replans plan with gamma='residual' only")
    if cfg["scheduler"].endswith("_bf") and \
            "plan.backfill" not in spans.NAMES:
        raise SystemExit("offline_replan: this program has no plan.backfill "
                         "span, so it cannot run a backfilled replan cell")
    core.configure_compile_cache()
    stream = JobStream(cfg, traffic, ctx.seed)
    m = stream.m
    drv = core.StreamDriver(m, cfg["scheduler"], **opts)
    session = drv.session
    fed, jobs = [], {}
    with ctx.span("backlog"):
        for k in range(int(traffic["backlog_jobs"])):
            spec = stream.job(k)
            job = core.Job(spec.jid, [core.Coflow(spec.jid, i, d)
                                      for i, d in enumerate(spec.demands)],
                           list(spec.edges), weight=spec.weight,
                           release=spec.release)
            fed.append(spec)
            jobs[spec.jid] = job
            session.advance(until=float(job.release))
            drv.feed(job)
    snap = session.snapshot()
    sub, _ = residual_of([jobs[jid] for jid in snap.active], snap.remaining,
                         snap.done, m)
    rec.notes["backlog"] = {"jobs": len(sub.jobs),
                            "coflows": sum(j.mu for j in sub.jobs),
                            "finished": len(fed) - len(sub.jobs)}
    # the engine scheduler the session's replans call
    scheduler = session._scheduler

    def replan() -> dict:
        core.clear_caches()
        p = scheduler.plan_full(sub)
        p.transcript()
        return p.job_completions()

    for _ in range(int(traffic["warmup_plans"])):
        replan()
    plans = []
    ctx.open_window()
    while ctx.window_open():
        due = time.perf_counter()
        with ctx.span("replan"):
            comp = replan()
        done = time.perf_counter()
        rec.attempted += 1
        if not ctx.answered(due, done):
            break
        plans.append(comp)
    ctx.close_window()
    rec.memory_peak_bytes = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    rec.notes["requests_in_window"] = len(rec.requests)
    rec.notes["request_ms"] = [round((b - a) * 1e3, 1) for a, b in rec.requests]
    rec.notes["jobs_fed"] = len(fed)
    del drv, session, scheduler
    core.clear_caches()
    gc.collect()
    t0 = time.perf_counter()
    pieces = Pieces()
    ref_jobs, remaining, done = reference_residual(fed, m, cfg["scheduler"],
                                                   pieces)
    finished = {j.jid for j in ref_jobs
                if all((j.jid, c.cid) in done for c in j.coflows)}
    ref = reference_completions(ref_jobs, remaining, done, m,
                                cfg["scheduler"], pieces)
    checks, rec.failed = compare(fed, finished, plans, ref)
    rec.checks = [("residual_mismatch",
                   float(residual_mismatch(snap, remaining, done)), 0.0)]
    rec.checks += checks
    rec.notes["reference_s"] = round(time.perf_counter() - t0, 3)
    rec.notes["host_rss_peak_mb"] = round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1)
    return rec
