"""Closed-loop arrivals into one live ``SchedulerSession``.

Each planning request is one arrival.  The harness advances the session
to the arrival's release (span ``advance``: the fabric runs the plan in
force), and the request is then due; ``StreamDriver.feed`` submits it and
replans (span ``feed``: ``submit`` + ``frontier``), and the request is
answered when ``feed`` returns, its plan on the host.  The next arrival
follows at once, so the latency is the planner's service time and the
rate is its capacity.  The driver runs in pure mode, with no admission
policy, so its completions must equal the batch reference's.

Set-up feeds the stream's first ``warmup_arrivals`` arrivals untimed: they
compile and load the programs this backlog needs, and the window starts
from the backlog they leave.  The request in flight when the window
closes is finished and fed to the reference, but left out of the
metrics.  Afterwards the session is drained, its state freed, and every
job's completion is compared with the plain reference
(``bench/reference``) run on the host over every job fed.
"""
from __future__ import annotations

import gc
import time

from bench.lib.workload import JobStream
from bench.reference.online import ROOTED, simulate


def _backlog(session) -> dict:
    snap = session.snapshot()
    active = set(snap.active)
    return {"jobs": len(active),
            "coflows": sum(1 for (jid, _), rem in snap.remaining.items()
                           if jid in active and rem.any())}


def compare(fed, completions: dict, m: int, scheduler: str):
    """(checks, failed): every fed job's completion against the reference."""
    ref = simulate(fed, m, ROOTED[scheduler])
    missing = [s.jid for s in fed if s.jid not in completions]
    gaps = [abs(completions[s.jid] - ref[s.jid]) for s in fed
            if s.jid in completions]
    twct = sum(s.weight * (completions.get(s.jid, ref[s.jid]) - s.release)
               for s in fed)
    twct_ref = sum(s.weight * (ref[s.jid] - s.release) for s in fed)
    failed = len(missing) + sum(1 for g in gaps if g > 0)
    checks = [("jobs_unanswered", float(len(missing)), 0.0),
              ("completion_gap_max", float(max(gaps, default=0.0)), 0.0),
              ("twct_rel_gap", abs(twct - twct_ref) / max(twct_ref, 1.0),
               0.0)]
    return checks, failed


def run(ctx):
    import jax

    import repro.core as core

    cfg, traffic, rec = ctx.config, ctx.traffic, ctx.run
    core.configure_compile_cache()
    stream = JobStream(cfg, traffic, ctx.seed)
    m = stream.m
    drv = core.StreamDriver(m, cfg["scheduler"], **cfg["options"])
    session = drv.session
    fed = []

    def serve(k: int) -> tuple[float, float]:
        with ctx.span("bookkeeping"):
            spec = stream.job(k)
            job = core.Job(spec.jid, [core.Coflow(spec.jid, i, d)
                                      for i, d in enumerate(spec.demands)],
                           list(spec.edges), weight=spec.weight,
                           release=spec.release)
            fed.append(spec)
        with ctx.span("advance"):
            session.advance(until=float(job.release))
        due = time.perf_counter()
        with ctx.span("feed"):
            status = drv.feed(job)
        done = time.perf_counter()
        if status != "submitted":   # only with an admission policy
            rec.notes[status] = rec.notes.get(status, 0) + 1
        return due, done

    warm = int(traffic["warmup_arrivals"])
    for k in range(warm):
        serve(k)
    rec.notes["backlog_at_window_start"] = _backlog(session)
    stats0 = (session.stats.repairs, session.stats.reschedules)
    stats1 = stats0
    ctx.open_window()
    k = warm
    while ctx.window_open():
        due, done = serve(k)
        k += 1
        rec.attempted += 1
        if not ctx.answered(due, done):
            break
        stats1 = (session.stats.repairs, session.stats.reschedules)
    ctx.close_window()
    rec.counters["repairs"] = (stats0[0], stats1[0])
    rec.counters["reschedules"] = (stats0[1], stats1[1])
    rec.memory_peak_bytes = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    rec.notes["backlog_at_window_end"] = _backlog(session)
    rec.notes["requests_in_window"] = len(rec.requests)
    rec.notes["request_ms"] = [round((b - a) * 1e3, 1) for a, b in rec.requests]
    rec.notes["jobs_fed"] = len(fed)

    # No sane plan runs longer than every job one after another, each
    # delayed by the whole demand; a job not done by then never completes.
    sizes = sum(int(max(d.sum(axis=0).max(), d.sum(axis=1).max()))
                for s in fed for d in s.demands)
    session.advance(until=float(fed[-1].release + (len(fed) + 2) * sizes))
    completions = dict(session.frontier().finished)
    del drv, session
    core.clear_caches()
    gc.collect()
    t0 = time.perf_counter()
    rec.checks, rec.failed = compare(fed, completions, m, cfg["scheduler"])
    rec.notes["reference_s"] = round(time.perf_counter() - t0, 3)
    return rec

