"""The online protocol (§VII-C.2) as a closed batch loop.

At every arrival the plan in force is suspended, the remaining demands are
cut down to what was sent, and every job in the system is planned again
from scratch.  A partial window sends ``floor`` of its pro-rata units,
banked per edge so that fractions add up across windows.  Completion
times are absolute; TWCT measures each job from its release.
"""
from __future__ import annotations

import math

import numpy as np

from .model import Coflow, Instance, Job
from .plan import Pieces, gdm

_EPS = 1e-9
# the schedulers the reference plans, and whether their jobs are rooted trees
ROOTED = {"gdm": False, "gdm_rt": True}


def sub_instance(active, remaining, done, m):
    """The residual instance, every job released at 0; cid_maps[jid] maps
    its coflows back to their original ids."""
    jobs, cid_maps = [], {}
    for j in active:
        keep = [c.cid for c in j.coflows if (j.jid, c.cid) not in done]
        if not keep:
            continue
        idx = {orig: k for k, orig in enumerate(keep)}
        coflows = [Coflow(j.jid, idx[o], remaining[(j.jid, o)]) for o in keep]
        edges = [(idx[a], idx[b]) for a, b in j.edges
                 if a in idx and b in idx]
        jobs.append(Job(j.jid, coflows, edges, weight=j.weight, release=0))
        cid_maps[j.jid] = keep
    return Instance(m, jobs), cid_maps


def execute(entries, horizon, t0_abs, cid_maps, remaining, done) -> None:
    acc: dict = {}
    banked: dict = {}
    for e in sorted(entries, key=lambda e: e.t1):
        if e.units.size == 0:
            if e.t1 <= horizon + _EPS:
                done.setdefault((e.jid, cid_maps[e.jid][e.cid]),
                                t0_abs + e.t1)
            continue
        if e.t0 >= horizon:
            continue
        if e.t1 <= horizon + _EPS:
            amount, end = e.units, e.t1
        else:
            amount = np.floor(e.units * ((horizon - e.t0) / (e.t1 - e.t0)))
            end = horizon
        key = (e.jid, cid_maps[e.jid][e.cid])
        rem = remaining[key]
        a = acc.setdefault(key, np.zeros_like(rem, dtype=np.float64))
        t = banked.setdefault(key, np.zeros_like(rem))
        a[e.srcs, e.dsts] += amount
        cur = a[e.srcs, e.dsts]
        if cur.size and float(cur.max()) >= 2.0**53:
            raise ValueError("edge units left the float64 integer-exact range")
        avail = np.floor(cur + 1e-6).astype(np.int64) - t[e.srcs, e.dsts]
        take = np.minimum(np.maximum(avail, 0), rem[e.srcs, e.dsts])
        t[e.srcs, e.dsts] += take
        rem[e.srcs, e.dsts] -= take
        if rem.sum() == 0 and key not in done:
            done[key] = t0_abs + end


def simulate(specs, m: int, rooted: bool) -> dict[int, float]:
    """Every job's absolute completion time.  ``specs`` are the jobs fed,
    as ``bench.lib.workload.JobSpec``."""
    jobs = sorted(
        (Job(s.jid, [Coflow(s.jid, k, np.asarray(d, dtype=np.int64))
                     for k, d in enumerate(s.demands)],
             list(s.edges), weight=s.weight, release=s.release)
         for s in specs),
        key=lambda j: (j.release, j.jid))
    pieces = Pieces()
    remaining = {(j.jid, c.cid): c.demand.copy()
                 for j in jobs for c in j.coflows}
    done: dict = {}
    for j in jobs:
        for c in j.coflows:
            if remaining[(j.jid, c.cid)].sum() == 0:
                done[(j.jid, c.cid)] = float(j.release)
    arrivals = [float(j.release) for j in jobs]
    i = 0
    t = arrivals[0] if arrivals else 0.0
    active: list[Job] = []
    while i < len(jobs) or any(remaining[(j.jid, c.cid)].sum() > 0
                               for j in active for c in j.coflows):
        while i < len(jobs) and arrivals[i] <= t + _EPS:
            active.append(jobs[i])
            i += 1
        sub, cid_maps = sub_instance(active, remaining, done, m)
        if not sub.jobs:
            if i < len(jobs):
                t = arrivals[i]
                continue
            break
        entries = gdm(sub, rooted, pieces)
        t_next = arrivals[i] if i < len(jobs) else math.inf
        execute(entries, t_next - t, t, cid_maps, remaining, done)
        t = t_next if i < len(jobs) else t
    return {j.jid: max((done[(j.jid, c.cid)] for c in j.coflows),
                       default=float(j.release)) for j in jobs}
