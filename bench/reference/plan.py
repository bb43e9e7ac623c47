"""G-DM and G-DM-RT with evenly spread delays (Algorithms 2-5, §VI).

1. Order the jobs by the primal-dual Algorithm 5.
2. Group them by which interval (gamma 2^(b-1), gamma 2^b] holds
   T_j + rho_j + D_j, D_j the effective size of the first j jobs' sum.
3. Plan the groups one after another, each from when the previous one
   ends: DMA for general DAGs, DMA-RT (a nested DMA-SRT per job) for
   rooted trees.  Delays are spread evenly over [0, Delta / beta].

``gdm`` returns the plan as ledger entries on the residual's clock.
"""
from __future__ import annotations

import math
from fractions import Fraction

import numpy as np

from .bna import bna
from .model import (Instance, Job, aggregate_size, children_of, parents_of,
                    topological_order)
from .timeline import Edges, Merged, Unit, coflow_unit, merge_and_fix

BETA = 2.0


class Pieces:
    """BNA pieces per demand, memoized on the demand's bytes for one run."""

    def __init__(self):
        self._memo: dict[bytes, list] = {}

    def __call__(self, demand: np.ndarray) -> list:
        key = demand.tobytes()
        if key not in self._memo:
            self._memo[key] = bna(demand)
        return self._memo[key]


def load_rows(jobs: list[Job], m: int) -> np.ndarray:
    """(n, 2m): each job's summed row loads, then column loads."""
    d = np.zeros((len(jobs), 2 * m), dtype=np.float64)
    for k, j in enumerate(jobs):
        agg = j.aggregate_demand(m)
        d[k] = np.concatenate([agg.sum(axis=1), agg.sum(axis=0)])
    return d


def job_order(inst: Instance) -> list[int]:
    """Algorithm 5: build the permutation back to front."""
    jobs = inst.jobs
    n = len(jobs)
    if n == 0:
        return []
    d = load_rows(jobs, inst.m)
    key = np.array([j.T + j.release for j in jobs], dtype=np.float64)
    wres = np.array([j.weight for j in jobs], dtype=np.float64)
    alive = np.ones(n, dtype=bool)
    loads = d.sum(axis=0)
    sigma = [0] * n
    for k in range(n - 1, -1, -1):
        phi = int(np.argmax(loads))
        cand = np.flatnonzero(alive)
        j = int(cand[np.argmax(key[cand])])
        if key[j] > loads[phi]:
            pick = j
        else:
            loads_phi = d[cand, phi]
            with np.errstate(divide="ignore", invalid="ignore"):
                ratio = np.where(loads_phi > 0, wres[cand] / loads_phi,
                                 np.inf)
            pick = int(cand[np.argmin(ratio)])
            lam = float(wres[pick] / d[pick, phi]) if d[pick, phi] > 0 else 0.0
            wres[cand] = wres[cand] - lam * d[cand, phi]
        sigma[k] = pick
        alive[pick] = False
        loads -= d[pick]
    return [jobs[i].jid for i in sigma]


def group_jobs(inst: Instance, order: list[int]) -> list[list[int]]:
    by_id = {j.jid: j for j in inst.jobs}
    g = Fraction(inst.gamma())
    cum = np.cumsum(load_rows([by_id[jid] for jid in order], inst.m), axis=0)
    prefix = cum.max(axis=1).astype(np.int64)
    groups: dict[int, list[int]] = {}
    for i, jid in enumerate(order):
        key = by_id[jid].T + by_id[jid].release + int(prefix[i])
        b = 0 if key <= 0 else \
            ((g.denominator * key - 1) // g.numerator).bit_length()
        groups.setdefault(b, []).append(jid)
    return [groups[b] for b in sorted(groups)]


def spread(n: int, hi: int) -> list[int]:
    """n delays spread evenly over [0, hi]."""
    return [(i * hi) // max(n - 1, 1) if n > 1 else 0 for i in range(n)]


def isolated_job_unit(job: Job, pieces: Pieces) -> Unit:
    """DMA Step 1: the job's coflows back to back in topological order."""
    t = 0
    parts = []
    for cid in topological_order(job.mu, job.edges):
        c = job.coflows[cid]
        parts.append(coflow_unit(job.jid, cid, c.demand, t, pieces(c.demand)))
        t += c.D
    return Unit(job.jid,
                Edges.concat([p.edges for p in parts]).with_owner(job.jid),
                [e for p in parts for e in p.ledger])


def dma(jobs: list[Job], m: int, origin: int, pieces: Pieces) -> Merged:
    units = [isolated_job_unit(j, pieces) for j in jobs]
    delta = aggregate_size(c.demand for j in jobs for c in j.coflows)
    delays = dict(zip([j.jid for j in jobs],
                      spread(len(jobs), int(delta // BETA))))
    return merge_and_fix(units, m, delays, origin=origin)


def srt_start_times(job: Job) -> list[int]:
    """DMA-SRT Steps 1-2: one spread delay per source-to-sink path; a
    coflow starts at the earliest of its paths' starts that clears every
    parent."""
    n = job.mu
    if not job.is_rooted_forest():
        raise ValueError(f"job {job.jid} is not a rooted tree or forest")
    sizes = [c.D for c in job.coflows]
    ch = children_of(n, job.edges)
    indeg = [0] * n
    for _, b in job.edges:
        indeg[b] += 1
    paths: list[list[int]] = []
    stack = [[s] for s in reversed([i for i in range(n) if indeg[i] == 0])]
    while stack:
        p = stack.pop()
        if not ch[p[-1]]:
            paths.append(p)
            continue
        for v in ch[p[-1]]:
            stack.append(p + [v])
    delta_j = aggregate_size(c.demand for c in job.coflows)
    cand: list[list[int]] = [[] for _ in range(n)]
    for p, dp in zip(paths, spread(len(paths), int(delta_j // BETA))):
        acc = dp
        for c in p:
            cand[c].append(acc)
            acc += sizes[c]
    par = parents_of(n, job.edges)
    t = [0] * n
    for layer in job.layers():
        for c in layer:
            bound = max((t[q] + sizes[q] for q in par[c]), default=0)
            feas = [x for x in cand[c] if x >= bound]
            t[c] = min(feas) if feas else bound
    return t


def dma_rt(jobs: list[Job], m: int, origin: int, pieces: Pieces) -> Merged:
    """DMA-RT: a decomposed DMA-SRT per job, then delay, merge and fix."""
    units = []
    for j in jobs:
        starts = srt_start_times(j)
        parts = []
        for cid, c in enumerate(j.coflows):
            u = coflow_unit(j.jid, cid, c.demand, starts[cid],
                            pieces(c.demand))
            u.uid = cid
            parts.append(u)
        units.append(merge_and_fix(parts, m, decompose=True, pieces=pieces)
                     .to_unit(j.jid))
    delta = aggregate_size(c.demand for j in jobs for c in j.coflows)
    delays = dict(zip([j.jid for j in jobs],
                      spread(len(jobs), int(delta // BETA))))
    return merge_and_fix(units, m, delays, origin=origin)


def gdm(inst: Instance, rooted: bool, pieces: Pieces) -> list:
    """The plan's ledger entries, group after group."""
    by_id = {j.jid: j for j in inst.jobs}
    entries = []
    t_cur = 0
    for g in group_jobs(inst, job_order(inst)):
        jobs = [by_id[jid] for jid in g]
        start = max(t_cur, max((j.release for j in jobs), default=0))
        part = (dma_rt if rooted else dma)(jobs, inst.m, int(start), pieces)
        entries.extend(part.ledger)
        t_cur = int(math.ceil(part.makespan))
    return entries
