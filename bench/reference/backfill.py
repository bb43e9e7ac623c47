"""G-DM with backfilling (§VII), in numpy: the plan, then its packet-level
re-execution with leftover capacity handed to ready flows.

1. Plan G-DM with spread delays group by group, exactly as ``plan.gdm``,
   but merge each group with ``decompose=True``, which keeps its
   per-coflow edge intervals in expanded time: rate-1 stretches of an
   edge served for one coflow, cut from the BNA pieces of every merged
   interval.  A group starts when the previous one's ledger ends, as in
   ``plan.gdm``.
2. Cut time at every start and end of those intervals.  In each
   elementary interval [a, b):

   a. planned service first: every interval active over [a, b) sends
      min(b - a, what its coflow still owes on that edge); the active
      intervals form a matching, so this never exceeds a port;
   b. then the ports' leftover capacity goes, greedily, to coflows that
      still owe units, whose job is released by a, and whose parents all
      finished by a: coflows in order of their planned completion (the
      end of their last planned interval), ties by (job, coflow); within
      a coflow, edges in row-major order, each taking what both its ports
      have left.

   A coflow completes at the end of the interval in which it has sent
   everything; a coflow with no demand completes at max(release, its
   parents' completions), and counts as finished for its children once
   its parents have.

Where this follows the program's executor (``core/backfill.py``,
``exec="packet"``) rather than the paper's prose, which says only that
under-used port capacity goes to ready flows of other jobs: the time grid
(the planned intervals' ends), readiness judged at interval entry, the
earliest-planned-completion order, the row-major greedy within a coflow,
and the completion rule for empty coflows.  The units a coflow sends in
an interval are one entry for its planned service and one for its fill,
as the program's transcript has them, so a window cut in the middle
floors them alike.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from .model import Instance, aggregate_size, parents_of, topological_order
from .plan import BETA, Pieces, group_jobs, isolated_job_unit, job_order, \
    spread
from .timeline import Edges, Merged, merge_and_fix


@dataclass
class Sent:
    """Coflow (jid, cid) sends units[k] on (srcs[k], dsts[k]) over [t0, t1)."""

    jid: int
    cid: int
    t0: float
    t1: float
    srcs: np.ndarray
    dsts: np.ndarray
    units: np.ndarray


def ledger_end(part: Merged) -> float:
    """Where the next group starts: the end of the last window that moves
    units (of any window, when none does)."""
    busy = [e.t1 for e in part.ledger if e.units.size and e.units.sum() > 0]
    return float(max(busy) if busy else
                 max((e.t1 for e in part.ledger), default=0.0))


def gdm_parts(inst: Instance, pieces: Pieces) -> list[Merged]:
    """G-DM's groups, each merged with its per-coflow edge intervals."""
    by_id = {j.jid: j for j in inst.jobs}
    parts = []
    t_cur = 0
    for g in group_jobs(inst, job_order(inst)):
        jobs = [by_id[jid] for jid in g]
        start = max(t_cur, max((j.release for j in jobs), default=0))
        units = [isolated_job_unit(j, pieces) for j in jobs]
        delta = aggregate_size(c.demand for j in jobs for c in j.coflows)
        delays = dict(zip([j.jid for j in jobs],
                          spread(len(jobs), int(delta // BETA))))
        part = merge_and_fix(units, inst.m, delays, origin=int(start),
                             decompose=True, pieces=pieces)
        parts.append(part)
        t_cur = int(math.ceil(ledger_end(part)))
    return parts


def execute(inst: Instance, parts: list[Merged]) -> list[Sent]:
    """Re-execute the parts' edge intervals with backfilling."""
    m = inst.m
    by_id = {j.jid: j for j in inst.jobs}
    parents = {j.jid: parents_of(j.mu, j.edges) for j in inst.jobs}
    owed, where, coflow_of = {}, {}, {}
    for p in parts:
        for e in p.ledger:
            owed[(e.jid, e.cid)] = e.units.astype(np.int64)
            where[(e.jid, e.cid)] = (e.srcs, e.dsts)
            coflow_of[(e.jid, e.cid)] = {
                (int(s), int(r)): k for k, (s, r) in enumerate(zip(e.srcs,
                                                                  e.dsts))}
    segs = Edges.concat([p.coflow_edges for p in parts])
    planned_end: dict = {}
    for i in range(segs.size):
        key = (int(segs.jid[i]), int(segs.cid[i]))
        planned_end[key] = max(planned_end.get(key, 0), int(segs.t1[i]))
    order = sorted((key for key in owed if owed[key].sum() > 0),
                   key=lambda key: (planned_end[key], key))
    zero = {key for key in owed if owed[key].sum() == 0}
    done: dict = {}

    def finished(key, a) -> bool:
        """Sent everything in an earlier interval, or has no demand and
        its parents have finished."""
        if key not in zero:
            return done.get(key, math.inf) <= a
        return all(finished((key[0], q), a) for q in parents[key[0]][key[1]])

    out: list[Sent] = []
    if segs.size:
        events = np.unique(np.concatenate([segs.t0, segs.t1]))
        first = np.searchsorted(events, segs.t0)
        last = np.searchsorted(events, segs.t1)
    else:
        events = np.zeros(0, dtype=np.int64)
    starts: dict = {}
    ends: dict = {}
    for i in range(segs.size):
        starts.setdefault(int(first[i]), []).append(i)
        ends.setdefault(int(last[i]), []).append(i)
    active: set = set()
    for k in range(events.size - 1):
        active.difference_update(ends.get(k, ()))
        active.update(starts.get(k, ()))
        a, b = int(events[k]), int(events[k + 1])
        slack_s = np.full(m, b - a, dtype=np.int64)
        slack_r = np.full(m, b - a, dtype=np.int64)
        finished_now = []
        # a. planned service
        planned: dict = {}
        for i in sorted(active):
            key = (int(segs.jid[i]), int(segs.cid[i]))
            e = coflow_of[key][(int(segs.s[i]), int(segs.r[i]))]
            x = min(b - a, int(owed[key][e]))
            if x > 0:
                slack_s[segs.s[i]] -= x
                slack_r[segs.r[i]] -= x
                planned.setdefault(key, []).append((e, x))
        assert slack_s.min() >= 0 and slack_r.min() >= 0
        for key, got in planned.items():
            idx = np.array([e for e, _ in got], dtype=np.int64)
            amt = np.array([x for _, x in got], dtype=np.int64)
            owed[key][idx] -= amt
            s, r = where[key]
            out.append(Sent(key[0], key[1], a, b, s[idx], r[idx], amt))
            if owed[key].sum() == 0:
                finished_now.append(key)
        # b. leftover capacity to ready flows
        for key in order:
            if owed[key].sum() == 0:
                continue
            jid, cid = key
            if by_id[jid].release > a:
                continue
            if not all(finished((jid, q), a) for q in parents[jid][cid]):
                continue
            s, r = where[key]
            take = np.zeros_like(owed[key])
            for e in np.flatnonzero(owed[key] > 0):
                x = min(int(owed[key][e]), int(slack_s[s[e]]),
                        int(slack_r[r[e]]))
                if x > 0:
                    take[e] = x
                    slack_s[s[e]] -= x
                    slack_r[r[e]] -= x
            if take.sum() == 0:
                continue
            owed[key] -= take
            out.append(Sent(jid, cid, a, b, s, r, take))
            if owed[key].sum() == 0:
                finished_now.append(key)
        for key in finished_now:
            done[key] = b
    assert all(v.sum() == 0 for v in owed.values()), "units left unsent"
    for j in inst.jobs:
        for cid in topological_order(j.mu, j.edges):
            key = (j.jid, cid)
            if key in zero:
                t = max([done[(j.jid, q)] for q in parents[j.jid][cid]]
                        + [j.release])
                done[key] = t
                z = np.zeros(0, dtype=np.int64)
                out.append(Sent(j.jid, cid, t, t, z, z, z))
    return out


def gdm_bf(inst: Instance, pieces: Pieces) -> list[Sent]:
    """G-DM with packet backfilling: what each coflow sends, and when."""
    return execute(inst, gdm_parts(inst, pieces))
