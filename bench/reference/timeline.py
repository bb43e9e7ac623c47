"""Delay, merge and fix (DMA Steps 3-4, Lemma 6), in numpy.

A schedule is a set of edge intervals: edge (s, r) sends at rate 1 over
[t0, t1), owned by a scheduling unit and attributed to its coflow (jid,
cid).  Merging cuts time at every event; within interval I the merged
demand is constant, and I is stretched by alpha_I, the most packets any
port must move in it.  A ledger maps each coflow's window [t0, t0 + D)
through the stretch; completions and execution read the ledger.  With
``decompose=True`` each stretched interval is also decomposed (BNA on
l_I times the merged counts) and each unit's last packet is found by a
FIFO walk, which is what nesting a DMA-SRT schedule into DMA-RT needs.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .bna import bna
from .model import effective_size


@dataclass
class Edges:
    t0: np.ndarray
    t1: np.ndarray
    s: np.ndarray
    r: np.ndarray
    owner: np.ndarray
    jid: np.ndarray
    cid: np.ndarray

    @staticmethod
    def of(t0, t1, s, r, owner, jid, cid) -> "Edges":
        return Edges(*(np.asarray(a, dtype=np.int64)
                       for a in (t0, t1, s, r, owner, jid, cid)))

    @staticmethod
    def concat(parts: list["Edges"]) -> "Edges":
        parts = [p for p in parts if p.t0.size]
        if not parts:
            return Edges.of(*([[]] * 7))
        return Edges(*(np.concatenate([getattr(p, f) for p in parts])
                       for f in ("t0", "t1", "s", "r", "owner", "jid",
                                 "cid")))

    def shifted(self, dt: int) -> "Edges":
        return Edges(self.t0 + dt, self.t1 + dt, self.s, self.r, self.owner,
                     self.jid, self.cid)

    def with_owner(self, uid: int) -> "Edges":
        return Edges(self.t0, self.t1, self.s, self.r,
                     np.full_like(self.t0, uid), self.jid, self.cid)

    @property
    def size(self) -> int:
        return int(self.t0.size)


@dataclass
class Entry:
    """Coflow (jid, cid) moves units[k] on (srcs[k], dsts[k]) uniformly over
    [t0, t1)."""

    jid: int
    cid: int
    t0: float
    t1: float
    srcs: np.ndarray
    dsts: np.ndarray
    units: np.ndarray


@dataclass
class Unit:
    uid: int
    edges: Edges
    ledger: list[Entry]


def coflow_unit(jid: int, cid: int, demand: np.ndarray, start: int,
                pieces: list) -> Unit:
    """One coflow scheduled by its BNA pieces from ``start``: the pieces
    run-length encoded into edge intervals, and one ledger entry."""
    t0s, t1s, ss, rs = [], [], [], []
    open_edges: dict[tuple[int, int], int] = {}
    t = start
    for dur, match in pieces:
        cur = {(int(s), int(match[s])) for s in np.flatnonzero(match >= 0)}
        for e in list(open_edges):
            if e not in cur:
                t0s.append(open_edges.pop(e))
                t1s.append(t)
                ss.append(e[0])
                rs.append(e[1])
        for e in cur:
            if e not in open_edges:
                open_edges[e] = t
        t += int(dur)
    for e, et0 in open_edges.items():
        t0s.append(et0)
        t1s.append(t)
        ss.append(e[0])
        rs.append(e[1])
    n = len(t0s)
    edges = Edges.of(t0s, t1s, ss, rs, [cid] * n, [jid] * n, [cid] * n)
    s_idx, r_idx = np.nonzero(demand)
    entry = Entry(jid, cid, start, start + effective_size(demand),
                  s_idx.astype(np.int64), r_idx.astype(np.int64),
                  demand[s_idx, r_idx].astype(np.float64))
    return Unit(jid, edges, [entry])


@dataclass
class Merged:
    """What merge_and_fix returns: the stretch and the mapped ledger."""

    events: np.ndarray
    exp: np.ndarray
    ledger: list[Entry]
    exact_completion: dict | None = None
    coflow_edges: Edges | None = None

    def expand_time(self, t: float) -> float:
        t = np.asarray(t, dtype=np.float64)
        if self.events.size == 0:
            return float(t)
        lo, hi = self.events[0], self.events[-1]
        out = np.interp(np.clip(t, lo, hi), self.events, self.exp)
        out = np.where(t < lo, self.exp[0] - (lo - t), out)
        out = np.where(t > hi, self.exp[-1] + (t - hi), out)
        return float(out)

    @property
    def makespan(self) -> float:
        if self.exact_completion:
            return float(max(self.exact_completion.values()))
        busy = [e.t1 for e in self.ledger if e.units.size and e.units.sum() > 0]
        if busy:
            return float(max(busy))
        return float(max((e.t1 for e in self.ledger), default=0.0))

    def to_unit(self, uid: int) -> Unit:
        """Re-package a decomposed schedule as one unit of an outer merge."""
        ledger = [Entry(e.jid, e.cid, int(round(e.t0)), int(round(e.t1)),
                        e.srcs, e.dsts, e.units) for e in self.ledger]
        return Unit(uid, self.coflow_edges.with_owner(uid), ledger)


def alphas(events: np.ndarray, edges: Edges, m: int) -> np.ndarray:
    """Per interval, the most edges any port has active."""
    K = events.size - 1
    if K <= 0:
        return np.zeros(0, dtype=np.int64)
    si = np.searchsorted(events, edges.t0)
    ei = np.searchsorted(events, edges.t1)
    ds = np.zeros((K + 1, m), dtype=np.int64)
    dr = np.zeros((K + 1, m), dtype=np.int64)
    np.add.at(ds, (si, edges.s), 1)
    np.add.at(dr, (si, edges.r), 1)
    np.add.at(ds, (ei, edges.s), -1)
    np.add.at(dr, (ei, edges.r), -1)
    cs = np.cumsum(ds, axis=0)[:K]
    cr = np.cumsum(dr, axis=0)[:K]
    return np.maximum(cs.max(axis=1), cr.max(axis=1))


def merge_and_fix(units: list[Unit], m: int, delays: dict | None = None,
                  origin: int = 0, decompose: bool = False) -> Merged:
    delays = delays or {}
    edges = Edges.concat([u.edges.shifted(int(delays.get(u.uid, 0)))
                          for u in units])
    events = (np.unique(np.concatenate([edges.t0, edges.t1]))
              if edges.size else np.zeros(0, dtype=np.int64))
    al = alphas(events, edges, m)
    K = al.size
    lens = events[1:] - events[:-1] if K else np.zeros(0, dtype=np.int64)
    exp = np.concatenate([[0], np.cumsum(lens * np.maximum(al, 1))]) \
        .astype(np.float64)
    exp += origin + (float(events[0]) if K else 0.0)
    out = Merged(events.astype(np.float64) if K else np.zeros(0),
                 exp if K else np.zeros(0), [])
    for u in units:
        dt = int(delays.get(u.uid, 0))
        for e in u.ledger:
            out.ledger.append(Entry(
                e.jid, e.cid, out.expand_time(e.t0 + dt),
                out.expand_time(e.t1 + dt), e.srcs, e.dsts, e.units))
    if decompose:
        out.exact_completion, out.coflow_edges = _decompose(
            events, edges, al, exp, m)
    return out


def _decompose(events, edges: Edges, al, exp, m):
    """Per stretched interval, BNA on l_I times the merged counts; each
    edge's units are served FIFO in activation order.  Returns each unit's
    last-packet time and the served stretches as edge intervals."""
    completion: dict[int, float] = {}
    segs: list[tuple] = []
    if edges.size == 0:
        return completion, Edges.of(*([[]] * 7))
    K = al.size
    si = np.searchsorted(events, edges.t0)
    ei = np.searchsorted(events, edges.t1)
    add_at: list[list[int]] = [[] for _ in range(K + 1)]
    rem_at: list[list[int]] = [[] for _ in range(K + 1)]
    for i in range(edges.size):
        add_at[si[i]].append(i)
        rem_at[ei[i]].append(i)
    active: dict[tuple[int, int], list] = {}
    seq = 0
    for k in range(K):
        for i in rem_at[k]:
            key = (int(edges.s[i]), int(edges.r[i]))
            k3 = (int(edges.owner[i]), int(edges.jid[i]), int(edges.cid[i]))
            lst = active[key]
            for j, ent in enumerate(lst):
                if ent[1] == k3:
                    if ent[2] == 1:
                        lst.pop(j)
                    else:
                        ent[2] -= 1
                    break
            if not lst:
                del active[key]
        for i in add_at[k]:
            key = (int(edges.s[i]), int(edges.r[i]))
            k3 = (int(edges.owner[i]), int(edges.jid[i]), int(edges.cid[i]))
            lst = active.setdefault(key, [])
            for ent in lst:
                if ent[1] == k3:
                    ent[2] += 1
                    break
            else:
                lst.append([seq, k3, 1])
                seq += 1
        if not active:
            continue
        ln = int(events[k + 1] - events[k])
        if ln == 0:
            continue
        t_exp = int(round(exp[k]))
        a = int(al[k])
        queues = {key: [[k3, mult * ln] for _, k3, mult in sorted(lst)]
                  for key, lst in active.items()}
        if a <= 1:
            end = float(t_exp + ln)
            for key, q in queues.items():
                cursor = t_exp
                for k3, amt in q:
                    if amt > 0:
                        segs.append((cursor, cursor + amt, key[0], key[1])
                                    + k3)
                    cursor += amt
                    completion[k3[0]] = max(completion.get(k3[0], 0.0), end)
            continue
        srcs = np.array([s for s, _ in active], dtype=np.int64)
        dsts = np.array([r for _, r in active], dtype=np.int64)
        cnts = np.array([sum(e[2] for e in lst) for lst in active.values()],
                        dtype=np.int64)
        dm = np.zeros((m, m), dtype=np.int64)
        dm[srcs, dsts] = cnts * ln
        off = 0
        for dur, match in bna(dm):
            piece_end = float(t_exp + off + int(dur))
            for s_ in np.flatnonzero(match >= 0):
                key = (int(s_), int(match[s_]))
                q = queues.get(key)
                if not q:
                    continue
                served, used = int(dur), 0
                while served > 0 and q:
                    k3, rem = q[0]
                    take = min(rem, served)
                    rem -= take
                    served -= take
                    if take > 0:
                        segs.append((t_exp + off + used,
                                     t_exp + off + used + take,
                                     key[0], key[1]) + k3)
                    used += take
                    if rem == 0:
                        q.pop(0)
                    else:
                        q[0][1] = rem
                    completion[k3[0]] = max(completion.get(k3[0], 0.0),
                                            piece_end)
            off += int(dur)
        if off != ln * a:
            raise AssertionError("fix-up BNA length mismatch")
    cols = list(zip(*segs)) if segs else [[]] * 7
    return completion, Edges.of(*cols)
