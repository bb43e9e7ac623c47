"""BNA, the Birkhoff-von-Neumann decomposition of one coflow (Algorithm 1).

The schedule finishes in exactly D slots.  Each step takes a perfect
matching of the filled graph, where (s, r) is an edge iff d[s, r] > 0 or
both ports have slack (d_s < D and d_r < D); every tight port is then
matched through a real edge.  The step length is the smallest real matched
demand or the smallest slack of a port that does not transmit.  The
matching is kept across steps and repaired by augmenting paths (Kuhn),
smallest sender first.  The problem is restricted to the ports the demand
loads, padded with idle ports to a square.
"""
from __future__ import annotations

import numpy as np

NO_MATCH = -1


def _augment(start: int, adj_fn, match_sr: np.ndarray,
             match_rs: np.ndarray, m: int) -> bool:
    visited = np.zeros(m, dtype=bool)
    parent_r: dict[int, int] = {}
    stack = [start]
    frontier_of: dict[int, np.ndarray] = {}
    while stack:
        s = stack[-1]
        if s not in frontier_of:
            frontier_of[s] = np.flatnonzero(adj_fn(s) & ~visited)
        found = False
        while frontier_of[s].size:
            r = int(frontier_of[s][0])
            frontier_of[s] = frontier_of[s][1:]
            if visited[r]:
                continue
            visited[r] = True
            parent_r[r] = s
            nxt = int(match_rs[r])
            if nxt == NO_MATCH:
                while True:
                    ps = parent_r[r]
                    prev_r = int(match_sr[ps])
                    match_sr[ps] = r
                    match_rs[r] = ps
                    if ps == start:
                        return True
                    r = prev_r
            else:
                stack.append(nxt)
                found = True
                break
        if not found:
            stack.pop()
            frontier_of.pop(s, None)
    return False


def _bna_square(d: np.ndarray) -> list[tuple[int, np.ndarray]]:
    d = np.array(d, dtype=np.int64, copy=True)
    m = d.shape[0]
    row = d.sum(axis=1)
    col = d.sum(axis=0)
    D = int(max(row.max(initial=0), col.max(initial=0)))
    if D == 0:
        return []
    match_sr = np.full(m, NO_MATCH, dtype=np.int64)
    match_rs = np.full(m, NO_MATCH, dtype=np.int64)

    def adj_fn(s: int) -> np.ndarray:
        a = d[s] > 0
        if row[s] < D:
            a = a | (col < D)
        return a

    def repair() -> None:
        ms = np.flatnonzero(match_sr != NO_MATCH)
        if ms.size:
            rr = match_sr[ms]
            bad = (d[ms, rr] == 0) & ((row[ms] >= D) | (col[rr] >= D))
            for s in ms[bad]:
                match_rs[match_sr[s]] = NO_MATCH
                match_sr[s] = NO_MATCH
        for s in np.flatnonzero(match_sr == NO_MATCH):
            if not _augment(int(s), adj_fn, match_sr, match_rs, m):
                raise AssertionError("no perfect matching in the filled graph")

    pieces: list[tuple[int, np.ndarray]] = []
    repair()
    senders = np.arange(m)
    while D > 0:
        rcv = match_sr
        real = (rcv != NO_MATCH) & (d[senders, np.maximum(rcv, 0)] > 0)
        t = np.iinfo(np.int64).max
        if real.any():
            t = int(d[senders[real], rcv[real]].min())
        if (~real).any():
            t = min(t, int((D - row[~real]).min()))
        recv_real = np.zeros(m, dtype=bool)
        recv_real[rcv[real]] = True
        if (~recv_real).any():
            t = min(t, int((D - col[~recv_real]).min()))
        if t <= 0:
            raise AssertionError("zero-length BNA step")
        piece = np.full(m, NO_MATCH, dtype=np.int64)
        piece[senders[real]] = rcv[real]
        pieces.append((t, piece))
        sr, rr = senders[real], rcv[real]
        d[sr, rr] -= t
        row[sr] -= t
        col[rr] -= t
        D -= t
        if D == 0:
            break
        repair()
    return pieces


def bna(demand: np.ndarray) -> list[tuple[int, np.ndarray]]:
    """(duration, matching) pieces; matching[s] = r while s sends to r, -1
    while s idles.  The durations add up to D."""
    full = np.asarray(demand, dtype=np.int64)
    m = full.shape[0]
    rows = np.flatnonzero(full.sum(axis=1) > 0)
    cols = np.flatnonzero(full.sum(axis=0) > 0)
    k = max(rows.size, cols.size)
    if k == 0:
        return []
    if k == m:
        return _bna_square(full)
    rows_p = np.concatenate(
        [rows, np.setdiff1d(np.arange(m), rows)[:k - rows.size]])
    cols_p = np.concatenate(
        [cols, np.setdiff1d(np.arange(m), cols)[:k - cols.size]])
    out: list[tuple[int, np.ndarray]] = []
    for t, match in _bna_square(full[np.ix_(rows_p, cols_p)]):
        piece = np.full(m, NO_MATCH, dtype=np.int64)
        ss = np.flatnonzero(match != NO_MATCH)
        piece[rows_p[ss]] = cols_p[match[ss]]
        out.append((t, piece))
    return out
