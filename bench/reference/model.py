"""Coflow, job and instance: the data the plain reference plans over.

An m x m non-blocking switch; a coflow is an m x m integer demand matrix;
a job is a DAG over its coflows with Starts-After edges (a -> b: a must
complete before b starts), a weight and a release time (arXiv:2012.11702
§II).  All demands and durations are integers.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass

import numpy as np


def effective_size(demand: np.ndarray) -> int:
    """D (Definition 1): the largest load any port must send or receive."""
    if demand.size == 0:
        return 0
    return int(max(demand.sum(axis=1).max(initial=0),
                   demand.sum(axis=0).max(initial=0)))


def aggregate_size(demands) -> int:
    """Definition 2: effective size of the sum of the coflows."""
    total = None
    for d in demands:
        total = d.astype(np.int64, copy=True) if total is None else total + d
    return 0 if total is None else effective_size(total)


def topological_order(n: int, edges) -> list[int]:
    """Kahn's sort, smallest index first."""
    indeg = [0] * n
    out: list[list[int]] = [[] for _ in range(n)]
    for a, b in edges:
        indeg[b] += 1
        out[a].append(b)
    heap = [i for i in range(n) if indeg[i] == 0]
    heapq.heapify(heap)
    order: list[int] = []
    while heap:
        u = heapq.heappop(heap)
        order.append(u)
        for v in out[u]:
            indeg[v] -= 1
            if indeg[v] == 0:
                heapq.heappush(heap, v)
    if len(order) != n:
        raise ValueError("dependency graph has a cycle")
    return order


def parents_of(n: int, edges) -> list[list[int]]:
    par: list[list[int]] = [[] for _ in range(n)]
    for a, b in edges:
        par[b].append(a)
    return par


def children_of(n: int, edges) -> list[list[int]]:
    ch: list[list[int]] = [[] for _ in range(n)]
    for a, b in edges:
        ch[a].append(b)
    return ch


@dataclass
class Coflow:
    jid: int
    cid: int
    demand: np.ndarray      # (m, m) int64

    @property
    def D(self) -> int:
        return effective_size(self.demand)


@dataclass
class Job:
    jid: int
    coflows: list[Coflow]
    edges: list[tuple[int, int]]
    weight: float = 1.0
    release: int = 0

    @property
    def mu(self) -> int:
        return len(self.coflows)

    def aggregate_demand(self, m: int) -> np.ndarray:
        agg = np.zeros((m, m), dtype=np.int64)
        for c in self.coflows:
            agg += c.demand
        return agg

    @property
    def T(self) -> int:
        """Critical path size (Definition 3)."""
        n = self.mu
        if n == 0:
            return 0
        par = parents_of(n, self.edges)
        best = [0] * n
        for u in topological_order(n, self.edges):
            best[u] = self.coflows[u].D + max((best[p] for p in par[u]),
                                              default=0)
        return max(best)

    def layers(self) -> list[list[int]]:
        """S_0..S_{H-1} (Definition 6): coflows by longest path from a source."""
        n = self.mu
        par = parents_of(n, self.edges)
        depth = [0] * n
        for u in topological_order(n, self.edges):
            for p in par[u]:
                depth[u] = max(depth[u], depth[p] + 1)
        out: list[list[int]] = [[] for _ in range(max(depth, default=-1) + 1)]
        for u in range(n):
            out[depth[u]].append(u)
        return out

    def is_rooted_forest(self) -> bool:
        """Every out-degree <= 1 (fan-in trees) or every in-degree <= 1."""
        n = self.mu
        if n == 0:
            return False
        outdeg, indeg = [0] * n, [0] * n
        for a, b in self.edges:
            outdeg[a] += 1
            indeg[b] += 1
        return all(d <= 1 for d in outdeg) or all(d <= 1 for d in indeg)


@dataclass
class Instance:
    m: int
    jobs: list[Job]

    def gamma(self) -> int:
        """Smallest positive flow size (§VI-B)."""
        vals = [int(c.demand[c.demand > 0].min()) for j in self.jobs
                for c in j.coflows if (c.demand > 0).any()]
        return min(vals) if vals else 1
