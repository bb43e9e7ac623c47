"""Seeded, lazy job streams for the benchmark's cells.

A configuration file fixes the deployment (ports, job shape, coflow
marginals); a traffic file fixes the arrivals (load, calibration prefix).
``JobStream`` turns both and a seed into jobs, one at a time:

- Coflows follow the published marginals of the Facebook Hive/MapReduce
  trace as the paper reports them (arXiv:2012.11702 §VII): log-uniform
  widths from ``min_flows`` to ``max_flows`` flows, lognormal flow sizes
  clipped to ``[min_flow, max_flow]``, uniform port mapping with
  self-loops moved to another port.  Flows that land on the same
  (sender, receiver) pair add up.
- Jobs hold ``U{1, ..., 2 mu_bar - 1}`` coflows (mean ``mu_bar``) with a
  general DAG (each forward edge with probability ``edge_prob``) or a
  fan-in rooted tree (each non-root coflow keeps one edge to a random
  later coflow), the paper's two job families.
- Releases are Poisson arrivals in fabric time, floored to integers.  The
  rate puts the busiest port at ``load`` of its capacity over the
  stream's first ``calibration_jobs`` jobs: ``rate = load * K /
  max_port_work``.

Every seed serves one instance, with its ports relabelled: job ``k``'s
coflow count, widths, flow sizes, ports and edges come from a generator
seeded by ``k`` alone, the gaps between releases from one generator of
their own, and the seed draws a permutation of the ``m`` ports that
relabels every demand (sender and receiver alike).  So every seed brings
the same jobs, sizes and arrivals, and with them the same work: which
plans repair and which replan, the shapes that compile, the length of
every decomposition.  Jobs drawn afresh for every seed let the seed
decide how much work a window holds, and a run's rate then followed its
seed far more than its noise.  The calibration prefix is the first
``calibration_jobs`` jobs, whose busiest port does not change under a
relabelling, so the job sequence and the release times depend on the
seed alone, never on how many jobs a run consumes.  Nothing here imports
the program: a job is a plain ``JobSpec``, and each side builds its own
objects from it.

The sampling arithmetic follows ``repro.core.traces.fb_like_coflows`` /
``build_jobs``, the calibration and the Poisson arrivals
``repro.core.stream.stream_jobs`` / ``arrival_times``, kept
here so that the yardstick stays fixed when the program changes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

__all__ = ["JobSpec", "JobStream"]

_JOB, _GAPS, _PORTS = 0, 1, 2   # generator streams


@dataclass(frozen=True)
class JobSpec:
    """One job as plain data: coflow demands ((m, m) int64 each), Starts-After
    edges over their indices, weight and integer release time."""

    jid: int
    demands: tuple
    edges: tuple
    weight: float
    release: int


def _coflow(rng: np.random.Generator, m: int, cf: dict,
            u: float) -> np.ndarray:
    """One coflow whose width lies at quantile ``u`` of the log-uniform."""
    lo, hi = math.log10(int(cf["min_flows"])), \
        math.log10(max(int(cf["max_flows"]), int(cf["min_flows"]) + 1))
    width = int(round(10 ** (lo + u * (hi - lo))))
    width = min(width, m * (m - 1))
    mean, sigma = cf["flow_size_lognormal"]
    sizes = np.clip(np.round(rng.lognormal(mean=mean, sigma=sigma,
                                           size=width)),
                    cf["min_flow"], cf["max_flow"]).astype(np.int64)
    s = rng.integers(0, m, size=width)
    r = rng.integers(0, m, size=width)
    bad = s == r
    r[bad] = (r[bad] + 1 + rng.integers(0, m - 1, size=int(bad.sum()))) % m
    d = np.zeros((m, m), dtype=np.int64)
    np.add.at(d, (s, r), sizes)
    return d


def _edges(rng: np.random.Generator, n: int, dag: str,
           edge_prob: float) -> list[tuple[int, int]]:
    if dag == "general":
        return [(a, b) for a in range(n) for b in range(a + 1, n)
                if rng.random() < edge_prob]
    if dag == "fan_in_tree":
        return [(a, int(rng.integers(a + 1, n))) for a in range(n - 1)]
    raise ValueError(f"unknown job DAG family {dag!r}")


class JobStream:
    """The cell's arrivals: ``stream.job(k)`` is the k-th job, release included.

    ``config`` is the parsed configuration file, ``traffic`` the parsed
    traffic file (keys ``load`` and ``calibration_jobs``)."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        if seed < 0:
            raise ValueError(f"seed must be >= 0, got {seed}")
        self.m = int(config["ports"])
        self.jobs_cfg = config["jobs"]
        self.coflows_cfg = config["coflows"]
        self.seed = int(seed)
        self.ports = np.random.default_rng([self.seed, _PORTS]).permutation(
            self.m)
        self._gaps = np.random.default_rng([_GAPS])
        self._releases: list[int] = []
        self._clock = 0.0
        k = int(traffic["calibration_jobs"])
        work = np.zeros(2 * self.m, dtype=np.int64)
        for i in range(k):
            for d in self._content(i)[0]:
                work[:self.m] += d.sum(axis=1)
                work[self.m:] += d.sum(axis=0)
        self.max_port_work = int(work.max())
        self.rate = float(traffic["load"]) * k / max(self.max_port_work, 1)

    def _content(self, k: int) -> tuple[list[np.ndarray], list]:
        """Job ``k``'s coflow demands, on the instance's own port labels,
        and edges."""
        rng = np.random.default_rng([_JOB, k])
        n = int(rng.integers(1, 2 * int(self.jobs_cfg["mu_bar"])))
        demands = [_coflow(rng, self.m, self.coflows_cfg, float(rng.random()))
                   for _ in range(n)]
        edges = _edges(rng, n, self.jobs_cfg["dag"],
                       float(self.jobs_cfg.get("edge_prob", 0.5)))
        return demands, edges

    def _release(self, k: int) -> int:
        while len(self._releases) <= k:
            self._clock += float(self._gaps.exponential(1.0 / self.rate))
            if self._clock >= 2.0**53:
                raise ValueError("release time left the float64 "
                                 "integer-exact range")
            self._releases.append(int(math.floor(self._clock)))
        return self._releases[k]

    def job(self, k: int) -> JobSpec:
        demands, edges = self._content(k)
        p = self.ports                  # port a of this seed is port p[a]
        return JobSpec(jid=k, demands=tuple(d[np.ix_(p, p)] for d in demands),
                       edges=tuple(edges),
                       weight=float(self.jobs_cfg.get("weight", 1.0)),
                       release=self._release(k))
