"""Backend dispatch + compute caches for the scheduler engine.

A small ``GlobalConfig``-style module (after alpa's ``global_config``): one
process-wide :class:`BackendConfig` instance, initialized from environment
variables, selects how the engine's two hot paths execute:

* **alpha backend** — ``merge_and_fix`` (timeline.py, Lemma 6 Steps 3-4)
  computes alpha_I per merged interval.  ``"numpy"`` runs the chunked
  prefix-sum oracle (`timeline._alphas_vectorized`); ``"pallas"`` routes
  through the ``kernels/coflow_merge`` Pallas kernel (Mosaic-compiled on a
  TPU, interpret mode elsewhere); ``"auto"`` picks pallas iff
  ``jax.default_backend()`` is a TPU.  The two are bit-identical.  A kernel
  failure always raises: no backend falls back to another, so a run that
  resolved pallas did its work on the kernel.

* **BNA backend** — the batched matching layer (``core/matching.py``,
  ``bna_many``) vectorizes the multi-coflow BNA decomposition and
  dispatches its inner step per ``REPRO_BNA_BACKEND``: ``"numpy"`` runs the
  in-place vectorized step, ``"pallas"`` routes the same integer arithmetic
  through the ``kernels/bna_step`` kernel (Mosaic-compiled on a TPU,
  interpret mode elsewhere), ``"auto"`` picks pallas iff the backend is a
  TPU.  Bit-identical; failures raise, as for the alpha backend.  A bucket
  past the kernel's int32 range runs the numpy step (exact there), and
  ``cache_stats()["launches"]`` counts only the kernel's real launches.

* **BNA cache** — a bounded LRU keyed on ``(shape, dtype, bytes)`` of the
  demand, memoizing BNA decompositions (Algorithm 1).  Unlike the old
  per-``Coflow``-object memo, the content key survives the online driver's
  ``_sub_instance`` rebuilding fresh ``Coflow`` objects on every arrival,
  so untouched coflows hit across reschedules; including shape and dtype
  keeps differently-typed or differently-shaped demands from colliding.
  :func:`bna_pieces_many` is the batch entry: it consults the LRU first and
  hands ONLY the misses to ``bna_many`` in one batched call — this is what
  the engine's instance-level prefetch (``engine.plan`` /
  ``SchedulerSession``) goes through.  Hit/miss counters (scalar and
  per-batch) feed the benchmark report.

* **order cache** — a bounded LRU over the exact scheduling state (port
  count, and per job: id, weight, release, DAG edges, demand bytes)
  memoizing the primal-dual job order (Algorithm 5).  Keyed on the full
  state, reuse is results-identical by construction; it fires whenever the
  same state is re-planned (algorithm A/B pairs on one instance, beta
  sweeps, and online reschedules whose surviving jobs are untouched).

* **group-block cache** — a bounded LRU over spread-mode G-DM / G-DM-RT
  *group parts*: the DMA / DMA-RT schedule of one geometric group, built
  at origin 0 and keyed on the construction's full input (scheduler kind,
  port count, beta/decompose/nested/require_tree knobs, and the ordered
  member tuple with each job's DAG edges and per-coflow demand bytes).
  Spread-mode layouts are deterministic (zero rng draws) and translation
  invariant in the origin, so ``group_block(...).shifted_expanded(start)``
  is bit-identical to rebuilding the group at ``start`` — this is what
  lets full replans under a session-pinned gamma reassemble untouched
  groups as shifted blocks instead of re-running DMA (see
  ``core/session.py``).  Randomized delay modes are never cached (their
  layouts consume rng draws, so a cached result would corrupt the
  caller's stream).

* **loads / grouping-key caches** — per-job Algorithm 5 load vectors
  keyed on demand bytes (``ordering.job_load_vectors``), and the
  geometric-grouping prefix-load cumsum keyed on the ordered demand
  signature (:func:`grouping_keys`).  The cumsum cache is *incremental*:
  a replan whose Algorithm 5 order extends a cached prefix (appended
  arrivals) extends the cached cumsum with the new rows instead of
  recomputing the whole prefix — exact, because the loads are integers
  far below 2^53 (guarded).

* **plan backend** — the whole-planning-path knob (``core/pipeline.py``):
  ``"python"`` runs the classic per-coflow loop; ``"jit"`` routes the
  per-instance prefetch, the per-coflow edge-interval construction, and the
  Algorithm 5 ordering inputs through fixed-shape compiled XLA programs
  (bit-identical plans — all-integer arithmetic); ``"auto"`` picks jit iff
  the backend is a TPU (on CPU the compile latency only pays off for
  large instances, so it is opt-in there — same policy as the alpha/BNA
  knobs).  A pipeline failure raises.  The only host path left under jit
  is the per-bucket int32 range guard in ``core/pipeline.py``: a bucket
  whose loads would overflow int32 decomposes on the numpy path, exactly,
  and counts in ``cache_stats()["plan"]["compile"]["bucket_fallbacks"]``.

Environment switches (read once at import; also settable in-process)::

    REPRO_ALPHA_BACKEND    auto | numpy | pallas      (default: auto)
    REPRO_BNA_BACKEND      auto | numpy | pallas      (default: auto)
    REPRO_PLAN_BACKEND     auto | python | jit        (default: auto)
    REPRO_BNA_BATCH        1 | 0: instance-level batched BNA prefetch
                           (default: 1)
    REPRO_BNA_CACHE_SIZE   max cached decompositions  (default: 4096; 0 off)
    REPRO_ORDER_CACHE_SIZE max cached job orders      (default: 256;  0 off)
    REPRO_GROUP_CACHE_SIZE max cached group blocks    (default: 512;  0 off)
    REPRO_LOADS_CACHE_SIZE max cached per-job load
                           vectors (Algorithm 5)      (default: 4096; 0 off)
    REPRO_GKEY_CACHE_SIZE  max cached grouping-key
                           prefix cumsums             (default: 512;  0 off)
"""
from __future__ import annotations

import os
import sys
from collections import OrderedDict
from contextlib import contextmanager
from dataclasses import dataclass
from pathlib import Path
from typing import Iterable

import numpy as np

from repro.kernels import launch_counts, reset_launch_counts

from .spans import span

__all__ = [
    "BackendConfig",
    "config",
    "set_alpha_backend",
    "use_alpha_backend",
    "resolve_alpha_backend",
    "set_bna_backend",
    "use_bna_backend",
    "resolve_bna_backend",
    "set_plan_backend",
    "use_plan_backend",
    "resolve_plan_backend",
    "compute_alphas",
    "fused_merge_fix",
    "plan_edges",
    "plan_order_loads",
    "prefetch_plan",
    "bna_pieces",
    "bna_pieces_many",
    "prefetch_bna",
    "group_block",
    "grouping_prefix",
    "cache_stats",
    "clear_caches",
    "no_caches",
    "configure_compile_cache",
]

_ALPHA_BACKENDS = ("auto", "numpy", "pallas")
_BNA_BACKENDS = ("auto", "numpy", "pallas")
_PLAN_BACKENDS = ("auto", "python", "jit")


@dataclass
class BackendConfig:
    """Process-wide engine knobs (env-initialized, mutable in-process)."""

    alpha_backend: str = "auto"
    bna_backend: str = "auto"
    plan_backend: str = "auto"
    bna_batch: bool = True
    bna_cache_size: int = 4096
    order_cache_size: int = 256
    group_cache_size: int = 512
    loads_cache_size: int = 4096
    gkey_cache_size: int = 512

    @staticmethod
    def from_env() -> "BackendConfig":
        cfg = BackendConfig(
            alpha_backend=os.environ.get("REPRO_ALPHA_BACKEND", "auto").lower(),
            bna_backend=os.environ.get("REPRO_BNA_BACKEND", "auto").lower(),
            plan_backend=os.environ.get("REPRO_PLAN_BACKEND", "auto").lower(),
            bna_batch=os.environ.get("REPRO_BNA_BATCH", "1") != "0",
            bna_cache_size=int(os.environ.get("REPRO_BNA_CACHE_SIZE", "4096")),
            order_cache_size=int(os.environ.get("REPRO_ORDER_CACHE_SIZE", "256")),
            group_cache_size=int(os.environ.get("REPRO_GROUP_CACHE_SIZE", "512")),
            loads_cache_size=int(os.environ.get("REPRO_LOADS_CACHE_SIZE", "4096")),
            gkey_cache_size=int(os.environ.get("REPRO_GKEY_CACHE_SIZE", "512")),
        )
        if cfg.alpha_backend not in _ALPHA_BACKENDS:
            raise ValueError(
                f"REPRO_ALPHA_BACKEND={cfg.alpha_backend!r}; "
                f"expected one of {_ALPHA_BACKENDS}")
        if cfg.bna_backend not in _BNA_BACKENDS:
            raise ValueError(
                f"REPRO_BNA_BACKEND={cfg.bna_backend!r}; "
                f"expected one of {_BNA_BACKENDS}")
        if cfg.plan_backend not in _PLAN_BACKENDS:
            raise ValueError(
                f"REPRO_PLAN_BACKEND={cfg.plan_backend!r}; "
                f"expected one of {_PLAN_BACKENDS}")
        return cfg


config = BackendConfig.from_env()

#: JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
#: unset: one fixed directory in the checkout (src/repro/core/ -> root),
#: because the directory is part of every cache entry's key
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
    call this once before their first compile; importing the library never
    does.  When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it,
    and nothing is changed.  Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)


def set_alpha_backend(name: str) -> None:
    """One-line switch: route merge_and_fix alphas through `name`."""
    if name not in _ALPHA_BACKENDS:
        raise ValueError(f"unknown alpha backend {name!r}; "
                         f"expected one of {_ALPHA_BACKENDS}")
    config.alpha_backend = name


@contextmanager
def use_alpha_backend(name: str):
    prev = config.alpha_backend
    set_alpha_backend(name)
    try:
        yield
    finally:
        config.alpha_backend = prev


def _resolve_auto() -> str:
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "numpy"


def resolve_alpha_backend(force: str | None = None) -> str:
    """Concrete backend for this call: explicit override > config > auto."""
    name = force or config.alpha_backend
    return _resolve_auto() if name == "auto" else name


def set_bna_backend(name: str) -> None:
    """One-line switch: route the batched BNA step through `name`."""
    if name not in _BNA_BACKENDS:
        raise ValueError(f"unknown BNA backend {name!r}; "
                         f"expected one of {_BNA_BACKENDS}")
    config.bna_backend = name


@contextmanager
def use_bna_backend(name: str):
    prev = config.bna_backend
    set_bna_backend(name)
    try:
        yield
    finally:
        config.bna_backend = prev


def resolve_bna_backend(force: str | None = None) -> str:
    """Concrete BNA-step backend for this call (mirrors the alpha knob)."""
    name = force or config.bna_backend
    if name not in _BNA_BACKENDS:
        raise ValueError(f"unknown BNA backend {name!r}; "
                         f"expected one of {_BNA_BACKENDS}")
    return _resolve_auto() if name == "auto" else name


def set_plan_backend(name: str) -> None:
    """One-line switch: route whole-instance planning through `name`."""
    if name not in _PLAN_BACKENDS:
        raise ValueError(f"unknown plan backend {name!r}; "
                         f"expected one of {_PLAN_BACKENDS}")
    config.plan_backend = name


@contextmanager
def use_plan_backend(name: str):
    prev = config.plan_backend
    set_plan_backend(name)
    try:
        yield
    finally:
        config.plan_backend = prev


def resolve_plan_backend(force: str | None = None) -> str:
    """Concrete plan backend for this call: "auto" picks jit iff a TPU is
    attached (CPU compile latency only pays off for large instances, so jit
    is opt-in there — exactly the alpha/BNA auto policy)."""
    name = force or config.plan_backend
    if name not in _PLAN_BACKENDS:
        raise ValueError(f"unknown plan backend {name!r}; "
                         f"expected one of {_PLAN_BACKENDS}")
    if name == "auto":
        return "jit" if _resolve_auto() == "pallas" else "python"
    return name


def compute_alphas(events: np.ndarray, edges, m: int,
                   force: str | None = None) -> np.ndarray:
    """Per-interval alphas (max per-port packet count) for merge_and_fix.

    `edges` is a timeline.EdgeIntervals; `events` the sorted unique interval
    boundaries.  Dispatches per :func:`resolve_alpha_backend` (the two
    backends agree exactly — both count integer edge activations per port);
    a kernel error propagates.
    """
    from .timeline import _alphas_vectorized  # oracle (import cycle: lazy)

    if resolve_alpha_backend(force) == "pallas" and edges.size \
            and events.size > 1:
        from repro.kernels.coflow_merge.ops import edge_interval_alphas

        return np.asarray(
            edge_interval_alphas(events, edges.t0, edges.t1,
                                 edges.s, edges.r, m),
            dtype=np.int64)
    return _alphas_vectorized(events, edges, m)


# --------------------------------------------------------------------------
# jit planning pipeline dispatch (REPRO_PLAN_BACKEND; see core/pipeline.py)
# --------------------------------------------------------------------------

def prefetch_plan(demands: "Iterable[np.ndarray]") -> None:
    """Instance-level prefetch dispatched on the plan backend: under jit it
    warms the BNA *and* edge-interval caches through the compiled
    width-bucketed sweep (pipeline.prefetch_demands); otherwise it is
    exactly :func:`prefetch_bna`."""
    ds = list(demands)
    if resolve_plan_backend() == "jit":
        from . import pipeline

        pipeline.prefetch_demands(ds)
        return
    prefetch_bna(ds)


def plan_edges(demand: np.ndarray):
    """Relative (t0, t1, s, r) edge intervals of one coflow's BNA schedule
    under the jit plan backend; None routes the caller to the python path
    (the plan backend resolves python)."""
    if resolve_plan_backend() != "jit":
        return None
    from . import pipeline

    return pipeline.coflow_edges_rel(demand)


def plan_order_loads(instance):
    """Algorithm 5 load vectors from the jitted segment-sum (bit-identical
    integer sums); None routes the caller to the host computation."""
    if resolve_plan_backend() != "jit":
        return None
    from . import pipeline

    return pipeline.instance_load_vectors(instance)


def fused_merge_fix(events: np.ndarray, edges, m: int,
                    force: str | None = None):
    """(alphas, expansion deltas) in one compiled call via the
    ``kernels/merge_fix`` fused step — engaged only when the plan backend
    resolves jit AND the alpha backend resolves pallas (on CPU the numpy
    oracle stays the better default).  None → the caller runs the classic
    two-stage path.  Bit-identical: same kernel alphas, integer deltas."""
    if resolve_plan_backend() != "jit":
        return None
    if resolve_alpha_backend(force) != "pallas":
        return None
    if not (edges.size and events.size > 1):
        return None
    from repro.kernels.merge_fix.ops import merge_fix_step

    alphas, deltas = merge_fix_step(events, edges.t0, edges.t1,
                                    edges.s, edges.r, m)
    return (np.asarray(alphas, dtype=np.int64),
            np.asarray(deltas, dtype=np.int64))


# --------------------------------------------------------------------------
# bounded LRU caches with hit/miss counters
# --------------------------------------------------------------------------

class LRUCache:
    """Tiny bounded LRU with hit/miss counters; maxsize <= 0 disables."""

    def __init__(self, maxsize: int, name: str):
        self.name = name
        self.maxsize = maxsize
        self._od: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0

    def lookup(self, key):
        """(found, value); counts a hit/miss and refreshes recency."""
        if self.maxsize <= 0:
            self.misses += 1
            return False, None
        try:
            val = self._od[key]
        except KeyError:
            self.misses += 1
            return False, None
        self._od.move_to_end(key)
        self.hits += 1
        return True, val

    def peek(self, key):
        """(found, value) WITHOUT touching counters or recency — for
        secondary probes (the grouping-key prefix scan) whose hits/misses
        would otherwise distort the primary lookup's rates."""
        if self.maxsize <= 0 or key not in self._od:
            return False, None
        return True, self._od[key]

    def store(self, key, val) -> None:
        if self.maxsize <= 0:
            return
        self._od[key] = val
        self._od.move_to_end(key)
        while len(self._od) > self.maxsize:
            self._od.popitem(last=False)

    def __len__(self) -> int:
        return len(self._od)

    def clear(self) -> None:
        self._od.clear()
        self.hits = 0
        self.misses = 0

    def stats(self) -> dict:
        total = self.hits + self.misses
        return {"hits": self.hits, "misses": self.misses,
                "size": len(self._od),
                "hit_rate": (self.hits / total) if total else 0.0}


bna_cache = LRUCache(config.bna_cache_size, "bna")
order_cache = LRUCache(config.order_cache_size, "order")
group_cache = LRUCache(config.group_cache_size, "group")
loads_cache = LRUCache(config.loads_cache_size, "loads")
gkey_cache = LRUCache(config.gkey_cache_size, "gkey")

# per-batch counters for bna_pieces_many (surfaced in cache_stats()["bna"]
# ["batch"]): how many batched lookups ran, and how their members split
# into cache hits, misses handed to the batched decomposition (unique
# demands), and in-batch duplicates that shared a miss's result
_bna_batch = {"batches": 0, "hits": 0, "misses": 0, "deduped": 0}


def _bna_key(demand: np.ndarray) -> tuple:
    """BNA cache key: (shape, dtype, bytes).  Keying on the full identity —
    not just the port count and raw bytes — means demands that happen to
    share a byte string across dtypes/shapes can neither collide nor
    spuriously hit each other's entries."""
    return (demand.shape, demand.dtype.str, demand.tobytes())


def bna_pieces(demand: np.ndarray) -> list:
    """BNA decomposition of `demand`, memoized on (shape, dtype, bytes).

    The returned pieces are shared across callers and must be treated as
    read-only (every consumer in core/ only reads them).
    """
    from .bna import bna

    bna_cache.maxsize = config.bna_cache_size
    key = _bna_key(demand)
    found, pieces = bna_cache.lookup(key)
    if not found:
        pieces = bna(demand)
        bna_cache.store(key, pieces)
    return pieces


def bna_pieces_many(demands: list, keys: list | None = None) -> list:
    """BNA decompositions for a whole batch of demands: the LRU is
    consulted first, and ONLY the misses (deduplicated — repeated demands
    in one batch decompose once) go through the batched ``bna_many``
    decomposition in a single call.  Results are bit-identical to
    ``[bna_pieces(d) for d in demands]``; per-batch hit/miss counts land in
    ``cache_stats()["bna"]["batch"]``.  ``keys`` accepts precomputed
    ``_bna_key`` values (same order as ``demands``) so callers that
    already serialized the batch — the prefetch guard — don't pay the
    hashing twice."""
    from .matching import bna_many

    bna_cache.maxsize = config.bna_cache_size
    out: list = [None] * len(demands)
    miss_keys: list = []
    miss_demands: list = []
    by_key: dict = {}
    hits = 0
    for i, dem in enumerate(demands):
        key = _bna_key(dem) if keys is None else keys[i]
        found, pieces = bna_cache.lookup(key)
        if found:
            out[i] = pieces
            hits += 1
            continue
        slot = by_key.get(key)
        if slot is None:
            by_key[key] = [i]
            miss_keys.append(key)
            miss_demands.append(dem)
        else:
            slot.append(i)
    if miss_demands:
        with span("plan.decompose", coflows=len(miss_demands)):
            decomposed = bna_many(miss_demands)
        for key, pieces in zip(miss_keys, decomposed):
            bna_cache.store(key, pieces)
            for i in by_key[key]:
                out[i] = pieces
    _bna_batch["batches"] += 1
    _bna_batch["hits"] += hits
    _bna_batch["misses"] += len(miss_demands)
    _bna_batch["deduped"] += len(demands) - hits - len(miss_demands)
    return out


def prefetch_bna(demands: "Iterable[np.ndarray]") -> None:
    """Warm the BNA cache for every demand in one batched call — the
    instance-level prefetch ``engine.plan`` and ``SchedulerSession`` issue
    before ``dma.isolated_job_unit`` / ``dma_srt`` walk jobs one by one.

    A no-op when batching is off (REPRO_BNA_BATCH=0), the cache is
    disabled, or the instance's distinct demands cannot all FIT in the
    cache: a batch bigger than ``maxsize`` necessarily evicts some of its
    own entries — refreshed hits included — before the scheduler's walk
    reads them (sequential-LRU thrash: those lookups miss and re-run
    scalar BNA on top of the batched work, strictly worse than the scalar
    path).  Raise REPRO_BNA_CACHE_SIZE to batch bigger instances."""
    if not config.bna_batch or config.bna_cache_size <= 0:
        return
    ds = list(demands)
    if not ds:
        return
    keys = [_bna_key(d) for d in ds]
    if len(set(keys)) > config.bna_cache_size:
        return
    bna_pieces_many(ds, keys=keys)


# --------------------------------------------------------------------------
# spread-mode group-block cache (G-DM / G-DM-RT geometric groups)
# --------------------------------------------------------------------------

def _group_sig(jobs) -> tuple:
    """Per-job identity a spread-mode DMA/DMA-SRT layout is a function of:
    job id (embedded in the emitted ledger/expansion), weight and release
    (unread by the constructions but kept for soundness against future
    changes — both are constant per job across replans, so they cost no
    hits), DAG edges, and per-coflow (cid, shape, dtype, bytes)."""
    return tuple(
        (int(j.jid), float(j.weight), int(j.release), tuple(j.edges),
         tuple((c.cid, c.demand.shape, c.demand.dtype.str,
                c.demand.tobytes()) for c in j.coflows))
        for j in jobs)


def group_block(kind: str, jobs, m: int, *, beta: float = 2.0,
                decompose: bool = False, use_kernel: "bool | None" = None,
                nested: bool = True, require_tree: bool = True,
                delays: str = "spread"):
    """One geometric group's DMA (kind="gdm") / DMA-RT (kind="gdm_rt")
    schedule built at **origin 0**, memoized on the construction's full
    input.  Spread-mode layouts are deterministic (zero rng draws) and
    translation invariant in the origin, so callers place the block with
    ``.shifted_expanded(start)`` — bit-identical to rebuilding the group at
    ``start``.  This is what turns a "full replan" under a session-pinned
    gamma into a reassembly of already-built blocks (core/gdm.py group
    loop, core/session.py grouped repair).

    The returned FinalSchedule is shared across callers and must be
    treated as read-only (the same contract as the shared BNA pieces; its
    lazy decomposition fields are idempotent).  Randomized delay modes are
    rejected: their layouts consume rng draws, so a cached result would
    corrupt the caller's stream.
    """
    from .dma import dma
    from .dma_srt import dma_rt

    if kind not in ("gdm", "gdm_rt"):
        raise ValueError(f"unknown group-block kind {kind!r}; "
                         f"choose from ('gdm', 'gdm_rt')")
    if delays != "spread":
        raise ValueError(
            f"group_block caches spread-mode layouts only (got "
            f"delays={delays!r}): randomized modes consume rng draws")
    group_cache.maxsize = config.group_cache_size
    with span("plan.group_block") as sp:
        key = (kind, int(m), float(beta), bool(decompose), use_kernel,
               bool(nested), bool(require_tree), delays) + _group_sig(jobs)
        found, part = group_cache.lookup(key)
        sp.set(hit=found)
        if not found:
            if kind == "gdm_rt":
                part = dma_rt(list(jobs), m, beta=beta, rng=None, origin=0,
                              decompose=decompose, use_kernel=use_kernel,
                              nested=nested, require_tree=require_tree,
                              delays=delays)
            else:
                part = dma(list(jobs), m, beta=beta, rng=None, origin=0,
                           decompose=decompose, use_kernel=use_kernel,
                           delays=delays)
            group_cache.store(key, part)
        return part


# --------------------------------------------------------------------------
# incremental Algorithm 5 grouping-key prefix (geometric grouping, step 2)
# --------------------------------------------------------------------------

# how far back the prefix probe scans: appended-arrival replans extend the
# previous event's entry, and arrival batches are small, so a handful of
# probe lengths covers the streaming case without scanning the cache
_GKEY_PREFIX_PROBES = 4

# exact hits / prefix extensions / cold recomputes (cache_stats()["gkey"])
_gkey_counts = {"exact": 0, "extended": 0, "cold": 0}


def _gkey_sig(job) -> tuple:
    """What a job contributes to the prefix-load cumsum: its per-coflow
    demands (the load vector is their row/column sums)."""
    return tuple((c.demand.shape, c.demand.dtype.str, c.demand.tobytes())
                 for c in job.coflows)


def grouping_prefix(instance, order: list) -> np.ndarray:
    """D_i for the geometric grouping (paper §VI step 2): the effective
    size of the aggregate coflow of the first i jobs of ``order`` — the
    max over 2m ports of the prefix cumsum of per-job load vectors (row
    sums commute with prefix sums, so no (m, m) accumulation is needed;
    both the old fast path and the old dense fallback now share this one
    O(n·m) computation).

    Memoized on (m, ordered per-job demand signature) with **incremental
    prefix extension**: when the exact key misses but a recent prefix of
    the order is cached — the appended-arrivals replan shape — only the
    new rows are cumsum-extended from the cached last row.  Exact in
    float64 below 2^53 (guarded).  Returns an int64 array aligned with
    ``order``.
    """
    from .ordering import job_load_vectors

    gkey_cache.maxsize = config.gkey_cache_size
    if not order:
        return np.zeros(0, dtype=np.int64)
    by_id = {j.jid: j for j in instance.jobs}
    m = instance.m
    sigs = tuple(_gkey_sig(by_id[jid]) for jid in order)
    key = (m,) + sigs
    found, val = gkey_cache.lookup(key)
    if found:
        _gkey_counts["exact"] += 1
        return val[1]
    n = len(order)
    base_row, base_D, start = None, None, 0
    for p in range(n - 1, max(n - 1 - _GKEY_PREFIX_PROBES, 0), -1):
        hit, pv = gkey_cache.peek((m,) + sigs[:p])
        if hit:
            base_row, base_D, start = pv[0], pv[1], p
            break
    _gkey_counts["extended" if base_row is not None else "cold"] += 1
    rows = job_load_vectors([by_id[jid] for jid in order[start:]], m)
    cum = np.cumsum(rows, axis=0)
    if base_row is not None:
        cum += base_row
    if cum.size and float(cum[-1].max()) >= 2.0**53:
        # past 2^53 float64 drops integer precision and the prefix maxima
        # would silently stop being the exact effective sizes
        raise ValueError(
            "prefix load cumsum exceeds the float64 integer-exact "
            "range (2^53); the geometric grouping keys would be inexact")
    D_new = cum.max(axis=1).astype(np.int64)
    D = D_new if base_D is None else np.concatenate([base_D, D_new])
    last_row = cum[-1].copy() if cum.size else \
        (base_row if base_row is not None else np.zeros(2 * m))
    gkey_cache.store(key, (last_row, D))
    return D


def cache_stats() -> dict:
    stats = {"bna": {**bna_cache.stats(), "batch": dict(_bna_batch)},
             "order": order_cache.stats(),
             "group": group_cache.stats(),
             "loads": loads_cache.stats(),
             "gkey": {**gkey_cache.stats(), "prefix": dict(_gkey_counts)},
             "launches": launch_counts()}
    merge_fix = sys.modules.get("repro.kernels.merge_fix.ops")
    stats["merge_fix"] = (merge_fix.merge_fix_stats() if merge_fix
                          else {"calls": 0, "misses": 0})
    if "repro.core.pipeline" in sys.modules:
        stats["plan"] = sys.modules["repro.core.pipeline"].pipeline_stats()
    return stats


def _result_caches() -> "list[tuple[str, LRUCache]]":
    """(config size attr, cache) for every result memo this module owns —
    the single list clear_caches/no_caches iterate, so a new cache cannot
    be forgotten by one of them."""
    return [("bna_cache_size", bna_cache),
            ("order_cache_size", order_cache),
            ("group_cache_size", group_cache),
            ("loads_cache_size", loads_cache),
            ("gkey_cache_size", gkey_cache)]


def clear_caches() -> None:
    for _, cache in _result_caches():
        cache.clear()
    for k in _bna_batch:
        _bna_batch[k] = 0
    for k in _gkey_counts:
        _gkey_counts[k] = 0
    reset_launch_counts()
    if "repro.core.pipeline" in sys.modules:
        # result caches only; compiled executables are data-independent
        sys.modules["repro.core.pipeline"].clear_pipeline_caches()


@contextmanager
def no_caches():
    """Disable (and clear) the result caches — the from-scratch comparator.
    Covers the jit pipeline's edge cache too (compiled executables stay:
    they are data-independent, caching them is not a result memo)."""
    pairs = _result_caches()
    edge_cache = None
    if "repro.core.pipeline" in sys.modules:
        edge_cache = sys.modules["repro.core.pipeline"].edge_cache
    saved_cfg = {attr: getattr(config, attr) for attr, _ in pairs}
    caches = [c for _, c in pairs] + ([edge_cache] if edge_cache else [])
    saved = [(c.maxsize, dict(c._od), c.hits, c.misses) for c in caches]
    for attr, _ in pairs:
        setattr(config, attr, 0)
    for c in caches:
        c.clear()
        c.maxsize = 0
    try:
        yield
    finally:
        for attr, _ in pairs:
            setattr(config, attr, saved_cfg[attr])
        for c, (maxsize, od, hits, misses) in zip(caches, saved):
            c.maxsize = maxsize
            c._od = OrderedDict(od)
            c.hits, c.misses = hits, misses
