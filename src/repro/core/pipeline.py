"""Jitted planning pipeline — the whole-instance compiled planning path.

``REPRO_PLAN_BACKEND=jit`` (``core/backend.py``) replaces the hot half of a
cold-start plan — the per-coflow BNA decomposition loop and its Python
run-length encoding — with fixed-shape, width-bucketed XLA programs:

1. **Padded instance representation.**  Every demand is support-restricted
   exactly like the python path (``bna.support_restrict``), bucketed by
   padded width w (``matching.bucket_width``), and packed into a
   ``(B_pad, w, w)`` int32 stack (B padded to the next power of two, all-zero
   dummy lanes).  Per (m, width-bucket) signature ``(B_pad, w, T_cap)`` one
   XLA program is compiled and kept in a bounded LRU (`compile_cache`,
   keyed like the BNA cache), so repeated plans — scenario sweeps, seed
   batches, online reschedules — reuse the compiled step.

2. **One compiled decomposition per bucket.**  The filled-matrix BNA runs as
   a ``lax.while_loop`` whose body is the batched step (a jnp mirror of
   ``matching.bna_step_inplace`` — same integer formulas, bit-identical) and
   a vmapped augmenting-path repair (a jittable pointer-scan reformulation
   of ``matching._augment_py``: frontiers are consumed in increasing
   receiver order with visited-skipping, so it visits the *same* receivers
   in the *same* order and produces the same matchings).  Step buffers are
   bounded by ``T_cap = pow2(max nnz + 6w + 8)`` — the python path's own
   termination guard — so shapes are static.

3. **Vectorized RLE.**  The per-step matchings come back as one
   ``(B, T_cap, w)`` stack; the edge intervals every scheduler consumes
   (``timeline.unit_from_coflow_plan``'s run-length encoding) are extracted
   with a single vectorized boundary scan over the whole bucket and cached
   per demand (`edge_cache`, same key discipline as the BNA cache).  Within
   a coflow the row order is canonical (sender, then start time) instead of
   the python path's set-iteration order; every consumer is order-
   independent within a coflow (events/alphas are counts, the FIFO
   attribution of ``timeline._decompose`` keys on (owner, jid, cid) with at
   most one row per coflow per (s, r, start), and packet backfill caps
   never bind inside a matching), so plans are bit-identical — the 9x6
   equivalence grid in ``tests/test_pipeline.py`` pins this.

4. **Jitted ordering inputs.**  The Algorithm 5 load vectors (and the
   geometric-grouping prefix sizes derived from them) come from one
   segment-sum program over the stacked demands instead of a per-job numpy
   walk; the dual loop itself stays on the host (float control flow), fed
   with bit-identical integer loads.

Everything here is *exact*: all device arithmetic is integer (int32, with a
host-side range guard that falls back to the numpy decomposition per bucket
— still bit-identical — when loads would overflow), so jit-vs-python parity
is equality, not tolerance.  The pieces produced here are stored in the
shared BNA cache: python- and jit-planned processes interoperate freely.
"""
from __future__ import annotations

import time
import warnings

import numpy as np

from repro.kernels import count_launch

from . import backend as _backend
from .bna import _NO_MATCH, expand_pieces, support_restrict
from .matching import _bna_core_batch, bucket_width
from .spans import span

__all__ = [
    "prefetch_demands",
    "decompose_pieces",
    "coflow_edges_rel",
    "instance_load_vectors",
    "edge_cache",
    "compile_cache",
    "pipeline_stats",
    "clear_pipeline_caches",
]

_INT32_MAX = int(np.iinfo(np.int32).max)

#: per-program cap on the (lanes, T_cap, w) int32 piece stack a compiled
#: decomposition returns (a v5e chip holds 16 GB of HBM)
_PIECES_BYTES = 1 << 30

#: interval programs (``decompose_pieces``) run fixed lanes: the (lanes,
#: w, w) int32 demand stack a program steps is at most this large, a
#: program has at most ``_FIXED_LANES_MAX`` lanes, and no bucket is
#: narrower than ``_FIXED_MIN_WIDTH`` (padding ports carry no load, so the
#: pieces stay)
_FIXED_STEP_BYTES = 1 << 21
_FIXED_LANES_MAX = 256
_FIXED_MIN_WIDTH = 8

#: demand key -> (t0, t1, s, r) int64 *relative* edge intervals (start = 0);
#: the jit-path replacement for re-running the Python RLE per plan.
edge_cache = _backend.LRUCache(_backend.config.bna_cache_size, "plan_edges")

#: (kind, *shape signature) -> AOT-compiled XLA executable.
compile_cache = _backend.LRUCache(64, "plan_compile")

# counters surfaced via backend.cache_stats()["plan"]
_counters = {"compiles": 0, "compile_s": 0.0, "batches": 0,
             "bucket_fallbacks": 0}

_warned_overflow = False


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def pipeline_stats() -> dict:
    return {"edges": edge_cache.stats(),
            "compile": {**compile_cache.stats(), **_counters}}


def clear_pipeline_caches(compiled: bool = False) -> None:
    """Drop cached edge intervals (and, optionally, compiled executables —
    kept by default: recompiling is the expensive part and executables are
    data-independent)."""
    edge_cache.clear()
    if compiled:
        compile_cache.clear()
        _counters["compiles"] = 0
        _counters["compile_s"] = 0.0
    _counters["batches"] = 0
    _counters["bucket_fallbacks"] = 0


# --------------------------------------------------------------------------
# compiled decomposition (one program per (B_pad, w, T_cap) signature)
# --------------------------------------------------------------------------

def _build_decompose(w: int, T_cap: int):
    """The jitted bucket decomposition: (d (B, w, w) int32, ks (B,) int32)
    -> (ts (B, T_cap), pieces (B, T_cap, w), D_final (B,)).

    Mirrors ``matching._bna_core_batch`` without compaction: drained lanes
    keep running as no-ops (t == 0, piece all -1, no repair), which cannot
    change any lane's own step sequence — exactly the lock-step argument
    the batched numpy path already relies on."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    I32 = jnp.int32
    BIG = I32(_INT32_MAX)

    def step(d, row, col, D, match):
        # jnp mirror of matching.bna_step_inplace (same integer formulas)
        midx = jnp.maximum(match, 0)
        dm = jnp.take_along_axis(d, midx[:, :, None], axis=2)[:, :, 0]
        real = (match != _NO_MATCH) & (dm > 0)
        t = jnp.where(real, dm, BIG).min(axis=1)
        t = jnp.minimum(t, jnp.where(~real, D[:, None] - row, BIG).min(axis=1))
        onehot = (midx[:, :, None] == jnp.arange(w, dtype=I32)[None, None, :]) \
            & real[:, :, None]
        recv = onehot.any(axis=1)
        t = jnp.minimum(t, jnp.where(~recv, D[:, None] - col, BIG).min(axis=1))
        piece = jnp.where(real, match, I32(_NO_MATCH))
        d = d - jnp.where(onehot, t[:, None, None], 0)
        row = row - t[:, None] * real
        col = col - t[:, None] * recv
        D2 = D - t
        dm2 = jnp.take_along_axis(d, midx[:, :, None], axis=2)[:, :, 0]
        colm = jnp.take_along_axis(col, midx, axis=1)
        invalid = (match != _NO_MATCH) & (dm2 == 0) \
            & ((row >= D2[:, None]) | (colm >= D2[:, None])) \
            & (D2 > 0)[:, None]
        return t, piece, d, row, col, D2, invalid

    def augment_one(do, start, d, row, col, Dv, msr, mrs, k):
        # Pointer-scan Kuhn DFS == matching._augment_py: a sender's frontier
        # is consumed in increasing receiver order skipping visited ones;
        # any admissible receiver below the pointer was already consumed
        # (hence visited), so re-scanning from the pointer sees exactly the
        # frozen frontier's unvisited remainder.  Senders are pushed at most
        # once per search (each non-start sender is reached only through its
        # unique matched receiver), so the pointer never needs resetting.
        #
        # The search loop only RECORDS the free receiver; the augmenting
        # walk runs once after it, in its own loop.  A nested walk inside
        # the search body would never terminate under vmap: batched
        # while_loops keep re-executing the body for lanes that already
        # finished (masking discards the result), and re-walking a matching
        # the augmentation already rewired follows a parent/match cycle.
        idx = jnp.arange(w, dtype=I32)

        def cond(c):
            return (c[1] > 0) & jnp.logical_not(c[6])

        def body(c):
            stack, depth, ptr, visited, parent_r, end_r, done = c
            s = stack[depth - 1]
            adm = (d[s] > 0) | ((row[s] < Dv) & (col < Dv))
            ok = (idx >= ptr[s]) & (idx < k) & jnp.logical_not(visited) & adm
            has = ok.any()
            r = jnp.argmax(ok).astype(I32)
            nxt = mrs[r]
            free = nxt == _NO_MATCH
            visited = jnp.where(has, visited.at[r].set(True), visited)
            parent_r = jnp.where(has, parent_r.at[r].set(s), parent_r)
            ptr = jnp.where(has, ptr.at[s].set(r + 1), ptr)
            push = has & jnp.logical_not(free)
            stack = jnp.where(push, stack.at[depth].set(nxt), stack)
            depth = jnp.where(has,
                              jnp.where(push, depth + 1, depth), depth - 1)
            end_r = jnp.where(has & free, r, end_r)
            done = done | (has & free)
            return stack, depth, ptr, visited, parent_r, end_r, done

        init = (jnp.zeros(w, I32).at[0].set(start),
                jnp.where(do, I32(1), I32(0)),
                jnp.zeros(w, I32),
                jnp.zeros(w, jnp.bool_),
                jnp.full(w, _NO_MATCH, I32),
                I32(_NO_MATCH), jnp.asarray(False))
        c = lax.while_loop(cond, body, init)
        parent_r, end_r, done = c[4], c[5], c[6]

        def wbody(wc):
            r_, msr_, mrs_, _ = wc
            ps = parent_r[r_]
            prev_r = msr_[ps]
            msr_ = msr_.at[ps].set(r_)
            mrs_ = mrs_.at[r_].set(ps)
            return prev_r, msr_, mrs_, ps != start

        _, msr, mrs, _ = lax.while_loop(
            lambda wc: wc[3], wbody, (end_r, msr, mrs, done))
        return msr, mrs

    augment_vm = jax.vmap(augment_one,
                          in_axes=(0, None, 0, 0, 0, 0, 0, 0, 0))

    def repair(d, row, col, D, msr, mrs, ks, need, bad):
        # matching._repair_one across lanes flagged by `need`: clear the
        # invalidated edges, then re-augment unmatched senders in order.
        badn = bad & need[:, None]
        clear_r = ((msr[:, :, None] == jnp.arange(w, dtype=I32)[None, None, :])
                   & badn[:, :, None]).any(axis=1)
        msr = jnp.where(badn, I32(_NO_MATCH), msr)
        mrs = jnp.where(clear_r, I32(_NO_MATCH), mrs)

        def aug_s(s, carry):
            msr, mrs = carry
            do = need & (s < ks) & (msr[:, s] == _NO_MATCH)
            return augment_vm(do, s.astype(I32), d, row, col, D, msr, mrs, ks)

        return lax.fori_loop(0, w, aug_s, (msr, mrs))

    def decompose(d, ks):
        B = d.shape[0]
        row = d.sum(axis=2)
        col = d.sum(axis=1)
        D = jnp.maximum(row.max(axis=1), col.max(axis=1))
        msr = jnp.full((B, w), _NO_MATCH, I32)
        mrs = jnp.full((B, w), _NO_MATCH, I32)
        msr, mrs = repair(d, row, col, D, msr, mrs, ks, D > 0,
                          jnp.zeros((B, w), jnp.bool_))
        ts0 = jnp.zeros((B, T_cap), I32)
        ps0 = jnp.full((B, T_cap, w), _NO_MATCH, I32)

        def cond(c):
            return (c[3] > 0).any() & (c[8] < T_cap)

        def body(c):
            d, row, col, D, msr, mrs, ts, pieces, i = c
            t, piece, d, row, col, D, invalid = step(d, row, col, D, msr)
            ts = ts.at[:, i].set(t)
            pieces = pieces.at[:, i, :].set(piece)
            msr, mrs = repair(d, row, col, D, msr, mrs, ks,
                              invalid.any(axis=1), invalid)
            return d, row, col, D, msr, mrs, ts, pieces, i + 1

        c = lax.while_loop(cond, body,
                           (d, row, col, D, msr, mrs, ts0, ps0, I32(0)))
        return c[6], c[7], c[3]

    return decompose


def _build_decompose_intervals(w: int, T_cap: int):
    """``_build_decompose``'s program under a name of its own, so the
    device trace keeps the nested step's time (``jit_decompose_intervals``)
    apart from the coflow decomposition's (``jit_decompose``)."""
    decompose = _build_decompose(w, T_cap)

    def decompose_intervals(d, ks):
        return decompose(d, ks)

    return decompose_intervals


def _get_compiled(key: tuple, builder, avals) -> object:
    """AOT-compile `builder()` for the given input avals, LRU-cached on
    `key` (the compile cache is what makes repeated plans pay tracing and
    XLA compilation once per shape signature, like the BNA value cache)."""
    found, fn = compile_cache.lookup(key)
    if found:
        return fn
    import jax

    t0 = time.perf_counter()
    fn = jax.jit(builder()).lower(*avals).compile()
    _counters["compiles"] += 1
    _counters["compile_s"] += time.perf_counter() - t0
    # repro: allow(cache-key): both call sites build `key` from the exact shape parameters that determine builder and avals, so the unkeyed params cannot vary under a fixed key
    compile_cache.store(key, fn)
    return fn


# --------------------------------------------------------------------------
# vectorized RLE over the step stacks
# --------------------------------------------------------------------------

def _rle_batch(ts: np.ndarray, pieces: np.ndarray):
    """Run-length encode a whole bucket's (B, T, w) piece stack at once.

    An edge (s, piece[b, t, s]) is active during step t; boundaries where
    the receiver changes open/close intervals.  Opens and closes alternate
    per (b, s), so pairing the i-th open with the i-th close (both emitted
    in (b, s, boundary) order by np.nonzero) reconstructs the intervals.
    Returns (s, r, t0, t1, offsets) with rows of lane b in
    ``[offsets[b], offsets[b+1])``, ordered by (sender, start time)."""
    B, T, w = pieces.shape
    times = np.zeros((B, T + 1), np.int64)
    np.cumsum(ts, axis=1, dtype=np.int64, out=times[:, 1:])
    Pt = np.full((B, w, T + 2), -1, np.int32)
    Pt[:, :, 1:T + 1] = pieces.transpose(0, 2, 1)
    change = Pt[:, :, 1:] != Pt[:, :, :-1]
    bo, so, to = np.nonzero(change & (Pt[:, :, 1:] != -1))
    bc, sc, tc = np.nonzero(change & (Pt[:, :, :-1] != -1))
    r = Pt[bo, so, to + 1].astype(np.int64)
    t0 = times[bo, to]
    t1 = times[bc, tc]
    offs = np.zeros(B + 1, np.int64)
    np.cumsum(np.bincount(bo, minlength=B), out=offs[1:])
    return so.astype(np.int64), r, t0, t1, offs


def _steps_to_lists(ts: np.ndarray, pieces: np.ndarray, ks: list[int]):
    """Per-lane python (duration, match) lists from the step stacks —
    bit-identical to the numpy batch's recorded pieces (an alive lane's
    steps are exactly its prefix of positive durations)."""
    out = []
    for i, k in enumerate(ks):
        n = int(np.count_nonzero(ts[i]))
        assert bool((ts[i, :n] > 0).all()), "jit step stack not a prefix"
        out.append([(int(ts[i, j]), pieces[i, j, :k].astype(np.int64))
                    for j in range(n)])
    return out


class _BucketOverflow(Exception):
    """Bucket loads exceed int32 — decompose it on the numpy path."""


def _decompose_bucket_jit(subs: list[np.ndarray], w: int, intervals: bool):
    """Decompose one width bucket through the compiled path; returns per
    matrix ``(pieces_restricted, (t0, t1, s, r) restricted rel-edges)``.

    The bucket runs in chunks of at most ``_chunk_lanes(w, T_cap)`` lanes,
    so no compiled program's ``(lanes, T_cap, w)`` piece stack outgrows
    ``_PIECES_BYTES``: a chip-size bucket (m = 150 lands half the trace's
    coflows at w = 256, with T_cap up to 32768, 32 MiB per lane) would
    otherwise need more HBM than one chip has.  Lanes are independent, so chunking cannot
    change any lane's steps.

    ``intervals`` marks the merged-interval matrices of
    ``timeline._decompose``: they run their own programs,
    ``jit_decompose_intervals``, and pad every chunk to one lane count per
    (w, T_cap), ``_fixed_lanes``, instead of the next power of two of its
    size, since they come in batches of every size and one program per
    (w, T_cap) (w at least ``_FIXED_MIN_WIDTH``) keeps a replan from
    meeting a new program's compile."""
    nnz = max(int((s > 0).sum()) for s in subs)
    T_cap = _pow2(nnz + 6 * w + 8)
    for s in subs:
        if max(int(s.sum(axis=1).max()), int(s.sum(axis=0).max())) \
                >= _INT32_MAX:
            raise _BucketOverflow
    lanes = _chunk_lanes(w, T_cap)
    if intervals:
        lanes = min(lanes, _fixed_lanes(w))
    out = []
    for lo in range(0, len(subs), lanes):
        out.extend(_decompose_chunk(subs[lo:lo + lanes], w, T_cap,
                                    intervals, lanes))
    return out


def _fixed_lanes(w: int) -> int:
    """Lanes of a fixed-lane program at width w: a power of two whose
    int32 demand stack fits ``_FIXED_STEP_BYTES``, at most
    ``_FIXED_LANES_MAX``."""
    per_lane = w * w * 4
    fit = 1 << max((_FIXED_STEP_BYTES // per_lane).bit_length() - 1, 0)
    return min(fit, _FIXED_LANES_MAX)


def _chunk_lanes(w: int, T_cap: int) -> int:
    """Largest power-of-two lane count whose int32 piece stack fits
    ``_PIECES_BYTES`` (at least one lane)."""
    per_lane = T_cap * w * 4
    return 1 << max((_PIECES_BYTES // per_lane).bit_length() - 1, 0)


def _decompose_chunk(subs: list[np.ndarray], w: int, T_cap: int,
                     intervals: bool, lanes: int):
    B = len(subs)
    B_pad = lanes if intervals else _pow2(B)
    d = np.zeros((B_pad, w, w), np.int32)
    ks = np.zeros(B_pad, np.int32)
    for i, s in enumerate(subs):
        k = s.shape[0]
        d[i, :k, :k] = s
        ks[i] = k

    import jax

    avals = (jax.ShapeDtypeStruct((B_pad, w, w), np.int32),
             jax.ShapeDtypeStruct((B_pad,), np.int32))
    if intervals:
        fn = _get_compiled(("bna_intervals", B_pad, w, T_cap),
                           lambda: _build_decompose_intervals(w, T_cap),
                           avals)
        count_launch("decompose_intervals")
    else:
        fn = _get_compiled(("bna", B_pad, w, T_cap),
                           lambda: _build_decompose(w, T_cap), avals)
        count_launch("decompose")
    ts, pieces, D_end = (np.asarray(x) for x in fn(d, ks))
    if D_end.any():
        raise AssertionError("jitted BNA failed to terminate (bug)")
    klist = [s.shape[0] for s in subs]
    plists = _steps_to_lists(ts[:B], pieces[:B], klist)
    so, r, t0, t1, offs = _rle_batch(ts[:B], pieces[:B])
    rels = [(t0[offs[i]:offs[i + 1]], t1[offs[i]:offs[i + 1]],
             so[offs[i]:offs[i + 1]], r[offs[i]:offs[i + 1]])
            for i in range(B)]
    return list(zip(plists, rels))


def _decompose_bucket_py(subs: list[np.ndarray], w: int):
    """int32-overflow fallback: the numpy batched decomposition (the very
    code the jit path mirrors, so still bit-identical) + python RLE."""
    from .timeline import bna_pieces_to_edge_intervals

    global _warned_overflow
    if not _warned_overflow:
        _warned_overflow = True
        warnings.warn(
            "jit planning pipeline: bucket loads exceed int32; decomposing "
            "on the numpy path (results are identical)", RuntimeWarning)
    _counters["bucket_fallbacks"] += 1
    out = []
    # the bucket is past the int32 range, so the pallas step's own guard
    # would refuse it as well
    for plist in _bna_core_batch(subs, w, force="numpy"):
        ei = bna_pieces_to_edge_intervals(plist, 0)
        out.append((plist, (ei.t0, ei.t1, ei.s, ei.r)))
    return out


def _plan_decompositions(demands: list[np.ndarray]):
    """(pieces, rel_edges) per demand: pieces are full-m (duration, match)
    lists bit-identical to ``bna.bna``; rel_edges are (t0, t1, s, r) int64
    edge intervals of the coflow's isolated schedule anchored at 0."""
    with span("plan.decompose", coflows=len(demands)):
        _counters["batches"] += 1
        return _decompose_buckets(demands, intervals=False)


def _decompose_buckets(demands: list[np.ndarray], intervals: bool):
    """``_plan_decompositions``' work, bucket by bucket; ``intervals``: see
    ``_decompose_bucket_jit``."""
    out_p: list = [None] * len(demands)
    out_e: list = [None] * len(demands)
    buckets: dict[int, list] = {}
    for i, dem in enumerate(demands):
        d_full = np.asarray(dem, dtype=np.int64)
        sub, rows_p, cols_p = support_restrict(d_full)
        if sub is None:
            z = np.zeros(0, np.int64)
            out_p[i] = []
            out_e[i] = (z, z.copy(), z.copy(), z.copy())
            continue
        w = bucket_width(sub.shape[0])
        if intervals:
            w = max(w, _FIXED_MIN_WIDTH)
        buckets.setdefault(w, []).append(
            (i, sub, rows_p, cols_p, d_full.shape[0]))
    for w in sorted(buckets):
        items = buckets[w]
        subs = [it[1] for it in items]
        try:
            res = _decompose_bucket_jit(subs, w, intervals)
        except _BucketOverflow:
            res = _decompose_bucket_py(subs, w)
        for (i, _sub, rows_p, cols_p, m_full), (plist, rel) in \
                zip(items, res):
            if rows_p is None:
                out_p[i] = plist
                out_e[i] = rel
            else:
                out_p[i] = expand_pieces(plist, rows_p, cols_p, m_full)
                t0, t1, ss, rr = rel
                out_e[i] = (t0, t1, rows_p[ss], cols_p[rr])
    return out_p, out_e


def decompose_pieces(demands: list[np.ndarray]) -> list:
    """Full-m BNA pieces per merged-interval matrix through the compiled
    buckets, bit-identical to ``bna.bna``: the interval programs, one per
    (w, T_cap), and no ``plan.decompose`` span (the caller's
    ``plan.nested_decompose`` holds the time); nothing is cached here (the
    caller, ``backend.bna_pieces_many``, keeps the BNA cache)."""
    return _decompose_buckets([np.asarray(d, np.int64) for d in demands],
                              intervals=True)[0]


# --------------------------------------------------------------------------
# cache-facing entry points
# --------------------------------------------------------------------------

def prefetch_demands(demands) -> None:
    """Warm BOTH the shared BNA cache and the edge cache for every demand in
    one width-bucketed compiled sweep — the jit analogue of
    ``backend.prefetch_bna``, with the same batching/thrash guards."""
    cfg = _backend.config
    if not cfg.bna_batch or cfg.bna_cache_size <= 0:
        return
    ds = [np.asarray(d) for d in demands]
    if not ds:
        return
    edge_cache.maxsize = cfg.bna_cache_size
    _backend.bna_cache.maxsize = cfg.bna_cache_size
    keys = [_backend._bna_key(d) for d in ds]
    if len(set(keys)) > cfg.bna_cache_size:
        return
    miss_keys: list = []
    miss_demands: list = []
    seen: set = set()
    for key, dem in zip(keys, ds):
        if key in seen:
            continue
        seen.add(key)
        e_hit, _ = edge_cache.lookup(key)
        p_hit, _ = _backend.bna_cache.lookup(key)
        if e_hit and p_hit:
            continue
        miss_keys.append(key)
        miss_demands.append(dem)
    if not miss_demands:
        return
    pieces_list, edges_list = _plan_decompositions(miss_demands)
    for key, p, e in zip(miss_keys, pieces_list, edges_list):
        _backend.bna_cache.store(key, p)
        edge_cache.store(key, e)


def coflow_edges_rel(demand: np.ndarray):
    """(t0, t1, s, r) relative edge intervals of `demand`'s BNA schedule
    (start = 0), memoized on the BNA key.  The arrays are shared across
    callers and must be treated as read-only (like cached pieces)."""
    dem = np.asarray(demand)
    key = _backend._bna_key(dem)
    edge_cache.maxsize = _backend.config.bna_cache_size
    found, rel = edge_cache.lookup(key)
    if found:
        return rel
    pieces_list, edges_list = _plan_decompositions(
        [np.asarray(dem, np.int64)])
    rel = edges_list[0]
    edge_cache.store(key, rel)
    if not _backend.bna_cache.lookup(key)[0]:
        _backend.bna_cache.store(key, pieces_list[0])
    return rel


# --------------------------------------------------------------------------
# jitted ordering inputs (Algorithm 5 load vectors / grouping prefix sizes)
# --------------------------------------------------------------------------

def _build_loads(m: int, n_pad: int):
    import jax.numpy as jnp

    def loads(dstack, seg):
        rows = dstack.sum(axis=2)
        cols = dstack.sum(axis=1)
        out = jnp.zeros((n_pad + 1, 2 * m), jnp.int32)
        out = out.at[seg, :m].add(rows).at[seg, m:].add(cols)
        return out[:n_pad]

    return loads


def instance_load_vectors(instance) -> np.ndarray | None:
    """(n, 2m) float64 per-job aggregate load vectors — the jitted
    segment-sum mirror of ``ordering.job_load_vectors`` (integer sums, so
    values are bit-identical).  None when the instance's total demand would
    overflow int32 (callers fall back to the host path)."""
    jobs = instance.jobs
    m = instance.m
    n = len(jobs)
    if n == 0 or m == 0:
        return np.zeros((n, 2 * m), dtype=np.float64)
    if instance.total_demand() >= _INT32_MAX:
        return None
    dems = [c.demand for j in jobs for c in j.coflows]
    C = len(dems)
    if C == 0:
        return np.zeros((n, 2 * m), dtype=np.float64)
    C_pad = _pow2(C)
    n_pad = _pow2(n)
    dstack = np.zeros((C_pad, m, m), np.int32)
    seg = np.full(C_pad, n_pad, np.int32)
    i = 0
    for k, j in enumerate(jobs):
        for c in j.coflows:
            dstack[i] = c.demand
            seg[i] = k
            i += 1

    import jax

    avals = (jax.ShapeDtypeStruct((C_pad, m, m), np.int32),
             jax.ShapeDtypeStruct((C_pad,), np.int32))
    fn = _get_compiled(("loads", C_pad, m, n_pad),
                       lambda: _build_loads(m, n_pad), avals)
    count_launch("loads")
    return np.asarray(fn(dstack, seg))[:n].astype(np.float64)
