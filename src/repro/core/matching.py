"""Batched matching — multi-coflow BNA (Algorithm 1 across a whole batch).

Every scheduler's cold start runs BNA once per coflow, and the per-coflow
implementation (``core/bna.py``, the scalar reference) pays its Python/numpy
dispatch overhead per *iteration per coflow*.  :func:`bna_many` decomposes
many demand matrices at once instead:

1. **Support-restrict** each demand exactly as the scalar path does
   (`bna.support_restrict`), then **bucket** the resulting k x k matrices by
   padded width w (next power of two) and pack each bucket into a padded
   ``(K, w, w)`` int64 stack.  Padding ports carry zero load, so they are
   never tight, never real-matched, and constrain the step length only by
   ``D - 0 = D`` — never binding, because the step is always <= the minimum
   matched demand <= D.  The padded stack therefore decomposes to exactly
   the same pieces as the unpadded matrices.
2. Run the **filled-matrix decomposition in lock-step** across the bucket:
   the step-length computation (line 5 of Algorithm 1 in its filled-matrix
   form), the demand/row/col/D updates, and the matched-edge invalidation
   are vectorized over the whole active batch (one ``bna_step``), while the
   augmenting-path repair stays per-matrix (`bna._augment`, byte-identical
   adjacency) but touches only matrices whose matching was actually
   invalidated.  Matrices whose D hits zero leave the active set; the batch
   is compacted whenever more than half of it has drained.
3. Map the collected pieces back through the support remap
   (`bna.expand_pieces`).

The matrices are independent, so interleaving their iterations cannot change
any matrix's own step sequence: **pieces are bit-identical to the scalar
path** (``tests/test_matching.py`` property-tests this across the
width/dtype/zero-demand grid, and the 9x6 scenario matrix pins plan
identity).  The win is wall-clock only: per iteration, one batched step
replaces len(batch) scalar steps' worth of small-array numpy dispatch.

The batched step dispatches through the ``REPRO_BNA_BACKEND`` knob
(``core/backend.py``): ``numpy`` runs the in-place vectorized step below;
``pallas`` routes the same arithmetic through the ``kernels/bna_step``
Pallas kernel (Mosaic-compiled on a TPU, interpret mode elsewhere);
``auto`` picks pallas iff the backend is a TPU.  The two are bit-identical
(integer arithmetic, same formulas); a kernel failure raises.  A bucket
past the kernel's int32 range runs the numpy step, which is exact there.

The jit planning pipeline (``core/pipeline.py``, ``REPRO_PLAN_BACKEND``)
reuses this module's support-restrict/bucket/pack machinery and
``_bna_core_batch`` for buckets past its int32 range guard; its compiled
decomposition is a jnp mirror of :func:`bna_step_inplace` plus a vmapped
repair, proven (and tested) to produce the same per-lane step sequences.
"""
from __future__ import annotations

import numpy as np

from .bna import (_NO_MATCH, expand_pieces, support_restrict,
                  verify_bna_schedule)

__all__ = ["bna_many", "bna_step_inplace", "bucket_width"]

_BIG = np.iinfo(np.int64).max


def bucket_width(k: int) -> int:
    """Padded batch width for a k x k support-restricted demand: the next
    power of two, so mixed-width instances land in O(log m) buckets."""
    return 1 << max(k - 1, 0).bit_length()


def bna_many(
    demands: list[np.ndarray],
    validate: bool = False,
    force: str | None = None,
) -> list[list[tuple[int, np.ndarray]]]:
    """Decompose every demand in `demands`; element i is bit-identical to
    ``bna(demands[i])``.  `force` overrides the BNA backend for this call
    (None follows ``backend.config.bna_backend``)."""
    out: list[list[tuple[int, np.ndarray]] | None] = [None] * len(demands)
    buckets: dict[int, list[tuple[int, np.ndarray, np.ndarray | None,
                                  np.ndarray | None, int]]] = {}
    for i, dem in enumerate(demands):
        d_full = np.asarray(dem, dtype=np.int64)
        sub, rows_p, cols_p = support_restrict(d_full)
        if sub is None:
            out[i] = []
            continue
        w = bucket_width(sub.shape[0])
        buckets.setdefault(w, []).append(
            (i, sub, rows_p, cols_p, d_full.shape[0]))
    for w in sorted(buckets):
        items = buckets[w]
        pieces_lists = _bna_core_batch([it[1] for it in items], w, force)
        for (i, _sub, rows_p, cols_p, m_full), pieces in zip(items, pieces_lists):
            out[i] = pieces if rows_p is None else \
                expand_pieces(pieces, rows_p, cols_p, m_full)
            if validate:
                verify_bna_schedule(np.asarray(demands[i], dtype=np.int64),
                                    out[i])
    return out  # type: ignore[return-value]


# --------------------------------------------------------------------------
# batched core
# --------------------------------------------------------------------------

def _augment_py(start: int, k: int, dlist: list, rowlist: list,
                collist: list, Dv: int, msr: list, mrs: list) -> bool:
    """`bna._augment` on Python-native state.

    At batch widths (k <= ~64) the augmenting DFS is dispatch-bound, not
    compute-bound: per-element numpy access costs more than the comparison
    it performs.  This mirror runs the identical search — frontiers built
    in increasing receiver order when a sender is first reached (filtering
    receivers already visited at that moment, exactly like the scalar
    `np.flatnonzero(adj & ~visited)`), consumed with visited-skipping,
    alternating-path augmentation on the first free receiver — over plain
    lists, so the matchings it produces are identical and the constant is
    several times smaller."""
    visited = [False] * k
    parent_r: dict[int, int] = {}
    stack = [start]
    frontier: dict[int, list[int]] = {}
    pos: dict[int, int] = {}
    while stack:
        s = stack[-1]
        f = frontier.get(s)
        if f is None:
            ds = dlist[s]
            if rowlist[s] < Dv:
                f = [r for r in range(k)
                     if not visited[r] and (ds[r] > 0 or collist[r] < Dv)]
            else:
                f = [r for r in range(k) if not visited[r] and ds[r] > 0]
            frontier[s] = f
            pos[s] = 0
        found = False
        p = pos[s]
        while p < len(f):
            r = f[p]
            p += 1
            if visited[r]:
                continue
            visited[r] = True
            parent_r[r] = s
            nxt = mrs[r]
            if nxt == _NO_MATCH:
                pos[s] = p
                while True:   # augment along the alternating path to start
                    ps = parent_r[r]
                    prev_r = msr[ps]
                    msr[ps] = r
                    mrs[r] = ps
                    if ps == start:
                        return True
                    r = prev_r
            else:
                pos[s] = p
                stack.append(nxt)
                found = True
                break
        if not found:
            pos[s] = p
            stack.pop()
            frontier.pop(s, None)
    return False


def _initial_matching(d2: np.ndarray, row1: np.ndarray, col1: np.ndarray,
                      Dv: int, msr: np.ndarray, mrs: np.ndarray,
                      k: int) -> None:
    """Initial perfect matching on the filled graph of one matrix — the
    scalar `repair()` from an all-unmatched state, i.e. `_repair_one`
    with nothing to clear (augments senders in increasing order)."""
    _repair_one(d2, row1, col1, Dv, msr, mrs, k,
                np.zeros(k, dtype=bool))


def _repair_one(d2: np.ndarray, row1: np.ndarray, col1: np.ndarray, Dv: int,
                msr: np.ndarray, mrs: np.ndarray, k: int,
                bad: np.ndarray) -> None:
    """Scalar repair() for one matrix of the batch: clear the invalidated
    matched edges (`bad`, ascending sender order, exactly the scalar bad
    mask), then re-augment unmatched senders in increasing order."""
    dlist = d2[:k, :k].tolist()
    rowlist = row1[:k].tolist()
    collist = col1[:k].tolist()
    msr_l = msr[:k].tolist()
    mrs_l = mrs[:k].tolist()
    for s in np.flatnonzero(bad):
        r = msr_l[s]
        msr_l[s] = _NO_MATCH
        mrs_l[r] = _NO_MATCH
    for s in range(k):
        if msr_l[s] == _NO_MATCH:
            if not _augment_py(s, k, dlist, rowlist, collist, Dv,
                               msr_l, mrs_l):
                raise AssertionError(
                    "BNA invariant violated: no perfect matching")
    msr[:k] = msr_l
    mrs[:k] = mrs_l


def bna_step_inplace(
    d: np.ndarray,      # (L, w, w) int64, mutated
    row: np.ndarray,    # (L, w) int64, mutated
    col: np.ndarray,    # (L, w) int64, mutated
    D: np.ndarray,      # (L,) int64 (not mutated)
    match: np.ndarray,  # (L, w) int64 match_sr (not mutated)
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """One vectorized lock-step BNA iteration over the live batch,
    mutating d/row/col in place; returns ``(t, piece, D_new, invalid)``.

    This is the SINGLE numpy source of the step formulas: the numpy
    backend runs it directly, ``kernels/bna_step/ref.py`` wraps it on
    copies as the kernel oracle, and the Pallas kernel must stay
    bit-identical to it (all-integer arithmetic, so parity is equality).

    Formulas mirror the scalar ``_bna_core`` exactly: step length is the
    three-term min of line 5 in filled-matrix form (matched demands,
    idle-sender slack D - row, idle-receiver slack D - col); ``invalid``
    is the scalar repair()'s bad mask on the post-update state, masked to
    matrices still running (drained matrices get t == 0 and no repair)."""
    midx = np.maximum(match, 0)
    dm = np.take_along_axis(d, midx[:, :, None], axis=2)[:, :, 0]
    real = (match != _NO_MATCH) & (dm > 0)
    t = np.where(real, dm, _BIG).min(axis=1)
    t = np.minimum(t, np.where(~real, D[:, None] - row, _BIG).min(axis=1))
    recv = np.zeros(real.shape, dtype=bool)
    bi, si = np.nonzero(real)
    ri = midx[bi, si]
    recv[bi, ri] = True
    t = np.minimum(t, np.where(~recv, D[:, None] - col, _BIG).min(axis=1))
    piece = np.where(real, match, np.int64(_NO_MATCH))
    # transmit t units on every real matched edge
    d[bi, si, ri] -= t[bi]
    row -= t[:, None] * real
    col -= t[:, None] * recv
    D2 = D - t
    dm2 = np.take_along_axis(d, midx[:, :, None], axis=2)[:, :, 0]
    colm = np.take_along_axis(col, midx, axis=1)
    invalid = (match != _NO_MATCH) & (dm2 == 0) \
        & ((row >= D2[:, None]) | (colm >= D2[:, None])) \
        & (D2 > 0)[:, None]
    return t, piece, D2, invalid


def _resolve_step(force: str | None, B: int, w: int, D_max: int):
    """The pallas batched step for a (B, w, w) bucket whose effective sizes
    are at most `D_max`, per the REPRO_BNA_BACKEND dispatch (see
    backend.py); None runs the numpy step.  A bucket past the kernel's
    int32 range guards stays on the numpy step, exactly as
    ``coflow_merge.ops.padded_alphas`` keeps its alphas on the reference:
    that is an exactness guard, not a device fallback."""
    from .backend import resolve_bna_backend

    if resolve_bna_backend(force) != "pallas":
        return None
    # repro: allow(backend-dispatch): this IS the REPRO_BNA_BACKEND resolved dispatch site
    from repro.kernels.bna_step.ops import bna_step_batch, int32_exact

    return bna_step_batch if int32_exact(B, w, D_max) else None


def _bna_core_batch(
    subs: list[np.ndarray], w: int, force: str | None = None,
) -> list[list[tuple[int, np.ndarray]]]:
    """Decompose a bucket of support-restricted matrices (each k x k with
    bucket_width(k) == w) in lock-step.  Returns per-matrix pieces, each
    bit-identical to ``_bna_core`` on that matrix alone."""
    B = len(subs)
    ks_full = np.array([s.shape[0] for s in subs], dtype=np.int64)
    ks = ks_full.copy()
    d = np.zeros((B, w, w), dtype=np.int64)
    for i, s in enumerate(subs):
        k = s.shape[0]
        d[i, :k, :k] = s
    row = d.sum(axis=2)
    col = d.sum(axis=1)
    D = np.maximum(row.max(axis=1), col.max(axis=1))
    match_sr = np.full((B, w), _NO_MATCH, dtype=np.int64)
    match_rs = np.full((B, w), _NO_MATCH, dtype=np.int64)
    for i in range(B):
        _initial_matching(d[i], row[i], col[i], int(D[i]),
                          match_sr[i], match_rs[i], int(ks[i]))

    pieces_out: list[list[tuple[int, np.ndarray]]] = [[] for _ in range(B)]
    ids = np.arange(B, dtype=np.int64)
    # scalar guard: nnz + 2m + 4 iterations, slack 4m — take the bucket max
    guard = int((d > 0).sum(axis=(1, 2)).max(initial=0)) + 6 * w + 8
    step_pallas = _resolve_step(force, B, w, int(D.max(initial=0)))
    steps: list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]] = []
    it = 0
    while True:
        alive = D > 0
        if not alive.any():
            break
        it += 1
        if it > guard:
            raise AssertionError("batched BNA failed to terminate (bug)")

        if step_pallas is not None:
            t, piece, d, row, col, D, invalid = step_pallas(
                d, row, col, D, match_sr)
        else:
            t, piece, D, invalid = bna_step_inplace(d, row, col, D, match_sr)
        assert bool((t[alive] > 0).all()), "zero-length BNA step (bug)"
        steps.append((ids, t, piece, alive))

        finished = alive & (D == 0)
        if finished.any():
            match_sr[finished] = _NO_MATCH   # neutralize: no repair, t=0
            match_rs[finished] = _NO_MATCH
        for i in np.flatnonzero(invalid.any(axis=1)):
            _repair_one(d[i], row[i], col[i], int(D[i]),
                        match_sr[i], match_rs[i], int(ks[i]), invalid[i])

        live = D > 0
        n_live = int(live.sum())
        if n_live and n_live * 2 < d.shape[0]:
            # compact the batch (fresh arrays — recorded `ids` stay valid)
            d = d[live].copy()
            row = row[live].copy()
            col = col[live].copy()
            D = D[live].copy()
            match_sr = match_sr[live].copy()
            match_rs = match_rs[live].copy()
            ks = ks[live].copy()
            ids = ids[live].copy()

    for ids_a, t_a, piece_a, alive_a in steps:
        for j in np.flatnonzero(alive_a):
            i = int(ids_a[j])
            # slice the padded piece row back to the matrix's own width so
            # pieces are bit-identical to the scalar _bna_core output
            pieces_out[i].append(
                (int(t_a[j]), piece_a[j, : int(ks_full[i])].copy()))
    return pieces_out
