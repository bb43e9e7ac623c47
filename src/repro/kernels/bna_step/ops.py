"""Public wrapper for bna_step: int64 <-> int32 marshalling with an
overflow guard, padding to kernel tiles, dispatch (interpret on CPU).

Padding is semantics-transparent: padded matrices (batch axis) carry zero
demand and an empty matching, so their step length is 0 and their state is
a fixed point; padded ports (width axis) have zero load and match == -1, so
they are never real-matched and never bind the step length (their slack is
D - 0 = D >= t always).  The int32 narrowing is exact under the guard —
every input is bounded by the effective size, so all intermediates fit.
"""
from __future__ import annotations

import numpy as np

from .. import count_launch, default_interpret
from .bna_step import bna_step_padded

_I32_MAX = np.iinfo(np.int32).max


def _pad_batch(B: int) -> int:
    """The batch padded to a power of two (>= 8), so the shrinking active
    set revisits at most O(log B) compiled shapes."""
    return max(8, 1 << max(B - 1, 0).bit_length())


def _pad_lanes(w: int, interpret: bool) -> int:
    """Lanes padded to the VPU multiple (the chip's 128; 8 in interpret
    mode keeps the CPU tests cheap)."""
    lane = 8 if interpret else 128
    return max(lane, ((w + lane - 1) // lane) * lane)


def int32_exact(B: int, w: int, D_max: int,
                *, interpret: bool | None = None) -> bool:
    """Whether a (B, w, w) batch whose effective sizes are at most `D_max`
    passes :func:`bna_step_batch`'s int32 range guards — the check a
    caller makes once per bucket to keep it on the numpy step instead
    (B and D only shrink as the batch decomposes)."""
    if interpret is None:
        interpret = default_interpret()
    b_pad, w_pad = _pad_batch(B), _pad_lanes(w, interpret)
    return D_max < _I32_MAX and b_pad * w_pad * w_pad < _I32_MAX


def bna_step_batch(
    d: np.ndarray,      # (B, w, w) int64
    row: np.ndarray,    # (B, w) int64
    col: np.ndarray,    # (B, w) int64
    D: np.ndarray,      # (B,) int64
    match: np.ndarray,  # (B, w) int64
    *,
    interpret: bool | None = None,
) -> tuple[np.ndarray, ...]:
    """One batched BNA step through the Pallas kernel; numpy int64 in/out,
    bit-identical to ``ref.bna_step_ref`` on the same state."""
    if interpret is None:
        interpret = default_interpret()
    B, w, _ = d.shape
    if int(D.max(initial=0)) >= _I32_MAX:
        raise ValueError("demand too large for the int32 bna_step kernel "
                         f"(effective size {int(D.max())} >= 2^31-1); "
                         "use the numpy backend")
    b_pad = _pad_batch(B)
    w_pad = _pad_lanes(w, interpret)
    if b_pad * w_pad * w_pad >= _I32_MAX:
        raise ValueError(
            "batch too large for the int32 bna_step kernel "
            f"(padded element count {b_pad} * {w_pad}^2 >= 2^31-1); "
            "use the numpy backend")
    count_launch("bna_step")

    def pad2(a, fill=0):
        out = np.full((b_pad, w_pad), fill, dtype=np.int32)
        out[:B, :w] = a
        return out

    d32 = np.zeros((b_pad, w_pad, w_pad), dtype=np.int32)
    d32[:B, :w, :w] = d
    D32 = np.zeros((b_pad, 1), dtype=np.int32)
    D32[:B, 0] = D
    outs = bna_step_padded(
        d32, pad2(row), pad2(col), D32, pad2(match, fill=-1),
        interpret=interpret)
    t, piece, dn, rown, coln, Dn, inv = (np.asarray(o) for o in outs)
    return (
        t[:B, 0].astype(np.int64),
        piece[:B, :w].astype(np.int64),
        dn[:B, :w, :w].astype(np.int64),
        rown[:B, :w].astype(np.int64),
        coln[:B, :w].astype(np.int64),
        Dn[:B, 0].astype(np.int64),
        inv[:B, :w].astype(bool),
    )
