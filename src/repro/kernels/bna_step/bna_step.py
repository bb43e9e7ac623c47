"""bna_step kernel — the batched BNA inner loop (core of Algorithm 1 at
batch scale, the matching analogue of coflow_merge).

One invocation performs one lock-step iteration of the filled-matrix BNA
decomposition for a whole (B, w, w) stack of demand matrices: the matched
demands are gathered through a one-hot of the current matching, the step
length is the three-term min of line 5 (matched demand, idle-sender slack
D - row, idle-receiver slack D - col), the transmissions are applied, and
the matched-edge invalidation mask for the host-side augmenting-path repair
is emitted.  Everything is elementwise/reduction int32 arithmetic — the
kernel is BIT-IDENTICAL to the numpy oracle (`ref.bna_step_ref`), which is
what lets `REPRO_BNA_BACKEND=pallas` keep plans byte-for-byte equal.

TPU mapping: grid over the B matrices ("parallel" — matrices are
independent), one (w, w) demand tile per step.  Every value stays 2-D:
sender-indexed state (row loads, the matching, and the per-sender outputs)
travels as (w, 1) columns, receiver-indexed state (col loads) as (1, w)
rows, and the per-matrix scalars (D and the step length) as (1, 1)
blocks reduced to true scalars in the body.  The gather is a one-hot
compare of the match column against a receiver iota followed by a
keepdims lane reduction, so no value is ever reshaped from 2-D to 3-D
(Mosaic cannot lay such a cast out).  The jitted wrapper below moves the
(B, w) state between the caller's layout and the kernel's.  Arithmetic
intensity is ~3 ops/byte over the w*w tile: memory-bound, like
coflow_merge; the roofline section of `benchmarks.roofline_report` reports
the memory term at K -> 1e5.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NO_MATCH = -1
_BIG = jnp.iinfo(jnp.int32).max


def _bna_step_kernel(d_ref, row_ref, col_ref, D_ref, match_ref,
                     t_ref, piece_ref, dn_ref, rown_ref, coln_ref,
                     Dn_ref, inv_ref):
    d = d_ref[...]                     # (w, w) int32
    row = row_ref[...]                 # (w, 1) per sender
    col = col_ref[...]                 # (1, w) per receiver
    Dv = jnp.min(D_ref[...])           # scalar
    match = match_ref[...]             # (w, 1) per sender

    r_ids = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    matched = match != _NO_MATCH
    onehot = (r_ids == match) & matched                      # (w, w)
    dm = jnp.sum(jnp.where(onehot, d, 0), axis=1, keepdims=True)
    real = matched & (dm > 0)                                # (w, 1)
    served = onehot & real                                   # (w, w)
    recv = jnp.max(served.astype(jnp.int32), axis=0, keepdims=True) > 0

    # step length as a scalar: full reductions, so it splats to any shape
    t = jnp.minimum(
        jnp.minimum(jnp.min(jnp.where(real, dm, _BIG)),
                    jnp.min(jnp.where(real, _BIG, Dv - row))),
        jnp.min(jnp.where(recv, _BIG, Dv - col)))

    dn = d - jnp.where(served, t, 0)
    rown = row - jnp.where(real, t, 0)
    coln = col - jnp.where(recv, t, 0)
    Dn = Dv - t

    dmn = dm - jnp.where(real, t, 0)
    colm = jnp.sum(jnp.where(onehot, coln, 0), axis=1, keepdims=True)
    invalid = matched & (dmn == 0) & ((rown >= Dn) | (colm >= Dn)) & (Dn > 0)

    t_ref[...] = jnp.full(t_ref.shape, t, jnp.int32)
    piece_ref[...] = jnp.where(real, match, _NO_MATCH)
    dn_ref[...] = dn
    rown_ref[...] = rown
    coln_ref[...] = coln
    Dn_ref[...] = jnp.full(Dn_ref.shape, Dn, jnp.int32)
    inv_ref[...] = invalid.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bna_step_padded(
    d: jax.Array,       # (B, w_pad, w_pad) int32
    row: jax.Array,     # (B, w_pad) int32
    col: jax.Array,
    D: jax.Array,       # (B, 1) int32
    match: jax.Array,   # (B, w_pad) int32
    *,
    interpret: bool,
):
    """One step over the stack; outputs in the caller's layout:
    (t (B, 1), piece, d', row', col', D' (B, 1), invalid (B, w))."""
    B, w, _ = d.shape
    i32 = jnp.int32

    def spec(*shape):
        return pl.BlockSpec((None,) + shape,
                            lambda b: (b,) + (0,) * len(shape))

    column, lanes, scalar = spec(w, 1), spec(1, w), spec(1, 1)
    t, piece, dn, rown, coln, Dn, inv = pl.pallas_call(
        _bna_step_kernel,
        grid=(B,),
        in_specs=[spec(w, w), column, lanes, scalar, column],
        out_specs=[scalar, column, spec(w, w), column, lanes, scalar, column],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, 1), i32),    # t
            jax.ShapeDtypeStruct((B, w, 1), i32),    # piece
            jax.ShapeDtypeStruct((B, w, w), i32),    # d'
            jax.ShapeDtypeStruct((B, w, 1), i32),    # row'
            jax.ShapeDtypeStruct((B, 1, w), i32),    # col'
            jax.ShapeDtypeStruct((B, 1, 1), i32),    # D'
            jax.ShapeDtypeStruct((B, w, 1), i32),    # invalid
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(d, row[:, :, None], col[:, None, :], D[:, :, None], match[:, :, None])
    return (t[:, :, 0], piece[:, :, 0], dn, rown[:, :, 0], coln[:, 0, :],
            Dn[:, :, 0], inv[:, :, 0])
