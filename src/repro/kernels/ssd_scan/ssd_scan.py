"""Mamba2 SSD chunked-scan kernel (state-space duality).

TPU mapping: grid = (batch, heads, chunks) with the chunk axis sequential
("arbitrary") so the inter-chunk state h (N, P) persists in VMEM scratch.
Per chunk of length L the kernel computes, all in f32 on MXU-aligned tiles:

  intra:  Y += ((C B^T) .* M) X      M_ij = exp(cum_i - cum_j) for i >= j
  inter:  Y += exp(cum_i) * (C_i h)
  state:  h  = exp(cum_L) h + (B .* exp(cum_L - cum))^T X

where cum is the in-chunk cumulative sum of log a. log-space segsum keeps
the decay products stable for long chunks. VMEM per step: L*P (x, y) +
2*L*N (b, c) + L*L (mask) + N*P (state) floats; with L=128, N=128, P<=256
that is < 1 MiB.

State groups (n_groups < heads) are expressed in the b/c index_maps, same
trick as GQA in flash_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, loga_ref, b_ref, c_ref, y_ref, h_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (L, P)
    la = loga_ref[0, :, 0].astype(jnp.float32)       # (L,)
    b = b_ref[0, :, 0, :].astype(jnp.float32)        # (L, N)
    c = c_ref[0, :, 0, :].astype(jnp.float32)        # (L, N)

    cum = jnp.cumsum(la)                              # (L,)
    # intra-chunk: masked decay matrix in log space
    seg = cum[:, None] - cum[None, :]                 # (L, L): sum_{j<k<=i} la_k
    L = la.shape[0]
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    mask = jnp.where(tri, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * mask
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    h = h_ref[...]                                    # (N, P)
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        c, h, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # state update for the next chunk
    wb = b * jnp.exp(cum[-1] - cum)[:, None]          # (L, N)
    h_ref[...] = jnp.exp(cum[-1]) * h + jax.lax.dot_general(
        wb, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_padded(
    x: jax.Array,      # (B, S, H, P), S % chunk == 0
    loga: jax.Array,   # (B, S, H)  log decay (<= 0)
    b: jax.Array,      # (B, S, G, N)
    c: jax.Array,      # (B, S, G, N)
    *,
    chunk: int,
    interpret: bool,
) -> jax.Array:
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    assert S % chunk == 0
    rep = H // G
    grid = (B, H, S // chunk)

    return pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bb, h, ic: (bb, ic, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bb, h, ic: (bb, ic, h)),
            pl.BlockSpec((1, chunk, 1, N), lambda bb, h, ic: (bb, ic, h // rep, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda bb, h, ic: (bb, ic, h // rep, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, P), lambda bb, h, ic: (bb, ic, h, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, loga, b, c)
