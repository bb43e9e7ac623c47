"""coflow_merge kernel — the hot inner loop of the paper's DMA fix-up.

Given the (K, 2m) array of per-interval per-port packet-count *deltas*
(+1 where an edge activation enters a merged interval, -1 where it leaves),
compute alpha_t for every interval: the running per-port count, maxed over
ports. This is Steps 3-4 of DMA at scale: K is the number of merged
intervals (hundreds of thousands for the full Facebook-trace workload).

TPU mapping: grid over K-blocks, sequential ("arbitrary"), carrying the
running port counts (1, 2m) in VMEM scratch. Each step loads a
(block_k, 2m) delta tile into VMEM (2m padded to a 128 multiple by ops.py),
does a prefix sum down the time axis plus the carry, and writes the per-row
max.  Mosaic has no cumsum, so the prefix sum is a log-step (Hillis-Steele)
scan: log2(block_k) rounds of a sublane roll masked by a row iota, all in
exact int32 (every partial sum is a contiguous-range count, bounded by E).
Memory-bound by design: one pass over the delta array, arithmetic intensity
~2 ops/byte — the roofline benchmark for this kernel reports the memory
term, matching the analysis in EXPERIMENTS.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _prefix_sum_rows(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum down axis 0 of a (rows, lanes) int32 tile."""
    rows = x.shape[0]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    shift = 1
    while shift < rows:
        x = x + jnp.where(row_ids >= shift, pltpu.roll(x, shift, 0), 0)
        shift *= 2
    return x


def _merge_kernel(delta_ref, alpha_ref, carry_ref):
    ib = pl.program_id(0)

    @pl.when(ib == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    delta = delta_ref[...].astype(jnp.int32)          # (Bk, 2m)
    counts = carry_ref[...] + _prefix_sum_rows(delta)
    alpha_ref[...] = counts.max(axis=1, keepdims=True)
    carry_ref[...] = counts[-1:, :]


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def coflow_merge_padded(
    delta: jax.Array,   # (K_pad, ports_pad) int32, K_pad % block_k == 0
    *,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    K, ports = delta.shape
    assert K % block_k == 0
    grid = (K // block_k,)
    return pl.pallas_call(
        _merge_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_k, ports), lambda ib: (ib, 0))],
        out_specs=pl.BlockSpec((block_k, 1), lambda ib: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((K, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, ports), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(delta)
