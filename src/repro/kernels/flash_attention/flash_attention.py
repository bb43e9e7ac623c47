"""Blocked GQA attention kernel (FlashAttention-style online softmax).

TPU mapping: grid = (batch, q_heads, q_blocks, k_blocks) with the k-block
axis sequential ("arbitrary") so the f32 accumulators live in VMEM scratch
across k steps. Block shapes are (block_q, head_dim) / (block_k, head_dim);
head_dim is padded to a multiple of 128 by ops.py so the (Bq x d) @ (d x Bk)
products land on MXU-aligned shapes. VMEM working set per step:
Bq*d (q) + 2*Bk*d (k, v) + Bq*Bk (scores) + Bq*d (acc) floats — with the
default 128/128 blocks and d<=256 this is well under 1 MiB.

GQA is expressed in the k/v index_maps (q head h reads kv head
h // (Hq // Hkv)) — no materialized K/V repetition anywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, block_q: int, block_k: int,
                 seq_k: int, causal_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (Bq, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (Bk, d)
    v = v_ref[0, 0].astype(jnp.float32)          # (Bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # mask: causal + key padding
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_k
    if causal:
        # queries are aligned to the END of the key sequence (decode/prefill
        # convention): query i attends keys <= i + causal_offset
        qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        mask = mask & (qpos + causal_offset >= kpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (Bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                       # (Bq, Bk)
    correction = jnp.exp(m_prev - m_new)         # (Bq, 1)
    l_ref[...] = l_ref[...] * correction + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "seq_k",
                     "causal_offset", "interpret"),
)
def flash_attention_padded(
    q: jax.Array,   # (B, Hq, Sq_pad, d_pad)
    k: jax.Array,   # (B, Hkv, Sk_pad, d_pad)
    v: jax.Array,   # (B, Hkv, Sk_pad, d_pad)
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    seq_k: int,     # true (unpadded) key length, for masking
    causal_offset: int,
    interpret: bool,
) -> jax.Array:
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Sq % block_q == 0 and Sk % block_k == 0
    group = Hq // Hkv
    grid = (B, Hq, Sq // block_q, Sk // block_k)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_k=seq_k, causal_offset=causal_offset)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
