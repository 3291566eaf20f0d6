"""Pallas TPU decode-attention kernel (single query vs. a long KV cache).

serve_step attention is memory-bound: one query token must stream the whole
ring-buffer cache (32 k – 512 k entries) from HBM. The kernel is a
flash-decode: grid = (batch, q_heads, W/block_k) with the K-block axis
sequential, online-softmax state in VMEM scratch, one (1, hd) output write
at the last block. Slot validity (ring buffers that are not yet full) is
an additive f32 bias streamed alongside K.

Arithmetic intensity is O(1) FLOP/byte, so the roofline term this kernel
moves is HBM bytes: K/V blocks are read exactly once, in bf16, with no
(B, H, W) score materialization in HBM (the XLA path materializes scores).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]  # (1, hd)
    k = k_ref[0, 0]  # (bk, hd)
    v = v_ref[0, 0]  # (bk, hd)
    bias = bias_ref[0]  # (1, bk)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (1, bk)
    s = s + bias

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_bhd(q, k, v, bias, *, scale: float | None = None,
                         block_k: int = 512, interpret: bool = False):
    """q (B, nq, 1, hd); k/v (B, nkv, W, hd); bias (B, W) f32 additive
    (0 = attendable, NEG_INF = masked). Returns (B, nq, 1, hd).

    The bias travels as (B, 1, W) so its block is (1, block_k) in the last
    two dims — full sublane extent, lane-aligned — which the TPU's block
    tiling rule accepts; a (1, block_k) block of a (B, W) array is not."""
    b, nq, one, hd = q.shape
    nkv, w = k.shape[1], k.shape[2]
    g = nq // nkv
    block_k = min(block_k, w)
    assert w % block_k == 0
    if scale is None:
        scale = hd ** -0.5

    grid = (b, nq, w // block_k)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda bb, h, ki: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bb, h, ki, g=g: (bb, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bb, h, ki, g=g: (bb, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bb, h, ki: (bb, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda bb, h, ki: (bb, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nq, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, bias[:, None, :])
