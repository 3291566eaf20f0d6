"""Pallas TPU kernel for the Mamba2 SSD (state-space dual) chunked scan.

Grid = (batch, n_head_blocks, n_chunks) with the chunk axis sequential; the
per-(batch, head-block) recurrent state (bh, hp, ds) f32 lives in VMEM
scratch and is carried across chunk steps. Each step computes, per head:

  intra-chunk:  y_ij = Σ_{j<=i} exp(Acum_i - Acum_j) (C_i·B_j) dt_j x_j
  inter-chunk:  y_i += C_i · (exp(Acum_i) * state_in)
  state update: state = exp(Acum_last) * state + Σ_j B_j ⊗ (dt_j decay_j x_j)

which is exactly the discrete SSD form of [arXiv:2405.21060] — the
quadratic intra-chunk term maps onto the MXU (chunk×chunk matmuls) while
the O(S) state pass stays in VMEM, never round-tripping HBM.

Layout: Mosaic works on 2-D (sublane, lane) tiles, and a block's last two
dims must be (8, 128)-aligned or whole. The wrapper therefore moves heads
ahead of the sequence (x, y as (b, nh, s, hp)), keeps the per-head
scalars A and D whole in SMEM, and passes dt twice: as a row plane
(b, nh, s) and as a column plane (b, nh, s, 1), whose last dim is whole.
The body loops over the block's heads so every operation is 2-D: dt's
row and column forms are read directly, and the chunk cumsum in each
form is a masked sublane or lane reduction — no transposes, no 3-D
contractions.

VMEM at (Q=256, bh=8, hp=64, ds=128): x and y blocks 8·256·128·4 ≈ 1 MB
each (hp lane-padded), state 8·64·128·4 ≈ 0.25 MB, a few (Q, Q) f32
temporaries of 0.25 MB per head — ~8 MB with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtr_ref, dtc_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
                y_ref, hout_ref, state_scr, *, chunk: int, bh: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = h0_ref[0].astype(jnp.float32)

    q = chunk
    B = b_ref[0].astype(jnp.float32)     # (Q, ds)
    C = c_ref[0].astype(jnp.float32)     # (Q, ds)
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril = row >= col
    scores = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (Q, Q) = C·Bᵀ

    for h in range(bh):
        a = a_ref[hi * bh + h].astype(jnp.float32)      # SMEM scalar
        d = d_ref[hi * bh + h].astype(jnp.float32)
        x = x_ref[0, h].astype(jnp.float32)             # (Q, hp)
        dt_r = dtr_ref[0, h:h + 1, :].astype(jnp.float32)  # (1, Q)
        dt_c = dtc_ref[0, h].astype(jnp.float32)        # (Q, 1)
        da_r, da_c = dt_r * a, dt_c * a
        # inclusive chunk cumsum, as a column and as a row
        cum_c = jnp.sum(jnp.where(tril, da_r, 0.0), axis=1, keepdims=True)
        cum_r = jnp.sum(jnp.where(row <= col, da_c, 0.0), axis=0,
                        keepdims=True)
        # chunk total, broadcast over lanes first: Mosaic broadcasts a
        # (1, 1) value along one tiled axis at a time
        cum_last = jnp.broadcast_to(jnp.sum(da_r, axis=1, keepdims=True),
                                    (1, state_scr.shape[-1]))  # (1, ds)

        # intra-chunk quadratic term
        L = jnp.where(tril, jnp.exp(cum_c - cum_r), 0.0)  # (Q, Q)
        xdt = x * dt_c                                     # (Q, hp)
        y = jnp.dot(scores * L, xdt, preferred_element_type=jnp.float32)

        # inter-chunk: contribution of the state entering this chunk
        state_in = state_scr[h]                            # (hp, ds)
        y += jnp.exp(cum_c) * jax.lax.dot_general(
            C, state_in, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (Q, hp)
        y += d * x
        y_ref[0, h] = y.astype(y_ref.dtype)

        # state update
        xdec = xdt * jnp.exp(cum_last[:, :1] - cum_c)      # (Q, hp)
        upd = jax.lax.dot_general(
            xdec, B, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (hp, ds)
        state_scr[h] = jnp.exp(cum_last) * state_in + upd

    @pl.when(ci == nc - 1)
    def _finalize():
        hout_ref[0] = state_scr[...]


def ssd_scan_pallas(x, dt, A, B, C, D, h0, *, chunk: int = 256,
                    block_heads: int = 8, interpret: bool = False):
    """x (b,s,nh,hp); dt (b,s,nh) f32; A (nh,); B/C (b,s,ds); D (nh,);
    h0 (b,nh,hp,ds) f32. Returns (y (b,s,nh,hp), h_final (b,nh,hp,ds))."""
    b, s, nh, hp = x.shape
    ds = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    bh = min(block_heads, nh)
    assert nh % bh == 0

    grid = (b, nh // bh, s // chunk)
    y, hout = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, bh=bh),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bh, chunk, hp), lambda bb, hi, ci: (bb, hi, ci, 0)),
            pl.BlockSpec((1, bh, chunk), lambda bb, hi, ci: (bb, hi, ci)),
            pl.BlockSpec((1, bh, chunk, 1), lambda bb, hi, ci: (bb, hi, ci, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, ds), lambda bb, hi, ci: (bb, ci, 0)),
            pl.BlockSpec((1, chunk, ds), lambda bb, hi, ci: (bb, ci, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bh, hp, ds), lambda bb, hi, ci: (bb, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bh, chunk, hp), lambda bb, hi, ci: (bb, hi, ci, 0)),
            pl.BlockSpec((1, bh, hp, ds), lambda bb, hi, ci: (bb, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, s, hp), x.dtype),
            jax.ShapeDtypeStruct((b, nh, hp, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bh, hp, ds), jnp.float32)],
        interpret=interpret,
    )(jnp.moveaxis(x, 2, 1), jnp.moveaxis(dt, 2, 1),
      jnp.moveaxis(dt, 2, 1)[..., None],
      A.astype(jnp.float32), B, C, D.astype(jnp.float32), h0)
    return jnp.moveaxis(y, 1, 2), hout
