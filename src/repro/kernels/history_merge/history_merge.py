"""Pallas TPU kernel for inference-time watch-history injection (paper §III-B).

This is the per-request hot spot of the paper's technique: merge the user's
*batch* watch history (daily snapshot, long window) with the *real-time*
event buffer (seconds-fresh, short window) into one model-ready history —
time-ordered, deduplicated by item (freshest occurrence wins, real-time
beats batch on ties), truncated to the feature length K.

TPU adaptation: no data-dependent shapes and no sort primitive. Both inputs
arrive as fixed-size padded buffers with validity flags; the merge is
reformulated as **pairwise rank computation**:

  rank(i)  = #{ j valid, non-duplicate : j strictly fresher than i }
  slot(i)  = K - 1 - rank(i)            (right-aligned, ascending time)
  keep(i)  = valid(i) ∧ ¬dup(i) ∧ rank(i) < K

over the concatenated N = L_batch + L_rt events — O(N²) boolean work on
(N, N) tiles, fully vectorized (VPU), followed by a one-hot (N, K) scatter
expressed as a masked integer reduction. N ≈ a few hundred, so N² ≈ 10⁵
lane-ops per request. Grid = (batch,).

Layout: Mosaic works on 2-D (sublane, lane) tiles, so the body never
concatenates or reshapes 1-D rows. The wrapper concatenates the two
buffers in XLA, pads N up to a lane multiple with invalid (inert) events,
and hands every event plane to the kernel twice — as a (1, N) row and as
an (N, 1) column. Pairwise planes are then plain broadcasts:
``F[j, i]`` = "event j is fresher than event i" with j on sublanes and i
on lanes, and its transpose is ``G = ~(F | eye)`` (a strict total order),
so every per-event quantity is a sublane or a lane reduction and no
transpose is ever needed.

Freshness total order: (ts, is_rt, buffer index) lexicographic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _merge_kernel(ir_ref, tr_ref, vr_ref, ic_ref, tc_ref, vc_ref,
                  oi_ref, ot_ref, ov_ref, *, lb: int, k: int):
    n = ir_ref.shape[-1]
    items_r, ts_r, valid_r = ir_ref[0], tr_ref[0], vr_ref[0] > 0  # (1, N)
    items_c, ts_c, valid_c = ic_ref[0], tc_ref[0], vc_ref[0] > 0  # (N, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)  # j
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)  # i
    rt_j, rt_i = row >= lb, col >= lb

    # F[j, i]: event j strictly fresher than event i (lexicographic)
    fresher = (ts_c > ts_r) | ((ts_c == ts_r) & (
        (rt_j & ~rt_i) | ((rt_j == rt_i) & (row > col))))
    # G[j, i] = F[i, j]: the order is total and strict
    staler = ~(fresher | (row == col))
    same_item = items_c == items_r

    one, zero = jnp.int32(1), jnp.int32(0)
    # dup(i): a fresher valid event of the same item exists
    dup_r = jnp.max(jnp.where(valid_c & same_item & fresher, one, zero),
                    axis=0, keepdims=True) > 0                    # (1, N)
    dup_c = jnp.max(jnp.where(valid_r & same_item & staler, one, zero),
                    axis=1, keepdims=True) > 0                    # (N, 1)
    alive_r = valid_r & ~dup_r
    rank_c = jnp.sum(jnp.where(alive_r & staler, one, zero),
                     axis=1, keepdims=True)                       # (N, 1)
    keep_c = valid_c & ~dup_c & (rank_c < k)
    slot_c = k - 1 - rank_c  # right-aligned: rank 0 (freshest) -> slot K-1

    # one-hot scatter as a masked sublane reduction (no dynamic indexing)
    slots = jax.lax.broadcasted_iota(jnp.int32, (n, k), 1)
    onehot = keep_c & (slot_c == slots)                           # (N, K)
    oi_ref[0] = jnp.sum(jnp.where(onehot, items_c, zero), axis=0,
                        keepdims=True)
    ot_ref[0] = jnp.sum(jnp.where(onehot, ts_c, zero), axis=0,
                        keepdims=True)
    ov_ref[0] = jnp.sum(jnp.where(onehot, one, zero), axis=0, keepdims=True)


def history_merge_pallas(batch_items, batch_ts, batch_valid,
                         rt_items, rt_ts, rt_valid, *, out_len: int,
                         interpret: bool = False):
    """All inputs (B, L_batch) / (B, L_rt) int32. Returns
    (items, ts, valid) each (B, out_len) int32, right-aligned ascending-time,
    deduplicated by item id (freshest kept, real-time wins ties)."""
    b, lb = batch_items.shape
    lr = rt_items.shape[1]
    k = out_len
    n = max(LANES, -(-(lb + lr) // LANES) * LANES)

    def events(bx, rx):
        # padded tail = invalid events: never kept, never anyone's dup
        x = jnp.concatenate([bx, rx], axis=1).astype(jnp.int32)
        return jnp.pad(x, ((0, 0), (0, n - lb - lr)))

    planes = [events(batch_items, rt_items), events(batch_ts, rt_ts),
              events(batch_valid, rt_valid)]
    rows = [p[:, None, :] for p in planes]   # (B, 1, N)
    cols = [p[:, :, None] for p in planes]   # (B, N, 1)
    row_spec = pl.BlockSpec((1, 1, n), lambda bb: (bb, 0, 0))
    col_spec = pl.BlockSpec((1, n, 1), lambda bb: (bb, 0, 0))
    out_spec = pl.BlockSpec((1, 1, k), lambda bb: (bb, 0, 0))
    outs = pl.pallas_call(
        functools.partial(_merge_kernel, lb=lb, k=k),
        grid=(b,),
        in_specs=[row_spec] * 3 + [col_spec] * 3,
        out_specs=[out_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((b, 1, k), jnp.int32)] * 3,
        interpret=interpret,
    )(*rows, *cols)
    return tuple(o[:, 0, :] for o in outs)
