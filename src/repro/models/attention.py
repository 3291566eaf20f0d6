"""GQA attention: chunked causal prefill + ring-buffer KV-cache decode.

Design notes (TPU adaptation, see DESIGN.md §5/§6):

* The XLA path never materializes the full (S, S) score matrix: prefill
  scans over query chunks, bounding live memory at (chunk_q, S) fp32 scores
  per (batch, head) shard. The Pallas ``flash_attention`` kernel is the
  TPU-target implementation of the same contraction; ``impl="pallas"``
  routes through it (interpret=True on CPU in tests).
* GQA is computed in FLAT-head form: KV heads are repeated to n_heads
  before the contraction (``_repeat_kv``). Under tensor parallelism the
  repeat is a local per-shard slice (Megatron-style KV replication inside
  the TP group) — the grouped (nkv, g) factorization is NOT partitionable
  when nkv < tp and made GSPMD replicate 32k-seq score tensors. The Pallas
  kernels keep the grouped form (single-device VMEM tiling, where it IS
  the right shape).
* Sliding-window attention uses a **ring-buffer KV cache of capacity =
  window**; full attention uses capacity = max_seq. Keys are stored
  RoPE-rotated at write time, so ring overwrite needs no re-rotation.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import KeyGen, scaled_init, zeros
from repro.models.rope import apply_rope

NEG_INF = -1e30


def init_attention(kg: KeyGen, cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict[str, Any]:
    d, nq, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    p = {
        "wq": scaled_init(kg(), (d, nq, hd), d, dtype),
        "wk": scaled_init(kg(), (d, nkv, hd), d, dtype),
        "wv": scaled_init(kg(), (d, nkv, hd), d, dtype),
        "wo": scaled_init(kg(), (nq, hd, d), nq * hd, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros((nq, hd), dtype)
        p["bk"] = zeros((nkv, hd), dtype)
        p["bv"] = zeros((nkv, hd), dtype)
    return p


def _project_qkv(params, x, positions, cfg: ModelConfig):
    """x (B,S,d) -> q (B,S,nq,hd), k/v (B,S,nkv,hd); q,k RoPE-rotated
    unless the config has no position embedding."""
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.position_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _scale(cfg: ModelConfig) -> float:
    return cfg.attention_multiplier or cfg.head_dim_ ** -0.5


def _repeat_kv(k, g):
    """(B,S,nkv,hd) -> (B,S,nq,hd) by repeating each KV head g times.

    Flat-head layout on purpose: the grouped (nkv, g) factorization cannot
    be expressed to GSPMD when nkv < tp (it replicated 32k-seq score
    tensors — observed 54 GiB/device). With flat heads sharded over tp the
    repeat lowers to a local slice per shard (Megatron-style KV-head
    replication within the TP group).
    """
    if g == 1:
        return k
    return jnp.repeat(k, g, axis=2)


def _attend_chunk(q_chunk, k, v, mask, scale):
    """q_chunk (B,cq,nq,hd) · k/v (B,S,nq,hd) -> (B,cq,nq,hd).

    mask (B, cq, S) boolean: True = attendable.
    """
    # f32 accumulation WITHOUT materializing f32 copies of K in HBM (an
    # .astype(f32) on the output makes XLA upcast the operands instead —
    # observed as full-cache f32 conversions per decode step)
    scores = jnp.einsum("bqnh,bsnh->bnqs", q_chunk, k,
                        preferred_element_type=jnp.float32)
    scores = scores * scale
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnqs,bsnh->bqnh", probs.astype(v.dtype), v)
    return out


def _pad_heads(t, target: int):
    """Pad the head axis (B,S,H,hd) with zero heads up to ``target``.

    Perf iteration (EXPERIMENTS.md §Perf, llava/granite): head counts that
    don't divide tp (56, 24 vs 16) force head_dim-sharded attention whose
    score contraction psums (B,S,S)-sized tensors — padding to the next
    multiple of tp makes heads shardable. Zero q-heads produce garbage
    rows that are sliced off before the output projection; +tp/H extra
    attention FLOPs (<15%), zero extra parameters.
    """
    h = t.shape[2]
    if h == target:
        return t
    return jnp.pad(t, ((0, 0), (0, 0), (0, target - h), (0, 0)))


def attention_full(params, x, positions, cfg: ModelConfig, *,
                   valid: Optional[jnp.ndarray] = None,
                   prefix_kv: Optional[Dict[str, Any]] = None,
                   prefix_positions: Optional[jnp.ndarray] = None,
                   prefix_valid: Optional[jnp.ndarray] = None,
                   q_chunk: int = 512, head_pad_to: int = 0,
                   attn_sharding=None) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Causal (optionally sliding-window) self-attention over a full sequence.

    Returns (output (B,S,d), kv dict {"k","v"} each (B,S,nkv,hd)) — the kv
    dict seeds a decode cache after prefill.

    ``prefix_kv``: already-computed K/V of a cached prefix (B,Sp,nkv,hd) —
    the *incremental prefill* path used by inference-time feature injection:
    only the injected suffix is recomputed, queries attend to prefix+suffix.
    The returned kv dict covers prefix+suffix.
    """
    b, s, d = x.shape
    nkv, hd = cfg.n_kv_heads, cfg.head_dim_
    g = cfg.n_heads // nkv
    scale = _scale(cfg)
    q, k, v = _project_qkv(params, x, positions, cfg)

    qpos_full = positions  # (B,S) or (S,)
    if qpos_full.ndim == 1:
        qpos_full = jnp.broadcast_to(qpos_full[None, :], (b, s))
    kpos = qpos_full
    kvalid = valid if valid is not None else jnp.ones((b, s), bool)

    if prefix_kv is not None:
        sp = prefix_kv["k"].shape[1]
        k = jnp.concatenate([prefix_kv["k"], k], axis=1)
        v = jnp.concatenate([prefix_kv["v"], v], axis=1)
        ppos = (prefix_positions if prefix_positions is not None
                else jnp.broadcast_to(jnp.arange(sp, dtype=jnp.int32)[None], (b, sp)))
        kpos = jnp.concatenate([ppos, kpos], axis=1)
        pval = (prefix_valid if prefix_valid is not None
                else jnp.ones((b, sp), bool))
        kvalid = jnp.concatenate([pval, kvalid], axis=1)

    n_chunks = max(1, s // q_chunk) if s % q_chunk == 0 else -1
    if n_chunks == -1 or s <= q_chunk:
        # small / non-divisible sequences: single chunk
        q_chunk, n_chunks = s, 1

    nq = cfg.n_heads
    h_pad = nq
    if head_pad_to and nq % head_pad_to:
        h_pad = ((nq + head_pad_to - 1) // head_pad_to) * head_pad_to
    k_rep = _pad_heads(_repeat_kv(k, g), h_pad)
    v_rep = _pad_heads(_repeat_kv(v, g), h_pad)
    q = _pad_heads(q, h_pad)
    if attn_sharding is not None:
        q = jax.lax.with_sharding_constraint(q, attn_sharding)
        k_rep = jax.lax.with_sharding_constraint(k_rep, attn_sharding)
        v_rep = jax.lax.with_sharding_constraint(v_rep, attn_sharding)

    def body(carry, idx):
        del carry
        start = idx * q_chunk
        qc = jax.lax.dynamic_slice_in_dim(q, start, q_chunk, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(qpos_full, start, q_chunk, axis=1)
        mask = qp[:, :, None] >= kpos[:, None, :]  # causal
        if cfg.sliding_window:
            mask &= (qp[:, :, None] - kpos[:, None, :]) < cfg.sliding_window
        mask &= kvalid[:, None, :]
        out = _attend_chunk(qc, k_rep, v_rep, mask, scale)
        return None, out

    if n_chunks == 1:
        _, out = body(None, jnp.int32(0))
    else:
        _, outs = jax.lax.scan(body, None, jnp.arange(n_chunks, dtype=jnp.int32))
        # outs: (n_chunks, B, cq, H, hd) -> (B, S, H, hd)
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s, h_pad, hd)

    out = out[:, :, :nq]  # drop zero pad heads
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return y, {"k": k, "v": v}


# ----------------------------------------------------------------------
# Decode path: ring-buffer KV cache
# ----------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, capacity: int,
                  dtype=jnp.bfloat16) -> Dict[str, Any]:
    """One attention layer's cache. ``capacity`` = window size for SWA archs,
    max context otherwise. ``valid`` marks slots holding *real* tokens —
    left-padded prefills seed it False on pad slots (default all-True is
    correct for both fresh sessions, where the position logic gates, and
    the dry-run's notionally-full caches)."""
    nkv, hd = cfg.n_kv_heads, cfg.head_dim_
    return {
        "k": zeros((batch, capacity, nkv, hd), dtype),
        "v": zeros((batch, capacity, nkv, hd), dtype),
        "valid": jnp.ones((batch, capacity), bool),
    }


def cache_from_prefill(kv: Dict[str, Any], capacity: int,
                       valid: Optional[jnp.ndarray] = None) -> Dict[str, Any]:
    """Seed a decode cache from prefill K/V (keeps the trailing window if the
    prefill is longer than capacity). ``valid`` (B,S): prefill pad mask."""
    k, v = kv["k"], kv["v"]
    b, s = k.shape[:2]
    if valid is None:
        valid = jnp.ones((b, s), bool)
    else:
        # COMPACT per row (valid slots to the front, original order kept):
        # decode validity gates on ``slot <= pos`` and the decode position
        # can exceed the number of real entries after padded injection
        # segments — compaction guarantees every real entry sits below it.
        # (Attention is slot-order-agnostic: keys carry their RoPE rotation.)
        perm = jnp.argsort(~valid, axis=1, stable=True)
        k = jnp.take_along_axis(k, perm[:, :, None, None], axis=1)
        v = jnp.take_along_axis(v, perm[:, :, None, None], axis=1)
        valid = jnp.take_along_axis(valid, perm, axis=1)
    if s >= capacity:
        # ring layout: entry at slot (pos % capacity); after s tokens the
        # slots hold positions [s-capacity, s). Reconstruct that layout.
        shift = s % capacity
        return {"k": jnp.roll(k[:, s - capacity:], shift, axis=1),
                "v": jnp.roll(v[:, s - capacity:], shift, axis=1),
                "valid": jnp.roll(valid[:, s - capacity:], shift, axis=1)}
    pad = capacity - s
    return {
        "k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
        "valid": jnp.pad(valid, ((0, 0), (0, pad))),
    }


def _ring_write(cache_row, new_row, slot):
    """cache_row (W, nkv, hd), new_row (nkv, hd), slot scalar int32."""
    return jax.lax.dynamic_update_slice_in_dim(
        cache_row, new_row[None], slot, axis=0)


def attention_decode(params, x, pos, cache, cfg: ModelConfig,
                     ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One-token decode step.

    x (B,1,d); pos (B,) int32 — number of tokens already in context (the new
    token's absolute position); cache {"k","v"} (B,W,nkv,hd).
    Returns (out (B,1,d), updated cache).
    """
    b, one, d = x.shape
    assert one == 1
    nkv, hd = cfg.n_kv_heads, cfg.head_dim_
    g = cfg.n_heads // nkv
    w = cache["k"].shape[1]
    scale = _scale(cfg)

    q, k_new, v_new = _project_qkv(params, x, pos[:, None], cfg)
    slot = (pos % w).astype(jnp.int32)
    stored = cache.get("valid")
    if stored is None:
        stored = jnp.ones((b, w), bool)
    if jax.default_backend() == "cpu":
        # XLA CPU lowers the batched per-row dynamic-update (a scatter)
        # into a SEQUENTIAL while loop over rows, copying whole cache rows
        # per iteration — the dominant decode cost at serving batch sizes,
        # and a cross-row serialization that also defeats data-parallel
        # meshes. A one-hot blend writes identical values as one
        # vectorized op. TPU keeps the native scatter: its write is O(1)
        # per row while the blend would re-stream the whole cache.
        hit = (jnp.arange(w, dtype=jnp.int32)[None, :] == slot[:, None])
        k = jnp.where(hit[:, :, None, None], k_new[:, :1], cache["k"])
        v = jnp.where(hit[:, :, None, None], v_new[:, :1], cache["v"])
        stored = stored | hit
    else:
        k = jax.vmap(_ring_write)(cache["k"], k_new[:, 0], slot)
        v = jax.vmap(_ring_write)(cache["v"], v_new[:, 0], slot)
        stored = jax.vmap(
            lambda row, s: jax.lax.dynamic_update_slice_in_dim(
                row, jnp.ones((1,), bool), s, axis=0))(stored, slot)

    # validity: slot i holds a token iff i <= pos (ring: all slots once full)
    idx = jnp.arange(w, dtype=jnp.int32)[None, :]  # (1,W)
    valid = idx <= pos[:, None]
    # after ring wrap every slot is valid:
    valid = valid | (pos[:, None] >= w)
    valid &= stored  # left-padded prefill slots stay masked

    k_rep = _repeat_kv(k, g)  # (B,W,nq,hd) — local slice under TP
    v_rep = _repeat_kv(v, g)
    scores = jnp.einsum("bqnh,bsnh->bnqs", q, k_rep,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnqs,bsnh->bqnh", probs.astype(v.dtype), v_rep)
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return y, {"k": k, "v": v, "valid": stored}
