"""Mixture-of-Experts MLP with sort-based capacity dispatch.

TPU adaptation (DESIGN.md §5): no ragged ops — tokens are routed into
fixed-capacity per-expert buffers via an argsort permutation (MegaBlocks/
MaxText "dropping" style), computed **per batch row** so routing stays local
to the data shard (no cross-device all-to-all in the baseline; expert
parallelism over an explicit axis is a perf-iteration variant).

FLOPs are proportional to E·C = S·top_k·capacity_factor — i.e. faithful to
the *active* parameter count, which is what the roofline compares against.

**Expert share.** A layer holds ``n_experts`` of the router's
``n_router`` experts, router ids ``[expert_offset, expert_offset +
n_experts)``: it routes every token over all of them (published top-k,
weights renormalised over the top-k) and computes only its own experts'
part of the result. The other experts' part belongs to the chips that
hold them; on one chip it is simply absent (no exchange is made).

**Serving never drops a token** (``drop_free``): a buffer of ``S`` slots
per expert holds every token, since a token's top-k are distinct
experts, so a cached prefill plus an extend equals the full prefill
whatever ``capacity_factor`` the config carries for training.

A ``shared_d_ff`` expert (SwiGLU) runs on every token beside the routed
ones and is added to their result.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import KeyGen, cdiv, scaled_init
from repro.models.mlp import mlp


def init_moe(kg: KeyGen, cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict[str, Any]:
    m = cfg.moe
    d, f, e = cfg.d_model, cfg.d_ff, m.n_experts
    p = {
        "router": scaled_init(kg(), (d, m.n_router), d, jnp.float32),
        "gate": scaled_init(kg(), (e, d, f), d, dtype),
        "up": scaled_init(kg(), (e, d, f), d, dtype),
        "down": scaled_init(kg(), (e, f, d), f, dtype),
    }
    if m.shared_d_ff:
        fs = m.shared_d_ff
        p["shared_gate"] = scaled_init(kg(), (d, fs), d, dtype)
        p["shared_up"] = scaled_init(kg(), (d, fs), d, dtype)
        p["shared_down"] = scaled_init(kg(), (fs, d), fs, dtype)
    return p


def moe_capacity(seq: int, cfg: ModelConfig, drop_free: bool = False) -> int:
    """Slots per expert per batch row: ``seq`` when no token may drop,
    else the expected load ``seq * top_k / n_router`` times the
    capacity factor."""
    if drop_free:
        return seq
    m = cfg.moe
    return max(1, cdiv(int(seq * m.top_k * m.capacity_factor), m.n_router))


def moe_apply(params, x, cfg: ModelConfig, *, rng=None, moe_sharding=None,
              drop_free: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,d) -> (out (B,S,d), load-balance aux loss scalar).

    ``drop_free``: every token reaches each of its held top-k experts
    (serving); otherwise the capacity factor may drop tokens (training).

    ``moe_sharding``: optional (up_sharding, down_sharding) NamedShardings
    applied to the expert weights at USE time. Perf iteration (§Perf,
    mixtral train): with FSDP-stored expert weights (d over dp) GSPMD
    contracted over the sharded d and all-reduced 10 GiB (b,e,c,f) partial
    products per layer; constraining the weights to (experts, ·, tp) here
    forces the FSDP idiom instead — all-gather the (much smaller) weights
    once per layer, compute locally.
    """
    m = cfg.moe
    k = m.top_k
    c = moe_capacity(x.shape[1], cfg, drop_free)
    gate_w, up_w, down_w = params["gate"], params["up"], params["down"]
    ep_sharding, ep_split = None, 0
    if isinstance(moe_sharding, tuple) and moe_sharding[0] == "ep":
        # all-to-all expert parallelism with f-splitting (§Perf, mixtral):
        # e experts × m f-shards = tp "expert-shards"; dispatch moves to
        # expert-sharded layout via all-to-all, compute is fully local,
        # and the f-shard partials psum over groups of m.
        _, ep_sharding, ep_split = moe_sharding
    elif isinstance(moe_sharding, tuple):
        up_sh, down_sh = moe_sharding
        gate_w = jax.lax.with_sharding_constraint(gate_w, up_sh)
        up_w = jax.lax.with_sharding_constraint(up_w, up_sh)
        down_w = jax.lax.with_sharding_constraint(down_w, down_sh)

    with jax.named_scope("moe.router"):
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            params["router"])
        if rng is not None and m.router_jitter > 0:
            logits = logits + m.router_jitter * jax.random.normal(
                rng, logits.shape)
        probs = jax.nn.softmax(logits, axis=-1)  # (B,S,E)
        top_p, top_e = jax.lax.top_k(probs, k)  # (B,S,K)
        top_w = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    y = _routed(x, top_e, top_w, (gate_w, up_w, down_w), cfg, c,
                ep_sharding, ep_split)
    if m.shared_d_ff:
        with jax.named_scope("moe.shared"):
            y = y + mlp({"gate": params["shared_gate"],
                         "up": params["shared_up"],
                         "down": params["shared_down"]}, x)

    # ---- Switch-style load balance loss ------------------------------
    assign = jax.nn.one_hot(top_e[..., 0], m.n_router,
                            dtype=jnp.float32)  # top-1 fraction
    f_e = assign.mean(axis=(0, 1))
    p_e = probs.mean(axis=(0, 1))
    aux = m.n_router * jnp.sum(f_e * p_e) * m.load_balance_coef
    return y, aux


@jax.named_scope("moe.experts")
def _routed(x, top_e, top_w, weights, cfg: ModelConfig, c: int,
            ep_sharding, ep_split: int):
    """The held experts' part of the result: tokens sorted into ``c``
    slots per expert, each expert's SwiGLU, weighted sum back."""
    b, s, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    held = e != cfg.moe.n_router  # a share of the router's experts
    gate_w, up_w, down_w = weights
    # ---- sort-based dispatch, per batch row --------------------------
    sk = s * k
    e_flat = top_e.reshape(b, sk)
    if held:
        # router ids -> held ids; an expert held elsewhere sorts last, as e
        e_flat = e_flat - cfg.moe.expert_offset
        e_flat = jnp.where((e_flat >= 0) & (e_flat < e), e_flat, e)
    order = jnp.argsort(e_flat, axis=-1)  # stable
    sorted_e = jnp.take_along_axis(e_flat, order, axis=-1)
    # position within each expert's run of the sorted id list
    idx = jnp.arange(sk, dtype=jnp.int32)[None, :]
    is_start = jnp.concatenate(
        [jnp.ones((b, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=-1)
    run_start = jax.lax.cummax(jnp.where(is_start, idx, 0), axis=1)
    pos_in_expert = idx - run_start
    dropped = pos_in_expert >= c
    if held:
        dropped |= sorted_e == e
    dest = jnp.where(dropped, e * c, sorted_e * c + pos_in_expert)  # overflow bin

    tok_idx = order // k  # (B, SK) source token of each sorted (token,k) pair
    x_sorted = jnp.take_along_axis(x, tok_idx[..., None], axis=1)  # (B,SK,d)

    buf = jnp.zeros((b, e * c + 1, d), x.dtype)
    brow = jnp.arange(b, dtype=jnp.int32)[:, None]
    buf = buf.at[brow, dest].set(x_sorted, mode="drop")
    expert_in = buf[:, : e * c].reshape(b, e, c, d)

    # ---- per-expert SwiGLU -------------------------------------------
    if ep_split:
        ns, fm = ep_split, cfg.d_ff // ep_split
        xin = jnp.repeat(expert_in, ns, axis=1)  # (b, e*ns, c, d)
        if ep_sharding is not None:  # None = single-device math test
            xin = jax.lax.with_sharding_constraint(xin, ep_sharding)
        # weights (e,d,f) -> (e*ns, d, f/ns) expert-shards
        gw = gate_w.reshape(e, d, ns, fm).transpose(0, 2, 1, 3).reshape(
            e * ns, d, fm)
        uw = up_w.reshape(e, d, ns, fm).transpose(0, 2, 1, 3).reshape(
            e * ns, d, fm)
        dw = down_w.reshape(e, ns, fm, d).reshape(e * ns, fm, d)
        g = jnp.einsum("bEcd,Edf->bEcf", xin, gw)
        u = jnp.einsum("bEcd,Edf->bEcf", xin, uw)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        part = jnp.einsum("bEcf,Efd->bEcd", h, dw)
        expert_out = part.reshape(b, e, ns, c, d).sum(axis=2)
    else:
        g = jnp.einsum("becd,edf->becf", expert_in, gate_w)
        u = jnp.einsum("becd,edf->becf", expert_in, up_w)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        expert_out = jnp.einsum("becf,efd->becd", h, down_w)

    # ---- gather back + combine ---------------------------------------
    flat = jnp.concatenate(
        [expert_out.reshape(b, e * c, d), jnp.zeros((b, 1, d), x.dtype)], axis=1)
    y_sorted = jnp.take_along_axis(flat, dest[..., None], axis=1)  # (B,SK,d)
    y_flat = jnp.zeros((b, sk, d), x.dtype).at[brow, order].set(y_sorted)
    return (y_flat.reshape(b, s, k, d) *
            top_w[..., None].astype(x.dtype)).sum(axis=2)
