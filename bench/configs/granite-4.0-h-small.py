"""granite-4.0-h-small's own reference and work counts (cell.config_code).

The reference is the plain float32 forward pass of the configuration as
served (``bench/configs/granite-4.0-h-small.json``): one period of ten
layers (Mamba-2 mixers, one NoPE GQA mixer at ``attn_offset``), an MoE
block on every layer of which this chip holds experts ``[expert_offset,
expert_offset + n_experts)`` of the router's ``router_experts`` plus the
shared expert, Granite's four multipliers, the vocabulary slice. Per
layer, as ``GraniteMoeHybridDecoderLayer`` computes it:

    x = x + residual_multiplier * mixer(rms(x))
    x = x + residual_multiplier * (experts(rms(x)) + shared(rms(x)))

with the embedding times ``embedding_multiplier``, attention scores
times ``attention_multiplier``, no rotation of queries or keys, and the
logits divided by ``logits_scaling``. The router takes the top-k of all
``router_experts`` logits and a softmax over those k; an expert held on
another chip of the deployment contributes nothing here, in the program
and in the reference alike. The Mamba-2 mixer is the per-token SSD
recurrence of bench/reference.py with the gated RMSNorm. Every matrix
product goes through ``reference._mm``, so ``mode`` means what it means
for every configuration. No cache, padding mask, capacity or chunking:
every token reaches each of its held top-k experts.

Work counts follow bench/work.py's conventions (weights read once a
call, state read and written at float32, 2 operations a multiply-add,
logits of ``vocab_size`` rows counted where they are read), with three
kinds of layer: the Mamba-2 mixers count as work.py counts them, the
attention mixer adds its keys and values over the context, and the MoE
block counts its router at full width, the shared expert, and the routed
experts at their expected share: a token's top-k land on held experts
``top_k * n_experts / router_experts`` times on average. Routed expert
*weights* are read only for the held experts a call reaches: with ``n``
tokens routed independently, an expert is reached with probability
``1 - (1 - top_k / router_experts) ** n``, so a call is expected to read
``n_experts * (1 - (1 - top_k / router_experts) ** n)`` experts a layer
(6.3 of 9 for a decode step of 8 rows, all 9 for an inject of 128
tokens). Counting all held experts would put the decode step's least
time above what a chip that skips unreached experts could take. A slate
reads its float32 weights once and the later steps a bfloat16 copy
(``slate``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench import work
from bench.reference import HI, _conv, _mm, _rms

BYTES = work.BYTES


# ----------------------------------------------------------------------
# The reference
# ----------------------------------------------------------------------

def _mamba(h, p, cfg, mode):
    """The Mamba-2 mixer's output for normed input ``h`` (k, L, d)."""
    s = cfg.ssm
    nh, hp = cfg.d_inner // s.head_dim, s.head_dim
    z = _mm("bld,de->ble", h, p["wz"], mode)
    xr = jax.nn.silu(_conv(_mm("bld,de->ble", h, p["wx"], mode),
                           p["conv_x"], p["conv_bias_x"]))
    B = jax.nn.silu(_conv(_mm("bld,de->ble", h, p["wB"], mode),
                          p["conv_B"], p["conv_bias_B"]))
    C = jax.nn.silu(_conv(_mm("bld,de->ble", h, p["wC"], mode),
                          p["conv_C"], p["conv_bias_C"]))
    dt = jax.nn.softplus(_mm("bld,dh->blh", h, p["wdt"], mode)
                         + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    k, L = h.shape[:2]
    xh = xr.reshape(k, L, nh, hp)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * A)[:, :, None, None] * state
                 + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
        y = jnp.einsum("khps,ks->khp", state, c_t, precision=HI)
        return state, y + p["D"][None, :, None] * x_t

    state0 = jnp.zeros((k, nh, hp, s.d_state), jnp.float32)
    tmaj = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, state0, (tmaj(xh), tmaj(dt), tmaj(B), tmaj(C)))
    y = jnp.moveaxis(y, 0, 1).reshape(k, L, -1)
    g = _rms(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
    return _mm("ble,ed->bld", g, p["out_proj"], mode)


def _attention(h, p, cfg, mode):
    """Causal GQA without positions, scores times attention_multiplier."""
    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = _mm("bld,dnh->blnh", h, p["wq"], mode)
    k = jnp.repeat(_mm("bld,dnh->blnh", h, p["wk"], mode), nq // nkv, 2)
    v = jnp.repeat(_mm("bld,dnh->blnh", h, p["wv"], mode), nq // nkv, 2)
    scale = cfg.attention_multiplier or hd ** -0.5
    s = _mm("bqnh,bknh->bnqk", q, k, mode) * scale
    L = h.shape[1]
    causal = jnp.tril(jnp.ones((L, L), bool))
    prob = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bnqk,bknh->bqnh", prob, v, mode)
    return _mm("bqnh,nhd->bqd", o, p["wo"], mode)


def _swiglu(h, gate, up, down, mode):
    g = jax.nn.silu(_mm("bld,df->blf", h, gate, mode))
    return _mm("blf,fd->bld", g * _mm("bld,df->blf", h, up, mode), down,
               mode)


def _moe(h, p, cfg, mode):
    """The held experts' part of the routed result plus the shared
    expert. Router: top-k of all router logits, softmax over those k."""
    m = cfg.moe
    logits = _mm("bld,de->ble", h, p["router"], mode)
    top, ids = jax.lax.top_k(logits, m.top_k)
    w = jax.nn.softmax(top, axis=-1)
    held = jnp.arange(m.n_experts) + m.expert_offset
    # (k, L, n_experts): each held expert's weight, 0 where not chosen
    cw = jnp.sum(jnp.where(ids[..., None] == held, w[..., None], 0.0), -2)
    g = jax.nn.silu(_mm("bld,edf->blef", h, p["gate"], mode))
    u = _mm("bld,edf->blef", h, p["up"], mode)
    y = _mm("blef,efd->bld", g * u * cw[..., None], p["down"], mode)
    if m.shared_d_ff:
        y = y + _swiglu(h, p["shared_gate"], p["shared_up"],
                        p["shared_down"], mode)
    return y


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def logits_at(params, tokens, pos, *, cfg, mode="highest"):
    """tokens (k, L) int32, right-padded; pos (k, T) positions to read.
    Returns (k, T, vocab_size) float32 logits of the next token after
    each position."""
    table = params["embed"]["table"]
    x = table[tokens] * cfg.embedding_multiplier
    blocks = params["blocks"]
    period = len(blocks)
    rm, eps = cfg.residual_multiplier, cfg.norm_eps
    for i, kind in enumerate(cfg.layer_kinds()):
        lp = jax.tree.map(lambda a: a[i // period], blocks[f"pos{i % period}"])
        # a layer's weights (and their rounded copies in a lower mode)
        # are taken up only after the layer before it is done, so one
        # layer's copies are live at a time
        x, lp = jax.lax.optimization_barrier((x, lp))
        h = _rms(x, lp["norm1"]["scale"], eps)
        mix = (_attention(h, lp["attn"], cfg, mode) if kind == "attn"
               else _mamba(h, lp["ssm"], cfg, mode))
        x = x + rm * mix
        h = _rms(x, lp["norm2"]["scale"], eps)
        x = x + rm * _moe(h, lp["moe"], cfg, mode)
    x = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    x = _rms(x, params["final_norm"]["scale"], eps)
    return (_mm("btd,vd->btv", x, table[:cfg.vocab_size], mode)
            / cfg.logits_scaling)


# ----------------------------------------------------------------------
# Work counts
# ----------------------------------------------------------------------

def _ssm_cfg(cfg):
    """The Mamba-2 mixers' counts are work.py's for an SSM stack."""
    return dataclasses.replace(cfg, family="ssm", n_layers=1, moe=None)


def _kinds(cfg):
    kinds = cfg.layer_kinds()
    return kinds.count("ssm"), kinds.count("attn")


def _attn_params(cfg) -> int:
    return cfg.d_model * cfg.head_dim_ * (2 * cfg.n_heads
                                          + 2 * cfg.n_kv_heads)


def _expert_params(cfg) -> int:
    return 3 * cfg.d_model * cfg.d_ff


def _moe_fixed_params(cfg) -> int:
    """An MoE block's weights read whatever the routing: the router at
    its full width and the shared expert."""
    m = cfg.moe
    return cfg.d_model * m.n_router + 3 * cfg.d_model * m.shared_d_ff


def experts_reached(cfg, tokens: int) -> float:
    """Held experts a call of ``tokens`` routed tokens is expected to
    reach, per layer."""
    m = cfg.moe
    return m.n_experts * (1.0 - (1.0 - m.top_k / m.n_router) ** tokens)


def weight_bytes(cfg, tokens: int) -> float:
    """Weights a call of ``tokens`` tokens reads: every layer's mixer,
    norms, router and shared expert, the reached held experts, the final
    norm and one vocab_size x d table."""
    n_ssm, n_attn = _kinds(cfg)
    d = cfg.d_model
    # work.layer_params of an SSM layer holds its mixer and one norm
    mixers = (n_ssm * work.layer_params(_ssm_cfg(cfg))
              + n_attn * (_attn_params(cfg) + d))
    per_layer = (_moe_fixed_params(cfg) + d
                 + experts_reached(cfg, tokens) * _expert_params(cfg))
    return BYTES * (mixers + cfg.n_layers * per_layer + d
                    + cfg.vocab_size * d)


def _moe_token_flops(cfg) -> float:
    m = cfg.moe
    routed = m.top_k * m.n_experts / m.n_router
    return (2 * cfg.d_model * m.n_router
            + routed * 2 * _expert_params(cfg)
            + 6 * cfg.d_model * m.shared_d_ff)


def token_flops(cfg, ctx: int) -> float:
    """One token through every layer with ``ctx`` positions in context
    (itself included); lm_head not included."""
    n_ssm, n_attn = _kinds(cfg)
    attn = 2 * _attn_params(cfg) + 4 * cfg.n_heads * cfg.head_dim_ * ctx
    return (n_ssm * work.token_flops(_ssm_cfg(cfg), 0) + n_attn * attn
            + cfg.n_layers * _moe_token_flops(cfg))


def tokens_flops(cfg, start: int, n: int) -> float:
    """Tokens at context lengths start+1 .. start+n."""
    _, n_attn = _kinds(cfg)
    ctx_sum = n * start + n * (n + 1) // 2
    return (n * token_flops(cfg, 0)
            + n_attn * 4 * cfg.n_heads * cfg.head_dim_ * ctx_sum)


def _kv_bytes(cfg, ctx: int) -> int:
    _, n_attn = _kinds(cfg)
    return BYTES * n_attn * 2 * ctx * cfg.n_kv_heads * cfg.head_dim_


def _ssm_bytes(cfg) -> int:
    n_ssm, _ = _kinds(cfg)
    return n_ssm * work.state_bytes(_ssm_cfg(cfg), 0)


def state_bytes(cfg, ctx: int) -> int:
    """One row's cached state after ``ctx`` tokens: the Mamba-2 layers'
    state and conv tails, and ``ctx`` positions of keys and values."""
    return _ssm_bytes(cfg) + _kv_bytes(cfg, ctx)


def prefill(cfg, b: int, p: int):
    return {"flops": b * (tokens_flops(cfg, 0, p) + work.lm_head_flops(cfg)),
            "bytes": weight_bytes(cfg, b * p) + b * state_bytes(cfg, p)}


def inject(cfg, b: int, s: int, p: int):
    """``b`` rows of ``s`` tokens after ``p`` cached ones: the cached
    state read, the SSM state written whole and the ``s`` new key/value
    positions written."""
    new = _ssm_bytes(cfg) + _kv_bytes(cfg, s)
    return {"flops": b * (tokens_flops(cfg, p, s) + work.lm_head_flops(cfg)),
            "bytes": weight_bytes(cfg, b * s)
            + b * (state_bytes(cfg, p) + new)}


def slate(cfg, b: int, t: int, ctx: int):
    """``t - 1`` decode steps after ``ctx`` tokens in one call. The call
    reads each float32 weight its steps reach once; the configuration
    computes at one bfloat16 pass, so it keeps a bfloat16 copy of them
    (written once) and every later step reads only the copy of what its
    ``b`` tokens reach (the compiled slate does the same: the compiler
    converts the weights once, before the decode loop). Each step reads
    and writes every row's SSM state, reads its keys and values and
    writes one position of them."""
    steps = t - 1
    flops = b * (tokens_flops(cfg, ctx, steps)
                 + steps * work.lm_head_flops(cfg))
    rw = sum(2 * _ssm_bytes(cfg) + _kv_bytes(cfg, ctx + j) + _kv_bytes(cfg, 1)
             for j in range(steps))
    once = weight_bytes(cfg, b * steps)
    copies = (once + (steps - 1) * weight_bytes(cfg, b)) / 2
    return {"flops": flops, "bytes": once + copies + b * rw}


def scatter(cfg, b: int, p: int):
    return {"flops": 0.0, "bytes": 2 * b * state_bytes(cfg, p)}


def row_flops(cfg, p: int, n_sfx: int, t: int, prefilled: bool) -> float:
    f = tokens_flops(cfg, p, n_sfx) + tokens_flops(cfg, p + n_sfx, t - 1)
    if prefilled:
        f += tokens_flops(cfg, 0, p)
    return f + t * work.lm_head_flops(cfg)
