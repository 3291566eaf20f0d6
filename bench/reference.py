"""Plain references of the served models, in ``jax.numpy`` and float32.

Each takes the benchmark's weights (the program's parameter layout, read
by key name) and a batch of whole token streams, runs them through the
model from the first token, and returns the logits at the positions asked
for. No cache, no padding masks, no kernels, no chunking: the Mamba2
(SSD) layer is the plain per-token recurrence of arXiv:2405.21060

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

and the ranker is a pre-norm causal transformer (RoPE, rotate-half;
SwiGLU MLP; tied embeddings). Streams are right-padded; padding after a
row's last token cannot reach earlier positions of a causal model.

``mode`` sets the precision of every matrix product:

    "highest"  float32 operands at ``Precision.HIGHEST`` (the reference)
    "bf16"     operands rounded to bfloat16, float32 accumulation
    "fp8"      operands scaled into float8 e4m3 (per tensor for weights
               and values, per row for activations), float32 accumulation

"fp8" is the control: the reference put one precision below what the
configurations state they serve at (one bfloat16 pass), decoding its own
slates (``greedy_slates``) in the program's place.

A configuration that these two stacks do not cover (``covers``) brings
its own reference and work counts in ``bench/configs/<name>.py``
(cell.config_code), its ``logits_at`` built from ``_mm``, ``_quant``,
``_rms`` and ``_conv`` so that each mode means the same for every model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _quant(x, mode: str, per_row: bool):
    if mode == "highest":
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode != "fp8":
        raise ValueError(f"unknown precision mode {mode!r}")
    amax = (jnp.max(jnp.abs(x), axis=-1, keepdims=True) if per_row
            else jnp.max(jnp.abs(x)))
    s = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, mode: str):
    """einsum of activation ``a`` (per-row scale) and operand ``b``."""
    return jnp.einsum(spec, _quant(a, mode, True), _quant(b, mode, False),
                      precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _conv(x, w, b):
    """Causal depthwise conv, zero history: y_t = sum_i w_i x_{t-cw+1+i}."""
    cw, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    return sum(xp[:, i:i + s] * w[i] for i in range(cw)) + b


def _mamba_layer(x, lp, cfg, mode):
    eps, s = cfg.norm_eps, cfg.ssm
    nh, hp = cfg.d_inner // s.head_dim, s.head_dim
    p = lp["ssm"]
    h = _rms(x, lp["norm1"]["scale"], eps)
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
    k, L = x.shape[:2]
    xh = xr.reshape(k, L, nh, hp)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp            # (k,nh,hp) (k,nh) (k,ds) (k,ds)
        state = (jnp.exp(dt_t * A)[:, :, None, None] * state
                 + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
        y = jnp.einsum("khps,ks->khp", state, c_t, precision=HI)
        return state, y + p["D"][None, :, None] * x_t

    state0 = jnp.zeros((k, nh, hp, s.d_state), jnp.float32)
    tmaj = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, state0, (tmaj(xh), tmaj(dt), tmaj(B), tmaj(C)))
    y = jnp.moveaxis(y, 0, 1).reshape(k, L, -1)
    g = y * jax.nn.silu(z)
    g = _rms(g, p["norm_scale"], eps)
    return x + _mm("ble,ed->bld", g, p["out_proj"], mode)


def _rope(x, theta):
    hd, L = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attn_layer(x, lp, cfg, mode):
    eps = cfg.norm_eps
    p = lp["attn"]
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    hd = cfg.d_model // nq if not cfg.head_dim else cfg.head_dim
    h = _rms(x, lp["norm1"]["scale"], eps)
    q = _rope(_mm("bld,dnh->blnh", h, p["wq"], mode), cfg.rope_theta)
    k = _rope(_mm("bld,dnh->blnh", h, p["wk"], mode), cfg.rope_theta)
    v = _mm("bld,dnh->blnh", h, p["wv"], mode)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    s = _mm("bqnh,bknh->bnqk", q, k, mode) * hd ** -0.5
    L = x.shape[1]
    causal = jnp.tril(jnp.ones((L, L), bool))
    prob = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bnqk,bknh->bqnh", prob, v, mode)
    x = x + _mm("bqnh,nhd->bqd", o, p["wo"], mode)
    m = lp["mlp"]
    h = _rms(x, lp["norm2"]["scale"], eps)
    g = jax.nn.silu(_mm("bld,df->blf", h, m["gate"], mode))
    u = _mm("bld,df->blf", h, m["up"], mode)
    return x + _mm("blf,fd->bld", g * u, m["down"], mode)


def covers(cfg) -> bool:
    """Whether this module (and bench/work.py) covers ``cfg``: every layer
    a Mamba-2 mixer alone (family ssm), or every layer causal attention
    with RoPE and a dense SwiGLU (family dense), with none of the options
    the reference leaves out. Any other configuration brings its own
    reference (cell.config_code)."""
    kinds = set(zip(cfg.layer_kinds(), cfg.mlp_kinds()))
    plain = (not cfg.qkv_bias and not cfg.sliding_window
             and cfg.frontend == "none")
    return plain and ((cfg.family == "ssm" and kinds == {("ssm", "none")})
                      or (cfg.family == "dense"
                          and kinds == {("attn", "dense")}))


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def logits_at(params, tokens, pos, *, cfg, mode="highest"):
    """tokens (k, L) int32, right-padded; pos (k, T) positions to read.
    Returns (k, T, vocab_size) float32 logits of the next token after
    each position."""
    if not covers(cfg):
        raise NotImplementedError(f"no reference for {cfg.name}")
    layer = _mamba_layer if cfg.family == "ssm" else _attn_layer
    table = params["embed"]["table"]
    x = table[tokens]
    blocks = params["blocks"]
    if list(blocks) != ["pos0"]:
        raise NotImplementedError("reference expects one stacked layer kind")

    def body(x, lp):
        return layer(x, lp, cfg, mode), None

    x, _ = jax.lax.scan(body, x, blocks["pos0"])
    x = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    x = _rms(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (table if cfg.tie_embeddings else params["lm_head"]["table"])
    return _mm("btd,vd->btv", x, head[:cfg.vocab_size], mode)


def greedy_slates(logits_fn, params, prompts, slate_len, length, *, cfg,
                  mode):
    """(k, slate_len) slates decoded greedily by ``logits_fn`` (a
    configuration's ``logits_at``) at ``mode``: each pick is the best
    token not yet in its slate, given the prompt and the picks before it
    (one pass over the streams a pick)."""
    k = len(prompts)
    lens = np.asarray([len(p) for p in prompts])
    toks = np.zeros((k, length), np.int32)
    for r, p in enumerate(prompts):
        toks[r, :len(p)] = p
    rows = np.arange(k)
    picks = np.zeros((k, slate_len), np.int64)
    taken = np.zeros((k, cfg.vocab_size), bool)
    for j in range(slate_len):
        pos = (lens - 1 + j)[:, None].astype(np.int32)
        lg = np.asarray(logits_fn(params, toks, pos, cfg=cfg, mode=mode))
        picks[:, j] = np.where(taken, -np.inf, lg[:, 0]).argmax(-1)
        taken[rows, picks[:, j]] = True
        if j + 1 < slate_len:
            toks[rows, lens + j] = picks[:, j]
    return picks
