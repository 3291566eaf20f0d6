"""Granite 4.0-H's mechanisms at tiny widths: a period of Mamba-2 and NoPE
attention mixers, an MoE block holding a share of the router's experts
plus a shared expert, and Granite's four multipliers, all != 1.

The program (``forward``, and ``ServingEngine``'s prefill -> inject ->
finalize -> decode) is compared with a plain float32 reference written
here from the layer equations; the expert shares are tied to the whole
layer; and serving is shown never to drop a token.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig, get_config
from repro.models.common import KeyGen
from repro.models.model import forward, init_params, prefill
from repro.models.moe import init_moe, moe_apply, moe_capacity
from repro.serving.engine import ServingConfig, ServingEngine

HI = jax.lax.Precision.HIGHEST
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 4 layers: Mamba-2 at 0, 1, 3 and NoPE attention at 2; every layer MoE,
# holding router experts 3-5 of 8 (top-2) beside a shared expert
CFG = ModelConfig(
    name="tiny-granite", family="hybrid", n_layers=4, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=32, vocab_size=96, head_dim=16,
    tie_embeddings=True, position_embedding="none",
    ssm=SSMConfig(d_state=16, expand=2, head_dim=16, conv_width=4,
                  chunk_size=32, attn_period=4, attn_offset=2),
    moe=MoEConfig(n_experts=3, top_k=2, router_experts=8, expert_offset=3,
                  shared_d_ff=48),
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.125, logits_scaling=16.0)


def _params(cfg=CFG, seed=0):
    return init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)


# ----------------------------------------------------------------------
# The plain reference: whole token streams, no cache, mask or capacity
# ----------------------------------------------------------------------

def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _conv(x, w, b):
    cw, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, i:i + s] * w[i] for i in range(cw)) + b)


def ref_mamba(h, p, cfg):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t,
    then out_proj(rms(y * silu(z)) * scale)."""
    s = cfg.ssm
    nh, hp = cfg.d_inner // s.head_dim, s.head_dim
    z = _mm("bld,de->ble", h, p["wz"])
    x = _conv(_mm("bld,de->ble", h, p["wx"]), p["conv_x"], p["conv_bias_x"])
    B = _conv(_mm("bld,de->ble", h, p["wB"]), p["conv_B"], p["conv_bias_B"])
    C = _conv(_mm("bld,de->ble", h, p["wC"]), p["conv_C"], p["conv_bias_C"])
    dt = jax.nn.softplus(_mm("bld,dh->blh", h, p["wdt"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    b, L = h.shape[:2]
    xh = x.reshape(b, L, nh, hp)
    state = jnp.zeros((b, nh, hp, s.d_state), jnp.float32)
    ys = []
    for t in range(L):
        state = (jnp.exp(dt[:, t] * A)[:, :, None, None] * state
                 + (dt[:, t, :, None] * xh[:, t])[..., None]
                 * B[:, t, None, None, :])
        ys.append(_mm("bhps,bs->bhp", state, C[:, t])
                  + p["D"][None, :, None] * xh[:, t])
    y = jnp.stack(ys, 1).reshape(b, L, -1)
    g = _rms(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
    return _mm("ble,ed->bld", g, p["out_proj"])


def ref_attention(h, p, cfg):
    """Causal GQA over the stream, no positions, scores times the
    config's attention_multiplier."""
    g = cfg.n_heads // cfg.n_kv_heads
    q = _mm("bld,dnh->blnh", h, p["wq"])
    k = jnp.repeat(_mm("bld,dnh->blnh", h, p["wk"]), g, 2)
    v = jnp.repeat(_mm("bld,dnh->blnh", h, p["wv"]), g, 2)
    s = _mm("bqnh,bknh->bnqk", q, k) * cfg.attention_multiplier
    L = h.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    o = _mm("bnqk,bknh->bqnh", jax.nn.softmax(s, -1), v)
    return _mm("bqnh,nhd->bqd", o, p["wo"])


def _swiglu(h, gate, up, down):
    a = jax.nn.silu(_mm("...d,df->...f", h, gate)) * _mm(
        "...d,df->...f", h, up)
    return _mm("...f,fd->...d", a, down)


def ref_moe(h, p, cfg, shared=True):
    """Router over all router_experts, top-k, softmax over the top-k
    logits; each held expert weighted where chosen; plus the shared
    expert."""
    m = cfg.moe
    top, ids = jax.lax.top_k(_mm("bld,de->ble", h, p["router"]), m.top_k)
    w = jax.nn.softmax(top, -1)
    y = jnp.zeros_like(h)
    for j in range(m.n_experts):
        cw = jnp.sum(jnp.where(ids == m.expert_offset + j, w, 0.0), -1)
        y = y + cw[..., None] * _swiglu(h, p["gate"][j], p["up"][j],
                                        p["down"][j])
    if shared and m.shared_d_ff:
        y = y + _swiglu(h, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
    return y


def ref_logits(params, cfg, tokens):
    """(b, L) tokens -> (b, L, vocab_size) logits."""
    x = params["embed"]["table"][jnp.asarray(tokens)] * cfg.embedding_multiplier
    blocks = params["blocks"]
    period = len(blocks)
    rm, eps = cfg.residual_multiplier, cfg.norm_eps
    for i, kind in enumerate(cfg.layer_kinds()):
        lp = jax.tree.map(lambda a: a[i // period], blocks[f"pos{i % period}"])
        h = _rms(x, lp["norm1"]["scale"], eps)
        mix = (ref_attention(h, lp["attn"], cfg) if kind == "attn"
               else ref_mamba(h, lp["ssm"], cfg))
        x = x + rm * mix
        h = _rms(x, lp["norm2"]["scale"], eps)
        x = x + rm * ref_moe(h, lp["moe"], cfg)
    x = _rms(x, params["final_norm"]["scale"], eps)
    table = params["embed"]["table"][:cfg.vocab_size]
    return _mm("bld,vd->blv", x, table) / cfg.logits_scaling


def _streams(seed, b, length, vocab=CFG.vocab_size):
    return np.random.default_rng(seed).integers(1, vocab, (b, length))


# float32 on the CPU: the program's chunked SSD scan, its sorted expert
# dispatch and its fused norms sum in other orders than the reference's
# per-token recurrence and dense expert loop; over 4 layers that leaves
# about 1e-6 of the logits' scale. A bfloat16 pass anywhere would leave
# about 1e-3, so 1e-4 of the scale tells the two apart.
def _close(got, want, rel=1e-4):
    got, want = np.asarray(got), np.asarray(want)
    scale = np.abs(want).max()
    assert scale > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=rel * scale)


def test_config_exercises_every_mechanism():
    assert CFG.layer_kinds() == ("ssm", "ssm", "attn", "ssm")
    assert set(CFG.mlp_kinds()) == {"moe"}
    assert CFG.moe.n_router == 8 and CFG.moe.n_experts < CFG.moe.n_router
    assert CFG.attention_multiplier != CFG.head_dim_ ** -0.5
    for v in (CFG.embedding_multiplier, CFG.residual_multiplier,
              CFG.logits_scaling):
        assert v != 1.0
    CFG.validate()


@pytest.mark.parametrize("entry", ["forward", "prefill"])
def test_program_matches_the_reference(entry):
    """``forward`` (training capacity lifted, so no token drops) and the
    serving ``prefill`` against the plain reference, every position."""
    params = _params()
    toks = jnp.asarray(_streams(1, 2, 32), jnp.int32)
    if entry == "forward":
        cfg = dataclasses.replace(CFG, moe=CFG.moe.no_drop())
        got, _ = forward(params, cfg, toks)
    else:
        got, _ = prefill(params, CFG, toks)
    _close(got[..., :CFG.vocab_size], ref_logits(params, CFG, toks))


def test_nope_attention_sees_no_positions():
    """With no position embedding, attention is blind to where the
    stream starts: the same stream at shifted positions gives the same
    output, bit for bit; the same config with RoPE does not."""
    from repro.models.attention import attention_full
    p = init_params(CFG, jax.random.PRNGKey(3),
                    jnp.float32)["blocks"]["pos2"]["attn"]
    p = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 8, CFG.d_model))
    pos = jnp.arange(8, dtype=jnp.int32)[None]
    a, _ = attention_full(p, x, pos, CFG)
    b, _ = attention_full(p, x, pos + 100, CFG)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rope = dataclasses.replace(CFG, position_embedding="rope")
    c, _ = attention_full(p, x, pos + 100, rope)
    assert not np.allclose(np.asarray(a), np.asarray(c), atol=1e-3)


def _engine(params, prefill_len=24):
    return ServingEngine(CFG, params, ServingConfig(
        max_batch=2, prefill_len=prefill_len, inject_len=8,
        cache_capacity=64))


def test_served_path_matches_the_reference():
    """prefill (right-padded history) -> inject (left-aligned fresh
    events) -> finalize (the KV ring) -> decode steps, each step's
    logits against the reference's full forward over the stream."""
    params = _params()
    eng = _engine(params)
    hists = [list(_streams(5, 1, 17)[0]), list(_streams(6, 1, 9)[0])]
    fresh = [[21, 22, 23], [30, 31, 32, 33, 34]]
    nxt = [[50, 60, 70], [61, 71, 81]]
    toks, valid = eng.pad_tokens(hists, 24)
    st = eng.inject(eng.prefill(toks, valid), *eng.pad_tokens(
        fresh, 8, align="left"))
    want_first = [ref_logits(params, CFG, [h + f])[0, -1]
                  for h, f in zip(hists, fresh)]
    _close(st["last_valid_logits"][:, :CFG.vocab_size],
           jnp.stack(want_first))
    dec = eng.finalize(st)
    for j in range(3):
        logits, dec = eng.decode(dec, np.array([[n[j]] for n in nxt],
                                               np.int32))
        for row in range(2):
            stream = hists[row] + fresh[row] + nxt[row][:j + 1]
            want = ref_logits(params, CFG, [stream])[0, -1]
            _close(logits[row, :CFG.vocab_size], want)


@pytest.mark.parametrize("share", [1, 2, 4])
def test_expert_shares_add_up_to_the_whole_layer(share):
    """Each share holds ``share`` consecutive router experts; the shares'
    results, the shared expert counted once (in the first share only),
    add up to the uncut layer, the program's and the reference's."""
    whole = dataclasses.replace(
        CFG, moe=dataclasses.replace(CFG.moe, n_experts=8, router_experts=0,
                                     expert_offset=0))
    p = init_moe(KeyGen(jax.random.PRNGKey(7)), whole, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, CFG.d_model))
    with jax.default_matmul_precision("highest"):
        full, _ = moe_apply(p, x, whole, drop_free=True)
        total = jnp.zeros_like(x)
        for off in range(0, 8, share):
            m = dataclasses.replace(whole.moe, n_experts=share,
                                    router_experts=8, expert_offset=off,
                                    shared_d_ff=whole.moe.shared_d_ff
                                    if off == 0 else 0)
            cfg = dataclasses.replace(whole, moe=m)
            sp = {k: (v[off:off + share] if k in ("gate", "up", "down")
                      else v) for k, v in p.items()
                  if off == 0 or not k.startswith("shared")}
            part, _ = moe_apply(sp, x, cfg, drop_free=True)
            total = total + part
    _close(total, full, rel=1e-5)
    _close(total, ref_moe(x, p, whole), rel=1e-5)


def test_serving_drops_no_token():
    """At the training capacity factor (1.25) some expert of this input
    gets more tokens than its training buffer holds; serving still runs
    every token through its experts, so a cached prefill plus an inject
    equals the full prefill, and ``forward`` at that capacity does not."""
    assert CFG.moe.capacity_factor == 1.25
    params = _params(seed=2)
    hist, fresh = list(_streams(9, 1, 24)[0]), list(_streams(10, 1, 8)[0])
    full = jnp.asarray([hist + fresh], jnp.int32)
    # the busiest held expert's load in the first MoE layer, against
    # what the training capacity would keep
    from repro.models.norms import rmsnorm
    lp = jax.tree.map(lambda a: a[0], params["blocks"]["pos0"])
    x = params["embed"]["table"][full] * CFG.embedding_multiplier
    h = rmsnorm(lp["norm1"], x)
    from repro.models import ssm
    x = x + CFG.residual_multiplier * ssm.ssm_forward(lp["ssm"], h, CFG)[0]
    logits = _mm("bld,de->ble", rmsnorm(lp["norm2"], x), lp["moe"]["router"])
    ids = np.asarray(jax.lax.top_k(logits, CFG.moe.top_k)[1])
    off = CFG.moe.expert_offset
    load = max(int((ids == off + j).sum()) for j in range(CFG.moe.n_experts))
    assert load > moe_capacity(full.shape[1], CFG)

    eng = _engine(params, prefill_len=32)
    one = eng.prefill(*eng.pad_tokens([hist + fresh], 32))
    two = eng.inject(eng.prefill(*eng.pad_tokens([hist], 32)),
                     *eng.pad_tokens([fresh], 8, align="left"))
    v = CFG.vocab_size
    want = np.asarray(one["logits"][0, -1, :v])
    _close(two["last_valid_logits"][0, :v], want, rel=1e-5)
    trained, _ = forward(params, CFG, full)
    assert np.abs(np.asarray(trained[0, -1, :v]) - want).max() \
        > 1e-3 * np.abs(want).max()


def test_pool_counts_slot_bytes_by_kind():
    """A mixed slot holds SSM state and attention keys/values; the pool
    counts each kind, and the slot table's stats (``Gateway.stats().cache``)
    carry the counts."""
    from repro.serving.pool import DeviceStatePool, PagedStateCache
    eng = _engine(_params())
    pool = DeviceStatePool(eng, 4)
    s = CFG.ssm
    ssm_row = 4 * (CFG.n_ssm_heads * s.head_dim * s.d_state
                   + (s.conv_width - 1) * (CFG.d_inner + 2 * s.d_state))
    kv_row = 4 * 2 * 24 * CFG.n_kv_heads * CFG.head_dim_
    kinds = {"ssm": 3 * ssm_row, "kv": kv_row,
             "logits": 4 * CFG.vocab_padded, "mask": 24 + 4}
    assert pool.slot_bytes_by_kind == kinds
    assert sum(kinds.values()) == pool.slot_nbytes
    assert PagedStateCache(pool).stats()["slot_bytes_by_kind"] == kinds


def test_bench_configuration_is_the_registered_model_cut():
    """bench/configs/granite-4.0-h-small.json serves the registered model
    but for the keys its ``reduced`` names (the catalog's names, mapped
    to the program's in ``reduced_in_model``), whose published values
    are the registered ones."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "granite-4.0-h-small.json")) as f:
        conf = json.load(f)
    m = dict(conf["model"])
    m["ssm"], m["moe"] = SSMConfig(**m["ssm"]), MoEConfig(**m["moe"])
    cut = ModelConfig(**m)
    reg = get_config("granite-4.0-h-small")
    assert conf["reduced"] == ["num_hidden_layers", "num_local_experts",
                               "vocab_size"]
    names = conf["reduced_in_model"]
    assert set(names) == set(conf["reduced"]) == set(conf["published"])

    def get(cfg, name):
        obj = cfg
        for part in name.split("."):
            obj = getattr(obj, part)
        return obj

    for key, name in names.items():
        assert get(reg, name) == conf["published"][key]
        assert get(cut, name) == conf[key] != conf["published"][key]
    # everything else as registered; the share's router keeps all 72
    uncut = dataclasses.replace(
        cut, n_layers=reg.n_layers, vocab_size=reg.vocab_size,
        moe=dataclasses.replace(cut.moe, n_experts=reg.moe.n_experts,
                                router_experts=0))
    assert uncut == reg
    assert cut.moe.n_router == reg.moe.n_experts
    assert cut.layer_kinds() == reg.layer_kinds()[:10]
