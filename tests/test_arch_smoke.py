"""Per-architecture smoke tests (deliverable f).

Each assigned arch gets a REDUCED same-family variant (2 layers,
d_model<=512, <=4 experts) running one forward AND one train step on CPU,
asserting output shapes and absence of NaNs. Decode-capable archs also run
one serve step. Frontend archs (vlm/audio) exercise their prefix-embedding
stubs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import ASSIGNED
from repro.configs.base import get_config, reduced
from repro.models.frontend import audio_stub_embeds, vision_stub_embeds
from repro.models.model import (cache_from_prefill, decode_step, forward,
                                init_cache, init_params, prefill)
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training.train_loop import TrainConfig, make_train_step

B, S = 2, 32
# the assigned ten and the benchmark's granite-4.0-h-small (NoPE
# attention, Mamba-2, MoE with a shared expert, Granite's multipliers)
ARCHS = ASSIGNED + ("granite-4.0-h-small",)


def _setup(arch):
    cfg = reduced(get_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    return cfg, params, toks


def _prefix(cfg, n=4):
    if cfg.frontend == "vision":
        return vision_stub_embeds(jax.random.PRNGKey(2), B, n, cfg.d_model,
                                  jnp.float32)
    if cfg.frontend == "audio":
        return audio_stub_embeds(jax.random.PRNGKey(2), B, n, cfg.d_model,
                                 jnp.float32)
    return None


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nan(arch):
    cfg, params, toks = _setup(arch)
    pfx = _prefix(cfg)
    logits, aux = forward(params, cfg, toks, prefix_embeds=pfx)
    s_total = S + (pfx.shape[1] if pfx is not None else 0)
    assert logits.shape == (B, s_total, cfg.vocab_padded)
    assert not jnp.isnan(logits).any()
    assert not jnp.isnan(aux)


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch):
    cfg, params, toks = _setup(arch)
    pfx = _prefix(cfg)
    s_total = S + (pfx.shape[1] if pfx is not None else 0)
    batch = {"tokens": toks,
             "labels": jax.random.randint(jax.random.PRNGKey(3),
                                          (B, s_total), 0, cfg.vocab_size)}
    if pfx is not None:
        batch["prefix_embeds"] = pfx
        lm = np.ones((B, s_total), bool)
        lm[:, :pfx.shape[1]] = False
        batch["loss_mask"] = jnp.asarray(lm)
    tcfg = TrainConfig(adamw=AdamWConfig(lr=1e-3), remat=True, q_chunk=16,
                       param_dtype=jnp.float32)
    step = jax.jit(make_train_step(cfg, tcfg))
    opt = init_opt_state(params)
    params2, opt2, metrics = step(params, opt, batch)
    assert float(metrics["loss"]) > 0 and not np.isnan(float(metrics["loss"]))
    assert not np.isnan(float(metrics["grad_norm"]))
    # parameters actually moved
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()), params, params2))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_one_decode_step(arch):
    cfg, params, toks = _setup(arch)
    if cfg.moe:
        cfg = dataclasses.replace(cfg, moe=cfg.moe.no_drop())
    _, caches = prefill(params, cfg, toks)
    dc = cache_from_prefill(cfg, caches, capacity=64)
    pos = jnp.full((B,), S, jnp.int32)
    nxt = jax.random.randint(jax.random.PRNGKey(4), (B, 1), 0, cfg.vocab_size)
    logits, dc2 = decode_step(params, cfg, dc, nxt, pos)
    assert logits.shape == (B, 1, cfg.vocab_padded)
    assert not jnp.isnan(logits).any()


def test_moe_aux_loss_nonzero():
    cfg, params, toks = _setup("mixtral-8x22b")
    _, aux = forward(params, cfg, toks)
    assert float(aux) > 0


def test_jamba_interleave_pattern():
    cfg = get_config("jamba-v0.1-52b")
    kinds = cfg.layer_kinds()
    assert kinds.count("attn") == 4 and kinds.count("ssm") == 28  # 1:7
    assert cfg.mlp_kinds().count("moe") == 16  # every other layer


def test_moe_expert_parallel_split_matches_baseline():
    """The all-to-all EP f-split path (§Perf) is numerically identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.moe import init_moe, moe_apply
    from repro.models.common import KeyGen

    cfg = dataclasses.replace(
        reduced(get_config("mixtral-8x22b")), d_ff=64)
    cfg = dataclasses.replace(cfg, moe=cfg.moe.no_drop())
    params = init_moe(KeyGen(jax.random.PRNGKey(0)), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    base, aux0 = moe_apply(params, x, cfg)
    ep, aux1 = moe_apply(params, x, cfg, moe_sharding=("ep", None, 4))
    np.testing.assert_allclose(np.asarray(base), np.asarray(ep),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux0), float(aux1))
