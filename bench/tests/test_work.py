"""Closed forms of bench/work.py at tiny configurations, counted by hand
and against the program's own parameter and state shapes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import cell, work


def _cfg(kind):
    return cell.model_config(tiny.tiny_config(kind))


@pytest.mark.parametrize("kind", ["mamba", "ranker"])
def test_weight_bytes_match_the_program(kind):
    from repro.models.model import init_params
    cfg = _cfg(kind)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k, jnp.float32),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    pad_rows = cfg.vocab_padded - cfg.vocab_size
    assert work.weight_bytes(cfg) == 4 * (n - pad_rows * cfg.d_model)


@pytest.mark.parametrize("kind", ["mamba", "ranker"])
def test_state_bytes_match_the_program(kind):
    from repro.serving.engine import ServingConfig, ServingEngine
    cfg = _cfg(kind)
    s = tiny.tiny_config(kind)["serving"]
    params = cell.make_weights(cfg, 3)
    eng = ServingEngine(cfg, params, ServingConfig(
        max_batch=s["max_batch"], prefill_len=s["prefill_len"],
        inject_len=s["inject_len"], cache_capacity=s["cache_capacity"]))
    _, caches = eng.prefill_state_shapes()
    per_row = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(caches)) // s["max_batch"]
    assert work.state_bytes(cfg, s["prefill_len"]) == per_row


def test_dense_flops_by_hand():
    cfg = _cfg("ranker")          # d 32, 4 heads of 8, d_ff 64, 2 layers
    per_layer = lambda c: 4 * 2 * 32 * 32 + 3 * 2 * 32 * 64 + 4 * 4 * 8 * c
    assert work.token_flops(cfg, 10) == 2 * per_layer(10)
    assert work.tokens_flops(cfg, 5, 3) == sum(
        work.token_flops(cfg, c) for c in (6, 7, 8))
    assert work.lm_head_flops(cfg) == 2 * 32 * 96


def test_ssm_flops_by_hand():
    cfg = _cfg("mamba")           # d 64, din 128, 8 heads of 16, ds 16
    d, din, nh, hp, ds, cw = 64, 128, 8, 16, 16, 4
    per_layer = (2 * d * (2 * din + 2 * ds + nh) + 2 * cw * (din + 2 * ds)
                 + 4 * nh * hp * ds + 2 * din * d)
    assert work.token_flops(cfg, 123) == 2 * per_layer
    assert work.tokens_flops(cfg, 7, 5) == 5 * 2 * per_layer


@pytest.mark.parametrize("kind", ["mamba", "ranker"])
def test_calls_and_roofline(kind):
    cfg = _cfg(kind)
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    pf = work.prefill(cfg, 4, 32)
    assert pf["flops"] == 4 * (work.tokens_flops(cfg, 0, 32)
                               + work.lm_head_flops(cfg))
    assert pf["bytes"] == work.weight_bytes(cfg) + 4 * work.state_bytes(
        cfg, 32)
    inj = work.inject(cfg, 4, 8, 32)
    assert inj["flops"] == 4 * (work.tokens_flops(cfg, 32, 8)
                                + work.lm_head_flops(cfg))
    sl = work.slate(cfg, 4, 4, 40)
    assert sl["bytes"] > 3 * work.weight_bytes(cfg)
    t = work.least_time(pf, peak)
    assert t == max(pf["flops"] / 1e12, pf["bytes"] / 1e11)
    hit = work.row_flops(cfg, 32, 3, 4, prefilled=False)
    miss = work.row_flops(cfg, 32, 3, 4, prefilled=True)
    assert miss - hit == work.tokens_flops(cfg, 0, 32)
    assert hit == (work.tokens_flops(cfg, 32, 3) + work.tokens_flops(
        cfg, 35, 3) + 4 * work.lm_head_flops(cfg))


def test_full_size_orders_of_magnitude():
    """The real configurations: weights as the program holds them."""
    import os
    conf = cell.read_json(os.path.join(cell.BENCH, "configs",
                                       "mamba2-780m.json"))
    cfg = cell.model_config(conf)
    assert 3.0e9 < work.weight_bytes(cfg) < 3.2e9
    assert work.state_bytes(cfg, 256) * 8 == pytest.approx(6.2e8, rel=0.01)
    cfg = dataclasses.replace(cfg)  # frozen: unchanged by the counts
