"""Closed forms of bench/work.py at tiny configurations, counted by hand
and against the program's own parameter and state shapes."""
import dataclasses
import hashlib

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


# The shared work counts at the cells' served shapes (panes of 8, 256
# history tokens, 16 injected, slates of 8), as the counts stood before
# a configuration could bring its own: reached through cell.config_code,
# they must not move.
FULL_SIZE = {
    "mamba2-780m": {
        "prefill": {"flops": 3033415483392.0, "bytes": 3739911168},
        "inject": {"flops": 190746918912.0, "bytes": 4359226368},
        "slate": {"flops": 91560935424.0, "bytes": 30514584576},
        "scatter": {"flops": 0.0, "bytes": 1238630400},
        "row_flops": (35288481792.0, 414310957056.0)},
    "itfi-ranker": {
        "prefill": {"flops": 18278776832.0, "bytes": 38806528},
        "inject": {"flops": 1233387520.0, "bytes": 39855104},
        "slate": {"flops": 679870464.0, "bytes": 280820736},
        "scatter": {"flops": 0.0, "bytes": 33554432},
        "row_flops": (239157248.0, 2521382912.0)},
}


@pytest.mark.parametrize("name", sorted(FULL_SIZE))
def test_full_size_work_counts_through_the_loader(name):
    """The registered model at full size, through the loader: neither
    configuration brings a module, so the shared counts answer."""
    from repro.configs.base import get_config
    conf = {"name": name, "model": dataclasses.asdict(get_config(name))}
    cfg = cell.model_config(conf)
    code = cell.config_code(conf)
    want = FULL_SIZE[name]
    assert code.prefill(cfg, 8, 256) == want["prefill"]
    assert code.inject(cfg, 8, 16, 256) == want["inject"]
    assert code.slate(cfg, 8, 8, 272) == want["slate"]
    assert code.scatter(cfg, 8, 256) == want["scatter"]
    assert (code.row_flops(cfg, 256, 16, 8, False),
            code.row_flops(cfg, 256, 16, 8, True)) == want["row_flops"]


# sha256 (first 16 hex digits) of each leaf's bytes, drawn on the CPU
# from seed 2**31 + 15 before expert-stacked leaves had a rule of their
# own: configurations without experts draw exactly these weights
WEIGHT_SUMS = {
    "mamba": {
        "['blocks']['pos0']['norm1']['scale']": "02722f124d0f1736",
        "['blocks']['pos0']['ssm']['A_log']": "ecbc5dd1a6f4bab6",
        "['blocks']['pos0']['ssm']['D']": "9628e545ed3ac074",
        "['blocks']['pos0']['ssm']['conv_B']": "e53e3c2c4edc63d7",
        "['blocks']['pos0']['ssm']['conv_C']": "69dc93bf33bbf72f",
        "['blocks']['pos0']['ssm']['conv_bias_B']": "38723a2e5e8a17aa",
        "['blocks']['pos0']['ssm']['conv_bias_C']": "38723a2e5e8a17aa",
        "['blocks']['pos0']['ssm']['conv_bias_x']": "5f70bf18a0860070",
        "['blocks']['pos0']['ssm']['conv_x']": "80ed4068a8ce91b1",
        "['blocks']['pos0']['ssm']['dt_bias']": "573024ed111e9332",
        "['blocks']['pos0']['ssm']['norm_scale']": "893a106828fbdb95",
        "['blocks']['pos0']['ssm']['out_proj']": "8157c9b573693b34",
        "['blocks']['pos0']['ssm']['wB']": "dc556c1047612d66",
        "['blocks']['pos0']['ssm']['wC']": "1ac8134793b257f5",
        "['blocks']['pos0']['ssm']['wdt']": "6af6d7f983a5bd9d",
        "['blocks']['pos0']['ssm']['wx']": "8be215c5bc550343",
        "['blocks']['pos0']['ssm']['wz']": "95e21996954a20be",
        "['embed']['table']": "6828f9941c1ef40b",
        "['final_norm']['scale']": "2f20cd03c9cd392a",
    },
    "ranker": {
        "['blocks']['pos0']['attn']['wk']": "b9bf283649e1548d",
        "['blocks']['pos0']['attn']['wo']": "549f19b81a50a92d",
        "['blocks']['pos0']['attn']['wq']": "0e9a9dc4cea16a83",
        "['blocks']['pos0']['attn']['wv']": "0848b818068e18a7",
        "['blocks']['pos0']['mlp']['down']": "804d08ae2d2ea626",
        "['blocks']['pos0']['mlp']['gate']": "636b8f7eba770a0b",
        "['blocks']['pos0']['mlp']['up']": "a5457167c7388c85",
        "['blocks']['pos0']['norm1']['scale']": "2f20cd03c9cd392a",
        "['blocks']['pos0']['norm2']['scale']": "2f20cd03c9cd392a",
        "['embed']['table']": "73f27d29c85ceed0",
        "['final_norm']['scale']": "b638277a8690e175",
    },
}


@pytest.mark.parametrize("kind", sorted(WEIGHT_SUMS))
def test_weights_without_experts_are_drawn_as_before(kind):
    params = cell.make_weights(_cfg(kind), 2 ** 31 + 15)
    got = {jax.tree_util.keystr(path): hashlib.sha256(
        np.asarray(x).tobytes()).hexdigest()[:16]
        for path, x in jax.tree_util.tree_leaves_with_path(params)}
    assert got == WEIGHT_SUMS[kind]


def test_expert_matrices_draw_at_their_input_width():
    """(layers, experts, in, out) leaves under "moe" take fan_in = in,
    not the expert count."""
    cfg = _cfg("hybrid")
    params = cell.make_weights(cfg, 7)
    d, f = cfg.d_model, cfg.d_ff
    for key in params["blocks"]:
        moe = params["blocks"][key]["moe"]
        for name, fan_in in (("gate", d), ("up", d), ("down", f)):
            sd = float(np.std(np.asarray(moe[name])))
            assert sd == pytest.approx(fan_in ** -0.5, rel=0.05), name
