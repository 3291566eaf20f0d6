"""granite-4.0-h-small's own module (bench/configs/granite-4.0-h-small.py)
at tiny widths on the CPU: its reference against the program, its
precision modes, its work counts against the program's shapes, and the
cell run end to end from files through it.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_granite.py
"""
import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import cell, harness, work

MODULE = os.path.join(cell.BENCH, "configs", "granite-4.0-h-small.py")
SEED = 2 ** 31 + 16

# the served configuration's structure at tiny widths: Mamba-2 at layer 0,
# NoPE attention at layer 1, MoE on both holding router experts 1-2 of 4
# plus a shared expert, Granite's multipliers
GRANITE = {"name": "tiny-granite", "family": "hybrid", "n_layers": 2,
           "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "d_ff": 32,
           "vocab_size": 96, "head_dim": 16, "norm_eps": 1e-05,
           "rope_theta": 10000.0, "sliding_window": 0, "qkv_bias": False,
           "tie_embeddings": True,
           "moe": {"n_experts": 2, "top_k": 2, "period": 1, "offset": 0,
                   "router_jitter": 0.0, "load_balance_coef": 0.01,
                   "capacity_factor": 1.25, "router_experts": 4,
                   "expert_offset": 1, "shared_d_ff": 48},
           "ssm": {"d_state": 16, "expand": 2, "head_dim": 16,
                   "conv_width": 4, "chunk_size": 32, "dt_min": 0.001,
                   "dt_max": 0.1, "attn_period": 2, "attn_offset": 1},
           "frontend": "none", "source": "tiny",
           "position_embedding": "none", "embedding_multiplier": 12.0,
           "residual_multiplier": 0.22, "attention_multiplier": 0.125,
           "logits_scaling": 16.0}


@pytest.fixture(scope="module")
def code():
    return cell.load_module(MODULE, "bench_config_granite_test")


@pytest.fixture(scope="module")
def tiny_cfg():
    conf = tiny.tiny_config("hybrid")
    conf["model"] = GRANITE
    return cell.model_config(conf)


def _program_logits(params, cfg, toks):
    from repro.models import model
    with jax.default_matmul_precision("highest"):
        lg, _ = model.prefill(params, cfg, jnp.asarray(toks))
    return np.asarray(lg[..., :cfg.vocab_size])


def test_reference_matches_the_program(code, tiny_cfg):
    """``logits_at`` at HIGHEST against the program's serving prefill
    (drop-free MoE) at HIGHEST, every position of two streams: both are
    float32 end to end, so they agree to float32 rounding (1e-5 of the
    logits' scale; a bfloat16 pass would leave about 1e-3)."""
    params = cell.make_weights(tiny_cfg, SEED)
    toks = np.random.default_rng(0).integers(1, 96, (2, 32)).astype(np.int32)
    pos = np.tile(np.arange(32, dtype=np.int32), (2, 1))
    ref = np.asarray(code.logits_at(params, toks, pos, cfg=tiny_cfg,
                                    mode="highest"))
    got = _program_logits(params, tiny_cfg, toks)
    np.testing.assert_allclose(got, ref, rtol=0,
                               atol=1e-5 * np.abs(ref).max())


def test_fp8_reads_farther_off_than_bf16(code, tiny_cfg):
    """Each precision below the reference moves its logits further: the
    float8 control lies further from HIGHEST than bfloat16 does."""
    params = cell.make_weights(tiny_cfg, SEED)
    toks = np.random.default_rng(1).integers(1, 96, (4, 24)).astype(np.int32)
    pos = np.tile(np.arange(16, 24, dtype=np.int32), (4, 1))
    at = {m: np.asarray(code.logits_at(params, toks, pos, cfg=tiny_cfg,
                                       mode=m))
          for m in ("highest", "bf16", "fp8")}
    off = {m: np.abs(at[m] - at["highest"]).max() for m in ("bf16", "fp8")}
    assert 0 < off["bf16"] < off["fp8"]


def test_only_held_experts_contribute(code, tiny_cfg):
    """Zeroing the held experts' weights changes the reference; the
    router keeps its full width whatever is held."""
    params = cell.make_weights(tiny_cfg, SEED)
    assert params["blocks"]["pos0"]["moe"]["router"].shape[-1] == 4
    assert params["blocks"]["pos0"]["moe"]["gate"].shape[1] == 2
    toks = np.random.default_rng(2).integers(1, 96, (1, 16)).astype(np.int32)
    pos = np.array([[15]], np.int32)
    base = np.asarray(code.logits_at(params, toks, pos, cfg=tiny_cfg))
    zeroed = jax.tree_util.tree_map_with_path(
        lambda p, w: w * 0 if str(getattr(p[-1], "key", "")) in (
            "gate", "up", "down") else w, params)
    assert not np.allclose(base, np.asarray(code.logits_at(
        zeroed, toks, pos, cfg=tiny_cfg)))


def test_weight_and_state_bytes_match_the_program(code, tiny_cfg):
    """Reading every held expert, the weights a call reads are the
    program's parameters (no vocabulary padding here: 96 rows padded to
    256 are left out); a row's state is what prefill returns."""
    from repro.models.model import init_params
    from repro.serving.engine import ServingConfig, ServingEngine
    shapes = jax.eval_shape(lambda k: init_params(tiny_cfg, k, jnp.float32),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    pad = (tiny_cfg.vocab_padded - tiny_cfg.vocab_size) * tiny_cfg.d_model
    assert code.weight_bytes(tiny_cfg, 10 ** 6) == pytest.approx(
        4 * (n - pad))
    assert code.experts_reached(tiny_cfg, 1) == pytest.approx(2 * 2 / 4)
    eng = ServingEngine(tiny_cfg, cell.make_weights(tiny_cfg, 3),
                        ServingConfig(max_batch=2, prefill_len=32,
                                      inject_len=8, cache_capacity=64))
    _, caches = eng.prefill_state_shapes()
    per_row = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(caches)) // 2
    assert code.state_bytes(tiny_cfg, 32) == per_row


def test_flops_by_hand(code, tiny_cfg):
    """One token, by hand: Mamba-2 as work.py counts it, attention's
    projections and context, each layer's router (4 wide), the held
    experts' expected share (top-2 of 4, 2 held: one expert a token) and
    the shared expert."""
    d, din, nh, hp, ds, cw = 64, 128, 8, 16, 16, 4
    mamba = (2 * d * (2 * din + 2 * ds + nh) + 2 * cw * (din + 2 * ds)
             + 4 * nh * hp * ds + 2 * din * d)
    attn = lambda c: 2 * d * 16 * (2 * 4 + 2 * 2) + 4 * 4 * 16 * c
    moe = 2 * d * 4 + 1 * 6 * d * 32 + 6 * d * 48
    assert code.token_flops(tiny_cfg, 10) == mamba + attn(10) + 2 * moe
    assert code.tokens_flops(tiny_cfg, 5, 3) == sum(
        code.token_flops(tiny_cfg, c) for c in (6, 7, 8))
    assert code.row_flops(tiny_cfg, 32, 8, 4, False) == (
        code.tokens_flops(tiny_cfg, 32, 8) + code.tokens_flops(tiny_cfg, 40, 3)
        + 4 * work.lm_head_flops(tiny_cfg))


def test_full_size_counts(code):
    """The served configuration: 2.055 B parameters; a decode step of 8
    rows reaches 6.3 of the 9 held experts a layer, an inject of 128
    tokens all of them; a slot holds 9 layers of SSM state and 256
    positions of one layer's keys and values."""
    conf = cell.read_json(os.path.join(cell.BENCH, "configs",
                                       "granite-4.0-h-small.json"))
    cfg = cell.model_config(conf)
    assert cfg.param_count() == 2_054_954_240
    assert code.experts_reached(cfg, 8) == pytest.approx(
        9 * (1 - (62 / 72) ** 8))
    assert code.experts_reached(cfg, 128) == pytest.approx(9, abs=1e-6)
    kv = 4 * 2 * 256 * 8 * 128
    ssm = 4 * 9 * (128 * 64 * 128 + 3 * (8192 + 256))
    assert code.state_bytes(cfg, 256) == ssm + kv
    s = conf["serving"]
    w = code.slate(cfg, s["max_batch"], s["slate_len"], 272)
    peak = cell.peaks("TPU v5 lite")
    # the slate's least time is set by its bytes (weights each step)
    assert w["bytes"] / peak["hbm_bytes_per_s"] > \
        w["flops"] / peak["flops_per_s"]


def test_slate_roofline_reads_the_granite_counts(code):
    """The new reader: a slate run at its least time, plus a finalize,
    reads just under 100%; no slate run reads nothing."""
    from bench import metrics_io
    conf = cell.read_json(os.path.join(cell.BENCH, "configs",
                                       "granite-4.0-h-small.json"))
    cfg = cell.model_config(conf)
    peak = cell.peaks("TPU v5 lite")
    lt = work.least_time(code.slate(cfg, 8, 8, 272), peak)
    r = metrics_io.load_reader("slate_roofline")
    ctx = {"module_s": {"jit__slate_impl@engine.decode_slate": 2 * lt,
                        "jit__finalize_impl@engine.finalize": 1e-4},
           "module_n": {"jit__slate_impl@engine.decode_slate": 2,
                        "jit__finalize_impl@engine.finalize": 2},
           "conf": conf, "cfg": cfg, "work": cell.config_code(conf),
           "peak": peak}
    v = r.read(ctx)
    assert 99.0 < v < 100.0
    ctx["module_n"] = {}
    assert r.read(ctx) is None


def test_tiny_cell_runs_correct_through_the_module(tmp_path, monkeypatch):
    """The module beside a tiny granite-shaped configuration: the cell
    runs from files, every request served, and the check (the module's
    reference at HIGHEST) finds the served slates correct."""
    monkeypatch.setitem(tiny.TINY_MODEL, "granite",
                        dict(copy.deepcopy(GRANITE)))
    with open(MODULE) as f:
        monkeypatch.setitem(tiny.CODE, "granite", f.read())
    conf = tiny.tiny_config("granite")
    name = tiny.write_bench(str(tmp_path), "granite", "session", 40,
                            code=True)
    spec = cell.load_spec(name, os.path.join(str(tmp_path), "bench"))
    assert spec.config["model"] == conf["model"]
    r = harness.run(spec, SEED, 1.5, False, require_tpu=False)
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] == 60


def test_control_decoding_in_fp8_reads_further_than_served(tmp_path,
                                                            monkeypatch):
    """The fp8 control in the program's place: its widest gap is larger
    than the served slates' (calibrate.py sets the chip's limit
    between them)."""
    monkeypatch.setitem(tiny.TINY_MODEL, "granite",
                        dict(copy.deepcopy(GRANITE)))
    with open(MODULE) as f:
        monkeypatch.setitem(tiny.CODE, "granite", f.read())
    name = tiny.write_bench(str(tmp_path), "granite", "session", 40,
                            code=True)
    spec = cell.load_spec(name, os.path.join(str(tmp_path), "bench"))
    r = harness.run(spec, SEED, 1.5, False, require_tpu=False,
                    control="fp8")
    assert r["check"]["logit_gap"]["value"] > r["_served_gap"]

