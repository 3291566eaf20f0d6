"""Config registry: the 10 assigned architectures, the benchmark's
granite-4.0-h-small, and shapes."""
import dataclasses

import pytest

from repro.configs.archs import ASSIGNED
from repro.configs.base import get_config, list_configs, pad_vocab, reduced
from repro.configs.shapes import SHAPES, get_shape

EXPECTED = {
    "mamba2-780m": dict(n_layers=48, d_model=1536, vocab_size=50280),
    "granite-moe-3b-a800m": dict(n_layers=32, d_model=1536, n_heads=24,
                                 n_kv_heads=8, d_ff=512, vocab_size=49155),
    "llama3.2-1b": dict(n_layers=16, d_model=2048, n_heads=32, n_kv_heads=8,
                        d_ff=8192, vocab_size=128256),
    "mixtral-8x22b": dict(n_layers=56, d_model=6144, n_heads=48,
                          n_kv_heads=8, d_ff=16384, vocab_size=32768),
    "musicgen-large": dict(n_layers=48, d_model=2048, n_heads=32,
                           n_kv_heads=32, d_ff=8192, vocab_size=2048),
    "codeqwen1.5-7b": dict(n_layers=32, d_model=4096, n_heads=32,
                           n_kv_heads=32, d_ff=13440, vocab_size=92416),
    "command-r-plus-104b": dict(n_layers=64, d_model=12288, n_heads=96,
                                n_kv_heads=8, d_ff=33792, vocab_size=256000),
    "llava-next-34b": dict(n_layers=60, d_model=7168, n_heads=56,
                           n_kv_heads=8, d_ff=20480, vocab_size=64000),
    "jamba-v0.1-52b": dict(n_layers=32, d_model=4096, n_heads=32,
                           n_kv_heads=8, d_ff=14336, vocab_size=65536),
    "deepseek-67b": dict(n_layers=95, d_model=8192, n_heads=64,
                         n_kv_heads=8, d_ff=22016, vocab_size=102400),
    # hf:ibm-granite/granite-4.0-h-small config.json
    "granite-4.0-h-small": dict(n_layers=40, d_model=4096, n_heads=32,
                                n_kv_heads=8, head_dim=128, d_ff=768,
                                vocab_size=100352, tie_embeddings=True,
                                position_embedding="none",
                                embedding_multiplier=12.0,
                                residual_multiplier=0.22,
                                attention_multiplier=0.0078125,
                                logits_scaling=16.0),
}
# registered beside the assigned ten: served by the benchmark
REGISTERED = ASSIGNED + ("granite-4.0-h-small",)

# hyperparameters straight from the assignment
MOE = {"granite-moe-3b-a800m": (40, 8), "mixtral-8x22b": (8, 2),
       "jamba-v0.1-52b": (16, 2), "granite-4.0-h-small": (72, 10)}


def test_all_assigned_registered():
    for a in ASSIGNED:
        assert a in list_configs()
    assert len(ASSIGNED) == 10


@pytest.mark.parametrize("arch", REGISTERED)
def test_exact_assigned_hparams(arch):
    cfg = get_config(arch)
    for k, v in EXPECTED[arch].items():
        assert getattr(cfg, k) == v, (arch, k)
    if arch in MOE:
        assert (cfg.moe.n_experts, cfg.moe.top_k) == MOE[arch]
    cfg.validate()


def test_families_span_six_types():
    fams = {get_config(a).family for a in ASSIGNED}
    assert fams == {"ssm", "moe", "dense", "audio", "vlm", "hybrid"}


@pytest.mark.parametrize("arch,approx_b", [
    ("mamba2-780m", 0.78), ("llama3.2-1b", 1.24), ("mixtral-8x22b", 141.0),
    ("deepseek-67b", 67.0), ("command-r-plus-104b", 104.0),
    ("jamba-v0.1-52b", 52.0), ("granite-4.0-h-small", 32.2),
])
def test_param_counts_match_names(arch, approx_b):
    n = get_config(arch).param_count() / 1e9
    assert approx_b * 0.7 < n < approx_b * 1.4, f"{arch}: {n:.1f}B"


def test_active_params_moe():
    cfg = get_config("mixtral-8x22b")
    # 8x22b: ~39B active of ~141B total
    assert cfg.active_param_count() < 0.35 * cfg.param_count()


def test_reduced_configs_are_small():
    for a in REGISTERED:
        r = reduced(get_config(a))
        assert r.n_layers == 2 and r.d_model <= 512
        if r.moe:
            assert r.moe.n_experts <= 4
        r.validate()


def test_granite_4_h_small_layout():
    """36 Mamba-2 mixers and 4 NoPE GQA mixers at layers 5, 15, 25, 35;
    an MoE block on every layer with a shared SwiGLU expert of 1536."""
    cfg = get_config("granite-4.0-h-small")
    kinds = cfg.layer_kinds()
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    assert kinds.count("ssm") == 36
    assert set(cfg.mlp_kinds()) == {"moe"}
    s = cfg.ssm
    assert (cfg.n_ssm_heads, s.head_dim, s.d_state, s.conv_width,
            s.chunk_size) == (128, 64, 128, 4, 256)
    assert cfg.moe.shared_d_ff == 1536 and cfg.moe.n_router == 72


def test_granite_4_h_small_param_count():
    """32B-A9B: the shared expert and the 72-wide router counted, every
    layer's experts and the tied 100352-row table once."""
    cfg = get_config("granite-4.0-h-small")
    assert 32.1e9 < cfg.param_count() < 32.3e9
    assert 8.5e9 < cfg.active_param_count() < 9.5e9
    d = cfg.d_model
    no_shared = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, shared_d_ff=0))
    assert cfg.param_count() - no_shared.param_count() == 40 * 3 * d * 1536


def test_shapes():
    assert get_shape("train_4k").tokens == 4096 * 256
    assert get_shape("long_500k").seq_len == 524288
    assert {s.kind for s in SHAPES.values()} == {"train", "prefill", "decode"}


def test_pad_vocab():
    assert pad_vocab(49155) == 49408
    assert pad_vocab(256) == 256
