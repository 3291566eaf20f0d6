"""Serving engine: prefill → inject → decode equals one unpadded pass.

This is the TPU-native form of the paper's claim: injected fresh events
change the model state exactly as if they had been part of the batch
history all along — at O(suffix) cost.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.models.model import forward, init_params
from repro.serving import engine as engine_mod
from repro.serving.engine import ServingConfig, ServingEngine

ARCHS = ["llama3.2-1b", "mamba2-780m", "jamba-v0.1-52b", "mixtral-8x22b"]


def _engine(arch, **kw):
    cfg = reduced(get_config(arch))
    if cfg.moe:
        cfg = dataclasses.replace(cfg, moe=cfg.moe.no_drop())
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    scfg = ServingConfig(max_batch=2, prefill_len=24, inject_len=8,
                         cache_capacity=64, **kw)
    return cfg, params, ServingEngine(cfg, params, scfg)


@pytest.mark.parametrize("arch", ARCHS)
def test_inject_then_decode_matches_oracle(arch):
    cfg, params, eng = _engine(arch)
    hists = [[5, 7, 9, 11, 13, 2, 4, 6], [100, 101, 102]]
    fresh = [[21, 22, 23], [30]]
    nxt = [50, 60]

    toks, valid = eng.pad_tokens(hists, 24)
    st = eng.prefill(toks, valid)
    stoks, svalid = eng.pad_tokens(fresh, 8, align="left")
    st = eng.inject(st, stoks, svalid)
    dec = eng.finalize(st)
    logits, dec = eng.decode(dec, np.array([[t] for t in nxt], np.int32))

    for row in range(2):
        stream = hists[row] + fresh[row] + [nxt[row]]
        ref, _ = forward(params, cfg, jnp.asarray([stream], jnp.int32))
        np.testing.assert_allclose(np.asarray(ref[0, -1]),
                                   np.asarray(logits[row]),
                                   atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-780m"])
def test_double_injection(arch):
    """Two injection rounds (two request waves) still exact."""
    cfg, params, eng = _engine(arch)
    hists = [[3, 1, 4, 1, 5], [9, 2, 6]]
    f1 = [[10, 11], [12]]
    f2 = [[13], [14, 15]]
    toks, valid = eng.pad_tokens(hists, 24)
    st = eng.prefill(toks, valid)
    for f in (f1, f2):
        stoks, svalid = eng.pad_tokens(f, 8, align="left")
        st = eng.inject(st, stoks, svalid)
    dec = eng.finalize(st)
    logits, _ = eng.decode(dec, np.array([[7], [8]], np.int32))
    for row in range(2):
        stream = hists[row] + f1[row] + f2[row] + [7 + row]
        ref, _ = forward(params, cfg, jnp.asarray([stream], jnp.int32))
        np.testing.assert_allclose(np.asarray(ref[0, -1]),
                                   np.asarray(logits[row]),
                                   atol=2e-3, rtol=2e-3)


def test_injection_changes_prediction():
    """Freshness matters: injecting events must move the logits."""
    cfg, params, eng = _engine("llama3.2-1b")
    hists = [[5, 7, 9, 11], [1, 2, 3]]
    toks, valid = eng.pad_tokens(hists, 24)
    st = eng.prefill(toks, valid)
    dec_stale = eng.finalize(st)
    l_stale, _ = eng.decode(dec_stale, np.array([[50], [60]], np.int32))

    stoks, svalid = eng.pad_tokens([[21, 22], [30]], 8, align="left")
    st2 = eng.inject(st, stoks, svalid)
    dec_fresh = eng.finalize(st2)
    l_fresh, _ = eng.decode(dec_fresh, np.array([[50], [60]], np.int32))
    assert float(jnp.max(jnp.abs(l_stale - l_fresh))) > 1e-3


def test_pad_tokens_empty_rows():
    """Empty sequences produce all-pad rows (and so do absent rows)."""
    _, _, eng = _engine("llama3.2-1b")
    toks, valid = eng.pad_tokens([[], [1, 2]], 8)
    assert toks.shape == (2, 8) and valid.shape == (2, 8)
    assert valid[0].sum() == 0 and toks[0].sum() == 0
    np.testing.assert_array_equal(toks[1, -2:], [1, 2])
    # batch with fewer rows than max_batch: trailing rows are pad-only
    toks, valid = eng.pad_tokens([[3]], 8)
    assert valid[1].sum() == 0


def test_pad_tokens_truncation_keeps_tail():
    """Sequences longer than ``length`` keep the most recent tokens, for
    both alignments."""
    _, _, eng = _engine("llama3.2-1b")
    seq = list(range(1, 13))  # longer than length=8
    toks, valid = eng.pad_tokens([seq], 8)
    np.testing.assert_array_equal(toks[0], seq[-8:])
    assert valid[0].all()
    toks, valid = eng.pad_tokens([seq], 8, align="left")
    np.testing.assert_array_equal(toks[0], seq[-8:])
    assert valid[0].all()


def test_pad_tokens_raises_beyond_max_batch():
    """Inputs past max_batch raise instead of silently dropping requests —
    callers with larger waves must pane-split (serving/loop.py does)."""
    _, _, eng = _engine("llama3.2-1b")  # max_batch=2
    with pytest.raises(ValueError, match="max_batch"):
        eng.pad_tokens([[1], [2], [3], [4]], 8)
    # exactly max_batch still fine
    toks, valid = eng.pad_tokens([[1], [2]], 8)
    assert toks.shape == (2, 8)


def test_pad_tokens_left_alignment():
    _, _, eng = _engine("llama3.2-1b")
    toks, valid = eng.pad_tokens([[5, 6], []], 8, align="left")
    np.testing.assert_array_equal(toks[0, :2], [5, 6])
    assert valid[0, :2].all() and not valid[0, 2:].any()
    assert valid[1].sum() == 0


def test_greedy_sample():
    cfg, params, eng = _engine("llama3.2-1b")
    logits = jnp.zeros((2, cfg.vocab_padded)).at[0, 5].set(9.).at[1, 7].set(9.)
    tok = eng.sample(logits)
    assert tok.tolist() == [5, 7]


# ----------------------------------------------------------------------
# The served weight copy (rounded to bfloat16 where the chip rounds
# float32 products): engaged here by describing the engine's platform as
# a TPU, since the CPU never rounds them
# ----------------------------------------------------------------------

# leaves read otherwise than as a matmul operand: the embedding lookup,
# conv taps and biases, norm scales, A_log, D and dt_bias
KEPT_FLOAT32 = {"table", "conv_x", "conv_B", "conv_C", "conv_bias_x",
                "conv_bias_B", "conv_bias_C", "scale", "norm_scale",
                "A_log", "D", "dt_bias"}


def _as_on_tpu(cfg, params, scfg, mesh=None):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "_platform", lambda mesh: "tpu")
        return ServingEngine(cfg, params, scfg, mesh=mesh)


def _served_case(kind):
    if kind == "ssm":
        cfg = reduced(get_config("mamba2-780m"))
    else:
        from test_granite_hybrid import CFG as cfg
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params, ServingConfig(max_batch=2, prefill_len=24,
                                      inject_len=8, cache_capacity=64)


def _serve_pane(eng):
    """prefill -> inject (with fallback scores) -> slate on one pane."""
    toks, valid = eng.pad_tokens([[5, 7, 9, 11, 13, 2, 4], [40, 41]], 24)
    st = eng.prefill(toks, valid)
    inj = eng.inject(st, *eng.pad_tokens([[21, 22, 23], []], 8,
                                         align="left"),
                     fallback_logits=st["logits"][:, -1])
    slate = eng.decode_slate(inj, inj["first_logits"], 5)
    return [np.asarray(st["logits"]), np.asarray(inj["logits"]),
            np.asarray(inj["first_logits"]), slate]


@pytest.mark.parametrize("kind", ["ssm", "hybrid-moe"])
def test_rounded_copy_serves_the_round_tripped_weights(kind):
    """With the copy engaged, every float32 leaf but the kept ones is
    held in bfloat16 and the kept ones are the params' own arrays; prefill
    and inject logits and slate ids equal, bit for bit, an engine that
    reads float32 params whose rounded leaves went through bfloat16."""
    cfg, params, scfg = _served_case(kind)
    eng = _as_on_tpu(cfg, params, scfg)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    served = jax.tree.leaves(eng.served_params)
    for (path, p), s in zip(flat, served):
        if path[-1].key in KEPT_FLOAT32:
            assert s is p and s.dtype == jnp.float32, path
        else:
            assert s.dtype == jnp.bfloat16, path
    assert eng.rounded_weight_bytes == sum(
        s.nbytes for s in served if s.dtype == jnp.bfloat16) > 0
    trip = jax.tree.map(lambda p, s: s.astype(jnp.float32)
                        if s.dtype == jnp.bfloat16 else p,
                        params, eng.served_params)
    want = _serve_pane(ServingEngine(cfg, trip, scfg))
    for got, ref in zip(_serve_pane(eng), want):
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("case", ["cpu", "highest", "bfloat16"])
def test_served_tree_is_params_where_nothing_rounds(case):
    """No copy, and 0 rounded bytes, on a CPU backend, under HIGHEST
    precision, and for a bfloat16 tree."""
    cfg, params, scfg = _served_case("ssm")
    if case == "cpu":
        eng = ServingEngine(cfg, params, scfg)
    elif case == "highest":
        with jax.default_matmul_precision("highest"):
            eng = _as_on_tpu(cfg, params, scfg)
    else:
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = _as_on_tpu(cfg, params, scfg)
    assert eng.served_params is eng.params
    assert eng.rounded_weight_bytes == 0
    from conftest import make_gateway
    assert make_gateway(engine=eng).stats().rounded_weight_bytes == 0


def test_call_under_highest_reads_the_float32_params():
    """An engine whose copy is engaged still serves the float32 params
    to a call made under HIGHEST precision."""
    cfg, params, scfg = _served_case("ssm")
    eng = _as_on_tpu(cfg, params, scfg)
    assert eng.served_params is not eng.params
    with jax.default_matmul_precision("highest"):
        got = _serve_pane(eng)
        want = _serve_pane(ServingEngine(cfg, params, scfg))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_patch_rounds_only_the_patched_leaves():
    """``apply_patch`` rounds the patched matmul leaf again, passes a
    patched float32-kept leaf through, keeps every other leaf of the
    copy, and the next slate reads the new values."""
    cfg, params, scfg = _served_case("hybrid-moe")
    eng = _as_on_tpu(cfg, params, scfg)
    from conftest import make_gateway
    assert (make_gateway(engine=eng).stats().rounded_weight_bytes
            == eng.rounded_weight_bytes > 0)
    before = dict(jax.tree_util.tree_flatten_with_path(
        eng.served_params)[0])
    wx = "['blocks']['pos0']['ssm']['wx']"
    norm = "['final_norm']['scale']"
    flat = {jax.tree_util.keystr(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    noise = jax.random.normal(jax.random.PRNGKey(1), flat[wx].shape)
    patch = {wx: flat[wx] + noise, norm: flat[norm] * 0.5}
    old_slate = _serve_pane(eng)[-1]
    assert eng.apply_patch(patch) == 2
    after = jax.tree_util.tree_flatten_with_path(eng.served_params)[0]
    for path, s in after:
        key = jax.tree_util.keystr(path)
        if key == wx:
            assert s.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(s), np.asarray(patch[wx].astype(jnp.bfloat16)))
        elif key == norm:
            assert s is eng.params["final_norm"]["scale"]
        else:
            assert s is before[path], key
    new_params = jax.tree_util.tree_map_with_path(
        lambda p, x: patch.get(jax.tree_util.keystr(p), x), params)
    want = _serve_pane(_as_on_tpu(cfg, new_params, scfg))
    got = _serve_pane(eng)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert not np.array_equal(got[-1], old_slate)
