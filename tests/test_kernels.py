"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles.

All kernels run in interpret mode: the kernel body executes in Python on
the CPU. That checks the math and the grid/block decomposition, but it
never lowers the kernel for the TPU, so it cannot see a block the chip's
tiling rule refuses or a body Mosaic cannot compile;
tests/test_tpu_compile.py compiles every kernel for a described TPU v5e
for that.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention, ring_bias
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.history_merge.ops import history_merge
from repro.kernels.history_merge.ref import (history_merge_python,
                                             history_merge_ref)
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref_sequential
from repro.models.ssm import ssd_chunked


def _tol(dtype):
    return dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

@pytest.mark.parametrize("s,nq,nkv,hd,causal,window,dtype", [
    (256, 4, 2, 64, True, 0, jnp.float32),
    (256, 4, 4, 64, False, 0, jnp.float32),
    (384, 8, 2, 128, True, 0, jnp.float32),      # pad path (384 % 128 != 0 ok)
    (256, 4, 1, 64, True, 128, jnp.float32),     # sliding window, MQA
    (256, 4, 2, 64, True, 0, jnp.bfloat16),
])
def test_flash_attention_vs_ref(s, nq, nkv, hd, causal, window, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    b = 2
    q = jax.random.normal(k1, (b, s, nq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (b, s, nkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (b, s, nkv, hd), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_k=128, interpret=True)
    ref = jnp.moveaxis(attention_ref(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        causal=causal, window=window), 2, 1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


# ----------------------------------------------------------------------
# decode attention
# ----------------------------------------------------------------------

@pytest.mark.parametrize("w,nq,nkv,hd,dtype", [
    (512, 4, 2, 64, jnp.float32),
    (1024, 8, 1, 128, jnp.float32),
    (512, 4, 4, 64, jnp.bfloat16),
])
def test_decode_attention_vs_ref(w, nq, nkv, hd, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    b = 3
    q = jax.random.normal(k1, (b, 1, nq, hd), jnp.float32).astype(dtype)
    kc = jax.random.normal(k2, (b, w, nkv, hd), jnp.float32).astype(dtype)
    vc = jax.random.normal(k3, (b, w, nkv, hd), jnp.float32).astype(dtype)
    pos = jnp.array([10, w // 2, 2 * w], jnp.int32)  # partial, half, wrapped
    out = decode_attention(q, kc, vc, pos, block_k=256, interpret=True)
    ref = jnp.moveaxis(decode_attention_ref(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(kc, 1, 2), jnp.moveaxis(vc, 1, 2),
        ring_bias(pos, w)), 2, 1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


# ----------------------------------------------------------------------
# SSD scan
# ----------------------------------------------------------------------

def _ssd_inputs(key, b, s, nh, hp, ds, dtype):
    ks = jax.random.split(key, 5)
    x = (jax.random.normal(ks[0], (b, s, nh, hp), jnp.float32) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    B = (jax.random.normal(ks[3], (b, s, ds)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[4], (b, s, ds)) * 0.3).astype(dtype)
    D = jnp.ones((nh,), jnp.float32)
    return x, dt, A, B, C, D


@pytest.mark.parametrize("s,nh,hp,ds,chunk,dtype", [
    (128, 8, 32, 64, 32, jnp.float32),
    (128, 4, 64, 128, 64, jnp.float32),
    (64, 2, 32, 32, 16, jnp.bfloat16),
])
def test_ssd_kernel_vs_sequential(s, nh, hp, ds, chunk, dtype):
    x, dt, A, B, C, D = _ssd_inputs(jax.random.PRNGKey(2), 2, s, nh, hp, ds, dtype)
    y, h = ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    yr, hr = ssd_ref_sequential(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=1e-3, rtol=1e-3)


def test_ssd_kernel_with_initial_state():
    x, dt, A, B, C, D = _ssd_inputs(jax.random.PRNGKey(3), 2, 64, 4, 32, 64,
                                    jnp.float32)
    h0 = jax.random.normal(jax.random.PRNGKey(4), (2, 4, 32, 64))
    y, h = ssd_scan(x, dt, A, B, C, D, chunk=32, init_state=h0, interpret=True)
    yr, hr = ssd_ref_sequential(x, dt, A, B, C, D, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5,
                               rtol=2e-5)


def test_ssd_chunked_jnp_matches_sequential():
    x, dt, A, B, C, D = _ssd_inputs(jax.random.PRNGKey(5), 2, 128, 8, 32, 64,
                                    jnp.float32)
    y, h = ssd_chunked(x, dt, A, B, C, D, chunk=32)
    yr, hr = ssd_ref_sequential(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5,
                               rtol=2e-5)


# ----------------------------------------------------------------------
# history merge (the paper's injection op)
# ----------------------------------------------------------------------

def _random_events(rng, b, lb, lr, n_items=25, tmax=1000):
    bi = rng.randint(0, n_items, (b, lb)).astype(np.int32)
    bt = rng.randint(0, tmax, (b, lb)).astype(np.int32)
    bv = (rng.rand(b, lb) < 0.8).astype(np.int32)
    ri = rng.randint(0, n_items, (b, lr)).astype(np.int32)
    rt = rng.randint(tmax // 2, 2 * tmax, (b, lr)).astype(np.int32)
    rv = (rng.rand(b, lr) < 0.8).astype(np.int32)
    return bi, bt, bv, ri, rt, rv


@pytest.mark.parametrize("lb,lr,k,seed", [
    (12, 6, 8, 0), (16, 8, 16, 1), (4, 12, 6, 2), (20, 4, 32, 3),
])
def test_history_merge_kernel_matches_python(lb, lr, k, seed):
    rng = np.random.RandomState(seed)
    arrs = _random_events(rng, 3, lb, lr)
    j = [jnp.asarray(a) for a in arrs]
    for impl in ("pallas_interpret", "xla"):
        oi, ot, ov = history_merge(*j, out_len=k, impl=impl)
        for row in range(3):
            batch = [(int(i), int(t)) for i, t, v in
                     zip(arrs[0][row], arrs[1][row], arrs[2][row]) if v]
            rt = [(int(i), int(t)) for i, t, v in
                  zip(arrs[3][row], arrs[4][row], arrs[5][row]) if v]
            want = history_merge_python(batch, rt, k)
            got = [(int(i), int(t)) for i, t, v in
                   zip(oi[row], ot[row], ov[row]) if v]
            assert got == want, (impl, row)


def test_history_merge_kernel_equals_xla_oracle():
    rng = np.random.RandomState(7)
    arrs = [jnp.asarray(a) for a in _random_events(rng, 4, 24, 12)]
    a = history_merge(*arrs, out_len=16, impl="pallas_interpret")
    b = history_merge(*arrs, out_len=16, impl="xla")
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
