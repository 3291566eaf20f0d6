"""Sharded serving path: spec resolution, byte-accounted LRU, and
sharded-vs-single-device equivalence.

Two layers of coverage:
  * in-process — a degenerate 1×1 mesh runs the FULL sharded code path
    (serving_pspecs resolution, NamedSharding jits, device_put placement)
    on the single real CPU device and must be bit-identical to the
    mesh-free engine;
  * subprocess — tools/sharded_equiv_check.py forces 8 host devices (the
    dry-run XLA_FLAGS pattern) in a child process and asserts slate
    identity across a real 8-way data-parallel mesh. A subprocess keeps
    the forced device count out of this process (conftest.py asserts the
    flag never leaks into the tier-1 environment).
"""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.feature_store import BatchFeatureStore, FeatureStoreConfig
from repro.core.injection import FeatureInjector, InjectionConfig
from repro.core.realtime import RealtimeConfig, RealtimeFeatureService
from repro.launch.mesh import make_serving_mesh
from repro.models.model import init_params, param_shapes, prefill
from repro.serving import engine as engine_mod
from repro.serving.engine import ServingConfig, ServingEngine
from repro.serving.loop import InjectionServer, PrefillStateCache, ServerConfig
from repro.sharding.rules import seq_cache_pspecs, serving_pspecs

DAY = 86400
N_USERS, N_ITEMS = 40, 300

_CFG = ModelConfig(name="shard-test", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128,
                   vocab_size=N_ITEMS + 256, rope_theta=1e4,
                   tie_embeddings=True)
_PARAMS = init_params(_CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
_SCFG = ServingConfig(max_batch=4, prefill_len=32, inject_len=8,
                      cache_capacity=64)


def _server(mesh=None, cache_bytes=None, use_cache=True):
    store = BatchFeatureStore(FeatureStoreConfig(
        n_users=N_USERS, feature_len=24))
    rts = RealtimeFeatureService(RealtimeConfig(
        n_users=N_USERS, buffer_len=8, ingest_latency=0))
    rng = np.random.RandomState(0)
    store.extend(rng.randint(0, N_USERS, 1500),
                 rng.randint(0, N_ITEMS, 1500),
                 rng.randint(0, 5 * DAY, 1500))
    rng = np.random.RandomState(0)
    rts.extend(rng.randint(0, N_USERS, 1500),
               rng.randint(0, N_ITEMS, 1500),
               rng.randint(0, 5 * DAY, 1500))
    inj = FeatureInjector(InjectionConfig(policy="inject", feature_len=24),
                          store, rts)
    eng = ServingEngine(_CFG, _PARAMS, _SCFG, mesh=mesh)
    return InjectionServer(eng, inj, ServerConfig(
        slate_len=3, cache_entries=64, cache_bytes=cache_bytes,
        use_cache=use_cache))


# ----------------------------------------------------------------------
# In-process: the sharded code path on a 1×1 mesh == the plain engine
# ----------------------------------------------------------------------

def test_mesh_1x1_bitwise_equals_plain_engine():
    plain, sharded = _server(mesh=None), _server(mesh=make_serving_mesh(1, 1))
    assert sharded.engine.data_shards == 1
    now = 5 * DAY + 100
    rng = np.random.RandomState(1)
    for wave in range(3):  # miss wave, then hit waves with fresh suffixes
        u = rng.randint(0, N_USERS, 6)
        for srv in (plain, sharded):
            srv.injector.batch.extend(u, (u + 3) % N_ITEMS,
                                      np.full(6, now - 30))
            srv.injector.realtime.extend(u, (u + 3) % N_ITEMS,
                                         np.full(6, now - 30))
        q = rng.randint(0, N_USERS, 9)
        rp, rs = plain.serve(q, now), sharded.serve(q, now)
        # one device, identical op order -> identical floats, not just close
        np.testing.assert_array_equal(rp.scores, rs.scores)
        np.testing.assert_array_equal(rp.slate, rs.slate)
        now += 200
    assert sharded.cache.hits > 0


def test_rounded_copy_keeps_the_params_shardings():
    """With the served copy engaged (the platform described as a TPU),
    every leaf of the copy on the mesh has its param's sharding, the
    matmul leaves in bfloat16, and the mesh engine serves what the
    plain one does."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "_platform", lambda mesh: "tpu")
        sharded = ServingEngine(_CFG, _PARAMS, _SCFG,
                                mesh=make_serving_mesh(1, 1))
        plain = ServingEngine(_CFG, _PARAMS, _SCFG)
    served = jax.tree_util.tree_flatten_with_path(sharded.served_params)[0]
    for (path, s), p in zip(served, jax.tree.leaves(sharded.params)):
        assert s.sharding == p.sharding, path
        assert s.dtype == (jnp.bfloat16 if path[-1].key in
                           ("wq", "wk", "wv", "wo", "gate", "up", "down")
                           else jnp.float32), path
    assert sharded.rounded_weight_bytes == plain.rounded_weight_bytes > 0
    toks, valid = plain.pad_tokens([[5, 7, 9], [11, 12]], 32)
    np.testing.assert_array_equal(
        np.asarray(sharded.prefill(toks, valid)["logits"]),
        np.asarray(plain.prefill(toks, valid)["logits"]))


def test_mesh_engine_rejects_uneven_batch():
    mesh = AbstractMesh((8, 1), ("data", "model"))
    with pytest.raises(ValueError, match="multiple of the data-axis"):
        serving_pspecs(_CFG, mesh, max_batch=6)


def test_serving_params_replicated_over_data():
    """Serving replicates weights across data-parallel replicas: no param
    spec may reference the data axis (FSDP is stripped), while cache and
    token specs must shard their batch dim over it."""
    mesh = AbstractMesh((8, 2), ("data", "model"))
    sp = serving_pspecs(_CFG, mesh, max_batch=16)

    def axes_of(spec):
        out = set()
        for ax in spec:
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                if a is not None:
                    out.add(a)
        return out

    for spec in jax.tree.leaves(sp.params,
                                is_leaf=lambda x: isinstance(x, P)):
        assert "data" not in axes_of(spec), spec
    assert "data" in axes_of(sp.tokens)
    assert sp.data_shards == 8
    for spec in jax.tree.leaves(sp.seq_caches,
                                is_leaf=lambda x: isinstance(x, P)):
        assert axes_of(spec) <= {"data", "model"}


@pytest.mark.parametrize("arch_cfg", [_CFG], ids=["dense"])
def test_seq_cache_specs_match_prefill_tree(arch_cfg):
    """seq_cache_pspecs must mirror the exact pytree prefill returns —
    a structure mismatch would fail deep inside jit out_shardings."""
    mesh = AbstractMesh((2, 1), ("data", "model"))
    specs = seq_cache_pspecs(arch_cfg, mesh, batch=4)
    shapes = param_shapes(arch_cfg, dtype=jnp.float32)
    toks = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    valid = jax.ShapeDtypeStruct((4, 16), jnp.bool_)
    _, caches = jax.eval_shape(
        lambda p, t, v: prefill(p, arch_cfg, t, valid=v), shapes, toks, valid)
    # same treedef (tree.map raises otherwise) and rank compatibility
    jax.tree.map(
        lambda s, spec: None if len(spec) <= len(s.shape) else
        pytest.fail(f"{spec} too long for {s.shape}"),
        caches, specs, is_leaf=lambda x: isinstance(x, P))


# ----------------------------------------------------------------------
# Byte-accounted LRU
# ----------------------------------------------------------------------

def _entry(kbytes):
    return {"caches": {"k": np.zeros((kbytes * 1024 // 4,), np.float32)},
            "last_logits": np.zeros((0,), np.float32)}


def test_cache_byte_accounting_per_shard():
    c = PrefillStateCache(budget=100, shards=4)
    c.put(1, 0, _entry(64))
    assert c.bytes_per_shard == 64 * 1024 // 4
    c.put(1, 0, _entry(32))  # replacement accounts delta, not sum
    assert c.bytes_per_shard == 32 * 1024 // 4
    c.invalidate_except(99)
    assert c.bytes_per_shard == 0 and len(c) == 0
    assert c.stats()["shards"] == 4


def test_cache_byte_budget_evicts_lru():
    c = PrefillStateCache(budget=100, byte_budget=100 * 1024, shards=1)
    for u in range(4):
        c.put(u, 0, _entry(40))  # 4 * 40KiB > 100KiB -> keep newest 2
    assert len(c) == 2 and c.evictions == 2
    assert c.get(0, 0) is None and c.get(3, 0) is not None
    assert c.bytes_per_shard <= 100 * 1024


def test_cache_byte_budget_always_keeps_newest():
    """A byte budget smaller than one entry must still admit the entry
    the current pane is about to serve from."""
    c = PrefillStateCache(budget=100, byte_budget=1024, shards=1)
    c.put(7, 0, _entry(64))
    assert len(c) == 1 and c.get(7, 0) is not None


def test_server_tracks_entry_bytes():
    srv = _server(mesh=make_serving_mesh(1, 1))
    srv.serve(np.arange(8), 5 * DAY + 100)
    st = srv.cache.stats()
    assert st["entries"] == 8
    # sanity: per-entry cost is the sliced sequence-form state, nonzero
    # and far below the full-pane footprint
    assert 0 < st["bytes_per_shard"] < 64 * 2 ** 20


def test_warm_stops_at_byte_budget():
    """warm() must not keep prefilling once the byte budget is full —
    the extra states would evict each other before ever serving."""
    srv = _server(mesh=make_serving_mesh(1, 1), cache_bytes=300_000)
    warmed = srv.warm(np.arange(40), 5 * DAY + 100)
    # stopped within one pane of the first byte-pressure eviction,
    # far short of all 40 users
    assert warmed < 40
    assert warmed <= len(srv.cache) + srv.engine.scfg.max_batch


def test_sampled_slate_decode_raises():
    """A temperature>0 engine must fail loudly, not silently serve
    greedy slates."""
    import dataclasses as _dc
    eng = ServingEngine(_CFG, _PARAMS,
                        _dc.replace(_SCFG, temperature=0.7))
    with pytest.raises(NotImplementedError, match="greedy-only"):
        eng.decode_slate({"caches": None}, None, 3)


def test_byte_budget_eviction_stays_correct():
    """Serving under heavy byte pressure (entries evict constantly) must
    still match the uncached oracle — eviction can cost speed, never
    correctness."""
    tight = _server(mesh=make_serving_mesh(1, 1), cache_bytes=300_000)
    oracle = _server(mesh=None, use_cache=False)
    now = 5 * DAY + 100
    for lo in (0, 8, 0):
        q = np.arange(lo, lo + 8) % N_USERS
        rt, ro = tight.serve(q, now), oracle.serve(q, now)
        np.testing.assert_allclose(rt.scores, ro.scores, atol=2e-3,
                                   rtol=2e-3)
        np.testing.assert_array_equal(rt.slate, ro.slate)
    assert tight.cache.evictions > 0


# ----------------------------------------------------------------------
# Subprocess: real 8-device mesh (dry-run XLA_FLAGS pattern)
# ----------------------------------------------------------------------

def test_sharded_equivalence_on_8_host_devices():
    root = os.path.join(os.path.dirname(__file__), "..")
    script = os.path.join(root, "tools", "sharded_equiv_check.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep +
                         env.get("PYTHONPATH", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "SHARDED-EQUIV OK" in out.stdout
