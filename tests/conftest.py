"""Shared test scaffolding.

Path setup + the engine/gateway **fixture factory** the serving-side
test modules (test_serving_loop / test_serving_api / test_state_pool /
test_rollover / test_scenarios) build their platforms from, replacing
the per-module copies of the same tiny arch + seeded feature plane.

One engine per (mesh,) is cached for the whole session — the jit caches
live on the engine, so sharing it across modules means each pane shape
compiles once per run instead of once per file. Params come from
``PRNGKey(0)`` at fixed shapes, so every module still sees bitwise the
same model the per-module blocks used to build.

Import the helpers directly (tests/ is rootdir-style, so ``conftest``
is importable):

    from conftest import (DAY, FEATURE_LEN, N_ITEMS, N_USERS,
                          make_gateway, seeded_injector, tiny_engine)
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Tests run against the single real CPU device — the forced-host-device
# trick (benchmarks serving_sharded; the subprocess spawned by
# tests/test_serving_sharded.py) must never leak into this
# process: jax locks the device count at first init, so a leaked flag
# would silently change every test's device topology.
assert "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""), \
    "forced-host-device XLA_FLAGS must not leak into the test environment"

DAY = 86400
N_USERS, N_ITEMS = 40, 300
FEATURE_LEN = 24

_ENGINES = {}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-wave / long-trace cases "
        "(deselect with -m 'not slow')")


def tiny_model_config(name="tiny-test"):
    """The shared 2-layer/64-wide dense ranker every serving test uses:
    small enough to prefill in milliseconds, deep enough that KV layout
    and cache handoff bugs still surface."""
    from repro.configs.base import ModelConfig
    return ModelConfig(name=name, family="dense", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, d_ff=128,
                       vocab_size=N_ITEMS + 256, rope_theta=1e4,
                       tie_embeddings=True)


def tiny_engine(mesh1x1=False, **scfg_kw):
    """Session-cached ServingEngine on the tiny arch (max_batch=4,
    prefill_len=32, inject_len=8 unless overridden). ``mesh1x1`` routes
    through the sharded code path on a 1x1 serving mesh. Engines with
    non-default serving shapes are cached per shape."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import init_params
    from repro.serving.engine import ServingConfig, ServingEngine

    scfg_kw.setdefault("max_batch", 4)
    scfg_kw.setdefault("prefill_len", 32)
    scfg_kw.setdefault("inject_len", 8)
    scfg_kw.setdefault("cache_capacity", 64)
    key = (mesh1x1,) + tuple(sorted(scfg_kw.items()))
    if key not in _ENGINES:
        mesh = None
        if mesh1x1:
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(1, 1)
        cfg = tiny_model_config()
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        _ENGINES[key] = ServingEngine(cfg, params, ServingConfig(**scfg_kw),
                                      mesh=mesh)
    return _ENGINES[key]


def seed_events(seed=0, n=1500, t_hi=5 * DAY):
    """The canonical seeded history: n events over [0, t_hi) uniform in
    (user, item)."""
    import numpy as np
    rng = np.random.RandomState(seed)
    return (rng.randint(0, N_USERS, n), rng.randint(0, N_ITEMS, n),
            rng.randint(0, t_hi, n))


def seeded_injector(policy="inject", snapshot_offset=0, events=None,
                    seed=0):
    """Batch store + realtime service, both fed the same seeded event
    stream, behind a FeatureInjector with the given policy."""
    from repro.core.feature_store import (BatchFeatureStore,
                                          FeatureStoreConfig)
    from repro.core.injection import FeatureInjector, InjectionConfig
    from repro.core.realtime import RealtimeConfig, RealtimeFeatureService

    store = BatchFeatureStore(FeatureStoreConfig(
        n_users=N_USERS, feature_len=FEATURE_LEN,
        snapshot_offset=snapshot_offset))
    rts = RealtimeFeatureService(RealtimeConfig(
        n_users=N_USERS, buffer_len=8, ingest_latency=0))
    us, its, tss = events if events is not None else seed_events(seed)
    store.extend(us, its, tss)
    rts.extend(us, its, tss)
    return FeatureInjector(
        InjectionConfig(policy=policy, feature_len=FEATURE_LEN), store, rts)


def make_gateway(policy="inject", engine=None, injector=None,
                 snapshot_offset=0, events=None, seed=0, **cfg_kw):
    """Gateway over the shared tiny engine + a freshly seeded platform.
    ``cfg_kw`` goes straight into ServerConfig (slate_len defaults to 3,
    cache_entries to 64, matching the historical per-module setups)."""
    from repro.serving.scheduler import Gateway, ServerConfig

    cfg_kw.setdefault("slate_len", 3)
    cfg_kw.setdefault("cache_entries", 64)
    inj = injector or seeded_injector(policy, snapshot_offset, events, seed)
    return Gateway(engine if engine is not None else tiny_engine(),
                   inj, ServerConfig(**cfg_kw))


def ingest(gw, users, items, ts):
    """Feed (user, item, ts) triples through the gateway's observe
    surface one event at a time (the trickle path)."""
    for u, i, t in zip(users, items, ts):
        gw.observe((int(u), int(i), int(t)))
