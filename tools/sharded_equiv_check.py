import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
# ^ MUST precede any jax import: jax locks the device count on first
# init. This script is run as a
# SUBPROCESS by tests/test_serving_sharded.py precisely so the forced
# device count never leaks into the main test process (conftest.py
# asserts it doesn't).

"""Sharded-vs-single-device serving equivalence check.

Builds the same tiny model + feature plane twice — one request-level
Gateway on the plain single-device engine, one on an 8×1
("data","model") CPU mesh — and drives both through the same request
trace (per-request submits, interleaved ingest) including LRU-cached
hits, a mixed-policy wave (batch/inject/fresh rows sharing panes), and
TWO snapshot-generation rollovers: one crossed by a request's clock
mid-trace, one rolled explicitly by ``tick()`` between waves so the
warm handoff (rekeyed unchanged rows serving the next wave, changed
rows re-prefilled) is exercised and its telemetry compared across
meshes. Asserts slates are IDENTICAL and logits agree within float
tolerance at every wave.

  PYTHONPATH=src python tools/sharded_equiv_check.py

Prints ``SHARDED-EQUIV OK`` and exits 0 on success.
"""
import sys

import numpy as np


def main() -> int:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ModelConfig
    from repro.core.feature_store import (BatchFeatureStore,
                                          FeatureStoreConfig)
    from repro.core.injection import FeatureInjector, InjectionConfig
    from repro.core.realtime import RealtimeConfig, RealtimeFeatureService
    from repro.launch.mesh import make_serving_mesh
    from repro.models.model import init_params
    from repro.serving.api import Request
    from repro.serving.engine import ServingConfig, ServingEngine
    from repro.serving.scheduler import Gateway, ServerConfig

    assert len(jax.devices()) == 8, jax.devices()

    DAY = 86400
    n_users, n_items = 40, 300
    cfg = ModelConfig(name="equiv-test", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=n_items + 256, rope_theta=1e4,
                      tie_embeddings=True)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    scfg = ServingConfig(max_batch=8, prefill_len=32, inject_len=8,
                         cache_capacity=64)

    def server(mesh):
        store = BatchFeatureStore(FeatureStoreConfig(
            n_users=n_users, feature_len=24))
        rts = RealtimeFeatureService(RealtimeConfig(
            n_users=n_users, buffer_len=8, ingest_latency=0))
        rng = np.random.RandomState(0)
        u = rng.randint(0, n_users, 1500)
        i = rng.randint(0, n_items, 1500)
        t = rng.randint(0, 5 * DAY, 1500)
        store.extend(u, i, t)
        rts.extend(u, i, t)
        inj = FeatureInjector(InjectionConfig(
            policy="inject", feature_len=24), store, rts)
        eng = ServingEngine(cfg, params, scfg, mesh=mesh)
        return Gateway(eng, inj, ServerConfig(
            slate_len=3, cache_entries=64))

    single = server(mesh=None)
    sharded = server(mesh=make_serving_mesh(8, 1))

    rng = np.random.RandomState(1)
    now = 5 * DAY + 100
    policies = [None, "batch", "inject", "fresh"]
    # wave 1-3: interleaved ingest/serve inside one generation (misses,
    # then hits with fresh suffixes; wave 3 mixes per-request policies
    # in shared panes); wave 4: past the next snapshot boundary — the
    # generation rolls mid-trace (warm handoff: unchanged rows rekey,
    # changed rows re-prefill); wave 5: an explicit mid-trace tick()
    # rolls ANOTHER generation with only a handful of changed users,
    # then the wave serves mostly from rekeyed entries
    for wave, at in enumerate([now, now + 120, now + 300,
                               now + DAY + 100, now + 2 * DAY + 100]):
        if wave == 4:
            # events for a FEW users only, then roll the generation on
            # the clock before any request arrives: the rollover itself
            # is the thing under test here
            u5 = np.arange(5)
            it5 = rng.randint(0, n_items, 5)
            for gw in (single, sharded):
                gw.observe_many(u5, it5, np.full(5, at - 3600))
                gw.tick(at - 60)
            r1 = single.stats()["rollover"]
            r8 = sharded.stats()["rollover"]
            assert r1 == r8, f"rollover stats diverged\n{r1}\n{r8}"
            # changed users' old-gen entries are RETAINED through the
            # handoff window (first-victim under pressure), not purged
            assert r8["rekeyed"] > 0 and r8["retained"] > 0, r8
            assert single.cache.rekeys == sharded.cache.rekeys > 0
            print(f"mid-trace rollover: rekeyed={r8['rekeyed']} "
                  f"retained={r8['retained']} (both meshes)")
        u = rng.randint(0, n_users, 12)
        it = rng.randint(0, n_items, 12)
        ts = np.full(12, at - 40)
        for gw in (single, sharded):
            gw.observe_many(u, it, ts)
        q = rng.randint(0, n_users, 19)  # pane-splits at max_batch=8
        reqs = [Request(user=int(x), now=at,
                        policy=policies[j % 4] if wave == 2 else None)
                for j, x in enumerate(q)]
        out = []
        for gw in (single, sharded):
            tickets = [gw.submit(r) for r in reqs]  # trickle: pane-full
            gw.flush(at)                            # flushes + tail
            out.append((np.stack([t.response.slate for t in tickets]),
                        np.stack([t.response.scores for t in tickets]),
                        sum(t.response.telemetry.cache_hit
                            for t in tickets)))
        (s1, l1, h1), (s8, l8, h8) = out
        assert (s1 == s8).all(), \
            f"wave {wave}: slates diverged\n{s1}\n{s8}"
        assert h1 == h8, f"wave {wave}: hit counts diverged {h1} != {h8}"
        diff = np.abs(l1 - l8).max()
        assert diff < 2e-3, f"wave {wave}: logits max|Δ|={diff}"
        print(f"wave {wave}: slates equal, logits max|Δ|={diff:.2e}, "
              f"hits={h8}")
    assert sharded.cache.hits > 0 and sharded.cache.invalidations > 0
    assert sharded.cache.shards == 8
    assert sharded.stats()["paths"]["inject"] > 0
    print("SHARDED-EQUIV OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
