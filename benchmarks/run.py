"""Benchmark harness — one section per paper result/figure + kernel/serving
microbenches.

  PYTHONPATH=src python -m benchmarks.run [--only SECTION]
  PYTHONPATH=src python benchmarks/run.py --suite feature_plane [--smoke]

Sections
  ab_lift            paper §IV: A/B lift table (reads experiments/ab_report.json)
  latency_ablation   engagement vs feature staleness (same report)
  injection_overhead paper §III-B: history_merge op throughput
  serving_phases     prefill vs inject vs decode cost (O(suffix) claim)
  kernel_micro       Pallas-kernel oracle timings (XLA path on CPU)
  feature_plane      vectorized EventLog stores vs the loop reference
                     (snapshot materialization + batched lookups at
                     1k/100k/1M users; writes BENCH_feature_plane.json)
  serving            end-to-end InjectionServer: cached-inject vs
                     full-prefill-per-request under interleaved ingest at
                     1k/10k users (writes BENCH_serving.json)
  serving_sharded    the same loop data-parallel over 1/2/8-device
                     ("data","model") meshes — rps scaling + sharded-vs-
                     single-device equivalence (writes
                     BENCH_serving_sharded.json)
  scheduler          request-level Gateway (per-request submits through
                     the micro-batching scheduler) vs the legacy wave
                     path on the same traffic: throughput parity at 100%
                     hit rate + the per-request queue+serve latency
                     percentiles only the request API can measure, plus
                     the continuous scheduler over the paged device
                     state pool (max_wait=0: zero sim-time queue delay,
                     slates bitwise equal to the wave path, compiled
                     gather/scatter collective count recorded from
                     tools/slot_pool_check.py)
                     (writes BENCH_scheduler.json)
  rollover           the daily-boundary cost: eager purge + synchronous
                     snapshot build (legacy) vs warm handoff +
                     incremental build — boundary stall, post-rollover
                     first-wave prefill storm, miss-storm depth, p99
                     (writes BENCH_rollover.json)
  scenarios          production traffic regimes (diurnal / flash_crowd /
                     cold_start_storm / churn_heavy / mixed_fleet) from
                     the seeded trace generator, each gated on its SLO
                     contract; flash_crowd proves deadline-aware load
                     shedding bounds p99 (writes BENCH_scenarios.json)
  ingest             tiered sliding-window EventLog under sustained
                     ingest: bounded steady-state memory across window
                     rollovers, bitwise exactness vs an unbounded-log
                     oracle (late-arrival demotion included), and the
                     churn_compact scenario — compaction live on gateway
                     ticks with mixed engine/decay panes — holding its
                     SLO contract (writes BENCH_ingest.json)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

if any("serving_sharded" in a for a in sys.argv):  # also --suite=… form
    # the dry-run's forced-host-device trick: the sharded suite simulates
    # its 8-device mesh on one CPU. Must land in XLA_FLAGS before the
    # first jax init (the import right below), so it keys off argv.
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _timeit(fn, *args, n=20, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / n


# ----------------------------------------------------------------------
def bench_ab_lift():
    print("\n== ab_lift (paper §IV: engagement lift table) ==")
    path = os.path.join(ROOT, "experiments", "ab_report.json")
    if not os.path.exists(path):
        print("  [skip] run examples/ab_experiment.py first")
        return
    for tag, fname in (("regime A (intent drift)", "ab_report.json"),
                       ("regime B (trust bias)", "ab_report_regimeB.json")):
        path = os.path.join(ROOT, "experiments", fname)
        if not os.path.exists(path):
            continue
        rep = json.load(open(path))
        ctrl = rep["arms"]["control"]["ctr"]
        print(f"  -- {tag} --")
        print(f"  {'arm':14s} {'ctr':>8s} {'lift%':>8s} {'p':>8s} sig")
        print(f"  {'control':14s} {ctrl:8.4f} {'--':>8s} {'--':>8s}")
        for name, t in rep["tests"].items():
            arm = name.replace("_vs_control", "")
            if arm.startswith("stale_"):
                continue
            print(f"  {arm:14s} {rep['arms'][arm]['ctr']:8.4f} "
                  f"{t['lift']*100:+8.2f} {t['p_t']:8.4f} "
                  f"{'YES' if t['significant'] else 'no'}")


def bench_latency_ablation():
    print("\n== latency_ablation (engagement vs feature staleness) ==")
    path = os.path.join(ROOT, "experiments", "ab_report.json")
    if not os.path.exists(path):
        print("  [skip] run examples/ab_experiment.py --latency first")
        return
    rep = json.load(open(path))
    rows = [(n, a) for n, a in rep["arms"].items() if n.startswith("stale_")]
    if not rows:
        print("  [skip] no latency arms in the report (use --latency)")
        return
    print(f"  {'staleness':>12s} {'ctr':>8s}")
    print(f"  {'24h batch':>12s} {rep['arms']['control']['ctr']:8.4f}")
    for n, a in sorted(rows, key=lambda r: -int(r[0].split('_')[1][:-1])):
        lam = int(n.split("_")[1][:-1])
        print(f"  {lam:>11d}s {a['ctr']:8.4f}")
    print(f"  {'inject(rt)':>12s} {rep['arms']['treatment']['ctr']:8.4f}")


# ----------------------------------------------------------------------
def bench_injection_overhead():
    print("\n== injection_overhead (history_merge at serving shapes) ==")
    from repro.kernels.history_merge.ops import history_merge
    rng = np.random.RandomState(0)
    print(f"  {'batch':>6s} {'L_hist':>7s} {'L_rt':>5s} {'K':>4s} "
          f"{'us/req (xla)':>13s}")
    for b, lb, lr, k in [(64, 64, 16, 64), (256, 64, 16, 64),
                         (256, 256, 32, 256), (1024, 64, 16, 64)]:
        args = (rng.randint(0, 5000, (b, lb)).astype(np.int32),
                rng.randint(0, 10**6, (b, lb)).astype(np.int32),
                np.ones((b, lb), np.int32),
                rng.randint(0, 5000, (b, lr)).astype(np.int32),
                rng.randint(10**6, 2 * 10**6, (b, lr)).astype(np.int32),
                np.ones((b, lr), np.int32))
        jargs = [jnp.asarray(a) for a in args]
        dt = _timeit(lambda *a: history_merge(*a, out_len=k, impl="xla"),
                     *jargs, n=10)
        print(f"  {b:6d} {lb:7d} {lr:5d} {k:4d} {dt / b * 1e6:13.2f}")


def bench_serving_phases():
    print("\n== serving_phases (inject is O(suffix), not O(history)) ==")
    from repro.configs.base import get_config, reduced
    from repro.models.model import init_params
    from repro.serving.engine import ServingConfig, ServingEngine
    for arch in ("llama3.2-1b", "mamba2-780m"):
        cfg = reduced(get_config(arch))
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        eng = ServingEngine(cfg, params, ServingConfig(
            max_batch=8, prefill_len=512, inject_len=16, cache_capacity=1024))
        rng = np.random.RandomState(0)
        hists = [list(rng.randint(1, cfg.vocab_size, 500)) for _ in range(8)]
        toks, valid = eng.pad_tokens(hists, 512)
        t_prefill = _timeit(eng.prefill, toks, valid, n=5)
        state = eng.prefill(toks, valid)
        fresh = [list(rng.randint(1, cfg.vocab_size, 8)) for _ in range(8)]
        stoks, svalid = eng.pad_tokens(fresh, 16, align="left")
        t_inject = _timeit(lambda s, sv: eng.inject(state, s, sv),
                           stoks, svalid, n=5)
        dec = eng.finalize(eng.inject(state, stoks, svalid))
        tok = np.array([[1]] * 8, np.int32)
        t_decode = _timeit(lambda t: eng.decode(dec, t)[0], tok, n=5)
        print(f"  {arch:14s} prefill(512)={t_prefill*1e3:7.1f}ms "
              f"inject(16)={t_inject*1e3:6.1f}ms "
              f"decode(1)={t_decode*1e3:6.1f}ms "
              f"ratio inject/prefill={t_inject/t_prefill:.2f}")


def bench_kernel_micro():
    print("\n== kernel_micro (oracle-path timings on CPU; Pallas targets TPU) ==")
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.models.ssm import ssd_chunked
    from repro.kernels.ssd_scan.ref import ssd_ref_sequential
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (2, 8, 1024, 64))
    k = jax.random.normal(k2, (2, 2, 1024, 64))
    v = jax.random.normal(k3, (2, 2, 1024, 64))
    ref = jax.jit(lambda q, k, v: attention_ref(q, k, v))
    print(f"  attention_ref  1k seq: {_timeit(ref, q, k, v, n=5)*1e3:8.1f} ms")

    x = jax.random.normal(k1, (2, 1024, 8, 64)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(k2, (2, 1024, 8)) - 2)
    A = -jnp.exp(jax.random.normal(k3, (8,)) * 0.3)
    B = jax.random.normal(k1, (2, 1024, 128)) * 0.3
    C = jax.random.normal(k2, (2, 1024, 128)) * 0.3
    D = jnp.ones((8,))
    chunked = jax.jit(lambda *a: ssd_chunked(*a, chunk=256))
    seq = jax.jit(ssd_ref_sequential)
    t_c = _timeit(chunked, x, dt, A, B, C, D, n=5)
    t_s = _timeit(seq, x, dt, A, B, C, D, n=5)
    print(f"  ssd chunked vs sequential 1k: {t_c*1e3:7.1f} ms vs "
          f"{t_s*1e3:7.1f} ms (speedup {t_s/t_c:.1f}x — the SSD trick)")


# ----------------------------------------------------------------------
DAY = 86400


def _time_once(fn, *args, repeat=3):
    """Best-of-N wall time for host-side (numpy) work."""
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args)
        best = min(best, time.perf_counter() - t0)
    return best, out


def bench_feature_plane(smoke: bool = False, out_path: str = None):
    """Vectorized array-backed feature plane vs the retired loop reference.

    Measures, per population size:
      * full-population snapshot materialization (``run_snapshot``)
      * batched ``lookup_at_cutoff`` (4096 users)
      * realtime ``lookup`` (256-user serve batch)
      * the serving loop's interleaved pattern — alternating 256-event
        ingest with 256-user realtime + cutoff lookups (reads racing an
        unsorted pending suffix), 50 rounds
    The loop reference is only timed up to 100k users (1M would take
    minutes per snapshot — which is the point of this refactor).
    """
    print("\n== feature_plane (vectorized EventLog vs loop reference) ==")
    from repro.core._reference import (ReferenceBatchFeatureStore,
                                       ReferenceRealtimeFeatureService)
    from repro.core.feature_store import BatchFeatureStore, FeatureStoreConfig
    from repro.core.realtime import RealtimeConfig, RealtimeFeatureService

    sizes = [(1_000, 16), (10_000, 8)] if smoke \
        else [(1_000, 32), (100_000, 32), (1_000_000, 8)]
    ref_limit = 100_000
    cutoff = 15 * DAY

    def interleaved(batch_store, rt_service, rng, rounds=50):
        """The serve pattern: observe a wave of events, then look up."""
        n_users = batch_store.cfg.n_users
        for r in range(rounds):
            u = rng.randint(0, n_users, 256)
            it = rng.randint(0, 50_000, 256)
            t = np.full(256, cutoff + r * 60)
            for x, y, z in zip(u.tolist(), it.tolist(), t.tolist()):
                batch_store.append(x, y, z)
                rt_service.ingest(x, y, z)
            now = cutoff + r * 60 + 30
            rt_service.lookup(u, now)
            batch_store.lookup_at_cutoff(u, now)

    results = []
    print(f"  {'users':>9s} {'events':>9s} {'snap(vec)':>10s} "
          f"{'snap(ref)':>10s} {'speedup':>8s} {'lookup4k(vec)':>14s} "
          f"{'lookup4k(ref)':>14s} {'rt256(vec)':>11s} "
          f"{'serve50(vec)':>13s} {'serve50(ref)':>13s}")
    for n_users, ev_per_user in sizes:
        rng = np.random.RandomState(0)
        n = n_users * ev_per_user
        users = rng.randint(0, n_users, n).astype(np.int64)
        items = rng.randint(0, 50_000, n).astype(np.int32)
        tss = rng.randint(0, 30 * DAY, n).astype(np.int64)

        store = BatchFeatureStore(FeatureStoreConfig(
            n_users=n_users, feature_len=64))
        store.extend(users, items, tss)
        # first snapshot pays the lazy index rebuild — charge it honestly
        t_snap_vec, _ = _time_once(store.run_snapshot, cutoff, repeat=1)
        t2, _ = _time_once(store.run_snapshot, cutoff + DAY, repeat=1)
        t_snap_vec = min(t_snap_vec, t2)
        q4k = rng.randint(0, n_users, 4096)
        t_lkp_vec, _ = _time_once(store.lookup_at_cutoff, q4k, cutoff)

        rts = RealtimeFeatureService(RealtimeConfig(
            n_users=n_users, buffer_len=16, ingest_latency=0,
            retention=30 * DAY))
        rts.extend(users, items, tss)
        q256 = rng.randint(0, n_users, 256)
        t_rt_vec, _ = _time_once(rts.lookup, q256, cutoff)

        t_snap_ref = t_lkp_ref = t_serve_ref = None
        if n_users <= ref_limit:
            ref = ReferenceBatchFeatureStore(FeatureStoreConfig(
                n_users=n_users, feature_len=64))
            for u, it, t in zip(users.tolist(), items.tolist(), tss.tolist()):
                ref.append(u, it, t)
            t_snap_ref, _ = _time_once(ref.run_snapshot, cutoff, repeat=1)
            t_lkp_ref, _ = _time_once(ref.lookup_at_cutoff, q4k, cutoff,
                                      repeat=1)
            rref = ReferenceRealtimeFeatureService(RealtimeConfig(
                n_users=n_users, buffer_len=16, ingest_latency=0,
                retention=30 * DAY))
            for u, it, t in zip(users.tolist(), items.tolist(), tss.tolist()):
                rref.ingest(u, it, t)
            # correctness spot-check rides along with the timing run
            for a, b in zip(store.lookup_at_cutoff(q4k, cutoff),
                            ref.lookup_at_cutoff(q4k, cutoff)):
                np.testing.assert_array_equal(a, b)
            for a, b in zip(rts.lookup(q256, cutoff),
                            rref.lookup(q256, cutoff)):
                np.testing.assert_array_equal(a, b)
            t_serve_ref, _ = _time_once(
                interleaved, ref, rref, np.random.RandomState(1), repeat=1)
        # interleaved timing mutates the stores — run it last
        t_serve_vec, _ = _time_once(
            interleaved, store, rts, np.random.RandomState(1), repeat=1)
        speedup = t_snap_ref / t_snap_vec if t_snap_ref else None
        results.append({
            "n_users": n_users, "n_events": n,
            "snapshot_vec_s": t_snap_vec, "snapshot_ref_s": t_snap_ref,
            "snapshot_speedup": speedup,
            "lookup4096_vec_s": t_lkp_vec, "lookup4096_ref_s": t_lkp_ref,
            "realtime256_vec_s": t_rt_vec,
            "interleaved50_vec_s": t_serve_vec,
            "interleaved50_ref_s": t_serve_ref,
        })
        fmt = lambda v, w: f"{v*1e3:{w}.2f}ms" if v is not None else " " * w + "--"
        print(f"  {n_users:9d} {n:9d} {fmt(t_snap_vec, 8)} "
              f"{fmt(t_snap_ref, 8)} "
              f"{speedup and f'{speedup:7.1f}x' or '     --'} "
              f"{fmt(t_lkp_vec, 12)} {fmt(t_lkp_ref, 12)} "
              f"{fmt(t_rt_vec, 9)} {fmt(t_serve_vec, 11)} "
              f"{fmt(t_serve_ref, 11)}")
    # smoke runs get their own file so they never clobber the committed
    # full-size record
    default_name = ("BENCH_feature_plane_smoke.json" if smoke
                    else "BENCH_feature_plane.json")
    out_path = out_path or os.path.join(ROOT, default_name)
    with open(out_path, "w") as f:
        json.dump({"suite": "feature_plane", "smoke": smoke,
                   "results": results}, f, indent=2)
    print(f"  wrote {os.path.abspath(out_path)}")
    return results


# ----------------------------------------------------------------------
def bench_serving(smoke: bool = False, out_path: str = None):
    """End-to-end InjectionServer: cached-inject vs full-prefill-per-request.

    Interleaved workload at each population size: every round ingests a
    wave of fresh events (offline log + realtime stream) then serves
    request batches of random users; the cached server pays inject(suffix)
    + decode per hit, the baseline re-prefills the full history on every
    request. Reports requests/sec and p50/p99 per-step (one fixed-shape
    pane) latency, then spot-checks the two paths produce the same logits.
    """
    print("\n== serving (cached-inject vs full-prefill, interleaved ingest) ==")
    from repro.configs.base import ModelConfig
    from repro.core.feature_store import BatchFeatureStore, FeatureStoreConfig
    from repro.core.injection import FeatureInjector, InjectionConfig
    from repro.core.realtime import RealtimeConfig, RealtimeFeatureService
    from repro.models.model import init_params
    from repro.serving.engine import ServingConfig, ServingEngine
    from repro.serving.loop import InjectionServer, ServerConfig

    n_items = 4000
    feature_len = 240   # long batch history — the cost re-prefill pays
    cfg = ModelConfig(
        name="itfi-ranker-bench", family="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=2, d_ff=512, vocab_size=n_items + 256,
        rope_theta=10000.0, tie_embeddings=True)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = ServingEngine(cfg, params, ServingConfig(
        max_batch=16, prefill_len=256, inject_len=16, cache_capacity=512))

    sizes = [(1_000, 1)] if smoke else [(1_000, 3), (10_000, 3)]
    ev_per_user = 64 if smoke else 256
    results = []

    def build(n_users, use_cache):
        rng = np.random.RandomState(0)
        n = n_users * ev_per_user
        store = BatchFeatureStore(FeatureStoreConfig(
            n_users=n_users, feature_len=feature_len))
        rts = RealtimeFeatureService(RealtimeConfig(
            n_users=n_users, buffer_len=8, ingest_latency=0))
        us = rng.randint(0, n_users, n).astype(np.int64)
        its = rng.randint(0, n_items, n).astype(np.int64)
        tss = rng.randint(0, 5 * DAY, n).astype(np.int64)
        store.extend(us, its, tss)
        rts.extend(us, its, tss)
        inj = FeatureInjector(InjectionConfig(
            policy="inject", feature_len=feature_len), store, rts)
        return InjectionServer(eng, inj, ServerConfig(
            slate_len=4, cache_entries=4096, use_cache=use_cache))

    def req_users(rng, n_users, size):
        """Request traffic with hot-user locality (sessions): 80% of
        requests come from the hottest 10% of users — uniform traffic
        would make every serving cache useless by construction."""
        hot = max(n_users // 10, 1)
        pick_hot = rng.rand(size) < 0.8
        return np.where(pick_hot, rng.randint(0, hot, size),
                        rng.randint(0, n_users, size))

    wave = 64  # requests per serve() call (4 panes — lets the server's
    #            cache-aware batching group hit rows into pure-hit panes)

    def workload(srv, n_users, rounds, waves_per_round, seed=1):
        """Interleaved ingest/serve; returns per-wave serve latencies.

        Before timing, the cache is warmed over (up to budget) users — the
        daily job's post-snapshot precompute pass. The baseline server
        ignores warm(); its every request re-prefills by construction.
        """
        rng = np.random.RandomState(seed)
        now = 5 * DAY + 100

        def ingest_wave():
            u = req_users(rng, n_users, 64)
            it = rng.randint(0, n_items, 64)
            t = np.full(64, now - 30)
            srv.injector.batch.extend(u, it, t)
            srv.injector.realtime.extend(u, it, t)

        # untimed: roll the snapshot, warm the cache (daily-job precompute),
        # and compile every jit on the request path (incl. inject — needs a
        # fresh wave to exist)
        srv.warm(np.arange(n_users), now)  # clamps itself to the budget
        ingest_wave()
        srv.serve(req_users(rng, n_users, wave), now)
        h0, m0 = srv.cache.hits, srv.cache.misses

        lat = []
        for r in range(rounds):
            ingest_wave()
            for _ in range(waves_per_round):
                q = req_users(rng, n_users, wave)
                t0 = time.perf_counter()
                srv.serve(q, now)
                lat.append(time.perf_counter() - t0)
            now += 60
        return np.asarray(lat), srv.cache.hits - h0, srv.cache.misses - m0

    rounds = 4 if smoke else 12
    print(f"  {'users':>7s} {'path':>12s} {'req/s':>8s} {'p50':>8s} "
          f"{'p99':>9s} {'hit%':>6s} {'prefills':>9s}   (p50/p99 per "
          f"{wave}-request wave)")
    for n_users, waves in sizes:
        row = {"n_users": n_users}
        for tag, use_cache in (("cached", True), ("full", False)):
            srv = build(n_users, use_cache)
            lat, hits, misses = workload(srv, n_users, rounds,
                                         waves_per_round=waves)
            n_req = len(lat) * wave
            rps = n_req / lat.sum()
            st = srv.stats()
            hit = hits / max(hits + misses, 1)
            row[tag] = {
                "requests": int(n_req), "rps": float(rps),
                "wave_requests": wave,
                "p50_ms": float(np.percentile(lat, 50) * 1e3),
                "p99_ms": float(np.percentile(lat, 99) * 1e3),
                "hit_rate": float(hit), "stats": st,
            }
            print(f"  {n_users:7d} {tag:>12s} {rps:8.1f} "
                  f"{row[tag]['p50_ms']:6.1f}ms {row[tag]['p99_ms']:7.1f}ms "
                  f"{hit * 100:5.1f}% {st['prefill_calls']:9d}")
        row["speedup"] = row["cached"]["rps"] / row["full"]["rps"]

        # logits spot-check: identical stacks, same request -> same scores
        sc = build(n_users, True)
        sf = build(n_users, False)
        rng = np.random.RandomState(2)
        now = 5 * DAY + 100
        wave_u = rng.randint(0, n_users, 64)
        wave_i = rng.randint(0, n_items, 64)
        for srv in (sc, sf):
            srv.injector.batch.extend(wave_u, wave_i, np.full(64, now - 30))
            srv.injector.realtime.extend(wave_u, wave_i, np.full(64, now - 30))
        q = rng.randint(0, n_users, eng.scfg.max_batch)
        sc.serve(q, now - 60)  # populate the cache, then hit it
        a = sc.serve(q, now)
        b_ = sf.serve(q, now)
        diff = float(np.abs(a.scores - b_.scores).max())
        row["logits_max_abs_diff"] = diff
        row["logits_allclose"] = bool(diff < 2e-3)
        row["slates_equal"] = bool((a.slate == b_.slate).all())
        print(f"  {n_users:7d} speedup={row['speedup']:.2f}x "
              f"logits max|Δ|={diff:.2e} "
              f"slates_equal={row['slates_equal']}")
        results.append(row)

    default_name = ("BENCH_serving_smoke.json" if smoke
                    else "BENCH_serving.json")
    out_path = out_path or os.path.join(ROOT, default_name)
    with open(out_path, "w") as f:
        json.dump({"suite": "serving", "smoke": smoke,
                   "config": {"arch": cfg.name, "max_batch": eng.scfg.max_batch,
                              "prefill_len": eng.scfg.prefill_len,
                              "inject_len": eng.scfg.inject_len,
                              "feature_len": feature_len,
                              "slate_len": 4},
                   "results": results}, f, indent=2)
    print(f"  wrote {os.path.abspath(out_path)}")
    return results


# ----------------------------------------------------------------------
def bench_scheduler(smoke: bool = False, out_path: str = None):
    """Request-level Gateway vs the legacy wave path on the same traffic.

    Three rows per population size, separating two different costs:

      1. ``wave`` — the legacy pre-grouped ``serve(users, now)`` path.
      2. ``gateway_wave`` — the SAME waves through the request API
         (``submit_many`` + ``flush``). The scheduler sees the whole
         wave at once, so it forms the identical panes (incl. the
         cache-aware hit/miss partitioning): this isolates the
         facade's own cost (typed requests, tickets, per-request
         telemetry), which must stay within ~10% of the wave path —
         the redesign's parity bar.
      3. ``gateway_trickle`` — per-request ``submit`` at one
         sim-second per arrival with a pane-deadline of 2*max_batch
         sim-seconds (pane-full flushes, deadline tail via ``tick``).
         At 100% hit rate this too is pane-for-pane identical work; at
         lower hit rates it honestly pays the *scheduling-granularity*
         cost of latency-bounded micro-batching — an eager pane-full
         flush never holds more than one pane, so it cannot regroup
         hits around misses the way a whole-wave drain can, and more
         panes carry an admission prefill.

    The trickle row is also the one that can measure what a wave API
    cannot: every request's individual queue+serve wall latency
    (submit -> response), recorded as req_p50/p99 next to the pane
    serve latency and the sim-time queue-delay telemetry.

      4. ``gateway_continuous`` — the same per-request trickle through
         the continuous scheduler (``max_wait=0``) over the paged
         device-resident state pool (``pool_slots``): every arrival is
         served immediately in a padded partial pane, so the sim-time
         queue delay collapses to zero (vs the trickle row's
         deadline-bounded p99) at the price of one engine pane per
         request. Its slates are checked bitwise against the wave
         path's (``slates_equal_wave``) — the pool's one-hot
         gather/scatter and the partial-pane padding are exact — and
         the compiled gather/scatter collective count (expected 0) is
         recorded from a ``tools/slot_pool_check.py`` subprocess run.

    Rounds are **interleaved across the three paths** (wave round,
    gateway_wave round, trickle round, repeat): shared CI hosts
    throttle on a seconds-to-minutes timescale, and sequential
    per-path measurement hands whole slow windows to one path —
    interleaving spreads them evenly so the ratios compare serving
    work, not scheduler luck.
    """
    print("\n== scheduler (request-level Gateway vs wave path) ==")
    import warnings as _warnings

    from repro.configs.base import ModelConfig
    from repro.core.feature_store import BatchFeatureStore, FeatureStoreConfig
    from repro.core.injection import FeatureInjector, InjectionConfig
    from repro.core.realtime import RealtimeConfig, RealtimeFeatureService
    from repro.models.model import init_params
    from repro.serving.api import Request
    from repro.serving.engine import ServingConfig, ServingEngine
    from repro.serving.loop import InjectionServer
    from repro.serving.scheduler import Gateway, ServerConfig

    n_items = 4000
    feature_len = 240
    cfg = ModelConfig(
        name="itfi-ranker-bench", family="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=2, d_ff=512, vocab_size=n_items + 256,
        rope_theta=10000.0, tie_embeddings=True)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = ServingEngine(cfg, params, ServingConfig(
        max_batch=16, prefill_len=256, inject_len=16, cache_capacity=512))

    sizes = [1_000] if smoke else [1_000, 10_000]
    ev_per_user = 64 if smoke else 256
    rounds = 3 if smoke else 10
    wave = 64                    # requests per round-wave (4 panes)
    deadline = 2 * eng.scfg.max_batch  # sim-seconds a request may queue

    def build(n_users):
        rng = np.random.RandomState(0)
        n = n_users * ev_per_user
        store = BatchFeatureStore(FeatureStoreConfig(
            n_users=n_users, feature_len=feature_len))
        rts = RealtimeFeatureService(RealtimeConfig(
            n_users=n_users, buffer_len=8, ingest_latency=0))
        us = rng.randint(0, n_users, n).astype(np.int64)
        its = rng.randint(0, n_items, n).astype(np.int64)
        tss = rng.randint(0, 5 * DAY, n).astype(np.int64)
        store.extend(us, its, tss)
        rts.extend(us, its, tss)
        return FeatureInjector(InjectionConfig(
            policy="inject", feature_len=feature_len), store, rts)

    def req_users(rng, n_users, size):
        hot = max(n_users // 10, 1)
        pick_hot = rng.rand(size) < 0.8
        return np.where(pick_hot, rng.randint(0, hot, size),
                        rng.randint(0, n_users, size))

    def ingest(inj_or_gw, rng, n_users, now):
        u = req_users(rng, n_users, 64)
        it = rng.randint(0, n_items, 64)
        t = np.full(64, now - 30)
        inj = getattr(inj_or_gw, "injector", inj_or_gw)
        inj.batch.extend(u, it, t)
        inj.realtime.extend(u, it, t)

    results = []
    print(f"  {'users':>7s} {'path':>16s} {'req/s':>8s} {'req p50':>9s} "
          f"{'req p99':>9s} {'pane p50':>9s} {'pane p99':>9s} {'hit%':>6s}")
    for n_users in sizes:
        row = {"n_users": n_users, "wave_requests": wave, "rounds": rounds}
        scfg = ServerConfig(slate_len=4, cache_entries=4096)
        t00 = 5 * DAY + 100

        # four independent stacks fed identical seeded traffic; their
        # timed rounds run interleaved (see docstring)
        pool_slots = 1024
        srv = InjectionServer(eng, build(n_users), scfg)   # wave
        gww = Gateway(eng, build(n_users), scfg)           # gateway_wave
        gwt = Gateway(eng, build(n_users), scfg)           # trickle
        gwc = Gateway(eng, build(n_users), ServerConfig(   # continuous
            slate_len=4, pool_slots=pool_slots, max_wait=0))
        st_w = {"rng": np.random.RandomState(1), "now": t00, "lat": [],
                "slates": []}
        st_gw = {"rng": np.random.RandomState(1), "now": t00, "lat": []}
        st_tr = {"rng": np.random.RandomState(1), "now": t00,
                 "req_lat": [], "pane_lat": [], "pending": [],
                 "t_total": 0.0}
        st_c = {"rng": np.random.RandomState(1), "now": t00,
                "req_lat": [], "slates": [], "t_total": 0.0}

        def wave_round(s, timed=True):
            ingest(srv.gateway, s["rng"], n_users, s["now"])
            q = req_users(s["rng"], n_users, wave)
            t0 = time.perf_counter()
            res = srv.serve(q, s["now"])
            if timed:
                s["lat"].append(time.perf_counter() - t0)
            s["slates"].append(np.asarray(res.slate))
            s["now"] += 60

        def gateway_wave_round(s, timed=True):
            ingest(gww, s["rng"], n_users, s["now"])
            q = req_users(s["rng"], n_users, wave)
            t0 = time.perf_counter()
            gww.submit_many([Request(user=int(u), now=s["now"]) for u in q])
            gww.flush(s["now"])
            if timed:
                s["lat"].append(time.perf_counter() - t0)
            s["now"] += 60

        def trickle_round(s, timed=True):
            ingest(gwt, s["rng"], n_users, s["now"])
            t_seg0 = time.perf_counter()
            for u in req_users(s["rng"], n_users, wave):
                t = gwt.submit(Request(user=int(u), now=s["now"],
                                       deadline=s["now"] + deadline))
                s["pending"].append(t)
                s["now"] += 1  # one arrival per sim-second
                if t.done and timed:  # this submit filled + flushed a pane
                    done_wall = time.perf_counter()
                    # the flush ran inside this submit call, so the
                    # triggering request's submit->done wall time IS the
                    # pane's serve latency
                    s["pane_lat"].append(done_wall - t.submitted_wall)
                    s["req_lat"] += [done_wall - p.submitted_wall
                                     for p in s["pending"] if p.done]
                s["pending"] = [p for p in s["pending"] if not p.done]
            gwt.tick(s["now"] + deadline)  # deadline-flush the tail
            done_wall = time.perf_counter()
            if timed:
                s["t_total"] += done_wall - t_seg0
                s["req_lat"] += [done_wall - p.submitted_wall
                                 for p in s["pending"] if p.done]
            s["pending"] = [p for p in s["pending"] if not p.done]
            # next round's arrivals start past the tail-flush tick's
            # clock (now + deadline) — backdated stamps would inflate
            # the sim-time queue-delay telemetry
            s["now"] += deadline + 4

        def continuous_round(s, timed=True):
            ingest(gwc, s["rng"], n_users, s["now"])
            t_seg0 = time.perf_counter()
            for u in req_users(s["rng"], n_users, wave):
                t = gwc.submit(Request(user=int(u), now=s["now"]))
                assert t.done  # max_wait=0: served on arrival
                if timed:
                    s["req_lat"].append(
                        time.perf_counter() - t.submitted_wall)
                s["slates"].append(np.asarray(t.response.slate))
                s["now"] += 1  # one arrival per sim-second
            gwc.poll()  # claim the completion stream
            if timed:
                s["t_total"] += time.perf_counter() - t_seg0
            # keep the four clocks in lockstep with the trickle stack
            s["now"] += deadline + 4

        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore", DeprecationWarning)
            # untimed: warm every cache, compile every jit
            for g in (srv, gww, gwt, gwc):
                g.warm(np.arange(n_users), t00)
            wave_round(st_w, timed=False)
            gateway_wave_round(st_gw, timed=False)
            trickle_round(st_tr, timed=False)
            continuous_round(st_c, timed=False)
            counters = [(g.cache.hits, g.cache.misses)
                        for g in (srv, gww, gwt, gwc)]
            for _ in range(rounds):  # timed, interleaved
                wave_round(st_w)
                gateway_wave_round(st_gw)
                trickle_round(st_tr)
                continuous_round(st_c)

        def hit_rate(g, h0m0):
            hits, misses = g.cache.hits - h0m0[0], g.cache.misses - h0m0[1]
            return float(hits / max(hits + misses, 1))

        lat = np.asarray(st_w["lat"])
        row["wave"] = {
            "rps": float(rounds * wave / lat.sum()),
            "wave_p50_ms": float(np.percentile(lat, 50) * 1e3),
            "wave_p99_ms": float(np.percentile(lat, 99) * 1e3),
            "hit_rate": hit_rate(srv, counters[0]),
        }
        lat = np.asarray(st_gw["lat"])
        row["gateway_wave"] = {
            "rps": float(rounds * wave / lat.sum()),
            "wave_p50_ms": float(np.percentile(lat, 50) * 1e3),
            "wave_p99_ms": float(np.percentile(lat, 99) * 1e3),
            "hit_rate": hit_rate(gww, counters[1]),
        }
        pane_lat = np.asarray(st_tr["pane_lat"])
        req_lat = np.asarray(st_tr["req_lat"])
        st = gwt.stats()
        row["gateway_trickle"] = {
            "rps": float(rounds * wave / st_tr["t_total"]),
            "req_p50_ms": float(np.percentile(req_lat, 50) * 1e3),
            "req_p99_ms": float(np.percentile(req_lat, 99) * 1e3),
            "pane_p50_ms": float(np.percentile(pane_lat, 50) * 1e3),
            "pane_p99_ms": float(np.percentile(pane_lat, 99) * 1e3),
            "hit_rate": hit_rate(gwt, counters[2]),
            "queue_delay_sim": st["queue_delay"],
            "paths": st["paths"], "deadline_flushes": st["deadline_flushes"],
        }
        req_lat = np.asarray(st_c["req_lat"])
        cst = gwc.stats()
        wave_slates = np.concatenate(st_w["slates"])
        cont_slates = np.stack(st_c["slates"])
        trickle_p99 = row["gateway_trickle"]["queue_delay_sim"]["p99"]
        cont_p99 = cst["queue_delay"]["p99"]
        row["gateway_continuous"] = {
            "rps": float(rounds * wave / st_c["t_total"]),
            "req_p50_ms": float(np.percentile(req_lat, 50) * 1e3),
            "req_p99_ms": float(np.percentile(req_lat, 99) * 1e3),
            "hit_rate": hit_rate(gwc, counters[3]),
            "queue_delay_sim": cst["queue_delay"],
            "paths": cst["paths"], "panes": cst["panes"],
            "pool_slots": pool_slots,
            "slot_bytes": gwc.pool.slot_nbytes,
            "slates_equal_wave": bool(
                np.array_equal(wave_slates, cont_slates)),
            # the latency lever: sim-time p99 queue delay vs the
            # deadline-bounded trickle (>= 2x better is the bar; with
            # max_wait=0 the continuous path's delay is identically 0)
            "p99_queue_delay_vs_trickle": {
                "trickle": float(trickle_p99),
                "continuous": float(cont_p99),
                "improved_2x": bool(2 * cont_p99 <= trickle_p99),
            },
        }
        row["facade_ratio"] = (row["gateway_wave"]["rps"]
                               / row["wave"]["rps"])
        row["trickle_ratio"] = (row["gateway_trickle"]["rps"]
                                / row["wave"]["rps"])
        w, gwv, g = row["wave"], row["gateway_wave"], row["gateway_trickle"]
        print(f"  {n_users:7d} {'wave':>16s} {w['rps']:8.1f} {'--':>9s} "
              f"{'--':>9s} {w['wave_p50_ms']:7.1f}ms {w['wave_p99_ms']:7.1f}ms "
              f"{w['hit_rate'] * 100:5.1f}%")
        print(f"  {n_users:7d} {'gateway_wave':>16s} {gwv['rps']:8.1f} "
              f"{'--':>9s} {'--':>9s} {gwv['wave_p50_ms']:7.1f}ms "
              f"{gwv['wave_p99_ms']:7.1f}ms {gwv['hit_rate'] * 100:5.1f}%")
        print(f"  {n_users:7d} {'gateway_trickle':>16s} {g['rps']:8.1f} "
              f"{g['req_p50_ms']:7.1f}ms {g['req_p99_ms']:7.1f}ms "
              f"{g['pane_p50_ms']:7.1f}ms {g['pane_p99_ms']:7.1f}ms "
              f"{g['hit_rate'] * 100:5.1f}%")
        c = row["gateway_continuous"]
        print(f"  {n_users:7d} {'gateway_cont':>16s} {c['rps']:8.1f} "
              f"{c['req_p50_ms']:7.1f}ms {c['req_p99_ms']:7.1f}ms "
              f"{'--':>9s} {'--':>9s} {c['hit_rate'] * 100:5.1f}%")
        print(f"  {n_users:7d} facade ratio (gateway_wave/wave) = "
              f"{row['facade_ratio']:.2f} (parity bar: >= 0.90); trickle "
              f"ratio = {row['trickle_ratio']:.2f}; per-request latency is "
              f"the column the wave path cannot fill")
        qd = c["p99_queue_delay_vs_trickle"]
        print(f"  {n_users:7d} continuous: queue_delay_sim p99 "
              f"{qd['trickle']:.0f}s -> {qd['continuous']:.0f}s "
              f"(improved_2x={qd['improved_2x']}), slates_equal_wave="
              f"{c['slates_equal_wave']}, {c['panes']} panes over "
              f"{pool_slots} pool slots")
        results.append(row)

    # the zero-collective proof for the pool's compiled gather/scatter:
    # run the HLO scan in a subprocess (it forces an 8-device CPU
    # topology via XLA_FLAGS, which must never leak into this process)
    # and record the count next to the rows it certifies. The child is
    # held to the CPU: this process may own the chip, and a second
    # process reaching for it would fail or hang
    import subprocess
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "slot_pool_check.py")],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
                 JAX_PLATFORMS="cpu"))
    pool_ok = (proc.returncode == 0
               and "SLOT-POOL OK collectives=0" in proc.stdout)
    slot_pool_check = {"ok": bool(pool_ok),
                       "collectives": 0 if pool_ok else None}
    print(f"  slot_pool_check: ok={pool_ok} collectives="
          f"{slot_pool_check['collectives']} (8-way data mesh HLO scan)")

    default_name = ("BENCH_scheduler_smoke.json" if smoke
                    else "BENCH_scheduler.json")
    out_path = out_path or os.path.join(ROOT, default_name)
    with open(out_path, "w") as f:
        json.dump({"suite": "scheduler", "smoke": smoke,
                   "config": {"arch": cfg.name, "max_batch": eng.scfg.max_batch,
                              "prefill_len": eng.scfg.prefill_len,
                              "inject_len": eng.scfg.inject_len,
                              "feature_len": feature_len, "slate_len": 4,
                              "deadline_s": deadline},
                   "slot_pool_check": slot_pool_check,
                   "results": results}, f, indent=2)
    print(f"  wrote {os.path.abspath(out_path)}")
    return results


# ----------------------------------------------------------------------
def bench_rollover(smoke: bool = False, out_path: str = None):
    """What a generation rollover costs, before and after this PR.

    Two independent measurements, because the stall and the storm live
    at different scales:

    **build** (store only, population scale) — the daily boundary used
    to re-materialize the full ``(n_users, feature_len)`` plane
    synchronously inside the clock call that crossed it. Times the full
    ``run_snapshot`` oracle vs the incremental ``SnapshotBuilder``
    (changed-user delta + copy-forward) at 1M users with a ~1% changed
    fraction, reporting total build time AND the max single
    budget-bounded ``step()`` — the worst stall any one ``tick`` pays
    under amortization.

    **serving** (end-to-end gateway) — the old rollover purged the
    whole prefill-state cache, so the first post-rollover waves were a
    100% miss storm of full prefills. Drives identical seeded traffic
    (hot-user locality, warmed cache, ~10% of users changed across the
    boundary) through three gateways: ``eager`` (warm_handoff=False +
    synchronous build — the legacy behavior), ``warm`` (handoff +
    budget-sliced incremental build), and ``background`` (handoff +
    off-thread build: boundary ticks are O(1) polls). Records the
    boundary-crossing clock-call wall time, per-wave prefill-path rows,
    hit rate and latency for the post-rollover waves, the miss-storm
    depth (waves until a wave is all-hit again), and the rekeyed
    fraction. Responses are asserted bitwise identical across all
    modes — the handoff and the off-thread build are optimizations
    only.
    """
    print("\n== rollover (eager purge + sync build vs warm handoff + "
          "incremental) ==")
    from repro.configs.base import ModelConfig
    from repro.core.feature_store import BatchFeatureStore, FeatureStoreConfig
    from repro.core.injection import FeatureInjector, InjectionConfig
    from repro.core.realtime import RealtimeConfig, RealtimeFeatureService
    from repro.models.model import init_params
    from repro.serving.api import Request
    from repro.serving.engine import ServingConfig, ServingEngine
    from repro.serving.scheduler import Gateway, ServerConfig

    results = {}

    # ---- part A: build amortization at population scale ---------------
    n_build = 50_000 if smoke else 1_000_000
    ev_per_user = 4 if smoke else 8
    budget = max(n_build // 500, 1)  # users per step() slice
    g1, g2 = 5 * DAY, 6 * DAY
    rng = np.random.RandomState(0)
    n = n_build * ev_per_user
    stores = [BatchFeatureStore(FeatureStoreConfig(
        n_users=n_build, feature_len=64)) for _ in range(3)]
    us = rng.randint(0, n_build, n).astype(np.int64)
    its = rng.randint(0, 50_000, n).astype(np.int32)
    tss = rng.randint(0, 5 * DAY, n).astype(np.int64)
    for s in stores:
        s.extend(us, its, tss)
        s.run_snapshot(g1)
    # ~1% of users get events inside the rolled period
    cu = rng.choice(n_build, n_build // 100, replace=False)
    cit = rng.randint(0, 50_000, len(cu))
    for s in stores:
        s.extend(cu, cit, np.full(len(cu), g1 + 500))
        # pre-build the log's lazy sorted index so both paths time BUILD
        # work, not shared index maintenance: the first population-scale
        # read after an append pays an amortized full re-sort either way
        # (EventLog._ensure_base), and during a live serving day that
        # cost is paid continuously by ordinary reads, not by the
        # snapshot job that happens to run next
        s._log._rebuild()
    full, inc, bgs = stores
    t_full, _ = _time_once(full.run_snapshot, g2, repeat=1)
    t0 = time.perf_counter()
    builder = inc.begin_snapshot(g2)  # delta scan + copy-forward alloc
    t_create = time.perf_counter() - t0
    step_times = []
    while not builder.done:
        t0 = time.perf_counter()
        builder.step(budget)
        step_times.append(time.perf_counter() - t0)
    for a, b in zip(full._snapshots[g2], inc._snapshots[g2]):
        np.testing.assert_array_equal(a, b)  # the oracle differential
    # the worst single clock call a gateway pays: builder creation rides
    # the first slice (Gateway._step_snapshot_build creates + steps)
    worst_slice = max([t_create + step_times[0]] + step_times[1:])
    t_inc_total = t_create + sum(step_times)
    results["build"] = {
        "n_users": n_build, "n_events": int(inc._log.n_events),
        "changed_users": int(builder.n_changed),
        "changed_frac": builder.n_changed / n_build,
        "step_budget_users": budget,
        "full_build_s": t_full,
        "incremental_create_s": float(t_create),
        "incremental_total_s": float(t_inc_total),
        "incremental_steps": len(step_times),
        "incremental_max_clock_slice_s": float(worst_slice),
        "bitwise_equal_oracle": True,
        "speedup_total": t_full / max(t_inc_total, 1e-9),
        "stall_reduction": t_full / max(worst_slice, 1e-9),
    }
    b = results["build"]
    print(f"  build @ {n_build} users: full={t_full*1e3:.0f}ms "
          f"incremental total={b['incremental_total_s']*1e3:.0f}ms "
          f"({b['changed_users']} changed, {b['incremental_steps']} steps "
          f"of {budget}) worst clock slice="
          f"{b['incremental_max_clock_slice_s']*1e3:.1f}ms -> "
          f"stall {b['stall_reduction']:.0f}x smaller, "
          f"total {b['speedup_total']:.1f}x faster")

    # background builder: the whole copy-forward + fill + diff runs on a
    # worker thread; the serving thread pays only builder creation, O(1)
    # polls, and the finalize (late fixup + install). Every slice below
    # is serving-thread wall time — the stall a clock call would pay.
    t_wall0 = time.perf_counter()
    bg_builder = bgs.begin_snapshot_background(g2)
    bg_create = time.perf_counter() - t_wall0
    bg_slices = [bg_create]  # creation rides the boundary tick
    polls = 0
    while True:
        t0 = time.perf_counter()
        rem = bg_builder.poll()
        bg_slices.append(time.perf_counter() - t0)
        polls += 1
        if rem == 0:
            break
        time.sleep(1e-3)
    bg_wall = time.perf_counter() - t_wall0
    for a, c in zip(full._snapshots[g2], bgs._snapshots[g2]):
        np.testing.assert_array_equal(a, c)  # off-thread differential
    results["build"]["background"] = {
        "create_s": float(bg_create),
        "wall_total_s": float(bg_wall),
        "serving_thread_busy_s": float(sum(bg_slices)),
        "polls": polls,
        "max_clock_slice_s": float(max(bg_slices)),
        "worker_steps": int(bg_builder.steps),
        "bitwise_equal_oracle": True,
        "stall_reduction": t_full / max(max(bg_slices), 1e-9),
    }
    bb = results["build"]["background"]
    print(f"  background @ {n_build} users: wall="
          f"{bb['wall_total_s']*1e3:.0f}ms across {polls} polls, "
          f"serving thread busy {bb['serving_thread_busy_s']*1e3:.1f}ms, "
          f"worst clock slice={bb['max_clock_slice_s']*1e3:.2f}ms -> "
          f"stall {bb['stall_reduction']:.0f}x smaller than full, "
          f"bitwise equal to oracle")

    # ---- part B: the post-rollover miss storm --------------------------
    n_items = 4000
    feature_len = 240
    n_users = 400 if smoke else 2_000
    sv_ev_per_user = 32 if smoke else 64
    post_waves = 6 if smoke else 12
    pre_waves = 2 if smoke else 4
    wave = 64
    changed_frac = 0.10

    cfg = ModelConfig(
        name="itfi-ranker-bench", family="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=2, d_ff=512, vocab_size=n_items + 256,
        rope_theta=10000.0, tie_embeddings=True)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = ServingEngine(cfg, params, ServingConfig(
        max_batch=16, prefill_len=256, inject_len=16, cache_capacity=512))

    def build_gw(mode):
        rng = np.random.RandomState(0)
        n = n_users * sv_ev_per_user
        store = BatchFeatureStore(FeatureStoreConfig(
            n_users=n_users, feature_len=feature_len))
        rts = RealtimeFeatureService(RealtimeConfig(
            n_users=n_users, buffer_len=8, ingest_latency=0))
        us = rng.randint(0, n_users, n).astype(np.int64)
        its = rng.randint(0, n_items, n).astype(np.int64)
        tss = rng.randint(0, 5 * DAY, n).astype(np.int64)
        store.extend(us, its, tss)
        rts.extend(us, its, tss)
        inj = FeatureInjector(InjectionConfig(
            policy="inject", feature_len=feature_len), store, rts)
        if mode == "eager":
            scfg = ServerConfig(slate_len=4, cache_entries=4096,
                                warm_handoff=False)
        elif mode == "warm":
            scfg = ServerConfig(slate_len=4, cache_entries=4096,
                                warm_handoff=True,
                                snapshot_build_budget=max(n_users // 4, 1))
        else:  # background: off-thread build, O(1) boundary ticks
            scfg = ServerConfig(slate_len=4, cache_entries=4096,
                                warm_handoff=True, background_build=True)
        return Gateway(eng, inj, scfg)

    def req_users(rng, size):
        hot = max(n_users // 10, 1)
        pick_hot = rng.rand(size) < 0.8
        return np.where(pick_hot, rng.randint(0, hot, size),
                        rng.randint(0, n_users, size))

    def serve_wave(gw, rng, now):
        q = req_users(rng, wave)
        t0 = time.perf_counter()
        tk = gw.submit_many([Request(user=int(u), now=int(now))
                             for u in q])
        gw.flush(now)
        dt = time.perf_counter() - t0
        prefills = sum(t.response.telemetry.path == "prefill" for t in tk)
        hits = sum(t.response.telemetry.cache_hit for t in tk)
        return dt, prefills, hits, tk

    t00 = 5 * DAY + 100
    rngc = np.random.RandomState(5)
    changed = rngc.choice(n_users, int(n_users * changed_frac),
                          replace=False)
    changed_items = rngc.randint(0, n_items, len(changed))

    mode_rows = {}
    fingerprints = {}
    for mode in ("eager", "warm", "background"):
        gw = build_gw(mode)
        rng = np.random.RandomState(1)
        now = t00
        gw.warm(np.arange(n_users), now)     # daily-job precompute
        serve_wave(gw, np.random.RandomState(99), now)  # compile, untimed
        pre = [serve_wave(gw, rng, now + 60 * i)[:3]
               for i in range(pre_waves)]
        # the rolled period's events: ~10% of users change
        gw.observe_many(changed, changed_items,
                        np.full(len(changed), now + 3600))
        # cross the boundary on the clock; the eager gateway pays the
        # full synchronous build + purge inside ONE call, the warm
        # gateway amortizes budget-bounded slices across ticks
        t_boundary = now + DAY
        tick_times = []
        while gw.injector.generation(t_boundary) != 6 * DAY:
            t0 = time.perf_counter()
            gw.tick(t_boundary)
            tick_times.append(time.perf_counter() - t0)
            if mode == "background":
                # ticks are O(1) polls; the worker needs wall time
                time.sleep(1e-3)
            assert len(tick_times) < (2000 if mode == "background" else 100)
        post = []
        tks = []
        for i in range(post_waves):
            dt, prefills, hits, tk = serve_wave(
                gw, rng, t_boundary + 60 * (i + 1))
            post.append((dt, prefills, hits))
            tks.append(tk)
        fingerprints[mode] = (
            np.concatenate([np.stack([t.response.slate for t in tk])
                            for tk in tks]),
            np.concatenate([np.stack([t.response.scores for t in tk])
                            for tk in tks]))
        storm = next((i for i, (_, p, h) in enumerate(post)
                      if p == 0 and h == wave), len(post))
        st = gw.stats()["rollover"]
        pre_lat = np.array([d for d, _, _ in pre])
        post_lat = np.array([d for d, _, _ in post])
        mode_rows[mode] = {
            "boundary_clock_calls": len(tick_times),
            "boundary_call_max_ms": float(max(tick_times) * 1e3),
            "boundary_total_ms": float(sum(tick_times) * 1e3),
            "pre_wave_p99_ms": float(np.percentile(pre_lat, 99) * 1e3),
            "post_wave_p99_ms": float(np.percentile(post_lat, 99) * 1e3),
            "first_wave_prefills": int(post[0][1]),
            "first_wave_hit_rate": float(post[0][2] / wave),
            "miss_storm_waves": int(storm),
            "post_prefills_per_wave": [int(p) for _, p, _ in post],
            "rekeyed": int(st["rekeyed"]),
            "invalidated": int(st["invalidated"]),
            "retained": int(st["retained"]),
            "rekeyed_frac": float(
                st["rekeyed"] / max(st["rekeyed"] + st["invalidated"]
                                    + st["retained"], 1)),
        }
        r = mode_rows[mode]
        print(f"  {mode:>6s}: boundary max-call="
              f"{r['boundary_call_max_ms']:.1f}ms "
              f"first-wave prefills={r['first_wave_prefills']}/{wave} "
              f"hit={r['first_wave_hit_rate']*100:.0f}% "
              f"storm={r['miss_storm_waves']} waves "
              f"post p99={r['post_wave_p99_ms']:.1f}ms "
              f"rekeyed={r['rekeyed']}")

    # the handoff (and the off-thread build) is an optimization only:
    # identical responses in every mode
    for m in ("warm", "background"):
        np.testing.assert_array_equal(fingerprints["eager"][0],
                                      fingerprints[m][0])
        np.testing.assert_array_equal(fingerprints["eager"][1],
                                      fingerprints[m][1])
    e, w = mode_rows["eager"], mode_rows["warm"]
    results["serving"] = {
        "n_users": n_users, "wave_requests": wave,
        "changed_frac": changed_frac,
        "modes": mode_rows,
        "responses_bitwise_equal": True,
        "first_wave_prefill_reduction": (
            e["first_wave_prefills"] / max(w["first_wave_prefills"], 1)),
        "miss_storm_reduction_waves": (e["miss_storm_waves"]
                                       - w["miss_storm_waves"]),
    }
    print(f"  post-rollover first-wave prefills {e['first_wave_prefills']} "
          f"-> {w['first_wave_prefills']} "
          f"({results['serving']['first_wave_prefill_reduction']:.1f}x "
          f"fewer); responses bitwise equal across modes")

    default_name = ("BENCH_rollover_smoke.json" if smoke
                    else "BENCH_rollover.json")
    out_path = out_path or os.path.join(ROOT, default_name)
    with open(out_path, "w") as f:
        json.dump({"suite": "rollover", "smoke": smoke,
                   "config": {"arch": cfg.name, "max_batch": 16,
                              "prefill_len": 256, "inject_len": 16,
                              "feature_len": feature_len,
                              "slate_len": 4},
                   "results": results}, f, indent=2)
    print(f"  wrote {os.path.abspath(out_path)}")
    return results


# ----------------------------------------------------------------------
def bench_online(smoke: bool = False, out_path: str = None):
    """Online trainer + hot-swapped delta weight patches, end to end.

    Three measurements:

    **cadence** — patch install frequency vs serving cost. Replays the
    same seeded request/event waves through gateways that install a
    delta patch (trainable = embedding slice) never / every 8 waves /
    every 2 waves, under both install policies (``purge`` drops
    version-stale cache entries, ``rewarm`` re-prefills them between
    panes on a budget). Reports throughput, hit rate, patches applied,
    and the **install stall** — the worst single ``install_patch()``
    slice the serving thread paid (the hot-swap is O(patch): this
    number must stay in single-digit milliseconds, and the schema check
    gates the committed artifact at 5 ms).

    **swap** — the bitwise contract: after an install, the gateway's
    responses must equal a COLD gateway built directly from the
    trainer's weights, slate for slate, bit for bit.

    **drift** — why online weights matter at all: on a stream whose
    item distribution shifts mid-run, the online trainer's loss
    recovers after the drift while a frozen model's loss stays
    elevated (the frozen run is the same trainer machinery at lr=0, so
    both consume byte-identical batches).
    """
    print("\n== online (incremental trainer + hot-swapped patches) ==")
    from repro.configs.base import ModelConfig
    from repro.core.feature_store import BatchFeatureStore, FeatureStoreConfig
    from repro.core.injection import FeatureInjector, InjectionConfig
    from repro.core.realtime import RealtimeConfig, RealtimeFeatureService
    from repro.models.model import init_params
    from repro.serving.api import Request
    from repro.serving.engine import ServingConfig, ServingEngine
    from repro.serving.scheduler import Gateway, ServerConfig
    from repro.training import OnlineTrainer, OnlineTrainerConfig
    from repro.training.optimizer import AdamWConfig
    from repro.training.train_loop import TrainConfig

    n_items = 1000
    feature_len = 48
    n_users = 256 if smoke else 512
    ev_per_user = 16 if smoke else 24
    n_waves = 8 if smoke else 16
    wave = 32
    cfg = ModelConfig(
        name="itfi-ranker-online", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=n_items + 256,
        rope_theta=10000.0, tie_embeddings=True)
    scfg = ServingConfig(max_batch=16, prefill_len=64, inject_len=8,
                         cache_capacity=512)
    tcfg = TrainConfig(adamw=AdamWConfig(lr=1e-3, warmup_steps=2,
                                         total_steps=100_000),
                       remat=False, param_dtype=jnp.float32)
    ocfg = OnlineTrainerConfig(batch_size=8, seq_len=32,
                               trainable=("embed",))

    def build(policy="purge"):
        """Fresh engine (weights get patched) + seeded platform +
        trainer over the gateway's own event log."""
        rng = np.random.RandomState(0)
        n = n_users * ev_per_user
        store = BatchFeatureStore(FeatureStoreConfig(
            n_users=n_users, feature_len=feature_len))
        rts = RealtimeFeatureService(RealtimeConfig(
            n_users=n_users, buffer_len=8, ingest_latency=0))
        us = rng.randint(0, n_users, n).astype(np.int64)
        its = rng.randint(0, n_items, n).astype(np.int64)
        tss = rng.randint(0, 5 * DAY, n).astype(np.int64)
        store.extend(us, its, tss)
        rts.extend(us, its, tss)
        inj = FeatureInjector(InjectionConfig(
            policy="inject", feature_len=feature_len), store, rts)
        eng = ServingEngine(cfg, init_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.float32), scfg)
        gw = Gateway(eng, inj, ServerConfig(
            slate_len=4, cache_entries=1024, patch_policy=policy,
            rewarm_budget=64))
        tr = OnlineTrainer(cfg, eng.params, store.log, cfg=ocfg,
                           train_cfg=tcfg)
        return gw, tr

    t00 = 5 * DAY + 100

    def serve_wave(gw, rng, now):
        q = rng.randint(0, n_users, wave)
        t0 = time.perf_counter()
        tk = gw.submit_many([Request(user=int(u), now=int(now))
                             for u in q])
        gw.flush(now)
        return time.perf_counter() - t0, tk

    def drive(gw, tr, every, policy):
        rng = np.random.RandomState(1)
        erng = np.random.RandomState(2)
        gw.warm(np.arange(n_users), t00)
        serve_wave(gw, np.random.RandomState(99), t00)  # compile, untimed
        tr.step()                                       # compile, untimed
        serve_s = 0.0
        installs = []
        for i in range(n_waves):
            now = t00 + 60 * (i + 1)
            # feedback trickle keeps the trainer's log suffix non-empty
            gw.observe_many(erng.randint(0, n_users, 16),
                            erng.randint(0, n_items, 16),
                            np.full(16, now - 30))
            dt, _ = serve_wave(gw, rng, now)
            serve_s += dt
            if every and (i + 1) % every == 0:
                tr.step()
                patch = tr.make_patch()
                t0 = time.perf_counter()
                gw.install_patch(patch)
                installs.append(time.perf_counter() - t0)
            gw.tick(now + 30)       # rewarm policy rebuilds here
        st = gw.stats()
        return {
            "name": (f"every{every}_{policy}" if every else "none"),
            "install_every_waves": int(every),
            "policy": policy,
            "patches_applied": int(st.patches_applied),
            "model_version": int(st.model_version),
            "rps": float(n_waves * wave / serve_s),
            "hit_rate": float(st.cache["hits"]
                              / max(st.cache["hits"]
                                    + st.cache["misses"], 1)),
            "patch_install_max_ms": float(st.patch_install_max_ms),
            "patch_install_mean_ms": float(
                np.mean(installs) * 1e3 if installs else 0.0),
        }

    results = {"cadence": []}
    for every, policy in ((0, "purge"), (8, "purge"), (2, "purge"),
                          (2, "rewarm")):
        gw, tr = build(policy)
        row = drive(gw, tr, every, policy)
        results["cadence"].append(row)
        print(f"  {row['name']:>13s}: rps={row['rps']:8.1f} "
              f"hit={row['hit_rate']*100:5.1f}% "
              f"patches={row['patches_applied']:2d} "
              f"install max={row['patch_install_max_ms']:.2f}ms "
              f"mean={row['patch_install_mean_ms']:.2f}ms")

    # ---- swap equivalence: hot-swapped == cold from patched weights ---
    gw, tr = build()
    rng = np.random.RandomState(7)
    q = rng.randint(0, n_users, wave)
    gw.warm(np.arange(n_users), t00)
    serve_wave(gw, np.random.RandomState(99), t00)
    tr.step()
    patch = tr.make_patch()
    t0 = time.perf_counter()
    gw.install_patch(patch)
    install_ms = (time.perf_counter() - t0) * 1e3
    t2 = t00 + 600
    tk = [gw.submit(Request(user=int(u), now=t2)) for u in q]
    gw.flush(t2)
    cold_eng = ServingEngine(cfg, tr.params, scfg)
    cold = Gateway(cold_eng, FeatureInjector(
        InjectionConfig(policy="inject", feature_len=feature_len),
        gw.injector.batch, gw.injector.realtime),
        ServerConfig(slate_len=4, cache_entries=1024))
    ck = [cold.submit(Request(user=int(u), now=t2)) for u in q]
    cold.flush(t2)
    slates = np.stack([t.response.slate for t in tk])
    scores = np.stack([t.response.scores for t in tk])
    np.testing.assert_array_equal(
        slates, np.stack([t.response.slate for t in ck]))
    np.testing.assert_array_equal(
        scores, np.stack([t.response.scores for t in ck]))
    results["swap"] = {
        "bitwise_equal": True,
        "patches_applied": int(gw.stats().patches_applied),
        "model_version": int(gw.stats().model_version),
        "install_ms": float(install_ms),
        "patch_leaves": int(patch.n_leaves),
        "patch_params": int(patch.n_params),
    }
    print(f"  swap: {patch.n_leaves} leaves / {patch.n_params} params "
          f"installed in {install_ms:.2f}ms; responses bitwise equal "
          f"to cold gateway from patched weights")

    # ---- drift: online adapts, frozen does not ------------------------
    from repro.core.event_log import EventLog
    chunks = 16 if smoke else 30
    drift_at = chunks // 2
    d_users = 32
    log = EventLog(n_users=d_users)
    mk = lambda lr: OnlineTrainer(
        cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32),
        log, cfg=OnlineTrainerConfig(batch_size=8, seq_len=16,
                                     min_new_events=32),
        train_cfg=TrainConfig(adamw=AdamWConfig(
            lr=lr, warmup_steps=2, total_steps=100_000),
            remat=False, param_dtype=jnp.float32))
    online, frozen = mk(3e-2), mk(0.0)   # same batches, lr=0 never moves
    t = 0
    online_loss, frozen_loss = [], []
    for c in range(chunks):
        base = 0 if c < drift_at else 500
        for _ in range(64):
            u = t % d_users
            log.append(u, base + u, 1000 + t)
            t += 1
        mo, mf = online.step(), frozen.step()
        online_loss.append(float(mo["loss"]))
        frozen_loss.append(float(mf["loss"]))
    post = slice(-(chunks - drift_at) // 2, None)  # settled post-drift
    o_post = float(np.mean(online_loss[post]))
    f_post = float(np.mean(frozen_loss[post]))
    results["drift"] = {
        "chunks": chunks, "drift_chunk": drift_at,
        "online_loss": online_loss, "frozen_loss": frozen_loss,
        "online_post_drift_loss": o_post,
        "frozen_post_drift_loss": f_post,
        "adaptation_ratio": f_post / max(o_post, 1e-9),
    }
    print(f"  drift @ chunk {drift_at}: post-drift loss online="
          f"{o_post:.3f} frozen={f_post:.3f} "
          f"({results['drift']['adaptation_ratio']:.1f}x)")

    default_name = ("BENCH_online_smoke.json" if smoke
                    else "BENCH_online.json")
    out_path = out_path or os.path.join(ROOT, default_name)
    with open(out_path, "w") as f:
        json.dump({"suite": "online", "smoke": smoke,
                   "config": {"arch": cfg.name, "max_batch": 16,
                              "prefill_len": 64, "inject_len": 8,
                              "feature_len": feature_len,
                              "slate_len": 4},
                   "results": results}, f, indent=2)
    print(f"  wrote {os.path.abspath(out_path)}")
    return results


# ----------------------------------------------------------------------
def bench_serving_sharded(smoke: bool = False, out_path: str = None):
    """Data-parallel InjectionServer over 1 → 2 → 8 simulated devices.

    Same model/feature plane as the ``serving`` suite. **Strong
    scaling**: every mesh runs the identical serving configuration —
    ``max_batch=64`` panes, identical request stream (256-request waves,
    hot-user locality, warmed cache, interleaved ingest) — and only the
    ("data","model") mesh underneath changes: (1,1)/(2,1)/(8,1) built
    from forced host devices, so each pane splits into 64/32/8 rows per
    device. rps is total requests over summed serve() wall time.

    Identical pane shapes also make the equivalence check exact: the
    widest mesh must serve the same slates as the 1-device mesh (serving
    params are replicated over data and the partitioned programs are
    collective-free).

    Two scaling numbers are recorded, because simulated devices share
    this host's CPU cores:

    * ``wallclock_scaling_1_to_8`` — raw same-config wall-clock ratio.
      All 8 simulated devices contend for the same few cores (CI runners
      have 2-4), and a single device's XLA programs already engage the
      shared intra-op thread pool, so this is hard-capped near 1 by
      construction — it measures the host's core budget, not the
      sharding design.
    * ``rps_scaling_1_to_8`` (headline) — **isolated-shard scaling**.
      The serving programs are verified collective-free (the bench
      compiles the dp=8 inject/slate programs and records the collective
      instruction count in the JSON — it must be 0), so one device's
      shard computation is completely independent of its peers; on real
      multi-chip hardware the wave's wall time is one shard's wall time.
      The bench therefore *measures* a single shard serving its
      1/8 slice of the wave on a dedicated device (same per-device rows
      as the dp=8 mesh, own feature-plane slice of host work) and
      reports wave_time(1 device, full wave) / wave_time(one isolated
      shard) — simulating on the host what it cannot run.
    """
    print("\n== serving_sharded (data-parallel serving loop, CPU mesh) ==")
    from repro.configs.base import ModelConfig
    from repro.core.feature_store import BatchFeatureStore, FeatureStoreConfig
    from repro.core.injection import FeatureInjector, InjectionConfig
    from repro.core.realtime import RealtimeConfig, RealtimeFeatureService
    from repro.launch.mesh import make_serving_mesh
    from repro.models.model import init_params
    from repro.serving.engine import ServingConfig, ServingEngine
    from repro.serving.loop import InjectionServer, ServerConfig

    assert len(jax.devices()) >= 8, \
        "serving_sharded needs the forced-host-device XLA flag (set at " \
        "module import when this suite is on the command line)"

    n_items = 4000
    feature_len = 240
    max_batch = 64
    n_users = 500 if smoke else 2_000
    ev_per_user = 32 if smoke else 128
    mesh_sizes = [1, 8] if smoke else [1, 2, 8]
    rounds = 2 if smoke else 8
    wave = 256  # requests per serve() call = 4 panes at max_batch=64

    cfg = ModelConfig(
        name="itfi-ranker-bench", family="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=2, d_ff=512, vocab_size=n_items + 256,
        rope_theta=10000.0, tie_embeddings=True)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

    def build(dp, mb=max_batch):
        eng = ServingEngine(cfg, params, ServingConfig(
            max_batch=mb, prefill_len=256, inject_len=16,
            cache_capacity=512), mesh=make_serving_mesh(dp, 1))
        rng = np.random.RandomState(0)
        n = n_users * ev_per_user
        store = BatchFeatureStore(FeatureStoreConfig(
            n_users=n_users, feature_len=feature_len))
        rts = RealtimeFeatureService(RealtimeConfig(
            n_users=n_users, buffer_len=8, ingest_latency=0))
        us = rng.randint(0, n_users, n).astype(np.int64)
        its = rng.randint(0, n_items, n).astype(np.int64)
        tss = rng.randint(0, 5 * DAY, n).astype(np.int64)
        store.extend(us, its, tss)
        rts.extend(us, its, tss)
        inj = FeatureInjector(InjectionConfig(
            policy="inject", feature_len=feature_len), store, rts)
        return InjectionServer(eng, inj, ServerConfig(
            slate_len=4, cache_entries=4096))

    def req_users(rng, size):
        hot = max(n_users // 10, 1)
        pick_hot = rng.rand(size) < 0.8
        return np.where(pick_hot, rng.randint(0, hot, size),
                        rng.randint(0, n_users, size))

    def workload(srv, wave_n=None):
        wave_n = wave_n or wave
        rng = np.random.RandomState(1)
        now = 5 * DAY + 100

        def ingest_wave():
            u = req_users(rng, 64)
            it = rng.randint(0, n_items, 64)
            t = np.full(64, now - 30)
            srv.injector.batch.extend(u, it, t)
            srv.injector.realtime.extend(u, it, t)

        srv.warm(np.arange(n_users), now)
        ingest_wave()
        srv.serve(req_users(rng, wave_n), now)  # compile everything untimed
        lat = []
        for _ in range(rounds):
            ingest_wave()
            q = req_users(rng, wave_n)
            t0 = time.perf_counter()
            srv.serve(q, now)
            lat.append(time.perf_counter() - t0)
            now += 60
        return np.asarray(lat)

    def run_one(dp, mb, tag, wave_n=None):
        srv = build(dp, mb)
        lat = workload(srv, wave_n)
        wave_n = wave_n or wave
        rps = rounds * wave_n / lat.sum()
        row = {
            "data": dp, "model": 1, "max_batch": mb,
            "wave_requests": wave_n, "rounds": rounds, "rps": float(rps),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "cache": srv.cache.stats(),
        }
        print(f"  {tag:>16s} {mb:9d} {wave_n:5d} {rps:8.1f} "
              f"{row['p50_ms']:6.1f}ms {row['p99_ms']:7.1f}ms "
              f"{row['cache']['bytes_per_shard']:12d}")
        return row

    results = {"meshes": []}
    print(f"  {'mesh':>16s} {'max_batch':>9s} {'wave':>5s} {'req/s':>8s} "
          f"{'p50':>8s} {'p99':>9s} {'bytes/shard':>12s}")
    for dp in mesh_sizes:
        results["meshes"].append(run_one(dp, max_batch, f"{dp}x1"))
    r0, rN = results["meshes"][0], results["meshes"][-1]
    results["wallclock_scaling_1_to_8"] = rN["rps"] / r0["rps"]

    # Isolated-shard scaling: one dp=8 shard = an independent program on
    # 1/8 of the pane (verified collective-free below), serving its 1/8
    # slice of the wave on a dedicated device. wave_time(1 device, full
    # wave) / wave_time(isolated shard) is the multi-chip scaling this
    # host's shared cores cannot express as raw wall-clock.
    shard_rows = max_batch // 8
    shard = run_one(1, shard_rows, f"shard (1/8 wave)", wave_n=wave // 8)
    results["isolated_shard"] = shard
    results["rps_scaling_1_to_8"] = (
        r0["p50_ms"] / shard["p50_ms"])
    results["rps_scaling_1_to_8_method"] = (
        "p50 wave wall-time ratio: full 256-request wave on one device "
        "vs one shard (1/8 of the pane rows, 1/8 of the wave) on a "
        "dedicated device. Valid because the partitioned programs carry "
        "zero collectives (recorded below). Assumes host-side "
        "feature/pane assembly scales with shards (per-shard frontends, "
        "user-hash routing); a single-controller deployment where one "
        "python host assembles every pane is bounded by "
        "wallclock_scaling_1_to_8 instead.")
    print(f"  wall-clock scaling 1->{rN['data']} (shared-core host): "
          f"{results['wallclock_scaling_1_to_8']:.2f}x")
    print(f"  isolated-shard scaling 1->8 (headline): "
          f"{results['rps_scaling_1_to_8']:.2f}x")

    # evidence for the isolation argument: the dp=8 partitioned serving
    # programs must contain ZERO collective ops
    import re as _re
    widest = build(mesh_sizes[-1])
    eng = widest.engine
    toks, valid = eng.pad_tokens(
        [[1, 2, 3]] * max_batch, eng.scfg.prefill_len)
    st = eng.prefill(toks, valid)
    stoks, svalid = eng.pad_tokens([[4]] * max_batch,
                                   eng.scfg.inject_len, align="left")
    fb = np.zeros((max_batch, cfg.vocab_padded), np.float32)
    s2 = eng.inject(st, stoks, svalid, fallback_logits=fb)
    eng.decode_slate(s2, s2["first_logits"], 4)
    fin = eng.finalize(s2)
    pat = _re.compile(r"all-reduce|all-gather|collective-permute|"
                      r"all-to-all|reduce-scatter")
    n_coll = 0
    for lowered in (
            eng._prefill.lower(eng.params, jnp.asarray(toks),
                               jnp.asarray(valid)),
            eng._slate_fns[4].lower(
                eng.params, fin["caches"], fin["pos"],
                eng._place(s2["first_logits"], eng._tok_ns))):
        n_coll += len(pat.findall(lowered.compile().as_text()))
    results["collective_ops_in_partitioned_programs"] = n_coll
    print(f"  collectives in dp={mesh_sizes[-1]} serving programs: "
          f"{n_coll} (isolation argument holds iff 0)")

    # equivalence: identical request wave on fresh 1-device vs widest mesh
    s1, s8 = build(1), build(mesh_sizes[-1])
    rng = np.random.RandomState(2)
    now = 5 * DAY + 100
    u, it = req_users(rng, 64), rng.randint(0, n_items, 64)
    for srv in (s1, s8):
        srv.injector.batch.extend(u, it, np.full(64, now - 30))
        srv.injector.realtime.extend(u, it, np.full(64, now - 30))
    q = req_users(rng, max_batch)
    a = s1.serve(q, now - 60)  # admit, then hit — exercises the cached path
    a = s1.serve(q, now)
    b = s8.serve(q, now - 60)
    b = s8.serve(q, now)
    diff = float(np.abs(a.scores - b.scores).max())
    results["equivalence"] = {
        "logits_max_abs_diff": diff,
        "logits_allclose": bool(diff < 2e-3),
        "slates_equal": bool((a.slate == b.slate).all()),
    }
    print(f"  1x1 vs {mesh_sizes[-1]}x1: slates_equal="
          f"{results['equivalence']['slates_equal']} "
          f"logits max|Δ|={diff:.2e}")

    default_name = ("BENCH_serving_sharded_smoke.json" if smoke
                    else "BENCH_serving_sharded.json")
    out_path = out_path or os.path.join(ROOT, default_name)
    with open(out_path, "w") as f:
        json.dump({"suite": "serving_sharded", "smoke": smoke,
                   "config": {"arch": cfg.name, "max_batch": max_batch,
                              "prefill_len": 256, "inject_len": 16,
                              "feature_len": feature_len,
                              "n_users": n_users, "slate_len": 4},
                   "results": results}, f, indent=2)
    print(f"  wrote {os.path.abspath(out_path)}")
    return results


try:  # python -m benchmarks.run vs python benchmarks/run.py
    from benchmarks.ingest import bench_ingest
    from benchmarks.scenarios import bench_scenarios
except ImportError:
    from ingest import bench_ingest
    from scenarios import bench_scenarios

SECTIONS = {
    "ab_lift": bench_ab_lift,
    "latency_ablation": bench_latency_ablation,
    "injection_overhead": bench_injection_overhead,
    "serving_phases": bench_serving_phases,
    "kernel_micro": bench_kernel_micro,
    "feature_plane": bench_feature_plane,
    "serving": bench_serving,
    "serving_sharded": bench_serving_sharded,
    "scheduler": bench_scheduler,
    "rollover": bench_rollover,
    "online": bench_online,
    "scenarios": bench_scenarios,
    "ingest": bench_ingest,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(SECTIONS))
    ap.add_argument("--suite", default=None, choices=sorted(SECTIONS),
                    help="run a single suite (alias of --only)")
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes for CI (feature_plane/serving only)")
    ap.add_argument("--out", default=None,
                    help="output path for suites that write a BENCH json")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    pick = args.suite or args.only
    for name, fn in SECTIONS.items():
        if pick and name != pick:
            continue
        if name in ("feature_plane", "serving", "serving_sharded",
                    "scheduler", "rollover", "online", "scenarios",
                    "ingest"):
            if not pick:  # full-size suites take minutes — run them
                continue  # explicitly via --suite
            fn(smoke=args.smoke, out_path=args.out)
        else:
            fn()


if __name__ == "__main__":
    main()
