"""Serving launcher — the ITFI flow on the serving engine.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --reduced

Demonstrates the three-phase request path (DESIGN.md §2):
  1. prefill(batch_history)    — daily-job-cacheable state
  2. inject(fresh_events)      — the paper's inference-time injection
  3. decode                    — unchanged serving

and prints per-phase timings, showing injection costs O(suffix) rather
than O(history).

``--loop`` instead drives the **request-level Gateway** (feature stores
-> injector -> prefill-state cache -> engine behind the micro-batching
scheduler) with a deterministic seeded request trace: arrivals trickle
in one at a time (``gateway.submit``), feedback events ride along
between them (``gateway.observe``), panes flush on pane-full or
deadline (``gateway.tick``), and a per-request A/B split
(``--ab``: hash-assigned control/treatment arms as per-request
policies) shares the same panes. Served results are claimed off the
streaming surface (``gateway.poll``). Prints per-round throughput plus
the gateway's structured telemetry summary (paths, queue-delay
percentiles, cache stats):

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --reduced \
      --loop --users 500 --rounds 4 [--ab]

``--pool SLOTS`` swaps the host LRU for the paged device-resident
state pool (slot-table cache, one-hot gather/scatter pane assembly)
and ``--max-wait SECS`` turns on continuous batching (0 = serve every
arrival immediately in a padded partial pane — the latency floor):

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --reduced \
      --loop --pool 512 --max-wait 0 --users 500 --rounds 4

``--mesh data,model`` runs either mode **sharded**: the engine jits with
NamedSharding in/out specs over a ("data", "model") mesh and request
panes split over the data axis (``--batch`` must divide it). With
``JAX_PLATFORMS=cpu`` the launcher reuses the dry-run's forced-host-device
XLA trick so e.g. ``--mesh 8,1 --batch 16`` is runnable (and CI-testable)
on one machine; on any other platform the mesh takes real devices:

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
      --arch mamba2-780m --reduced --loop --mesh 8,1 --batch 16 \
      --users 500 --rounds 4
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

# NOTE: jax is imported inside main(), after --mesh handling — forcing
# host devices for the CPU multi-device path must precede the first jax
# device query.

DAY = 86400


def run_loop(cfg, params, args, mesh=None) -> None:
    """Deterministic seeded request trace through the Gateway:
    per-request arrivals interleaved with feedback events, pane-full and
    deadline flushes, optional per-request A/B arms."""
    from repro.core.ab import ARM_POLICIES, request_arm
    from repro.core.feature_store import (BatchFeatureStore,
                                          FeatureStoreConfig)
    from repro.core.injection import FeatureInjector, InjectionConfig
    from repro.core.realtime import RealtimeConfig, RealtimeFeatureService
    from repro.serving.api import Request
    from repro.serving.engine import ServingConfig, ServingEngine
    from repro.serving.scheduler import Gateway, ServerConfig

    n_users, n_items = args.users, cfg.vocab_size - 256
    feature_len = min(args.history, 64)
    eng = ServingEngine(cfg, params, ServingConfig(
        max_batch=args.batch, prefill_len=args.history,
        inject_len=args.fresh,
        cache_capacity=args.history + args.fresh + 64), mesh=mesh)
    rng = np.random.RandomState(args.seed)

    store = BatchFeatureStore(FeatureStoreConfig(
        n_users=n_users, feature_len=feature_len))
    rts = RealtimeFeatureService(RealtimeConfig(
        n_users=n_users, buffer_len=16, ingest_latency=0))
    n_ev = n_users * 16
    us = rng.randint(0, n_users, n_ev)
    its = rng.randint(0, n_items, n_ev)
    tss = rng.randint(0, 5 * DAY, n_ev)
    store.extend(us, its, tss)
    rts.extend(us, its, tss)
    inj = FeatureInjector(InjectionConfig(
        policy=args.policy, feature_len=feature_len), store, rts)
    gw = Gateway(eng, inj, ServerConfig(
        slate_len=4, cache_entries=n_users,
        pool_slots=args.pool, max_wait=args.max_wait,
        snapshot_build_budget=args.build_budget,
        rewarm_budget=args.rewarm))
    if args.pool:
        print(f"paged state pool: {args.pool} device slots x "
              f"{gw.pool.slot_nbytes / 1e6:.2f} MB/slot"
              + (f", continuous max_wait={args.max_wait}s"
                 if args.max_wait is not None else ""))

    now = 5 * DAY + 100
    t0 = time.time()
    warmed = gw.warm(np.arange(n_users), now)
    print(f"warm: {warmed} prefill states in {time.time() - t0:.1f}s "
          f"(incl. compile)")

    deadline = args.batch * 2  # seconds an arrival may wait in the queue
    per_round = args.batch * 4
    for r in range(args.rounds):
        tickets = []
        t0 = time.time()
        for _ in range(per_round):
            # the trace interleaves arrivals with feedback events
            # (~1 event per 4 requests), all from one seeded stream
            if rng.rand() < 0.25:
                gw.observe((int(rng.randint(0, n_users)),
                            int(rng.randint(0, n_items)), now - 30))
            u = int(rng.randint(0, n_users))
            if args.ab:
                arm = request_arm(u, salt=args.seed)
                req = Request(user=u, now=now, policy=ARM_POLICIES[arm],
                              tag=arm, deadline=now + deadline)
            else:
                req = Request(user=u, now=now, deadline=now + deadline)
            tickets.append(gw.submit(req))
            now += 1  # one arrival per second
        served = gw.drain(now + deadline)  # tail deadline fires + claim
        dt = time.time() - t0
        assert all(t.done for t in tickets)
        assert {t.request_id for t in served} >= {t.request_id
                                                 for t in tickets}
        hits = sum(t.response.telemetry.cache_hit for t in tickets)
        qd = np.array([t.response.telemetry.queue_delay for t in tickets])
        print(f"round {r}: {len(tickets)} reqs in {dt * 1e3:6.1f}ms "
              f"({len(tickets) / dt:7.1f} req/s) hits={hits} "
              f"queue-delay p50={np.percentile(qd, 50):.0f}s "
              f"max={qd.max()}s slate[0]="
              f"{tickets[0].response.slate.tolist()}")
        # next round's arrivals must not be stamped behind the clock the
        # tail-flush tick just advanced to (now + deadline) — a backdated
        # arrival would inflate its queue-delay telemetry
        now += max(60, deadline)
        if args.roll_midway and r == args.rounds // 2 - 1:
            # jump the clock past the next daily boundary so the second
            # half of the trace serves across a generation rollover
            # (warm handoff; with --build-budget the build amortizes
            # over the ticks the serving rounds issue)
            now = ((now // DAY) + 1) * DAY + 100
            gw.tick(now)
            ro = gw.stats()["rollover"]
            print(f"-- generation rollover at {now}: rekeyed="
                  f"{ro['rekeyed']} invalidated={ro['invalidated']} "
                  f"pending_build={ro['pending_build_users']} "
                  f"pending_rewarm={ro['pending_rewarm']}")

    st = gw.stats()
    if args.ab:
        by_arm = {}
        for t in tickets:
            by_arm.setdefault(t.response.telemetry.tag, 0)
            by_arm[t.response.telemetry.tag] += 1
        print(f"last-round arms (mixed panes): {by_arm}")
    print(f"telemetry: paths={st['paths']} "
          f"queue_delay p50={st['queue_delay']['p50']:.0f}s "
          f"p99={st['queue_delay']['p99']:.0f}s "
          f"deadline_flushes={st['deadline_flushes']} "
          f"panes={st['panes']}")
    ro = st["rollover"]
    print(f"rollover: rollovers={ro['rollovers']} rekeyed={ro['rekeyed']} "
          f"invalidated={ro['invalidated']} rebuilt={ro['rebuilt']} "
          f"build_steps={ro['build_steps']} "
          f"build_time={ro['build_time_s']*1e3:.1f}ms")
    print(f"stats: {st.as_dict()}")


def run_scenario_cli(args) -> None:
    """--scenario NAME: replay one named production traffic scenario
    (serving/loadgen.py) against the chosen arch and print the SLO
    scorecard. mixed_fleet keeps its own multi-arch roster; every other
    scenario runs on a reduced variant of ``--arch``."""
    import dataclasses

    from repro.serving.loadgen import get_scenario, run_scenario

    spec = get_scenario(args.scenario, smoke=args.smoke)
    if args.arch and not spec.archs:
        spec = dataclasses.replace(spec, archs=(args.arch,))
    print(f"scenario {spec.name}: horizon={spec.horizon}s "
          f"users={spec.n_users} shed_policy={spec.shed_policy}")
    for res in run_scenario(spec):
        m = res.metrics
        print(f"\n[{res.arch}] trace={res.trace_fingerprint} "
              f"slates={res.slate_fingerprint}")
        print(f"  requests={m['requests']} served={m['served']} "
              f"shed={m['shed']} deadline_misses={m['deadline_misses']} "
              f"hit_rate={m['hit_rate']:.2f}")
        print(f"  queue delay p50/p99/max = {m['queue_delay']['p50']:.0f}/"
              f"{m['queue_delay']['p99']:.0f}/{m['queue_delay']['max']}s")
        for g in res.gates:
            mark = "PASS" if g["pass"] else "FAIL"
            print(f"  [{mark}] {g['gate']:22s} budget={g['budget']} "
                  f"actual={g['actual']}")
        print(f"  SLO: {'PASS' if res.slo_pass else 'FAIL'}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="registered model config (required unless "
                         "--scenario, which defaults to its own tiny "
                         "ranker / fleet roster)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--history", type=int, default=256)
    ap.add_argument("--fresh", type=int, default=8)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--loop", action="store_true",
                    help="drive the request-level Gateway with a seeded trace")
    ap.add_argument("--users", type=int, default=200)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--policy", default="inject",
                    choices=["batch", "inject", "fresh"])
    ap.add_argument("--ab", action="store_true",
                    help="--loop: per-request A/B arms (hash-assigned "
                         "control=batch / treatment=inject policies "
                         "sharing the same mixed-policy panes)")
    ap.add_argument("--roll-midway", action="store_true",
                    help="--loop: jump the clock past a daily boundary "
                         "halfway through the trace so the second half "
                         "serves across a generation rollover (warm "
                         "handoff)")
    ap.add_argument("--build-budget", type=int, default=None,
                    help="--loop: amortize snapshot builds — at most "
                         "this many users materialized per clock call "
                         "(default: synchronous full build)")
    ap.add_argument("--rewarm", type=int, default=0,
                    help="--loop: re-prefill up to this many "
                         "rollover-invalidated users per tick")
    ap.add_argument("--pool", type=int, default=None, metavar="SLOTS",
                    help="--loop: paged device-resident state pool with "
                         "this many slots (replaces the host LRU; must "
                         "be >= --batch)")
    ap.add_argument("--max-wait", type=int, default=None, metavar="SECS",
                    help="--loop: continuous batching — flush a partial "
                         "pane once its oldest arrival has waited this "
                         "long (0 = serve every arrival immediately)")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="run sharded over a data,model mesh (e.g. 8,1); "
                         "--batch must be a multiple of the data size")
    ap.add_argument("--scenario", default=None, metavar="NAME",
                    help="replay a named production traffic scenario "
                         "(diurnal / flash_crowd / cold_start_storm / "
                         "churn_heavy / mixed_fleet) through the Gateway "
                         "against this --arch (reduced shapes) and print "
                         "the SLO scorecard; --smoke shrinks the trace")
    ap.add_argument("--smoke", action="store_true",
                    help="--scenario: short-horizon variant of the trace")
    args = ap.parse_args()

    if args.scenario:
        run_scenario_cli(args)
        return
    if args.arch is None:
        ap.error("--arch is required (except with --scenario)")

    mesh_shape = None
    if args.mesh:
        mesh_shape = tuple(int(x) for x in args.mesh.split(","))
        if len(mesh_shape) != 2:
            raise SystemExit("--mesh wants two sizes: data,model")
        n = mesh_shape[0] * mesh_shape[1]
        if n > 1 and os.environ.get("JAX_PLATFORMS") == "cpu":
            # the dry-run trick: simulate the mesh's devices on one CPU
            # host (must land in XLA_FLAGS before jax first initializes).
            # Only when the CPU is asked for explicitly: a chip host whose
            # TPU failed to initialise must not silently serve on fakes
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={n}")

    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_serving_mesh
    from repro.models.model import init_params
    from repro.serving.engine import ServingConfig, ServingEngine

    enable_compile_cache()
    mesh = None
    if mesh_shape is not None:
        mesh = make_serving_mesh(*mesh_shape)
        print(f"mesh: data={mesh_shape[0]} model={mesh_shape[1]} "
              f"({len(jax.devices())} devices visible)")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params = init_params(cfg, jax.random.PRNGKey(args.seed),
                         dtype=jnp.float32)

    if args.loop:
        run_loop(cfg, params, args, mesh=mesh)
        return

    scfg = ServingConfig(max_batch=args.batch, prefill_len=args.history,
                         inject_len=args.fresh,
                         cache_capacity=args.history + args.fresh + 64)
    eng = ServingEngine(cfg, params, scfg, mesh=mesh)
    rng = np.random.RandomState(args.seed)

    hists = [list(rng.randint(1, cfg.vocab_size, rng.randint(
        args.history // 2, args.history))) for _ in range(args.batch)]
    fresh = [list(rng.randint(1, cfg.vocab_size, rng.randint(1, args.fresh)))
             for _ in range(args.batch)]

    def timed(name, fn, *a):
        t0 = time.time()
        out = fn(*a)
        jax.block_until_ready(jax.tree.leaves(out)[0])
        t1 = time.time()
        out2 = fn(*a)  # warm (jit-cached) call
        jax.block_until_ready(jax.tree.leaves(out2)[0])
        print(f"{name:22s} cold={t1 - t0:7.3f}s warm={time.time() - t1:7.3f}s")
        return out2

    toks, valid = eng.pad_tokens(hists, args.history)
    state = timed("prefill(batch hist)", eng.prefill, toks, valid)
    stoks, svalid = eng.pad_tokens(fresh, args.fresh, align="left")
    state = timed("inject(fresh events)", eng.inject, state, stoks, svalid)
    dec = timed("finalize(ring cache)", eng.finalize, state)

    tok = np.array([[1]] * args.batch, np.int32)
    t0 = time.time()
    for i in range(args.decode_steps):
        logits, dec = eng.decode(dec, tok)
        tok = np.asarray(eng.sample(logits))[:, None]
    jax.block_until_ready(logits)
    dt = (time.time() - t0) / args.decode_steps
    print(f"decode: {args.decode_steps} steps, {dt * 1e3:.1f} ms/step "
          f"(incl. first-step compile)")


if __name__ == "__main__":
    main()
