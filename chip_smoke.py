"""Smoke run of the serving path on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py             # one chip (what a plain run needs)
    python chip_smoke.py --chips 4   # sharded Gateway on one 4-chip host

One process, no CPU fallback, no interpret mode: without a TPU the run
stops before any phase and exits non-zero. Any failed phase or check
exits non-zero. With one chip the phases are, in order:

1. device    -- JAX must report a TPU.
2. kernels   -- each Pallas kernel runs compiled at the widths phase 3
                serves and is compared with its ``ref.py`` (history_merge
                exactly; the float kernels against a HIGHEST-precision
                reference, within KERNEL_RTOL of the reference's scale).
3. serve     -- ``Gateway`` -> injector -> engine with the paged device
                pool, at full registered width: ``mamba2-780m`` (48
                layers, d_model 1536, vocab 50280, float32 weights from
                a seed), then the paper's ``itfi-ranker``. A seeded
                feature plane of N_USERS users with HISTORY events each;
                the trace warms the pool, then serves cache hits, misses
                with eviction, the inject / batch / fresh policies and
                one snapshot rollover through submit / observe / tick /
                drain.
4. checks    -- the pool's scatter -> gather round trip is bitwise, and
                served scores agree with a HIGHEST-precision full
                prefill of (history ++ fresh suffix) within SCORE_RTOL,
                and miss by more both another user's reference and,
                for each row served with fresh events, the reference
                without them.

``--chips 4`` runs only the sharded path: the same trace through a
``Gateway`` whose engine is sharded over a ("data", "model") mesh of
4x1 and of 2x2, compared pane by pane with a one-device engine in the
same process, plus the round trip through the mesh pool's one-hot gather.

Wall times and peak device bytes are printed as smoke timings: one run,
compilation included -- not benchmark metrics. The last line of standard
output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
DAY = 86400

# serving shapes of phase 3 (and so the kernel widths of phase 2)
MAX_BATCH = 8
PREFILL_LEN = 256
INJECT_LEN = 16
CACHE_CAPACITY = 512       # ring KV slots: prefill + inject + slate fit
POOL_SLOTS = 32            # 32 x 77.4 MB mamba2-780m slots fit 16 GB
SLATE_LEN = 8
N_USERS = 2048
HISTORY = 256              # events per user in the seeded plane
RT_BUFFER = 16             # realtime ring per user
N_ITEMS = 5119             # item ids every served vocabulary holds
SERVE_ARCHS = ("mamba2-780m", "itfi-ranker")
SEED = 0                   # weights, feature plane, trace and kernel inputs
MESH_SHAPES = ((4, 1), (2, 2))

# kernel widths: granite-moe-3b-a800m attention heads, mamba2-780m SSD
ATTN_HEADS = dict(nq=24, nkv=8, hd=64)
SSD_HEADS = dict(nh=48, hp=64, ds=128, chunk=256)

# max |kernel - reference| / max |reference| allowed per float kernel
KERNEL_RTOL = 2e-2
# max |served - reference| / max |reference| over a served row's scores.
# The served path runs float32 matmuls at the TPU's default precision (one
# bfloat16 pass), the reference at HIGHEST. On a v5e the same full prefill
# at default precision is up to 4.5e-2 (mamba2-780m) and 1.8e-2
# (itfi-ranker) from HIGHEST over seeds 0 and 1, and served rows sit at
# the same scale (up to 4.4e-2 and 1.8e-2): 0.05 would leave 1.1x of
# headroom, 0.1 leaves 2.3x. A row's reference without its fresh events
# (at least 0.82 away) and another user's reference (at least 0.91) are
# farther than 0.1.
SCORE_RTOL = 1e-1


class SmokeFailure(RuntimeError):
    pass


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def info(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ----------------------------------------------------------------------
# Phase 2: kernels
# ----------------------------------------------------------------------

def kernel_cases():
    """``(name, make(key) -> args, kernel(*args), reference(*args))`` for
    each Pallas kernel at the widths phase 3 serves. history_merge must
    match exactly; the others within KERNEL_RTOL."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention.ops import decode_attention, ring_bias
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.history_merge.ops import history_merge
    from repro.kernels.history_merge.ref import history_merge_ref
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_ref_sequential

    b = MAX_BATCH
    bhsd = lambda t: jnp.moveaxis(t, 1, 2)

    def hm_make(key):
        k = jax.random.split(key, 6)
        ri = lambda kk, n, lo, hi: jax.random.randint(kk, (b, n), lo, hi,
                                                      jnp.int32)
        # a small item range forces duplicates across and within buffers
        return (ri(k[0], HISTORY, 0, 200), ri(k[1], HISTORY, 0, 5 * DAY),
                ri(k[2], HISTORY, 0, 2),
                ri(k[3], RT_BUFFER, 0, 200), ri(k[4], RT_BUFFER, 4 * DAY,
                                                6 * DAY),
                ri(k[5], RT_BUFFER, 0, 2))

    def ssd_make(key):
        nh, hp, ds = SSD_HEADS["nh"], SSD_HEADS["hp"], SSD_HEADS["ds"]
        k = jax.random.split(key, 5)
        x = jax.random.normal(k[0], (b, PREFILL_LEN, nh, hp)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(k[1], (b, PREFILL_LEN, nh))
                             - 2.0)
        A = -jnp.exp(jax.random.normal(k[2], (nh,)) * 0.3)
        B = jax.random.normal(k[3], (b, PREFILL_LEN, ds)) * 0.3
        C = jax.random.normal(k[4], (b, PREFILL_LEN, ds)) * 0.3
        return x, dt, A, B, C, jnp.ones((nh,), jnp.float32)

    def decode_make(key):
        nq, nkv, hd = ATTN_HEADS["nq"], ATTN_HEADS["nkv"], ATTN_HEADS["hd"]
        k = jax.random.split(key, 3)
        w = CACHE_CAPACITY
        # partial, half-full, full and wrapped rings
        pos = jnp.asarray([10, w // 2, w - 1, 2 * w] * (b // 4), jnp.int32)
        return (jax.random.normal(k[0], (b, 1, nq, hd)),
                jax.random.normal(k[1], (b, w, nkv, hd)),
                jax.random.normal(k[2], (b, w, nkv, hd)), pos)

    def decode_ref(q, kc, vc, pos):
        out = decode_attention_ref(bhsd(q), bhsd(kc), bhsd(vc),
                                   ring_bias(pos, kc.shape[1]))
        return bhsd(out)

    def flash_make(key):
        nq, nkv, hd = ATTN_HEADS["nq"], ATTN_HEADS["nkv"], ATTN_HEADS["hd"]
        k = jax.random.split(key, 3)
        s = PREFILL_LEN
        return (jax.random.normal(k[0], (b, s, nq, hd)),
                jax.random.normal(k[1], (b, s, nkv, hd)),
                jax.random.normal(k[2], (b, s, nkv, hd)))

    def flash_ref(q, k, v):
        return bhsd(attention_ref(bhsd(q), bhsd(k), bhsd(v), causal=True))

    return [
        ("history_merge", hm_make,
         lambda *a: history_merge(*a, out_len=HISTORY, impl="pallas"),
         lambda *a: history_merge_ref(*a, out_len=HISTORY)),
        ("ssd_scan", ssd_make,
         lambda *a: ssd_scan(*a, chunk=SSD_HEADS["chunk"]),
         ssd_ref_sequential),
        ("decode_attention", decode_make,
         lambda *a: decode_attention(*a, block_k=CACHE_CAPACITY),
         decode_ref),
        ("flash_attention", flash_make,
         lambda *a: flash_attention(*a, causal=True), flash_ref),
    ]


def run_kernels(seed: int) -> None:
    import jax

    for i, (name, make, kernel, reference) in enumerate(kernel_cases()):
        args = make(jax.random.PRNGKey(seed + i))
        got = jax.tree.leaves(jax.block_until_ready(kernel(*args)))
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(reference(*args))
        got = [np.asarray(g) for g in got]
        want = [np.asarray(w) for w in want]
        check(all(g.shape == w.shape for g, w in zip(got, want)),
              f"{name}: output shapes differ from its reference")
        diff = max(float(np.max(np.abs(g.astype(np.float64) - w)))
                   for g, w in zip(got, want))
        if name == "history_merge":
            info(f"kernel {name}: max|d|={diff} (must be 0: int outputs)")
            check(diff == 0, f"{name}: outputs differ from its reference")
            continue
        scale = max(float(np.max(np.abs(w))) for w in want)
        info(f"kernel {name}: max|d|={diff:.3e} ref max|x|={scale:.3e} "
             f"rel={diff / scale:.3e} (tol {KERNEL_RTOL})")
        check(diff <= KERNEL_RTOL * scale,
              f"{name}: max|d| {diff} exceeds {KERNEL_RTOL} x {scale}")


# ----------------------------------------------------------------------
# Phase 3/4: the Gateway path
# ----------------------------------------------------------------------

def build_engine(cfg, params, mesh=None):
    from repro.serving.engine import ServingConfig, ServingEngine
    return ServingEngine(cfg, params, ServingConfig(
        max_batch=MAX_BATCH, prefill_len=PREFILL_LEN, inject_len=INJECT_LEN,
        cache_capacity=CACHE_CAPACITY), mesh=mesh)


def build_gateway(engine, seed: int):
    """Gateway over a freshly seeded feature plane: N_USERS users with
    HISTORY events each over five days, every one also in the realtime
    service (whose ring keeps the newest RT_BUFFER)."""
    from repro.core.feature_store import BatchFeatureStore, FeatureStoreConfig
    from repro.core.injection import FeatureInjector, InjectionConfig
    from repro.core.realtime import RealtimeConfig, RealtimeFeatureService
    from repro.serving.scheduler import Gateway, ServerConfig

    rng = np.random.RandomState(seed)
    users = np.repeat(np.arange(N_USERS), HISTORY)
    items = rng.randint(0, N_ITEMS, users.size)
    ts = rng.randint(0, 5 * DAY, users.size)
    store = BatchFeatureStore(FeatureStoreConfig(n_users=N_USERS,
                                                 feature_len=HISTORY))
    rts = RealtimeFeatureService(RealtimeConfig(
        n_users=N_USERS, buffer_len=RT_BUFFER, ingest_latency=0))
    store.extend(users, items, ts)
    rts.extend(users, items, ts)
    inj = FeatureInjector(InjectionConfig(policy="inject",
                                          feature_len=HISTORY), store, rts)
    return Gateway(engine, inj, ServerConfig(slate_len=SLATE_LEN,
                                             pool_slots=POOL_SLOTS))


def reference_rows(gw, users, policies, now, precision="highest",
                   suffix=True):
    """What the served rows should score: a full prefill of each row's
    history ++ fresh suffix -- the token streams the Gateway feeds its
    cached path -- at the given matmul precision (None: the default the
    served path runs at). ``suffix=False`` drops the fresh suffix of the
    inject rows: what a server that lost the injection would score.
    Returns (scores (n, V), greedy slates (n, SLATE_LEN))."""
    import contextlib

    import jax

    from repro.core.pipeline import items_to_tokens

    eng, inj = gw.engine, gw.injector
    u = np.asarray(users, np.int64)
    streams = []
    for i, pol in enumerate(policies):
        look = (inj.batch.lookup_at_cutoff if pol == "fresh"
                else inj.batch.lookup)
        items, _, valid = look(u[i:i + 1], now)
        hist = items_to_tokens(items, valid)[0][valid[0] > 0].tolist()
        sfx = (inj.fresh_suffix_tokens(u[i:i + 1], now, cap=INJECT_LEN)[0]
               if pol == "inject" and suffix else [])
        streams.append(hist[-PREFILL_LEN:] + sfx)
    n = PREFILL_LEN + INJECT_LEN
    if eng.cfg.ssm is not None:  # the SSD scan takes whole chunks
        c = eng.cfg.ssm.chunk_size
        n = -(-n // c) * c
    toks, valid = eng.pad_tokens(streams, n)
    with (contextlib.nullcontext() if precision is None
          else jax.default_matmul_precision(precision)):
        state = eng.prefill(toks, valid)
        first = state["logits"][:, -1]
        slates = eng.decode_slate(state, first, SLATE_LEN)
    v = eng.cfg.vocab_size
    n = len(users)
    return np.asarray(first)[:n, :v], slates[:n]


def rel_diffs(scores, ref_scores):
    """Per row: max |score - reference| over the reference row's largest
    magnitude."""
    return [float(np.max(np.abs(s - r)) / np.max(np.abs(r)))
            for s, r in zip(scores, ref_scores)]


def compare_rows(name, served_scores, served_slates, ref_scores, ref_slates,
                 near=()):
    """Served rows must match their reference rows within SCORE_RTOL, and
    the check must have teeth: every served row, held against the next
    row's reference (another user's stream), must miss it by more, as must
    each ``(served, faulty reference)`` pair in ``near``."""
    rel = rel_diffs(served_scores, ref_scores)
    wrong = rel_diffs(served_scores, ref_scores[1:] + ref_scores[:1])
    equal = [bool(np.array_equal(a, b))
             for a, b in zip(served_slates, ref_slates)]
    info(f"{name}: {len(rel)} rows, max rel|d|={max(rel):.3e} (tol "
         f"{SCORE_RTOL}), against another row's reference min rel|d|="
         f"{min(wrong):.3e}, equal slates {sum(equal)}/{len(equal)}")
    check(max(rel) <= SCORE_RTOL,
          f"{name}: served scores differ from the reference by "
          f"{max(rel):.3e} > {SCORE_RTOL}")
    check(min(wrong) > SCORE_RTOL,
          f"{name}: another user's reference is within {SCORE_RTOL} too; "
          f"the comparison cannot tell rows apart")
    if near:
        miss = rel_diffs(*zip(*near))
        info(f"{name}: {len(miss)} rows with fresh events against their "
             f"reference without them min rel|d|={min(miss):.3e}")
        check(min(miss) > SCORE_RTOL,
              f"{name}: a row's reference without its fresh events is "
              f"within {SCORE_RTOL}; the comparison cannot see a lost "
              f"injection")


def serve_trace(gw, seed: int, on_pane=None):
    """The phase-3 request trace. Returns, pane by pane, ``(name, users,
    policies, now, slates, scores)`` in submit order. ``on_pane(gw,
    pane)`` runs right after each pane is served, before anything else
    touches the gateway."""
    from repro.serving.api import Request

    rng = np.random.RandomState(seed + 1)
    v = gw.engine.cfg.vocab_size
    panes = []

    def fresh_events(users, now, n=2):
        for u in users:
            for _ in range(n):
                gw.observe((int(u), int(rng.randint(0, N_ITEMS)), now - 30))

    def serve(name, users, policies, now, hits=None):
        tickets = [gw.submit(Request(user=int(u), now=now, policy=p))
                   for u, p in zip(users, policies)]
        if len(users) < MAX_BATCH:
            gw.drain(now)
        check(all(t.done for t in tickets), f"pane {name}: not served")
        got = [t.response.telemetry.cache_hit for t in tickets]
        check(hits is None or got == hits,
              f"pane {name}: cache hits {got}, expected {hits}")
        pane = (name, list(users), list(policies), now,
                [t.response.slate for t in tickets],
                [t.response.scores[:v] for t in tickets])
        panes.append(pane)
        if on_pane is not None:
            on_pane(gw, pane)
        return tickets

    now = 5 * DAY + 100
    warmed = gw.warm(np.arange(POOL_SLOTS), now)
    check(warmed == POOL_SLOTS, f"warm admitted {warmed} of {POOL_SLOTS}")
    # cache hits with fresh events injected
    hit = list(range(8))
    fresh_events(hit, now)
    serve("hit-inject", hit, ["inject"] * 8, now, hits=[True] * 8)
    # hits: control rows (cached state, no suffix) beside treatment rows
    mixed = list(range(8, 16))
    fresh_events(mixed[1::2], now)
    serve("hit-batch+inject", mixed, ["batch", "inject"] * 4, now,
          hits=[True] * 8)
    # misses into a full pool: each admission evicts an LRU entry
    # (users 16..23, warmed but never served since)
    miss = list(range(1000, 1008))
    fresh_events(miss[::2], now)
    serve("miss-evict", miss, ["inject"] * 8, now, hits=[False] * 8)
    # oracle rows (prefilled at the request cutoff, never cached; their
    # scratch slots evict 27..30) in a short pane beside hits
    fresh_events([24, 25, 26], now, n=1)
    serve("fresh+inject", [1500, 1501, 1502, 1503, 24, 25, 26],
          ["fresh"] * 4 + ["inject"] * 3, now, hits=[False] * 4 + [True] * 3)
    # the daily snapshot lands: users whose history did not change keep
    # their slots (rekeyed), the others miss
    now = 6 * DAY + 100
    gw.tick(now)
    kept = [8, 10, 12, 14, 31, 1001, 1003, 1005]
    fresh_events(kept, now)
    serve("rolled-hit-inject", kept, ["inject"] * 8, now, hits=[True] * 8)
    changed = [0, 1, 2, 3, 1000, 1002, 1004, 1006]
    fresh_events(changed[:4], now)
    serve("rolled-miss", changed, ["inject"] * 4 + ["batch"] * 4, now,
          hits=[False] * 8)

    st = gw.stats()
    c, ro, paths = st["cache"], st["rollover"], st["paths"]
    info(f"trace: paths={paths} hits={c['hits']} misses={c['misses']} "
         f"evictions={c['evictions']} rollovers={ro['rollovers']} "
         f"rekeyed={ro['rekeyed']} invalidated={ro['invalidated']}")
    check(c["hits"] > 0 and c["evictions"] > 0, "no cache hit or eviction")
    check(ro["rollovers"] >= 1 and ro["rekeyed"] > 0, "no warm rollover")
    check(all(paths[p] > 0 for p in ("inject", "cached", "prefill")),
          f"a serving path was not taken: {paths}")
    return panes


def pool_round_trip(pool, seed: int) -> None:
    """Scatter random rows into scattered slots, gather them back: every
    leaf must come back bit for bit (floats with full mantissas, ints over
    the whole int32 range)."""
    import jax
    import jax.numpy as jnp

    eng = pool.engine
    logits_s, caches_s = eng.prefill_state_shapes()
    b, p = MAX_BATCH, PREFILL_LEN
    shapes = {"caches": caches_s,
              "valid": jax.ShapeDtypeStruct((b, p), jnp.bool_),
              "next_pos": jax.ShapeDtypeStruct((b,), jnp.int32),
              "logits": logits_s}
    leaves, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def rand(k, s):
        if s.dtype == jnp.bool_:
            return jax.random.bernoulli(k, 0.5, s.shape)
        if jnp.issubdtype(s.dtype, jnp.integer):
            return jax.random.randint(k, s.shape, jnp.iinfo(jnp.int32).min,
                                      jnp.iinfo(jnp.int32).max, s.dtype)
        return jax.random.normal(k, s.shape, s.dtype)

    state = jax.tree.unflatten(treedef, [rand(k, s)
                                         for k, s in zip(keys, leaves)])
    host = jax.tree.map(np.asarray, state)
    slots = [3 * i + 1 for i in range(b)]
    pool.scatter(state, slots)
    got, last = pool.gather(slots)
    pairs = ([(np.asarray(g), w) for g, w in zip(
        jax.tree.leaves(got["caches"]), jax.tree.leaves(host["caches"]))]
        + [(np.asarray(got["valid"]), host["valid"]),
           (np.asarray(got["next_pos"]), host["next_pos"]),
           (np.asarray(last), host["logits"][:, -1])])
    as_bytes = lambda a: np.ascontiguousarray(a).view(np.uint8)
    bad = sum(int(np.sum(as_bytes(g) != as_bytes(w))) for g, w in pairs)
    info(f"pool round trip: {len(pairs)} leaves, "
         f"{sum(w.nbytes for _, w in pairs)} bytes, {bad} bytes differ")
    check(bad == 0, "pool gather(scatter(x)) != x")


def device_bytes():
    import jax
    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()]


def peak_bytes():
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def init_params_for(arch: str, seed: int):
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models.model import init_params

    cfg = get_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    n = sum(x.size for x in jax.tree.leaves(params))
    info(f"{arch}: {cfg.n_layers} layers, d_model {cfg.d_model}, vocab "
         f"{cfg.vocab_size}, {n} float32 parameters")
    return cfg, params


def run_serve(arch: str, seed: int) -> None:
    import jax

    t0 = time.perf_counter()
    cfg, params = init_params_for(arch, seed)
    gw = build_gateway(build_engine(cfg, params), seed)
    info(f"{arch}: pool {POOL_SLOTS} slots x {gw.pool.slot_nbytes} bytes; "
         f"setup wall_s={time.perf_counter() - t0:.1f}")
    pool_round_trip(gw.pool, seed)

    rows = {"scores": [], "slates": [], "ref_scores": [], "ref_slates": [],
            "floor_scores": [], "near": []}

    def on_pane(gw, pane):
        name, users, policies, now, slates, scores = pane
        ref_scores, ref_slates = reference_rows(gw, users, policies, now)
        floor_scores, _ = reference_rows(gw, users, policies, now, None)
        bare_scores, _ = reference_rows(gw, users, policies, now,
                                        suffix=False)
        rows["scores"] += scores
        rows["slates"] += slates
        rows["ref_scores"] += list(ref_scores)
        rows["ref_slates"] += list(ref_slates)
        rows["floor_scores"] += list(floor_scores)
        # rows whose reference moves with the fresh suffix
        rows["near"] += [(s, b) for s, r, b in
                         zip(scores, ref_scores, bare_scores)
                         if not np.array_equal(r, b)]

    t0 = time.perf_counter()
    serve_trace(gw, seed, on_pane)
    info(f"{arch}: trace + references wall_s={time.perf_counter() - t0:.1f}"
         f" peak_bytes_in_use={peak_bytes()}")
    # the precision floor: the same full prefill at the served path's
    # default matmul precision, against HIGHEST
    floor = rel_diffs(rows["floor_scores"], rows["ref_scores"])
    info(f"{arch} default-precision full prefill vs HIGHEST: max rel|d|="
         f"{max(floor):.3e}")
    check(rows["near"], "no served row had fresh events")
    compare_rows(f"{arch} served vs full prefill", rows["scores"],
                 rows["slates"], rows["ref_scores"], rows["ref_slates"],
                 rows["near"])


def run_mesh(arch: str, seed: int) -> None:
    """The trace on a one-device engine, then on each mesh; pane by pane
    the sharded gateway must serve what the one-device one served."""
    import jax

    from repro.launch.mesh import make_serving_mesh

    cfg, params = init_params_for(arch, seed)
    host_params = jax.device_get(params)
    del params
    one = jax.device_put(host_params, jax.devices()[0])
    gw = build_gateway(build_engine(cfg, one), seed)
    t0 = time.perf_counter()
    base = serve_trace(gw, seed)
    info(f"{arch} one device: trace wall_s={time.perf_counter() - t0:.1f}")
    del gw, one
    gc.collect()
    for shape in MESH_SHAPES:
        mesh = make_serving_mesh(*shape)
        t0 = time.perf_counter()
        gw = build_gateway(build_engine(cfg, host_params, mesh=mesh), seed)
        pool_round_trip(gw.pool, seed)
        panes = serve_trace(gw, seed)
        info(f"{arch} mesh {shape[0]}x{shape[1]}: trace wall_s="
             f"{time.perf_counter() - t0:.1f} bytes_in_use per device="
             f"{device_bytes()}")
        for a, b in zip(base, panes):
            check(a[1] == b[1] and a[2] == b[2], "trace diverged")
        compare_rows(f"{arch} mesh {shape[0]}x{shape[1]} vs one device",
                     [s for p in panes for s in p[5]],
                     [s for p in panes for s in p[4]],
                     [s for p in base for s in p[5]],
                     [s for p in base for s in p[4]])
        del gw
        gc.collect()


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded Gateway phase on a "
                         "4x1 and a 2x2 mesh")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found -- JAX's first device is on "
              f"{dev.platform!r}; this smoke run needs a chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    info(f"device kind={dev.device_kind!r} count={len(devices)} "
         f"jax={jax.__version__} seed={SEED}")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    info(f"compile cache: {enable_compile_cache()}")
    compile_s, cache_hits = [0.0], [0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    t_start = time.perf_counter()
    if args.chips == 4:
        run_mesh(SERVE_ARCHS[0], SEED)
    else:
        t0 = time.perf_counter()
        run_kernels(SEED)
        info(f"kernels wall_s={time.perf_counter() - t0:.1f}")
        for arch in SERVE_ARCHS:
            run_serve(arch, SEED)
            gc.collect()
    info(f"total wall_s={time.perf_counter() - t_start:.1f} "
         f"backend_compile_s={compile_s[0]:.1f} "
         f"persistent_cache_hits={cache_hits[0]}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
