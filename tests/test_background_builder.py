"""Concurrency battery for the off-thread snapshot builder.

The `BackgroundSnapshotBuilder` is the repo's first real concurrency:
a worker thread builds the next generation's feature plane against a
frozen ``EventLog.view()`` while the serving thread keeps appending,
then the serving thread installs the finished arrays atomically. These
tests pin the contract from three sides:

* **differential** — the background-built generation is bit-for-bit
  equal to the ``run_snapshot`` oracle, under concurrent appends
  (including late events with old in-window timestamps landing
  mid-build), with the interleaving made deterministic by a
  step-barrier hook on the builder thread;
* **certification** — ``changed_users_between`` still certifies the
  handoff delta after the off-thread path (superset of the true row
  diff, so the warm rekey stays safe);
* **rollover-aware eviction order** — during the handoff window both
  caches hold dual-generation entries for changed users, and those
  evict before any live entry under budget pressure (host LRU by
  entry/byte budget; paged pool by slot pressure, pin-aware).
"""
import threading
import time

import numpy as np
import pytest

from conftest import DAY, N_ITEMS, N_USERS, make_gateway, tiny_engine

from repro.core.feature_store import BatchFeatureStore, FeatureStoreConfig
from repro.serving.api import Request
from repro.serving.scheduler import PrefillStateCache

G1, G2 = 5 * DAY, 6 * DAY


def _seeded_stores(n=2, n_users=200, feature_len=16, seed=0, events=900):
    """``n`` stores fed the identical event stream, snapshotted at G1."""
    rng = np.random.RandomState(seed)
    us = rng.randint(0, n_users, events).astype(np.int64)
    its = rng.randint(0, 500, events).astype(np.int32)
    tss = rng.randint(0, G1, events).astype(np.int64)
    stores = [BatchFeatureStore(FeatureStoreConfig(
        n_users=n_users, feature_len=feature_len)) for _ in range(n)]
    for s in stores:
        s.extend(us, its, tss)
        s.run_snapshot(G1)
    return stores


def _paused_builder(store, chunk=32):
    """Start a background build paused after its first worker chunk.

    Returns ``(builder, release)``: the worker is parked at a
    step-barrier inside the build — the caller appends/asserts with a
    deterministic interleaving, then sets ``release``."""
    entered = threading.Event()
    release = threading.Event()
    calls = {"n": 0}

    def hook():
        calls["n"] += 1
        if calls["n"] == 1:
            entered.set()
            assert release.wait(30), "test never released the builder"

    b = store.begin_snapshot_background(G2, step_hook=hook, chunk=chunk)
    assert entered.wait(30), "builder thread never reached the barrier"
    return b, release


# ----------------------------------------------------------------------
# differential: background build == run_snapshot oracle, bitwise
# ----------------------------------------------------------------------

def test_background_build_equals_oracle_with_midbuild_appends():
    """Deterministic interleaving: the worker is parked mid-build while
    the caller appends new-period events AND late events with old
    in-window timestamps; the installed arrays still equal the oracle's
    idempotent re-run as of install time."""
    full, bg = _seeded_stores()
    rng = np.random.RandomState(3)
    cu = rng.choice(200, 20, replace=False)
    cit = rng.randint(0, 500, 20)
    for s in (full, bg):
        s.extend(cu, cit, np.full(20, G1 + 500))

    b, release = _paused_builder(bg)
    # mid-build traffic: fresh events inside the rolled period, plus a
    # LATE arrival whose ts is old but inside the new window — the
    # previous build can't contain it, the fixup must catch it
    mid_u = np.array([7, 8, 9], np.int64)
    mid_i = np.array([41, 42, 43])
    mid_t = np.array([G2 - 50, G1 + 900, 3 * DAY])
    for s in (full, bg):
        s.extend(mid_u, mid_i, mid_t)
    release.set()
    assert b.join(60) == 0 and b.done

    full.run_snapshot(G2)  # oracle, as of the same log contents
    for a, c in zip(full._snapshots[G2], bg._snapshots[G2]):
        np.testing.assert_array_equal(a, c)
    # the late old-ts event (user 9, ts=3*DAY inside [G2-window, G2))
    # was appended after build start, so the fixup re-filled it
    assert b.late_fixups >= 1


def test_background_build_with_concurrent_append_storm():
    """Free-running (no barrier) build racing a storm of appends from
    the caller thread — the install must still be bitwise equal to the
    oracle run over the exact same final log."""
    full, bg = _seeded_stores(events=4000, n_users=400)
    b = bg.begin_snapshot_background(G2, chunk=16)
    rng = np.random.RandomState(11)
    applied = []
    while not b._built.is_set():
        u = rng.randint(0, 400, 5).astype(np.int64)
        it = rng.randint(0, 500, 5)
        ts = rng.randint(G1, G2, 5)
        bg.extend(u, it, ts)
        applied.append((u, it, ts))
        time.sleep(0)  # yield so the worker makes progress
    assert b.poll() == 0 and b.done
    for u, it, ts in applied:
        full.extend(u, it, ts)
    full.run_snapshot(G2)
    for a, c in zip(full._snapshots[G2], bg._snapshots[G2]):
        np.testing.assert_array_equal(a, c)


def test_background_full_build_on_store_without_previous_generation():
    """No previous frozen generation -> the worker does a full build
    (every user), still equal to the oracle."""
    rng = np.random.RandomState(5)
    mk = lambda: BatchFeatureStore(FeatureStoreConfig(  # noqa: E731
        n_users=64, feature_len=8))
    full, bg = mk(), mk()
    us = rng.randint(0, 64, 300)
    its = rng.randint(0, 100, 300)
    tss = rng.randint(0, G1, 300)
    for s in (full, bg):
        s.extend(us, its, tss)
    b = bg.begin_snapshot_background(G1, chunk=16)
    assert b.full_build
    assert b.join(60) == 0
    full.run_snapshot(G1)
    for a, c in zip(full._snapshots[G1], bg._snapshots[G1]):
        np.testing.assert_array_equal(a, c)


def test_certification_survives_offthread_path():
    """changed_users_between after a background build: certified (not
    None), a superset of the true row diff, and exact on the rows the
    worker pre-diffed — mid-build changed users included."""
    _, bg = _seeded_stores()
    rng = np.random.RandomState(9)
    cu = rng.choice(200, 15, replace=False)
    bg.extend(cu, rng.randint(0, 500, 15), np.full(15, G1 + 700))

    b, release = _paused_builder(bg)
    bg.extend([123], [77], [G2 - 10])  # user changes mid-build
    release.set()
    assert b.join(60) == 0

    certified = bg.changed_users_between(G1, G2)
    assert certified is not None
    pi, pt, pv = bg._snapshots[G1]
    ni, nt, nv = bg._snapshots[G2]
    true_diff = np.where(
        ((ni != pi) | (nt != pt) | (nv != pv)).any(axis=1))[0]
    assert set(true_diff.tolist()) <= set(certified.tolist())
    assert 123 in set(certified.tolist())
    # every user OUTSIDE the certified set is bitwise unchanged — the
    # property the warm rekey rests on
    keep = np.setdiff1d(np.arange(200), certified)
    np.testing.assert_array_equal(ni[keep], pi[keep])


def test_worker_exception_is_sticky():
    """A crash on the builder thread re-raises from poll() — and keeps
    re-raising; the generation must never install."""
    _, bg = _seeded_stores()

    def boom():
        raise RuntimeError("injected fault")

    b = bg.begin_snapshot_background(G2, step_hook=boom)
    b._built.wait(30)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="background build"):
            b.poll()
    assert not b.done and G2 not in bg._snapshots


def test_registered_generation_rejected_like_sync_builder():
    _, bg = _seeded_stores()
    with pytest.raises(ValueError, match="already registered"):
        bg.begin_snapshot_background(G1)


# ----------------------------------------------------------------------
# gateway integration: background_build=True
# ----------------------------------------------------------------------

def _settle(gw, now, timeout=60.0):
    """Tick until the in-flight background build installs."""
    t0 = time.monotonic()
    gw.tick(now)
    while gw._builder is not None:
        assert time.monotonic() - t0 < timeout, "build never installed"
        time.sleep(0.001)
        gw.tick(now)


def test_gateway_background_rollover_bitwise_equal_sync():
    """A gateway with background_build serves bitwise the same slates
    across a rollover as the synchronous-build gateway on the same
    trace, and the rollover stats reconcile on the semantic fields."""
    eng = tiny_engine()
    gws = {"sync": make_gateway(engine=eng),
           "bg": make_gateway(engine=eng, background_build=True)}
    now = 5 * DAY + 100
    users = list(range(8))
    out = {}
    for name, gw in gws.items():
        tk = gw.submit_many([Request(user=u, now=now) for u in users])
        gw.flush(now)
        gw.observe_many([0, 1], [9, 10], [now + 300] * 2)
        if name == "bg":
            _settle(gw, now + DAY)
        else:
            gw.tick(now + DAY)
        assert gw.injector.generation(now + DAY) == 6 * DAY
        tk += gw.submit_many(
            [Request(user=u, now=now + DAY + 5) for u in users])
        gw.flush(now + DAY + 5)
        out[name] = tk
    for a, c in zip(out["sync"], out["bg"]):
        np.testing.assert_array_equal(a.response.slate, c.response.slate)
        np.testing.assert_array_equal(a.response.scores, c.response.scores)
    s1 = gws["sync"].stats()["rollover"]
    s2 = gws["bg"].stats()["rollover"]
    for field in ("rollovers", "rekeyed", "invalidated", "retained"):
        assert s1[field] == s2[field], field
    # the background gateway recorded its install's arrays, so the
    # handoff certified and rekeyed the 6 unchanged users
    assert s2["rekeyed"] == 6 and s2["retained"] == 2


def test_gateway_background_build_off_serving_thread():
    """While the worker builds, clock calls return without advancing
    the build inline: the builder thread is a different thread, and a
    paused worker never blocks tick()."""
    gw = make_gateway(background_build=True)
    now = 5 * DAY + 100
    gw.tick(now)  # catch-up (cold store) runs synchronously, by design
    gw.observe_many([0, 1, 2], [5, 6, 7], [now + 200] * 3)

    entered = threading.Event()
    release = threading.Event()
    orig = gw.injector.batch.begin_snapshot_background

    def paused(ts, **kw):
        def hook():
            if not entered.is_set():
                entered.set()
                release.wait(30)
        return orig(ts, step_hook=hook, chunk=8)

    gw.injector.batch.begin_snapshot_background = paused
    gw.tick(now + DAY)  # starts the worker; does NOT build inline
    assert entered.wait(30)
    assert gw._builder is not None
    worker = gw._builder._thread
    assert worker is not threading.current_thread() and worker.daemon
    # generation has NOT rolled: the build is in flight, serving reads
    # the previous generation (the paper's "static between snapshots")
    assert gw.injector.generation(now + DAY) == 5 * DAY
    for _ in range(3):
        gw.tick(now + DAY)  # O(1) polls while the worker is parked
    assert gw.injector.generation(now + DAY) == 5 * DAY
    release.set()
    _settle(gw, now + DAY)
    assert gw.injector.generation(now + DAY) == 6 * DAY
    st = gw.stats()["rollover"]
    assert st["rollovers"] == 1 and st["build_time_s"] > 0


# ----------------------------------------------------------------------
# rollover-aware eviction order (the handoff window's dual residency)
# ----------------------------------------------------------------------

def _entry(nbytes=64):
    return {"x": np.zeros(nbytes // 8, np.int64)}


def test_host_cache_stale_first_eviction_under_entry_pressure():
    cache = PrefillStateCache(budget=8)
    for u in range(8):
        cache.put(u, 100, _entry())
    cache.rekey_generation(100, 200, changed=[0, 1], retain_changed=True)
    assert len(cache) == 8 and cache.stats()["handoff_stale"] == 2
    # LRU order says user 2's (rekeyed) entry should go next — but the
    # stale dual-generation entries are the designated victims
    cache.put(50, 200, _entry())
    cache.put(51, 200, _entry())
    assert cache.stale_evictions == 2
    assert (0, 100) not in cache and (1, 100) not in cache
    assert (2, 200) in cache  # live LRU survived the handoff window
    # stale set drained: eviction falls back to plain LRU
    cache.put(52, 200, _entry())
    assert cache.stale_evictions == 2 and (2, 200) not in cache


def test_host_cache_stale_first_eviction_under_byte_pressure():
    cache = PrefillStateCache(budget=64, byte_budget=8 * 64)
    for u in range(8):
        cache.put(u, 100, _entry(64))
    cache.rekey_generation(100, 200, changed=[3], retain_changed=True)
    assert cache.stats()["handoff_stale"] == 1
    cache.put(60, 200, _entry(64))  # byte budget exceeded -> evict one
    assert cache.stale_evictions == 1 and (3, 100) not in cache
    assert len(cache) == 8 and cache.bytes_per_shard == 8 * 64


def test_host_cache_stale_cleared_by_next_invalidate():
    cache = PrefillStateCache(budget=8)
    for u in range(4):
        cache.put(u, 100, _entry())
    cache.rekey_generation(100, 200, changed=[0, 1], retain_changed=True)
    cache.invalidate_except(200)  # next handoff sweeps the survivors
    assert len(cache) == 2 and cache.stats()["handoff_stale"] == 0


class _FakePool:
    """Metadata stub: PagedStateCache's table logic only reads these."""
    def __init__(self, n_slots):
        self.n_slots = n_slots
        self.slot_nbytes = 1024
        self.slot_bytes_by_kind = {"ssm": 1024, "kv": 0, "logits": 0,
                                   "mask": 0}
        self.data_shards = 1


def test_paged_cache_stale_first_eviction_pin_aware():
    from repro.serving.pool import PagedStateCache

    cache = PagedStateCache(_FakePool(4))
    slots = {u: cache.admit(u, 100, set()) for u in range(4)}
    cache.rekey_generation(100, 200, changed=[0, 1], retain_changed=True)
    assert cache.stats()["handoff_stale"] == 2
    # slot pressure with stale user 0's slot PINNED by the pane under
    # assembly: the OTHER stale entry must be the victim
    s = cache.admit(7, 200, pinned={slots[0]})
    assert s == slots[1] and cache.stale_evictions == 1
    assert (0, 100) in cache and (1, 100) not in cache
    # unpinned again: the remaining stale entry goes before any live one
    s = cache.admit(8, 200, pinned=set())
    assert s == slots[0] and cache.stale_evictions == 2
    # stale drained: plain pin-aware LRU (user 2 is now the LRU entry)
    s = cache.admit(9, 200, pinned=set())
    assert s == slots[2] and cache.stale_evictions == 2
    assert (3, 200) in cache


def test_gateway_handoff_window_evicts_stale_before_rekeyed():
    """End to end on the host LRU: after a certified handoff with
    retained entries, serving NEW users under budget pressure evicts
    the dual-generation entries first — every rekeyed (live) entry
    survives the storm."""
    gw = make_gateway(cache_entries=10)
    now = 5 * DAY + 100
    users = list(range(8))
    gw.submit_many([Request(user=u, now=now) for u in users])
    gw.flush(now)
    gw.observe_many([0, 1, 2], [11, 12, 13], [now + 500] * 3)
    gw.tick(now + DAY)
    gen_b = gw.injector.generation(now + DAY)
    st = gw.cache.stats()
    assert st["handoff_stale"] == 3 and len(gw.cache) == 8
    # 4 new users -> 12 entries against a budget of 10: 2 evictions,
    # both must come from the retained stale set
    newbies = [20, 21, 22, 23]
    gw.submit_many([Request(user=u, now=now + DAY) for u in newbies])
    gw.flush(now + DAY)
    assert gw.cache.stale_evictions == 2
    assert len(gw.cache) == 10
    for u in (3, 4, 5, 6, 7):          # every rekeyed entry survived
        assert (u, (gen_b, 0)) in gw.cache
    for u in newbies:
        assert (u, (gen_b, 0)) in gw.cache
