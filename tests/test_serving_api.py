"""Request-level serving API: typed Request/Response lifecycle, the
micro-batching Gateway, mixed-policy panes, deadlines, telemetry, and
the legacy wave wrapper's bitwise-compatibility contract.

The load-bearing claims, matching the redesign's acceptance criteria:

  * the Gateway serves **bitwise-identical** slates/scores to the
    legacy wave API on the same request trace — whether the trace
    arrives as waves (submit_many+flush) or trickles in request by
    request (per-request submit, pane-full flushes) — on a single
    device AND through the 1×1-mesh sharded code path;
  * a **mixed-policy pane** (batch/inject/fresh rows coexisting)
    serves every row the same result as a single-policy server of that
    row's policy — arms are request labels, not deployments;
  * a **deadline** flushes a partial pane on the clock; nothing is
    served before it fires, everything queued is served when it does;
  * construction-time validation fails fast with clear messages
    instead of shape errors inside jit.
"""
import dataclasses

import numpy as np
import pytest

from conftest import DAY, N_ITEMS, N_USERS
from conftest import ingest as _ingest
from conftest import make_gateway, seeded_injector, tiny_engine
from repro.core.ab import ARM_POLICIES, arm_requests, request_arm
from repro.serving.api import (Event, Request, as_event, assign_arms,
                               hash_arm)
from repro.serving.loop import InjectionServer, ServeResult
from repro.serving.scheduler import Gateway, ServerConfig

_ENGINE = tiny_engine()  # the conftest session-shared tiny platform
_CFG = _ENGINE.cfg


def _mesh_engine():
    return tiny_engine(mesh1x1=True)  # the 1×1-mesh sharded code path


def _injector(policy="inject"):
    return seeded_injector(policy)


def _gateway(policy="inject", engine=None, **cfg_kw):
    return make_gateway(policy, engine=engine or _ENGINE, **cfg_kw)


# ----------------------------------------------------------------------
# Construction-time validation
# ----------------------------------------------------------------------

def test_request_validation():
    with pytest.raises(ValueError, match="unknown policy"):
        Request(user=1, now=0, policy="bogus")
    with pytest.raises(ValueError, match="slate_len"):
        Request(user=1, now=0, slate_len=0)
    with pytest.raises(ValueError, match="deadline"):
        Request(user=1, now=100, deadline=99)
    with pytest.raises(ValueError, match="user"):
        Request(user=-1, now=0)
    # frozen: a request cannot be mutated after validation
    r = Request(user=1, now=0)
    with pytest.raises(dataclasses.FrozenInstanceError):
        r.user = 2
    # deadline == now is legal (serve at the next clock advance)
    assert Request(user=1, now=5, deadline=5).deadline == 5


def test_server_config_validation():
    with pytest.raises(ValueError, match="slate_len"):
        ServerConfig(slate_len=0)
    with pytest.raises(ValueError, match="cache_entries"):
        ServerConfig(cache_entries=0)
    with pytest.raises(ValueError, match="cache_bytes"):
        ServerConfig(cache_bytes=0)


def test_gateway_construction_validation():
    # slate_len beyond the item vocabulary fails at construction, not as
    # a shape error inside the decode jit
    with pytest.raises(ValueError, match="vocab"):
        _gateway(slate_len=_CFG.vocab_size + 1)
    # an unknown policy string on the injector fails at the facade
    inj = _injector()
    object.__setattr__(inj.cfg, "policy", "bogus")
    with pytest.raises(ValueError, match="unknown default policy"):
        Gateway(_ENGINE, inj, ServerConfig())


def test_submit_rejects_oversized_slate_len():
    gw = _gateway()
    with pytest.raises(ValueError, match="vocab"):
        gw.submit(Request(user=1, now=0, slate_len=_CFG.vocab_size + 1))
    assert gw.pending == 0  # the bad request never entered the queue


def test_submit_rejects_out_of_range_user():
    """An unknown user fails at the call site with a clear message —
    inside pane execution it would be a numpy IndexError that takes the
    whole pane (including innocent co-batched requests) down."""
    gw = _gateway()
    with pytest.raises(ValueError, match="out of range"):
        gw.submit(Request(user=N_USERS, now=0))
    assert gw.pending == 0


@pytest.mark.parametrize("half", ["_launch", "_retire"])
def test_drain_dequeues_each_pane_as_it_serves(monkeypatch, half):
    """If a later pane raises mid-drain, already-served tickets must be
    out of the queue: a retried flush may re-try the failed pane but
    must never re-execute responses the caller already holds. Pane 2
    fails in its launch (pane 1, then in flight, retires and dequeues
    before the exception leaves) or in its retire."""
    gw = _gateway()
    now = 5 * DAY + 100
    real = getattr(type(gw), half)
    calls = {"n": 0}

    def flaky(self, *a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("injected pane failure")
        return real(self, *a, **k)

    monkeypatch.setattr(type(gw), half, flaky)
    reqs = [Request(user=u, now=now) for u in range(8)]  # 2 panes at b=4
    with pytest.raises(RuntimeError, match="injected"):
        gw.submit_many(reqs)
    # pane 1 served and dequeued; pane 2 failed and stayed queued
    assert gw.pending == 4 and gw.requests == 4
    monkeypatch.setattr(type(gw), half, real)
    first_pane_ids = [t.response.telemetry.pane_id
                      for t in gw.flush(now) if t.response]
    # recovery serves ONLY the failed pane; earlier responses untouched
    assert gw.requests == 8 and gw.pending == 0
    assert len(first_pane_ids) == 4


def test_a_failed_retire_keeps_its_pane_and_the_one_in_flight_queued(
        monkeypatch):
    """Pane 1's retire raises after pane 2 was launched: neither pane's
    responses reached the caller, so both stay queued, and the retried
    flush serves them bitwise as a gateway that never failed."""
    gw, clean = _gateway(), _gateway()
    now = 5 * DAY + 100
    reqs = [Request(user=u, now=now) for u in range(8)]  # 2 panes at b=4

    def broken(self, p):
        raise RuntimeError("injected retire failure")

    monkeypatch.setattr(type(gw), "_retire", broken)
    with pytest.raises(RuntimeError, match="injected"):
        gw.submit_many(reqs)
    assert gw.pending == 8 and gw.requests == 0 and not gw.poll()
    monkeypatch.undo()
    got = gw.flush(now)
    want = clean.submit_many(reqs)
    assert gw.pending == 0 and gw.requests == 8
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.response.slate, b.response.slate)
        assert a.response.scores.tobytes() == b.response.scores.tobytes()


@pytest.mark.parametrize("n_panes", [1, 3])
def test_a_drain_keeps_one_pane_in_flight(n_panes):
    """Pane k+1 is launched before pane k retires, every pane retires
    before the call returns, and ``panes_overlapped`` counts the panes
    launched with an earlier one unread: n - 1 of a drain of n."""
    gw = _gateway()
    order = []
    launch, retire = gw._launch, gw._retire

    def logged_launch(pane, gen, overlapped):
        p = launch(pane, gen, overlapped)
        order.append(("launch", p.pane_id))
        return p

    def logged_retire(p):
        retire(p)
        order.append(("retire", p.pane_id))

    gw._launch, gw._retire = logged_launch, logged_retire
    b = _ENGINE.scfg.max_batch
    tickets = gw.submit_many([Request(user=u, now=5 * DAY + 100)
                              for u in range(n_panes * b)])
    assert all(t.done for t in tickets) and gw.pending == 0
    want = [("launch", 0)]
    for k in range(n_panes):
        if k + 1 < n_panes:
            want.append(("launch", k + 1))
        want.append(("retire", k))
    assert order == want
    st = gw.stats()
    assert (st.panes, st.panes_overlapped) == (n_panes, n_panes - 1)


def test_submit_many_validates_whole_batch_before_enqueuing():
    """A bad request mid-batch must not strand earlier rows in the
    queue with their ticket handles lost to the exception."""
    gw = _gateway()
    reqs = [Request(user=1, now=0),
            Request(user=2, now=0, slate_len=_CFG.vocab_size + 1)]
    with pytest.raises(ValueError, match="vocab"):
        gw.submit_many(reqs)
    assert gw.pending == 0  # nothing enqueued, nothing orphaned


def test_as_event_coercions():
    assert as_event((1, 2, 3)) == Event(1, 2, 3)
    assert as_event(Event(1, 2, 3)) == Event(1, 2, 3)

    class Rec:
        user, item, ts = 4, 5, 6
    assert as_event(Rec()) == Event(4, 5, 6)
    with pytest.raises(TypeError, match="event"):
        as_event("nope")


# ----------------------------------------------------------------------
# Wave wrapper vs Gateway: bitwise equivalence on the same trace
# ----------------------------------------------------------------------

def _run_trace_wave(srv: InjectionServer):
    """The legacy path: pre-grouped waves through serve(users, now)."""
    rng = np.random.RandomState(3)
    now = 5 * DAY + 100
    scores, slates = [], []
    for wave in range(3):
        u = rng.randint(0, N_USERS, 10)
        _ingest(srv.gateway, u, (u + 3) % N_ITEMS, np.full(10, now - 30))
        q = rng.randint(0, N_USERS, 11)  # 2 full panes + a padded one
        with pytest.warns(DeprecationWarning):
            r = srv.serve(q, now)
        scores.append(r.scores)
        slates.append(r.slate)
        now += 300
    return np.concatenate(scores), np.concatenate(slates)


def _run_trace_trickle(gw: Gateway):
    """The same trace as per-request arrivals: submit() one at a time
    (full panes flush eagerly, in arrival order), flush() at wave end."""
    rng = np.random.RandomState(3)
    now = 5 * DAY + 100
    scores, slates = [], []
    for wave in range(3):
        u = rng.randint(0, N_USERS, 10)
        _ingest(gw, u, (u + 3) % N_ITEMS, np.full(10, now - 30))
        q = rng.randint(0, N_USERS, 11)
        tickets = [gw.submit(Request(user=int(x), now=now)) for x in q]
        gw.flush(now)
        scores.append(np.stack([t.response.scores for t in tickets]))
        slates.append(np.stack([t.response.slate for t in tickets]))
        now += 300
    return np.concatenate(scores), np.concatenate(slates)


@pytest.mark.slow
@pytest.mark.parametrize("mesh", [False, True], ids=["plain", "mesh1x1"])
def test_wave_vs_gateway_bitwise(mesh):
    """The redesign's core contract: the Gateway serves bitwise-identical
    results to the legacy wave API on the same request trace — including
    when arrivals trickle in (different pane composition: rows are
    independent, so micro-batching may regroup them freely)."""
    eng = _mesh_engine() if mesh else _ENGINE
    sw, lw = _run_trace_wave(InjectionServer(eng, _injector(),
                                             ServerConfig(slate_len=3,
                                                          cache_entries=64)))
    sg, lg = _run_trace_trickle(_gateway(engine=eng))
    np.testing.assert_array_equal(lw, lg)   # slates: bitwise
    np.testing.assert_array_equal(sw, sg)   # scores: bitwise


def test_wave_wrapper_matches_submit_many_flush():
    """serve(users, now) is literally submit_many + flush on default
    requests — same tickets, same order, same counters."""
    a, b = _gateway(), _gateway()
    srv = InjectionServer.__new__(InjectionServer)
    srv.gateway = a
    users = np.random.RandomState(5).randint(0, N_USERS, 9)
    now = 5 * DAY + 100
    with pytest.warns(DeprecationWarning):
        r = srv.serve(users, now)
    assert isinstance(r, ServeResult)
    tickets = b.submit_many(
        [Request(user=int(u), now=now) for u in users])
    b.flush(now)
    np.testing.assert_array_equal(
        r.scores, np.stack([t.response.scores for t in tickets]))
    np.testing.assert_array_equal(
        r.slate, np.stack([t.response.slate for t in tickets]))
    assert a.panes == b.panes and a.prefill_calls == b.prefill_calls


def test_legacy_serve_honors_non_monotonic_now():
    """The pre-Gateway loop served each wave AT the call's ``now`` even
    when an earlier call used a later time (replay/backfill tools rely
    on it); the shim must rewind the gateway's otherwise-monotonic
    clock rather than silently serving at max(now, previous now)."""
    t0, t1 = 5 * DAY + 100, 6 * DAY + 100  # a generation apart
    users = np.arange(6)
    time_traveler = InjectionServer(_ENGINE, _injector(),
                                    ServerConfig(slate_len=3,
                                                 cache_entries=64))
    oracle = InjectionServer(_ENGINE, _injector(),
                             ServerConfig(slate_len=3, cache_entries=64))
    with pytest.warns(DeprecationWarning):
        time_traveler.serve(users, t1)        # clock moves to t1
        r_back = time_traveler.serve(users, t0)   # ...then rewinds
        r_ref = oracle.serve(users, t0)           # fresh server at t0
    np.testing.assert_array_equal(r_back.scores, r_ref.scores)
    np.testing.assert_array_equal(r_back.slate, r_ref.slate)


def test_legacy_serve_emits_deprecation_warning():
    srv = InjectionServer(_ENGINE, _injector(),
                          ServerConfig(slate_len=3, cache_entries=16))
    with pytest.warns(DeprecationWarning, match="Gateway"):
        srv.serve(np.arange(4), 5 * DAY + 100)


# ----------------------------------------------------------------------
# Mixed-policy panes
# ----------------------------------------------------------------------

def test_mixed_policy_pane_matches_single_policy_servers():
    """Rows with different per-request policies coexist in one pane and
    each row matches a single-policy server of its policy, row for row —
    the A/B split as request labels instead of deployments."""
    now = 5 * DAY + 100
    users = np.arange(8)
    policies = ["batch", "inject", "fresh", "inject",
                "batch", "fresh", "inject", "batch"]
    fresh_items = (users + 7) % N_ITEMS

    gw = _gateway()  # default policy "inject"; per-request overrides
    _ingest(gw, users, fresh_items, np.full(8, now - 20))
    tickets = gw.submit_many(
        [Request(user=int(u), now=now, policy=p)
         for u, p in zip(users, policies)])
    gw.flush(now)
    # the pane really was mixed (not silently re-partitioned by policy)
    pane_pols = {}
    for t in tickets:
        pane_pols.setdefault(t.response.telemetry.pane_id, set()).add(
            t.response.telemetry.policy)
    assert any(len(ps) > 1 for ps in pane_pols.values())

    for pol in ("batch", "inject", "fresh"):
        ref = _gateway(pol)
        _ingest(ref, users, fresh_items, np.full(8, now - 20))
        rt = ref.submit_many([Request(user=int(u), now=now) for u in users])
        ref.flush(now)
        for i, p in enumerate(policies):
            if p != pol:
                continue
            np.testing.assert_allclose(
                tickets[i].response.scores, rt[i].response.scores,
                atol=2e-3, rtol=2e-3)
            np.testing.assert_array_equal(
                tickets[i].response.slate, rt[i].response.slate)


def test_mixed_pane_policies_actually_differ():
    """The mixed-pane test above would be vacuous if the three policies
    served identical scores — show they move for at least one row."""
    now = 5 * DAY + 100
    users = np.arange(6)
    gw = _gateway()
    _ingest(gw, users, (users + 7) % N_ITEMS, np.full(6, now - 20))
    outs = {}
    for pol in ("batch", "inject"):
        t = gw.submit_many([Request(user=int(u), now=now, policy=pol)
                            for u in users])
        gw.flush(now)
        outs[pol] = np.stack([x.response.scores for x in t])
    assert np.abs(outs["batch"] - outs["inject"]).max() > 1e-3


def test_fresh_rows_in_mixed_pane_never_cached():
    """Ephemeral admissions: a fresh-policy row rides the pane's
    admission prefill but must not enter the (user, generation) cache —
    its history depends on the request cutoff."""
    gw = _gateway()
    now = 5 * DAY + 100
    gw.submit_many([Request(user=0, now=now, policy="fresh"),
                    Request(user=1, now=now, policy="inject")])
    gw.flush(now)
    gen = gw.injector.generation(now)
    assert (1, (gen, 0)) in gw.cache and (0, (gen, 0)) not in gw.cache


# ----------------------------------------------------------------------
# Scheduling: pane-full, deadlines, duplicates
# ----------------------------------------------------------------------

def test_pane_full_flush_on_submit():
    gw = _gateway()
    now = 5 * DAY + 100
    tk = [gw.submit(Request(user=u, now=now + u)) for u in range(3)]
    assert gw.pending == 3 and not any(t.done for t in tk)
    t4 = gw.submit(Request(user=3, now=now + 3))  # fills the max_batch=4 pane
    assert gw.pending == 0 and t4.done and all(t.done for t in tk)
    # queue delay telemetry: served at the newest arrival's clock
    assert tk[0].response.telemetry.queue_delay == 3
    assert t4.response.telemetry.queue_delay == 0


def test_deadline_triggers_partial_pane_flush():
    """A short pane flushes when the clock reaches a queued deadline —
    latency beats utilization once a deadline fires."""
    gw = _gateway()
    now = 5 * DAY + 100
    t1 = gw.submit(Request(user=1, now=now, deadline=now + 30))
    t2 = gw.submit(Request(user=2, now=now + 5))
    assert gw.pending == 2 and not t1.done
    served = gw.tick(now + 29)           # deadline not reached
    assert served == [] and gw.pending == 2
    served = gw.tick(now + 30)           # deadline fires -> partial pane
    assert {t.request_id for t in served} == {t1.request_id, t2.request_id}
    assert t1.done and t2.done and gw.pending == 0
    assert t1.response.telemetry.queue_delay == 30
    assert gw.stats()["deadline_flushes"] == 1
    # slate is real: the padded pane still decodes distinct items
    assert len(set(t1.response.slate.tolist())) == 3


def test_submit_at_deadline_flushes_immediately():
    """An arrival whose clock reaches a pending deadline triggers the
    flush itself — no tick needed."""
    gw = _gateway()
    now = 5 * DAY + 100
    t1 = gw.submit(Request(user=1, now=now, deadline=now + 10))
    t2 = gw.submit(Request(user=2, now=now + 10))  # clock hits t1's deadline
    assert t1.done and t2.done and gw.pending == 0


def test_deadline_equal_to_now_at_submit_serves_immediately():
    """The boundary of ``_deadline_due`` (deadline <= clock): a request
    arriving already AT its deadline must flush inside the submit call
    itself, served at ``now`` with zero delay — not wait for a tick, and
    not count as a miss (it was served exactly on time)."""
    gw = _gateway()
    now = 5 * DAY + 100
    t = gw.submit(Request(user=1, now=now, deadline=now))
    assert t.done
    tel = t.response.telemetry
    assert tel.served_at == now and tel.queue_delay == 0
    assert gw.stats()["deadline_flushes"] == 1
    assert gw.stats()["deadline_misses"] == 0


def test_multiple_deadlines_fire_on_one_tick():
    """One coarse tick jumping past several queued deadlines: a single
    deadline flush serves them all, and each request served past its
    own deadline is counted as a miss — late service must never be
    silent."""
    gw = _gateway()
    now = 5 * DAY + 100
    t1 = gw.submit(Request(user=1, now=now, deadline=now + 5))
    t2 = gw.submit(Request(user=2, now=now, deadline=now + 5))
    t3 = gw.submit(Request(user=3, now=now + 1, deadline=now + 7))
    served = gw.tick(now + 10)
    assert {x.request_id for x in served} == \
        {t1.request_id, t2.request_id, t3.request_id}
    assert gw.stats()["deadline_flushes"] == 1  # one flush, not three
    assert gw.stats()["deadline_misses"] == 3   # all served late
    assert all(x.response.telemetry.served_at == now + 10 for x in served)


def test_deadline_fires_during_rewarm_window():
    """A deadline flush landing inside a rollover's re-warm window: the
    tick that fires the deadline must still serve the partial pane (on
    the new generation) AND keep spending the re-warm budget — the two
    duties of ``tick`` cannot starve each other."""
    gw = _gateway(rewarm_budget=1)
    now = 5 * DAY + 100
    users = np.arange(8)
    gw.warm(users, now)
    # events inside the next generation's window: all eight users change
    # across the 6*DAY boundary, so the rollover invalidates their
    # cached states and queues them for budgeted re-warm
    _ingest(gw, users, (users + 3) % N_ITEMS, np.full(8, now + 50))
    now2 = 6 * DAY + 10
    gw.tick(now2)
    st = gw.stats()
    assert st["rollover"].rollovers == 1
    pending0 = st["rollover"].pending_rewarm
    assert pending0 > 0
    t = gw.submit(Request(user=3, now=now2 + 1, deadline=now2 + 3))
    assert not t.done
    served = gw.tick(now2 + 3)          # deadline fires mid re-warm
    assert [x.request_id for x in served] == [t.request_id]
    assert t.response.telemetry.generation == 6 * DAY
    assert gw.stats()["deadline_misses"] == 0
    # the re-warm queue kept draining across the deadline tick
    assert gw.stats()["rollover"].pending_rewarm < pending0


def test_duplicate_users_one_wave_single_admission():
    """A wave repeating one cold user counts per-row misses but pays one
    admission prefill (same contract as the legacy wave path)."""
    gw = _gateway()
    now = 5 * DAY + 100
    tk = gw.submit_many([Request(user=5, now=now)] * 3)
    gw.flush(now)
    assert all(t.done for t in tk)
    assert gw.cache.misses == 3 and gw.cache.hits == 0
    assert gw.prefill_calls == 1
    # all three rows got identical results (same user, same state)
    np.testing.assert_array_equal(tk[0].response.slate, tk[1].response.slate)
    np.testing.assert_array_equal(tk[0].response.scores, tk[2].response.scores)
    tk2 = gw.submit_many([Request(user=5, now=now + 10)] * 2)
    gw.flush(now + 10)
    assert gw.cache.hits == 2 and all(
        t.response.telemetry.cache_hit for t in tk2)


def test_cache_aware_ordering_over_the_queue():
    """When more than a pane's worth is queued, hits group into pure-hit
    panes ahead of misses (the wave path's 3x win, preserved)."""
    gw = _gateway()
    now = 5 * DAY + 100
    gw.warm(np.arange(4), now)           # users 0..3 cached
    reqs = [Request(user=u, now=now) for u in (0, 30, 1, 31, 2, 32, 3, 33)]
    tk = gw.submit_many(reqs)            # 2 full panes, interleaved hit/miss
    assert all(t.done for t in tk)
    hit_panes = {t.response.telemetry.pane_id for t in tk
                 if t.response.telemetry.cache_hit}
    miss_panes = {t.response.telemetry.pane_id for t in tk
                  if not t.response.telemetry.cache_hit}
    assert hit_panes and miss_panes and not (hit_panes & miss_panes)


# ----------------------------------------------------------------------
# Per-request slate lengths
# ----------------------------------------------------------------------

def test_per_request_slate_len_masked_decode():
    """Rows with different slate_lens share one pane: each row gets
    exactly its length, items distinct, and the greedy prefix matches
    what a uniform decode of the pane max would have chosen."""
    gw = _gateway(slate_len=4)
    now = 5 * DAY + 100
    lens = [1, 2, 4, 3]
    tk = gw.submit_many([Request(user=u, now=now, slate_len=sl)
                         for u, sl in zip(range(4), lens)])
    gw.flush(now)
    uniform = _gateway(slate_len=4)
    tu = uniform.submit_many([Request(user=u, now=now) for u in range(4)])
    uniform.flush(now)
    for t, tu_i, sl in zip(tk, tu, lens):
        slate = t.response.slate
        assert slate.shape == (sl,)
        assert len(set(slate.tolist())) == sl
        assert t.response.telemetry.slate_len == sl
        np.testing.assert_array_equal(slate, tu_i.response.slate[:sl])


def test_engine_masked_decode_slate_matches_unmasked():
    """decode_slate(row_lens=) == plain decode_slate with tails masked
    to -1 — the masked program changes layout, never the chosen items."""
    eng = _ENGINE
    rng = np.random.RandomState(0)
    hists = [list(rng.randint(1, _CFG.vocab_size, 20)) for _ in range(4)]
    toks, valid = eng.pad_tokens(hists, 32)
    state = eng.prefill(toks, valid)
    first = state["logits"][:, -1]
    full = eng.decode_slate(state, first, 4)
    lens = np.array([1, 4, 2, 3], np.int32)
    masked = eng.decode_slate(state, first, 4, row_lens=lens)
    for r in range(4):
        np.testing.assert_array_equal(masked[r, :lens[r]], full[r, :lens[r]])
        assert (masked[r, lens[r]:] == -1).all()


# ----------------------------------------------------------------------
# Telemetry + facade
# ----------------------------------------------------------------------

def test_telemetry_paths_and_generation():
    gw = _gateway()
    now = 5 * DAY + 100
    users = np.arange(4)
    t1 = gw.submit_many([Request(user=int(u), now=now) for u in users])
    gw.flush(now)
    assert all(t.response.telemetry.path == "prefill" for t in t1)
    gen = gw.injector.generation(now)
    assert all(t.response.telemetry.generation == gen for t in t1)
    # no fresh events since the probe -> pure cache reads
    t2 = gw.submit_many([Request(user=int(u), now=now + 5) for u in users])
    gw.flush(now + 5)
    assert all(t.response.telemetry.path == "cached" for t in t2)
    assert all(t.response.telemetry.cache_hit for t in t2)
    # fresh events arrive -> the hits take the inject path
    _ingest(gw, users, (users + 9) % N_ITEMS, np.full(4, now + 6))
    t3 = gw.submit_many([Request(user=int(u), now=now + 10) for u in users])
    gw.flush(now + 10)
    assert all(t.response.telemetry.path == "inject" for t in t3)
    st = gw.stats()
    assert st["paths"] == {"prefill": 4, "cached": 4, "inject": 4,
                           "decay": 0}
    assert st["queue_delay"]["window"] == 12


def test_tick_rolls_generation_with_warm_handoff():
    """gateway.tick is the clock: a day boundary rolls the snapshot. By
    default the rollover is a warm handoff — users whose snapshot rows
    are unchanged keep their cached states under the new generation
    (rekeyed, not purged); with warm_handoff=False the legacy
    purge-everything rollover applies."""
    gw = _gateway()
    now = 5 * DAY + 100
    gw.submit_many([Request(user=u, now=now) for u in range(4)])
    gw.flush(now)
    gen_a = gw.injector.generation(now)
    assert len(gw.cache) == 4
    gw.tick(now + DAY)  # no events between generations: nothing changed
    gen_b = gw.injector.generation(now + DAY)
    assert gen_b != gen_a
    assert len(gw.cache) == 4 and gw.cache.rekeys == 4
    assert gw.cache.invalidations == 0
    assert all(g == (gen_b, 0) for (_, g) in gw.cache._entries)
    st = gw.stats()["rollover"]
    assert st["rollovers"] == 1 and st["rekeyed"] == 4

    # legacy contract, still available: purge-everything rollover
    gw = _gateway(warm_handoff=False)
    gw.submit_many([Request(user=u, now=now) for u in range(4)])
    gw.flush(now)
    gw.tick(now + DAY)
    assert len(gw.cache) == 0 and gw.cache.invalidations == 4
    assert gw.cache.rekeys == 0


def test_observe_feeds_both_stores():
    gw = _gateway()
    now = 5 * DAY + 100
    n_log = len(gw.injector.batch._log)
    gw.observe(Event(user=3, item=17, ts=now))
    assert len(gw.injector.batch._log) == n_log + 1
    sfx = gw.injector.fresh_suffix(np.array([3]), now + 1)
    assert (17, now) in sfx[0]


def test_warm_through_gateway():
    gw = _gateway(cache_entries=6)
    n = gw.warm(np.arange(20), 5 * DAY + 100)
    assert n == 6 and len(gw.cache) == 6 and gw.cache.evictions == 0


# ----------------------------------------------------------------------
# Per-request A/B assignment
# ----------------------------------------------------------------------

def test_hash_arm_deterministic_and_salted():
    a = [hash_arm(u) for u in range(200)]
    assert a == [hash_arm(u) for u in range(200)]      # stable
    assert set(a) == {"control", "treatment"}          # both arms used
    b = [hash_arm(u, salt=1) for u in range(200)]
    assert a != b                                      # re-randomizable
    assert assign_arms(np.arange(5)) == tuple(hash_arm(u) for u in range(5))
    with pytest.raises(ValueError):
        hash_arm(1, arms=())


def test_arm_requests_label_the_wave():
    reqs = arm_requests(np.arange(10), now=123, salt=0)
    for u, r in enumerate(reqs):
        assert r.tag == request_arm(u) and r.policy == ARM_POLICIES[r.tag]
        assert r.user == u and r.now == 123
    # both arms really occur and serve together in mixed panes
    gw = _gateway()
    tk = gw.submit_many(arm_requests(np.arange(8), now=5 * DAY + 100))
    assert {t.response.telemetry.tag for t in tk} == {"control", "treatment"}
