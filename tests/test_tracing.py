"""Program spans (``repro.serving.tracing``) in a real profiler trace.

A pooled Gateway serves two panes of the same four users under
``jax.profiler.trace``: the first with no fresh event since the snapshot
(prefilled, admitted to the pool, no inject), the second after one fresh
event each (gathered from the pool, injected). The trace's host plane is
read back with ``jax.profiler.ProfileData``: the spans nest layer by
layer on the serving thread, each pane has one span carrying its id, and
the engine's programs run under their functions' names. Serving with the
profiler on returns bitwise what it returns with it off. A third trace
holds one drain of three panes, to show the launch and retire halves of
a drain that keeps one pane in flight.
"""
import glob
import os

import jax
import numpy as np
import pytest

from conftest import DAY, make_gateway, tiny_engine
from repro.serving.api import Request

USERS = [1, 2, 3, 4]
NOW = 5 * DAY + 100


def _serve(gw):
    first = gw.submit_many([Request(user=u, now=NOW) for u in USERS])
    gw.observe_many(np.asarray(USERS), np.asarray([5, 6, 7, 8]),
                    np.full(len(USERS), NOW - 30))
    second = gw.submit_many([Request(user=u, now=NOW) for u in USERS])
    assert all(t.done for t in first + second)
    return first, second


def _spans(pd):
    """(name, start, end, stats, parent name) of every ``repro.`` event
    on the host line that holds the pane spans."""
    lines = [list(line.events) for plane in pd.planes
             if plane.name.startswith("/host:") for line in plane.lines]
    line = max(lines, key=lambda evs: sum(
        e.name == "repro.gateway.pane" for e in evs))
    evs = sorted((e for e in line if e.name.startswith("repro.")),
                 key=lambda e: (e.start_ns, -e.duration_ns))
    out, stack = [], []
    for e in evs:
        a, b = e.start_ns, e.start_ns + e.duration_ns
        while stack and stack[-1][2] <= a:
            stack.pop()
        out.append((e.name, a, b, dict(e.stats),
                    stack[-1][0] if stack else None))
        stack.append((e.name, a, b))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    gw = make_gateway(engine=tiny_engine(), pool_slots=8)
    (first, second), pd, spans = _trace(
        str(tmp_path_factory.mktemp("trace")), lambda: _serve(gw))
    return first, second, pd, spans


def _trace(d, serve):
    with jax.profiler.trace(d):
        out = serve()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return out, pd, _spans(pd)


@pytest.fixture(scope="module")
def traced_drain(tmp_path_factory):
    """One ``submit_many`` of three full panes: a single drain."""
    gw = make_gateway(engine=tiny_engine(), pool_slots=16)
    b = gw.engine.scfg.max_batch
    tickets, _, spans = _trace(
        str(tmp_path_factory.mktemp("drain")),
        lambda: gw.submit_many([Request(user=u, now=NOW)
                                for u in range(3 * b)]))
    assert all(t.done for t in tickets)
    return spans


def _panes(spans):
    return [s for s in spans if s[0] == "repro.gateway.pane"]


def _retires(spans):
    return [s for s in spans if s[0] == "repro.gateway.retire"]


def _within(spans, pane, name):
    return [s for s in spans if s[0] == name
            and pane[1] <= s[1] and s[2] <= pane[2]]


def test_one_pane_span_per_pane_carrying_its_id(traced):
    first, second, _, spans = traced
    panes = _panes(spans)
    ids = [{t.response.telemetry.pane_id for t in ts}
           for ts in (first, second)]
    assert ids == [{0}, {1}]
    assert [(p[3]["pane"], p[3]["rows"]) for p in panes] == [(0, 4), (1, 4)]


@pytest.mark.parametrize("name,parent", [
    ("repro.gateway.pane", "repro.gateway.submit"),
    ("repro.feature.suffixes", "repro.gateway.pane"),
    ("repro.feature.histories", "repro.gateway.pane"),
    ("repro.feature.tokens", "repro.gateway.pane"),
    ("repro.pool.scatter", "repro.gateway.pane"),
    ("repro.pool.gather", "repro.gateway.pane"),
    ("repro.engine.prefill", "repro.gateway.pane"),
    ("repro.engine.inject", "repro.gateway.pane"),
    ("repro.engine.finalize", "repro.gateway.pane"),
    ("repro.engine.slate", "repro.gateway.pane"),
    ("repro.engine.readback", "repro.gateway.retire"),
    ("repro.gateway.readback", "repro.gateway.retire"),
    ("repro.gateway.respond", "repro.gateway.retire"),
    ("repro.gateway.retire", "repro.gateway.submit"),
    ("repro.gateway.submit", None),
    ("repro.feature.observe", None),
])
def test_spans_nest_by_layer(traced, name, parent):
    spans = traced[3]
    mine = [s for s in spans if s[0] == name]
    assert mine, name
    assert {s[4] for s in mine} == {parent}


def test_every_span_is_named_in_the_program_namespace(traced):
    pd = traced[2]
    names = {e.name for plane in pd.planes for line in plane.lines
             for e in line.events if e.name.startswith(("repro.", "bench."))}
    assert names and all(n.startswith("repro.") for n in names)


def test_inject_only_in_the_pane_with_a_suffix(traced):
    spans = traced[3]
    first, second = _panes(spans)
    assert not _within(spans, first, "repro.engine.inject")
    assert len(_within(spans, second, "repro.engine.inject")) == 1
    # the first pane admits its users; the second finds them in the pool
    assert len(_within(spans, first, "repro.engine.prefill")) == 1
    assert not _within(spans, second, "repro.engine.prefill")


@pytest.mark.parametrize("name", ["repro.engine.readback",
                                  "repro.gateway.readback"])
def test_each_pane_reads_back_once(traced, name):
    spans = traced[3]
    retires = _retires(spans)
    assert [r[3]["pane"] for r in retires] == [
        p[3]["pane"] for p in _panes(spans)]
    for retire in retires:
        assert len(_within(spans, retire, name)) == 1


def test_a_drain_launches_each_pane_before_the_last_one_retires(
        traced_drain):
    """n panes in one drain open n pane spans and n retire spans; every
    pane but the first is launched while the one before it is unread
    (``overlapped``), and pane k retires after pane k+1's launch."""
    panes, retires = _panes(traced_drain), _retires(traced_drain)
    assert [p[3]["pane"] for p in panes] == [0, 1, 2]
    assert [r[3]["pane"] for r in retires] == [0, 1, 2]
    assert [bool(p[3]["overlapped"]) for p in panes] == [False, True, True]
    for k in range(2):
        assert panes[k + 1][2] <= retires[k][1]
    for r in retires:
        assert r[4] == "repro.gateway.submit"
        assert _within(traced_drain, r, "repro.engine.readback")


def test_engine_programs_run_under_their_names(traced):
    """The compiled programs' operations name their module after the
    function the engine jits, not ``jit__unknown``."""
    pd = traced[2]
    modules = {v for plane in pd.planes for line in plane.lines
               for e in line.events for k, v in e.stats
               if k == "hlo_module"}
    assert {"jit__prefill_impl", "jit__inject_impl", "jit__finalize_impl",
            "jit__slate_impl"} <= modules
    assert not any("unknown" in m for m in modules)


def test_the_profiler_changes_no_result(traced):
    first, second, _, _ = traced
    plain = _serve(make_gateway(engine=tiny_engine(), pool_slots=8))
    for on, off in zip(first + second, plain[0] + plain[1]):
        np.testing.assert_array_equal(on.response.slate, off.response.slate)
        assert on.response.scores.tobytes() == off.response.scores.tobytes()
