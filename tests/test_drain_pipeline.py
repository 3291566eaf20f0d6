"""The drain keeps one pane in flight: pane k+1 is launched before pane k
is read back. Served that way, a drain of several panes gives slates,
scores, telemetry and counters bitwise equal to the same panes served
one drain each, where nothing overlaps.

The cases reach what pane k+1's launch may touch while pane k is still
on the device: slot-pressure evictions of the slots pane k was gathered
from, the host LRU's admissions and evictions, every policy in one pane
(ephemeral "fresh" admissions, model-free "decay" rows, cached rows
without a suffix), per-row slate lengths, and the 1x1-mesh engine.
"""
import dataclasses

import numpy as np
import pytest

from conftest import DAY, make_gateway, tiny_engine
from repro.serving.api import Request

NOW = 5 * DAY + 100
# 14 rows: three full panes and a short one at max_batch 4; user 3 and
# user 11 come twice
USERS = [3, 11, 0, 25, 1, 3, 30, 2, 17, 9, 33, 5, 11, 21]
WARM = [0, 1, 2, 3, 17, 33]
FRESH = [0, 2, 3, 9, 17, 21, 25, 30]   # users with events since the cut

CASES = {
    # 4 slots: every miss pane evicts slots an earlier pane was read from
    "pool_evicting": dict(cfg=dict(pool_slots=4)),
    "lru_evicting": dict(cfg=dict(cache_entries=5)),
    "mixed_policies": dict(
        cfg=dict(pool_slots=8),
        policies=["inject", "fresh", "decay", "batch"]),
    "slate_lens": dict(cfg=dict(pool_slots=8), lens=[1, 3, 2, 5]),
    "mesh": dict(cfg=dict(pool_slots=4), mesh=True),
}


def _requests(case):
    pols, lens = case.get("policies"), case.get("lens")
    return [Request(user=u, now=NOW,
                    policy=pols[i % len(pols)] if pols else None,
                    slate_len=lens[i % len(lens)] if lens else None)
            for i, u in enumerate(USERS)]


def _gateway(case):
    gw = make_gateway(engine=tiny_engine(mesh1x1=case.get("mesh", False)),
                      max_wait=0, **case["cfg"])
    gw.warm(WARM, NOW)
    gw.observe_many(np.asarray(FRESH), np.arange(len(FRESH)) + 40,
                    np.full(len(FRESH), NOW - 30))
    return gw


@pytest.mark.parametrize("name", list(CASES))
def test_a_pipelined_drain_serves_what_one_drain_per_pane_serves(name):
    case = CASES[name]
    gw = _gateway(case)
    panes = []
    launch = gw._launch

    def recorded(pane, gen, overlapped):
        panes.append(list(pane))
        return launch(pane, gen, overlapped)

    gw._launch = recorded
    gw.submit_many(_requests(case))  # max_wait=0: one drain serves all
    assert len(panes) == 4 and gw.pending == 0

    ref = _gateway(case)
    want = [t for pane in panes
            for t in ref.submit_many([t.request for t in pane])]
    got = [t for pane in panes for t in pane]
    assert len(got) == len(want) == len(USERS)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.response.slate, b.response.slate)
        assert a.response.scores.tobytes() == b.response.scores.tobytes()
        assert a.response.telemetry == dataclasses.replace(
            b.response.telemetry, request_id=a.request_id)
    paths = {t.response.telemetry.path for t in got}
    assert "prefill" in paths and ({"inject", "cached"} & paths)
    st, st_ref = gw.stats(), ref.stats()
    assert (st.panes_overlapped, st_ref.panes_overlapped) == (3, 0)
    assert st == dataclasses.replace(st_ref, panes_overlapped=3)
