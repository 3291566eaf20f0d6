"""The one traffic generator: a mix's parameters + a rate + a seed ->
the open-loop schedule of a run.

Every seed gets the same amount of work in another order: the count of
arrivals is fixed at ``rate x seconds`` (a Poisson process conditioned on
its count: due times uniform over the window), and the fresh events per
arrival are a fixed multiset over ``events_per_arrival`` shuffled by the
seed. Users, items and event lags are drawn from the seed.

Mix parameters (``bench/traffic/<name>.json``):

    users               "hot_set": uniform over a hot set of exactly
                        ``pool_slots`` users, all warmed at set-up;
                        "all": uniform over every user of the plane, with
                        the pool warmed by a seeded random set
    events_per_arrival  [lo, hi] fresh watch events before each arrival
    event_lag_s         [lo, hi] seconds between an event and its arrival
    fresh_before_window events each warm user has watched since the
                        snapshot before the window opens, delivered at
                        set-up, at distinct times in the snapshot's first
                        ``PRE_WINDOW_S`` seconds (optional, default 0)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import numpy as np

from bench.cell import derived_seed

PRE_WINDOW_S = 60      # pre-window events lie in [cutoff, cutoff + this)


@dataclasses.dataclass
class Schedule:
    due_s: np.ndarray          # (N,) seconds after the window opens, sorted
    users: np.ndarray          # (N,)
    n_events: np.ndarray       # (N,) fresh events before each arrival
    ev_items: np.ndarray       # (sum n_events,) item ids, arrival order
    ev_lag: np.ndarray         # (sum n_events,) seconds before the arrival
    warm_users: np.ndarray     # users whose states set-up puts in the pool
    pre_users: np.ndarray      # (P,) events delivered at set-up, in order
    pre_items: np.ndarray      # (P,)
    pre_ts: np.ndarray         # (P,) absolute, from the snapshot's cutoff

    @property
    def ev_start(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.n_events)])


def make_schedule(mix: Dict[str, Any], conf: Dict[str, Any], n_catalog: int,
                  rate: float, seconds: float, seed: int,
                  cutoff: int) -> Schedule:
    rng = np.random.default_rng(derived_seed(seed, "traffic"))
    n_users = conf["plane"]["n_users"]
    slots = conf["serving"]["pool_slots"]
    n = max(1, int(round(rate * seconds)))
    due = np.sort(rng.uniform(0.0, seconds, n))
    warm = rng.choice(n_users, size=slots, replace=False).astype(np.int64)
    if mix["users"] == "hot_set":
        users = warm[rng.integers(0, slots, n)]
    elif mix["users"] == "all":
        users = rng.integers(0, n_users, n, dtype=np.int64)
    else:
        raise ValueError(f"unknown users {mix['users']!r}")
    lo, hi = mix["events_per_arrival"]
    n_ev = rng.permutation(np.resize(np.arange(lo, hi + 1), n))
    m = int(n_ev.sum())
    l_lo, l_hi = mix["event_lag_s"]
    k = int(mix.get("fresh_before_window", 0))
    pre_ts = cutoff + np.sort(np.stack([
        rng.choice(PRE_WINDOW_S, size=k, replace=False) for _ in warm]), 1)
    return Schedule(
        due_s=due, users=users, n_events=n_ev.astype(np.int64),
        ev_items=rng.integers(0, n_catalog, m, dtype=np.int64),
        ev_lag=rng.integers(l_lo, l_hi + 1, m, dtype=np.int64),
        warm_users=warm, pre_users=np.repeat(warm, k),
        pre_items=rng.integers(0, n_catalog, slots * k, dtype=np.int64),
        pre_ts=pre_ts.reshape(-1).astype(np.int64))
