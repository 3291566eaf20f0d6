"""What decides ``correct``: served slates against the plain reference.

After the window, a sample of the requests it finished is drawn from the
seed, with the request of the longest prompt in it. Each sampled request's
prompt is rebuilt from what the harness itself generated, by the feature
semantics the configuration serves:

    history  the user's snapshot events (ts < cutoff), oldest first,
             newest ``prefill_len`` kept
    suffix   the user's fresh events that the realtime ring held when the
             request was served: the newest ``rt_buffer`` delivered by
             then, visible at the gateway's clock (ts <= clock), exact
             duplicates dropped, ordered by (ts, item), newest
             ``inject_len`` kept

and tokens are item + 1. The reference runs each prompt with the served
slate's tokens appended. A served token's gap is how far its reference
logit lies below the reference's best among the items not yet in its
slate; the number compared is the widest gap over the sample. A slate
that repeats an item or names no item has an infinite gap.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench.cell import derived_seed


def sample(window, n_rows: int, seed: int, prompt_len) -> List[int]:
    """Indices of finished requests: the longest prompt, then a seeded
    draw of the others."""
    fin = [k for k, t in enumerate(window.tickets)
           if t is not None and t.response is not None]
    if not fin:
        return []
    lens = np.asarray([prompt_len(k) for k in fin])
    first = fin[int(np.argmax(lens))]
    rest = [k for k in fin if k != first]
    rng = np.random.default_rng(derived_seed(seed, "check"))
    pick = rng.choice(len(rest), size=min(n_rows - 1, len(rest)),
                      replace=False) if rest else []
    return [first] + [rest[i] for i in sorted(pick)]


class Prompts:
    """Rebuilds served prompts from the plane, the schedule and the
    window's record of which turn delivered each event."""

    def __init__(self, conf, plane, sched, window, warm_events=None):
        s, p = conf["serving"], conf["plane"]
        self.prefill_len, self.inject_len = s["prefill_len"], s["inject_len"]
        self.rt_buffer = p["rt_buffer"]
        self.plane, self.window = plane, window
        users = np.repeat(sched.users, sched.n_events)
        ts = np.repeat(window.clock, sched.n_events) - sched.ev_lag
        items, turns = sched.ev_items, window.ev_turn
        if warm_events is not None:        # delivered before turn 0
            wu, wi, wt = warm_events
            users = np.concatenate([wu, users])
            items = np.concatenate([wi, items])
            ts = np.concatenate([wt, ts])
            turns = np.concatenate([np.full(len(wu), -1), turns])
        self._by_user: Dict[int, np.ndarray] = {}
        order = np.argsort(users, kind="stable")   # delivery order kept
        for u, grp in _groups(users[order], order):
            self._by_user[u] = np.stack([items[grp], ts[grp], turns[grp]], 1)
        self.users = sched.users

    def history(self, u: int) -> List[int]:
        return (self.plane.items[u][-self.prefill_len:] + 1).tolist()

    def suffix(self, k: int) -> List[int]:
        u = int(self.users[k])
        evs = self._by_user.get(u)
        if evs is None:
            return []
        held = evs[evs[:, 2] <= self.window.turn[k]][-self.rt_buffer:]
        c = self.window.serve_clock[k]
        vis = held[(held[:, 1] <= c) & (held[:, 1] >= self.plane.cutoff)]
        pairs = sorted({(int(t), int(i)) for i, t, _ in vis})
        return [i + 1 for _, i in pairs][-self.inject_len:]

    def prompt(self, k: int) -> List[int]:
        return self.history(int(self.users[k])) + self.suffix(k)


def _groups(sorted_keys, order):
    if len(sorted_keys) == 0:
        return
    cut = np.flatnonzero(np.r_[True, sorted_keys[1:] != sorted_keys[:-1],
                               True])
    for a, b in zip(cut[:-1], cut[1:]):
        yield int(sorted_keys[a]), order[a:b]


def streams(prompts: List[List[int]], slates: np.ndarray, length: int
            ) -> Tuple[np.ndarray, np.ndarray]:
    """Right-padded (k, length) token streams prompt ++ slate[:-1], and
    the (k, T) positions whose next-token logits pick each slate item."""
    k, t = slates.shape
    toks = np.zeros((k, length), np.int32)
    pos = np.zeros((k, t), np.int32)
    for r, (pr, sl) in enumerate(zip(prompts, slates)):
        s = list(pr) + [int(x) for x in sl[:-1]]
        toks[r, :len(s)] = s
        pos[r] = len(pr) - 1 + np.arange(t)
    return toks, pos


def _allowed(slates: np.ndarray, vocab: int) -> np.ndarray:
    """(k, T, vocab) mask of the items each pick may choose from."""
    k, t = slates.shape
    mask = np.ones((k, t, vocab), bool)
    for j in range(1, t):
        prev = slates[:, :j]
        ok = (prev >= 0) & (prev < vocab)
        rows, cols = np.nonzero(ok)
        mask[rows, j, prev[rows, cols]] = False
    return mask


def served_gaps(ref: np.ndarray, slates: np.ndarray) -> np.ndarray:
    """(k, T) gap of each served token below the reference's best."""
    vocab = ref.shape[-1]
    allowed = _allowed(slates, vocab)
    best = np.where(allowed, ref, -np.inf).max(-1)
    s = np.clip(slates, 0, vocab - 1)
    got = np.take_along_axis(ref, s[..., None], -1)[..., 0]
    ok = (slates >= 0) & (slates < vocab) \
        & np.take_along_axis(allowed, s[..., None], -1)[..., 0]
    return np.where(ok, best - got, np.inf)
