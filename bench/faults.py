"""Faults planted in the served path underneath a run, each of which the
check must find (``correct`` false): the CPU tests plant them at a tiny
size, ``calibrate.py --fault <name>`` on the chip at the cell's own.

Each takes the gateway after set-up and breaks one thing the timed path
does, by replacing a method on the instance."""
import numpy as np


def lost_injection(gw):
    """The fresh suffix never reaches the model: cached state served."""
    gw._suffixes = lambda reqs, pols, now: [[] for _ in reqs]


def altered_token(gw):
    """The slate's first item is changed where the engine produces it."""
    eng = gw.engine
    orig = eng.decode_slate

    def bad(*a, **k):
        s = orig(*a, **k).copy()
        s[:, 0] = (s[:, 0] + 1) % eng.cfg.vocab_size
        return s
    eng.decode_slate = bad


def half_left_out(gw):
    """Half of the panes are left out: each answered with the slates the
    pane before it was given."""
    eng = gw.engine
    orig = eng.decode_slate
    last = []

    def bad(*a, **k):
        s = orig(*a, **k)
        out = last[-1] if last and len(last) % 2 else s
        last.append(s)
        return out
    eng.decode_slate = bad


def stale_state(gw):
    """The inject returns its input state unchanged: the pick comes from
    the cached scores, as if no fresh event had been injected."""
    eng = gw.engine
    orig = eng.inject

    def bad(state, toks, valid, fallback_logits=None):
        return orig(state, toks, np.zeros_like(valid), fallback_logits)
    eng.inject = bad


ALL = {f.__name__: f for f in (lost_injection, altered_token, half_left_out,
                               stale_state)}
