"""The open-loop window: Poisson arrivals driven through
``Gateway.submit_many`` -> ``Gateway.poll`` on the harness's own clock.

Each turn of the loop observes the fresh events of every arrival now due,
submits those arrivals together, then polls. The gateway's continuous
batching (``max_wait=0``) serves what it is given inside ``submit_many``,
forming its own panes. The harness sleeps only when nothing is due, and
that sleep is the only time the gateway holds no work.

Times come from ``time.perf_counter``. A request's latency runs from when
it was due to the poll that hands back its slate. The window closes at
the first poll at or after ``seconds``, or at ``seconds`` when the
harness is idle then; ``slates_per_s`` counts the slates claimed by the
close over the window's length. What is still due then is served after
the close (the drain): it counts for latency, not for the rate.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

STALL_S = 0.5    # a turn of the loop this long has its Python stack taken


@dataclasses.dataclass
class Window:
    t0: float                  # perf_counter when the window opened
    close: float               # perf_counter when it closed
    due: np.ndarray            # (N,) perf_counter each arrival was due
    submitted: np.ndarray      # (N,) perf_counter of its submit_many
    done: np.ndarray           # (N,) perf_counter of the poll with its slate
    clock: np.ndarray          # (N,) request clock (seconds) of the arrival
    serve_clock: np.ndarray    # (N,) gateway clock when it was submitted
    turn: np.ndarray           # (N,) loop turn that submitted it
    ev_turn: np.ndarray        # (E,) loop turn that observed each event
    tickets: List[Any]
    turns: int
    at_close: Any = None
    longest: Dict[str, Tuple[float, float]] = dataclasses.field(
        default_factory=dict)  # call -> (seconds, seconds into the window)
    stalls: List[Tuple[str, float, str]] = dataclasses.field(
        default_factory=list)  # (call, seconds into the window, stack)
    watch_late: Tuple[float, float] = (0.0, 0.0)  # (seconds, at)

    @property
    def seconds(self) -> float:
        return self.close - self.t0

    @property
    def completed(self) -> int:
        return int(np.sum(self.done <= self.close))

    def latency_ms(self) -> np.ndarray:
        return (self.done - self.due) * 1e3

    def lateness_ms(self) -> np.ndarray:
        return (self.submitted - self.due) * 1e3


class Watch:
    """The loop's calls, each in a profiler span (``bench.<call>``): the
    longest of each kind, and the Python stack of the loop wherever it is
    once a turn has run STALL_S (a thread looks every 50 ms), so that a
    stall in the window names where it happened."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.longest: Dict[str, Tuple[float, float]] = {}
        self.stalls: List[Tuple[str, float, str]] = []
        self.late = (0.0, 0.0)              # longest oversleep, when
        self._turn = [self.t0, False]       # start, stack taken
        self._name = "harness"
        self._main = threading.get_ident()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._look, daemon=True)
        self._thread.start()

    def turn(self) -> None:
        self._turn = [time.perf_counter(), False]

    @contextlib.contextmanager
    def call(self, name: str):
        import jax
        start = time.perf_counter()
        self._name = name
        try:
            with jax.profiler.TraceAnnotation("bench." + name):
                yield
        finally:
            self._name = "harness"
            d = time.perf_counter() - start
            if d > self.longest.get(name, (0.0, 0.0))[0]:
                self.longest[name] = (d, start - self.t0)

    def _look(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(0.05):
            now = time.perf_counter()
            late, last = now - last - 0.05, now
            if late > self.late[0]:
                # a host that stalls the whole process wakes this thread
                # late too; a wait on the device does not
                self.late = (late, now - self.t0)
            turn, name = self._turn, self._name
            if turn[1] or name == "wait" or len(self.stalls) >= 5 \
                    or time.perf_counter() - turn[0] < STALL_S:
                continue
            turn[1] = True
            frame = sys._current_frames().get(self._main)
            stack = "".join(traceback.format_stack(frame)[-10:]) \
                if frame is not None else ""
            self.stalls.append((name, turn[0] - self.t0, stack))

    def close(self) -> None:
        self._stop.set()
        self._thread.join()


def run_window(gw, sched, base_clock: int, seconds: float,
               on_turn: Optional[Callable[[float], None]] = None,
               on_close: Optional[Callable[[], Any]] = None) -> Window:
    """Drive ``sched`` (traffic.Schedule) through ``gw`` for ``seconds``,
    then serve what is left. ``on_turn(now)`` runs between turns (the
    traced run starts and stops the profiler there); ``on_close()`` runs
    once, as the window closes, and what it returns is kept as
    ``Window.at_close``."""
    from repro.serving.api import Request

    n = len(sched.due_s)
    ev0 = sched.ev_start
    clock = base_clock + np.floor(sched.due_s).astype(np.int64)
    ev_user = np.repeat(sched.users, sched.n_events)
    ev_ts = np.repeat(clock, sched.n_events) - sched.ev_lag
    submitted = np.zeros(n)
    done = np.full(n, np.inf)
    serve_clock = np.zeros(n, np.int64)
    turn_of = np.full(n, -1, np.int64)
    ev_turn = np.full(len(ev_user), -1, np.int64)
    tickets: List[Any] = [None] * n
    index = {}

    watch = Watch()
    t0 = watch.t0
    due = t0 + sched.due_s
    t_end = t0 + seconds
    close = at_close = None
    i = n_done = turn = 0
    max_clock = base_clock
    while n_done < n:
        watch.turn()
        now = time.perf_counter()
        j = int(np.searchsorted(due, now, side="right"))
        if j > i:
            a, b = ev0[i], ev0[j]
            with watch.call("observe"):
                gw.observe_many(ev_user[a:b], sched.ev_items[a:b], ev_ts[a:b])
            ev_turn[a:b] = turn
            reqs = [Request(user=int(sched.users[k]), now=int(clock[k]))
                    for k in range(i, j)]
            max_clock = max(max_clock, int(clock[j - 1]))
            submitted[i:j] = time.perf_counter()
            serve_clock[i:j] = max_clock
            turn_of[i:j] = turn
            with watch.call("submit_many"):
                ts = gw.submit_many(reqs)
            for k, t in zip(range(i, j), ts):
                tickets[k] = t
                index[id(t)] = k
            i = j
            turn += 1
        with watch.call("poll"):
            got = gw.poll()
        stamp = time.perf_counter()
        for t in got:
            done[index.pop(id(t))] = stamp
        n_done += len(got)
        if close is None and stamp >= t_end:
            close = stamp
            at_close = on_close() if on_close is not None else None
        if on_turn is not None:
            on_turn(stamp)
        if n_done == n:
            break
        if index and (i == n or due[i] > stamp):
            # served rows are claimed by the poll above; anything still
            # outstanding with nothing new due would wait forever
            with watch.call("flush"):
                gw.flush()
            continue
        if i < n and due[i] > stamp:
            wake = due[i] if close is not None else min(due[i], t_end)
            with watch.call("wait"):
                time.sleep(max(0.0, wake - time.perf_counter()))
    if close is None:
        with watch.call("wait"):
            time.sleep(max(0.0, t_end - time.perf_counter()))
        close = max(t_end, time.perf_counter())
        at_close = on_close() if on_close is not None else None
    watch.close()
    return Window(t0=t0, close=close, due=due, submitted=submitted,
                  done=done, clock=clock, serve_clock=serve_clock,
                  turn=turn_of, ev_turn=ev_turn, tickets=tickets,
                  turns=turn, at_close=at_close, longest=watch.longest,
                  stalls=watch.stalls, watch_late=watch.late)


@contextlib.contextmanager
def counting_compiles():
    """Counts, while the block runs, backend compilations and programs
    read from the persistent cache: ``[compiles, cache hits]``."""
    import jax
    box = [0, 0]
    active = [True]

    def on_duration(event, duration, **_):
        if active[0] and event == "/jax/core/compile/backend_compile_duration":
            box[0] += 1

    def on_event(event, **_):
        if active[0] and event == "/jax/compilation_cache/cache_hits":
            box[1] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield box
    finally:
        active[0] = False


@contextlib.contextmanager
def gc_pauses():
    """Python's garbage collections while the block runs, by generation:
    ``{"count": [g0, g1, g2], "total_ms": ..., "max_ms": ...}``."""
    import gc
    box = {"count": [0, 0, 0], "total_ms": 0.0, "max_ms": 0.0}
    start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            start[0] = time.perf_counter()
            return
        ms = (time.perf_counter() - start[0]) * 1e3
        box["count"][info["generation"]] += 1
        box["total_ms"] += ms
        box["max_ms"] = max(box["max_ms"], ms)

    gc.callbacks.append(on_gc)
    try:
        yield box
    finally:
        gc.callbacks.remove(on_gc)

