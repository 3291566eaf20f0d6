"""Reduction of a profiler trace to the benchmark's device numbers.

A trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``) holds a
plane per device (``/device:TPU:<n>``) and one for the host
(``/host:CPU``). On a device plane the line named ``XLA Modules`` holds
one event per run of a compiled program (``jit__prefill_impl(<id>)``) and
the line ``XLA Ops`` one per operation; the host plane's thread lines hold
the runtime's launches (``PJRT_LoadedExecutable_Execute``) and the
harness's own spans: ``bench.slice``, the loop's ``bench.observe``,
``bench.submit_many``, ``bench.poll``, ``bench.flush`` and
``bench.wait``, and, nested in them, the spans the traced run puts
around the program's layers (``bench.engine.inject``,
``bench.pool.scatter``, ...). All times are on one clock, in
nanoseconds.

Programs are named by what launched them: a launch lies inside jaxlib's
span of the jitted call that made it (``PjitFunction(_inject_impl)``, on
the Python thread's line, while the TPU runtime records the launch on a
line of its own), which names the program it runs (``jit__inject_impl``);
the n-th run of a program on the device is the n-th launch of that
program on the host (one stream, in order), and a run is labelled
``<module>@<span>`` with the innermost layer span around its launch
(``jit__inject_impl@engine.inject``). A launch the trace does not name is
matched in order among the unnamed. A small program can start on the
device as soon as it is launched, and the two clocks can disagree by
more than the slack: such a run misses its launch, and matched in order
over all programs every run after it would take the launch before its
own.

Within the traced slice (the ``bench.slice`` span):

    busy          union of the device's operation intervals
    work in hand  the slice less the harness's waits for the next arrival
                  (the gateway holds no request only then)
    modules       device time and run count per program label
    idle gaps     slice less busy, each nanosecond charged to the
                  innermost harness span it fell in (``other`` where none)
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict, deque
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

LAUNCH = "PJRT_LoadedExecutable_Execute"
CALL = "PjitFunction("     # jaxlib's span of a jitted call: "PjitFunction(f)"
LAYER_SPANS = ("bench.engine.", "bench.pool.")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
CLOCK_SLACK_NS = 2e6   # device and host clocks agree to about a millisecond


# ----------------------------------------------------------------------
# Interval arithmetic
# ----------------------------------------------------------------------

def union(iv: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(iv: Iterable[Interval]) -> float:
    return sum(b - a for a, b in iv)


def clip(iv: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def intersect(x: Sequence[Interval], y: Sequence[Interval]) -> List[Interval]:
    """Intersection of two unions (each sorted and disjoint)."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if a < b:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(x: Sequence[Interval], y: Sequence[Interval]) -> List[Interval]:
    """x less y (each sorted and disjoint)."""
    out = []
    j = 0
    for a, b in x:
        cur = a
        while j < len(y) and y[j][1] <= cur:
            j += 1
        k = j
        while k < len(y) and y[k][0] < b:
            if y[k][0] > cur:
                out.append((cur, y[k][0]))
            cur = max(cur, y[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def module_name(event_name: str) -> str:
    """``jit__inject_impl(1234)`` -> ``jit__inject_impl``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


# ----------------------------------------------------------------------
# Reduction
# ----------------------------------------------------------------------

def nest(spans: Sequence[Tuple[str, float, float]]):
    """Spans of one thread, each with its depth (0 outermost), sorted by
    start; spans on one thread nest properly."""
    out, stack = [], []
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1] <= a:
            stack.pop()
        out.append((name, a, b, len(stack)))
        stack.append(b)
    return out


def innermost(spans, t: float, prefixes: Sequence[str]):
    """Name of the deepest span with one of ``prefixes`` holding ``t``."""
    best, depth = None, -1
    for name, a, b, d in spans:
        if a > t:
            break
        if b > t and d > depth and name.startswith(prefixes):
            best, depth = name, d
    return best


def call_at(calls: Sequence[Tuple[float, float, str]], t: float):
    """Program of the innermost jitted call (start, end, program) holding
    ``t``; ``calls`` sorted by start."""
    for i in range(bisect.bisect_right(calls, (t, float("inf"), "")) - 1,
                   -1, -1):
        if calls[i][1] > t:
            return calls[i][2]
    return None


def label_modules(modules, launches, spans):
    """(label, start, end) per module run: each run matched to the earliest
    unmatched launch of its program (of the unnamed launches, where the
    trace names none of its program; a launch named after no module run
    counts as unnamed) no later than its start (plus clock slack), and
    labelled with the layer span around that launch. A run
    that misses its own launch (launched before the trace began, or
    stamped before it by more than the slack) moves the matches of its
    own program only, never another program's."""
    programs = {module_name(m[0]) for m in modules}
    queues: Dict[Optional[str], deque] = defaultdict(deque)
    for t, program in sorted(launches, key=lambda launch: launch[0]):
        queues[program if program in programs else None].append(t)
    out = []
    for name, a, b in sorted(modules, key=lambda m: m[1]):
        label = module_name(name)
        queue = queues[label if label in queues else None]
        if queue and queue[0] <= a + CLOCK_SLACK_NS:
            span = innermost(spans, queue.popleft(), LAYER_SPANS)
            if span is not None:
                label += "@" + span[len("bench."):]
        out.append((label, a, b))
    return out


def charge_idle(idle: Sequence[Interval], spans) -> Dict[str, float]:
    """Seconds of ``idle`` per innermost span (name less ``bench.``)."""
    charged: Dict[str, float] = defaultdict(float)
    remaining = list(idle)
    for name, a, b, _ in sorted(spans, key=lambda s: -s[3]):
        if name == "bench.slice" or not remaining:
            continue
        part = intersect(remaining, [(a, b)])
        if part:
            charged[name[len("bench."):]] += length(part) * 1e-9
            remaining = subtract(remaining, part)
    charged["other"] = length(remaining) * 1e-9
    return dict(charged)


def reduce(slice_iv: Interval, host: Sequence[Tuple[str, float, float]],
           ops: Sequence[Interval],
           modules: Sequence[Tuple[str, float, float]],
           launches: Sequence[Tuple[float, Optional[str]]] = ()) -> Dict:
    """Device numbers of one traced slice. ``host``: (name, start, end) of
    the harness's spans (one thread); ``ops`` and ``modules`` of one
    device; ``launches``: (host start, program or None) of each program
    launch. Seconds out, nanoseconds in."""
    lo, hi = slice_iv
    modules = label_modules(modules, launches, nest(host))
    spans = nest([s for s in host if s[1] < hi and s[2] > lo])
    waits = union(clip([(a, b) for n, a, b in host if n == "bench.wait"],
                       lo, hi))
    hand = subtract([(lo, hi)], waits)
    busy = union(clip(ops, lo, hi))
    mod_t: Dict[str, float] = defaultdict(float)
    mod_n: Dict[str, int] = defaultdict(int)
    for label, a, b in modules:
        if lo <= a < hi:
            mod_t[label] += (b - a) * 1e-9
            mod_n[label] += 1
    idle = subtract([(lo, hi)], busy)
    charged = charge_idle(idle, [s for s in spans if s[0] != "bench.slice"])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": length(busy) * 1e-9,
        "hand_s": length(hand) * 1e-9,
        "busy_hand_s": length(intersect(busy, hand)) * 1e-9,
        "module_s": dict(mod_t),
        "module_n": dict(mod_n),
        "idle_by_host_s": dict(charged),
        "breakdown": {
            "device_ops": sorted(([k, v] for k, v in mod_t.items()),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": sorted(([k, v] for k, v in charged.items() if v > 0),
                                key=lambda kv: -kv[1])[:10],
        },
    }


def load(path: str):
    """(harness spans, launches, {device plane: (ops, modules)}) from one
    xplane file; a launch is (host start, program), the program named by
    the jitted call around it in time (``jit_<function>``, as XLA names
    the module), or None."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    host, starts, calls, devices = [], [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                elif line.name == MODULE_LINE:
                    modules += [(e.name, e.start_ns, e.start_ns
                                 + e.duration_ns) for e in line.events]
            if ops or modules:
                devices[plane.name] = (ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
                    elif e.name == LAUNCH:
                        starts.append(e.start_ns)
                    elif e.name.startswith(CALL) and e.name.endswith(")"):
                        calls.append((e.start_ns, e.start_ns + e.duration_ns,
                                      "jit_" + e.name[len(CALL):-1]))
    calls.sort()
    return host, [(t, call_at(calls, t)) for t in starts], devices


def reduce_dir(trace_dir: str) -> Dict:
    """Reduce the one trace under ``trace_dir``: the first device (a
    one-chip cell holds one), within the ``bench.slice`` span."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(files)}")
    host, launches, devices = load(files[0])
    sl = [(a, b) for n, a, b in host if n == "bench.slice"]
    if len(sl) != 1:
        raise RuntimeError(f"expected one bench.slice span, found {len(sl)}")
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    name = sorted(devices)[0]
    ops, modules = devices[name]
    out = reduce(sl[0], host, ops, modules, launches)
    out["device_plane"] = name
    return out
