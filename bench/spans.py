"""The program's own spans in a traced slice: the device's idle time
charged to the layer the serving thread was in.

The program opens a span ``repro.<layer>.<step>`` at each layer boundary
of its serving thread (``src/repro/serving/tracing.py``), on the
profiler's host plane and its clock. This module reads them from the same
``.xplane.pb`` as trace.py, one host thread line at a time, and keeps the
serving thread: the line that holds the ``repro.gateway.pane`` spans.
Spans of any other thread are never charged. Within the ``bench.slice``
span:

    panes           pane spans that start in the slice
    spans           per span name: how many start in the slice, and the
                    seconds they cover (total) and cover less their
                    children (self), clipped to the slice
    idle_s          device idle while work was in hand (the slice less
                    the union of the device's operations, less the
                    harness's ``bench.wait``: the time base of
                    ``device_idle_share``), each nanosecond charged to
                    the innermost program span around it
    unattributed_s  that idle under no program span

A program without these spans gives 0 panes and empty maps.

GROUPS sums that idle, per pane, by layer. The benchmark's traced run
(trace.py) does not read the program spans yet; one run of a cell with
them added, printed on standard error:

    python3 -m bench.spans --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace
from bench.trace import (Interval, clip, intersect, length, nest, subtract,
                         union)

PREFIX = "repro."
PANE = "repro.gateway.pane"
WAIT = "bench.wait"
# device idle per pane charged to the program spans of one layer; every
# span name falls in exactly one group
GROUPS = {
    "feature_idle_ms": r"^repro\.feature\.",
    "dispatch_idle_ms":
        r"^repro\.(engine\.(prefill|inject|finalize|slate)|pool\.\w+)$",
    "readback_idle_ms": r"^repro\.(engine|gateway)\.readback$",
    "gateway_idle_ms": r"^repro\.gateway\.(?!readback$)",
}

Span = Tuple[str, float, float]


def self_intervals(spans: Sequence[Span]) -> List[Tuple[str, List[Interval]]]:
    """(name, self intervals) of each span of one thread: its interval
    less its children's (spans of one thread nest properly)."""
    nested = nest(spans)
    kids: List[List[Interval]] = [[] for _ in nested]
    stack: List[int] = []
    for i, (_, a, _, _) in enumerate(nested):
        while stack and nested[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            kids[stack[-1]].append(nested[i][1:3])
        stack.append(i)
    return [(name, subtract([(a, b)], union(kids[i])))
            for i, (name, a, b, _) in enumerate(nested)]


def reduce(slice_iv: Interval, spans: Sequence[Span],
           ops: Sequence[Interval], waits: Sequence[Interval] = ()) -> Dict:
    """The serving thread's ``spans`` over one slice; ``ops`` are the
    device's operation intervals and ``waits`` the harness's waits for
    the next arrival. Seconds out, nanoseconds in."""
    lo, hi = slice_iv
    spans = [s for s in spans if s[1] < hi and s[2] > lo]
    busy = union(clip(ops, lo, hi))
    hand = subtract([(lo, hi)], union(clip(waits, lo, hi)))
    idle = subtract(hand, busy)
    stats: Dict[str, Dict[str, float]] = {}
    for name, a, b in spans:
        st = stats.setdefault(name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
        st["n"] += int(lo <= a < hi)
        st["total_s"] += (min(b, hi) - max(a, lo)) * 1e-9
    own: Dict[str, List[Interval]] = defaultdict(list)
    for name, iv in self_intervals(spans):
        own[name] += clip(iv, lo, hi)
    idle_s = {}
    for name, iv in own.items():
        iv = union(iv)
        stats[name]["self_s"] = length(iv) * 1e-9
        idle_s[name] = length(intersect(idle, iv)) * 1e-9
    idle_total = length(idle) * 1e-9
    return {"panes": int(stats.get(PANE, {}).get("n", 0)),
            "spans": stats,
            "idle_s": idle_s,
            "idle_in_hand_s": idle_total,
            "unattributed_s": idle_total - sum(idle_s.values())}


def serving_thread(lines: Sequence[Sequence[Span]]) -> List[Span]:
    """The line holding the most pane spans ([] where none holds one)."""
    best = max(lines, key=lambda ln: sum(s[0] == PANE for s in ln),
               default=[])
    return list(best) if any(s[0] == PANE for s in best) else []


def load(path: str) -> List[List[Span]]:
    """The ``repro.`` spans of each host thread line of one xplane file."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith(PREFIX)]
            if evs:
                out.append(evs)
    return out


def from_file(path: str, slice_iv: Interval, host: Sequence[Span],
              ops: Sequence[Interval]) -> Dict:
    """``reduce`` of the serving thread's spans in ``path``; ``host`` is
    trace.py's list of the harness's spans (its waits are taken out).
    Writes the idle charged to each span, per pane, to stderr."""
    waits = [(a, b) for n, a, b in host if n == WAIT]
    out = reduce(slice_iv, serving_thread(load(path)), ops, waits)
    n = out["panes"]
    if n:
        per = {k: 1e3 * v / n for k, v in out["idle_s"].items() if v > 0}
        print(f"[bench] program spans: {n} panes; device idle in hand "
              f"{1e3 * out['idle_in_hand_s'] / n:.4f} ms a pane, charged "
              + ", ".join(f"{k} {v:.4f}" for k, v in
                          sorted(per.items(), key=lambda kv: -kv[1]))
              + f"; to no program span {1e3 * out['unattributed_s'] / n:.4f}"
              " ms a pane", file=sys.stderr, flush=True)
    return out


def idle_ms_per_pane(prog: Dict, pattern: str) -> Optional[float]:
    """Device idle ms per pane charged to the program spans whose name
    matches ``pattern`` (a regular expression), or None where the trace
    holds no pane span."""
    if not prog.get("panes"):
        return None
    rx = re.compile(pattern)
    return 1e3 * sum(v for k, v in prog["idle_s"].items()
                     if rx.search(k)) / prog["panes"]


def program(trace_dir: str, device_plane: str) -> Dict:
    """``from_file`` of the one trace under ``trace_dir``, over the
    ``bench.slice`` span and the operations of ``device_plane``, as
    trace.reduce_dir finds them."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    host, _, devices = trace.load(path)
    sl, = [(a, b) for n, a, b in host if n == "bench.slice"]
    return from_file(path, sl, host, devices[device_plane][0])


def main(argv=None) -> int:
    """bench/run.py's traced run, with the program spans read from its
    trace before the trace is removed."""
    from bench import run

    plain = trace.reduce_dir

    def reduce_dir(trace_dir):
        out = plain(trace_dir)
        prog = program(trace_dir, out["device_plane"])
        print("[bench] program spans by layer, ms a pane: "
              + ", ".join(f"{k} {idle_ms_per_pane(prog, g)}"
                          for k, g in GROUPS.items()),
              file=sys.stderr, flush=True)
        return out

    trace.reduce_dir = reduce_dir
    return run.main(list(sys.argv[1:] if argv is None else argv)
                    + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
