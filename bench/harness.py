"""One run of one cell, phase by phase (``run.py`` is its command line).

    1. device     JAX must report the chips the cell asks for, on a TPU
    2. set-up     weights, feature plane, gateway, the pool warmed, every
                  shape of the window compiled and run twice
    3. window     the open-loop window (window.py), profiled mid-window
                  when ``trace`` is set
    4. drain      what is still due is served, untimed for the rate
    5. check      the served slates against the plain reference (check.py)
    6. result     the result line (its last key, check, holds each number
                  compared beside its limit)

End-to-end metrics (``trace=False``) come from the harness's clock; the
per-layer metrics (``trace=True``) from the readers in ``bench/metrics``
over the profiler trace and the gateway's counters.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, Optional

import numpy as np

from bench import cell as cell_mod
from bench import check, metrics_io, traffic, window

CLOCK_OFFSET = 100        # the window's clock starts this long after cutoff
TRACE_AT = 0.6            # the traced slice starts this far into the window
TRACE_LEN = 0.3           # and lasts this share of it (at most TRACE_MAX_S)
TRACE_MAX_S = 3.0
# the program's layers, by the attribute that reaches them from the
# gateway, and the calls the traced run wraps in a span of its own
LAYER_CALLS = {"engine": ("prefill", "inject", "finalize", "decode_slate"),
               "pool": ("gather", "scatter"),
               "gateway": ("_histories", "_suffixes", "_assemble_pool",
                           "_execute")}


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``<root>/.jax_cache`` (a fixed
    path: it is part of the cache's key), every program kept however
    small or quick to compile."""
    import jax
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoDevice(RuntimeError):
    pass


def device_info(chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is on "
                       f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_stats() -> Dict[str, Any]:
    import jax
    return jax.devices()[0].memory_stats() or {}


def peak_bytes() -> Optional[int]:
    return memory_stats().get("peak_bytes_in_use")


def _stats(gw) -> Dict[str, Any]:
    s = gw.stats()
    return {"requests": s["requests"], "panes": s["panes"],
            "prefill_calls": s["prefill_calls"],
            "inject_calls": s["inject_calls"],
            "hits": s["cache"]["hits"], "misses": s["cache"]["misses"],
            "evictions": s["cache"]["evictions"],
            "paths": dict(s["paths"])}


def _delta(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    out = {k: b[k] - a[k] for k in a if k != "paths"}
    out["paths"] = {k: b["paths"][k] - a["paths"].get(k, 0)
                    for k in b["paths"]}
    return out


def warm_up(gw, conf, sched, base: int, seed: int, catalog: int):
    """Put the warm users' states in the pool, deliver the events they
    watched before the window (the mix's ``fresh_before_window``), then
    serve one pane of them, with two more fresh events each, twice:
    prefill, scatter, gather, inject, finalize and the slate all compile
    and run before the window. Returns the fresh events it delivered
    (users, items, ts), in delivery order."""
    from repro.serving.api import Request

    warmed = gw.warm(sched.warm_users, base)
    if warmed != len(sched.warm_users):
        raise RuntimeError(f"warm admitted {warmed} of "
                           f"{len(sched.warm_users)} users")
    b = conf["serving"]["max_batch"]
    rng = np.random.default_rng(cell_mod.derived_seed(seed, "warm"))
    users = np.concatenate([sched.pre_users,
                            np.repeat(sched.warm_users[:b], 2)])
    items = np.concatenate([sched.pre_items,
                            rng.integers(0, catalog, 2 * b)])
    ts = np.concatenate([sched.pre_ts, base - rng.integers(1, 30, 2 * b)])
    gw.observe_many(users, items, ts)
    for _ in range(2):
        gw.submit_many([Request(user=int(u), now=base)
                        for u in sched.warm_users[:b]])
        gw.drain(base)
    return users, items, ts


def reference_check(params, cfg, conf, pr, win, seed, control=None):
    """Sample the window's finished requests and compare their slates
    with the reference. With ``control`` (a precision of reference.py)
    the sampled requests' slates are the reference's own greedy decode
    at that precision: the control put in the program's place, judged by
    the same comparison. Returns (readings dict, gaps)."""
    import jax

    from bench import reference

    s = conf["serving"]
    n_rows = conf["check"]["sample_rows"]
    idx = check.sample(win, n_rows, seed, lambda k: len(pr.prompt(k)))
    if not idx:
        return {"rows": 0}, np.full((1, 1), np.inf)
    length = s["prefill_len"] + s["inject_len"] + s["slate_len"] - 1
    # every request of the sample is scored at the same shapes
    prompts = [pr.prompt(k) for k in idx]
    prompts += [prompts[0]] * (n_rows - len(idx))
    if control is None:
        slates = np.stack([np.asarray(win.tickets[k].response.slate)
                           for k in idx]).astype(np.int64)
        slates = np.concatenate([slates] + [slates[:1]] * (n_rows - len(idx)))
    else:
        slates = reference.greedy_slates(params, prompts, s["slate_len"],
                                         length, cfg=cfg, mode=control)
    toks, pos = check.streams(prompts, slates, length)
    ref = np.asarray(jax.block_until_ready(reference.logits_at(
        params, toks, pos, cfg=cfg, mode="highest")))
    gaps = check.served_gaps(ref[:len(idx)], slates[:len(idx)])
    n_sfx = [len(p) - s["prefill_len"] for p in prompts[:len(idx)]]
    return ({"rows": len(idx), "tokens": int(slates[:len(idx)].size),
             "longest_prompt": max(len(p) for p in prompts),
             "shortest_prompt": min(len(p) for p in prompts),
             "rows_with_suffix": int(sum(n > 0 for n in n_sfx))}, gaps)


def run(spec, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, t_start: Optional[float] = None,
        control: Optional[str] = None, fault=None,
        reference: bool = True) -> Dict[str, Any]:
    """One run of the cell ``spec`` (cell.Spec). Returns the result
    object the command prints. ``fault(gw)`` plants a fault in the
    gateway before the window (bench/faults.py); with ``control`` the
    check judges the control's slates in place of the served ones, and
    ``_served_gap`` keeps the served slates' reading (calibrate.py)."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    dev = device_info(spec.chips, require_tpu)
    peak_table = cell_mod.peaks(dev["kind"], spec.bench_dir) if (
        require_tpu or trace) else None
    conf, mix, cellf = spec.config, spec.traffic, spec.cell
    cfg = cell_mod.model_config(conf)
    catalog = cell_mod.n_items(cfg)
    phase = {}

    t = time.perf_counter()
    with window.counting_compiles() as weight_compiles:
        params = cell_mod.make_weights(cfg, seed, jax.devices()[0])
    phase["weights_s"] = time.perf_counter() - t
    phase["weights_compiles"] = weight_compiles[0]
    t = time.perf_counter()
    plane = cell_mod.make_plane(conf, catalog, seed)
    gw = cell_mod.build_gateway(cfg, params, conf, plane)
    sched = traffic.make_schedule(mix, conf, catalog, cellf["rate_per_s"],
                                  seconds, seed, plane.cutoff)
    phase["plane_s"] = time.perf_counter() - t
    base = plane.cutoff + CLOCK_OFFSET
    t = time.perf_counter()
    with window.counting_compiles() as setup_compiles:
        warm_events = warm_up(gw, conf, sched, base, seed, catalog)
    phase["warm_s"] = time.perf_counter() - t
    phase["setup_compiles"] = setup_compiles[0]
    phase["setup_cache_hits"] = setup_compiles[1]
    if fault is not None:
        fault(gw)
    # the set-up's heap (plane, programs, pool bookkeeping) is kept out of
    # the collector's passes in the window, as a server freezes what it
    # loaded before it takes traffic; the window's own objects are not
    gc.collect()
    gc.freeze()

    tracer = None
    if trace:
        wrap_layers(gw)
        tracer = _Tracer(gw, seconds)
    st_open = _stats(gw)
    with window.counting_compiles() as win_compiles, \
            window.gc_pauses() as win_gc:
        win = window.run_window(gw, sched, base, seconds,
                                None if tracer is None else tracer.on_turn,
                                on_close=lambda: _stats(gw))
    st_close = win.at_close
    if tracer is not None:
        tracer.finish()
    setup_s = win.due[0] - t_start
    peak = peak_bytes()
    failed = sum(1 for tk in win.tickets if tk is None or tk.response is None
                 or tk.response.shed)
    lat = win.latency_ms()
    late = win.lateness_ms()
    log(f"{spec.name} seed={seed}: setup_s={setup_s:.3f} "
        f"({', '.join(f'{k}={v:.3f}' if isinstance(v, float) else f'{k}={v}' for k, v in phase.items())})")
    log(f"window: {len(win.due)} due, {win.completed} claimed by the close "
        f"after {win.seconds:.3f} s, {win.turns} turns, "
        f"compiles in window {win_compiles[0]}, garbage collections "
        f"{win_gc['count']} taking {win_gc['total_ms']:.3f} ms "
        f"(longest {win_gc['max_ms']:.3f})")
    log(f"latency ms p50={np.percentile(lat, 50):.3f} "
        f"p95={np.percentile(lat, 95):.3f} max={lat.max():.3f}; "
        f"generator lateness ms p50={np.percentile(late, 50):.3f} "
        f"max={late.max():.3f}")
    log("longest calls (ms, at s into the window): " + ", ".join(
        f"{k} {1e3 * d:.3f} at {at:.3f}" for k, (d, at) in
        sorted(win.longest.items())))
    log(f"the watch thread woke at most {1e3 * win.watch_late[0]:.3f} ms "
        f"late, at {win.watch_late[1]:.3f} s")
    for name, at, stack in win.stalls:
        log(f"turn over {window.STALL_S} s at {at:.3f} s, in {name}:\n"
            f"{stack}")
    wd = _delta(st_open, st_close)
    log(f"window counters: {json.dumps(wd)}; device memory: "
        f"{json.dumps(memory_stats())}")

    # the program's state goes before the reference runs
    del gw
    gc.unfreeze()
    gc.collect()
    prompts = check.Prompts(conf, plane, sched, win, warm_events)
    t = time.perf_counter()
    if reference:
        readings, gaps = reference_check(params, cfg, conf, prompts, win,
                                         seed)
    else:
        readings, gaps = {"rows": 0}, np.full((1, 1), np.inf)
    served_gap = float(np.max(gaps))
    if control is not None:
        log(f"served slates: widest gap {served_gap:.6g}")
        readings, gaps = reference_check(params, cfg, conf, prompts, win,
                                         seed, control=control)
        readings["control"] = control
    check_s = time.perf_counter() - t
    limit = cellf["logit_gap_limit"]
    gap = float(np.max(gaps))
    correct = bool(failed == 0 and readings["rows"] > 0 and gap <= limit)
    log(f"check ({check_s:.3f} s): {json.dumps(readings)}")

    device = dict(dev, memory_peak_bytes=peak)
    result: Dict[str, Any] = {"correct": correct, "attempted": len(win.due),
                              "failed": failed}
    if trace:
        red = tracer.reduce(cfg, conf)
        red["peak"] = peak_table
        red["rows"] = tracer.rows(win, prompts, conf)
        result["metrics"] = metrics_io.read_all(spec.name, red,
                                                spec.benchmark, spec.bench_dir)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["device"] = device
        result["breakdown"] = red["breakdown"]
    else:
        result["metrics"] = {
            "slates_per_s": {"value": win.completed / win.seconds,
                             "unit": "slates/s"},
            "latency_p50_ms": {"value": float(np.percentile(lat, 50)),
                               "unit": "ms"},
            "latency_p95_ms": {"value": float(np.percentile(lat, 95)),
                               "unit": "ms"},
            "setup_s": {"value": float(setup_s), "unit": "s"},
        }
        keep = metrics_io.end_to_end_names(spec.name, spec.benchmark)
        result["metrics"] = {k: v for k, v in result["metrics"].items()
                             if k in keep}
        result["device"] = device
    result["check"] = {
        "logit_gap": {"value": gap, "limit": limit},
        "unserved": {"value": failed, "limit": 0},
    }
    result["_served_gap"] = served_gap
    result["_check"] = readings
    result["_window"] = {"due": len(win.due), "seconds": win.seconds,
                         "turns": win.turns,
                         "lateness_ms_p95": float(np.percentile(late, 95)),
                         "lateness_ms_max": float(late.max()),
                         "latency_ms_p50": float(np.percentile(lat, 50)),
                         "latency_ms_p95": float(np.percentile(lat, 95)),
                         "rows_per_pane": wd["requests"] / max(1, wd["panes"]),
                         "compiles": win_compiles[0], "gc": win_gc,
                         "stalls": len(win.stalls),
                         "setup": phase}
    log(f"logit_gap {gap:.6g} limit {limit}")
    log(f"unserved {failed} limit 0")
    return result


def wrap_layers(gw) -> None:
    """Put a span (``bench.<layer>.<call>``) around each call of
    LAYER_CALLS, on the instances; a call the program lacks is left
    out."""
    import functools

    import jax

    def wrapped(fn, name):
        @functools.wraps(fn)
        def call(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return call

    for layer, calls in LAYER_CALLS.items():
        obj = gw if layer == "gateway" else getattr(gw, layer, None)
        for c in calls:
            fn = getattr(obj, c, None)
            if fn is not None:
                setattr(obj, c, wrapped(fn, f"bench.{layer}.{c}"))


class _Tracer:
    """Starts the profiler TRACE_AT into the window and stops it after
    the traced slice (``bench.slice``), TRACE_LEN of the window and at
    most TRACE_MAX_S. Collecting the trace stalls the loop for tens of
    seconds after the slice: the traced run's numbers come from the
    slice and its counters alone."""

    def __init__(self, gw, seconds: float):
        self.gw = gw
        self.at = TRACE_AT * seconds
        self.len = min(TRACE_MAX_S, TRACE_LEN * seconds)
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t0 = None
        self.state = "before"
        self.span = None
        self.st = []

    def on_turn(self, now: float) -> None:
        import jax
        if self.t0 is None:
            self.t0 = now
        if self.state == "before" and now - self.t0 >= self.at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # the harness's spans suffice
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.wall = [time.perf_counter(), None]
            self.st.append(_stats(self.gw))
            self.span = jax.profiler.TraceAnnotation("bench.slice")
            self.span.__enter__()
            self.state = "on"
        elif self.state == "on" and now - self.t0 >= self.at + self.len:
            self.span.__exit__(None, None, None)
            self.wall[1] = time.perf_counter()
            self.st.append(_stats(self.gw))
            jax.profiler.stop_trace()
            self.state = "after"

    def finish(self) -> None:
        self.gw = None          # the program's state goes before the check
        if self.state != "after":
            raise RuntimeError("the window ended before the traced slice")

    def rows(self, win, prompts, conf):
        """(prefilled, fresh tokens) of each row served in the slice."""
        lo, hi = self.wall
        p = conf["serving"]["prefill_len"]
        out = []
        for k in np.flatnonzero((win.done >= lo) & (win.done <= hi)):
            tel = win.tickets[k].response.telemetry
            out.append((not tel.cache_hit, len(prompts.prompt(int(k))) - p))
        return out

    def reduce(self, cfg, conf):
        from bench import trace as trace_mod
        try:
            red = trace_mod.reduce_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        red["slice_counters"] = _delta(self.st[0], self.st[1])
        red["cfg"], red["conf"] = cfg, conf
        return red
