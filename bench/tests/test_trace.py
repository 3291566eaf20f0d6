"""The trace reduction on synthetic intervals: busy union, work in hand,
per-program device time, idle gaps charged to the harness's spans."""
import pytest

from bench import trace as tr

MS = 1e6  # nanoseconds


def test_interval_algebra():
    assert tr.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                         (7, 10)]
    assert tr.subtract([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]
    assert tr.intersect([(0, 4), (6, 10)], [(3, 7), (9, 12)]) == [
        (3, 4), (6, 7), (9, 10)]
    assert tr.clip([(0, 4), (6, 10), (11, 12)], 2, 8) == [(2, 4), (6, 8)]
    assert tr.length([(0, 4), (6, 10)]) == 8
    assert tr.module_name("jit__inject_impl(1234)") == "jit__inject_impl"


def test_reduce_slice():
    host = [("bench.slice", 0, 100 * MS),
            ("bench.wait", 10 * MS, 20 * MS),
            ("bench.wait", 50 * MS, 60 * MS),
            ("bench.submit_many", 20 * MS, 50 * MS),
            ("bench.submit_many", 60 * MS, 100 * MS),
            ("bench.wait", 120 * MS, 130 * MS)]          # after the slice
    ops = [(22 * MS, 30 * MS), (35 * MS, 45 * MS), (40 * MS, 45 * MS),
           (62 * MS, 90 * MS), (-5 * MS, 2 * MS)]       # one straddles
    modules = [("jit__inject_impl(12)", 22 * MS, 30 * MS),
               ("jit__slate_impl(13)", 35 * MS, 45 * MS),
               ("jit__inject_impl(12)", 62 * MS, 90 * MS),
               ("jit__prefill_impl(9)", -5 * MS, 2 * MS)]  # starts before
    r = tr.reduce((0, 100 * MS), host, ops, modules)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["hand_s"] == pytest.approx(0.080)
    assert r["busy_s"] == pytest.approx(0.048)          # 2 + 8 + 10 + 28
    assert r["busy_hand_s"] == pytest.approx(0.048)     # no op in a wait
    assert r["module_s"] == pytest.approx({"jit__inject_impl": 0.036,
                                           "jit__slate_impl": 0.010})
    assert r["module_n"] == {"jit__inject_impl": 2, "jit__slate_impl": 1}
    idle = r["idle_by_host_s"]
    assert idle["wait"] == pytest.approx(0.020)
    assert idle["submit_many"] == pytest.approx(0.024)  # 12 + 12
    assert idle["other"] == pytest.approx(0.008)        # [2, 10)
    assert sum(idle.values()) == pytest.approx(0.100 - 0.048)
    assert r["breakdown"]["device_ops"][0] == ["jit__inject_impl",
                                               pytest.approx(0.036)]
    assert r["breakdown"]["idle_gaps"][0][0] == "submit_many"


def test_reduce_nothing_on_device():
    r = tr.reduce((0, 10 * MS), [("bench.slice", 0, 10 * MS)], [], [])
    assert r["busy_s"] == 0 and r["module_s"] == {}
    assert r["idle_by_host_s"]["other"] == pytest.approx(0.010)


def test_programs_named_by_their_launch():
    """Launches the trace does not name (here the programs all come out
    of XLA as jit__unknown): the n-th run on the device is the n-th such
    launch on the host, named by the layer span around it (the innermost
    one)."""
    host = [("bench.slice", 0, 100 * MS),
            ("bench.submit_many", 0, 100 * MS),
            ("bench.engine.inject", 10 * MS, 12 * MS),
            ("bench.engine.decode_slate", 20 * MS, 60 * MS),
            ("bench.engine.finalize", 21 * MS, 23 * MS),
            ("bench.pool.gather", 5 * MS, 6 * MS)]
    launches = [(5.5 * MS, None), (11 * MS, None), (22 * MS, None),
                (30 * MS, None), (70 * MS, None)]
    modules = [("jit__gather_take_impl(1)", 6 * MS, 9 * MS),
               ("jit__unknown(2)", 12 * MS, 25 * MS),
               ("jit__unknown(3)", 25 * MS, 27 * MS),
               ("jit__unknown(4)", 31 * MS, 60 * MS),
               ("jit_convert_element_type(5)", 70.5 * MS, 71 * MS),
               # launched before the trace began: no launch to match
               ("jit__unknown(4)", -10 * MS, -1 * MS)]
    ops = [(a, b) for _, a, b in modules]
    r = tr.reduce((0, 100 * MS), host, ops, modules, launches)
    assert r["module_n"] == {"jit__gather_take_impl@pool.gather": 1,
                             "jit__unknown@engine.inject": 1,
                             "jit__unknown@engine.finalize": 1,
                             "jit__unknown@engine.decode_slate": 1,
                             "jit_convert_element_type": 1}
    assert r["module_s"]["jit__unknown@engine.decode_slate"] == \
        pytest.approx(0.029)
    # idle [0,6), [9,12), [27,31), [60,70.5), [71,100), charged to the
    # innermost span around each part
    idle = r["idle_by_host_s"]
    assert idle["pool.gather"] == pytest.approx(0.001)        # [5,6)
    assert idle["engine.inject"] == pytest.approx(0.002)      # [10,12)
    assert idle["engine.decode_slate"] == pytest.approx(0.004)  # [27,31)
    assert "engine.finalize" not in idle
    assert idle["submit_many"] == pytest.approx(0.005 + 0.001 + 0.0105
                                                + 0.029)
    assert idle["other"] == 0


def _pane(t, late_convert=False):
    """One pane's launches (host) and runs (device) from ``t`` ms: a
    conversion and the gather in pool.gather, then inject, finalize and
    the slate in their engine spans; ``late_convert``: the conversion's
    run is stamped 3 ms before its launch, past the clocks' slack."""
    spans = [("bench.pool.gather", t * MS, (t + 2) * MS),
             ("bench.engine.inject", (t + 2) * MS, (t + 3) * MS),
             ("bench.engine.finalize", (t + 3) * MS, (t + 4) * MS),
             ("bench.engine.decode_slate", (t + 4) * MS, (t + 5) * MS)]
    progs = ["jit_convert_element_type", "jit__gather_take_impl",
             "jit__inject_impl", "jit__finalize_impl", "jit__slate_impl"]
    starts = [t + 0.2, t + 1, t + 2.5, t + 3.5, t + 4.5]
    launches = [(x * MS, p) for x, p in zip(starts, progs)]
    runs = [(f"{p}({k})", (x + 1) * MS, (x + 1.1) * MS)
            for k, (x, p) in enumerate(zip(starts, progs))]
    if late_convert:
        runs[0] = (runs[0][0], (t + 0.2 - 3) * MS, (t + 0.2 - 2.9) * MS)
    return spans, launches, runs


def test_a_run_that_misses_its_launch_moves_no_other():
    """Launches named by their program: one run stamped before its
    launch, past the slack, loses its own label; every other run keeps
    the span of its own launch (matched in order over all programs, each
    run after it would take the launch before its own)."""
    host, launches, modules = [("bench.slice", 0, 100 * MS)], [], []
    for i, t in enumerate((10, 30, 50)):
        s, la, m = _pane(t, late_convert=i == 1)
        host += s
        launches += la
        modules += m
    ops = [(a, b) for _, a, b in modules]
    r = tr.reduce((0, 100 * MS), host, ops, modules, launches)
    assert r["module_n"] == {"jit_convert_element_type@pool.gather": 2,
                             "jit__gather_take_impl@pool.gather": 3,
                             "jit__inject_impl@engine.inject": 3,
                             "jit__finalize_impl@engine.finalize": 3,
                             "jit__slate_impl@engine.decode_slate": 3,
                             "jit_convert_element_type": 1}
    # named after no run (or not named): matched in order over all
    for name in (None, "jit_elsewhere"):
        other = [(t, name) for t, _ in launches]
        r = tr.reduce((0, 100 * MS), host, ops, modules, other)
        assert r["module_n"]["jit__slate_impl@engine.finalize"] == 2


def test_launches_are_named_by_the_jitted_call_around_them(tmp_path,
                                                           monkeypatch):
    """trace.load names each launch by the innermost PjitFunction span
    around it in time: on the chip jaxlib's spans sit on the Python
    thread's line and the runtime's launches on a line of their own; a
    launch outside any call is unnamed."""
    import types

    import jax

    def ev(name, a, b):
        return types.SimpleNamespace(name=name, start_ns=a * MS,
                                     duration_ns=(b - a) * MS)
    python = [ev("bench.slice", 0, 50), ev("PjitFunction(_inject_impl)", 1, 4),
              ev("PjitFunction(_inject_impl)", 1.5, 3.5),
              ev("PjitFunction(_slate_impl)", 10, 12)]
    runtime = [ev(tr.LAUNCH, 2, 3), ev(tr.LAUNCH, 11, 11.5),
               ev(tr.LAUNCH, 20, 21)]
    line = lambda name, evs: types.SimpleNamespace(name=name, events=evs)
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        line(tr.MODULE_LINE, [ev("jit__inject_impl(1)", 5, 6)])])
    prof = types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[
            line("python3", python), line("main/869", runtime)]), dev])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: prof))
    host, launches, devices = tr.load(str(tmp_path / "x.xplane.pb"))
    assert launches == [(2 * MS, "jit__inject_impl"),
                        (11 * MS, "jit__slate_impl"), (20 * MS, None)]
    assert host == [("bench.slice", 0, 50 * MS)]
    assert list(devices) == ["/device:TPU:0"]
