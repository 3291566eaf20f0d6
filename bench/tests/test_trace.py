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
    """The engine's programs all come out of XLA as jit__unknown; the
    n-th run on the device is the n-th launch on the host, named by the
    layer span around it (the innermost one)."""
    host = [("bench.slice", 0, 100 * MS),
            ("bench.submit_many", 0, 100 * MS),
            ("bench.engine.inject", 10 * MS, 12 * MS),
            ("bench.engine.decode_slate", 20 * MS, 60 * MS),
            ("bench.engine.finalize", 21 * MS, 23 * MS),
            ("bench.pool.gather", 5 * MS, 6 * MS)]
    launches = [5.5 * MS, 11 * MS, 22 * MS, 30 * MS, 70 * MS]
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
