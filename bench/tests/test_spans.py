"""The program-span reduction (bench/spans.py) on synthetic intervals and
on a stand-in for a profiler file: self time, idle charged to the
innermost span of the serving thread alone, the harness's waits left
out, and trace.py's own numbers untouched by the program's spans."""
import re
import types

import pytest

from bench import spans as sp
from bench import trace as tr

MS = 1e6  # nanoseconds

# one pane of the serving thread, 0-100 ms, and a wait for the next
# arrival, 100-120 ms, inside a 0-150 ms slice
SERVING = [("repro.gateway.submit", 0 * MS, 100 * MS),
           ("repro.gateway.pane", 2 * MS, 98 * MS),
           ("repro.feature.suffixes", 4 * MS, 8 * MS),
           ("repro.pool.gather", 8 * MS, 10 * MS),
           ("repro.engine.inject", 10 * MS, 12 * MS),
           ("repro.engine.finalize", 12 * MS, 13 * MS),
           ("repro.engine.slate", 13 * MS, 15 * MS),
           ("repro.engine.readback", 15 * MS, 90 * MS),
           ("repro.gateway.readback", 90 * MS, 94 * MS),
           ("repro.gateway.respond", 94 * MS, 97 * MS),
           ("repro.gateway.submit", 120 * MS, 150 * MS),
           ("repro.gateway.pane", 121 * MS, 160 * MS)]  # past the slice
# the device runs gather, inject, finalize and slate back to back from
# 10 to 88 ms, and the next pane's programs from 125 ms
OPS = [(10 * MS, 88 * MS), (125 * MS, 145 * MS)]
WAITS = [(100 * MS, 120 * MS)]
SLICE = (0, 150 * MS)


def test_self_time_is_a_span_less_its_children():
    st = sp.reduce(SLICE, SERVING, OPS, WAITS)["spans"]
    assert st["repro.gateway.submit"]["n"] == 2
    assert st["repro.gateway.submit"]["total_s"] == pytest.approx(0.130)
    # 0-2, 98-100 and 120-121 ms
    assert st["repro.gateway.submit"]["self_s"] == pytest.approx(0.005)
    # 2-4, 97-98 ms of the first pane, 121-150 ms of the second (clipped)
    assert st["repro.gateway.pane"]["self_s"] == pytest.approx(0.032)
    assert st["repro.gateway.pane"]["total_s"] == pytest.approx(0.125)
    assert st["repro.engine.readback"]["self_s"] == pytest.approx(0.075)


def test_idle_is_charged_to_the_innermost_span():
    r = sp.reduce(SLICE, SERVING, OPS, WAITS)
    assert r["panes"] == 2
    idle = r["idle_s"]
    # 0-2, 98-100 and 120-121 ms
    assert idle["repro.gateway.submit"] == pytest.approx(0.005)
    # 2-4 and 97-98 ms, then 121-125 and 145-150 ms of the second pane
    assert idle["repro.gateway.pane"] == pytest.approx(0.012)
    assert idle["repro.feature.suffixes"] == pytest.approx(0.004)
    assert idle["repro.pool.gather"] == pytest.approx(0.002)     # 8-10
    assert idle["repro.engine.inject"] == 0
    assert idle["repro.engine.readback"] == pytest.approx(0.002)  # 88-90
    assert idle["repro.gateway.readback"] == pytest.approx(0.004)
    assert idle["repro.gateway.respond"] == pytest.approx(0.003)
    # 150 ms less 98 busy less the 20 ms wait
    assert r["idle_in_hand_s"] == pytest.approx(0.032)
    assert r["unattributed_s"] == pytest.approx(0.0, abs=1e-12)
    assert sum(idle.values()) == pytest.approx(r["idle_in_hand_s"])


def test_idle_inside_a_wait_is_not_charged():
    """The device idles all through the harness's wait; none of it is
    charged, whatever span the thread was in (here a submit span that
    wraps the wait)."""
    spans = [("repro.gateway.submit", 0, 100 * MS),
             ("repro.gateway.pane", 10 * MS, 90 * MS)]
    r = sp.reduce((0, 100 * MS), spans, [(20 * MS, 30 * MS)],
                  [(40 * MS, 80 * MS)])
    assert r["idle_in_hand_s"] == pytest.approx(0.050)
    assert r["idle_s"]["repro.gateway.pane"] == pytest.approx(0.030)
    assert r["idle_s"]["repro.gateway.submit"] == pytest.approx(0.020)


def test_only_the_serving_thread_is_charged():
    """A second thread's spans (a background build, say) overlap the
    serving thread's idle; they are never charged."""
    other = [("repro.feature.histories", 0, 150 * MS),
             ("repro.engine.prefill", 20 * MS, 30 * MS)]
    assert sp.serving_thread([other, SERVING]) == SERVING
    assert sp.serving_thread([other]) == []
    alone = sp.reduce(SLICE, SERVING, OPS, WAITS)
    picked = sp.reduce(SLICE, sp.serving_thread([other, SERVING]), OPS,
                       WAITS)
    assert picked == alone
    assert "repro.feature.histories" not in picked["idle_s"]


def test_idle_outside_every_span_is_unattributed():
    spans = [("repro.gateway.pane", 10 * MS, 20 * MS)]
    r = sp.reduce((0, 40 * MS), spans, [(12 * MS, 18 * MS)])
    assert r["idle_s"]["repro.gateway.pane"] == pytest.approx(0.004)
    assert r["unattributed_s"] == pytest.approx(0.030)


def test_no_program_spans_reads_nothing():
    r = sp.reduce(SLICE, [], OPS, WAITS)
    assert r["panes"] == 0 and r["spans"] == {} and r["idle_s"] == {}
    for g in GROUPS:
        assert sp.idle_ms_per_pane(r, g) is None
    assert sp.idle_ms_per_pane({}, GROUPS[0]) is None


GROUPS = list(sp.GROUPS.values())
NAMES = ["repro.gateway.submit", "repro.gateway.pane",
         "repro.gateway.readback", "repro.gateway.respond",
         "repro.feature.observe", "repro.feature.histories",
         "repro.feature.suffixes", "repro.feature.tokens",
         "repro.pool.gather", "repro.pool.scatter",
         "repro.engine.prefill", "repro.engine.inject",
         "repro.engine.finalize", "repro.engine.slate",
         "repro.engine.readback"]


@pytest.mark.parametrize("name", NAMES)
def test_every_span_is_in_one_group(name):
    assert sum(bool(re.search(g, name)) for g in GROUPS) == 1


def test_the_four_groups_add_up_to_the_charged_idle():
    r = sp.reduce(SLICE, SERVING, OPS, WAITS)
    parts = [sp.idle_ms_per_pane(r, g) for g in GROUPS]
    assert parts[2] == pytest.approx(1e3 * 0.006 / 2)     # readback
    assert sum(parts) == pytest.approx(1e3 * r["idle_in_hand_s"] / 2)


# ----------------------------------------------------------------------
# A stand-in for jax.profiler.ProfileData: the planes trace.py and
# spans.py read
# ----------------------------------------------------------------------

def _ev(name, a, b):
    return types.SimpleNamespace(name=name, start_ns=a, duration_ns=b - a)


def _profile(with_program):
    loop = [("bench.slice", 0, 150 * MS),
            ("bench.wait", 100 * MS, 120 * MS),
            ("bench.submit_many", 0, 100 * MS),
            ("bench.submit_many", 120 * MS, 150 * MS),
            ("bench.engine.inject", 10 * MS, 12 * MS),
            ("bench.engine.decode_slate", 12 * MS, 90 * MS),
            ("PJRT_LoadedExecutable_Execute", 10.5 * MS, 11 * MS),
            ("PJRT_LoadedExecutable_Execute", 14 * MS, 14.5 * MS)]
    if with_program:
        loop += SERVING
    other = ([("repro.feature.histories", 0, 150 * MS)] if with_program
             else [])
    line = lambda evs: types.SimpleNamespace(
        name="python", events=[_ev(*e) for e in evs])
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name=tr.OP_LINE,
                              events=[_ev("fusion", a, b) for a, b in OPS]),
        types.SimpleNamespace(name=tr.MODULE_LINE, events=[
            _ev("jit__inject_impl(1)", 11 * MS, 20 * MS),
            _ev("jit__slate_impl(2)", 20 * MS, 88 * MS)])])
    host = types.SimpleNamespace(name="/host:CPU",
                                 lines=[line(loop), line(other)])
    return types.SimpleNamespace(planes=[host, dev])


@pytest.fixture
def fake_trace(tmp_path, monkeypatch):
    """(trace.reduce_dir, spans.program) of one stand-in profile."""
    import jax
    (tmp_path / "x.xplane.pb").write_bytes(b"")
    which = {}
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: _profile(which["on"])))

    def reduce_dir(with_program):
        which["on"] = with_program
        red = tr.reduce_dir(str(tmp_path))
        return red, sp.program(str(tmp_path), red["device_plane"])
    return reduce_dir


def test_trace_keys_are_the_same_with_and_without_program_spans(
        fake_trace, capsys):
    (plain, none), (spanned, prog) = fake_trace(False), fake_trace(True)
    assert none["panes"] == 0
    assert plain == spanned
    assert plain["module_n"] == {"jit__inject_impl@engine.inject": 1,
                                 "jit__slate_impl@engine.decode_slate": 1}
    assert prog == sp.reduce(SLICE, SERVING, OPS, WAITS)
    assert "to no program span" in capsys.readouterr().err


def test_main_adds_the_program_spans_to_a_traced_run(monkeypatch, capsys):
    """The run is bench/run.py's, traced; its reduction is trace.py's
    with the program spans' groups printed beside it."""
    from bench import run
    seen = {}

    def fake_run(argv):
        seen["argv"] = argv
        seen["red"] = tr.reduce_dir("d")
        return 0
    monkeypatch.setattr(run, "main", fake_run)
    monkeypatch.setattr(tr, "reduce_dir",
                        lambda d: {"device_plane": "/device:TPU:0"})
    monkeypatch.setattr(sp, "program", lambda d, plane: sp.reduce(
        SLICE, SERVING, OPS, WAITS))
    assert sp.main(["--workload", "w", "--seed", "1", "--seconds", "2"]) == 0
    assert seen["argv"][-2:] == ["--trace", "1"]
    assert seen["red"] == {"device_plane": "/device:TPU:0"}
    err = capsys.readouterr().err
    assert "readback_idle_ms 3.0" in err
