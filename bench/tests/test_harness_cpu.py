"""Every cell kind end to end at a tiny size on the CPU, through the
harness's internals (the command itself refuses to run without a TPU),
from a directory that holds nothing but the benchmark's files; and the
check coming out false when the served path is broken underneath.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import tiny
from bench import cell, faults, harness

SEED = 2 ** 31 + 11  # more than 32 signed bits hold


def _run(tmp_path, kind, traffic, rate, fault=None, seed=SEED,
         control=None):
    name = tiny.write_bench(str(tmp_path), kind, traffic, rate)
    spec = cell.load_spec(name, os.path.join(str(tmp_path), "bench"))
    return harness.run(spec, seed, 1.5, False, require_tpu=False,
                       fault=fault, control=control)


@pytest.mark.parametrize("kind,traffic", [
    ("mamba", "session"), ("ranker", "session"),
    ("mamba", "first_visit"), ("ranker", "first_visit")])
def test_cell_runs_correct_from_files(tmp_path, kind, traffic):
    r = _run(tmp_path, kind, traffic, 40)
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] == 60
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert [k for k in r if not k.startswith("_")][-1] == "check"
    assert set(r["metrics"]) == {"slates_per_s", "setup_s"}
    assert r["metrics"]["slates_per_s"]["value"] > 0
    if traffic == "session":
        # the ring is full before the window: every request injects
        # inject_len fresh tokens on top of prefill_len history tokens
        s = tiny.tiny_config(kind)["serving"]
        full = s["prefill_len"] + s["inject_len"]
        assert r["_check"]["shortest_prompt"] == full
        assert r["_check"]["longest_prompt"] == full


@pytest.mark.parametrize("seed", [1, SEED])
def test_every_seed_gets_the_same_work(seed):
    """Arrivals, events per arrival and pre-window events come in the
    same counts for every seed; pre-window events lie after the cutoff,
    before the window's, at distinct times per user."""
    from bench import traffic
    conf = tiny.tiny_config("mamba")
    mix = cell.read_json(os.path.join(cell.BENCH, "traffic",
                                      "session.json"))
    cutoff = 5 * cell.DAY
    sc = traffic.make_schedule(mix, conf, 95, 40, 1.5, seed, cutoff)
    slots = conf["serving"]["pool_slots"]
    k = mix["fresh_before_window"]
    assert len(sc.due_s) == 60 and sc.n_events.sum() == 150
    assert len(sc.pre_users) == slots * k
    assert (sc.pre_ts >= cutoff).all()
    assert (sc.pre_ts < cutoff + traffic.PRE_WINDOW_S).all()
    for u in sc.warm_users:
        ts = sc.pre_ts[sc.pre_users == u]
        assert len(np.unique(ts)) == k


@pytest.mark.parametrize("fault", sorted(faults.ALL))
@pytest.mark.parametrize("kind", ["mamba", "ranker"])
def test_broken_path_is_not_correct(tmp_path, kind, fault):
    r = _run(tmp_path, kind, "session", 40, fault=faults.ALL[fault])
    assert not r["correct"], r["check"]
    assert r["check"]["logit_gap"]["value"] > r["check"]["logit_gap"][
        "limit"]


@pytest.mark.parametrize("kind", ["mamba", "ranker"])
def test_control_in_the_programs_place_is_not_correct(tmp_path, kind):
    """The fp8 reference's own slates, put in place of the served ones,
    come out as not correct through the same comparison, while the
    served slates of the same run pass it."""
    r = _run(tmp_path, kind, "session", 40, control="fp8")
    assert r["_served_gap"] <= r["check"]["logit_gap"]["limit"]
    assert not r["correct"], r["check"]
    assert r["check"]["logit_gap"]["value"] > r["check"]["logit_gap"][
        "limit"]


def test_a_stall_in_the_window_is_named(tmp_path, capsys):
    """A pane that holds the loop past STALL_S has the loop's stack
    taken, naming the call it stalled in."""
    import time

    def slow_once(gw):
        eng, orig, left = gw.engine, gw.engine.decode_slate, [1]

        def slow(*a, **k):
            if left[0]:
                left[0] = 0
                time.sleep(0.8)
            return orig(*a, **k)
        eng.decode_slate = slow
    r = _run(tmp_path, "mamba", "session", 40, fault=slow_once)
    assert r["correct"], r["check"]
    assert r["_window"]["stalls"] == 1
    err = capsys.readouterr().err
    assert "in submit_many" in err and "time.sleep(0.8)" in err


def test_command_refuses_without_tpu():
    root = os.path.dirname(cell.BENCH)
    p = subprocess.run([sys.executable, os.path.join(root, "bench", "run.py"),
                        "--workload", "mamba2-780m.session", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
