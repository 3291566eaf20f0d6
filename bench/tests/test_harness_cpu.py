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
    name = tiny.write_bench(str(tmp_path), kind, traffic, rate,
                            code=kind in tiny.CODE)
    spec = cell.load_spec(name, os.path.join(str(tmp_path), "bench"))
    return harness.run(spec, seed, 1.5, False, require_tpu=False,
                       fault=fault, control=control)


def _calls(root):
    """What the stand-in hybrid's own module was asked, in order."""
    path = os.path.join(str(root), "bench", "configs", "tiny-hybrid.calls")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return f.read().split("\n")[:-1]


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


def test_a_hybrid_is_checked_by_its_own_module(tmp_path):
    """A configuration with mixed layer kinds and experts runs correct
    through the reference its bench/configs/<name>.py brings."""
    r = _run(tmp_path, "hybrid", "session", 40)
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] == 60
    assert _calls(tmp_path) == ["logits_at highest"]


def test_a_hybrid_is_added_by_adding_files(tmp_path):
    """The benchmark as it stands, with a hybrid configuration and its
    cell added as files: nothing that was there changes, BENCHMARK.json
    only gains entries, and the cell runs correct through its module."""
    import json
    import shutil
    bench_dir = os.path.join(str(tmp_path), "bench")
    shutil.copytree(cell.BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm_path = os.path.join(str(tmp_path), "BENCHMARK.json")
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), bm_path)
    before = _tree(str(tmp_path))
    name = tiny.add_cell(str(tmp_path), "hybrid", "session", 40, code=True)
    after = _tree(str(tmp_path))
    assert {k for k in before if before[k] != after[k]} == {
        "BENCHMARK.json"}
    assert set(after) - set(before) == {
        "bench/configs/tiny-hybrid.json", "bench/configs/tiny-hybrid.py",
        "bench/cells/tiny-hybrid.session.json"}
    old, new = json.loads(before["BENCHMARK.json"]), json.loads(
        after["BENCHMARK.json"])
    for key, val in old.items():
        grown = key in ("configs", "workloads")
        assert (new[key][:-1] if grown else new[key]) == val, key
    spec = cell.load_spec(name, bench_dir)
    r = harness.run(spec, SEED, 1.5, False, require_tpu=False)
    assert r["correct"], r["check"]
    assert _calls(tmp_path) == ["logits_at highest"]


def _tree(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = fh.read()
    return out


def test_a_json_only_configuration_uses_the_shared_code(tmp_path):
    from bench import reference, work
    name = tiny.write_bench(str(tmp_path), "ranker", "session", 40)
    spec = cell.load_spec(name, os.path.join(str(tmp_path), "bench"))
    code = cell.config_code(spec.config, spec.bench_dir)
    assert code.logits_at is reference.logits_at
    for c in cell.CODE_CALLS[1:]:
        assert getattr(code, c) is getattr(work, c)


@pytest.mark.parametrize("module", [None, "def logits_at(*a, **k): pass\n"])
def test_a_hybrid_without_its_module_stops_at_set_up(tmp_path, monkeypatch,
                                                      module):
    """No module, or one that leaves out a call: the run stops before the
    weights are drawn, naming the file and the calls it has to define,
    and never reaches the shared reference."""
    from bench import reference
    name = tiny.write_bench(str(tmp_path), "hybrid", "session", 40)
    path = os.path.join(str(tmp_path), "bench", "configs", "tiny-hybrid.py")
    if module is not None:
        with open(path, "w") as f:
            f.write(module)

    def never(*a, **k):
        raise AssertionError("set-up went on")
    monkeypatch.setattr(cell, "make_weights", never)
    monkeypatch.setattr(reference, "logits_at", never)
    spec = cell.load_spec(name, os.path.join(str(tmp_path), "bench"))
    with pytest.raises(ValueError, match="bench/configs/tiny-hybrid.py "
                       "must define (logits_at, )?prefill, inject, slate, "
                       "scatter, row_flops$"):
        harness.run(spec, SEED, 1.5, False, require_tpu=False)


def test_a_module_gives_every_call_or_none(tmp_path):
    """A module beside a configuration that the shared code covers still
    has to give every call: none of the shared ones fills a gap."""
    name = tiny.write_bench(str(tmp_path), "ranker", "session", 40)
    path = os.path.join(str(tmp_path), "bench", "configs", "tiny-ranker.py")
    with open(path, "w") as f:
        f.write("from bench.work import prefill, inject, slate, scatter\n")
    spec = cell.load_spec(name, os.path.join(str(tmp_path), "bench"))
    with pytest.raises(ValueError, match="bench/configs/tiny-ranker.py "
                       "must define logits_at, row_flops$"):
        cell.config_code(spec.config, spec.bench_dir)


def test_the_readers_use_the_configurations_work_calls(tmp_path,
                                                       monkeypatch):
    """The traced run hands the readers the configuration's work calls
    (``ctx["work"]``), and the roofline and mfu readers count with them."""
    from bench import metrics_io
    from bench import trace as trace_mod
    name = tiny.write_bench(str(tmp_path), "hybrid", "session", 40,
                            code=True)
    spec = cell.load_spec(name, os.path.join(str(tmp_path), "bench"))
    cfg = cell.model_config(spec.config)
    code = cell.config_code(spec.config, spec.bench_dir)
    monkeypatch.setattr(trace_mod, "reduce_dir", lambda d: {
        "module_s": {"jit__inject_impl@engine.inject": 0.01},
        "module_n": {"jit__inject_impl@engine.inject": 1},
        "hand_s": 1.0})
    tracer = harness._Tracer(None, 10.0)
    tracer.st = [{"requests": 0, "paths": {}}] * 2
    ctx = tracer.reduce(cfg, spec.config, code)
    assert ctx["work"] is code
    ctx.update(peak=cell.peaks("TPU v5 lite"), rows=[(False, 8)] * 4)
    for m in ("inject_roofline", "mfu.session"):
        assert metrics_io.load_reader(m).read(ctx) > 0
    assert _calls(tmp_path) == ["inject"] + ["row_flops"] * 4


@pytest.mark.parametrize("fault", sorted(faults.ALL))
@pytest.mark.parametrize("kind", ["mamba", "ranker", "hybrid"])
def test_broken_path_is_not_correct(tmp_path, kind, fault):
    r = _run(tmp_path, kind, "session", 40, fault=faults.ALL[fault])
    assert not r["correct"], r["check"]
    assert r["check"]["logit_gap"]["value"] > r["check"]["logit_gap"][
        "limit"]


@pytest.mark.parametrize("kind", ["mamba", "ranker", "hybrid"])
def test_control_in_the_programs_place_is_not_correct(tmp_path, kind):
    """The fp8 reference's own slates, put in place of the served ones,
    come out as not correct through the same comparison, while the
    served slates of the same run pass it. A configuration's own module
    decodes the control and judges it."""
    r = _run(tmp_path, kind, "session", 40, control="fp8")
    assert r["_served_gap"] <= r["check"]["logit_gap"]["limit"]
    assert not r["correct"], r["check"]
    assert r["check"]["logit_gap"]["value"] > r["check"]["logit_gap"][
        "limit"]
    if kind in tiny.CODE:
        slate_len = tiny.tiny_config(kind)["serving"]["slate_len"]
        assert _calls(tmp_path) == (["logits_at highest"]
                                    + ["logits_at fp8"] * slate_len
                                    + ["logits_at highest"])


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
