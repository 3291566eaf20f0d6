"""BENCHMARK.json against the files the harness finds by name, and
against the limits BENCHMARK.json must keep: every cell has its
configuration, traffic and cell files; every per-layer metric its reader,
declaring what BENCHMARK.json declares; and the readers read."""
import json
import math
import os
import re

import pytest

from bench import cell, metrics_io

ROOT = os.path.dirname(cell.BENCH)
BM_PATH = os.path.join(ROOT, "BENCHMARK.json")
BM = cell.read_json(BM_PATH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys_and_size():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(BM_PATH) <= 64 * 1024
    assert BM["paths"] == ["bench"]
    assert BM["command"] == ["python3", "bench/run.py"]
    assert isinstance(BM["run_seconds"], int) and 1 <= BM["run_seconds"] <= 51


def test_names_units_and_lines():
    names = ([c["name"] for c in BM["configs"]] + CELLS
             + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
             + [w["traffic"] for w in BM["workloads"]])
    assert all(NAME.match(n) for n in names), names
    for group in (BM["configs"], BM["workloads"],
                  BM["end_to_end"] + BM["per_layer"]):
        assert len({g["name"] for g in group}) == len(group)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    texts = ([w["why"] for w in BM["workloads"] + BM["configs"]]
             + [c["source"] for c in BM["configs"]]
             + [m["layer"] for m in BM["per_layer"]])
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)


def test_cells_have_their_files():
    pairs = set()
    for w in BM["workloads"]:
        spec = cell.load_spec(w["name"])
        assert w["chips"] == 1
        assert spec.cell["rate_per_s"] > 0
        assert spec.cell["logit_gap_limit"] > 0
        cell.model_config(spec.config)        # matches the registry
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(BM["workloads"])
    used = {w["config"] for w in BM["workloads"]}
    for c in BM["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        conf = cell.read_json(os.path.join(ROOT, c["file"]))
        assert conf["reduced"] == c["reduced"]
        assert conf["source"].startswith(c["source"])


def test_end_to_end_and_per_layer_coverage():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for c in CELLS:
        mine = metrics_io.end_to_end_names(c, BM)
        assert "setup_s" in mine and len(mine) >= 2
        layers = [m for m in BM["per_layer"]
                  if c in m.get("workloads", CELLS)]
        assert layers
        for m in layers:
            assert m["moves"] in mine
    for m in BM["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("m", BM["per_layer"], ids=lambda m: m["name"])
def test_reader_declares_what_the_benchmark_declares(m):
    r = metrics_io.load_reader(m["name"])
    for key in ("unit", "layer", "moves", "source"):
        assert getattr(r, key.upper(), m[key]) == m[key]
    assert r.read(_ctx(empty=True)) is None
    v = r.read(_ctx())
    assert v is not None and math.isfinite(v) and v >= 0
    if m["unit"] == "%":
        assert v <= 100.0 + 1e-9


READERS = sorted(f[:-3] for f in os.listdir(os.path.join(cell.BENCH,
                                                       "metrics"))
                 if f.endswith(".py"))


@pytest.mark.parametrize("name", READERS)
def test_every_reader_reads(name):
    """Every reader, of one metric or of a family, reads the context."""
    r = metrics_io.load_reader(name)
    assert r.UNIT and r.LAYER and r.SOURCE
    assert r.read(_ctx(empty=True)) is None
    v = r.read(_ctx())
    assert v is not None and math.isfinite(v) and v >= 0
    if r.UNIT == "%":
        assert v <= 100.0 + 1e-9


def _ctx(empty=False):
    """A reduced trace as trace.py returns it, for a one-chip mamba2-780m
    cell: every program run at its least time, the device busy 60% of
    the time work was in hand."""
    from bench import work
    conf = cell.read_json(os.path.join(cell.BENCH, "configs",
                                       "mamba2-780m.json"))
    cfg = cell.model_config(conf)
    peak = cell.peaks("TPU v5 lite")
    s = conf["serving"]
    lt = lambda w: work.least_time(w, peak)
    counters = {"requests": 0 if empty else 80, "panes": 0 if empty else 10,
                "prefill_calls": 0, "inject_calls": 0, "hits": 0,
                "misses": 0 if empty else 40, "evictions": 0, "paths": {}}
    runs = {} if empty else {
        "jit__unknown@engine.prefill": (lt(work.prefill(cfg, 8, 256)), 1),
        "jit__unknown@engine.inject": (lt(work.inject(cfg, 8, 16, 256)), 1),
        "jit__unknown@engine.finalize": (0.001, 1),
        "jit__unknown@engine.decode_slate": (lt(work.slate(cfg, 8, 8, 272)),
                                             1),
        "jit__scatter_impl@pool.scatter": (0.009, 1)}
    return {"module_s": {k: v[0] for k, v in runs.items()},
            "module_n": {k: v[1] for k, v in runs.items()},
            "slice_counters": counters,
            "hand_s": 0.0 if empty else 1.0,
            "busy_hand_s": 0.0 if empty else 0.6,
            "rows": [] if empty else [(True, 16)] * 8 + [(False, 16)] * 72,
            "cfg": cfg, "conf": conf, "peak": peak}


def test_eager_operations_beside_a_program_are_left_out():
    """A call may launch eager operations (jnp.full, a conversion) beside
    its program: a jit_<op> module in the same span must not count as a
    run of the program."""
    ctx = _ctx()
    ctx["module_s"]["jit_broadcast_in_dim@engine.inject"] = 1e-5
    ctx["module_n"]["jit_broadcast_in_dim@engine.inject"] = 2
    r = metrics_io.load_reader("inject_ms")
    assert r.read(ctx) == pytest.approx(
        1e3 * ctx["module_s"]["jit__unknown@engine.inject"])


def test_a_family_reader_serves_each_split_name(tmp_path):
    """``mfu.<part>`` is read by ``mfu.py`` where it has no reader of its
    own; a reader of its own wins."""
    assert metrics_io.reader_path("mfu.session").endswith("metrics/mfu.py")
    assert metrics_io.reader_path("mfu.later_cells").endswith(
        "metrics/mfu.py")
    d = tmp_path / "metrics"
    d.mkdir()
    (d / "mfu.py").write_text("")
    (d / "mfu.other.py").write_text("")
    assert metrics_io.reader_path("mfu.other", str(tmp_path)) == str(
        d / "mfu.other.py")


def test_result_line_is_json_serialisable_names():
    for m in BM["per_layer"]:
        assert json.dumps({m["name"]: {"value": 1.0, "unit": m["unit"]}})
