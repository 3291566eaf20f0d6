"""Tiny cells for the benchmark's CPU tests: the real files' structure at
sizes a test run holds."""
import copy
import json
import os

from bench import cell

ROOT = os.path.dirname(cell.BENCH)

TINY_MODEL = {
    "mamba": {"name": "tiny-mamba", "family": "ssm", "n_layers": 2,
              "d_model": 64, "n_heads": 0, "n_kv_heads": 1, "d_ff": 0,
              "vocab_size": 96, "head_dim": 0, "norm_eps": 1e-05,
              "rope_theta": 500000.0, "sliding_window": 0,
              "qkv_bias": False, "tie_embeddings": True, "moe": None,
              "ssm": {"d_state": 16, "expand": 2, "head_dim": 16,
                      "conv_width": 4, "chunk_size": 32, "dt_min": 0.001,
                      "dt_max": 0.1, "attn_period": 0, "attn_offset": 0},
              "frontend": "none", "source": "tiny"},
    "ranker": {"name": "tiny-ranker", "family": "dense", "n_layers": 2,
               "d_model": 32, "n_heads": 4, "n_kv_heads": 4, "d_ff": 64,
               "vocab_size": 96, "head_dim": 0, "norm_eps": 1e-05,
               "rope_theta": 10000.0, "sliding_window": 0,
               "qkv_bias": False, "tie_embeddings": True, "moe": None,
               "ssm": None, "frontend": "none", "source": "tiny"},
    # one Mamba-2 mixer and one attention mixer, an MoE block on both,
    # routed without dropping a token (capacity_factor = n_experts/top_k)
    "hybrid": {"name": "tiny-hybrid", "family": "hybrid", "n_layers": 2,
               "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "d_ff": 32,
               "vocab_size": 96, "head_dim": 16, "norm_eps": 1e-05,
               "rope_theta": 10000.0, "sliding_window": 0,
               "qkv_bias": False, "tie_embeddings": True,
               "moe": {"n_experts": 4, "top_k": 2, "period": 1,
                       "offset": 0, "router_jitter": 0.0,
                       "load_balance_coef": 0.01, "capacity_factor": 2.0},
               "ssm": {"d_state": 16, "expand": 2, "head_dim": 16,
                       "conv_width": 4, "chunk_size": 32, "dt_min": 0.001,
                       "dt_max": 0.1, "attn_period": 2, "attn_offset": 1},
               "frontend": "none", "source": "tiny"},
}

# A configuration's own module (bench/configs/<name>.py) for the stand-in
# hybrid, which the shared reference does not cover. It tests the
# plumbing, not the model: its reference is the program's own forward
# pass at HIGHEST (a precision mode rounds the weights through
# reference._quant), its work calls are the shared dense counts, and
# every call appends its name and mode to <name>.calls beside it.
HYBRID_CODE = '''
import os

import jax
import jax.numpy as jnp

from bench import reference, work

CALLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tiny-hybrid.calls")


def _note(what):
    with open(CALLS, "a") as f:
        f.write(what + "\\n")


def logits_at(params, tokens, pos, *, cfg, mode="highest"):
    from repro.models import model
    _note("logits_at " + mode)
    params = jax.tree.map(lambda w: reference._quant(w, mode, False), params)
    t = jnp.asarray(tokens)
    t = jnp.pad(t, ((0, 0), (0, -t.shape[1] % cfg.ssm.chunk_size)))
    with jax.default_matmul_precision("highest"):
        lg, _ = model.forward(params, cfg, t)
    lg = jnp.take_along_axis(lg, jnp.asarray(pos)[:, :, None], axis=1)
    return lg[..., :cfg.vocab_size]


def _noted(name):
    def call(*a, **k):
        _note(name)
        return getattr(work, name)(*a, **k)
    return call


prefill, inject, slate, scatter, row_flops = (
    _noted(n) for n in ("prefill", "inject", "slate", "scatter",
                        "row_flops"))
'''
CODE = {"hybrid": HYBRID_CODE}


# mixes that no cell of BENCHMARK.json runs yet, for the generator's
# other options: users drawn from the whole plane (first visits)
MIXES = {"first_visit": {"users": "all", "events_per_arrival": [1, 4],
                         "event_lag_s": [1, 30]}}


def tiny_config(kind: str):
    real = cell.read_json(os.path.join(ROOT, "bench", "configs",
                                       "mamba2-780m.json"))
    conf = copy.deepcopy(real)
    conf["name"] = TINY_MODEL[kind]["name"]
    conf.pop("registry")
    conf["model"] = TINY_MODEL[kind]
    conf["serving"] = {"max_batch": 4, "prefill_len": 32, "inject_len": 8,
                       "cache_capacity": 64, "slate_len": 4,
                       "pool_slots": 8}
    conf["plane"] = {"n_users": 64, "history": 32, "days": 5,
                     "rt_buffer": 8}
    conf["check"] = {"sample_rows": 12}
    return conf


def write_bench(root: str, kind: str, traffic: str, rate: float,
                limit: float = 1e-3, code: bool = False) -> str:
    """A checkout-like directory holding only files: BENCHMARK.json and
    bench/{configs,traffic,cells,metrics,peaks.json}; with ``code`` the
    configuration's own module (CODE) beside its json. Returns the
    cell."""
    b = os.path.join(root, "bench")
    for d in ("configs", "traffic", "cells", "metrics"):
        os.makedirs(os.path.join(b, d), exist_ok=True)
    real_mix = os.path.join(ROOT, "bench", "traffic", traffic + ".json")
    with open(os.path.join(b, "traffic", traffic + ".json"), "w") as f:
        json.dump(cell.read_json(real_mix) if os.path.exists(real_mix)
                  else MIXES[traffic], f)
    with open(os.path.join(b, "peaks.json"), "w") as f, open(os.path.join(
            ROOT, "bench", "peaks.json")) as g:
        f.write(g.read())
    real = cell.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump({"configs": [], "workloads": [],
                   "end_to_end": real["end_to_end"], "per_layer": []}, f)
    cname = add_cell(root, kind, traffic, rate, limit, code)
    bm = cell.read_json(os.path.join(root, "BENCHMARK.json"))
    bm["end_to_end"] = [_for_cell(m, cname, traffic)
                        for m in bm["end_to_end"]
                        if _for_cell(m, cname, traffic) is not None]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return cname


def add_cell(root: str, kind: str, traffic: str, rate: float,
             limit: float = 1e-3, code: bool = False) -> str:
    """Add a tiny configuration and its cell to the benchmark at ``root``
    the way a later change would, by adding files: its config (and, with
    ``code``, its module), its cell file, and one entry each in
    BENCHMARK.json's configs and workloads. Returns the cell."""
    conf = tiny_config(kind)
    cname = f"{conf['name']}.{traffic}"
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", conf["name"] + ".json"), "w") as f:
        json.dump(conf, f)
    if code:
        with open(os.path.join(b, "configs", conf["name"] + ".py"),
                  "w") as f:
            f.write(CODE[kind])
    with open(os.path.join(b, "cells", cname + ".json"), "w") as f:
        json.dump({"rate_per_s": rate, "logit_gap_limit": limit}, f)
    path = os.path.join(root, "BENCHMARK.json")
    bm = cell.read_json(path)
    bm["configs"].append({"name": conf["name"], "source": "tiny",
                          "file": f"bench/configs/{conf['name']}.json",
                          "reduced": [], "why": "tiny"})
    bm["workloads"].append({"name": cname, "config": conf["name"],
                            "traffic": traffic, "chips": 1, "why": "tiny"})
    with open(path, "w") as f:
        json.dump(bm, f)
    return cname


def _for_cell(metric, cname, traffic):
    """The real metric as it applies to a tiny cell of ``traffic``: kept
    where the real benchmark gives it to that traffic's cells."""
    if "workloads" not in metric:
        return metric
    if any(w.endswith("." + traffic) for w in metric["workloads"]):
        return dict(metric, workloads=[cname])
    return None
