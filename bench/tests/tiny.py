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
}


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
                limit: float = 1e-3) -> str:
    """A checkout-like directory holding only files: BENCHMARK.json and
    bench/{configs,traffic,cells,metrics,peaks.json}. Returns the cell."""
    conf = tiny_config(kind)
    cname = f"{conf['name']}.{traffic}"
    b = os.path.join(root, "bench")
    for d in ("configs", "traffic", "cells", "metrics"):
        os.makedirs(os.path.join(b, d), exist_ok=True)
    with open(os.path.join(b, "configs", conf["name"] + ".json"), "w") as f:
        json.dump(conf, f)
    real_mix = os.path.join(ROOT, "bench", "traffic", traffic + ".json")
    with open(os.path.join(b, "traffic", traffic + ".json"), "w") as f:
        json.dump(cell.read_json(real_mix) if os.path.exists(real_mix)
                  else MIXES[traffic], f)
    with open(os.path.join(b, "cells", cname + ".json"), "w") as f:
        json.dump({"rate_per_s": rate, "logit_gap_limit": limit}, f)
    with open(os.path.join(b, "peaks.json"), "w") as f, open(os.path.join(
            ROOT, "bench", "peaks.json")) as g:
        f.write(g.read())
    real = cell.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    bm = {"configs": [{"name": conf["name"], "source": "tiny",
                       "file": f"bench/configs/{conf['name']}.json",
                       "reduced": [], "why": "tiny"}],
          "workloads": [{"name": cname, "config": conf["name"],
                         "traffic": traffic, "chips": 1, "why": "tiny"}],
          "end_to_end": [_for_cell(m, cname, traffic)
                         for m in real["end_to_end"]
                         if _for_cell(m, cname, traffic) is not None],
          "per_layer": []}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
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
