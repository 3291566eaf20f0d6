"""One benchmark cell, built from its files.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
harness finds them, by name, at

    bench/configs/<config>.json   model sizes, serving shapes, feature plane
    bench/configs/<config>.py     the configuration's own reference and
                                  work counts, all of them, where the
                                  shared ones do not cover its layers
                                  (``config_code``)
    bench/traffic/<traffic>.json  the mix's parameters (read by traffic.py)
    bench/cells/<cell>.json       the cell's arrival rate and check limit

so a later cell, configuration or mix is added by adding files. Weights
and the feature plane are made here from the run's seed; the program
receives only them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types
from typing import Any, Dict

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
DAY = 86400


@dataclasses.dataclass
class Spec:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    cell: Dict[str, Any]
    benchmark: Dict[str, Any]
    bench_dir: str = BENCH


def read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, bench_dir: str = BENCH) -> Spec:
    """The cell ``workload`` of ``BENCHMARK.json`` (beside ``bench_dir``)
    with its configuration, traffic and cell files."""
    bm = read_json(os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bm["configs"] if c["name"] == w["config"])
    return Spec(
        name=workload, chips=int(w["chips"]),
        config=read_json(os.path.join(os.path.dirname(bench_dir), conf["file"])),
        traffic=read_json(os.path.join(bench_dir, "traffic",
                                   w["traffic"] + ".json")),
        cell=read_json(os.path.join(bench_dir, "cells", workload + ".json")),
        benchmark=bm, bench_dir=bench_dir)


# ----------------------------------------------------------------------
# The model configuration
# ----------------------------------------------------------------------

def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` as the file states it. A file that
    names a ``registry`` entry must match that entry field for field, so
    the benchmark runs what the program registers under that name."""
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

    m = dict(conf["model"])
    if m.get("ssm") is not None:
        m["ssm"] = SSMConfig(**m["ssm"])
    if m.get("moe") is not None:
        m["moe"] = MoEConfig(**m["moe"])
    cfg = ModelConfig(**m)
    if conf.get("registry"):
        from repro.configs.base import get_config
        reg = get_config(conf["registry"])
        if reg != cfg:
            raise ValueError(f"{conf['name']}: the file's model differs "
                             f"from the registered {conf['registry']!r}")
    return cfg


def load_module(path: str, name: str) -> types.ModuleType:
    """The Python file at ``path``, loaded by path as module ``name``."""
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# what a configuration's own module defines, all of them in place of the
# shared ones: the plain float32 reference (bench/reference.py) and the
# work counts of the calls the readers judge (bench/work.py)
CODE_CALLS = ("logits_at", "prefill", "inject", "slate", "scatter",
              "row_flops")


def config_code(conf: Dict[str, Any],
                bench_dir: str = BENCH) -> types.SimpleNamespace:
    """The reference and work calls of the configuration ``conf`` (the
    json of ``bench_dir``/configs/<name>.json), CODE_CALLS by name.

    Where ``bench/configs/<name>.py`` exists it is loaded by path and
    gives every call (it may import a shared one); otherwise the shared
    calls answer, for the configurations they cover
    (``reference.covers``). Anything else is refused here, at set-up. The
    precisions (``reference._quant``, ``_mm``), ``work.least_time`` and
    the check's comparison stay shared, so every configuration is judged
    by the same yardstick."""
    from bench import reference, work

    rel = f"bench/configs/{conf['name']}.py"
    path = os.path.join(bench_dir, "configs", conf["name"] + ".py")
    if os.path.exists(path):
        mod = load_module(path, "bench_config_" + conf["name"])
        missing = [c for c in CODE_CALLS if not hasattr(mod, c)]
    elif reference.covers(model_config(conf)):
        mod = types.SimpleNamespace(logits_at=reference.logits_at, **{
            c: getattr(work, c) for c in CODE_CALLS[1:]})
        missing = []
    else:
        missing = list(CODE_CALLS)
    if missing:
        raise ValueError(
            f"{conf['name']}: the shared reference and work counts cover "
            f"a stack of Mamba-2 layers or of dense attention layers only, "
            f"and a configuration's own module gives every call; {rel} "
            f"must define {', '.join(missing)}")
    return types.SimpleNamespace(**{c: getattr(mod, c) for c in CODE_CALLS})


def n_items(cfg) -> int:
    """Item ids the catalog holds: token = item + 1, token 0 is padding."""
    return cfg.vocab_size - 1


# ----------------------------------------------------------------------
# Weights: made on the device, from the seed, in one jitted call
# ----------------------------------------------------------------------

# leaf name -> how the leaf is drawn; matrices default to normal(0,
# fan_in^-1/2) with fan_in the leaf's input dims (after the layer axis)
_ONES = ("scale", "norm_scale", "D")
_ZEROS = ("conv_bias_x", "conv_bias_B", "conv_bias_C", "bq", "bk", "bv")
_FAN_IN_DIMS = {"wo": 2}          # (layers, heads, head_dim, d_model)
_EXPERT_MATS = ("gate", "up", "down")   # under "moe": (layers, experts,
                                        # in, out), fan_in = in


def _leaf_init(path, shape, key, d_model: int, cfg):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    name = path[-1]
    if name in _ONES:
        return jnp.ones(shape, f32)
    if name in _ZEROS:
        return jnp.zeros(shape, f32)
    if name == "table":
        return jax.random.normal(key, shape, f32) * d_model ** -0.5
    if name == "A_log":                     # A = -exp(A_log) in -[1, 16]
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if name == "dt_bias":                   # softplus(dt_bias) = dt
        s = cfg.ssm
        u = jax.random.uniform(key, shape, f32)
        dt = jnp.exp(jnp.log(s.dt_min) + u * (jnp.log(s.dt_max)
                                              - jnp.log(s.dt_min)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name.startswith("conv_"):            # depthwise, width conv_width
        lim = shape[-2] ** -0.5
        return jax.random.uniform(key, shape, f32, -lim, lim)
    if name in _EXPERT_MATS and "moe" in path[:-1]:
        fan_in = shape[-2]
    else:
        fan_in = int(np.prod(shape[1:1 + _FAN_IN_DIMS.get(name, 1)]))
    return jax.random.normal(key, shape, f32) * fan_in ** -0.5


def make_weights(cfg, seed: int, device=None):
    """Float32 weights in the program's parameter layout, drawn from
    ``seed`` on the device by one jitted call."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import init_params

    shapes = jax.eval_shape(lambda k: init_params(cfg, k, jnp.float32),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(str(getattr(p, "key", p)) for p in path)
             for path, _ in flat]

    def init(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf_init(p, s.shape, k, cfg.d_model, cfg)
            for p, (_, s), k in zip(paths, flat, keys)])

    key = jax.random.PRNGKey(derived_seed(seed, "weights"))
    fn = jax.jit(init) if device is None else jax.jit(
        init, out_shardings=jax.sharding.SingleDeviceSharding(device))
    return jax.block_until_ready(fn(key))


def derived_seed(seed: int, what: str) -> int:
    """A 31-bit seed for one use of the run's seed (any whole number)."""
    tag = int.from_bytes(what.encode(), "little") % (2 ** 31)
    return int(np.random.default_rng([seed % 2 ** 63, (seed >> 63) % 2 ** 63, tag])
               .integers(0, 2 ** 31 - 1))


# ----------------------------------------------------------------------
# The feature plane
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Plane:
    """Every user's batch history: ``items``/``ts`` of shape (n_users,
    history), ts ascending and distinct per user, all before ``cutoff``
    (the snapshot the window serves)."""
    items: np.ndarray
    ts: np.ndarray
    cutoff: int


def make_plane(conf: Dict[str, Any], n_catalog: int, seed: int) -> Plane:
    p = conf["plane"]
    u, h, days = p["n_users"], p["history"], p["days"]
    rng = np.random.default_rng(derived_seed(seed, "plane"))
    items = rng.integers(0, n_catalog, (u, h), dtype=np.int64)
    # one event per slot of width w: distinct, ascending timestamps
    w = days * DAY // h
    ts = (np.arange(h, dtype=np.int64)[None, :] * w
          + rng.integers(0, w, (u, h), dtype=np.int64))
    return Plane(items=items, ts=ts, cutoff=days * DAY)


def build_gateway(cfg, params, conf: Dict[str, Any], plane: Plane):
    """``Gateway`` -> injector -> engine + device pool over ``plane``:
    policy ``inject``, continuous batching (``max_wait=0``), the
    snapshot at ``plane.cutoff`` materialized."""
    from repro.core.feature_store import BatchFeatureStore, FeatureStoreConfig
    from repro.core.injection import FeatureInjector, InjectionConfig
    from repro.core.realtime import RealtimeConfig, RealtimeFeatureService
    from repro.serving.engine import ServingConfig, ServingEngine
    from repro.serving.scheduler import Gateway, ServerConfig

    s, p = conf["serving"], conf["plane"]
    engine = ServingEngine(cfg, params, ServingConfig(
        max_batch=s["max_batch"], prefill_len=s["prefill_len"],
        inject_len=s["inject_len"], cache_capacity=s["cache_capacity"]))
    store = BatchFeatureStore(FeatureStoreConfig(
        n_users=p["n_users"], feature_len=p["history"]))
    n_users, h = plane.items.shape
    store.extend(np.repeat(np.arange(n_users, dtype=np.int64), h),
                 plane.items.ravel(), plane.ts.ravel())
    store.run_snapshot(plane.cutoff)
    rts = RealtimeFeatureService(RealtimeConfig(
        n_users=p["n_users"], buffer_len=p["rt_buffer"], ingest_latency=0))
    inj = FeatureInjector(InjectionConfig(policy="inject",
                                          feature_len=p["history"]),
                          store, rts)
    return Gateway(engine, inj, ServerConfig(
        slate_len=s["slate_len"], pool_slots=s["pool_slots"], max_wait=0))


def peaks(kind: str, bench_dir: str = BENCH) -> Dict[str, Any]:
    """The published peaks of one chip, by ``device_kind``; a device
    that is not in ``bench/peaks.json`` is an error, not a default."""
    table = read_json(os.path.join(bench_dir, "peaks.json"))
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"known: {sorted(table)}")
    return table[kind]
