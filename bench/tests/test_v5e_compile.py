"""The cells' programs compiled for a described TPU v5e, at the cells'
sizes, without a chip: prefill, inject, finalize, slate, the pool's
scatter and gather, for each configuration of the benchmark. Each test
prints ``compiled.memory_analysis()``; PERF.md records the bytes.

    JAX_PLATFORMS=cpu python -m pytest -q -s bench/tests/test_v5e_compile.py

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
attention decode step compiles its CPU branch (a one-hot ring write).
"""
import functools
import os

import pytest

from bench import cell

CONFIGS = ("mamba2-780m",)
PROGRAMS = ("prefill", "inject", "finalize", "slate", "scatter", "gather")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such compiles cannot be read back without a chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    import jax
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def programs(conf, sharding):
    """name -> (jitted body, abstract args) at the configuration's
    served shapes."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import init_params
    from repro.serving import engine as E
    from repro.serving import pool as PL

    cfg = cell.model_config(conf)
    s = conf["serving"]
    b, p, n, cap = (s["max_batch"], s["prefill_len"], s["inject_len"],
                    s["cache_capacity"])
    slots, q = s["pool_slots"], 512
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda k: init_params(cfg, k, jnp.float32),
                            sd((2,), jnp.uint32))
    pf = functools.partial(E._prefill_impl, cfg=cfg, q_chunk=q)
    inj = functools.partial(E._inject_impl, cfg=cfg, q_chunk=q)
    fin = functools.partial(E._finalize_impl, cfg=cfg, capacity=cap)
    sl = functools.partial(E._slate_impl, cfg=cfg,
                           slate_len=s["slate_len"])
    tok = lambda length: sd((b, length), jnp.int32)
    msk = lambda length: sd((b, length), jnp.bool_)
    logits, caches = jax.eval_shape(pf, params, tok(p), msk(p))
    vp = logits.shape[-1]
    rows = sd((b,), jnp.int32)
    first = sd((b, vp), jnp.float32)
    inj_out = jax.eval_shape(inj, params, caches, tok(n), msk(n), msk(p),
                             rows, first)
    ring = jax.eval_shape(fin, inj_out["caches"], msk(p + n))
    slotted = jax.tree.map(
        lambda x: sd((x.shape[0], slots) + x.shape[2:], x.dtype), caches)
    pool = (slotted, sd((slots, p), jnp.bool_), sd((slots,), jnp.int32),
            sd((slots, vp), jnp.float32))
    out = {
        "prefill": (jax.jit(pf), (params, tok(p), msk(p))),
        "inject": (jax.jit(inj), (params, caches, tok(n), msk(n), msk(p),
                                  rows, first)),
        "finalize": (jax.jit(fin), (inj_out["caches"], msk(p + n))),
        "slate": (jax.jit(sl), (params, ring, rows, first)),
        "scatter": (jax.jit(PL._scatter_impl, donate_argnums=(0, 1, 2, 3)),
                    pool + (caches, msk(p), rows, logits,
                            sd((b, slots), jnp.float32))),
        "gather": (jax.jit(PL._gather_take_impl), pool + (rows,)),
    }
    return {k: (f, _abstract(a, sharding)) for k, (f, a) in out.items()}


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("config", CONFIGS)
def test_compiles_for_v5e(one_chip, config, program):
    conf = cell.read_json(os.path.join(cell.BENCH, "configs",
                                       config + ".json"))
    fn, args = programs(conf, one_chip)[program]
    compiled = fn.lower(*args).compile()
    ma = compiled.memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes")
    print(f"\n{config} {program}: " + " ".join(
        f"{f.replace('_size_in_bytes', '')}={getattr(ma, f)}"
        for f in fields))
    assert ma.argument_size_in_bytes + ma.output_size_in_bytes \
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes < 16e9
