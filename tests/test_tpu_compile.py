"""Compile the main path's kernels and pool programs for a TPU v5e chip that
is described, not attached.

Interpret mode runs a Pallas kernel's body in Python and never lowers it,
so it cannot see what the chip's compiler refuses: a block whose last two
dims break the (8, 128) tiling rule, a body Mosaic cannot lower, a float32
copy the compiler quietly runs in bfloat16. These tests compile at the
widths ``chip_smoke.py`` serves, so a regression fails here, without a
chip.

The TPU library may be loaded by one process at a time, and the process
keeps it until it exits. So the topology is described inside a module
fixture -- never at import, in a ``skipif`` or in ``parametrize`` -- and
every test of this kind lives in this one file, kept on one worker
(``--dist loadfile``, or the ``xdist_group`` below under ``--dist
loadgroup``): under several pytest workers only that worker loads the
library. The tests skip only where no TPU compiler is installed; any
other failure to describe the chip fails them.
"""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402  (the checkout root holds it)

KERNELS = ("history_merge", "ssd_scan", "decode_attention", "flash_attention")

pytestmark = pytest.mark.xdist_group("libtpu")


@pytest.fixture(scope="module")
def one_chip():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) is installed")
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise write its logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        # a compile for a described chip cannot be read back from the
        # persistent cache; keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    cases = {c[0]: c for c in chip_smoke.kernel_cases()}
    _, make, kernel, _ = cases[name]
    args = _on(one_chip, jax.eval_shape(make, jax.random.PRNGKey(0)))
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _pool_shapes(n_slots):
    """Pool and pane shapes of mamba2-780m at chip_smoke's serving
    shapes, from an eval_shape of its prefill (no weights allocated)."""
    from repro.configs.base import get_config
    from repro.models.model import param_shapes, prefill

    cfg = get_config("mamba2-780m")
    b, p = chip_smoke.MAX_BATCH, chip_smoke.PREFILL_LEN
    logits, caches = jax.eval_shape(
        lambda w, t, v: prefill(w, cfg, t, valid=v),
        param_shapes(cfg, dtype=jnp.float32),
        jax.ShapeDtypeStruct((b, p), jnp.int32),
        jax.ShapeDtypeStruct((b, p), jnp.bool_))
    slotted = lambda s: jax.ShapeDtypeStruct(
        (s.shape[0], n_slots) + s.shape[2:], s.dtype)
    pool = (jax.tree.map(slotted, caches),
            jax.ShapeDtypeStruct((n_slots, p), jnp.bool_),
            jax.ShapeDtypeStruct((n_slots,), jnp.int32),
            jax.ShapeDtypeStruct((n_slots, logits.shape[-1]), logits.dtype))
    pane = (caches, jax.ShapeDtypeStruct((b, p), jnp.bool_),
            jax.ShapeDtypeStruct((b,), jnp.int32), logits)
    onehot = jax.ShapeDtypeStruct((b, n_slots), jnp.float32)
    return pool, pane, onehot


def test_pool_scatter_keeps_float32_on_v5e(one_chip):
    """The writeback copies float32 state through a 0/1 contraction; at
    default precision the chip runs it as one bfloat16 pass and rounds
    every cached SSM state and score. No bf16 may appear in the program."""
    from repro.serving.pool import _scatter_impl

    pool, pane, onehot = _pool_shapes(n_slots=chip_smoke.MAX_BATCH)
    args = _on(one_chip, pool + pane + (onehot,))
    hlo = jax.jit(_scatter_impl, donate_argnums=(0, 1, 2, 3)).lower(
        *args).compile().as_text()
    assert "bf16" not in hlo


def test_pool_onehot_gather_keeps_float32_on_v5e(one_chip):
    """The mesh path's one-hot gather (a pool on one chip gathers by
    ``take``) must stay an exact float32 copy too."""
    from repro.serving.pool import _gather_impl

    pool, _, onehot = _pool_shapes(n_slots=chip_smoke.MAX_BATCH)
    args = _on(one_chip, pool + (onehot,))
    hlo = jax.jit(_gather_impl).lower(*args).compile().as_text()
    assert "bf16" not in hlo
