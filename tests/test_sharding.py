"""Sharding rules: spec trees match parameter trees, sharded dims divide."""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.archs import ASSIGNED
from repro.configs.base import get_config
from repro.models.model import cache_shapes, param_shapes
from repro.sharding.rules import (ShardingRules, batch_pspec, cache_pspecs,
                                  data_axes, param_pspecs)

SINGLE = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(shapes, specs, mesh):
    def one(s, spec):
        assert isinstance(spec, P), spec
        assert len(spec) <= len(s.shape), (s.shape, spec)
        for dim, ax in zip(s.shape, spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % n == 0, (s.shape, spec, dim, n)
    jax.tree.map(one, shapes, specs)


@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["pod", "multipod"])
@pytest.mark.parametrize("arch", ASSIGNED)
def test_param_specs_match_and_divide(arch, mesh):
    cfg = get_config(arch)
    shapes = param_shapes(cfg)
    specs = param_pspecs(cfg, mesh)
    # identical tree structure (tree.map would throw otherwise)
    _check_divisible(shapes, specs, mesh)


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("batch", [128, 1], ids=["b128", "b1"])
def test_cache_specs_match_and_divide(arch, batch):
    cfg = get_config(arch)
    shapes = cache_shapes(cfg, batch, 32768)
    specs = cache_pspecs(cfg, SINGLE, batch)
    _check_divisible(shapes, specs, SINGLE)


def test_batch_pspec():
    assert batch_pspec(SINGLE, 256) == P(("data",), None)
    assert batch_pspec(SINGLE, 1) == P(None, None)
    assert batch_pspec(MULTI, 256) == P(("pod", "data"), None)


def test_data_axes():
    assert data_axes(SINGLE) == ("data",)
    assert data_axes(MULTI) == ("pod", "data")


def test_rules_head_vs_headdim():
    # llama: 32 heads % 16 == 0 -> heads on tp
    r = ShardingRules.make(get_config("llama3.2-1b"), SINGLE)
    assert r.attn_heads_on_tp
    # granite: 24 heads % 16 != 0 -> head_dim on tp
    r = ShardingRules.make(get_config("granite-moe-3b-a800m"), SINGLE)
    assert not r.attn_heads_on_tp
    assert r.tpa(get_config("granite-moe-3b-a800m").head_dim_) == "model"


def test_moe_expert_placement():
    # jamba 16 experts % 16 == 0 -> expert-parallel over tp
    assert ShardingRules.make(get_config("jamba-v0.1-52b"), SINGLE).moe_experts_on_tp
    # mixtral 8 experts -> TP inside experts
    assert not ShardingRules.make(get_config("mixtral-8x22b"), SINGLE).moe_experts_on_tp
