"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS for 512 host devices
*before* any jax initialization; everything else sees the real devices).
"""
from __future__ import annotations

import jax

SINGLE_POD = (16, 16)                # 256 chips (one v5e-256 pod)
MULTI_POD = (2, 16, 16)              # 2 pods = 512 chips

# TPU v5e-class hardware constants used by the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12             # FLOP/s
HBM_BW = 819e9                       # B/s
ICI_BW_PER_LINK = 50e9               # B/s per link (~4 links usable/chip)
ICI_LINKS = 4


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_serving_mesh(data: int = 1, model: int = 1,
                      devices=None) -> jax.sharding.Mesh:
    """A ("data", "model") mesh over the first ``data*model`` devices.

    Unlike :func:`make_production_mesh` this does not assume the full pod —
    serving replicas are sized to traffic, and CI builds e.g. an 8×1 mesh
    out of ``--xla_force_host_platform_device_count`` CPU devices (the
    dry-run trick; see :func:`host_device_flags`). Degenerate meshes
    (1×1) are valid and run the sharded code path on one device.
    """
    import numpy as np
    n = data * model
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < n:
        raise ValueError(
            f"mesh {data}x{model} needs {n} devices, have {len(devices)}; "
            f"on CPU, set XLA_FLAGS={host_device_flags(n)!r} before the "
            f"first jax use (launch/serve.py --mesh does this for you "
            f"under JAX_PLATFORMS=cpu)")
    return jax.sharding.Mesh(
        np.asarray(devices[:n]).reshape(data, model), ("data", "model"))


def host_device_flags(n: int) -> str:
    """The XLA flag that simulates ``n`` host devices on one CPU — the
    dry-run's 512-device trick, reused by the sharded serving tests and
    benchmarks. Must be in ``XLA_FLAGS`` *before* jax first initializes."""
    return f"--xla_force_host_platform_device_count={n}"


def n_chips(multi_pod: bool = False) -> int:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    n = 1
    for s in shape:
        n *= s
    return n
