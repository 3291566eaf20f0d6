"""Serving mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (a CPU run that forces host devices sets XLA_FLAGS
*before* any jax initialization; everything else sees the real devices).
"""
from __future__ import annotations

import jax


def make_serving_mesh(data: int = 1, model: int = 1,
                      devices=None) -> jax.sharding.Mesh:
    """A ("data", "model") mesh over the first ``data*model`` devices.

    Serving replicas are sized to traffic, and CI builds e.g. an 8×1 mesh
    out of ``--xla_force_host_platform_device_count`` CPU devices (see
    :func:`host_device_flags`). Degenerate meshes (1×1) are valid and run
    the sharded code path on one device.
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
    """The XLA flag that simulates ``n`` host devices on one CPU, used by
    the sharded serving tests and benchmarks. Must be in ``XLA_FLAGS``
    *before* jax first initializes."""
    return f"--xla_force_host_platform_device_count={n}"
