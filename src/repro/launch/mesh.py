"""Production mesh factories.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). Single pod: 16x16 = 256 chips ("data", "model"); multi-pod:
2x16x16 = 512 chips ("pod", "data", "model").
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with every axis `Auto` (the sharding-in-types default
    this repo's specs are written against)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    import numpy as np
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    found = jax.devices()
    if len(found) < n:
        raise RuntimeError(
            f"production mesh {dict(zip(axes, shape))} needs {n} devices; "
            f"found {len(found)} {found[0].platform} device(s)")
    return make_mesh(shape, axes, devices=found[:n])


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 1):
    """Small mesh over host-platform devices for smoke tests/examples."""
    shape = (pod, data, model) if pod > 1 else (data, model)
    axes = ("pod", "data", "model") if pod > 1 else ("data", "model")
    return make_mesh(shape, axes)


def make_single_device_mesh():
    return make_mesh((1, 1), ("data", "model"))
