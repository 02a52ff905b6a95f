"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state.  Under the dry-run's 512 forced host devices the
single-pod mesh uses the first 256.
"""
from __future__ import annotations

import numpy as np

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512"
        )
    return _auto_mesh(shape, axes, devices[:n])


def make_mesh(shape, axes):
    """Generic helper for tests/benchmarks with small device counts."""
    n = int(np.prod(shape))
    return _auto_mesh(shape, axes, jax.devices()[:n])


def _auto_mesh(shape, axes, devices):
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
