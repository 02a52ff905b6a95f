"""Mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state.
"""
from __future__ import annotations

import numpy as np

import jax


def make_mesh(shape, axes):
    """A mesh of the first ``prod(shape)`` devices, every axis Auto."""
    n = int(np.prod(shape))
    return jax.make_mesh(tuple(shape), tuple(axes), devices=jax.devices()[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
