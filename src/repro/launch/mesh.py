"""Mesh construction for tests.

``make_test_mesh`` builds a small mesh over the devices at hand; it is a
function so that importing this module never touches jax device state.  A
vNPU tenant's mesh comes from its placement instead: the routing-table
assignment becomes the Mesh device layout (core/vmesh.py).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
from jax.sharding import AxisType


def make_test_mesh(shape: Tuple[int, ...] = (2, 4),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh over however many devices the test environment has."""
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(axes))
