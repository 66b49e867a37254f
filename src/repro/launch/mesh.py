"""Production mesh construction.

``make_production_mesh`` is the fixed entry point the multi-pod dry-run
compiles against: 16x16 = 256 chips per pod (single-pod), 2x16x16 = 512
chips multi-pod.  Defined as a function so importing this module never
touches jax device state.

``make_tenant_mesh`` is the vNPU path: the hypervisor's topology mapper
picks the physical cores and the routing-table assignment becomes the
Mesh device layout (core/vmesh.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under launch/dryrun.py (it forces 512 host devices)")
    return _make_mesh(shape, axes, devices[:n])


def make_test_mesh(shape: Tuple[int, ...] = (2, 4),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh over however many devices the test environment has."""
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return _make_mesh(shape, axes, devices[:n])
