"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; callers control when devices are initialized.
Single pod: (data=16, model=16) = 256 chips.  Multi-pod: (pod=2, data=16,
model=16) = 512 chips; the pod axis carries only DP-gradient/metric
traffic (DCN), never serving-path collectives.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def _mesh(dev, axes):
    return jax.sharding.Mesh(dev, axes,
                             axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            f"BEFORE importing jax (launch/dryrun.py does this)")
    dev = np.asarray(devices[:n]).reshape(shape)
    return _mesh(dev, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh from the first prod(shape) devices (tests)."""
    n = int(np.prod(shape))
    dev = np.asarray(jax.devices()[:n]).reshape(shape)
    return _mesh(dev, axes)
