"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never
touches jax device state): single pod = (data=16, model=16) = 256 chips;
multi-pod = (pod=2, data=16, model=16) = 512 chips. The dry-run launcher
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax import to materialize placeholder devices.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: the partitioner propagates
    shardings through the model code. (Left unspecified, axes are
    Explicit, and sharding-in-types rejects the embedding gathers and
    head reshapes the models do on sharded operands.)"""
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(shape: Tuple[int, ...] = (1, 1),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Mesh over the local devices: (1, 1) for tests and one chip,
    (1, 4) for the model-parallel evaluator on a four-chip host."""
    import jax
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        raise ValueError(f"need {n} devices, have {len(jax.devices())}")
    return _auto_mesh(shape, axes)


def mesh_from_devices(devices: Sequence, shape: Tuple[int, ...],
                      axes: Tuple[str, ...]):
    from jax.sharding import Mesh
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, axes)
