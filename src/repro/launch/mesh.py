"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state. The single-pod mesh is 16x16 = 256 chips
(TPU v5e pod); multi-pod adds a leading 2-pod axis (512 chips).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the model code places
    arrays with sharding constraints and lets the compiler propagate them,
    which the default ``Explicit`` axes reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(model: int = 1, n_devices=None):
    """Mesh over the first ``n_devices`` local devices (default: all)."""
    devices = jax.devices()[:n_devices]
    n = len(devices)
    model = min(model, n)
    return make_auto_mesh((n // model, model), ("data", "model"), devices=devices)
