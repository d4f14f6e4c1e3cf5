"""Production meshes for the multi-pod dry-run.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state — the dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialisation, and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh_for", "auto_mesh"]


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the model places activations with
    ``with_sharding_constraint``, which JAX accepts only on Auto axes (the
    default became Explicit in JAX 0.9)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """(data=16, model=16) single pod, (pod=2, data=16, model=16) 512-chip."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh_for(n_devices: int, model_par: int = 1):
    """Small mesh helper for examples/tests on however many devices exist."""
    assert n_devices % model_par == 0
    return auto_mesh((n_devices // model_par, model_par), ("data", "model"))
