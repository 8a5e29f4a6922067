"""Production meshes.

Single pod: (16, 16) ("data", "model") = 256 chips (TPU v5e pod).
Multi-pod:  (2, 16, 16) ("pod", "data", "model") = 512 chips.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS *before* first jax
init and only then calls this.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int = None, n_model: int = 1,
                   axes=("data", "model")):
    """Small mesh over however many (host) devices exist — tests."""
    n = len(jax.devices())
    n_data = n_data or (n // n_model)
    return jax.make_mesh((n_data, n_model), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
