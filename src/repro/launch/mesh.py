"""Production mesh construction.

Axes contract (matches DESIGN.md §3 and the sharding rules):

* single-pod: ``(data=16, model=16)`` — 256 chips (one v5e pod slice);
* multi-pod : ``(pod=2, data=16, model=16)`` — 512 chips across 2 pods;
  the ``pod`` axis is OUTERMOST so cross-pod collectives (gradient
  all-reduce) ride the inter-pod links while ``data``/``model`` stay on
  in-pod ICI.

Functions, not module-level constants: importing this module never
touches jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE any jax
import; see dryrun.py).
"""
from __future__ import annotations

import jax


def _mesh_kwargs(n_axes: int) -> dict:
    """Every axis Auto: the sharding rules let XLA propagate layouts."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/examples (e.g. (2, 4) on 8 CPU devices)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), **_mesh_kwargs(len(axes))
    )


def mesh_chip_count(mesh) -> int:
    import numpy as np

    return int(np.prod(mesh.devices.shape))
