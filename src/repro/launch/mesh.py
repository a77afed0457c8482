"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. The dry-run sets XLA_FLAGS before importing jax to
get 512 placeholder devices; real launches get devices from the Syndeo
runtime's gang allocation (one jax process per host, jax.distributed).

Every mesh axis is `Auto`: the model code places activations with
`with_sharding_constraint` (sharding/axes.py), which only Auto axes accept.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests use small virtual meshes, e.g. (2, 4))."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def dp_degree(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("data", 1) * sizes.get("pod", 1)
