"""Production mesh construction.

Functions, not module-level constants — importing this module never
touches jax device state (the dry-run sets its host-device XLA flag in
its ``main``, before any jax initialization).

Mesh semantics:
  pod   — federation node (ProFe gossip crosses this axis only)
  data  — in-node batch/FSDP parallelism
  model — in-node tensor parallelism

Every axis is ``Auto``: the round programs place their state with
``with_sharding_constraint`` specs, which ``jax.make_mesh``'s default
``Explicit`` axes refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU tests (axes exist, size 1)."""
    return make_mesh((1, 1), ("data", "model"))


def mesh_axis_names(mesh) -> tuple:
    return tuple(mesh.axis_names)


def dp_axes(mesh):
    """Axes the training batch shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
