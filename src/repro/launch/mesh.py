"""Production meshes.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the ``pod``
axis composes with ``data`` for batch/context sharding; ``model`` stays
intra-pod so tensor-parallel collectives never cross the slower inter-pod
links, and parameters are replicated across pods (gradient all-reduce is
the only cross-pod collective).

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets the forced host-device count first).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple:
    """The batch/context sharding axes for this mesh."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def n_chips(mesh) -> int:
    return int(mesh.devices.size)
