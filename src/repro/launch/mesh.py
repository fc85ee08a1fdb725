"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — required because the
dry-run pins the device count via XLA_FLAGS before any jax initialisation,
while smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """TPU v5e target: one 16x16 pod (256 chips), or 2 pods = 512 chips.

    Axes: ("data", "model") single pod; ("pod", "data", "model") multi-pod.
    The "pod" axis rides the slow inter-pod links (DCI/DCN); "data" and
    "model" ride intra-pod ICI — the hierarchy the paper's VM-leader
    collectives exploit (DESIGN.md §5).  All axes are Auto-typed, so
    placement follows the in/out shardings and sharding constraints.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))

