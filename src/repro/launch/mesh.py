"""Production mesh construction (spec: single-pod 16x16, multi-pod 2x16x16).

A FUNCTION, not a module constant — importing this module never touches
jax device state (device count locks on first jax init; only dryrun.py
forces the 512-host-device XLA flag, and only in its own process).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Whatever devices exist right now (elastic launch path)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
