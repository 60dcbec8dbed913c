"""Production meshes.  Functions, not module constants — importing this
module must never touch jax device state (the dry-run sets
XLA_FLAGS before any jax initialization)."""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: the model code places
    activations with ``with_sharding_constraint``, which an Explicit
    axis (the ``make_mesh`` default in JAX 0.9) refuses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips with a 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape: Tuple[int, ...] = (2, 2),
                    axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh for subprocess tests (XLA_FLAGS host device count)."""
    return _auto_mesh(shape, axes)


def data_axes(multi_pod: bool) -> Tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)
