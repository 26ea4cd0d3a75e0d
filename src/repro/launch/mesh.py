"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant)
so importing this module touches no jax device state.  The single-pod
production mesh is 16x16 = 256 chips (v5e pod); multi-pod prepends a
"pod" data-parallel axis (2 x 256 = 512 chips).  Axis types are Auto so
GSPMD propagates shardings through the model code.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests, laptop-scale runs)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (data=1, model=1)."""
    return make_mesh((1, 1), ("data", "model"))
