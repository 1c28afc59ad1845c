"""Production mesh construction.

Target: TPU v5e pods of 256 chips, arranged (data=16, model=16) per pod;
multi-pod adds a leading pure-DP ``pod`` axis (2 pods = 512 chips for the
dry-run; the axis generalizes to N pods).  Axis roles are documented in
runtime/sharding.py.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""

from __future__ import annotations

import jax

from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) 'data','model' per pod; (2, 16, 16) with a 'pod' DP axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} exceeds {n} devices")
    return _make_mesh((data, model), ("data", "model"))
