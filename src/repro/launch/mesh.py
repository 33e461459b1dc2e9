"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.  Single pod =
16x16 = 256 chips (v5e pod); multi-pod = 2 pods = 512 chips with a leading
'pod' axis (data-parallel across the DCI).
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axis types: shardings come from the
    logical-axis rules and XLA propagation, not from explicit types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
