"""Elastic scaling: restart on a different device count/mesh.

Shardings are logical rules resolved at record time; recordings embed the
mesh fingerprint.  On a topology change (node failure, scale-up):

  1. pick the new mesh from the surviving device count,
  2. restore the checkpoint (logical arrays) and device_put with the new
     mesh's shardings,
  3. re-record (re-compile) the step for the new mesh — the CODY recorder
     caches recordings per (workload, shape, mesh) fingerprint so repeated
     failovers to a known topology skip compilation entirely.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.sharding import rules_for, shardings_for


def choose_mesh_shape(n_devices: int, prefer_model: int = 16) -> Tuple[int, int]:
    """Largest (data, model) grid for the surviving devices; model axis
    capped at prefer_model and must divide n_devices."""
    model = min(prefer_model, n_devices)
    while n_devices % model:
        model -= 1
    return (n_devices // model, model)


def make_elastic_mesh(n_devices: Optional[int] = None, prefer_model: int = 16):
    devs = jax.devices()[:n_devices] if n_devices else jax.devices()
    shape = choose_mesh_shape(len(devs), prefer_model)
    import numpy as _np
    return jax.sharding.Mesh(
        _np.asarray(devs).reshape(shape), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def reshard_state(state_np, axes_tree, mesh, mode: str = "train"):
    """device_put a restored (numpy) state onto a new mesh."""
    rules = rules_for(mode, mesh.axis_names)
    sh = shardings_for(axes_tree, state_np, mesh, rules)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), state_np, sh)
