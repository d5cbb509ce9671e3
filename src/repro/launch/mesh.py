"""Production meshes. Function, not module constant — importing this
module must never touch jax device state (the dry-run sets its
XLA_FLAGS before first jax init)."""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """The one place a mesh is built. Every axis is ``Auto``: the
    sharding rules (distributed/sharding.py) place arrays, and XLA
    propagates the rest — the contract the model code is written to."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_elastic_mesh(n_devices: int | None = None, model_parallel: int = 16):
    """Re-form a (data, model) mesh from the first ``n_devices`` devices
    (default: all that survive) — the elastic-restart path (checkpoints
    are mesh-agnostic)."""
    devices = jax.devices()[:n_devices] if n_devices else jax.devices()
    n = len(devices)
    model = math.gcd(n, model_parallel)
    return make_mesh((n // model, model), ("data", "model"), devices=devices)


def mesh_name(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)
