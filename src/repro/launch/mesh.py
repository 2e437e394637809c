"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS *before* any jax import).

Mesh geometry (TPU v5e pods):
  single-pod:  (data=16, model=16)            = 256 chips
  multi-pod:   (pod=2, data=16, model=16)     = 512 chips
The "pod" axis composes with "data" for batch/FSDP sharding, so cross-pod
traffic is exactly the data-parallel gradient reduction (DCI-friendly), while
"model" (TP/EP/SP) stays inside a pod's ICI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axes, so the sharding rules'
    ``with_sharding_constraint`` calls may name every axis (``jax.make_mesh``
    alone gives Explicit axes, which reject them)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math

    need = math.prod(shape)
    have = len(jax.devices())
    if have == need:
        return make_mesh(shape, axes)
    if have < need:
        raise RuntimeError(
            f"need {need} devices for mesh {dict(zip(axes, shape))}, have {have} "
            "(dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count "
            "before any jax import)"
        )
    # more devices than needed (e.g. 512 host devices, single-pod mesh): slice
    import numpy as np
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()[:need]).reshape(shape)
    return Mesh(devs, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many devices this host exposes (tests/examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))
