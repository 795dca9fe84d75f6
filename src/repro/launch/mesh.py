"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state.  Single-pod: 16x16 = 256 chips (one v5e pod
slice); multi-pod: 2x16x16 = 512 chips with a leading "pod" data axis.
Every axis is ``AxisType.Auto``: the models place activations with
``with_sharding_constraint`` and let GSPMD propagate the rest, which
``jax.make_mesh``'s default ``Explicit`` axes refuse.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py sets this before importing jax)")
    return jax.make_mesh(shape, axes, devices=devices[:need],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_mesh_for(n_devices: int, *, want_model: int = 0) -> Mesh:
    """Best-effort (data, model) mesh for an arbitrary device count.

    Used by the elastic runtime when a pod loses nodes: keep the model axis
    intact (TP groups must stay whole) and shrink the data axis.
    """
    devices = jax.devices()[:n_devices]
    model = want_model or min(16, n_devices)
    while n_devices % model:
        model //= 2
    data = n_devices // model
    return jax.make_mesh((data, model), ("data", "model"), devices=devices,
                         axis_types=(AxisType.Auto,) * 2)
