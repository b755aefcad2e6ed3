"""Production meshes.

Mesh topology (TPU v5e pods):
    single-pod : (data=16, model=16)                   = 256 chips
    multi-pod  : (pod=2, data=16, model=16)            = 512 chips

The `pod` axis maps to the cross-pod DCI domain and carries only gradient
reduction; `model` stays inside an ICI axis.  Defined as functions (never
module-level constants) so importing this module never touches jax device
state — the dry-run forces 512 host devices before first jax init.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> Mesh:
    """`jax.make_mesh` with every axis Auto-typed."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run via "
            "launch/dryrun.py (forces --xla_force_host_platform_device_count=512)")
    return make_mesh(shape, axes, devices=devices[:n])


def make_local_mesh() -> Mesh:
    """Whatever is available (CPU smoke tests: 1 device)."""
    devices = jax.devices()
    n = len(devices)
    # factor n into (data, model)
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0:
            model = m
            break
    return make_mesh((n // model, model), ("data", "model"),
                            devices=devices)


def mesh_chips(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
