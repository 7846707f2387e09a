"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests see 1 device; only dryrun.py
sets the 512-placeholder-device XLA flag before first jax init.

Every constructor validates the requested axis sizes against the devices
that actually exist and raises the typed ``MeshDeviceError`` — the raw
``jax.sharding.Mesh`` failure ("len(devices) != prod(shape)" deep inside
jax internals) told the operator nothing about which flag to fix.
"""
from __future__ import annotations

import math

import jax
import numpy as np


class MeshDeviceError(ValueError):
    """Requested mesh axis sizes exceed (or do not tile) the device count."""


def _validate_axes(shape, axes) -> None:
    need = math.prod(shape)
    have = len(jax.devices())
    if need > have:
        raise MeshDeviceError(
            f"mesh {dict(zip(axes, shape))} needs {need} devices but only "
            f"{have} are available; on CPU, emulate more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"(must be set before jax initializes)")


def _make_mesh(shape, axes):
    _validate_axes(shape, axes)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with multi_pod=True."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_elastic_mesh(n_devices: int, model_parallel: int = 16):
    """Re-mesh after node loss: whatever devices remain, same model axis.

    Used by the elastic-restore path: a 512-chip checkpoint restores onto
    e.g. 256 chips by rebuilding (data', model) and re-sharding.
    """
    if n_devices % model_parallel:
        raise MeshDeviceError(
            f"elastic mesh: n_devices={n_devices} is not a multiple of "
            f"model_parallel={model_parallel}")
    return _make_mesh((n_devices // model_parallel, model_parallel),
                      ("data", "model"))


def make_tp_mesh(tp: int):
    """One-axis ("tp",) mesh over the first ``tp`` devices — the serving
    tensor-parallel mesh (dist/tp.py).  Unlike ``jax.make_mesh`` this may
    use a SUBSET of the devices, so tp=1..N all coexist in one process
    (the equivalence smokes compare tp shardings inside a single 8-device
    emulated-CPU run)."""
    if tp < 1:
        raise MeshDeviceError(f"tp must be >= 1, got {tp}")
    _validate_axes((tp,), ("tp",))
    return jax.sharding.Mesh(np.array(jax.devices()[:tp]), ("tp",))


def mesh_axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1
