"""Production meshes (assignment: 16x16 single-pod, 2x16x16 multi-pod).

`make_production_mesh` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — required because the
dry-run must set XLA_FLAGS before any backend initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh

SINGLE_POD = (16, 16)              # 256 chips (v5e pod)
MULTI_POD = (2, 16, 16)            # 2 pods = 512 chips


def _auto_axes(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            "the dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (see launch/dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devices[:need],
                         axis_types=_auto_axes(len(shape)))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Arbitrary mesh over a device prefix (smoke tests / examples)."""
    need = 1
    for s in shape:
        need *= s
    return jax.make_mesh(shape, axes, devices=jax.devices()[:need],
                         axis_types=_auto_axes(len(shape)))


def single_device_mesh() -> Mesh:
    return jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1], axis_types=_auto_axes(2))
