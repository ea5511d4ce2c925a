"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
state. The production target is TPU v5e: 16x16 = 256 chips per pod,
2 pods = 512 chips multi-pod. On the CPU container the dry-run forces 512
host platform devices (see dryrun.py) before calling this.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return _mesh(shape, axes)


def make_host_mesh(model: Optional[int] = None) -> Mesh:
    """Small mesh over whatever devices exist (smoke tests / examples)."""
    n = jax.device_count()
    model = model or 1
    return _mesh((n // model, model), ("data", "model"))


def _mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devs)} — the "
            f"dry-run must set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count before any jax import")
    # Auto axes: the model code places activations with
    # with_sharding_constraint, which refers to Auto mesh axes only
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))
