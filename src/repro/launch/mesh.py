"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run process sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax import
(see dryrun.py); tests and benches see the real single device.
"""

from __future__ import annotations

import math

import numpy as np

import jax
from jax.sharding import Mesh


def make_mesh(shape, axes) -> Mesh:
    """Mesh over the first prod(shape) available devices (the dry-run env
    exposes 512 host devices; the single-pod mesh uses the first 256)."""
    shape = tuple(int(s) for s in shape)
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax (dryrun.py does this)")
    arr = np.asarray(devs[:n]).reshape(shape)
    return Mesh(arr, tuple(axes),
                axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) = ("data", "model") — 256 chips.
    Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips.
    The "pod" axis extends to N pods unchanged (data-parallel across pods;
    ICI within a pod, DCN across)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """Mesh axes that carry batch/data parallelism."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
