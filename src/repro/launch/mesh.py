"""Production mesh construction.

A FUNCTION (never a module-level constant) so importing this module never
touches jax device state.  The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to materialize the placeholder devices.
"""
from __future__ import annotations

import warnings

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; the multi-pod mesh is 2 pods = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """A mesh over whatever devices actually exist (examples / tests).

    ``model_parallel`` that does not divide the device count cannot
    factor an ``(n // mp, mp)`` mesh; it is rounded DOWN to the largest
    divisor of ``n`` (with a warning) instead of crashing
    ``jax.make_mesh``."""
    n = len(jax.devices())
    mp = max(1, min(int(model_parallel), n))
    while n % mp:
        mp -= 1
    if mp != model_parallel:
        warnings.warn(
            f"model_parallel={model_parallel} does not factor the "
            f"{n}-device host platform; rounding down to "
            f"model_parallel={mp}", stacklevel=2)
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
