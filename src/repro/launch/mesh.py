"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes ``(data, model)``.
Multi-pod:  2 x 16 x 16 = 512 chips, axes ``(pod, data, model)`` — the
``pod`` axis composes with ``data`` for gradient reduction (reduce-scatter
within pod over ICI, cross-pod all-reduce over DCN), expressed to GSPMD by
sharding the batch over ``('pod', 'data')``.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(devices: np.ndarray, axes) -> Mesh:
    return Mesh(devices, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)}. "
            "Run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py sets this) or on real hardware.")
    return _make_mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_host_mesh() -> Mesh:
    """1x1 mesh over the local device — used by smoke tests and examples."""
    return _make_mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                      ("data", "model"))
