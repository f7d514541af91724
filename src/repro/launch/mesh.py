"""Mesh construction. Functions only — importing this module never
touches jax device state (device count locks on first jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The target deployment mesh.

      single-pod : (data=16, model=16)         — 256 chips (one v5e pod)
      multi-pod  : (pod=2, data=16, model=16)  — 512 chips over DCN

    'pod' is pure data parallelism (gradient all-reduce over DCN),
    'data' is FSDP, 'model' is tensor/expert parallelism (ICI).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    return _mk(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / laptop runs)."""
    n = jax.device_count()
    model = max(1, min(model, n))
    data = n // model
    return make_mesh((data, model), ("data", "model"))
