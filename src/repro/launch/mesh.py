"""Production mesh construction.

Single pod: (16, 16) = ("data", "model") — 256 chips.
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips; "pod" is the
DCN axis (data-parallel across slices), "data"/"model" are ICI axes.

Functions, not module-level constants: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
Every mesh in the repo — production, tests, examples, benchmarks — goes
through ``make_mesh``, so all of them have Auto axis types.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (host) devices exist — smoke tests."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
