"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is the outer data/FSDP axis (the paper's scheduler-group level:
one scheduler per pod, workers = mesh slices; DESIGN.md §2).

Defined as functions so importing this module never touches JAX device
state (device count is locked at first use).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_test_mesh"]


def _mesh(shape, axis_names):
    return jax.make_mesh(shape, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 1):
    """Small mesh over however many (host) devices the process has."""
    if pod > 1:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
