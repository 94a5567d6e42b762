"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (device count is locked at first jax init, and the
dry-run must set XLA_FLAGS before that happens).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_test_mesh"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, *, pod: int = 0):
    """Small mesh over however many (host) devices a test session has."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))
