"""Mesh construction: the one place this repo builds a ``jax.sharding.Mesh``.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — required because the dry-run forces 512 host
devices before first jax init, while tests/benches run on 1 CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """A mesh with ``Auto`` axes: GSPMD propagates shardings through
    reshapes and ``with_sharding_constraint`` (``jax.make_mesh`` defaults
    to ``Explicit`` axes, under which both need explicit out-shardings)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4, pod: int | None = None):
    """Small host-device meshes for subprocess tests."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
