"""Mesh construction.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
Every mesh in the repo is built through ``make_mesh``: ``jax.make_mesh``
defaults to ``Explicit`` axis types, while the sharding rules and the
engine's shard_map regions are written for ``Auto`` (GSPMD-propagated)
axes.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_serve_mesh():
    """The serving mesh over the devices this process sees: one data row,
    the model axis as wide as the device count (tensor-parallel decode)."""
    return make_mesh((1, len(jax.devices())), ("data", "model"))
