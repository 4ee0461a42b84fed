"""The one mesh factory: every mesh in the repo is built here.

Axes are ``AxisType.Auto``. Since JAX 0.9 ``jax.make_mesh`` defaults to
Explicit axes, under which a bare ``PartitionSpec`` in
``with_sharding_constraint`` is refused and slicing a sharded dim is not
implemented; the serving steps and the global KV pool are written for
GSPMD's Auto propagation. Enter a mesh context with ``jax.set_mesh``.

Functions, not module-level constants: importing this module never
touches jax device state (device count is locked at first jax init).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis Auto (GSPMD-propagated)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod 16x16 (data, model) or multi-pod 2x16x16."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
