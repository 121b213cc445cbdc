"""Mesh construction: every device mesh the repo builds starts here.

Functions (not module-level constants) so importing this module never
touches jax device state. The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to get placeholder devices; smoke tests and benches see 1 device.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto``: the engine's round cores
    shard through ``vmap(spmd_axis_name=...)``, ``with_sharding_constraint``
    and ``shard_map`` and leave propagation to GSPMD, which explicit-sharding
    axes (``jax.make_mesh``'s default) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU smoke runs of the same code paths."""
    return make_mesh((1, 1), ("data", "model"))
