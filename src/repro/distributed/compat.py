"""Mesh construction for the SPMD code (DESIGN.md §6).

``jax.make_mesh`` gives every axis the ``Explicit`` type by default; the
``shard_map`` and GSPMD code here is written for ``Auto`` axes, so every
mesh is built through :func:`make_mesh`.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with every axis of type ``Auto``."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_shapes),
                         devices=devices)
