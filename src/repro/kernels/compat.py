"""Mesh construction pinned to the installed jax (>= 0.9).

``jax.make_mesh`` builds Explicit axes by default since jax 0.7, and
``with_sharding_constraint`` (the logical-axis rules in
``repro.models.base``) only accepts Auto axes.  Every mesh in the repo is
built here so that choice lives in one place.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh"]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              devices=None):
    """``jax.make_mesh`` with every axis Auto (GSPMD-propagated)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)
