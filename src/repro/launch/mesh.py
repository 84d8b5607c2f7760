"""Production mesh construction (lazy: importing this never touches jax
device state — required so smoke tests see 1 device while the dry-run sees
512 placeholder devices via XLA_FLAGS)."""
from __future__ import annotations

import jax

from ..kernels.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Whatever devices exist locally, as (data, model) for examples/tests."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return make_mesh((n // model_axis, model_axis), ("data", "model"))
