"""Fused RMSNorm kernel for TPU (Pallas).

Bandwidth-bound: one pass over [block_rows, D] tiles in VMEM, f32 reduction,
fused scale multiply.  Saves the extra HBM round-trips of the unfused
mean-square / rsqrt / multiply chain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_kernel(x: jnp.ndarray, w: jnp.ndarray, *, eps: float = 1e-6,
                   block_rows: int = 256, interpret: bool = True):
    """x: [T, D]; w: [D] -> [T, D]."""
    T, D = x.shape
    block_rows = min(block_rows, T)
    assert T % block_rows == 0, (T, block_rows)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(T // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda t: (t, 0)),
            pl.BlockSpec((D,), lambda t: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((T, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w)
