"""Grouped (ragged) matmul kernel for MoE expert GEMM on TPU (Pallas).

``lhs`` rows are sorted by expert; ``group_offsets`` (scalar-prefetched into
SMEM) give each expert's [start, end) row range; ``rhs`` holds one weight
matrix per expert.  Grid = (T/block_t, F/block_f, E) with the expert axis
innermost so each output tile accumulates over the (few) experts that
overlap it; non-overlapping experts are skipped with ``pl.when``.

This is the megablocks-style gmm adapted to the MXU: block_t x block_f output
tiles (128-aligned), full-depth K panels resident in VMEM (fine up to
d_model ~8k in f32; larger models use bf16 operands).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(offs_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                block_t: int, n_experts: int):
    t = pl.program_id(0)
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = offs_ref[e]
    end = offs_ref[e + 1]
    row0 = t * block_t
    overlap = jnp.logical_and(end > row0, start < row0 + block_t)

    @pl.when(overlap)
    def _body():
        rows = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_t, 1), 0)
        mask = jnp.logical_and(rows >= start, rows < end)
        lhs = jnp.where(mask, lhs_ref[...].astype(jnp.float32), 0.0)
        acc_ref[...] += jax.lax.dot_general(
            lhs, rhs_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(e == n_experts - 1)
    def _finish():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def grouped_matmul_kernel(lhs: jnp.ndarray, rhs: jnp.ndarray,
                          group_offsets: jnp.ndarray, *,
                          block_t: int = 128, block_f: int = 128,
                          interpret: bool = True) -> jnp.ndarray:
    """lhs: [T, D] (rows sorted by expert), rhs: [E, D, F],
    group_offsets: [E+1] int32 -> out [T, F]."""
    T, D = lhs.shape
    E, _, F = rhs.shape
    block_t = min(block_t, T)
    block_f = min(block_f, F)
    assert T % block_t == 0 and F % block_f == 0, (T, F, block_t, block_f)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T // block_t, F // block_f, E),
        in_specs=[
            pl.BlockSpec((block_t, D), lambda t, f, e, offs: (t, 0)),
            pl.BlockSpec((None, D, block_f), lambda t, f, e, offs: (e, 0, f)),
        ],
        out_specs=pl.BlockSpec((block_t, block_f),
                               lambda t, f, e, offs: (t, f)),
        scratch_shapes=[pltpu.VMEM((block_t, block_f), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, block_t=block_t, n_experts=E),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, F), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(group_offsets.astype(jnp.int32), lhs, rhs)
