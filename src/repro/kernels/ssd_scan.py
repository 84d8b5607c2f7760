"""Mamba2 SSD intra-chunk kernel for TPU (Pallas).

Computes, per (batch, chunk, head) grid cell, the quadratic-within-chunk SSD
terms that dominate compute:

    y_diag[q, p]  = sum_{k<=q} C_q.B_k * exp(Acum_q - Acum_k) * dt_k * x[k, p]
    state[p, n]   = sum_k exp(Acum_Q - Acum_k) * dt_k * x[k, p] * B[k, n]

The chunk-decay matrix L = exp(segsum(a)) lives entirely in VMEM
([Q, Q] f32, 256 KB at Q=256) and both contractions are MXU matmuls
([Q,N]x[N,Q] and [Q,Q]x[Q,P]).  The cross-chunk recurrence (cheap,
O(chunks)) is composed around this kernel in ops.py with an associative
scan, exactly mirroring the pure-jnp oracle in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_ref, *,
                      q_len: int):
    # Blocks: x [Q, P]; dt, a [1, Q]; b, c [Q, N]; y [Q, P]; s [P, N].
    # Mosaic lowers neither cumsum nor a [Q] -> [Q, 1] relayout, so prefix
    # sums are masked lane reductions and row -> column moves go through
    # an identity mask; every value stays 2-D.
    x = x_ref[...].astype(jnp.float32)
    dt = dt_ref[...].astype(jnp.float32)        # [1, Q]
    a = a_ref[...].astype(jnp.float32)          # [1, Q]
    B = b_ref[...].astype(jnp.float32)          # [Q, N]
    C = c_ref[...].astype(jnp.float32)          # [Q, N]

    qi = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 1)
    causal = kj <= qi
    diag = kj == qi
    a_cum_col = jnp.sum(jnp.where(causal, a, 0.0), axis=1,
                        keepdims=True)                         # [Q, 1]
    a_cum_row = jnp.sum(jnp.where(diag, a_cum_col, 0.0), axis=0,
                        keepdims=True)                         # [1, Q]
    # L[q, k] = exp(a_cum[q] - a_cum[k]) for k <= q else 0.
    L = jnp.exp(jnp.where(causal, a_cum_col - a_cum_row, NEG_INF))

    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    M = CB * L * dt
    y_ref[...] = jax.lax.dot_general(
        M, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    a_total = jnp.sum(a, axis=1, keepdims=True)                # [1, 1]
    dt_col = jnp.sum(jnp.where(diag, dt, 0.0), axis=1, keepdims=True)
    xw = x * (jnp.exp(a_total - a_cum_col) * dt_col)           # [Q, P]
    s_ref[...] = jax.lax.dot_general(
        xw, B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(s_ref.dtype)


def ssd_chunk_kernel(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                     B: jnp.ndarray, C: jnp.ndarray, *,
                     interpret: bool = True):
    """Intra-chunk SSD terms.

    x: [G, Q, P]; dt, a: [G, Q]; B, C: [G, Q, N] where G = batch*chunks*heads
    flattened grid.  Returns (y_diag [G, Q, P] f32, states [G, P, N] f32).
    """
    G, Q, P = x.shape
    N = B.shape[-1]
    y, s = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, q_len=Q),
        grid=(G,),
        in_specs=[
            pl.BlockSpec((None, Q, P), lambda g: (g, 0, 0)),
            pl.BlockSpec((None, 1, Q), lambda g: (g, 0, 0)),
            pl.BlockSpec((None, 1, Q), lambda g: (g, 0, 0)),
            pl.BlockSpec((None, Q, N), lambda g: (g, 0, 0)),
            pl.BlockSpec((None, Q, N), lambda g: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, Q, P), lambda g: (g, 0, 0)),
            pl.BlockSpec((None, P, N), lambda g: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((G, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, dt[:, None, :], a[:, None, :], B, C)
    return y, s
