"""Flash attention forward kernel for TPU (Pallas, online-softmax).

Tiling: grid = (batch*q_heads, Sq/block_q, Sk/block_k); the k dimension is the
innermost (sequential) grid axis so the output block is revisited
consecutively while running max/sum/accumulator live in VMEM scratch.
Block sizes default to 128x128 — MXU-aligned on both matmul dims, and the
VMEM working set (q, k, v tiles + f32 accumulator) stays ~<2 MB.

GQA is handled in the index map: kv block index = q_head // group, so K/V are
never materialized per-q-head.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 sm_scale: float, causal: bool, block_q: int, block_k: int,
                 nk: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal: skip blocks entirely above the diagonal.
    run = True
    if causal:
        run = (j * block_k) <= (i * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[...].astype(jnp.float32)            # [Bq, Dh]
        k = k_ref[...].astype(jnp.float32)            # [Bk, Dh]
        v = v_ref[...].astype(jnp.float32)            # [Bk, Dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s *= sm_scale
        if causal:
            qi = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kj = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kj <= qi, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, sm_scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True) -> jnp.ndarray:
    """q: [B, Sq, H, Dh]; k, v: [B, Sk, KV, Dh] -> [B, Sq, H, Dh]."""
    B, Sq, H, Dh = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0
    group = H // KV
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(Dh)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, Dh)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, Dh)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, Dh)

    def kv_index(b, i, j):
        return (b // H) * KV + (b % H) // group, j, 0

    out = pl.pallas_call(
        functools.partial(_attn_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, Dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, Dh), kv_index),
            pl.BlockSpec((None, block_k, Dh), kv_index),
        ],
        out_specs=pl.BlockSpec((None, block_q, Dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, Sq, Dh).transpose(0, 2, 1, 3)
