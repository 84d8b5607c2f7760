"""ShapeDtypeStruct stand-ins for every model input (no device allocation).

``input_specs(cfg, shape)`` returns the data arguments of the lowered step
for one (architecture x input-shape) cell; ``empty_caches`` builds the
decode-cache pytree (shapes only under ``jax.eval_shape``).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs.shapes import ShapeSpec
from ..models.base import ModelConfig
from ..models import transformer, encdec
from ..models.layers import KVCache

S = jax.ShapeDtypeStruct

DECODE_MARGIN = 128  # cache headroom beyond the prefilled seq_len


def batch_specs(cfg: ModelConfig, shape: ShapeSpec,
                microbatches: int = 1) -> Dict[str, Any]:
    """Training / prefill batch: token ids (+ stub modality embeddings)."""
    B = shape.global_batch
    seq = shape.seq_len
    text = seq - (cfg.n_img_tokens if cfg.n_img_tokens else 0)
    out = {"inputs": S((B, text), jnp.int32),
           "targets": S((B, text), jnp.int32)}
    if cfg.n_img_tokens > 0:
        out["img_embeds"] = S((B, cfg.n_img_tokens, cfg.d_model), jnp.float32)
    if cfg.is_encoder_decoder:
        out["enc_embeds"] = S((B, cfg.enc_frames, cfg.d_model), jnp.float32)
    return out


def token_specs(shape: ShapeSpec) -> Any:
    return S((shape.global_batch,), jnp.int32)


def empty_caches(cfg: ModelConfig, batch: int, s_max: int):
    """Decode-cache pytree with concrete zeros (use under eval_shape for
    the dry-run; materialized only by real serving)."""
    if cfg.is_encoder_decoder:
        L = cfg.n_layers
        kv = KVCache(
            k=jnp.zeros((L, batch, cfg.n_kv_heads, s_max, cfg.d_head), cfg.dtype),
            v=jnp.zeros((L, batch, cfg.n_kv_heads, s_max, cfg.d_head), cfg.dtype),
            length=jnp.full((L,), 0, jnp.int32))
        cross = jnp.zeros((L, batch, cfg.enc_frames, cfg.n_kv_heads,
                           cfg.d_head), cfg.dtype)
        return encdec.EncDecCaches(self_kv=kv, cross_k=cross, cross_v=cross)
    one = transformer._empty_caches(cfg, batch, s_max)
    nb = cfg.n_blocks

    def stack(x):
        return jnp.zeros((nb,) + x.shape, x.dtype)

    return jax.tree.map(stack, one)


def cache_specs_shapes(cfg: ModelConfig, shape: ShapeSpec):
    """ShapeDtypeStruct pytree of the decode caches for a shape cell."""
    s_max = shape.seq_len + DECODE_MARGIN
    return jax.eval_shape(
        lambda: empty_caches(cfg, shape.global_batch, s_max))


def state_shapes(cfg: ModelConfig, optimizer=None):
    """(params, specs, opt_state) shapes via eval_shape — no allocation.

    The logical-axes specs are static metadata built during tracing; we
    capture them through a closure (they are not jax types)."""
    from ..models import api
    holder = {}

    def build(k):
        p, s = api.init(cfg, k)
        holder["specs"] = s
        return p

    params = jax.eval_shape(build, S((2,), jnp.uint32))
    opt = None
    if optimizer is not None:
        opt = jax.eval_shape(optimizer.init, params)
    return params, holder["specs"], opt
