"""Decoder-only LM assembly: dense / MoE / SSM / hybrid / VLM families.

Layers are grouped into repeating *blocks* (the config's ``layer_pattern``
period — 1 for homogeneous stacks, 8 for Jamba's 7-Mamba+1-attention
interleave) and the block stack runs under ``lax.scan`` over stacked
parameters so HLO size is O(1) in depth (MaxText-style), with optional
``jax.checkpoint`` remat per block.

Three entry points per model: ``forward`` (training), ``prefill`` (build
decode caches), ``decode_step`` (single token with caches).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .base import ModelConfig, ParamBuilder, stacked_specs, with_logical
from . import layers as L
from .layers import KVCache
from .moe import init_moe, moe_ffn
from .scopes import ATTN, EMBED, FFN, LAYER_SCAN, SSM, UNEMBED, scope
from .ssd import SSMCache, init_ssm, ssm_layer, ssm_prefill, ssm_decode, ssm_dims


def _layer_is_moe(cfg: ModelConfig, global_idx: int) -> bool:
    if cfg.n_experts <= 0:
        return False
    return global_idx % cfg.moe_every == (cfg.moe_every - 1)


def _has_ffn(cfg: ModelConfig) -> bool:
    return cfg.d_ff > 0 or cfg.n_experts > 0


# --------------------------------------------------------------------- init
def init_block(b: ParamBuilder, cfg: ModelConfig):
    """One block's parameters.  Every block has the same structure (the
    stack is scanned), so a layer's MoE-ness follows its position ``pos``
    in the pattern, as in ``_layer_forward``."""
    for pos, kind in enumerate(cfg.pattern):
        lb = b.child(f"l{pos}")
        lb.ones("ln1", (cfg.d_model,), (None,))
        if kind == "attn":
            L.init_attn(lb, cfg)
        else:
            init_ssm(lb, cfg)
        if _has_ffn(cfg):
            lb.ones("ln2", (cfg.d_model,), (None,))
            if _layer_is_moe(cfg, pos):
                init_moe(lb, cfg)
            else:
                L.init_mlp(lb, cfg)


def init_lm(cfg: ModelConfig, key: jax.Array):
    """Returns (params, logical-axis specs)."""
    b = ParamBuilder(key, cfg.param_dtype)
    init_embed(b, cfg)
    if cfg.n_img_tokens > 0:
        b.normal("mm_proj", (cfg.d_model, cfg.d_model), ("embed", None),
                 fan_in=cfg.d_model)
    bspecs = {}

    def one_block(k):
        bb = ParamBuilder(k, cfg.param_dtype)
        init_block(bb, cfg)
        bspecs.update(bb.specs)
        return bb.params

    # One block traced and vmapped over the per-block keys: the values equal
    # a loop over blocks, but the program no longer grows with depth (24
    # unrolled granite blocks took over two minutes to compile for a TPU).
    keys = jnp.stack([jax.random.fold_in(key, i + 1)
                      for i in range(cfg.n_blocks)])
    params, specs = b.done()
    params["blocks"] = jax.vmap(one_block)(keys)
    specs["blocks"] = stacked_specs(bspecs)
    return params, specs


def init_embed(b: ParamBuilder, cfg: ModelConfig):
    L.init_embed(b, cfg)


# ------------------------------------------------------------------ forward
def _layer_forward(cfg: ModelConfig, kind: str, pos: int, p, x):
    """One layer (mixer + FFN).  Returns (x, aux_loss).

    Remat is applied at THIS granularity: block-level remat would keep
    every layer's gathered weights of a heterogeneous block (Jamba: 8
    layers, 4 of them MoE) alive simultaneously during the recompute."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    if kind == "attn":
        h = L.attention(p["attn"], cfg, h, causal=True,
                        window=cfg.sliding_window)
    else:
        h = ssm_layer(p["ssm"], cfg, h)
    x = x + h
    if _has_ffn(cfg):
        h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if _layer_is_moe(cfg, pos):
            h, a = moe_ffn(p["moe"], cfg, h)
            aux = aux + a
        else:
            h = L.mlp(p["mlp"], h, n_chunks=cfg.ffn_chunks)
        x = x + h
    x = with_logical(x, ("batch", "seq", "embed"))
    return x, aux


def _block_forward(cfg: ModelConfig, bp,
                   x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One block (cfg.pattern), full sequence.  Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    for pos, kind in enumerate(cfg.pattern):
        p = bp[f"l{pos}"]
        f = functools.partial(_layer_forward, cfg, kind, pos)
        if cfg.remat:
            f = jax.checkpoint(
                f, policy=jax.checkpoint_policies.nothing_saveable)
        x, a = f(p, x)
        aux = aux + a
    return x, aux


def run_blocks(cfg: ModelConfig, params, x: jnp.ndarray):
    """Scan the block stack.  Returns (x, total_aux_loss)."""
    block_fn = functools.partial(_block_forward, cfg)
    if cfg.scan_layers and cfg.n_blocks > 1:
        def step(carry, bp):
            x, aux = carry
            x, a = block_fn(bp, x)
            return (x, aux + a), None
        (x, aux), _ = lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    else:
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.n_blocks):
            bp = jax.tree.map(lambda v: v[i], params["blocks"])
            x, a = block_fn(bp, x)
            aux = aux + a
    return x, aux


def forward(cfg: ModelConfig, params, tokens: jnp.ndarray,
            img_embeds: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: [B, S_text] -> logits [B, S_total, V].  VLM prepends image."""
    x = L.embed(params, cfg, tokens)
    if cfg.n_img_tokens > 0:
        assert img_embeds is not None
        img = jnp.einsum("bnd,de->bne", img_embeds.astype(cfg.dtype),
                         params["mm_proj"].astype(cfg.dtype))
        x = jnp.concatenate([img, x], axis=1)
    x, aux = run_blocks(cfg, params, x)
    return L.unembed(params, cfg, x), aux


def loss_fn(cfg: ModelConfig, params, batch: Dict[str, jnp.ndarray]):
    """Next-token cross-entropy.  batch: inputs [B,S], targets [B,S]."""
    logits, aux = forward(cfg, params, batch["inputs"],
                          img_embeds=batch.get("img_embeds"))
    if cfg.n_img_tokens > 0:
        logits = logits[:, cfg.n_img_tokens:]
    logits = logits.astype(jnp.float32)
    targets = batch["targets"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(targets, jnp.float32))
    nll = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = nll + 0.01 * aux
    return loss, {"nll": nll, "aux": aux,
                  "tokens": jnp.sum(mask)}


# ------------------------------------------------------------------- decode
def _empty_caches(cfg: ModelConfig, batch: int, s_max: int):
    """Per-block cache pytree (stacked over blocks by the caller)."""
    caches = {}
    d_inner, H, P, N = (ssm_dims(cfg) if any(k != "attn" for k in cfg.pattern)
                        else (0, 0, 0, 0))
    for pos, kind in enumerate(cfg.pattern):
        if kind == "attn":
            kv = KVCache(
                k=jnp.zeros((batch, cfg.n_kv_heads, s_max, cfg.d_head), cfg.dtype),
                v=jnp.zeros((batch, cfg.n_kv_heads, s_max, cfg.d_head), cfg.dtype),
                length=jnp.zeros((), jnp.int32))
            caches[f"l{pos}"] = kv
        else:
            conv_ch = d_inner + 2 * N
            caches[f"l{pos}"] = SSMCache(
                conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), cfg.dtype),
                state=jnp.zeros((batch, H, P, N), jnp.float32))
    return caches


def _block_prefill(cfg: ModelConfig, bp, x, s_max: int):
    caches = {}
    for pos, kind in enumerate(cfg.pattern):
        p = bp[f"l{pos}"]
        with scope(ATTN if kind == "attn" else SSM):
            h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
            if kind == "attn":
                h, c = L.attention_prefill(p["attn"], cfg, h, s_max,
                                           window=cfg.sliding_window)
            else:
                h, c = ssm_prefill(p["ssm"], cfg, h)
            caches[f"l{pos}"] = c
            x = x + h
        if _has_ffn(cfg):
            with scope(FFN):
                h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
                if _layer_is_moe(cfg, pos):
                    h, _ = moe_ffn(p["moe"], cfg, h)
                else:
                    h = L.mlp(p["mlp"], h, n_chunks=cfg.ffn_chunks)
                x = x + h
    return x, caches


def _block_decode(cfg: ModelConfig, bp, x, caches, i):
    """Block ``i`` of the stacked caches: attention writes its token into
    the stack in place; an SSM layer replaces its own state at ``i``."""
    caches = dict(caches)
    for pos, kind in enumerate(cfg.pattern):
        p = bp[f"l{pos}"]
        c = caches[f"l{pos}"]
        with scope(ATTN if kind == "attn" else SSM):
            h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
            if kind == "attn":
                h, c = L.attention_decode_at(p["attn"], cfg, h, c, i,
                                             window=cfg.sliding_window)
            else:
                h, new = ssm_decode(p["ssm"], cfg, h, jax.tree.map(
                    lambda v: lax.dynamic_index_in_dim(v, i, keepdims=False),
                    c))
                c = jax.tree.map(
                    lambda v, n: lax.dynamic_update_index_in_dim(v, n, i, 0),
                    c, new)
            caches[f"l{pos}"] = c
            x = x + h
        if _has_ffn(cfg):
            with scope(FFN):
                h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
                if _layer_is_moe(cfg, pos):
                    h, _ = moe_ffn(p["moe"], cfg, h)
                else:
                    h = L.mlp(p["mlp"], h, n_chunks=cfg.ffn_chunks)
                x = x + h
    return x, caches


def prefill(cfg: ModelConfig, params, tokens: jnp.ndarray, s_max: int,
            img_embeds: Optional[jnp.ndarray] = None):
    """Returns (last-token logits [B,V], stacked caches)."""
    with scope(EMBED):
        x = L.embed(params, cfg, tokens)
        if cfg.n_img_tokens > 0:
            img = jnp.einsum("bnd,de->bne", img_embeds.astype(cfg.dtype),
                             params["mm_proj"].astype(cfg.dtype))
            x = jnp.concatenate([img, x], axis=1)

    def step(x, bp):
        x, caches = _block_prefill(cfg, bp, x, s_max)
        return x, caches

    with scope(LAYER_SCAN):
        if cfg.scan_layers and cfg.n_blocks > 1:
            x, caches = lax.scan(step, x, params["blocks"])
        else:
            cl = []
            for i in range(cfg.n_blocks):
                bp = jax.tree.map(lambda v: v[i], params["blocks"])
                x, c = step(x, bp)
                cl.append(c)
            caches = jax.tree.map(lambda *xs: jnp.stack(xs), *cl)
    with scope(UNEMBED):
        logits = L.unembed(params, cfg, x[:, -1:])[:, 0]
    return logits, caches


def decode_step(cfg: ModelConfig, params, token: jnp.ndarray, caches):
    """token: [B] -> (logits [B,V], new caches).  The caches, stacked over
    blocks, are carried through the blocks and written in place: block
    ``i`` adds one token per attention layer at ``[i, :, :, length[i]]``,
    so a donated cache comes back in the same buffers."""
    with scope(EMBED):
        x = L.embed(params, cfg, token[:, None])

    with scope(LAYER_SCAN):
        if cfg.scan_layers and cfg.n_blocks > 1:
            def step(carry, bi):
                bp, i = bi
                return _block_decode(cfg, bp, *carry, i), None

            (x, caches), _ = lax.scan(
                step, (x, caches),
                (params["blocks"], jnp.arange(cfg.n_blocks)))
        else:
            for i in range(cfg.n_blocks):
                bp = jax.tree.map(lambda v: v[i], params["blocks"])
                x, caches = _block_decode(cfg, bp, x, caches, i)
    with scope(UNEMBED):
        logits = L.unembed(params, cfg, x)[:, 0]
    return logits, caches
