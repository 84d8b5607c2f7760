"""Core transformer layers: RMSNorm, RoPE, GQA attention, SwiGLU MLP.

Everything is a pure function of (cfg, params, inputs).  Attention supports
full training (causal / bidirectional), prefill (returns a KV cache) and
single-token decode (updates the cache in place functionally), with GQA,
optional per-head qk-norm (Qwen3), QKV bias (Qwen2) and sliding windows
(Jamba long-context).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .base import ModelConfig, ParamBuilder, with_logical, mesh_axis_size


# ----------------------------------------------------------------- RMSNorm
def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


# -------------------------------------------------------------------- RoPE
def rope_freqs(d_head: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, jnp.float32) / d_head))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: [..., S, H, Dh]; positions: [..., S] (broadcastable)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # [Dh/2]
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # [...,S,1,Dh/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------- Attention
class KVCache(NamedTuple):
    """One layer's cache, or (with a leading blocks axis on every leaf) the
    stack that ``transformer.decode_step`` updates in place.  Each kv head's
    ``[S_max, Dh]`` is contiguous, the order the decode dots read."""
    k: jnp.ndarray       # [B, KV, S_max, Dh]
    v: jnp.ndarray       # [B, KV, S_max, Dh]
    length: jnp.ndarray  # [] int32 current fill


def init_attn(b: ParamBuilder, cfg: ModelConfig, name: str = "attn",
              rope: bool = True):
    a = b.child(name)
    D, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    a.normal("wq", (D, H, Dh), ("embed", "heads", "head_dim"), fan_in=D)
    a.normal("wk", (D, KV, Dh), ("embed", "kv_heads", "head_dim"), fan_in=D)
    a.normal("wv", (D, KV, Dh), ("embed", "kv_heads", "head_dim"), fan_in=D)
    a.normal("wo", (H, Dh, D), ("heads", "head_dim", "embed"), fan_in=H * Dh)
    if cfg.qkv_bias:
        a.zeros("bq", (H, Dh), ("heads", "head_dim"))
        a.zeros("bk", (KV, Dh), ("kv_heads", "head_dim"))
        a.zeros("bv", (KV, Dh), ("kv_heads", "head_dim"))
    if cfg.qk_norm:
        a.ones("q_norm", (Dh,), (None,))
        a.ones("k_norm", (Dh,), (None,))


def _project_qkv(p, cfg: ModelConfig, x: jnp.ndarray, positions, rope: bool):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(cfg: ModelConfig, q, k, v, mask) -> jnp.ndarray:
    """q: [B,Sq,H,Dh]; k,v: [B,Sk,KV,Dh]; mask: [B,1,Sq,Sk] or None."""
    B, Sq, H, Dh = q.shape
    qg = _grouped_q(q, k.shape[2])
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    logits *= 1.0 / math.sqrt(Dh)
    if mask is not None:
        logits = jnp.where(mask[:, :, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return out.reshape(B, Sq, H, Dh)


def _grouped_q(q, KV: int):
    """q: [B,Sq,H,Dh] -> [B,Sq,KV,group,Dh], the query heads of each kv head."""
    B, Sq, H, Dh = q.shape
    group = H // KV
    qg = q.reshape(B, Sq, KV, group, Dh)
    # Give the grouped-head reshape a coherent layout when KV or group
    # divides the TP axis: without this GSPMD cannot propagate the
    # H-sharding of q through the (KV, group) split and falls back to
    # replicate-reshard of the full [B,KV,G,Sq,Sk] score tensor
    # (5.9 TiB/step of f32 all-gathers on qwen3-moe train).  When neither
    # dim divides (granite kv=8 g=2), constraining would *strip* the
    # existing H-sharding instead - skip.
    ms = mesh_axis_size("model")
    if KV % ms == 0 or group % ms == 0:
        qg = with_logical(qg, ("batch", None, "kv_heads", "heads", None),
                          partial=True)
    return qg


def _causal_mask(Sq: int, Sk: int, window: int = 0,
                 q_offset: int = 0) -> jnp.ndarray:
    i = jnp.arange(Sq)[:, None] + (Sk - Sq) + q_offset
    j = jnp.arange(Sk)[None, :]
    m = j <= i
    if window > 0:
        m &= j > i - window
    return m[None, None]  # [1,1,Sq,Sk] -> broadcast over batch/kv


# q-chunked (flash-style) attention: never materializes [Sq, Sk] scores for
# the whole sequence at once.  Default chunk keeps the per-chunk score block
# a few hundred MB at 32k context.
Q_CHUNK = 512


def _blocked_sdpa(cfg: ModelConfig, q, k, v, *, causal: bool, window: int,
                  q_chunk: int = Q_CHUNK) -> jnp.ndarray:
    """q: [B,Sq,H,Dh]; k,v: [B,Sk,KV,Dh].  Scans over q chunks."""
    B, Sq, H, Dh = q.shape
    Sk = k.shape[1]
    qc = min(q_chunk, Sq)
    while Sq % qc:
        qc -= 1
    nq = Sq // qc
    if nq == 1:
        mask = _causal_mask(Sq, Sk, window) if causal else None
        return _sdpa(cfg, q, k, v, mask)

    qs = q.reshape(B, nq, qc, H, Dh).swapaxes(0, 1)   # [nq, B, qc, H, Dh]

    def one(_, inp):
        ci, qb = inp
        if causal:
            i = jnp.arange(qc)[:, None] + (Sk - Sq) + ci * qc
            j = jnp.arange(Sk)[None, :]
            m = j <= i
            if window > 0:
                m &= j > i - window
            mask = m[None, None]
        else:
            mask = None
        return 0, _sdpa(cfg, qb, k, v, mask)

    _, outs = lax.scan(one, 0, (jnp.arange(nq), qs))
    return outs.swapaxes(0, 1).reshape(B, Sq, H, Dh)


def attention(p, cfg: ModelConfig, x: jnp.ndarray, *, causal: bool = True,
              rope: bool = True, window: int = 0) -> jnp.ndarray:
    """Full-sequence attention (training / encoding).  x: [B,S,D]."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _project_qkv(p, cfg, x, positions, rope)
    # Megatron-SP: residuals stay seq-sharded; layer internals shard heads
    # (the "seq" position is None so "heads" wins the model axis).
    q = with_logical(q, ("batch", None, "heads", "head_dim"))
    out = _blocked_sdpa(cfg, q, k, v, causal=causal, window=window)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return with_logical(out, ("batch", "seq", "embed"))


# Prefill bounds its float32 score blocks [sequences, H, q chunk, S] to
# this many bytes: past it, attention runs over groups of sequences.
PREFILL_SCORE_BYTES = 1 << 30


def _prefill_attn(p, cfg: ModelConfig, x: jnp.ndarray, window: int):
    """Causal attention over whole sequences x: [B,S,D] -> (out, k, v)."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _project_qkv(p, cfg, x, positions, rope=True)
    out = _blocked_sdpa(cfg, q, k, v, causal=True, window=window)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, k, v


def attention_prefill(p, cfg: ModelConfig, x: jnp.ndarray, s_max: int, *,
                      window: int = 0) -> Tuple[jnp.ndarray, KVCache]:
    """Causal prefill that also returns a KV cache padded to ``s_max``.
    A batch whose score blocks would pass ``PREFILL_SCORE_BYTES`` goes
    through attention a group of sequences at a time."""
    B, S, D = x.shape
    per_seq = cfg.n_heads * min(Q_CHUNK, S) * S * 4
    g = max(1, min(B, PREFILL_SCORE_BYTES // per_seq))
    while B % g:
        g -= 1
    if g == B:
        out, k, v = _prefill_attn(p, cfg, x, window)
    else:
        out, k, v = lax.map(lambda xg: _prefill_attn(p, cfg, xg, window),
                            x.reshape(B // g, g, S, D))
        out, k, v = (a.reshape(B, *a.shape[2:]) for a in (out, k, v))
    pad = ((0, 0), (0, 0), (0, s_max - S), (0, 0))
    kc, vc = (with_logical(jnp.pad(a.swapaxes(1, 2), pad),
                           ("batch", "kv_heads", "cache_seq", "head_dim"))
              for a in (k, v))
    return out, KVCache(k=kc, v=vc, length=jnp.array(S, jnp.int32))


def attention_decode_at(p, cfg: ModelConfig, x: jnp.ndarray, caches: KVCache,
                        i, *, window: int = 0) -> Tuple[jnp.ndarray, KVCache]:
    """Single-token decode on layer ``i`` of stacked caches (every leaf
    with a leading blocks axis).  x: [B,1,D].  Layer ``i`` is read where
    it lies, before the token goes in: its positions ``< length[i]``
    (inside the window) and the token's own k, v share one softmax.  The
    token's k, v are then written in place at ``[i, :, :, length[i]]``.
    (Read after the write, in a scan, the dots set the carried stack's
    layout and XLA copies the whole stack into and out of the loop.)"""
    B = x.shape[0]
    length = caches.length[i]
    q, k, v = _project_qkv(p, cfg, x, jnp.broadcast_to(length, (B, 1)),
                           rope=True)
    k = k.swapaxes(1, 2).astype(caches.k.dtype)          # [B, KV, 1, Dh]
    v = v.swapaxes(1, 2).astype(caches.v.dtype)
    k_old = lax.dynamic_index_in_dim(caches.k, i, keepdims=False)
    v_old = lax.dynamic_index_in_dim(caches.v, i, keepdims=False)
    KV, S_max, Dh = k_old.shape[1:]
    qg = _grouped_q(q, KV)

    def scores(kc):
        s = jnp.einsum("bqkgd,bksd->bkgqs", qg, kc).astype(jnp.float32)
        return s * (1.0 / math.sqrt(Dh))

    j = jnp.arange(S_max)
    valid = j < length
    if window > 0:
        valid &= j > length - window
    logits = jnp.concatenate([jnp.where(valid, scores(k_old), -1e30),
                              scores(k)], axis=-1)
    w = jax.nn.softmax(logits, axis=-1).astype(v_old.dtype)
    f32 = jnp.float32
    out = (jnp.einsum("bkgqs,bksd->bqkgd", w[..., :S_max], v_old).astype(f32)
           + jnp.einsum("bkgqs,bksd->bqkgd", w[..., S_max:].astype(f32),
                        v.astype(f32)))
    out = out.astype(x.dtype).reshape(q.shape)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    at = (i, 0, 0, length, 0)
    return out, KVCache(k=lax.dynamic_update_slice(caches.k, k[None], at),
                        v=lax.dynamic_update_slice(caches.v, v[None], at),
                        length=caches.length.at[i].add(1))


def attention_decode(p, cfg: ModelConfig, x: jnp.ndarray, cache: KVCache, *,
                     window: int = 0) -> Tuple[jnp.ndarray, KVCache]:
    """Single-token decode on one layer's cache.  x: [B,1,D]; appends to
    the cache at ``length``."""
    out, new = attention_decode_at(p, cfg, x,
                                   jax.tree.map(lambda a: a[None], cache), 0,
                                   window=window)
    return out, jax.tree.map(lambda a: a[0], new)


def cross_attention(p, cfg: ModelConfig, x: jnp.ndarray, enc_k, enc_v):
    """Decoder->encoder cross attention (whisper).  No RoPE, no mask."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
    out = _sdpa(cfg, q, enc_k, enc_v, None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def encode_kv(p, cfg: ModelConfig, enc_out: jnp.ndarray):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(enc_out.dtype))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(enc_out.dtype)
        v = v + p["bv"].astype(enc_out.dtype)
    return k, v


# -------------------------------------------------------------- SwiGLU MLP
def init_mlp(b: ParamBuilder, cfg: ModelConfig, name: str = "mlp",
             d_ff: Optional[int] = None):
    m = b.child(name)
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    m.normal("wi_gate", (D, F), ("embed", "mlp"), fan_in=D)
    m.normal("wi_up", (D, F), ("embed", "mlp"), fan_in=D)
    m.normal("wo", (F, D), ("mlp", "embed"), fan_in=F)


def mlp(p, x: jnp.ndarray, n_chunks: int = 1) -> jnp.ndarray:
    if n_chunks <= 1:
        g = jnp.einsum("bsd,df->bsf", x, p["wi_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, p["wi_up"].astype(x.dtype))
        h = jax.nn.silu(g) * u
        h = with_logical(h, ("batch", None, "mlp"))
        return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))
    # F-chunked (scan) variant: one weight chunk gathered/live at a time.
    D, F = p["wi_gate"].shape
    fc = F // n_chunks
    wg = p["wi_gate"].reshape(D, n_chunks, fc).swapaxes(0, 1)
    wu = p["wi_up"].reshape(D, n_chunks, fc).swapaxes(0, 1)
    wo = p["wo"].reshape(n_chunks, fc, D)

    def step(acc, ws):
        g_, u_, o_ = ws
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, g_.astype(x.dtype))) \
            * jnp.einsum("bsd,df->bsf", x, u_.astype(x.dtype))
        h = with_logical(h, ("batch", None, "mlp"))
        return acc + jnp.einsum("bsf,fd->bsd", h, o_.astype(x.dtype)), None

    out, _ = lax.scan(step, jnp.zeros_like(x), (wg, wu, wo))
    return out


# ------------------------------------------------------------- Embeddings
def init_embed(b: ParamBuilder, cfg: ModelConfig):
    b.normal("tok_embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
             stddev=1.0)
    b.normal("unembed", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
             fan_in=cfg.d_model)
    b.ones("final_norm", (cfg.d_model,), (None,))


def embed(params, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    x = params["tok_embed"].astype(cfg.dtype)[tokens]
    return with_logical(x, ("batch", "seq", "embed"))


def unembed(params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"].astype(x.dtype))
    return with_logical(logits, ("batch", None, "vocab"))
