"""Mixture-of-Experts FFN: top-k routing with capacity, two execution paths.

``moe_gather``  — scatter/gather dispatch + batched expert einsum.  Pure data
                  movement for dispatch (no one-hot einsum FLOP inflation),
                  shardable under plain pjit: experts are sharded on the
                  "experts" logical axis and GSPMD inserts the (all-to-all
                  equivalent) collectives.  Used by train/dry-run steps.
                  Where no sequence can overflow an expert (capacity >= the
                  sequence length: every decode step, short prompts) it
                  builds no capacity buffer: each expert runs over the
                  tokens themselves and a dense weight combines them.

``moe_block_ep`` — explicit expert parallelism for ``shard_map`` contexts:
                  tokens are exchanged with ``lax.all_to_all`` over the model
                  axis — the *exact* collective the paper studies — and the
                  dispatch collective can be scheduled with the
                  translation-aware warm-up plan (repro.core.overlap).
                  The receiving shard sorts its rows by local expert and
                  runs each row through its own expert only, with a
                  grouped matmul (``kernels.ops.gmm``).

``moe_held``    — one expert-parallel rank's share on one chip: routes over
                  every expert, and runs the choices that land on the
                  experts held here through the same sorted-row grouped
                  matmul, with no exchange and no capacity (dropless).

The first two share routing and drop tokens beyond capacity (GShard-style)
with residual passthrough.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import ops
from .base import ModelConfig, ParamBuilder, with_logical
from .scopes import (EP_COMBINE, EP_DISPATCH, EP_EXPERTS, EP_META, EP_ROUTE,
                     MOE_COMBINE, MOE_DISPATCH, MOE_EXPERTS, MOE_ROUTER,
                     scope)


def init_moe(b: ParamBuilder, cfg: ModelConfig, name: str = "moe"):
    """The router over all ``n_experts``; the weights of the experts held
    here (``experts_held`` of them, or all)."""
    m = b.child(name)
    D, F = cfg.d_model, cfg.d_ff_expert
    m.normal("router", (D, cfg.n_experts), ("embed", None), fan_in=D)
    E = cfg.experts_held or cfg.n_experts
    m.normal("wi_gate", (E, D, F), ("experts", "expert_embed", "expert_mlp"),
             fan_in=D)
    m.normal("wi_up", (E, D, F), ("experts", "expert_embed", "expert_mlp"),
             fan_in=D)
    m.normal("wo", (E, F, D), ("experts", "expert_mlp", "expert_embed"),
             fan_in=F)


def route(p, cfg: ModelConfig, x_flat: jnp.ndarray):
    """Top-k routing in fp32.  Returns (idx [T,k], weights [T,k], aux_loss)."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, cfg.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)         # renormalize over top-k
    # Switch-style load-balance auxiliary loss.
    T, E = logits.shape
    me = jnp.mean(probs, axis=0)                       # mean router prob / expert
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = E * jnp.sum(me * ce) / cfg.top_k
    return idx, w.astype(x_flat.dtype), aux


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, c)


def _expert_ffn(p, x: jnp.ndarray) -> jnp.ndarray:
    """x: [E, C, D] -> [E, C, D] batched SwiGLU over experts."""
    g = jnp.einsum("ecd,edf->ecf", x, p["wi_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", x, p["wi_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    h = with_logical(h, ("experts", None, "expert_mlp"))
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))


def _gather_experts(p, cfg: ModelConfig, buf: jnp.ndarray, dtype):
    """buf: [B, E, C, D] -> [B, E, C, D], ``moe_gather``'s expert SwiGLU."""
    E, D = cfg.n_experts, buf.shape[-1]
    F = p["wi_gate"].shape[-1]
    nch = cfg.ffn_chunks if (cfg.ffn_chunks > 1 and F % cfg.ffn_chunks == 0) else 1
    if nch == 1:
        g = jnp.einsum("becd,edf->becf", buf, p["wi_gate"].astype(dtype))
        u = jnp.einsum("becd,edf->becf", buf, p["wi_up"].astype(dtype))
        h = jax.nn.silu(g) * u
        h = with_logical(h, ("batch", "experts", None, "expert_mlp"))
        out_e = jnp.einsum("becf,efd->becd", h, p["wo"].astype(dtype))
    else:
        # F-chunked expert FFN (scan): bounds simultaneously-gathered
        # expert-weight shards (all-gathers cannot be hoisted out of loops).
        fc = F // nch
        wg = p["wi_gate"].reshape(E, D, nch, fc).transpose(2, 0, 1, 3)
        wu = p["wi_up"].reshape(E, D, nch, fc).transpose(2, 0, 1, 3)
        wo = p["wo"].reshape(E, nch, fc, D).transpose(1, 0, 2, 3)

        def step(acc, ws):
            g_, u_, o_ = ws
            h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf,
                                       g_.astype(dtype))) \
                * jnp.einsum("becd,edf->becf", buf, u_.astype(dtype))
            h = with_logical(h, ("batch", "experts", None, "expert_mlp"))
            return acc + jnp.einsum("becf,efd->becd", h,
                                    o_.astype(dtype)), None

        out_e, _ = lax.scan(step, jnp.zeros_like(buf), (wg, wu, wo))
    return with_logical(out_e, ("batch", "experts", None, None))


def moe_gather(p, cfg: ModelConfig, x: jnp.ndarray):
    """MoE FFN for [B,S,D] input under pjit auto-sharding.

    Dispatch is **per batch row** (capacity enforced per sequence): the
    scatter/gather never crosses the batch dimension, so every tensor stays
    naturally (batch x expert)-sharded — GSPMD inserts only the expert-axis
    exchange (the all-to-all the paper prices), never a global token
    reshuffle (which it implements as replicate-then-partition and blows
    per-device memory).

    A token picks an expert at most once, so a sequence sends an expert at
    most S rows.  Where the capacity C is at least S (every decode step,
    prompts of up to 8 tokens for granite), nothing can be dropped and no
    capacity buffer is built: token ``s`` takes slot ``s`` of every expert
    (``[B, E, S, D]``, the input broadcast), and the combine weighs each
    expert's output with the token's router weight, 0 where the token did
    not choose it.  Otherwise the rows are scattered into ``[B, E, C, D]``
    and choices past C are dropped."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(cfg, S)

    # routing (fp32) on [B,S,E]
    with scope(MOE_ROUTER):
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = lax.top_k(probs, k)                   # [B,S,k]
        w = (w / jnp.sum(w, axis=-1, keepdims=True)).astype(x.dtype)
        me = jnp.mean(probs, axis=(0, 1))
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                              axis=2), axis=(0, 1))
        aux = E * jnp.sum(me * ce) / k

    if C >= S:
        with scope(MOE_DISPATCH):
            buf = with_logical(jnp.broadcast_to(x[:, None], (B, E, S, D)),
                               ("batch", "experts", None, None))
            wd = (jax.nn.one_hot(idx, E, dtype=x.dtype)
                  * w[..., None]).sum(axis=2).swapaxes(1, 2)  # [B, E, S]
        with scope(MOE_EXPERTS):
            out_e = _gather_experts(p, cfg, buf, x.dtype)
        with scope(MOE_COMBINE):
            y = (out_e * wd[..., None]).sum(axis=1)
        return y, aux

    with scope(MOE_DISPATCH):
        a = idx.reshape(B, S * k)                      # [B, S*k] expert ids
        onehot = jax.nn.one_hot(a, E, dtype=jnp.int32)  # [B, S*k, E]
        pos = jnp.cumsum(onehot, axis=1) - onehot
        pos = jnp.take_along_axis(pos, a[..., None], axis=2)[..., 0]
        keep = pos < C
        safe_a = jnp.where(keep, a, 0)
        safe_pos = jnp.where(keep, pos, C - 1)
        xr = jnp.broadcast_to(x[:, :, None, :],
                              (B, S, k, D)).reshape(B, S * k, D)
        xr = jnp.where(keep[..., None], xr, 0).astype(x.dtype)

        def disp(xr_row, a_row, pos_row):
            return jnp.zeros((E, C, D), x.dtype).at[a_row, pos_row].add(xr_row)

        buf = jax.vmap(disp)(xr, safe_a, safe_pos)     # [B, E, C, D]
        buf = with_logical(buf, ("batch", "experts", None, None))

    with scope(MOE_EXPERTS):
        out_e = _gather_experts(p, cfg, buf, x.dtype)

    with scope(MOE_COMBINE):
        gathered = jax.vmap(lambda o, a_r, p_r: o[a_r, p_r])(
            out_e, safe_a, safe_pos)                   # [B, S*k, D]
        # Combine lands in the sequence-parallel layout: the
        # cross-expert-shard reduction becomes a reduce-scatter into
        # [B, S*k/TP, D] instead of a full all-reduce of [B, S*k, D]
        # (granite train: -31% collective bytes).
        gathered = with_logical(gathered, ("batch", "seq", None))
        gathered = jnp.where(keep[..., None], gathered, 0)
        y = (gathered.reshape(B, S, k, D) * w[..., None]).sum(axis=2)
    return y, aux


def _local_groups(recv_meta: jnp.ndarray, n_local: int):
    """Received slots grouped by local expert.

    ``recv_meta`` holds each slot's local expert id + 1 (0 = empty).
    Returns ``order``, the slots sorted by expert with the empty ones last
    (stable, so slot order holds within an expert), and ``sizes``
    [n_local] int32, each expert's row count."""
    meta = recv_meta.reshape(-1)
    key = jnp.where(meta == 0, n_local, meta - 1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=n_local + 1)[:n_local]
    return order, sizes.astype(jnp.int32)


def _grouped_swiglu(p, xs: jnp.ndarray, sizes: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU of rows [S, D] sorted by local expert (``sizes`` rows each),
    one grouped matmul per projection.  Rows past ``sum(sizes)`` come out
    undefined."""
    g = ops.gmm(xs, p["wi_gate"].astype(xs.dtype), sizes)
    u = ops.gmm(xs, p["wi_up"].astype(xs.dtype), sizes)
    return ops.gmm(jax.nn.silu(g) * u, p["wo"].astype(xs.dtype), sizes)


def _slot_order(ys: jnp.ndarray, order: jnp.ndarray,
                meta: jnp.ndarray) -> jnp.ndarray:
    """Sorted rows back to slot order, the empty slots (``meta`` 0, sorted
    past the filled rows, so undefined) zero."""
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.size, dtype=order.dtype))
    return jnp.where(meta.reshape(-1, 1) > 0, ys[back], 0)


def _local_experts(p, rows: jnp.ndarray, recv_meta: jnp.ndarray,
                   n_local: int) -> jnp.ndarray:
    """SwiGLU of each received row [S, D] through its own local expert,
    empty slots zero: the rows sorted by expert, one grouped matmul per
    projection, back to slot order."""
    order, sizes = _local_groups(recv_meta, n_local)
    return _slot_order(_grouped_swiglu(p, rows[order], sizes), order,
                       recv_meta)


def _held_block(p, x: jnp.ndarray, meta: jnp.ndarray, w: jnp.ndarray,
                n_held: int) -> jnp.ndarray:
    """One block of tokens through the held experts.  ``x`` [T, D];
    ``meta`` [T, k], each choice's held expert + 1 (0 = held elsewhere);
    ``w`` [T, k] the router's weights.  Returns [T, D], each token's
    weighted sum over its held choices."""
    T, k = meta.shape
    with scope(MOE_DISPATCH):
        order, sizes = _local_groups(meta, n_held)
        xs = x[order // k]
    with scope(MOE_EXPERTS):
        ys = _grouped_swiglu(p, xs, sizes)
    with scope(MOE_COMBINE):
        out = _slot_order(ys, order, meta).reshape(T, k, -1)
        return (out * w[..., None]).sum(axis=1)


# Tokens per block of the held-expert layer: at most this many times
# top_k rows (and their SwiGLU) are live at once, whatever the routing.
HELD_BLOCK_TOKENS = 8192


def moe_held(p, cfg: ModelConfig, x: jnp.ndarray):
    """MoE FFN of one expert-parallel rank for [B,S,D] input: routes over
    all ``n_experts`` and adds what the ``experts_held`` experts from
    ``expert_offset`` give; choices of experts held elsewhere add nothing
    here.  Dropless: every held choice is computed.  Tokens go through the
    experts in blocks of ``HELD_BLOCK_TOKENS`` (the last one padded)."""
    B, S, D = x.shape
    T, k, n = B * S, cfg.top_k, cfg.experts_held
    xf = x.reshape(T, D)
    with scope(MOE_ROUTER):
        idx, w, aux = route(p, cfg, xf)
    with scope(MOE_DISPATCH):
        local = idx - cfg.expert_offset
        meta = jnp.where((local >= 0) & (local < n), local + 1, 0)
    tb = min(T, HELD_BLOCK_TOKENS)
    nb = -(-T // tb)
    if nb == 1:
        y = _held_block(p, xf, meta, w, n)
    else:
        pad = nb * tb - T
        blocks = [jnp.pad(a, ((0, pad), (0, 0))).reshape(nb, tb, -1)
                  for a in (xf, meta, w)]
        y = lax.map(lambda a: _held_block(p, *a, n), blocks)
        y = y.reshape(nb * tb, D)[:T]
    return y.reshape(B, S, D), aux


def moe_ffn(p, cfg: ModelConfig, x: jnp.ndarray):
    """The MoE FFN a config serves: its held share of the experts
    (``moe_held``) where it holds one, else every expert (``moe_gather``)."""
    if cfg.experts_held:
        return moe_held(p, cfg, x)
    return moe_gather(p, cfg, x)


def moe_block_ep(p, cfg: ModelConfig, x: jnp.ndarray, axis_name: str,
                 plan=None, overlap_compute=None):
    """Expert-parallel MoE inside ``shard_map`` over ``axis_name``.

    ``x``: [T_loc, D] local tokens.  Experts are sharded: this shard holds
    ``E / axis_size`` of them (p's leaves are the local slices).  Dispatch
    and combine are explicit ``lax.all_to_all`` — the collective the paper
    analyzes — optionally scheduled with a warm-up chunk plan.
    """
    from ..core.overlap import scheduled_all_to_all

    ep = lax.psum(1, axis_name)
    T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    E_loc = E // ep
    C = _capacity(cfg, T) * E_loc                      # capacity per shard
    with scope(EP_ROUTE):
        idx, w, aux = route(p, cfg, x)                 # router is replicated
        a = idx.reshape(-1)                            # [T*k] global expert id
        shard = a // E_loc                             # destination shard
        # position within destination shard's receive slot for this source
        onehot = jax.nn.one_hot(shard, ep, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.take_along_axis(pos, shard[:, None], axis=1)[:, 0]
        keep = pos < C
        safe_shard = jnp.where(keep, shard, 0)
        safe_pos = jnp.where(keep, pos, C - 1)

    with scope(EP_DISPATCH):
        xr = jnp.repeat(x, k, axis=0)
        send = jnp.zeros((ep, C, D), x.dtype)
        send = send.at[safe_shard, safe_pos].add(
            jnp.where(keep[:, None], xr, 0).astype(x.dtype))
    with scope(EP_META):
        send_meta = jnp.zeros((ep, C), jnp.int32)
        send_meta = send_meta.at[safe_shard, safe_pos].add(
            jnp.where(keep, a % E_loc + 1, 0))         # 0 = empty slot

    # ---- dispatch all-to-all (optionally warm-up-scheduled) -------------
    with scope(EP_DISPATCH):
        if plan is not None:
            # Flat [ep*C, D] rows: the schedule chunks within each peer block.
            compute_fn, compute_arg = overlap_compute or (None, None)
            recv, _ = scheduled_all_to_all(send.reshape(ep * C, D), axis_name,
                                           plan, compute_fn=compute_fn,
                                           compute_arg=compute_arg)
            recv = recv.reshape(ep, C, D)
        else:
            recv = lax.all_to_all(send, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)
    with scope(EP_META):
        recv_meta = lax.all_to_all(send_meta, axis_name, split_axis=0,
                                   concat_axis=0, tiled=True)

    # ---- local experts: each received row through its own expert -------
    with scope(EP_EXPERTS):
        out_flat = _local_experts(p, recv.reshape(ep * C, D), recv_meta,
                                  E_loc)

    # ---- combine all-to-all back ----------------------------------------
    with scope(EP_COMBINE):
        back = lax.all_to_all(out_flat.reshape(ep, C, D), axis_name,
                              split_axis=0, concat_axis=0, tiled=True)
        gathered = back[safe_shard, safe_pos]
        gathered = jnp.where(keep[:, None], gathered, 0)
        y = (gathered.reshape(T, k, D)
             * w[..., None].astype(x.dtype)).sum(axis=1)
    return y, aux
