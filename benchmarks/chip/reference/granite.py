"""Plain float32 forward pass of a llama-style MoE language model.

Written from the model's equations, with no cache and no batching: one
sequence at a time, every layer over every position, causal attention,
RMSNorm, rotary positions (halves rotated), grouped key/value heads, a
top-k router renormalised over its choices, SwiGLU experts and an untied
unembedding.  It follows the served program's routing semantics: a
prompt is routed as one sequence with ``max(8, int(P * k * cf / E))``
slots per expert, filled in (token, choice) order, and choices past an
expert's slots add nothing; a decoded token is routed alone and never
dropped.  Every expert is computed over every position and the
unselected ones are weighted zero.

Weights come from ``benchmarks.chip.weights`` by the program's parameter
paths and are cast to float32.  ``fp8=True`` is the control: every matrix
product takes its operands quantized to float8 (e4m3, one scale per row
of activations and per matrix or expert of weights).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.chip import weights as W

F8_MAX = 448.0


def sizes(c: dict) -> dict:
    return dict(L=c["num_hidden_layers"], D=c["hidden_size"],
                H=c["num_attention_heads"], KV=c["num_key_value_heads"],
                Dh=c["head_dim"], F=c["intermediate_size"],
                E=c["num_local_experts"], k=c["num_experts_per_tok"],
                V=c["vocab_size"], theta=float(c["rope_theta"]),
                eps=float(c["rms_norm_eps"]), cf=float(c["capacity_factor"]))


def make_weights(c: dict, seed: int) -> dict:
    """Float32 weights in the program's layout (blocks stacked on axis 0)."""
    return _weights(W.base_key(seed), s=tuple(sorted(sizes(c).items())))


@functools.partial(jax.jit, static_argnames=("s",))
def _weights(key, *, s: tuple) -> dict:
    s = dict(s)
    L, D, H, KV, Dh, F, E, V = (s[n] for n in "L D H KV Dh F E V".split())
    shapes = {
        "tok_embed": (V, D), "unembed": (D, V), "final_norm": (D,),
        "blocks/l0/ln1": (L, D), "blocks/l0/ln2": (L, D),
        "blocks/l0/attn/wq": (L, D, H, Dh), "blocks/l0/attn/wk": (L, D, KV, Dh),
        "blocks/l0/attn/wv": (L, D, KV, Dh), "blocks/l0/attn/wo": (L, H, Dh, D),
        "blocks/l0/moe/router": (L, D, E),
        "blocks/l0/moe/wi_gate": (L, E, D, F), "blocks/l0/moe/wi_up": (L, E, D, F),
        "blocks/l0/moe/wo": (L, E, F, D),
    }
    return {p: W.leaf(key, p, shp, jnp.bfloat16).astype(jnp.float32)
            for p, shp in shapes.items()}


def fq(x, axes, on: bool):
    """Fake-quantize ``x`` to float8 e4m3 with one scale per slice that
    ``axes`` reduces over; identity when ``on`` is false."""
    if not on:
        return x
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def rmsnorm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """x: [S, heads, Dh]; position i rotates pair (j, j + Dh/2)."""
    S, _, Dh = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., : Dh // 2], x[..., Dh // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def combine_weights(probs, k: int, prompt_len: int, cap: int):
    """[S, E] weight of each expert for each position: the top-k
    probabilities renormalised, zero where the prompt's slots ran out."""
    S, E = probs.shape
    w, idx = lax.top_k(probs, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)            # [S, k, E]
    flat = onehot.reshape(S * k, E)
    earlier = (jnp.cumsum(flat, axis=0) - flat).reshape(S, k, E)
    pos = jnp.sum(earlier * onehot, axis=-1)                    # [S, k]
    in_prompt = jnp.arange(S)[:, None] < prompt_len
    # Prompt choices count slots among prompt positions only; positions
    # after the prompt never reach this count's limit.
    keep = jnp.where(in_prompt, pos < cap, True)
    return jnp.einsum("sk,ske->se", w * keep, onehot.astype(jnp.float32))


def forward(w, tokens, *, s: tuple, prompt_len: int, fp8: bool = False):
    """tokens: [S] int -> logits [S, V] float32."""
    s = dict(s)
    S = tokens.shape[0]
    H, KV, Dh, E, k = s["H"], s["KV"], s["Dh"], s["E"], s["k"]
    cap = max(8, int(prompt_len * k * s["cf"] / E))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    x = w["tok_embed"][tokens]

    def layer(x, lw):
        h = rmsnorm(x, lw["ln1"], s["eps"])
        hq = fq(h, -1, fp8)
        q = jnp.einsum("sd,dhk->shk", hq, fq(lw["wq"], (0, 1, 2), fp8))
        kk = jnp.einsum("sd,dhk->shk", hq, fq(lw["wk"], (0, 1, 2), fp8))
        v = jnp.einsum("sd,dhk->shk", hq, fq(lw["wv"], (0, 1, 2), fp8))
        q, kk = rope(q, s["theta"]), rope(kk, s["theta"])
        kk = jnp.repeat(kk, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", fq(q, -1, fp8), fq(kk, -1, fp8))
        sc = jnp.where(causal[None], sc / math.sqrt(Dh), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", fq(p, -1, fp8), fq(v, 0, fp8))
        x = x + jnp.einsum("qhd,hdm->qm", fq(o, (1, 2), fp8),
                           fq(lw["wo"], (0, 1, 2), fp8))
        h = rmsnorm(x, lw["ln2"], s["eps"])
        hq = fq(h, -1, fp8)
        probs = jax.nn.softmax(hq @ fq(lw["router"], (0, 1), fp8), axis=-1)
        cw = combine_weights(probs, k, prompt_len, cap)
        g = jnp.einsum("sd,edf->esf", hq, fq(lw["wi_gate"], (1, 2), fp8))
        u = jnp.einsum("sd,edf->esf", hq, fq(lw["wi_up"], (1, 2), fp8))
        a = jax.nn.silu(g) * u
        y = jnp.einsum("esf,efd->esd", fq(a, -1, fp8),
                       fq(lw["wo_e"], (1, 2), fp8))
        return x + jnp.einsum("se,esd->sd", cw, y), None

    blocks = {
        "ln1": w["blocks/l0/ln1"], "ln2": w["blocks/l0/ln2"],
        "wq": w["blocks/l0/attn/wq"], "wk": w["blocks/l0/attn/wk"],
        "wv": w["blocks/l0/attn/wv"], "wo": w["blocks/l0/attn/wo"],
        "router": w["blocks/l0/moe/router"],
        "wi_gate": w["blocks/l0/moe/wi_gate"], "wi_up": w["blocks/l0/moe/wi_up"],
        "wo_e": w["blocks/l0/moe/wo"],
    }
    x, _ = lax.scan(layer, x, blocks)
    x = rmsnorm(x, w["final_norm"], s["eps"])
    return fq(x, -1, fp8) @ fq(w["unembed"], (0, 1), fp8)


@functools.partial(jax.jit, static_argnames=("s", "prompt_len", "fp8"))
def served_logits(w, seqs, *, s: tuple, prompt_len: int, fp8: bool = False):
    """seqs: [n, S] -> logits [n, S - prompt_len + 1, V] at the positions
    that predict served tokens (the last prompt position onwards)."""
    return jax.vmap(lambda t: forward(w, t, s=s, prompt_len=prompt_len,
                                      fp8=fp8)[prompt_len - 1:])(seqs)


BLOCK = 8    # requests per reference call: bounds its activations


def served_gaps(c: dict, seed: int, prompts: np.ndarray, served: np.ndarray,
                *, control: bool = False) -> dict:
    """Per request (a prompt and the tokens served for it) and per served
    position: ``gap``, how far the served token's reference logit lies
    below the reference's best; ``margin``, how far the reference's best
    lies above its runner-up.  With ``control``, also ``control_gap``, the
    gap of the token that the float8 reference puts first.  Arrays are
    ``[requests, tokens]``."""
    s = tuple(sorted(sizes(c).items()))
    P = prompts.shape[1]
    w = make_weights(c, seed)
    seqs = np.concatenate([prompts, served[:, :-1]], axis=1).astype(np.int32)
    out = {"gap": [], "margin": [], "control_gap": []}
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(seqs), BLOCK):
            blk = jnp.asarray(seqs[i:i + BLOCK])
            toks = jnp.asarray(served[i:i + BLOCK])[..., None]
            ref = served_logits(w, blk, s=s, prompt_len=P)
            top2 = lax.top_k(ref, 2)[0]
            got = jnp.take_along_axis(ref, toks, -1)[..., 0]
            out["gap"].append(np.asarray(top2[..., 0] - got))
            out["margin"].append(np.asarray(top2[..., 0] - top2[..., 1]))
            if control:
                ctl = served_logits(w, blk, s=s, prompt_len=P, fp8=True)
                pick = jnp.argmax(ctl, axis=-1)[..., None]
                got = jnp.take_along_axis(ref, pick, -1)[..., 0]
                out["control_gap"].append(np.asarray(top2[..., 0] - got))
                del ctl
            del ref
    return {k: np.concatenate(v) for k, v in out.items() if v}
