"""Plain float32 forward pass of one expert-parallel rank's share of
Qwen3-MoE (hf: Qwen/Qwen3-235B-A22B).

Written from the model's equations, one sequence at a time, with no
cache, no kernels and no batching.  Per layer: RMSNorm; q, k and v
projections; RMSNorm of each head of q and of k (``q_norm``, ``k_norm``);
rotary positions (theta 1e6, halves rotated); causal softmax attention in
which each group of 16 query heads shares one key/value head; the output
projection and a residual add; RMSNorm; a softmax router over all of the
published experts, its top k renormalised over the choices; the SwiGLU
of each expert held here weighted by its share of the token's choices;
a residual add.  Then a final RMSNorm and the untied head.

Departures from the published model, the same as the program's share:

- only the experts ``[offset, offset + held)`` of each layer are held;
  a choice of an expert held elsewhere adds nothing here (the published
  model adds that expert's output, computed on another chip);
- fewer layers than published (the configuration file's
  ``num_hidden_layers``).

Every held expert is computed over every position and weighted zero
where it was not chosen; nothing is dropped.  Attention runs in blocks
of ``Q_BLOCK`` queries, so no score matrix of a whole sequence is held.

Weights come from ``benchmarks.chip.weights`` by the program's parameter
paths (drawn in bf16 as served, then cast to float32), one layer at a
time, so the float32 weights of the whole stack are never all resident.
``fp8=True`` is the control: every matrix product takes its operands
quantized to float8 (e4m3), as in ``reference/granite.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.chip import weights as W
from benchmarks.chip.reference.granite import fq, rmsnorm, rope

Q_BLOCK = 256      # queries per attention block
BLOCK = 4          # requests per call of the head


def sizes(c: dict) -> dict:
    return dict(L=c["num_hidden_layers"], D=c["hidden_size"],
                H=c["num_attention_heads"], KV=c["num_key_value_heads"],
                Dh=c["head_dim"], F=c["moe_intermediate_size"],
                E=c["router_experts"], n=c["num_experts"],
                off=c["expert_offset"], k=c["num_experts_per_tok"],
                V=c["vocab_size"], theta=float(c["rope_theta"]),
                eps=float(c["rms_norm_eps"]))


def layer_shapes(s: dict) -> dict:
    """The program's stacked per-layer leaves and their shapes."""
    L, D, H, KV, Dh = s["L"], s["D"], s["H"], s["KV"], s["Dh"]
    F, E, n = s["F"], s["E"], s["n"]
    return {
        "blocks/l0/ln1": (L, D), "blocks/l0/ln2": (L, D),
        "blocks/l0/attn/wq": (L, D, H, Dh), "blocks/l0/attn/wk": (L, D, KV, Dh),
        "blocks/l0/attn/wv": (L, D, KV, Dh), "blocks/l0/attn/wo": (L, H, Dh, D),
        "blocks/l0/attn/q_norm": (L, Dh), "blocks/l0/attn/k_norm": (L, Dh),
        "blocks/l0/moe/router": (L, D, E),
        "blocks/l0/moe/wi_gate": (L, n, D, F), "blocks/l0/moe/wi_up": (L, n, D, F),
        "blocks/l0/moe/wo": (L, n, F, D),
    }


def make_weights(c: dict, seed: int) -> dict:
    """The program's weights as served (bf16): the stacked layers, the
    embedding table and the head.  Each is cast to float32 only where
    it is used."""
    s = tuple(sorted(sizes(c).items()))
    return _weights(W.base_key(seed), s=s)


@functools.partial(jax.jit, static_argnames=("s",))
def _weights(key, *, s: tuple) -> dict:
    s = dict(s)
    shapes = dict(layer_shapes(s), tok_embed=(s["V"], s["D"]),
                  unembed=(s["D"], s["V"]), final_norm=(s["D"],))
    return {p: W.leaf(key, p, shp, jnp.bfloat16) for p, shp in shapes.items()}


def attend(q, k, v, fp8: bool):
    """Causal attention of one sequence: q [S, H, Dh], k and v [S, KV,
    Dh] -> [S, H, Dh], in blocks of ``Q_BLOCK`` queries."""
    S, H, Dh = q.shape
    group = H // k.shape[1]
    k = fq(jnp.repeat(k, group, axis=1), -1, fp8)
    v = fq(jnp.repeat(v, group, axis=1), 0, fp8)
    nb = -(-S // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - S), (0, 0), (0, 0)))
    qb = qb.reshape(nb, Q_BLOCK, H, Dh)

    def block(args):
        i, qs = args
        pos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", fq(qs, -1, fp8), k) / math.sqrt(Dh)
        sc = jnp.where(pos[None, :, None] >= jnp.arange(S)[None, None, :],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", fq(p, -1, fp8), v)

    out = lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(nb * Q_BLOCK, H, Dh)[:S]


def held_weights(probs, k: int, off: int, n: int):
    """[S, n] weight of each held expert for each position: the top-k
    probabilities renormalised over the choices, on the experts chosen
    among ``[off, off + n)``."""
    w, idx = lax.top_k(probs, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.einsum("sk,skn->sn", w, jax.nn.one_hot(idx - off, n))


@functools.partial(jax.jit, static_argnames=("s", "fp8"))
def layer(w: dict, x, *, s: tuple, fp8: bool = False):
    """One layer over ``x`` [n, S, D] float32, a sequence at a time; ``w``
    holds the layer's leaves (bf16)."""
    s = dict(s)
    w = {name.split("/", 2)[-1]: a.astype(jnp.float32) for name, a in w.items()}
    eps = s["eps"]

    def one(x):
        h = fq(rmsnorm(x, w["ln1"], eps), -1, fp8)
        q = jnp.einsum("sd,dhk->shk", h, fq(w["attn/wq"], (0, 1, 2), fp8))
        kk = jnp.einsum("sd,dhk->shk", h, fq(w["attn/wk"], (0, 1, 2), fp8))
        v = jnp.einsum("sd,dhk->shk", h, fq(w["attn/wv"], (0, 1, 2), fp8))
        q = rope(rmsnorm(q, w["attn/q_norm"], eps), s["theta"])
        kk = rope(rmsnorm(kk, w["attn/k_norm"], eps), s["theta"])
        o = attend(q, kk, v, fp8)
        x = x + jnp.einsum("qhd,hdm->qm", fq(o, (1, 2), fp8),
                           fq(w["attn/wo"], (0, 1, 2), fp8))
        h = fq(rmsnorm(x, w["ln2"], eps), -1, fp8)
        return x + moe(w, h, s, fp8)

    return lax.map(one, x)


def moe(w: dict, h, s: dict, fp8: bool = False):
    """The held experts' part of the MoE FFN of ``h`` [S, D] (float32
    leaves ``moe/router``, ``moe/wi_gate``, ``moe/wi_up``, ``moe/wo``)."""
    probs = jax.nn.softmax(h @ fq(w["moe/router"], (0, 1), fp8), axis=-1)
    cw = held_weights(probs, s["k"], s["off"], s["n"])
    g = jnp.einsum("sd,edf->esf", h, fq(w["moe/wi_gate"], (1, 2), fp8))
    u = jnp.einsum("sd,edf->esf", h, fq(w["moe/wi_up"], (1, 2), fp8))
    y = jnp.einsum("esf,efd->esd", fq(jax.nn.silu(g) * u, -1, fp8),
                   fq(w["moe/wo"], (1, 2), fp8))
    return jnp.einsum("se,esd->sd", cw, y)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def head(x, final_norm, unembed, *, eps: float, fp8: bool = False):
    """Logits [n, S, V] of the final hidden states ``x`` [n, S, D]."""
    x = rmsnorm(x, final_norm.astype(jnp.float32), eps)
    return fq(x, -1, fp8) @ fq(unembed.astype(jnp.float32), (0, 1), fp8)


def forward(c: dict, w: dict, seqs: np.ndarray, *, first: int,
            fp8: bool = False) -> jax.Array:
    """seqs: [n, S] tokens -> hidden states [n, S - first, D] after the
    final layer at positions ``first`` onwards (float32), one layer's
    weights cast at a time."""
    st = sizes(c)
    s = tuple(sorted(st.items()))
    x = w["tok_embed"][jnp.asarray(seqs)].astype(jnp.float32)
    for i in range(st["L"]):
        lw = {p: w[p][i] for p in layer_shapes(st)}
        x = layer(lw, x, s=s, fp8=fp8)
    return x[:, first:]


def compare(c: dict, seed: int, prompts: np.ndarray, served: np.ndarray,
            *, control: bool = False) -> dict:
    """Per request (a prompt and the tokens served for it) and per served
    position: ``gap``, how far the served token's reference logit lies
    below the reference's best; ``margin``, how far that best lies above
    its runner-up.  ``first`` and ``last`` [requests, V]: the reference's
    logits at the last prompt position (what prefill computes) and at the
    last served token's (what the last decode step computes).  With
    ``control``, the same of the float8 reference: ``control_gap``, the gap
    of the token it puts first, ``control_first`` and ``control_last``."""
    st = sizes(c)
    P = prompts.shape[1]
    w = make_weights(c, seed)
    seqs = np.concatenate([prompts, served[:, :-1]], axis=1).astype(np.int32)
    out = {"gap": [], "margin": [], "first": [], "last": []}
    if control:
        out.update(control_gap=[], control_first=[], control_last=[])
    with jax.default_matmul_precision("highest"):
        hid = forward(c, w, seqs, first=P - 1)
        ctl = forward(c, w, seqs, first=P - 1, fp8=True) if control else None
        for i in range(0, len(seqs), BLOCK):
            ref = head(hid[i:i + BLOCK], w["final_norm"], w["unembed"],
                       eps=st["eps"])
            top2 = lax.top_k(ref, 2)[0]
            toks = jnp.asarray(served[i:i + BLOCK])[..., None]
            got = jnp.take_along_axis(ref, toks, -1)[..., 0]
            out["gap"].append(np.asarray(top2[..., 0] - got))
            out["margin"].append(np.asarray(top2[..., 0] - top2[..., 1]))
            out["first"].append(np.asarray(ref[:, 0]))
            out["last"].append(np.asarray(ref[:, -1]))
            if control:
                cl = head(ctl[i:i + BLOCK], w["final_norm"], w["unembed"],
                          eps=st["eps"], fp8=True)
                pick = jnp.argmax(cl, axis=-1)[..., None]
                got = jnp.take_along_axis(ref, pick, -1)[..., 0]
                out["control_gap"].append(np.asarray(top2[..., 0] - got))
                out["control_first"].append(np.asarray(cl[:, 0]))
                out["control_last"].append(np.asarray(cl[:, -1]))
                del cl
            del ref
    return {k: np.concatenate(v) for k, v in out.items()}
