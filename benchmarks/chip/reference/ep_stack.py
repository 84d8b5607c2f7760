"""Plain float32 reference of a stack of expert-parallel MoE layers.

Each layer: router logits over all experts, softmax, the top-k
renormalised over the choices, SwiGLU experts, and a residual add.  It
follows the program's drop rule: the tokens of each source chip are
counted in (token, choice) order per destination chip, which has
``max(8, int(T * k * cf / E)) * E / n_chips`` slots for each source, and a
choice past them adds nothing.  All experts run over all tokens in
chunks and the unselected ones are weighted zero, so nothing here depends
on how the program dispatches.

It also returns each token's routing margin: the least, over the layers,
gap between the k-th and the (k+1)-th router logit.  Where that gap is
within rounding of the program's bf16 activations, the two sides may
choose different experts for the token, and no precision settles which
is right; the harness leaves such tokens out of the comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.chip import weights as W
from benchmarks.chip.reference.granite import fq

EXPERT_CHUNK = 16


def sizes(c: dict) -> dict:
    return dict(L=c["num_hidden_layers"], D=c["hidden_size"],
                F=c["moe_intermediate_size"], E=c["num_experts"],
                k=c["num_experts_per_tok"], cf=float(c["capacity_factor"]))


@functools.partial(jax.jit, static_argnames=("s", "layer"))
def layer_weights(key, *, s: tuple, layer: int) -> dict:
    """One layer's weights as drawn (bf16); ``moe_layer`` casts them to
    float32 a chunk of experts at a time, which is exact and keeps a
    layer of 128 published-width experts within one chip's memory."""
    s = dict(s)
    D, F, E = s["D"], s["F"], s["E"]
    shapes = {"router": (D, E), "wi_gate": (E, D, F), "wi_up": (E, D, F),
              "wo": (E, F, D)}
    return {n: W.leaf(key, f"l{layer}/{n}", shp, jnp.bfloat16)
            for n, shp in shapes.items()}


@functools.partial(jax.jit, static_argnames=("s", "n_chips", "fp8"))
def moe_layer(w, x, *, s: tuple, n_chips: int, fp8: bool = False):
    """x: [N, D] (chip-major rows) -> (x + moe(x), margin [N])."""
    s = dict(s)
    N, D = x.shape
    E, k = s["E"], s["k"]
    T, e_loc = N // n_chips, E // n_chips
    cap = max(8, int(T * k * s["cf"] / E)) * e_loc
    xq = fq(x, -1, fp8)
    logits = xq @ fq(w["router"].astype(jnp.float32), (0, 1), fp8)
    top, idx = lax.top_k(logits, k + 1)
    margin = top[:, k - 1] - top[:, k]
    probs = jax.nn.softmax(logits, axis=-1)
    wk = jnp.take_along_axis(probs, idx[:, :k], axis=1)
    wk = wk / jnp.sum(wk, axis=-1, keepdims=True)
    dest = (idx[:, :k] // e_loc).reshape(n_chips, T * k)
    oh = jax.nn.one_hot(dest, n_chips, dtype=jnp.int32)         # [n, T*k, n]
    pos = jnp.sum((jnp.cumsum(oh, axis=1) - oh) * oh, axis=-1)
    keep = (pos < cap).reshape(N, k)
    cw = jnp.einsum("nk,nke->ne", wk * keep,
                    jax.nn.one_hot(idx[:, :k], E, dtype=jnp.float32))

    nc = E // EXPERT_CHUNK
    chunks = (cw.T.reshape(nc, EXPERT_CHUNK, N),
              w["wi_gate"].reshape(nc, EXPERT_CHUNK, D, -1),
              w["wi_up"].reshape(nc, EXPERT_CHUNK, D, -1),
              w["wo"].reshape(nc, EXPERT_CHUNK, -1, D))

    def chunk(y, cs):
        c_w, wg, wu, wo = cs
        wg, wu, wo = (a.astype(jnp.float32) for a in (wg, wu, wo))
        g = jnp.einsum("nd,edf->enf", xq, fq(wg, (1, 2), fp8))
        u = jnp.einsum("nd,edf->enf", xq, fq(wu, (1, 2), fp8))
        a = fq(jax.nn.silu(g) * u, -1, fp8)
        return y + jnp.einsum("en,enf,efd->nd", c_w, a,
                              fq(wo, (1, 2), fp8)), None

    y, _ = lax.scan(chunk, jnp.zeros_like(x), chunks)
    return x + y, margin


def run(c: dict, seed: int, xs, n_chips: int, *, fp8: bool = False):
    """xs: input batches, each [N, D] -> (outputs, least margins), one of
    each per batch.  Each layer's weights are drawn once for all."""
    s = tuple(sorted(sizes(c).items()))
    key = W.base_key(seed)
    xs = [jnp.asarray(x, jnp.float32) for x in xs]
    margins = [jnp.full((x.shape[0],), jnp.inf, jnp.float32) for x in xs]
    with jax.default_matmul_precision("highest"):
        for layer in range(dict(s)["L"]):
            w = layer_weights(key, s=s, layer=layer)
            for i, x in enumerate(xs):
                xs[i], m = moe_layer(w, x, s=s, n_chips=n_chips, fp8=fp8)
                margins[i] = jnp.minimum(margins[i], m)
            del w
    return [np.asarray(x) for x in xs], [np.asarray(m) for m in margins]


def row_errors(out: np.ndarray, ref: np.ndarray, x0: np.ndarray) -> np.ndarray:
    """Per row, |(out - x0) - (ref - x0)| / |ref - x0|: the error of what
    the layers added, relative to what the reference adds."""
    d_ref = ref.astype(np.float64) - x0
    d_out = out.astype(np.float64) - x0
    num = np.linalg.norm(d_out - d_ref, axis=1)
    return num / np.maximum(np.linalg.norm(d_ref, axis=1), 1e-30)
