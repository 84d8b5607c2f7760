"""``reference/qwen3_share.py`` against an independent numpy forward pass
(float64, written from the equations again), at a small size."""
import jax
import numpy as np
import pytest

from benchmarks.chip.reference import qwen3_share as ref
from benchmarks.chip.tests.cases import SEED
from benchmarks.chip.tests.share_cases import share_config


def rms(x, g, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rotate(x, theta):
    """x [S, heads, Dh]: position i turns the pair (j, j + Dh/2) by
    i * theta^(-2j/Dh)."""
    S, _, Dh = x.shape
    ang = np.arange(S)[:, None] * theta ** (-np.arange(0, Dh, 2) / Dh)
    c, s = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    a, b = x[..., :Dh // 2], x[..., Dh // 2:]
    return np.concatenate([a * c - b * s, a * s + b * c], -1)


def numpy_logits(c, w, seq):
    st = ref.sizes(c)
    L, H, KV, Dh, k = st["L"], st["H"], st["KV"], st["Dh"], st["k"]
    off, n, eps = st["off"], st["n"], st["eps"]
    f = {p: np.asarray(a, np.float64) for p, a in w.items()}
    x = f["tok_embed"][seq]
    S = len(seq)
    for i in range(L):
        g = {p.split("/", 2)[-1]: a[i] for p, a in f.items()
             if p.startswith("blocks/")}
        h = rms(x, g["ln1"], eps)
        q = rotate(rms(np.einsum("sd,dhk->shk", h, g["attn/wq"]),
                       g["attn/q_norm"], eps), st["theta"])
        kk = rotate(rms(np.einsum("sd,dhk->shk", h, g["attn/wk"]),
                        g["attn/k_norm"], eps), st["theta"])
        v = np.einsum("sd,dhk->shk", h, g["attn/wv"])
        o = np.zeros_like(q)
        for head in range(H):
            kv = head // (H // KV)
            sc = q[:, head] @ kk[:, kv].T / np.sqrt(Dh)
            sc[np.triu_indices(S, 1)] = -np.inf
            p = np.exp(sc - sc.max(-1, keepdims=True))
            o[:, head] = (p / p.sum(-1, keepdims=True)) @ v[:, kv]
        x = x + np.einsum("shk,hkd->sd", o, g["attn/wo"])
        h = rms(x, g["ln2"], eps)
        z = h @ g["moe/router"]
        probs = np.exp(z - z.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        y = np.zeros_like(x)
        for t in range(S):
            top = np.argsort(-probs[t])[:k]
            wt = probs[t, top] / probs[t, top].sum()
            for e, we in zip(top, wt):
                if off <= e < off + n:
                    gate = h[t] @ g["moe/wi_gate"][e - off]
                    up = h[t] @ g["moe/wi_up"][e - off]
                    act = gate / (1 + np.exp(-gate)) * up
                    y[t] += we * (act @ g["moe/wo"][e - off])
        x = x + y
    return rms(x, f["final_norm"], eps) @ f["unembed"]


@pytest.mark.parametrize("length", [9, 300])
def test_reference_matches_numpy(length):
    """A sequence shorter than one query block, and one over two blocks
    (the last padded)."""
    c = share_config()
    w = ref.make_weights(c, SEED)
    seq = np.random.default_rng(length).integers(0, c["vocab_size"], length)
    with jax.default_matmul_precision("highest"):
        hid = ref.forward(c, w, seq[None], first=0)
        got = np.asarray(ref.head(hid, w["final_norm"], w["unembed"],
                                  eps=float(c["rms_norm_eps"])))[0]
    want = numpy_logits(c, w, seq)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_held_weights_keep_only_the_held_block():
    """Choices of experts held elsewhere weigh nothing; the held ones keep
    their renormalised top-k weight."""
    probs = np.array([[0.4, 0.3, 0.2, 0.1, 0.0, 0.0]], np.float32)
    cw = np.asarray(ref.held_weights(probs, k=2, off=1, n=2))
    np.testing.assert_allclose(cw, [[0.3 / 0.7, 0.0]], rtol=1e-6)
