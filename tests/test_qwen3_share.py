"""qwen3-moe-235b-a22b as one expert-parallel rank's share, on the serve
path, against the plain float32 reference (``benchmarks/chip/reference/
qwen3_share.py``) at a small size with seeded random weights.

- prefill, then decode steps through the caches, give the reference's
  full-forward logits;
- the held-expert layer's outputs over all the ranks of the deployment
  add up to the uncut layer (every expert held);
- routing skewed so that every choice lands on the held block (the
  dropless worst case, over several blocks of tokens) against the one-hot
  oracle of ``tests/test_moe_ep.py``.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import weights as W
from benchmarks.chip.reference import qwen3_share as ref
from repro import configs
from repro.configs.qwen3_moe_235b_a22b import share
from repro.launch.serve import init_params, make_steps
from repro.models import moe
from repro.models.base import ParamBuilder
from test_moe_ep import onehot_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 16
RANKS = 4                  # experts 4 a rank of 16: offsets 0, 4, 8, 12
# |program - reference| / |reference| over all the compared logits
# (Frobenius norms; one routing choice that flips between the two
# precisions moves a few logits far, so a largest-element bound would be
# set by that flip).  float32: the sides differ in summation order only
# (3e-7 seen on three prompt sets).  bfloat16: the program's activations
# round to 8 bits between ops (0.005-0.017 seen); the float8 control reads
# 0.058-0.075 on the same prompts, so a program one precision below the
# configuration's fails this bound.
TOL = {"float32": 1e-5, "bfloat16": 0.03}


def small(dtype: str, offset: int = 4):
    """The cell's configuration at a small size: 3 layers, a router over
    16 experts top-4, the 4 experts from ``offset`` held."""
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           "qwen3-moe-235b-a22b-ep16.json")) as f:
        c = json.load(f)
    c.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=8,
             num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
             router_experts=16, num_experts=4, expert_offset=offset,
             num_experts_per_tok=4, vocab_size=512)
    base = configs.get_config(c["arch"]).replace(
        n_layers=3, d_model=64, n_heads=8, n_kv_heads=2, d_head=16,
        d_ff_expert=32, n_experts=16, top_k=4, vocab_size=512,
        dtype=dtype, param_dtype=dtype)
    return c, share(4, offset, base)


def params_from_seed(cfg):
    """The benchmark's weights (bf16 draws, as the reference reads them)
    in the program's tree and dtype."""
    key = W.base_key(SEED)
    shapes = jax.eval_shape(lambda k: init_params(
        cfg.replace(param_dtype="bfloat16"), k), key)
    return jax.tree.map(lambda a: a.astype(cfg.dtype), W.fill(shapes, key))


def serve(cfg, params, prompts, steps):
    """Prefill, then ``steps`` greedy decode steps through the caches;
    logits [B, steps + 1, V] float32 and the tokens fed back."""
    B, P = prompts.shape
    prefill, decode = make_steps(cfg, P + steps + 1)
    logits, caches = prefill(params, {"inputs": jnp.asarray(prompts)})
    got, toks = [logits], [jnp.argmax(logits, -1).astype(jnp.int32)]
    for _ in range(steps):
        tok, logits, caches = decode(params, toks[-1], caches)
        got.append(logits)
        toks.append(tok)
    return (np.stack([np.asarray(g, np.float32) for g in got], 1),
            np.stack([np.asarray(t) for t in toks[:-1]], 1))


def rel_err(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def reference_logits(c, prompts, fed, fp8=False):
    """The reference's logits at the last prompt position and at each
    token ``fed`` back after it."""
    P = prompts.shape[1]
    w = ref.make_weights(c, SEED)
    seqs = np.concatenate([prompts, fed], axis=1).astype(np.int32)
    hid = ref.forward(c, w, seqs, first=P - 1, fp8=fp8)
    return np.asarray(ref.head(hid, w["final_norm"], w["unembed"],
                               eps=float(c["rms_norm_eps"]), fp8=fp8))


@pytest.fixture
def blocks_of_16(monkeypatch):
    """Held-expert blocks of 16 tokens: prefill runs several."""
    monkeypatch.setattr(moe, "HELD_BLOCK_TOKENS", 16)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_and_decode_match_the_reference(dtype, blocks_of_16):
    """Prefill over 3 prompts of 40 tokens (8 blocks of the held-expert
    layer, the last padded), then 5 decode steps through the caches,
    against the reference's full forward pass at every position that
    predicts a served token."""
    c, cfg = small(dtype)
    params = params_from_seed(cfg)
    prompts = np.random.default_rng(0).integers(0, 512, (3, 40),
                                                dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got, fed = serve(cfg, params, prompts, 5)
        want = reference_logits(c, prompts, fed)
        if dtype == "bfloat16":
            ctl = reference_logits(c, prompts, fed, fp8=True)
            assert rel_err(ctl, want) > TOL[dtype]
    assert got.shape == want.shape == (3, 6, 512)
    assert rel_err(got, want) <= TOL[dtype]


def test_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """Each rank's held-expert layer, over the same tokens and router, sums
    with the other ranks' to the reference's layer with every expert
    held (16 experts, 4 ranks of 4)."""
    c, cfg = small("float32", offset=0)
    b = ParamBuilder(jax.random.PRNGKey(1), "float32")
    moe.init_moe(b, cfg.replace(experts_held=0), "moe")
    p = b.params["moe"]                          # all 16 experts
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 21, cfg.d_model))
    s = dict(ref.sizes(dict(c, num_experts=16, expert_offset=0)))
    with jax.default_matmul_precision("highest"):
        total = 0
        for r in range(RANKS):
            held = slice(4 * r, 4 * r + 4)
            pr = dict(p, wi_gate=p["wi_gate"][held], wi_up=p["wi_up"][held],
                      wo=p["wo"][held])
            y, _ = jax.jit(lambda pr, x, cfg=share(4, 4 * r, cfg):
                           moe.moe_held(pr, cfg, x))(pr, x)
            total = total + np.asarray(y)
        w = {f"moe/{k}": v for k, v in p.items()}
        uncut = np.stack([np.asarray(ref.moe(w, xs, s)) for xs in x])
    assert np.abs(total - uncut).max() <= 1e-5 * np.abs(uncut).max()


@pytest.mark.parametrize("block", [8, 64])
def test_every_choice_on_the_held_block(block, monkeypatch):
    """A router that sends each token's 4 choices to the 4 held experts:
    every row lands here (no capacity, nothing dropped), in 3 blocks of 8
    tokens (the last padded) or in one, as the one-hot oracle computes."""
    _, cfg = small("float32")
    monkeypatch.setattr(moe, "HELD_BLOCK_TOKENS", block)
    b = ParamBuilder(jax.random.PRNGKey(3), "float32")
    moe.init_moe(b, cfg, "moe")
    p = dict(b.params["moe"])
    r = -np.ones((cfg.d_model, cfg.n_experts), np.float32)
    r[:, 4:8] = 1.0 + 0.1 * np.arange(4)         # experts 4-7, distinct
    p["router"] = jnp.asarray(r)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4),
                                  (1, 20, cfg.d_model))) + 0.1
    with jax.default_matmul_precision("highest"):
        y, _ = jax.jit(lambda p, x: moe.moe_held(p, cfg, x))(p, x)
        idx, w, _ = moe.route(p, cfg, x[0])
        meta = idx - cfg.expert_offset + 1
        assert np.asarray((meta >= 1) & (meta <= 4)).all()
        rows = jnp.repeat(x[0], cfg.top_k, axis=0)
        want = onehot_experts(p, rows, meta, 4).reshape(20, 4, -1)
        want = (want * w[..., None]).sum(1)
    assert np.abs(np.asarray(y[0]) - np.asarray(want)).max() <= \
        1e-5 * np.abs(np.asarray(want)).max()


def test_prefill_attention_over_groups_of_sequences(monkeypatch):
    """A score bound that lets prefill attention take 2 of 4 prompts at a
    time: the grouped prefill, and decode steps through the caches it
    fills, give the reference's logits as the whole batch at once does."""
    from repro.models import layers

    c, cfg = small("float32")
    params = params_from_seed(cfg)
    prompts = np.random.default_rng(1).integers(0, 512, (4, 24),
                                                dtype=np.int32)
    per_seq = cfg.n_heads * 24 * 24 * 4          # float32 scores, 1 chunk
    monkeypatch.setattr(layers, "PREFILL_SCORE_BYTES", 2 * per_seq)
    seen = []
    inner = layers._prefill_attn

    def spy(p, cfg, x, window):
        seen.append(x.shape[0])
        return inner(p, cfg, x, window)

    monkeypatch.setattr(layers, "_prefill_attn", spy)
    with jax.default_matmul_precision("highest"):
        got, fed = serve(cfg, params, prompts, 3)
        want = reference_logits(c, prompts, fed)
    assert set(seen) == {2}                      # every layer, in groups
    assert got.shape == want.shape == (4, 4, 512)
    assert rel_err(got, want) <= TOL["float32"]
