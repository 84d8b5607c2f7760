"""The decode step writes its token into the stacked KV cache in place.

``transformer.decode_step`` carries the caches through the block scan and
writes one token per attention layer.  Here it runs against the form it
replaced, kept as a reference: a scan over ``(blocks, caches)`` that
writes the token into each layer's cache and attends over the result,
emitting new caches.  Six greedy steps after a prefill, on the smoke
configurations of a MoE, qwen3-moe's held-expert share (qk-norm), a
hybrid attention+SSM stack, a sliding window, and an unscanned stack.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro import configs
from repro.launch.serve import init_params, make_steps, serving_config
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.moe import moe_ffn
from repro.models.ssd import ssm_decode
from test_qwen3_share import small

B, P, STEPS = 3, 12, 6
# float32: the sides differ in summation order only (3e-6 seen).
# bfloat16, |program - reference| / |reference| over each step's logits
# (Frobenius norms): the sides round the attention's value sum at
# different points (0.005-0.008 seen; either side is 0.007-0.009 off a
# float32 run of the same weights).
TOL = {"float32": 1e-4, "bfloat16": 0.02}


def ref_attention_decode(p, cfg, x, cache, window):
    """Write the token at ``length``, then attend over the whole cache."""
    pos = jnp.broadcast_to(cache.length, (x.shape[0], 1))
    q, k, v = L._project_qkv(p, cfg, x, pos, rope=True)
    at = (0, 0, cache.length, 0)
    kc = lax.dynamic_update_slice(cache.k, k.swapaxes(1, 2), at)
    vc = lax.dynamic_update_slice(cache.v, v.swapaxes(1, 2), at)
    j = jnp.arange(kc.shape[2])
    valid = j <= cache.length
    if window > 0:
        valid &= j > cache.length - window
    out = L._sdpa(cfg, q, kc.swapaxes(1, 2), vc.swapaxes(1, 2),
                  valid[None, None, None])
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, L.KVCache(k=kc, v=vc, length=cache.length + 1)


def ref_decode_step(cfg, params, token, caches):
    """Scan over (blocks, caches) as inputs, the new caches as outputs."""
    def block(x, bc):
        bp, cache = bc
        new = {}
        for pos, kind in enumerate(cfg.pattern):
            p, c = bp[f"l{pos}"], cache[f"l{pos}"]
            h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
            if kind == "attn":
                h, new[f"l{pos}"] = ref_attention_decode(
                    p["attn"], cfg, h, c, cfg.sliding_window)
            else:
                h, new[f"l{pos}"] = ssm_decode(p["ssm"], cfg, h, c)
            x = x + h
            if not T._has_ffn(cfg):
                continue
            h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
            if T._layer_is_moe(cfg, pos):
                x = x + moe_ffn(p["moe"], cfg, h)[0]
            else:
                x = x + L.mlp(p["mlp"], h, n_chunks=cfg.ffn_chunks)
        return x, new

    x = L.embed(params, cfg, token[:, None])
    x, caches = lax.scan(block, x, (params["blocks"], caches))
    return L.unembed(params, cfg, x)[:, 0], caches


CONFIGS = {
    "granite_moe": lambda: configs.get_smoke_config("granite-moe-1b-a400m"),
    "qwen3_share": lambda: small("bfloat16")[1],
    "hybrid_ssm": lambda: configs.get_smoke_config(
        "jamba-1.5-large-398b").replace(n_layers=16),      # two blocks
    "sliding_window": lambda: configs.get_smoke_config(
        "qwen2-1.5b").replace(sliding_window=5),
    "granite_unscanned": lambda: configs.get_smoke_config(
        "granite-moe-1b-a400m").replace(scan_layers=False),
}
# Each configuration in float32, and in the serving dtype where greedy
# tokens can be compared: the hybrid's bf16 SSM state is 0.6-0.7 off its
# float32 run on either side, so two roundings of it pick different tokens.
CASES = ([(name, "float32") for name in CONFIGS]
         + [(name, "bfloat16") for name in CONFIGS if name != "hybrid_ssm"])


def _err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("case,dtype", CASES)
def test_in_place_decode_matches_the_scan_over_caches(case, dtype):
    cfg = serving_config(CONFIGS[case]().replace(dtype=dtype))
    key = jax.random.PRNGKey(17)
    params = init_params(cfg, key)
    prompts = jax.random.randint(jax.random.fold_in(key, 1), (B, P), 0,
                                 cfg.vocab_size)
    prefill, decode = make_steps(cfg, P + STEPS)
    ref = jax.jit(functools.partial(ref_decode_step, cfg))
    logits, caches = prefill(params, {"inputs": prompts})
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), caches)
    kv = caches[f"l{cfg.pattern.index('attn')}"]
    assert kv.k.shape == (cfg.n_blocks, B, cfg.n_kv_heads, P + STEPS,
                          cfg.d_head)
    ref_caches = jax.tree.map(jnp.copy, caches)     # ``decode`` donates
    tok = ref_tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(STEPS):
        tok, logits, caches = decode(params, tok, caches)
        ref_logits, ref_caches = ref(params, ref_tok, ref_caches)
        ref_tok = jnp.argmax(ref_logits, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(ref_tok))
        assert _err(logits, ref_logits) < TOL[dtype]
    assert jax.tree.structure(caches) == jax.tree.structure(ref_caches)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), caches) == shapes
    for got, want in zip(jax.tree.leaves(caches), jax.tree.leaves(ref_caches)):
        assert _err(got, want) < TOL[dtype]
