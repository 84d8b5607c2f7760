"""``moe_gather`` with and without its capacity buffer.

Where the capacity C is at least the sequence length S, no sequence can
overflow an expert, and ``moe_gather`` runs each expert over the tokens
themselves (slot = token) with a dense combine weight.  Here it runs
against the capacity path it replaced there, kept as the reference: the
per-sequence scatter into ``[B, E, C, D]``, the experts over every slot,
the gather back and the weighted sum over each token's choices.  Where
S > C ``moe_gather`` still takes the capacity path, and must drop the
same choices as the reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro import configs
from repro.models import moe
from repro.models.base import ParamBuilder

# Largest |program - reference| over the reference's largest |y|: the two
# sum a token's expert outputs in another order (float32).
TOL = 1e-5
# (batch, sequence length, router forced onto experts 0 and 1)
CASES = {"decode_b1": (1, 1, False), "decode_b3": (3, 1, False),
         "decode_b64": (64, 1, False), "prefill_s8": (2, 8, False),
         "over_capacity": (2, 16, True)}


def capacity_moe(p, cfg, x):
    """The capacity path: rows scattered per sequence into [B, E, C, D],
    choices past C dropped, every slot through its expert, gathered back."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = moe._capacity(cfg, S)
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, k)
    w = (w / jnp.sum(w, axis=-1, keepdims=True)).astype(x.dtype)
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                          axis=2), axis=(0, 1))
    aux = E * jnp.sum(me * ce) / k
    a = idx.reshape(B, S * k)
    onehot = jax.nn.one_hot(a, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=1) - onehot
    pos = jnp.take_along_axis(pos, a[..., None], axis=2)[..., 0]
    keep = pos < C
    safe_a = jnp.where(keep, a, 0)
    safe_pos = jnp.where(keep, pos, C - 1)
    xr = jnp.broadcast_to(x[:, :, None, :], (B, S, k, D)).reshape(B, S * k, D)
    xr = jnp.where(keep[..., None], xr, 0).astype(x.dtype)
    buf = jax.vmap(lambda r, a_r, p_r: jnp.zeros((E, C, D), x.dtype)
                   .at[a_r, p_r].add(r))(xr, safe_a, safe_pos)
    out_e = moe._gather_experts(p, cfg, buf, x.dtype)
    gathered = jax.vmap(lambda o, a_r, p_r: o[a_r, p_r])(out_e, safe_a,
                                                         safe_pos)
    gathered = jnp.where(keep[..., None], gathered, 0)
    y = (gathered.reshape(B, S, k, D) * w[..., None]).sum(axis=2)
    return y, aux


def inputs(B, S, forced):
    cfg = configs.get_smoke_config("granite-moe-1b-a400m").replace(
        dtype="float32")
    b = ParamBuilder(jax.random.PRNGKey(0), cfg.dtype)
    moe.init_moe(b, cfg, "moe")
    p = dict(b.params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32)
    if forced:
        # All-positive tokens against a router whose columns 0 and 1 are
        # the largest: every token picks experts 0 and 1.
        x = jnp.abs(x) + 0.1
        r = np.zeros((cfg.d_model, cfg.n_experts), np.float32)
        r[:, 0], r[:, 1] = 1.0, 0.9
        p["router"] = jnp.asarray(r)
    return cfg, p, x


@pytest.mark.parametrize("case", list(CASES))
def test_moe_gather_matches_the_capacity_path(case):
    B, S, forced = CASES[case]
    cfg, p, x = inputs(B, S, forced)
    C = moe._capacity(cfg, S)
    assert (C < S) == forced
    step = jax.jit(lambda p, x: moe.moe_gather(p, cfg, x))
    # The capacity scatter is there exactly where S > C.
    assert ("scatter" in step.lower(p, x).as_text()) == (C < S)
    y, aux = step(p, x)
    want, want_aux = jax.jit(lambda p, x: capacity_moe(p, cfg, x))(p, x)
    y, want = np.asarray(y), np.asarray(want)
    assert np.abs(y - want).max() <= TOL * np.abs(want).max()
    assert np.isclose(float(aux), float(want_aux), rtol=TOL)
    if forced:
        # Experts 0 and 1 take the first C tokens of each sequence; the
        # rest lose both choices and add nothing.
        assert np.all(y[:, C:] == 0)
        assert np.all(np.abs(y[:, :C]).max(axis=-1) > 0)
