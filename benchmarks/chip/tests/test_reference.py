"""The plain float32 references against the program, at small sizes."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import weights as W
from benchmarks.chip.reference import granite as gref
from benchmarks.chip.tests.cases import CHIP, SEED, granite_config


def test_granite_reference_matches_prefill_and_decode():
    """Program prefill, then decode steps through its cache, in float32,
    give the reference's logits at the same positions."""
    from repro import configs
    from repro.launch.serve import init_params, make_steps

    c = granite_config()
    cfg = configs.get_config(c["arch"]).replace(
        dtype="float32", param_dtype="float32", **c["overrides"])
    key = W.base_key(SEED)
    shapes = jax.eval_shape(lambda k: init_params(
        cfg.replace(param_dtype="bfloat16"), k), key)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          W.fill(shapes, key))
    B, P, G = 3, 24, 6
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (B, P), dtype=np.int32)
    prefill, decode = make_steps(cfg, P + G)
    with jax.default_matmul_precision("highest"):
        logits, caches = prefill(params, {"inputs": jnp.asarray(prompts)})
        got = [np.asarray(logits)]
        toks = [np.asarray(jnp.argmax(logits, -1), np.int32)]
        tok = jnp.asarray(toks[-1])
        for _ in range(G - 1):
            tok, logits, caches = decode(params, tok, caches)
            got.append(np.asarray(logits))
            toks.append(np.asarray(tok))
        w = gref.make_weights(c, SEED)
        s = tuple(sorted(gref.sizes(c).items()))
        for b in range(B):
            seq = np.concatenate([prompts[b], [t[b] for t in toks[:-1]]])
            want = np.asarray(gref.served_logits(
                w, jnp.asarray(seq)[None], s=s, prompt_len=P))[0]
            have = np.stack([g[b] for g in got])
            assert np.abs(have - want).max() <= 1e-4 * np.abs(want).max()


def test_granite_capacity_drop_followed():
    """A prompt whose choices overflow an expert's slots: the reference
    drops the same choices the program does (logits still agree)."""
    c = granite_config()
    probs = jnp.zeros((10, 8)).at[:, 0].set(0.9).at[:, 1].set(0.1)
    cw = gref.combine_weights(probs, 2, prompt_len=6, cap=4)
    # Expert 0 is every position's first choice: prompt positions 4 and 5
    # find its 4 slots taken; positions after the prompt are never dropped.
    assert np.asarray(cw[:4, 0]).min() > 0
    assert np.asarray(cw[4:6, 0]).max() == 0
    assert np.asarray(cw[6:, 0]).min() > 0
    assert c["capacity_factor"] == 1.25


def test_ep_reference_matches_moe_block_ep_on_four_devices():
    root = CHIP.parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{root / 'src'}{os.pathsep}{root}")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.chip.tests.cases", "reference"],
        cwd=root, env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["gap"] < 1e-4


@pytest.mark.parametrize("fp8", [False, True])
def test_fake_quantize(fp8):
    x = jnp.linspace(-3.0, 3.0, 101).reshape(1, -1)
    q = gref.fq(x, -1, fp8)
    err = float(jnp.abs(q - x).max())
    assert (err == 0.0) if not fp8 else (0 < err < 0.2)
