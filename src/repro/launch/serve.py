"""Serving launcher: prefill + batched greedy decode on the local device.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --tokens 16
    PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-1b-a400m \
        --full --batch 8 --prompt-len 512 --tokens 32

Local runs default to the reduced smoke config; ``--full`` serves the
published config.  Parameters are initialised on the device, under
``jax.jit``, in the serving dtype (bf16 weights, as the sharded serve steps
in ``launch/steps.py`` hold them).  Both steps are compiled and warmed up
before anything is timed; the decode step donates its cache.
"""
from __future__ import annotations

import argparse
import functools
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..models import api
from ..models.base import ModelConfig
from ..models.scopes import UNEMBED, scope
from .compile_cache import CompileCounter, enable_compile_cache


def serving_config(cfg: ModelConfig) -> ModelConfig:
    """Weights held in the compute dtype (bf16 for every registry arch)."""
    return cfg.replace(param_dtype=cfg.dtype)


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key: jax.Array):
    """Random parameters made on the device in ``cfg.param_dtype``."""
    return api.init(cfg, key)[0]


def make_batch(cfg: ModelConfig, key: jax.Array, batch: int,
               prompt_len: int):
    """Prompt tokens (plus stub image/audio embeddings) drawn from ``key``."""
    out = {"inputs": jax.random.randint(key, (batch, prompt_len), 0,
                                        cfg.vocab_size)}
    if cfg.n_img_tokens > 0:
        out["img_embeds"] = jax.random.normal(
            key, (batch, cfg.n_img_tokens, cfg.d_model), cfg.dtype)
    if cfg.is_encoder_decoder:
        out["enc_embeds"] = jax.random.normal(
            key, (batch, cfg.enc_frames, cfg.d_model), cfg.dtype)
    return out


def make_steps(cfg: ModelConfig, s_max: int):
    """Jitted ``prefill(params, batch) -> (logits, caches)`` and
    ``decode(params, token, caches) -> (next_token, logits, caches)``;
    decode donates ``caches`` and picks the next token greedily."""
    def prefill(params, batch):
        return api.prefill(cfg, params, batch, s_max)

    def decode(params, token, caches):
        logits, caches = api.decode_step(cfg, params, token, caches)
        with scope(UNEMBED):
            nxt = jnp.argmax(logits, axis=-1).astype(token.dtype)
        return nxt, logits, caches

    return jax.jit(prefill), jax.jit(decode, donate_argnums=(2,))


@dataclass
class ServeResult:
    tokens: np.ndarray          # [B, new_tokens] greedy tokens
    prefill_logits: np.ndarray  # [B, V] f32, last prompt position
    last_logits: np.ndarray     # [B, V] f32, final decode step
    compile_s: float            # prefill + decode compile
    prefill_s: float            # one prefill, compiled and warm
    decode_step_s: float        # mean of the timed decode steps


def serve(cfg: ModelConfig, *, batch: int, prompt_len: int,
          new_tokens: int, seed: int = 0, log=print) -> ServeResult:
    """Serve ``batch`` random prompts and greedily decode ``new_tokens``.

    The first token comes from prefill, the other ``new_tokens - 1`` from
    decode steps.  Host clock around work that ends in
    ``block_until_ready``; compilation and one warm-up call of each step
    come first and are not in the step times.
    """
    if new_tokens < 2:
        raise ValueError(f"new_tokens must be >= 2, got {new_tokens}")
    cfg = serving_config(cfg)
    key = jax.random.PRNGKey(seed)
    with CompileCounter() as setup:
        t0 = time.perf_counter()
        params = jax.block_until_ready(init_params(cfg, key))
        log(f"init_s {time.perf_counter() - t0:.3f}")
        prompts = make_batch(cfg, jax.random.fold_in(key, 1), batch,
                             prompt_len)
        prefill, decode = make_steps(cfg, prompt_len + new_tokens)

        t0 = time.perf_counter()
        _, caches_s = jax.eval_shape(prefill, params, prompts)
        prefill = prefill.lower(params, prompts).compile()
        tok_s = jax.ShapeDtypeStruct((batch,), jnp.int32)
        decode = decode.lower(params, tok_s, caches_s).compile()
        compile_s = time.perf_counter() - t0
        log(f"compile_s {compile_s:.3f}")

        # Warm-up: one call of each compiled step, and the first token's
        # argmax, which would otherwise compile inside the timed prefill.
        first_logits, caches = prefill(params, prompts)
        tok = jnp.argmax(first_logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(decode(params, tok, caches))
    log(f"setup_compiles {setup}")

    # A compile or trace counted here is a retrace inside the timed steps.
    with CompileCounter() as timed:
        t0 = time.perf_counter()
        first_logits, caches = prefill(params, prompts)
        tok = jnp.argmax(first_logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready((tok, caches))
        prefill_s = time.perf_counter() - t0

        toks = [tok]
        logits = first_logits
        t0 = time.perf_counter()
        for _ in range(new_tokens - 1):
            tok, logits, caches = decode(params, tok, caches)
            toks.append(tok)
        jax.block_until_ready((toks, logits))
        decode_step_s = (time.perf_counter() - t0) / (new_tokens - 1)
    log(f"prefill_s {prefill_s:.6f}")
    log(f"decode_step_s {decode_step_s:.6f}")
    log(f"timed_compiles {timed}")

    return ServeResult(
        tokens=np.stack([np.asarray(t) for t in toks], axis=1),
        prefill_logits=np.asarray(first_logits, np.float32),
        last_logits=np.asarray(logits, np.float32),
        compile_s=compile_s, prefill_s=prefill_s,
        decode_step_s=decode_step_s)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=configs.list_archs())
    ap.add_argument("--full", action="store_true",
                    help="serve the published config, not the smoke one")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = (configs.get_config(args.arch) if args.full
           else configs.get_smoke_config(args.arch))
    res = serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
                new_tokens=args.tokens, seed=args.seed)
    print(f"{args.arch}: decoded {args.tokens} tok x{args.batch} on "
          f"{jax.devices()[0].device_kind} "
          f"({args.batch / res.decode_step_s:.1f} tok/s steady decode)")
    print("sequence 0:", res.tokens[0].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
