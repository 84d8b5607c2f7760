"""Closed-loop serving rounds of one expert-parallel rank's share of
Qwen3-MoE through ``repro.launch.serve``.

The rounds, the end-to-end metrics and the checks of ``prefill_rounds``
(``served_gap``, ``prefill_err`` and ``decode_err``), with the configuration's share:
each layer's router keeps all its published experts and this chip holds
the experts ``[expert_offset, expert_offset + num_experts)``
(``models/moe.py:moe_held``).  The plain float32 reference is
``reference/qwen3_share.py``, given the same share.

``--trace 1`` also records engagement (and prints it on stderr): per
layer, the rows the held experts receive in the traced round's prefill
and in its first decode step, and the share of the router's choices held
here.  They are counted after the traced slice, by a replay of the same
round through the program's own layers (``held_rows``).
"""
from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.chip import weights as W
from benchmarks.chip.common import check_sizes, module_name
from benchmarks.chip.drivers import prefill_rounds, serve_rounds
from benchmarks.chip.reference import qwen3_share as ref


def held_rows(cfg, params, tokens, caches=None, s_max: int = 0):
    """Per layer, the rows each held expert receives when ``tokens`` go
    through the program's layers: a prefill of ``tokens`` [B, S] (no
    ``caches``), or a decode step of ``tokens`` [B] on ``caches``."""
    from repro.models import layers as L
    from repro.models import moe

    eps, n = cfg.norm_eps, cfg.experts_held

    def step(x, lc):
        p, cache = lc
        p = p["l0"]
        h = L.rmsnorm(x, p["ln1"], eps)
        if cache is None:
            a, _ = L.attention_prefill(p["attn"], cfg, h, s_max)
        else:
            a, _ = L.attention_decode(p["attn"], cfg, h, cache["l0"])
        x = x + a
        h = L.rmsnorm(x, p["ln2"], eps)
        idx, _, _ = moe.route(p["moe"], cfg, h.reshape(-1, h.shape[-1]))
        local = idx - cfg.expert_offset
        local = jnp.where((local >= 0) & (local < n), local, n)
        rows = jnp.bincount(local.reshape(-1), length=n + 1)[:n]
        return x + moe.moe_ffn(p["moe"], cfg, h)[0], rows

    x = L.embed(params, cfg, tokens if caches is None else tokens[:, None])
    _, rows = lax.scan(step, x, (params["blocks"], caches))
    return rows


class Driver(prefill_rounds.Driver):
    def setup(self) -> None:
        from repro import configs
        from repro.launch.serve import init_params, make_steps, serving_config

        c = self.ctx.config
        cfg = serving_config(configs.get_config(c["arch"]).replace(
            **c["overrides"]))
        check_sizes(cfg, {
            "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"], "d_head": c["head_dim"],
            "qk_norm": True, "rope_theta": c["rope_theta"],
            "norm_eps": c["rms_norm_eps"],
            "d_ff_expert": c["moe_intermediate_size"],
            "n_experts": c["router_experts"],
            "experts_held": c["num_experts"],
            "expert_offset": c["expert_offset"],
            "top_k": c["num_experts_per_tok"], "vocab_size": c["vocab_size"],
            "dtype": c["dtype"]})
        self.cfg = cfg
        self.dev = dev = self.ctx.devices[0]
        key = W.base_key(self.ctx.seed)
        shapes = jax.eval_shape(functools.partial(init_params, cfg), key)
        t0 = time.perf_counter()
        with jax.default_device(dev):
            self.params = jax.block_until_ready(
                jax.jit(functools.partial(W.fill, shapes))(key))
        t1 = time.perf_counter()

        prefill, decode = make_steps(cfg, self.P + self.G)
        batch = {"inputs": jax.ShapeDtypeStruct((self.B, self.P), jnp.int32)}
        logits_s, caches_s = jax.eval_shape(prefill, self.params, batch)
        tok_s = jax.ShapeDtypeStruct((self.B,), jnp.int32)
        self.prefill = prefill.lower(self.params, batch).compile()
        self.decode = decode.lower(self.params, tok_s, caches_s).compile()
        self.first = jax.jit(serve_rounds.first_token).lower(
            logits_s).compile()
        self.programs = {"prefill": module_name(self.prefill),
                         "decode": module_name(self.decode)}
        t2 = time.perf_counter()
        self.warm_up()
        self.setup_detail = {"jax_init_s": t0 - self.ctx.t_start,
                             "weights_s": t1 - t0, "compile_s": t2 - t1,
                             "warm_up_s": time.perf_counter() - t2}

    def traced(self) -> dict:
        """``serve_rounds``' traced slice, then the engagement of the traced
        round (round 0): ``held_rows_prefill`` and ``held_rows_decode``
        [layers][held experts], and ``held_share``, the share of the
        router's choices over both that land on the held experts."""
        rec = super().traced()
        cfg, s_max = self.cfg, self.P + self.G
        prompts = jax.device_put(self.prompts(0), self.dev)
        logits, caches = self.prefill(self.params, {"inputs": prompts})
        dec = jax.jit(functools.partial(held_rows, cfg))(
            self.params, self.first(logits), caches)
        dec = np.asarray(dec)
        del logits, caches
        pre = np.asarray(jax.jit(functools.partial(
            held_rows, cfg, s_max=s_max))(self.params, prompts))
        choices = cfg.n_layers * cfg.top_k * self.B * (self.P + 1)
        got = {"held_rows_prefill": pre.tolist(),
               "held_rows_decode": dec.tolist(),
               "held_share": float(pre.sum() + dec.sum()) / choices}
        print(f"engagement {json.dumps(got)}", file=sys.stderr, flush=True)
        rec.update(got)
        return rec

    def reference(self, prompts, toks, control: bool) -> dict:
        return ref.compare(self.ctx.config, self.ctx.seed, prompts, toks,
                           control=control)
