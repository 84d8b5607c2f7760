"""Small cells of ``qwen3_share_decode`` and ``granite_prefill`` for the
CPU tests, with the faults they plant.

The configurations keep the files' keys and shrink the sizes, so the
drivers, the references and the comparison run as they do on the chip.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from benchmarks.chip.tests.cases import CHIP, SEED, granite_config

# Limits for the small cells, with room over the bf16 program's readings
# there (served gap ~1e-3, prefill and decode error 0.004-0.006) and below
# the float8 control's (prefill and decode error ~0.05 and more).  The
# decode error's sits under what a float8 KV cache in decode alone reads
# (0.012-0.016), a cut that leaves prefill and the served tokens alone.
LIMITS = {"served_gap": 0.05, "prefill_err": 0.02, "decode_err": 0.009}


def share_config() -> dict:
    """Two layers, 16 experts top-4 in the router, experts 4-7 held here."""
    c = json.loads((CHIP / "configs" / "qwen3-moe-235b-a22b-ep16.json")
                   .read_text())
    c.update(overrides=dict(n_layers=2, d_model=64, n_heads=8, n_kv_heads=2,
                            d_head=16, d_ff_expert=32, n_experts=16, top_k=4,
                            vocab_size=512, experts_held=4, expert_offset=4),
             num_hidden_layers=2, hidden_size=64, num_attention_heads=8,
             num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
             router_experts=16, num_experts=4, expert_offset=4,
             num_experts_per_tok=4, vocab_size=512)
    return c


def share_traffic(**kw) -> dict:
    t = json.loads((CHIP / "traffic" / "long_prompt_rounds.json").read_text())
    t.update(batch=4, prompt_len=20, gen_tokens=10, check_requests=3,
             trace_decode_steps=3, ref_margin=0.0, limits=dict(LIMITS))
    t.update(kw)
    return t


def prefill_traffic(**kw) -> dict:
    t = json.loads((CHIP / "traffic" / "prefill_rounds.json").read_text())
    t.update(batch=1, prompt_len=40, gen_tokens=4, check_requests=4,
             trace_decode_steps=3, ref_margin=0.0, limits=dict(LIMITS))
    t.update(kw)
    return t


CELLS = {"qwen3_share_decode": (share_config, share_traffic, "share_rounds"),
         "granite_prefill": (granite_config, prefill_traffic,
                             "prefill_rounds")}


def driver(workload: str, **traffic):
    """The cell's driver on the CPU, not yet set up."""
    from benchmarks.chip import run
    from benchmarks.chip.common import Context

    config, traffic_of, name = CELLS[workload]
    run._paths()
    ctx = Context(workload=workload, config=config(),
                  traffic=traffic_of(**traffic), seed=SEED,
                  devices=jax.devices()[:1], trace_dir=run.TRACE_ROOT / workload)
    return run.load_file_module(CHIP / "drivers" / f"{name}.py",
                                f"driver_{name}").Driver(ctx)


def run_small(workload: str, trace: bool = False, **traffic) -> dict:
    from benchmarks.chip import run

    config, traffic_of, _ = CELLS[workload]
    return run.run_cell(workload, SEED, 0.3, trace, devices=jax.devices()[:1],
                        config=config(), traffic=traffic_of(**traffic))


def readings_of_rounds(workload: str, rounds: int, control: bool = False):
    """The cell's readings after ``rounds`` whole rounds, with no timed
    window: the sampled requests do not depend on the host's speed."""
    drv = driver(workload)
    drv.setup()
    for r in range(rounds):
        drv._finish(drv._start(r))
    drv.release()
    return drv.readings(control=control)


def plant(monkeypatch, fault: str) -> None:
    """Break the program underneath the driver."""
    from repro.launch import serve
    from repro.models import layers, moe

    if fault == "token_altered":
        orig = serve.make_steps

        def broken(cfg, s_max):
            prefill, decode = orig(cfg, s_max)

            def bad(params, token, caches):
                nxt, logits, caches = decode(params, token, caches)
                length = jax.tree.leaves(caches)[-1].reshape(-1)[0]
                nxt = jnp.where(length == s_max - 3,
                                (nxt + 1) % cfg.vocab_size, nxt)
                return nxt, logits, caches

            return prefill, jax.jit(bad, donate_argnums=(2,))

        monkeypatch.setattr(serve, "make_steps", broken)
    elif fault == "decode_cache_fp8":
        orig = serve.make_steps

        def cut(cfg, s_max):
            prefill, decode = orig(cfg, s_max)

            def bad(params, token, caches):
                caches = jax.tree.map(
                    lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, caches)
                return decode(params, token, caches)

            return prefill, jax.jit(bad, donate_argnums=(2,))

        monkeypatch.setattr(serve, "make_steps", cut)
    elif fault == "no_qk_norm":
        orig = layers._project_qkv
        monkeypatch.setattr(layers, "_project_qkv",
                            lambda p, cfg, *a, **k: orig(p, cfg.replace(
                                qk_norm=False), *a, **k))
    elif fault == "expert_dropped":
        orig = moe._held_block
        monkeypatch.setattr(moe, "_held_block",
                            lambda p, x, meta, w, n: orig(
                                p, x, jnp.where(meta == 2, 0, meta), w, n))
    elif fault == "offset_shifted":
        orig = moe.moe_held
        monkeypatch.setattr(moe, "moe_held", lambda p, cfg, x: orig(
            p, cfg.replace(expert_offset=cfg.expert_offset + 1), x))
    else:
        raise ValueError(fault)
