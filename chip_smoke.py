"""Smoke test of the device path on a TPU, through the normal entry points.

    python chip_smoke.py             # one chip: device check, serve, calibrate
    python chip_smoke.py --chips 4   # four chips: expert-parallel all-to-all

One chip serves granite-moe-1b-a400m at its published widths (random
weights from ``--seed``) through ``repro.launch.serve``, checks the logits
and the decode cache, then calibrates the Pallas kernels compiled on the
chip and replays the RAT simulator with that profile.  ``--chips 4`` runs
only the expert-parallel MoE dispatch/combine all-to-all across four chips,
plain and under the translation-aware plan, against a one-device reference.

Everything runs in this one process (a child could not reach the chip the
parent holds).  The first fault raises; nothing is caught.  Without a TPU,
or outside a checkout of the repository, it exits non-zero and prints no
result.  The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "granite-moe-1b-a400m"

# Tolerances of the float32 cache check: |decode - prefill| <= ATOL +
# RTOL * |prefill| on every logit.  A cache written at the wrong position
# or read with the wrong length moves logits by O(1).
CACHE_RTOL, CACHE_ATOL = 1e-3, 1e-3
# bf16 expert parallelism against the float32 one-device reference, as a
# share of the reference's largest magnitude (a few bf16 roundings).
EP_REF_TOL = 2e-2


def _log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    """The devices, or SystemExit when JAX finds no TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{devices[0].platform!r}")
    return devices


# ---------------------------------------------------------------- one chip
def check_cache(cfg, *, seed: int, batch: int, prompt_len: int) -> float:
    """prefill(prompt) then one decode step must give the last-position
    logits of prefill(prompt + token), in float32, row by row.

    The served weights (same seed) are cast to float32.  Routing is held
    dropless (capacity for every token at every expert) so a capacity drop
    cannot pass for a cache fault.  Returns the largest absolute difference.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import (init_params, make_batch, make_steps,
                                    serving_config)

    key = jax.random.PRNGKey(seed)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          init_params(serving_config(cfg), key))
    cfg = cfg.replace(dtype="float32", param_dtype="float32")
    if cfg.n_experts:
        cfg = cfg.replace(capacity_factor=cfg.n_experts / cfg.top_k)
    with jax.default_matmul_precision("highest"):
        prompts = make_batch(cfg, jax.random.fold_in(key, 1), batch,
                             prompt_len)
        prefill, decode = make_steps(cfg, prompt_len + 1)
        logits, caches = prefill(params, prompts)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        _, dec_logits, _ = decode(params, tok, caches)
        longer = dict(prompts, inputs=jnp.concatenate(
            [prompts["inputs"], tok[:, None]], axis=1))
        ref_logits, _ = prefill(params, longer)
    got = np.asarray(dec_logits, np.float64)
    want = np.asarray(ref_logits, np.float64)
    err = np.abs(got - want)
    bad = err > CACHE_ATOL + CACHE_RTOL * np.abs(want)
    _log(f"cache check: batch {batch} x {prompt_len} tokens, float32, "
         f"tolerance atol {CACHE_ATOL} rtol {CACHE_RTOL}; max |diff| "
         f"{err.max():.3e}, logit scale {np.abs(want).max():.3e}")
    if bad.any():
        rows = sorted(set(np.nonzero(bad)[0].tolist()))
        raise AssertionError(f"decode-step logits disagree with prefill in "
                             f"rows {rows} (max |diff| {err.max():.3e})")
    return float(err.max())


def phase_serve(cfg, *, seed: int, batch: int, prompt_len: int,
                new_tokens: int, check_batch: int):
    import numpy as np

    from repro.launch.serve import serve

    res = serve(cfg, batch=batch, prompt_len=prompt_len,
                new_tokens=new_tokens, seed=seed, log=_log)
    for name in ("prefill_logits", "last_logits"):
        arr = getattr(res, name)
        if arr.shape != (batch, cfg.vocab_size):
            raise AssertionError(f"{name} has shape {arr.shape}")
        if not np.isfinite(arr).all():
            raise AssertionError(f"{name} holds non-finite values")
    if res.tokens.shape != (batch, new_tokens):
        raise AssertionError(f"tokens have shape {res.tokens.shape}")
    if res.tokens.min() < 0 or res.tokens.max() >= cfg.vocab_size:
        raise AssertionError("a decoded token is outside the vocabulary")
    _log(f"served {batch} x {prompt_len}-token prompts, {new_tokens} tokens "
         f"each; sequence 0 starts {res.tokens[0, :8].tolist()}")
    t0 = time.perf_counter()
    check_cache(cfg, seed=seed, batch=check_batch, prompt_len=prompt_len)
    _log(f"cache_check_s {time.perf_counter() - t0:.3f}")
    return res


def phase_calibrate(arch: str, *, n_gpus: int = 16, reps: int = 3):
    """Measure the kernel tier on this backend (no cache file read or
    written) and replay a 2-step decode workload with the profile."""
    import jax

    from repro.workloads import calibrate, derive_workload, replay

    prof = calibrate(arch, "decode_32k", n_gpus=n_gpus, reps=reps,
                     force=True)
    if prof.interpret != (jax.default_backend() != "tpu"):
        raise AssertionError(f"profile records interpret={prof.interpret} "
                             f"on backend {jax.default_backend()!r}")
    for name, w in sorted(prof.phases.items()):
        _log(f"calibrated {name}: {'+'.join(w.kernels)} measured "
             f"{w.measured_wall_ns:.0f} ns, window {w.roofline_ns:.0f} -> "
             f"{w.calibrated_ns:.0f} ns (interpret={prof.interpret})")
    trace = derive_workload(arch, "decode_32k", n_gpus=n_gpus, n_steps=2,
                            compute_profile=prof)
    rep = replay(trace)
    _log(f"replay {arch} decode_32k on {n_gpus} GPUs: cold degradation "
         f"{rep.cold_degradation:.4f}, steady {rep.steady_degradation:.4f}")
    return prof, rep


# ------------------------------------------------------------- four chips
def phase_ep(cfg, *, n_dev: int, tokens_per_dev: int, seed: int):
    """Expert-parallel MoE over ``n_dev`` devices, plain and scheduled,
    against the one-device ``moe_gather`` in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.scheduler import TranslationAwareScheduler
    from repro.kernels.compat import make_mesh
    from repro.models.base import ParamBuilder
    from repro.models.moe import init_moe, moe_block_ep, moe_gather
    from repro.workloads import moe_a2a_bytes

    if cfg.n_experts % n_dev:
        raise ValueError(f"{cfg.n_experts} experts do not split over "
                         f"{n_dev} devices")
    # Capacity for every token at every expert: nothing drops, so the EP
    # and one-device paths must agree on every token.
    cfg = cfg.replace(capacity_factor=cfg.n_experts / cfg.top_k)
    e_loc = cfg.n_experts // n_dev
    mesh = make_mesh((n_dev,), ("model",), devices=jax.devices()[:n_dev])
    espec = P("model", None, None)
    p_sh = {"router": NamedSharding(mesh, P()),
            **{k: NamedSharding(mesh, espec)
               for k in ("wi_gate", "wi_up", "wo")}}

    def init(key):
        b = ParamBuilder(key, jnp.bfloat16)
        init_moe(b, cfg, "moe")
        return b.params["moe"]

    key = jax.random.PRNGKey(seed)
    params = jax.jit(init, out_shardings=p_sh)(key)
    n_tok = n_dev * tokens_per_dev
    x = jax.jit(lambda k: jax.random.normal(k, (n_tok, cfg.d_model),
                                            jnp.bfloat16),
                out_shardings=NamedSharding(mesh, P("model", None)))(
                    jax.random.fold_in(key, 1))

    for s in params["wi_gate"].addressable_shards:
        _log(f"wi_gate shard on {s.device}: experts {s.index[0]}, "
             f"shape {tuple(s.data.shape)}")
    held = {s.device: s.data.shape[0]
            for s in params["wi_gate"].addressable_shards}
    if len(held) != n_dev or set(held.values()) != {e_loc}:
        raise AssertionError(f"expert shards {held}, want {e_loc} experts on "
                             f"each of {n_dev} devices")

    plan = TranslationAwareScheduler(n_gpus=n_dev, overlap_compute_ns=5e3) \
        .plan_all_to_all(moe_a2a_bytes(cfg, tokens_per_dev, n_dev, 2))
    _log(f"plan: {plan.total_bytes} B per device, warm-up "
         f"{plan.warmup_chunk_bytes} B, {plan.n_chunks} chunks")

    def ep(scheduled: bool):
        def inner(x, wg, wu, wo, router):
            p = {"wi_gate": wg, "wi_up": wu, "wo": wo, "router": router}
            kw = {}
            if scheduled:
                # Producing compute the warm-up chunk is issued under.
                kw = dict(plan=plan, overlap_compute=(
                    lambda h: jnp.tanh(h @ router.astype(h.dtype)), x))
            return moe_block_ep(p, cfg, x, "model", **kw)[0]
        return jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P("model", None), espec, espec, espec, P()),
            out_specs=P("model", None), check_vma=False))

    args = (x, params["wi_gate"], params["wi_up"], params["wo"],
            params["router"])
    outs = {}
    for name, scheduled in (("plain", False), ("scheduled", True)):
        fn = ep(scheduled)
        t0 = time.perf_counter()
        fn = fn.lower(*args).compile()
        compile_s = time.perf_counter() - t0
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        outs[name] = np.asarray(jax.block_until_ready(fn(*args)), np.float32)
        _log(f"ep {name}: compile_s {compile_s:.3f}, one call "
             f"{time.perf_counter() - t0:.6f} s")

    dev0 = jax.devices()[0]
    p32 = jax.tree.map(lambda a: jax.device_put(a, dev0).astype(jnp.float32),
                       params)
    with jax.default_matmul_precision("highest"):
        ref, _ = jax.jit(lambda p, x: moe_gather(p, cfg, x[None]))(
            p32, jax.device_put(x, dev0).astype(jnp.float32))
    ref = np.asarray(ref[0], np.float32)

    scale = float(np.abs(ref).max())
    d_sched = float(np.abs(outs["scheduled"] - outs["plain"]).max())
    d_ref = max(float(np.abs(o - ref).max()) for o in outs.values())
    _log(f"ep check: {n_tok} tokens, |scheduled - plain| max {d_sched:.3e}, "
         f"|ep - float32 reference| max {d_ref:.3e} (scale {scale:.3e}, "
         f"tolerance {EP_REF_TOL} x scale)")
    if d_sched > 2.0 ** -8 * scale:
        raise AssertionError(f"scheduled and plain all-to-all disagree: "
                             f"{d_sched:.3e}")
    if not d_ref <= EP_REF_TOL * scale:
        raise AssertionError(f"expert-parallel output is off the reference: "
                             f"{d_ref:.3e} at scale {scale:.3e}")
    return outs, ref


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip expert-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {ROOT}/src")
    sys.path.insert(0, str(ROOT / "src"))
    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    t0 = time.perf_counter()
    devices = require_tpu()
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devices)} device(s)")
    cache_dir = enable_compile_cache()
    _log(f"device: {devices[0].device_kind} x{len(devices)}, compile cache "
         f"{cache_dir}")

    cfg = configs.get_config(ARCH)
    if args.chips == 4:
        phase_ep(cfg, n_dev=4, tokens_per_dev=256, seed=args.seed)
        _log(f"phase ep_all_to_all_s {time.perf_counter() - t0:.3f}")
    else:
        phase_serve(cfg, seed=args.seed, batch=8, prompt_len=512,
                    new_tokens=32, check_batch=2)
        _log(f"phase serve_s {time.perf_counter() - t0:.3f}")
        t1 = time.perf_counter()
        phase_calibrate(ARCH)
        _log(f"phase calibrate_s {time.perf_counter() - t1:.3f}")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
