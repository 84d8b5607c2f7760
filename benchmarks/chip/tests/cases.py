"""Small cells for the CPU tests, and the faults they plant.

The configurations keep the files' keys and shrink the sizes, so the
drivers, the references and the comparison run as they do on the chip.
``python -m benchmarks.chip.tests.cases <case>`` runs one EP case in a
process of its own (it needs four virtual devices, which must be set
before JAX starts) and prints the result's JSON as its last line.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
SEED = 2**31 + 77          # larger than 32 signed bits hold


def granite_config() -> dict:
    c = json.loads((CHIP / "configs" / "granite-moe-1b-a400m.json").read_text())
    c.update(overrides=dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                            d_head=16, d_ff_expert=32, n_experts=8, top_k=2,
                            vocab_size=256),
             num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=32,
             num_local_experts=8, num_experts_per_tok=2, vocab_size=256)
    return c


def granite_traffic(limit: float) -> dict:
    t = json.loads((CHIP / "traffic" / "decode_rounds.json").read_text())
    t.update(batch=4, prompt_len=16, gen_tokens=12, check_requests=3,
             ref_margin=0.0,
             limits={"served_gap": limit})
    return t


def ep_config() -> dict:
    c = json.loads((CHIP / "configs" / "qwen3-moe-235b-a22b-ep4.json")
                   .read_text())
    c.update(overrides=dict(n_layers=2, d_model=64, d_ff_expert=32,
                            n_experts=16, top_k=4),
             num_hidden_layers=2, hidden_size=64, moe_intermediate_size=32,
             num_experts=16, num_experts_per_tok=4)
    return c


def ep_traffic(limit: float) -> dict:
    t = json.loads((CHIP / "traffic" / "ep_decode256.json").read_text())
    t.update(tokens_per_chip=16, batches=3, check_batches=2,
             route_margin=1e-2, limits={"ep_row_err": limit})
    return t


# Limits for the small cells, with room over the bf16 program's readings
# there (served gap ~4e-5; row error ~1.3e-2 on tokens routed by a margin
# of 1e-2 or more, while the float8 control reads ~0.5).
GRANITE_LIMIT = 0.05
EP_LIMIT = 0.05


def run_small(workload: str, config: dict, traffic: dict, n_dev: int):
    import jax

    from benchmarks.chip import run
    return run.run_cell(workload, SEED, 0.2, False,
                        devices=jax.devices()[:n_dev], config=config,
                        traffic=traffic)


def plant_ep(fault: str) -> None:
    """Break the expert-parallel path underneath the driver."""
    import jax
    import jax.numpy as jnp

    from repro.models import moe

    if fault == "no_exchange":
        jax.lax.all_to_all = lambda x, *a, **k: x
        return
    orig = moe.moe_block_ep

    def broken(p, cfg, x, axis_name, **kw):
        y, aux = orig(p, cfg, x, axis_name, **kw)
        h = y.shape[0] // 2
        if fault == "half_batch":
            y = jnp.concatenate([y[:h], y[:h]])
        elif fault == "token_altered":
            y = y.at[0].set(y[1])
        return y, aux

    moe.moe_block_ep = broken


def ep_reference_gap() -> float:
    """Largest |program - reference| over the scale of the reference, for
    the float32 program stack against the plain reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from benchmarks.chip import weights as W
    from benchmarks.chip.reference import ep_stack as ref
    from repro import configs
    from repro.kernels.compat import make_mesh
    from repro.models import moe

    c = ep_config()
    cfg = configs.get_config(c["arch"]).replace(dtype="float32",
                                                **c["overrides"])
    L, D, E, F = cfg.n_layers, cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    shapes = {f"l{i}": {"router": (D, E), "wi_gate": (E, D, F),
                        "wi_up": (E, D, F), "wo": (E, F, D)} for i in range(L)}
    key = W.base_key(SEED)
    params = jax.tree_util.tree_map_with_path(
        lambda p, s: W.leaf(key, W.path_str(p), s, jnp.bfloat16).astype(
            jnp.float32), shapes, is_leaf=lambda s: isinstance(s, tuple))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (4 * 16, D)))
    mesh = make_mesh((4,), ("model",), devices=jax.devices()[:4])
    espec = P("model", None, None)
    specs = {f"l{i}": {"router": P(), "wi_gate": espec, "wi_up": espec,
                       "wo": espec} for i in range(L)}

    def stack(p, x):
        for i in range(L):
            x = x + moe.moe_block_ep(p[f"l{i}"], cfg, x, "model")[0]
        return x

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.shard_map(
            stack, mesh=mesh, in_specs=(specs, P("model", None)),
            out_specs=P("model", None), check_vma=False))(params, x)
    (want,), _ = ref.run(c, SEED, [x], 4)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def main(case: str) -> int:
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    if case == "reference":
        print(json.dumps({"gap": ep_reference_gap()}))
        return 0
    if case == "control":
        from benchmarks.chip import run
        from benchmarks.chip.common import Context

        ctx = Context(workload="qwen3moe_ep4", config=ep_config(),
                      traffic=ep_traffic(EP_LIMIT), seed=SEED,
                      devices=jax.devices()[:4])
        drv = run.load_file_module(CHIP / "drivers" / "ep_stack.py",
                                   "driver_ep_stack").Driver(ctx)
        drv.setup()
        drv.window(0.2)
        drv.release()
        print(json.dumps(drv.readings(control=True)))
        return 0
    if case != "sound":
        plant_ep(case)
    res = run_small("qwen3moe_ep4", ep_config(), ep_traffic(EP_LIMIT), 4)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
