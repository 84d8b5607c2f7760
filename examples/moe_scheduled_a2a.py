"""The paper's technique inside a real MoE block: expert-parallel all-to-all
with translation-aware warm-up scheduling (repro.core.overlap).

Runs the explicit shard_map EP MoE (the collective the paper analyzes) on
whatever devices exist, once unscheduled and once under a
TranslationAwareScheduler plan, and verifies both produce identical outputs.
On 1 CPU device the all-to-all is an identity collective — the point here is
the code path; the dry-run exercises it at 512 devices and the simulator
quantifies the win (benchmarks/opt_pretranslation).

    PYTHONPATH=src python examples/moe_scheduled_a2a.py
"""
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.core.scheduler import TranslationAwareScheduler
from repro.models.moe import moe_block_ep, init_moe
from repro.models.base import ParamBuilder
from repro.launch.mesh import make_local_mesh


def main():
    cfg = get_smoke_config("granite-moe-1b-a400m")
    mesh = make_local_mesh(model_axis=len(jax.devices()))
    ep = mesh.shape["model"]
    assert cfg.n_experts % ep == 0

    b = ParamBuilder(jax.random.PRNGKey(0))
    init_moe(b, cfg, "moe")
    params = b.params["moe"]
    T, D = 64, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)

    sch = TranslationAwareScheduler(n_gpus=max(ep, 8),
                                    overlap_compute_ns=5e3)
    plan = sch.plan_all_to_all(T * D * 4)
    print(f"plan: warm-up {plan.warmup_chunk_bytes}B, "
          f"{plan.n_chunks} chunks, est speedup {plan.est_speedup:.3f}x")

    def run(x, params, use_plan):
        def inner(x, wi_g, wi_u, wo, router):
            p = {"wi_gate": wi_g, "wi_up": wi_u, "wo": wo, "router": router}
            if not use_plan:
                return moe_block_ep(p, cfg, x, "model")[0]
            # Producing compute the warm-up chunk hides under.
            overlap = (lambda h: jnp.tanh(h @ router), x)
            return moe_block_ep(p, cfg, x, "model", plan=plan,
                                overlap_compute=overlap)[0]
        espec = P("model", None, None)
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), espec, espec, espec, P()),
            out_specs=P(), check_vma=False,
        )(x, params["wi_gate"], params["wi_up"], params["wo"],
          params["router"])

    y0 = jax.jit(lambda x, p: run(x, p, False))(x, params)
    print("EP MoE (unscheduled) output:", np.asarray(y0).shape,
          "finite:", bool(np.isfinite(np.asarray(y0)).all()))
    # The scheduled path wires the warm-up chunk through core.overlap.
    y1 = jax.jit(lambda x, p: run(x, p, True))(x, params)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=1e-5)
    print("scheduled == unscheduled outputs: OK")


if __name__ == "__main__":
    main()
