"""Expert-parallel MoE layer stack across chips through ``moe_block_ep``.

One jitted ``shard_map`` over a ``model`` axis of all the cell's chips
runs ``repro.models.moe.moe_block_ep`` (the plain dispatch and combine
all-to-alls) for each layer of the configuration, with a residual add
between layers.  Each chip holds ``num_experts / chips`` experts of every
layer; the router is replicated.  A step is one batch of
``tokens_per_chip`` token vectors per chip; the window cycles through
``batches`` device-resident batches drawn from the seed, waits for each
step, and ends at the first step end after ``--seconds``.

End-to-end: ``ep_step_ms``, the window over the steps it completed.
Correctness: the last output of ``check_batches`` batches drawn from the
seed against the plain float32 reference (``reference/ep_stack.py``).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.chip import weights as W
from benchmarks.chip.common import Context, check_sizes, module_name, span
from benchmarks.chip.reference import ep_stack as ref

SPANS = ("ep.step", "ep.wait")


class Driver:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        t = ctx.traffic
        self.n = len(ctx.devices)
        self.T = t["tokens_per_chip"]
        self.nb = t["batches"]
        self.last = {}            # batch slot -> its latest output
        self.steps = 0

    def setup(self) -> None:
        from repro import configs
        from repro.kernels.compat import make_mesh
        from repro.models import moe
        from repro.models.base import ParamBuilder

        c = self.ctx.config
        cfg = configs.get_config(c["arch"]).replace(**c["overrides"])
        check_sizes(cfg, {
            "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "d_ff_expert": c["moe_intermediate_size"],
            "n_experts": c["num_experts"], "top_k": c["num_experts_per_tok"],
            "capacity_factor": c["capacity_factor"], "dtype": c["dtype"]})
        if cfg.n_experts % self.n:
            raise ValueError(f"{cfg.n_experts} experts do not split over "
                             f"{self.n} chips")
        self.cfg = cfg
        L, D = cfg.n_layers, cfg.d_model
        mesh = make_mesh((self.n,), ("model",), devices=self.ctx.devices)
        self.mesh = mesh

        def one_layer(key):
            b = ParamBuilder(key, cfg.dtype)
            moe.init_moe(b, cfg, "moe")
            return b.params["moe"]

        key = W.base_key(self.ctx.seed)
        layer = jax.eval_shape(one_layer, key)
        shapes = {f"l{i}": layer for i in range(L)}
        espec = P("model", None, None)
        specs = {f"l{i}": {"router": P(), "wi_gate": espec, "wi_up": espec,
                           "wo": espec} for i in range(L)}
        shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda s: isinstance(s, P))
        t0 = time.perf_counter()
        self.params = jax.block_until_ready(jax.jit(
            functools.partial(W.fill, shapes), out_shardings=shard)(key))
        t1 = time.perf_counter()

        xs = NamedSharding(mesh, P("model", None))
        n_tok = self.n * self.T

        def batches(k):
            return [jax.random.normal(jax.random.fold_in(k, 1000 + i),
                                      (n_tok, D), jnp.dtype(cfg.dtype))
                    for i in range(self.nb)]

        self.batches = jax.jit(batches, out_shardings=[xs] * self.nb)(key)

        def stack(p, x):
            for i in range(L):
                y, _ = moe.moe_block_ep(p[f"l{i}"], cfg, x, "model")
                x = x + y
            return x

        step = jax.jit(jax.shard_map(
            stack, mesh=mesh, in_specs=(specs, P("model", None)),
            out_specs=P("model", None), check_vma=False))
        self.step = step.lower(self.params, self.batches[0]).compile()
        self.programs = {"step": module_name(self.step)}
        t2 = time.perf_counter()
        for x in self.batches:      # warm-up: every batch once
            jax.block_until_ready(self.step(self.params, x))
        self.setup_detail = {"jax_init_s": t0 - self.ctx.t_start,
                             "weights_s": t1 - t0, "compile_s": t2 - t1,
                             "warm_up_s": time.perf_counter() - t2}

    def _step(self) -> None:
        slot = self.steps % self.nb
        with span("ep.step"):
            out = self.step(self.params, self.batches[slot])
        with span("ep.wait"):
            out.block_until_ready()
        self.last[slot] = out
        self.steps += 1

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        while True:
            self._step()
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        return {"metrics": {"ep_step_ms": elapsed / self.steps * 1e3},
                "attempted": self.steps, "failed": 0,
                "detail": {"steps": self.steps, "elapsed_s": elapsed}}

    def traced(self) -> dict:
        n = self.ctx.traffic["trace_steps"]
        self.ctx.start_trace()
        with span("trace.window"):
            for _ in range(n):
                self._step()
        self.ctx.stop_trace()
        return {"programs": self.programs, "steps": n, "spans": SPANS,
                "attempted": n, "failed": 0}

    def release(self) -> None:
        """Bring the sampled inputs and outputs to the host, free the rest."""
        rng = np.random.default_rng([self.ctx.seed, 2])
        slots = sorted(self.last)
        k = min(self.ctx.traffic["check_batches"], len(slots))
        pick = sorted(rng.choice(slots, k, replace=False).tolist())
        self.checked = [(np.asarray(self.batches[s], np.float32),
                         np.asarray(self.last[s], np.float32)) for s in pick]
        del self.params, self.batches, self.step
        self.last.clear()

    def readings(self, control: bool = False) -> dict:
        """``ep_row_err``: the largest row error among the sampled batches'
        tokens whose routing margin is at least the traffic's
        ``route_margin``.  With ``control``, the float8 reference's."""
        m = self.ctx.traffic["route_margin"] or 0.0
        x0s = [x for x, _ in self.checked]
        with jax.default_device(self.ctx.devices[0]):
            wants, margins = ref.run(self.ctx.config, self.ctx.seed, x0s,
                                     self.n)
            ctls = (ref.run(self.ctx.config, self.ctx.seed, x0s, self.n,
                            fp8=True)[0] if control else None)
        err = np.concatenate([ref.row_errors(out, want, x0) for
                              (x0, out), want in zip(self.checked, wants)])
        margin = np.concatenate(margins)
        sure = margin >= m
        got = {"ep_row_err": float(err[sure].max(initial=0.0)),
               "ambiguous_share": float(1.0 - sure.mean())}
        steps = (0.0, 0.003, 0.01, 0.02, 0.03, 0.05)
        got["err_by_margin"] = {
            str(t): [float(err[margin >= t].max(initial=0.0)),
                     float((margin < t).mean())] for t in steps}
        if control:
            ctl = np.concatenate([ref.row_errors(c, want, x0) for
                                  c, want, x0 in zip(ctls, wants, x0s)])
            got["control_row_err"] = float(ctl[sure].max(initial=0.0))
            got["control_by_margin"] = {
                str(t): float(ctl[margin >= t].max(initial=0.0))
                for t in steps}
        return got

    def checks(self):
        """(name, value, limit) of each compared number, and every reading."""
        limits = self.ctx.traffic["limits"]
        got = self.readings()
        return [(name, got[name], limits[name]) for name in limits], got
