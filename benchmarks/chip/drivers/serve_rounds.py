"""Closed-loop batch serving in rounds through ``repro.launch.serve``.

A round is ``batch`` requests with ``prompt_len``-token prompts drawn from
the seed: one prefill, then greedy decode steps until each request holds
``gen_tokens`` tokens (the first from prefill).  Every token is fetched to
the host as it is made, as a streaming server must.  The window runs
rounds back to back and closes with the first token after ``--seconds``.

End-to-end: ``gen_tok_s`` (generated tokens over the whole window,
prefill included) and ``itl_p95_ms`` (95th percentile of every gap
between two successive tokens of a request).  Correctness: a sample of
finished requests, drawn from the seed, against the plain float32
reference (``reference/granite.py``).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights as W
from benchmarks.chip.common import Context, check_sizes, module_name, span
from benchmarks.chip.reference import granite as ref

SPANS = ("round.prompts", "round.prefill", "round.decode_step", "token_fetch")
STALL_S = 0.1     # a gap this long is logged with its round and token


def first_token(logits):
    """Greedy first token from the prefill's last-position logits."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class Driver:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        t = ctx.traffic
        self.B, self.P, self.G = t["batch"], t["prompt_len"], t["gen_tokens"]
        self.finished = []        # (prompts [B,P], tokens [B,G]) per round

    # ---------------------------------------------------------------- setup
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
            "d_ff_expert": c["intermediate_size"],
            "n_experts": c["num_local_experts"],
            "top_k": c["num_experts_per_tok"], "vocab_size": c["vocab_size"],
            "capacity_factor": c["capacity_factor"], "dtype": c["dtype"]})
        self.cfg = cfg
        dev = self.ctx.devices[0]
        self.dev = dev
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
        self.first = jax.jit(first_token).lower(logits_s).compile()
        self.programs = {"prefill": module_name(self.prefill),
                         "decode": module_name(self.decode)}
        t2 = time.perf_counter()
        # Warm-up: every program, with the window's shapes.
        self.warm_up()
        self.setup_detail = {"jax_init_s": t0 - self.ctx.t_start,
                             "weights_s": t1 - t0, "compile_s": t2 - t1,
                             "warm_up_s": time.perf_counter() - t2}

    def prompts(self, r: int) -> np.ndarray:
        rng = np.random.default_rng([self.ctx.seed, 1, r + 1])
        return rng.integers(0, self.cfg.vocab_size, (self.B, self.P),
                            dtype=np.int32)

    # --------------------------------------------------------------- rounds
    def _start(self, r: int) -> dict:
        """Prompts of round ``r``, prefill and the first token fetched."""
        with span("round.prompts"):
            prompts = self.prompts(r)
            batch = {"inputs": jax.device_put(prompts, self.dev)}
        with span("round.prefill"):
            logits, caches = self.prefill(self.params, batch)
            tok = self.first(logits)
        st = {"prompts": prompts, "toks": np.empty((self.B, self.G), np.int32),
              "stamps": np.empty(self.G), "tok": tok, "caches": caches, "n": 0}
        self._fetch(st)
        return st

    def _fetch(self, st: dict) -> None:
        with span("token_fetch"):
            st["toks"][:, st["n"]] = np.asarray(st["tok"])
        st["stamps"][st["n"]] = time.perf_counter()
        st["n"] += 1
        if st["n"] == self.G:
            del st["caches"], st["tok"]
            self.finished.append((st["prompts"], st["toks"]))

    def _step(self, st: dict) -> None:
        with span("round.decode_step"):
            st["tok"], _, st["caches"] = self.decode(
                self.params, st["tok"], st["caches"])
        self._fetch(st)

    def _finish(self, st: dict) -> None:
        while st["n"] < self.G:
            self._step(st)

    def warm_up(self) -> None:
        st = self._start(-1)
        for _ in range(8):
            self._step(st)

    def window(self, seconds: float) -> dict:
        """Rounds back to back until ``seconds`` have passed; the window
        closes after the token that crosses that time.  Tokens of the round
        then in flight count; if no round finished in the window, the one
        in flight is finished afterwards, untimed, for the check."""
        rounds = []
        t0 = time.perf_counter()
        st = self._start(0)
        rounds.append(st)
        while time.perf_counter() - t0 < seconds:
            if st["n"] == self.G:
                st = self._start(len(rounds))
                rounds.append(st)
            else:
                self._step(st)
        elapsed = time.perf_counter() - t0
        tokens = self.B * sum(r["n"] for r in rounds)
        # Every request of a round sees the same gaps, so the percentile
        # over the rounds' gaps is the percentile over all requests' gaps.
        per_round = [np.diff(r["stamps"][:r["n"]]) for r in rounds]
        gaps = np.concatenate(per_round)
        stalls = [[i, int(j) + 1, float(g) * 1e3] for i, d in enumerate(per_round)
                  for j, g in enumerate(d) if g > STALL_S]
        if not self.finished:
            self._finish(st)
        return {"metrics": {"gen_tok_s": tokens / elapsed,
                            "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3},
                "attempted": self.B * len(rounds), "failed": 0,
                "detail": {"rounds": len(rounds), "tokens": tokens,
                           "elapsed_s": elapsed, "gaps": int(gaps.size),
                           "itl_mean_ms": float(gaps.mean()) * 1e3,
                           "itl_max_ms": float(gaps.max()) * 1e3,
                           "stalls": stalls[:10]}}

    def traced(self) -> dict:
        """Trace the prefill and the first decode steps of a round, then
        finish the round untraced."""
        steps = self.ctx.traffic["trace_decode_steps"]
        self.ctx.start_trace()
        with span("trace.window"):
            st = self._start(0)
            for _ in range(steps):
                self._step(st)
        self.ctx.stop_trace()
        self._finish(st)
        # Decode step i attends the prompt and i tokens.
        fills = [self.P + i for i in range(1, steps + 1)]
        return {"programs": self.programs, "decode_fills": fills,
                "batch": self.B, "spans": SPANS,
                "attempted": self.B, "failed": 0}

    def release(self) -> None:
        del self.params, self.prefill, self.decode, self.first

    # ---------------------------------------------------------- correctness
    def sample(self):
        """Requests to check, drawn from the seed among the finished."""
        rng = np.random.default_rng([self.ctx.seed, 2])
        pairs = [(r, b) for r in range(len(self.finished))
                 for b in range(self.B)]
        n = min(self.ctx.traffic["check_requests"], len(pairs))
        pick = sorted(rng.choice(len(pairs), n, replace=False).tolist())
        prompts = np.stack([self.finished[pairs[i][0]][0][pairs[i][1]]
                            for i in pick])
        toks = np.stack([self.finished[pairs[i][0]][1][pairs[i][1]]
                         for i in pick])
        return prompts, toks

    def readings(self, control: bool = False) -> dict:
        """``served_gap``: the widest gap of a served token below the
        reference's best, over the sampled requests' positions at which
        the reference's best leads its runner-up by ``ref_margin`` or more
        (at a closer call no precision settles which token is right).
        With ``control``, the float8 reference's reading, and both at
        other margins."""
        prompts, toks = self.sample()
        with jax.default_device(self.dev):
            got = ref.served_gaps(self.ctx.config, self.ctx.seed, prompts,
                                  toks, control=control)
        m = self.ctx.traffic["ref_margin"] or 0.0
        sure = got["margin"] >= m
        out = {"served_gap": float(got["gap"][sure].max(initial=0.0)),
               "open_share": float(1.0 - sure.mean())}
        margins = (0.0, 0.2, 0.3, 0.35, 0.4, 0.45, 0.5, 0.6)
        out["gap_by_margin"] = {
            str(t): float(got["gap"][got["margin"] >= t].max(initial=0.0))
            for t in margins}
        if control:
            out["control_gap"] = float(got["control_gap"][sure].max(
                initial=0.0))
            out["control_by_margin"] = {
                str(t): float(got["control_gap"][got["margin"] >= t].max(
                    initial=0.0)) for t in margins}
        return out

    def checks(self):
        """(name, value, limit) of each compared number, and every reading."""
        limits = self.ctx.traffic["limits"]
        got = self.readings()
        return [(name, got[name], limits[name]) for name in limits], got
