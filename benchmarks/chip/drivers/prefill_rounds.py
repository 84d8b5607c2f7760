"""Closed-loop serving rounds that also check what prefill computed.

The rounds of ``serve_rounds`` (a batch of prompts drawn from the seed,
one prefill, greedy decode steps to ``gen_tokens``, every token fetched
to the host as it is made), for traffic whose answers may be as short as
a few tokens: warm-up steps no further than the round's last token.

Correctness compares, for a sample of finished requests drawn from the
seed, with the plain float32 reference (``reference/granite.py``):

- ``served_gap``: as in ``serve_rounds``, the widest gap of a served
  token's reference logit below the reference's best, at positions where
  that best leads its runner-up by ``ref_margin`` or more;
- ``prefill_err``: the prefill's logits at the last prompt position
  against the reference's, ``|program - reference| / |reference|`` per
  sampled request, the median over them.  It reads every sampled
  request, whatever the margins.  A precision cut moves every request's
  error; a request whose routing flips between the two precisions (a
  near-tie in bf16) moves one request's far, and the median leaves it
  out;
- ``decode_err``: the same of the logits of each round's last decode
  step, which reads the caches filled by prefill and every earlier
  step, against the reference's at that position.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.chip.common import span
from benchmarks.chip.drivers import serve_rounds
from benchmarks.chip.reference import granite as ref

WARM_STEPS = 8
MARGINS = (0.0, 0.1, 0.15, 0.2, 0.25, 0.3, 0.35, 0.4, 0.5)


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Median over requests (rows) of ``|got - want| / |want|``."""
    return float(np.median(np.linalg.norm(got - want, axis=-1)
                           / np.linalg.norm(want, axis=-1)))


class Driver(serve_rounds.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        # per finished round: (prefill's last-position logits, the last
        # decode step's logits), on the device
        self.finished_logits = []

    def _start(self, r: int) -> dict:
        """Prompts of round ``r``, prefill and the first token fetched;
        the prefill's logits stay on the device for the check."""
        with span("round.prompts"):
            prompts = self.prompts(r)
            batch = {"inputs": jax.device_put(prompts, self.dev)}
        with span("round.prefill"):
            logits, caches = self.prefill(self.params, batch)
            tok = self.first(logits)
        st = {"prompts": prompts, "toks": np.empty((self.B, self.G), np.int32),
              "stamps": np.empty(self.G), "tok": tok, "caches": caches,
              "n": 0, "logits": logits}
        self._fetch(st)
        return st

    def _step(self, st: dict) -> None:
        """A decode step; the round's last keeps its logits for the check."""
        with span("round.decode_step"):
            st["tok"], logits, st["caches"] = self.decode(
                self.params, st["tok"], st["caches"])
        if st["n"] == self.G - 1:
            st["last"] = logits
        self._fetch(st)

    def _fetch(self, st: dict) -> None:
        super()._fetch(st)
        if st["n"] == self.G:
            self.finished_logits.append((st.pop("logits"), st.pop("last")))

    def warm_up(self) -> None:
        """Every program once with the window's shapes; the warm-up round
        is not among the finished requests."""
        st = self._start(-1)
        for _ in range(min(WARM_STEPS, self.G - 1)):
            self._step(st)
        self.finished.clear()
        self.finished_logits.clear()

    # ---------------------------------------------------------- correctness
    def sample(self):
        """Requests to check, drawn from the seed among the finished: their
        prompts, served tokens, and the logits of prefill and of the last
        decode step (float32, on the host)."""
        rng = np.random.default_rng([self.ctx.seed, 2])
        pairs = [(r, b) for r in range(len(self.finished))
                 for b in range(self.B)]
        n = min(self.ctx.traffic["check_requests"], len(pairs))
        pick = [pairs[i] for i in
                sorted(rng.choice(len(pairs), n, replace=False).tolist())]
        prompts = np.stack([self.finished[r][0][b] for r, b in pick])
        toks = np.stack([self.finished[r][1][b] for r, b in pick])
        first, last = (np.stack([np.asarray(self.finished_logits[r][i][b],
                                            np.float32) for r, b in pick])
                       for i in (0, 1))
        return prompts, toks, first, last

    def reference(self, prompts, toks, control: bool) -> dict:
        """``gap``, ``margin``, ``first`` and ``last`` (the reference's
        logits at the last prompt position and at the last decode step's)
        per sampled request; with ``control``, the float8 reference's
        ``control_gap``, ``control_first`` and ``control_last``."""
        c = self.ctx.config
        s = tuple(sorted(ref.sizes(c).items()))
        P = prompts.shape[1]
        w = ref.make_weights(c, self.ctx.seed)
        seqs = np.concatenate([prompts, toks[:, :-1]], axis=1).astype(np.int32)
        out = {"gap": [], "margin": [], "first": [], "last": []}
        if control:
            out.update(control_gap=[], control_first=[], control_last=[])
        with jax.default_matmul_precision("highest"):
            for i in range(0, len(seqs), ref.BLOCK):
                blk = seqs[i:i + ref.BLOCK]
                want = np.asarray(ref.served_logits(w, blk, s=s, prompt_len=P))
                best = np.sort(want, axis=-1)[..., -2:]
                got = np.take_along_axis(want, toks[i:i + ref.BLOCK, :, None],
                                         -1)[..., 0]
                out["gap"].append(best[..., 1] - got)
                out["margin"].append(best[..., 1] - best[..., 0])
                out["first"].append(want[:, 0])
                out["last"].append(want[:, -1])
                if control:
                    ctl = np.asarray(ref.served_logits(w, blk, s=s,
                                                       prompt_len=P, fp8=True))
                    pick = np.argmax(ctl, axis=-1)[..., None]
                    got = np.take_along_axis(want, pick, -1)[..., 0]
                    out["control_gap"].append(best[..., 1] - got)
                    out["control_first"].append(ctl[:, 0])
                    out["control_last"].append(ctl[:, -1])
        return {k: np.concatenate(v) for k, v in out.items()}

    def readings(self, control: bool = False) -> dict:
        """``served_gap`` (with ``open_share``, the share of positions it
        leaves out), ``prefill_err`` and ``decode_err``; with ``control``,
        the float8 reference's ``control_served_gap``,
        ``control_prefill_err`` and ``control_decode_err``, and the gaps of
        both at other margins."""
        prompts, toks, first, last = self.sample()
        with jax.default_device(self.dev):
            got = self.reference(prompts, toks, control)
        m = self.ctx.traffic["ref_margin"]
        sure = got["margin"] >= m
        out = {"served_gap": float(got["gap"][sure].max(initial=0.0)),
               "open_share": float(1.0 - sure.mean()),
               "prefill_err": rel_err(first, got["first"]),
               "decode_err": rel_err(last, got["last"]),
               "requests": len(prompts),
               "gap_by_margin": {str(t): float(got["gap"][got["margin"] >= t]
                                               .max(initial=0.0))
                                 for t in MARGINS}}
        if control:
            out["control_served_gap"] = float(got["control_gap"][sure].max(
                initial=0.0))
            out["control_prefill_err"] = rel_err(got["control_first"],
                                                 got["first"])
            out["control_decode_err"] = rel_err(got["control_last"],
                                                got["last"])
            out["control_by_margin"] = {
                str(t): float(got["control_gap"][got["margin"] >= t]
                              .max(initial=0.0)) for t in MARGINS}
        return out
