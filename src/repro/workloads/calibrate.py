"""Measured compute windows for workload replay (DESIGN.md §9).

:mod:`repro.workloads.derive` prices the compute gap between collectives
with a pure roofline guess (``flops / (peak * mfu)``).  This module replaces
the guess's *shape* with measurement: it runs the repaired Pallas kernel
tier (``rmsnorm`` + ``flash_attention`` for attention mixers, ``ssd_scan``
for SSM mixers, ``grouped_matmul`` for MoE and dense FFNs) over
representative slices of the exact shapes ``derive_workload`` emits, and
produces a :class:`ComputeProfile` — one calibrated window per
``(arch, shape, phase)`` — cached to JSON and loadable offline (no jax).

Calibration model (roofline-anchored relative timing)
-----------------------------------------------------
Off-TPU the kernels execute in Pallas interpret mode, so absolute wall
times are Python-speed, not hardware-speed.  What interpret mode *does*
measure faithfully is the relative cost structure across kernels — which
phase spends more time per useful FLOP (softmax/normalization overhead,
ragged-group masking, scan recurrences).  The profile therefore keeps the
roofline as the absolute anchor and redistributes it by measured
per-phase inefficiency:

    inv_eff(p)       = wall_ns(p) / flops_measured(p)
    wbar             = sum_p n_p * roofline_ns(p) * inv_eff(p)
                       / sum_p n_p * roofline_ns(p)
    calibrated_ns(p) = roofline_ns(p) * inv_eff(p) / wbar

where ``n_p`` is the phase's layer multiplicity (a 7-mamba:1-attn hybrid
weighs the ssm window seven times).  The normalization preserves the total
step compute (``sum_p n_p * calibrated == sum_p n_p * roofline``) while
phases whose kernels do more non-matmul work per FLOP get proportionally
wider windows — exactly the
quantity replay overlap conclusions are sensitive to (NeuMMU's point about
modeled vs. executed compute).  On a real TPU the same harness runs with
``interpret=False`` and the measured times *are* hardware times; the anchor
then simply corrects residual MFU error.

Module import is jax-free (profiles must load in the pure-simulator
environment); only :func:`calibrate` imports the kernel tier lazily.
"""
from __future__ import annotations

import dataclasses
import json
import math
import time
from dataclasses import asdict, dataclass, field
from pathlib import Path
from typing import Dict, Optional

from .derive import (PodSpec, _layer_is_moe, layer_roofline_ns, resolve_pod,
                     step_shape)

# v2: per-phase `layers` multiplicity entered the anchor normalization —
# v1 caches carry unweighted calibrated windows and must be re-measured.
PROFILE_VERSION = 2

# Caps keeping interpret-mode measurement tractable on CPU while staying on
# the kernels' real tiling grid (the measured slice uses the model's true
# head/state dims; only the token/sequence extents shrink).
_CAP_TOKENS = 128
_CAP_SEQ = 128
_CAP_HEADS = 4
_CAP_EXPERTS = 4
_CAP_FF = 128


@dataclass
class PhaseWindow:
    """One phase's measured + calibrated compute window (per layer)."""

    phase: str                 # attn_mixer | ssm_mixer | moe_ffn | dense_ffn
    kernels: tuple             # kernel names measured for this phase
    roofline_ns: float         # derive.py's per-layer roofline window
    measured_wall_ns: float    # wall time of the capped slice (interpreted
                               # off-TPU, compiled on a TPU)
    measured_flops: float      # analytic flops of the measured slice
    calibrated_ns: float = 0.0
    layers: int = 1            # layer multiplicity (anchor weight)

    @property
    def inv_eff(self) -> float:
        return self.measured_wall_ns / max(self.measured_flops, 1.0)


@dataclass
class ComputeProfile:
    """Per-(arch, shape) calibrated compute windows, keyed by phase."""

    arch: str
    shape: str
    n_gpus: int
    ep: int
    tp: int
    dp: int
    interpret: bool = True     # False when measured on real hardware
    version: int = PROFILE_VERSION
    phases: Dict[str, PhaseWindow] = field(default_factory=dict)

    def window_ns(self, phase: str) -> Optional[float]:
        w = self.phases.get(phase)
        return w.calibrated_ns if w is not None else None

    def matches(self, arch: str, shape: str, n_gpus: int,
                ep: Optional[int] = None, tp: Optional[int] = None,
                dp: Optional[int] = None) -> bool:
        """Is this profile valid for the given workload?  The parallelism
        split matters: rooflines (and hence calibrated windows) scale with
        ep/tp/dp, so a profile for one split must not be applied to
        another.  ``None`` skips a component (unresolved pods)."""
        return (self.arch == arch and self.shape == shape
                and self.n_gpus == n_gpus
                and (ep is None or self.ep == ep)
                and (tp is None or self.tp == tp)
                and (dp is None or self.dp == dp))

    # ------------------------------------------------------------- JSON I/O
    def to_json(self) -> str:
        d = asdict(self)
        for p in d["phases"].values():
            p["kernels"] = list(p["kernels"])
        return json.dumps(d, indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "ComputeProfile":
        d = json.loads(text)
        if d.get("version") != PROFILE_VERSION:
            raise ValueError(
                f"compute profile version {d.get('version')!r} != "
                f"{PROFILE_VERSION}; re-run calibration")
        phases = {k: PhaseWindow(**{**v, "kernels": tuple(v["kernels"])})
                  for k, v in d.pop("phases").items()}
        return cls(phases=phases, **d)

    def save(self, path) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(self.to_json())
        return path

    @classmethod
    def load(cls, path) -> "ComputeProfile":
        return cls.from_json(Path(path).read_text())


def default_cache_path(arch: str, shape: str, n_gpus: int,
                       root="calibration") -> Path:
    return Path(root) / f"{arch}_{shape}_g{n_gpus}.json"


# --------------------------------------------------------------------------
# Phase naming shared with derive.py (duck-typed configs default to attn).
# --------------------------------------------------------------------------
def layer_kind(cfg, i: int) -> str:
    pattern = getattr(cfg, "layer_pattern", ()) or ("attn",)
    return pattern[i % len(pattern)]


def mixer_phase(cfg, i: int) -> str:
    return "attn_mixer" if layer_kind(cfg, i) == "attn" else "ssm_mixer"


def ffn_phase(cfg, i: int) -> str:
    return "moe_ffn" if _layer_is_moe(cfg, i) else "dense_ffn"


# --------------------------------------------------------------------------
# Measurement harness
# --------------------------------------------------------------------------
def _time_call(fn, reps: int) -> float:
    """Best-of-``reps`` wall time (ns) of ``fn()``, after one warmup."""
    import jax

    jax.block_until_ready(fn())                    # compile + warm caches
    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e9


def _measure_attn_mixer(cfg, reps: int):
    import jax
    import jax.numpy as jnp

    from ..kernels import ops

    D = cfg.d_model
    H = min(cfg.n_heads, _CAP_HEADS)
    KV = max(1, min(cfg.n_kv_heads, H))
    Dh = cfg.d_head
    S = _CAP_SEQ
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (1, S, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (1, S, KV, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (1, S, KV, Dh), jnp.float32)
    x = jax.random.normal(ks[3], (_CAP_TOKENS, D), jnp.float32)
    w = jax.random.normal(ks[4], (D,), jnp.float32)

    wall = (_time_call(lambda: ops.rmsnorm(x, w), reps)
            + _time_call(lambda: ops.flash_attention(
                q, k, v, causal=True, block_q=min(128, S),
                block_k=min(128, S)), reps))
    flops = 4.0 * _CAP_TOKENS * D + 4.0 * H * S * S * Dh
    return wall, flops, ("rmsnorm", "flash_attention")


def _measure_ssm_mixer(cfg, reps: int):
    import jax
    import jax.numpy as jnp

    from ..kernels import ops

    H = min(max(1, cfg.d_model * cfg.ssm_expand // max(cfg.ssm_head_dim, 1)),
            2)
    P = max(cfg.ssm_head_dim, 8)
    N = min(max(cfg.ssm_state, 16), 64)
    S, chunk = _CAP_SEQ, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (1, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, S, H), jnp.float32))
    A_log = jax.random.normal(ks[2], (H,), jnp.float32) * 0.5
    B = jax.random.normal(ks[3], (1, S, N), jnp.float32) / math.sqrt(N)
    C = jax.random.normal(ks[4], (1, S, N), jnp.float32) / math.sqrt(N)

    wall = _time_call(lambda: ops.ssd_scan(x, dt, A_log, B, C, chunk=chunk),
                      reps)
    nc = S // chunk
    flops = nc * H * (2.0 * chunk * chunk * (N + P) + 2.0 * chunk * P * N)
    return wall, flops, ("ssd_scan",)


def _measure_ffn(cfg, moe: bool, reps: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..kernels import ops

    D = cfg.d_model
    F = _CAP_FF
    E = min(cfg.n_experts, _CAP_EXPERTS) if moe else 1
    T = _CAP_TOKENS
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    lhs = jax.random.normal(ks[0], (T, D), jnp.float32)
    rhs = jax.random.normal(ks[1], (E, D, F), jnp.float32) / math.sqrt(D)
    # equal ragged groups covering every row (the hot MoE case)
    offs = jnp.asarray(np.linspace(0, T, E + 1, dtype=np.int32))

    wall = _time_call(lambda: ops.grouped_matmul(lhs, rhs, offs), reps)
    flops = 2.0 * T * D * F
    return wall, flops, ("grouped_matmul",)


# --------------------------------------------------------------------------
# Roofline windows per phase — shared with derive_workload (derive.py's
# step_shape / layer_roofline_ns are the single source of the formulas, so
# the anchor can never drift from the windows derivation emits).
# --------------------------------------------------------------------------
def _phase_rooflines(cfg, spec, pod: PodSpec):
    """(phase -> per-layer roofline ns, phase -> layer multiplicity)."""
    t_step, _, flop_mult = step_shape(spec, pod)
    roof: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for i in range(cfg.n_layers):
        roof_mixer, roof_ffn = layer_roofline_ns(cfg, i, t_step, pod,
                                                 flop_mult)
        for phase, ns in ((mixer_phase(cfg, i), roof_mixer),
                          (ffn_phase(cfg, i), roof_ffn)):
            roof.setdefault(phase, ns)
            count[phase] = count.get(phase, 0) + 1
    return roof, count


_MEASURERS = {
    "attn_mixer": lambda cfg, reps: _measure_attn_mixer(cfg, reps),
    "ssm_mixer": lambda cfg, reps: _measure_ssm_mixer(cfg, reps),
    "moe_ffn": lambda cfg, reps: _measure_ffn(cfg, True, reps),
    "dense_ffn": lambda cfg, reps: _measure_ffn(cfg, False, reps),
}


def calibrate(arch, shape: str, *, pod: Optional[PodSpec] = None,
              n_gpus: Optional[int] = None, reps: int = 3,
              cache_path=None, force: bool = False) -> ComputeProfile:
    """Measure (or load) the :class:`ComputeProfile` of ``(arch, shape)``.

    ``cache_path`` (or :func:`default_cache_path`) is read unless ``force``
    and written after measurement, so CI and offline replays share one JSON
    artifact.  Measurement imports jax; loading does not.
    """
    if isinstance(arch, str):
        from ..configs import get_config            # lazy: imports jax
        cfg = get_config(arch)
    else:
        cfg = arch
    from ..configs.shapes import SHAPES             # pure-python
    spec = SHAPES[shape]

    pod = pod or PodSpec()
    if n_gpus is not None:
        pod = dataclasses.replace(pod, n_gpus=n_gpus)
    pod = resolve_pod(pod, cfg, spec.kind)

    if cache_path is not None and not force:
        p = Path(cache_path)
        if p.exists():
            try:
                prof = ComputeProfile.load(p)
            except (ValueError, KeyError, TypeError,
                    json.JSONDecodeError):
                prof = None      # stale version / corrupt cache: re-measure
            if prof is not None and prof.matches(cfg.name, shape,
                                                 pod.n_gpus, pod.ep,
                                                 pod.tp, pod.dp):
                return prof

    from ..kernels.ops import on_tpu                  # lazy: imports jax

    rooflines, counts = _phase_rooflines(cfg, spec, pod)
    phases: Dict[str, PhaseWindow] = {}
    for phase, roof in rooflines.items():
        wall, flops, kernels = _MEASURERS[phase](cfg, reps)
        phases[phase] = PhaseWindow(
            phase=phase, kernels=kernels, roofline_ns=roof,
            measured_wall_ns=wall, measured_flops=flops,
            layers=counts[phase])

    # Roofline-anchored redistribution (module docstring): preserve the
    # layer-weighted step total while phases inherit their measured
    # relative inefficiency.
    total_roof = sum(w.layers * w.roofline_ns for w in phases.values())
    wbar = (sum(w.layers * w.roofline_ns * w.inv_eff
                for w in phases.values())
            / total_roof) if total_roof > 0 else 1.0
    for w in phases.values():
        w.calibrated_ns = (w.roofline_ns * w.inv_eff / wbar
                           if wbar > 0 else w.roofline_ns)

    prof = ComputeProfile(arch=cfg.name, shape=shape, n_gpus=pod.n_gpus,
                          ep=pod.ep, tp=pod.tp, dp=pod.dp,
                          interpret=not on_tpu(), phases=phases)
    if cache_path is not None:
        prof.save(cache_path)
    return prof
