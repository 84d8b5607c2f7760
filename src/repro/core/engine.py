"""Simulation results and the one-shot :func:`simulate` entry point.

The simulator prices a collective page-epoch by page-epoch (DESIGN.md §3):
:mod:`repro.core.engine_vec` is the engine, :class:`repro.core.session.
SimSession` drives it across successive collective invocations, and the
request-level DES (:mod:`repro.core.ref_des`) is the oracle it is checked
against (DESIGN.md §7).  Both return the :class:`RunResult` defined here.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from .config import SimConfig
from .tlb import Counters


@dataclass
class IterationResult:
    completion_ns: float
    ideal_completion_ns: Optional[float] = None
    counters: Optional[Counters] = None

    @property
    def degradation(self) -> float:
        return (self.completion_ns / self.ideal_completion_ns
                if self.ideal_completion_ns else float("nan"))


@dataclass
class RunResult:
    """Output of one simulation run (possibly several iterations)."""

    iterations: List[IterationResult]
    counters: Counters
    config: SimConfig
    collective_bytes: int
    # Per-request RAT latency trace (ns), ordered by (flow, request index),
    # only populated when cfg.collect_trace.
    trace: Optional[np.ndarray] = None
    trace_flow_bounds: Optional[List[int]] = None
    mean_stall_ns: float = 0.0
    # run_iteration calls fully served by the warm fast path
    # (DESIGN.md §15.2).
    fastpath_calls: int = 0

    @property
    def completion_ns(self) -> float:
        return self.iterations[0].completion_ns

    @property
    def total_ns(self) -> float:
        return sum(it.completion_ns for it in self.iterations)

    @property
    def mean_rat_ns(self) -> float:
        return self.counters.mean_rat_ns

    def breakdown(self) -> Dict[str, float]:
        """Mean round-trip latency components per request (paper Fig. 6).

        Fabric components are the tier-0 (intra-tier) path latencies; on
        hierarchical topologies flows crossing upper tiers pay more (see
        ``Flow.oneway_ns``/``return_ns``), which shows up in completion
        time rather than in this per-request decomposition.
        """
        fab = self.config.fabric
        return {
            "oneway_ns": fab.oneway_ns,
            "rat_ns": self.counters.mean_rat_ns,
            "stall_ns": self.mean_stall_ns,
            "hbm_ns": fab.hbm_ns,
            "return_ns": fab.return_ns,
        }



def simulate(nbytes: int, cfg: SimConfig) -> RunResult:
    """Simulate ``cfg.collective`` of ``nbytes`` per GPU under ``cfg``.

    Thin wrapper over :class:`repro.core.session.SimSession`: one session is
    created, ``cfg.iterations`` back-to-back invocations of the collective
    are replayed through it (translation state stays warm across
    iterations), and the aggregate is returned.  The pattern layer supplies
    per-step flow sets; steps are dependency barriers (step k+1's flows
    start at step k's completion).  Symmetric patterns simulate one
    representative target (exact — every GPU is loaded identically);
    asymmetric ones (broadcast) simulate every receiving target regardless
    of ``cfg.symmetric``.
    """
    from .session import SimSession  # local import: session builds on this

    sess = SimSession(cfg)
    for _ in range(cfg.iterations):
        sess.run(nbytes)
    return sess.result(collective_bytes=nbytes)
