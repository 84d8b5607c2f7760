"""Benchmarks reproducing every figure of the paper, one function per figure.

Each returns a list of CSV rows ``(name, us_per_call, derived)`` where
``us_per_call`` is the simulated collective completion time and ``derived``
carries the figure's headline metric (degradation ratio, latency, fraction,
hit-rates...).  ``check_*`` fields assert the paper's claims.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

from repro.core import ratsim, paper_config, simulate, KB, MB, GB
from repro.core.config import (TLBConfig, PreTranslationConfig,
                               PrefetchConfig, FabricConfig, SimConfig,
                               TranslationConfig)

SIZES = [1 * MB, 4 * MB, 16 * MB, 64 * MB, 256 * MB, 1 * GB, 4 * GB]
GPUS = [8, 16, 32, 64]

Row = Tuple[str, float, str]

# Shared sweep memo: fig4 and fig5 read the same (n_gpus, size) grid, and
# ratsim.sweep fans it out over a process pool exactly once.
_GRID_CACHE: dict = {}


def _grid():
    return ratsim.sweep(SIZES, GPUS, cache=_GRID_CACHE)


def fig4_overhead() -> List[Row]:
    """Fig 4: RAT performance degradation vs ideal, 8-64 GPUs x 1MB-4GB."""
    grid = _grid()
    rows = []
    for n in GPUS:
        for s in SIZES:
            c = grid[(n, s)]
            rows.append((f"fig4/gpus{n}/size{s//MB}MB",
                         c.baseline.completion_ns / 1e3,
                         f"degradation={c.degradation:.4f}"))
    # headline claims
    d1 = max(grid[(n, 1 * MB)].degradation for n in GPUS)
    d16 = np.mean([grid[(n, 16 * MB)].degradation for n in GPUS])
    rows.append(("fig4/check_1MB_up_to_1.4x", 0.0,
                 f"max_deg={d1:.3f} in(1.3,1.5)={1.3 < d1 < 1.5}"))
    rows.append(("fig4/check_16MB_about_1.1x", 0.0,
                 f"mean_deg={d16:.3f} in(1.05,1.2)={1.05 < d16 < 1.2}"))
    return rows


def fig5_latency() -> List[Row]:
    """Fig 5: mean RAT latency per request, same sweep (memoized: the grid
    is priced once, by whichever of fig4/fig5 runs first)."""
    grid = _grid()
    rows = []
    for n in GPUS:
        for s in SIZES:
            r = grid[(n, s)].baseline
            rows.append((f"fig5/gpus{n}/size{s//MB}MB",
                         r.completion_ns / 1e3,
                         f"mean_rat_ns={r.mean_rat_ns:.1f}"))
    return rows


def fig6_breakdown() -> List[Row]:
    """Fig 6: round-trip latency fraction spent in RAT (16 GPUs)."""
    rows = []
    for s in SIZES:
        c = ratsim.compare(s, 16)
        b = c.baseline.breakdown()
        rows.append((f"fig6/size{s//MB}MB", c.baseline.completion_ns / 1e3,
                     f"rat_frac={c.rat_fraction:.3f};"
                     f"oneway={b['oneway_ns']:.0f};rat={b['rat_ns']:.0f};"
                     f"hbm={b['hbm_ns']:.0f};return={b['return_ns']:.0f}"))
    f1 = ratsim.compare(1 * MB, 16).rat_fraction
    rows.append(("fig6/check_1MB_rat_fraction", 0.0,
                 f"frac={f1:.3f} paper~0.30 in(0.2,0.5)={0.2 < f1 < 0.5}"))
    return rows


def fig7_hier() -> List[Row]:
    """Fig 7: hit/miss breakdown at target translation modules (16 GPUs)."""
    rows = []
    for s in SIZES:
        ctr = ratsim.run(s, 16).counters
        t = ctr.requests
        fr = {k: v / t for k, v in ctr.by_class.items()}
        l1lvl = fr["l1_hit"] + fr["l1_mshr_hum"]
        rows.append((f"fig7/size{s//MB}MB", 0.0,
                     f"l1={fr['l1_hit']:.3f};l1_mshr={fr['l1_mshr_hum']:.3f};"
                     f"l2={fr['l2_hit']:.4f};l2_hum={fr['l2_hum']:.4f};"
                     f"walk={fr['walk']:.4f};l1_level={l1lvl:.3f}"
                     f";check_gt90pct={l1lvl > 0.9}"))
    return rows


def fig8_hum() -> List[Row]:
    """Fig 8: L1-level decomposition (hits vs hit-under-miss) vs size."""
    rows = []
    prev = 0.0
    for s in SIZES:
        ctr = ratsim.run(s, 16).counters
        fr_hit = ctr.by_class["l1_hit"] / ctr.requests
        fr_hum = ctr.by_class["l1_mshr_hum"] / ctr.requests
        rows.append((f"fig8/size{s//MB}MB", 0.0,
                     f"l1_hit={fr_hit:.3f};hum={fr_hum:.3f};"
                     f"hits_grow={fr_hit >= prev}"))
        prev = fr_hit
    return rows


def fig9_10_traces() -> List[Row]:
    """Figs 9/10: per-request RAT latency traces, 1MB and 256MB (16 GPUs)."""
    rows = []
    for s, name in [(1 * MB, "fig9_1MB"), (256 * MB, "fig10_256MB")]:
        cfg = paper_config(16).replace(collect_trace=True)
        r = simulate(s, cfg)
        tr = r.trace
        spikes = float(np.mean(tr > 4 * 50.0))
        rows.append((f"{name}/trace", r.completion_ns / 1e3,
                     f"median_ns={np.median(tr):.0f};"
                     f"p99_ns={np.percentile(tr, 99):.0f};"
                     f"max_ns={tr.max():.0f};spike_frac={spikes:.4f}"))
    return rows


def fig11_l2_sweep() -> List[Row]:
    """Fig 11: L2-TLB size sweep at 16MB / 32 GPUs."""
    rows = []
    base = None
    for entries in (16, 32, 64, 512, 32768):
        cfg = paper_config(32)
        tr = dataclasses.replace(
            cfg.translation,
            l2=TLBConfig(entries=entries, assoc=2, hit_latency_ns=100.0,
                         mshr_entries=512))
        c = ratsim.compare(16 * MB, 32, cfg=cfg.replace(translation=tr))
        if entries == 32:
            base = c.degradation
        rows.append((f"fig11/l2_{entries}", c.baseline.completion_ns / 1e3,
                     f"degradation={c.degradation:.4f}"))
    big = rows[-1][2]
    rows.append(("fig11/check_flat_beyond_32", 0.0,
                 f"deg32={base:.4f};{big};flat={'degradation=%.4f' % base == big}"))
    return rows


def opt_pretranslation() -> List[Row]:
    """Paper §6.1 evaluated: fused pre-translation recovers small collectives."""
    rows = []
    for n in (16, 64):
        for s in (1 * MB, 4 * MB, 16 * MB):
            base = ratsim.compare(s, n)
            cfg = paper_config(n).replace(
                pretranslation=PreTranslationConfig(
                    enabled=True, lead_time_ns=3000.0, pages_per_flow=0))
            opt = simulate(s, cfg)
            deg = opt.completion_ns / base.ideal.completion_ns
            rows.append((f"opt_pretrans/gpus{n}/size{s//MB}MB",
                         opt.completion_ns / 1e3,
                         f"base_deg={base.degradation:.3f};opt_deg={deg:.3f};"
                         f"recovers={deg < 1.05}"))
    return rows


def opt_prefetch() -> List[Row]:
    """Paper §6.2 evaluated: software TLB prefetch under scarce ingress
    buffering (mid-stream walks stall the port; prefetch hides them)."""
    rows = []
    for s in (16 * MB, 64 * MB, 256 * MB):
        fab = FabricConfig(n_gpus=16, ingress_entries=64)
        cfg = paper_config(16).replace(fabric=fab)
        base = simulate(s, cfg)
        opt = simulate(s, cfg.replace(
            prefetch=PrefetchConfig(enabled=True, depth=2)))
        speedup = base.completion_ns / opt.completion_ns
        rows.append((f"opt_prefetch/size{s//MB}MB", opt.completion_ns / 1e3,
                     f"speedup={speedup:.3f};helps={speedup > 1.0}"))
    return rows


# multipod_all_to_all is deliberately absent: on fig12's flat default
# topology it coincides with hier_all_to_all (pod group == node group); its
# figure lives in fig14, on an actual multi_pod fabric.
COLLECTIVES = ("all_to_all", "ring_allreduce", "rd_allreduce", "all_gather",
               "reduce_scatter", "broadcast", "hier_all_to_all")


def fig12_collective_sweep() -> List[Row]:
    """Fig 12 (ours, beyond the paper): Fig-4-style RAT degradation sweep
    across collective patterns — one run answers which collectives are
    RAT-sensitive at which sizes and GPU counts."""
    rows = []
    degs_small = {}
    for coll in COLLECTIVES:
        for n in (16, 64):
            for s in (1 * MB, 16 * MB, 256 * MB):
                c = ratsim.compare(s, n, collective=coll)
                if n == 16 and s == 1 * MB:
                    degs_small[coll] = c.degradation
                rows.append((f"fig12/{coll}/gpus{n}/size{s//MB}MB",
                             c.baseline.completion_ns / 1e3,
                             f"degradation={c.degradation:.4f};"
                             f"mean_rat_ns={c.baseline.mean_rat_ns:.1f}"))
    # Headline: all-pairs pays n-1 cold walks concurrently on one step's
    # critical path (worst), broadcast trees re-pay the cold working set at
    # every hop (close behind); bandwidth-optimal rings amortize a single
    # cold walk over 2(n-1) steps (best).
    worst = max(degs_small, key=degs_small.get)
    best = min(degs_small, key=degs_small.get)
    rows.append(("fig12/check_1MB_16gpu_sensitivity_spread", 0.0,
                 f"worst={worst}:{degs_small[worst]:.3f};"
                 f"best={best}:{degs_small[best]:.3f};"
                 f"spread={degs_small[worst] - degs_small[best]:.3f}"))
    return rows


def fig13_workload_replay() -> List[Row]:
    """Fig 13 (ours, beyond the paper): per-token RAT degradation trajectory
    of a real MoE decode loop replayed through a persistent-TLB session.

    Token 0 pays the cold Link-TLB walks of every layer's dispatch/combine
    all-to-all; later tokens reuse the warmed entries — the paper's
    warm-vs-cold claim evaluated on the workload it matters for.  The large
    qwen3-moe rows show the contrasting regime: its per-layer buffer
    working set exceeds L2 Link-TLB reach, so even steady-state tokens keep
    walking (capacity, not cold, misses).
    """
    from repro.workloads import derive_workload, replay

    rows = []
    for arch, n_tok in (("granite-moe-1b-a400m", 4),
                        ("qwen3-moe-235b-a22b", 2)):
        trace = derive_workload(arch, "decode_32k", n_gpus=16, n_steps=n_tok)
        rep = replay(trace)
        for s in rep.steps:
            rows.append((f"fig13/{arch}/token{s.step}", s.comm_ns / 1e3,
                         f"degradation={s.degradation:.4f};walks={s.walks}"))
        cold, steady = rep.cold_degradation, rep.steady_degradation
        rows.append((f"fig13/{arch}/check_cold_above_steady", 0.0,
                     f"cold={cold:.4f};steady={steady:.4f};"
                     f"warms_up={cold > steady}"))
    return rows


def fig13_workload_replay_calibrated() -> List[Row]:
    """Fig 13 (calibrated): the same decode replays with compute windows
    *measured* on the repaired Pallas kernel tier instead of the roofline
    guess (repro.workloads.calibrate), plus the paper-§6.1 question those
    windows finally make answerable: how much of each token-0 cold-RAT
    excess could a fused pre-translation pass hide inside the calibrated
    compute window that precedes the collective — reported per phase
    (per-layer windows) and per arch.
    """
    from repro.workloads import (calibrate, default_cache_path,
                                 derive_workload, replay)

    rows = []
    for arch, n_tok in (("granite-moe-1b-a400m", 4),
                        ("qwen3-moe-235b-a22b", 2)):
        prof = calibrate(arch, "decode_32k", n_gpus=16,
                         cache_path=default_cache_path(arch, "decode_32k",
                                                       16))
        trace = derive_workload(arch, "decode_32k", n_gpus=16,
                                n_steps=n_tok, compute_profile=prof)
        rep = replay(trace, compute_profile=prof)
        for s in rep.steps:
            rows.append((f"fig13cal/{arch}/token{s.step}", s.comm_ns / 1e3,
                         f"degradation={s.degradation:.4f};walks={s.walks};"
                         f"compute_us={s.compute_ns/1e3:.2f}"))
        # Fused pre-translation headroom: a pre-translation pass issued with
        # the producing compute hides at most min(window, cold excess) of
        # each collective's RAT overhead.  Token 0 only — that is where the
        # cold walks live.
        ideal_ns = {(r.collective, r.nbytes, r.n_gpus): r.completion_ns
                    for r in rep.ideal_calls}
        by_phase: dict = {}
        for c, rec in zip(trace.calls, rep.calls):
            if c.step != 0:
                continue
            ex = rec.completion_ns - ideal_ns[(c.collective, c.nbytes,
                                               c.group)]
            if ex <= 0:
                continue
            key = c.phase or "untagged"
            agg = by_phase.setdefault(key, [0.0, 0.0])
            agg[0] += ex
            agg[1] += min(c.compute_ns, ex)
        tot_ex = sum(v[0] for v in by_phase.values())
        tot_hide = sum(v[1] for v in by_phase.values())
        for ph, (ex, hide) in sorted(by_phase.items()):
            rows.append((f"fig13cal/{arch}/hide/{ph}", 0.0,
                         f"cold_excess_us={ex/1e3:.2f};"
                         f"hideable_us={hide/1e3:.2f};"
                         f"frac={hide/ex:.3f}"))
        rows.append((f"fig13cal/{arch}/pretrans_hiding", 0.0,
                     f"cold_excess_us={tot_ex/1e3:.2f};"
                     f"hideable_us={tot_hide/1e3:.2f};"
                     f"frac={tot_hide/tot_ex if tot_ex else 0.0:.3f}"))
    return rows


GPUS14 = (16, 64, 256, 1024)
TOPOS14 = ("single_clos", "two_tier", "multi_pod")


def fig14_topology_scaling() -> List[Row]:
    """Fig 14 (ours, beyond the paper): pod-scale RAT degradation, 16 -> 1024
    GPUs, per topology, cold vs warm Link TLBs.

    One sweep (fanned over the process pool) prices every (topology, pod
    size, buffer size) point with ``iterations=2``: iteration 0 is the cold
    collective, iteration 1 reruns it on the warmed TLBs — so each point
    yields the cold and the warm degradation against the same zero-RAT
    ideal.  Tier parameters: 16-GPU leaves under a 2x-oversubscribed spine
    (``two_tier``) and 16-GPU Clos pods over a 4x-oversubscribed scale-out
    hop (``multi_pod``); at 16 GPUs both degenerate to the single Clos, so
    the three curves share their leftmost point by construction.
    """
    sizes = (1 * MB, 16 * MB)
    base = SimConfig(fabric=FabricConfig(leaf_size=16, oversubscription=2.0,
                                         pod_size=16),
                     iterations=2)
    grid = ratsim.sweep(sizes, GPUS14, topologies=TOPOS14, base_cfg=base)
    rows = []
    for topo in TOPOS14:
        for n in GPUS14:
            for s in sizes:
                c = grid[(topo, n, s)]
                b, i = c.baseline.iterations, c.ideal.iterations
                cold = b[0].completion_ns / i[0].completion_ns
                warm = b[1].completion_ns / i[1].completion_ns
                rows.append((f"fig14/{topo}/gpus{n}/size{s//MB}MB",
                             b[0].completion_ns / 1e3,
                             f"cold_deg={cold:.4f};warm_deg={warm:.4f}"))
    # Headline checks: the 16-GPU points coincide across topologies
    # (degenerate tiers), and warm TLBs erase (almost all of) the cold tax
    # at every scale and topology.
    agree = all(
        grid[(t, 16, s)].baseline.completion_ns
        == grid[("single_clos", 16, s)].baseline.completion_ns
        for t in TOPOS14 for s in sizes)
    rows.append(("fig14/check_16gpu_topologies_degenerate", 0.0,
                 f"agree={agree}"))
    warm_ok = all(
        (grid[(t, n, s)].baseline.iterations[1].completion_ns
         <= grid[(t, n, s)].baseline.iterations[0].completion_ns + 1e-9)
        for t in TOPOS14 for n in GPUS14 for s in sizes)
    rows.append(("fig14/check_warm_never_worse_than_cold", 0.0,
                 f"ok={warm_ok}"))
    # Pattern choice on the scale-out hop: pod-staged multipod_all_to_all
    # vs direct all-to-all on the same multi_pod fabric — staging trades
    # 2x volume for (pods-1) oversubscribed crossings per GPU instead of
    # (n - n/pods).
    for n in (64, 256):
        fab = FabricConfig(n_gpus=n, topology="multi_pod", pod_size=16)
        direct = ratsim.compare(16 * MB, n, cfg=SimConfig(fabric=fab))
        staged = ratsim.compare(
            16 * MB, n,
            cfg=SimConfig(fabric=fab, collective="multipod_all_to_all"))
        rows.append((f"fig14/multipod_vs_direct/gpus{n}", 0.0,
                     f"direct_us={direct.baseline.completion_ns/1e3:.2f};"
                     f"staged_us={staged.baseline.completion_ns/1e3:.2f};"
                     f"direct_deg={direct.degradation:.4f};"
                     f"staged_deg={staged.degradation:.4f}"))
    return rows


# fig15 serving grid: one arch (the small latency-sensitive MoE — the
# paper's 1.4x regime), short outputs so several busy/idle cycles fit the
# step cap, retention well under the inter-burst gaps so every quiet period
# flushes the warmed Link TLBs.
_FIG15_BASE = dict(arch="granite-moe-1b-a400m", n_requests=24, seed=7,
                   retention_ns=50_000.0, steps_cap=120, burst_size=4,
                   burstiness=24.0, prompt_mean=128, output_mean=8)


def fig15_serving_tail_latency() -> List[Row]:
    """Fig 15 (ours, beyond the paper): request-level serving tail latency.

    Bursty arrivals drive a continuous-batching serving simulation
    (repro.serving) in which idle gaps between bursts outlive
    ``tlb_retention_ns``: each burst's leading steps re-pay the cold
    Link-TLB walks, so RAT degradation concentrates in the TTFT *tail*
    (p99 > mean) — the paper's small-collective cold-miss regime expressed
    as what it does to serving SLOs.  The §6 optimizations are measured on
    the same stream: fused pre-translation (§6.1) claws tail latency back,
    software prefetch (§6.2) is reported for completeness (decode
    collectives are too small to build mid-stream walk queues).
    """
    from repro.serving import TrafficPoint, sweep_traffic

    pts = {
        "bursty/single_clos/l2_512/rps16": TrafficPoint(
            rps=16.0, arrival="bursty", **_FIG15_BASE),
        "bursty/two_tier/l2_512/rps16": TrafficPoint(
            rps=16.0, arrival="bursty", topology="two_tier", leaf_size=8,
            oversubscription=2.0, **_FIG15_BASE),
        "bursty/single_clos/l2_64/rps16": TrafficPoint(
            rps=16.0, arrival="bursty", l2_entries=64, **_FIG15_BASE),
        "bursty/single_clos/l2_512/rps4": TrafficPoint(
            rps=4.0, arrival="bursty", **_FIG15_BASE),
        "poisson/single_clos/l2_512/rps16": TrafficPoint(
            rps=16.0, arrival="poisson", **_FIG15_BASE),
        "bursty/single_clos/l2_512/rps16/pretrans": TrafficPoint(
            rps=16.0, arrival="bursty", pretranslation=True, **_FIG15_BASE),
        "bursty/single_clos/l2_512/rps16/prefetch": TrafficPoint(
            rps=16.0, arrival="bursty", prefetch=True, **_FIG15_BASE),
    }
    grid = sweep_traffic(list(pts.values()))
    rows = []
    res = {name: grid[pt] for name, pt in pts.items()}
    for name, r in res.items():
        ttft = r.ttft_percentiles()
        cold, warm = r.cold_comm_ns, r.warm_comm_ns
        rows.append((f"fig15/{name}", ttft[50.0] / 1e3,
                     f"mean_deg={r.mean_ttft_degradation:.4f};"
                     f"p99_deg={r.p99_ttft_degradation:.4f};"
                     f"ttft_p99_us={ttft[99.0]/1e3:.1f};"
                     f"cold_steps={r.cold_steps};"
                     f"cold_frac={cold/(cold+warm or 1):.4f}"))
    bursty = [n for n in res if n.startswith("bursty") and "/pre" not in n]
    tails = {n: (res[n].p99_ttft_degradation, res[n].mean_ttft_degradation)
             for n in bursty}
    rows.append(("fig15/check_bursty_tail_concentration", 0.0,
                 "p99_exceeds_mean="
                 + str(all(p > m for p, m in tails.values()))
                 + ";" + ";".join(f"{n.split('/', 1)[0]}_{i}="
                                  f"{p:.3f}>{m:.3f}"
                                  for i, (n, (p, m))
                                  in enumerate(tails.items()))))
    base = res["bursty/single_clos/l2_512/rps16"]
    pre = res["bursty/single_clos/l2_512/rps16/pretrans"]
    pf = res["bursty/single_clos/l2_512/rps16/prefetch"]
    rows.append(("fig15/check_pretranslation_claws_back_tail", 0.0,
                 f"base_p99={base.p99_ttft_degradation:.4f};"
                 f"pretrans_p99={pre.p99_ttft_degradation:.4f};"
                 f"claws_back="
                 f"{pre.p99_ttft_degradation < base.p99_ttft_degradation}"))
    rows.append(("fig15/prefetch_delta", 0.0,
                 f"base_p99={base.p99_ttft_degradation:.4f};"
                 f"prefetch_p99={pf.p99_ttft_degradation:.4f}"))
    return rows


# fig16 fleet grid: the fig15 serving regime served by a *fleet* of pod
# replicas.  No TLB retention — a warmed replica stays warm forever, so the
# only cold-RAT events after the initial warmup are replicas *born* cold by
# the autoscaler.  Steady-state percentiles discard the first quarter of
# the stream (both fleets start cold at t=0; the comparison isolates the
# spin-up tax, not the shared warmup).
_FIG16_BASE = dict(arch="granite-moe-1b-a400m", n_requests=32, seed=7,
                   steps_cap=400, burst_size=4, burstiness=24.0,
                   prompt_mean=128, output_mean=8, rps=16.0,
                   arrival="bursty")
_FIG16_SLO = 1.25          # p99 TTFT degradation the fleet must hold


def _steady_p99_deg(res, after_ns: float) -> float:
    d = [r.ttft_degradation for r in res.first_token_served
         if r.req.arrival_ns >= after_ns
         and r.ttft_degradation is not None]
    return float(np.percentile(d, 99.0)) if d else float("nan")


def fig16_fleet_scaling() -> List[Row]:
    """Fig 16 (ours, beyond the paper): fleet provisioning vs the RAT tax.

    The same bursty stream served by fleets of pod replicas
    (repro.serving.fleet): replica counts and routing policies answer
    "what holds p99 TTFT degradation under the SLO at this rps", and a
    queue-depth autoscaler at *equal aggregate capacity* shows the cost of
    elasticity — every replica it spins up starts with stone-cold Link
    TLBs, so scale-up events re-inject the cold-walk warmup into the
    steady-state tail that a statically provisioned (once-warmed) fleet
    has already paid off.
    """
    from repro.serving import FleetPoint, TrafficPoint, sweep_fleet

    traffic = TrafficPoint(**_FIG16_BASE)
    reqs = traffic.requests()
    cut_ns = reqs[len(reqs) // 4].arrival_ns
    churn = dict(autoscale=True, min_replicas=1, scale_up_queued=1,
                 scale_down_idle_ns=5e7)
    pts = {
        "static/r1/round_robin": FleetPoint(traffic=traffic, replicas=1),
        "static/r2/round_robin": FleetPoint(traffic=traffic, replicas=2),
        "static/r4/round_robin": FleetPoint(traffic=traffic, replicas=4),
        "static/r2/least_loaded": FleetPoint(
            traffic=traffic, replicas=2, router="least_loaded"),
        "static/r2/affinity": FleetPoint(
            traffic=traffic, replicas=2, router="affinity"),
        "auto/r2/churn": FleetPoint(
            traffic=traffic, replicas=2, max_replicas=2, **churn),
        "auto/r2/churn_slow_spin": FleetPoint(
            traffic=traffic, replicas=2, max_replicas=2,
            spinup_latency_ns=2e7, **churn),
    }
    grid = sweep_fleet(list(pts.values()))
    res = {name: grid[pt] for name, pt in pts.items()}
    rows = []
    for name, r in res.items():
        p99 = _steady_p99_deg(r, cut_ns)
        ttft = r.ttft_percentiles()
        rows.append((f"fig16/{name}", ttft[50.0] / 1e3,
                     f"steady_p99_deg={p99:.4f};"
                     f"mean_deg={r.mean_ttft_degradation:.4f};"
                     f"ttft_p99_us={ttft[99.0]/1e3:.1f};"
                     f"spin_ups={r.spin_ups};retired={r.retired};"
                     f"rejected={len(r.rejected)};"
                     f"cold_steps={r.cold_steps};"
                     f"holds_slo={p99 < _FIG16_SLO}"))
    static = res["static/r2/round_robin"]
    auto = res["auto/r2/churn"]
    s_p99 = _steady_p99_deg(static, cut_ns)
    a_p99 = _steady_p99_deg(auto, cut_ns)
    rows.append(("fig16/check_cold_spinup_tax", 0.0,
                 f"static_p99={s_p99:.4f};auto_p99={a_p99:.4f};"
                 f"spin_ups={auto.spin_ups};"
                 f"equal_capacity={auto.peak_replicas <= 2};"
                 f"taxed={bool(auto.spin_ups >= 1 and a_p99 > s_p99)}"))
    # The provisioning answer: smallest static fleet holding the SLO.
    fits = [n.split("/")[1] for n in
            ("static/r1/round_robin", "static/r2/round_robin",
             "static/r4/round_robin")
            if _steady_p99_deg(res[n], cut_ns) < _FIG16_SLO]
    rows.append(("fig16/check_static_provisioning", 0.0,
                 f"rps={_FIG16_BASE['rps']};slo={_FIG16_SLO};"
                 f"smallest_fit={fits[0] if fits else 'none'};"
                 f"any_fit={bool(fits)}"))
    return rows


# fig17 deployment: 4 KB translation granules (the host-page regime) on the
# 16-GPU Clos.  Under Table 1's 2 MB pages the cold-walk tax is a ~1 us
# additive constant that never flips the algorithm choice; at 4 KB the tax
# scales with the page count AND with how the algorithm's step structure
# exposes it (a 2(n-1)-step ring re-pays a walk tail at every step barrier,
# recursive doubling concentrates all walks in step 0), so cold and warm
# completions rank the candidates differently near the ring/rd bandwidth
# crossover.  Sizes are bucket-unique (one per power-of-two bucket) so each
# prices its own PolicyTable row; 33 MB sits inside the crossover band.
_FIG17_SIZES = (8 * MB, 16 * MB, 33 * MB, 64 * MB, 128 * MB)
_FIG17_N = 16


def fig17_algorithm_selection() -> List[Row]:
    """Fig 17 (ours, beyond the paper): RAT-aware algorithm selection.

    The policy layer (repro.core.select, DESIGN.md §14) prices every
    registered candidate of a logical collective per (size, fabric, cold |
    warm Link-TLB state).  This figure shows the selection surface for
    ``allreduce`` on small translation pages: recursive doubling wins the
    latency-bound sizes, the ring wins bandwidth-bound sizes, and in the
    crossover band the *cold* optimum (rd — one concentrated walk storm)
    differs from the *warm* optimum (ring — cheaper steady-state bytes).
    A PolicyTable built from the same pricing then beats the fixed default
    end-to-end through a persistent-TLB session: cold call resolved to rd,
    warm re-issue of the same buffer resolved back to ring.
    """
    from repro.core.select import AutoPolicy, FixedPolicy, build_policy_table
    from repro.core.session import SimSession

    base = SimConfig(translation=TranslationConfig(page_bytes=4 * KB))
    fab = FabricConfig(n_gpus=_FIG17_N)
    auto = AutoPolicy(base=base)
    rows = []
    diverging = []
    for s in _FIG17_SIZES:
        sc = auto.scores("allreduce", s, fab)
        cold = min(sc, key=lambda c: sc[c][0])
        warm = min(sc, key=lambda c: sc[c][1])
        if cold != warm:
            diverging.append(s)
        for cand in sorted(sc):
            c_ns, w_ns = sc[cand]
            rows.append((f"fig17/allreduce/size{s//MB}MB/{cand}",
                         c_ns / 1e3,
                         f"cold_us={c_ns/1e3:.2f};warm_us={w_ns/1e3:.2f};"
                         f"cold_pick={cand == cold};"
                         f"warm_pick={cand == warm}"))
    rows.append(("fig17/check_cold_warm_optima_diverge", 0.0,
                 f"page_kb=4;gpus={_FIG17_N};topology=single_clos;"
                 f"diverging_sizes_mb={[s // MB for s in diverging]};"
                 f"any={bool(diverging)}"))

    # The deployable artifact: a PolicyTable cached from the same pricing
    # (the AutoPolicy memo is shared, so nothing is simulated twice).
    table = build_policy_table(_FIG17_SIZES, [_FIG17_N],
                               logicals=("allreduce",), base=base, auto=auto)
    sz = diverging[0] if diverging else 33 * MB
    for state in ("cold", "warm"):
        res = table.resolve("allreduce", sz, fab, state=state)
        rows.append((f"fig17/table/size{sz//MB}MB/{state}", 0.0,
                     f"collective={res.collective};"
                     f"provenance={res.provenance}"))

    # End-to-end on the diverging point, replayed through SimSession with
    # the policy threaded (the same path derivation and serving use), in
    # the regime where the cold-state entry matters: idle gaps past
    # ``tlb_retention_ns`` flush the warmth between calls (fig15's bursty
    # re-entry), so every call resolves in cold state — the table rides rd
    # where the fixed default re-pays ring's per-step walk tails.
    n_calls = 3
    cfg = base.replace(fabric=fab, tlb_retention_ns=500_000.0)
    totals = {}
    for name, pol in (("fixed", FixedPolicy()), ("table", table)):
        sess = SimSession(cfg, policy=pol)
        recs = [sess.run(sz, collective="allreduce",
                         gap_ns=0.0 if i == 0 else 1e6, label=f"call{i}")
                for i in range(n_calls)]
        totals[name] = sum(r.completion_ns for r in recs)
        rows.append((f"fig17/session/flushed/{name}", totals[name] / 1e3,
                     ";".join(f"{r.label}={r.collective}:"
                              f"{r.completion_ns/1e3:.2f}us"
                              for r in recs)))
    gain = totals["fixed"] - totals["table"]
    rows.append(("fig17/check_table_beats_fixed_default", 0.0,
                 f"size_mb={sz//MB};calls={n_calls};"
                 f"fixed_us={totals['fixed']/1e3:.2f};"
                 f"table_us={totals['table']/1e3:.2f};"
                 f"gain_us={gain/1e3:.2f};strict={gain > 0}"))
    # The steady-warm counterpoint, reported for honesty: switching
    # algorithms also switches which stations hold the warm L1 entries, so
    # a cold rd -> warm ring transition re-fills L1s from L2 once — in a
    # never-flushed steady loop the table's warm entry (ring, the fixed
    # choice) is what keeps it from paying that transition repeatedly.
    warm_cfg = base.replace(fabric=fab)
    for name, pol in (("fixed", FixedPolicy()), ("table", table)):
        sess = SimSession(warm_cfg, policy=pol)
        recs = [sess.run(sz, collective="allreduce", label=f"call{i}")
                for i in range(3)]
        rows.append((f"fig17/session/steady/{name}",
                     sum(r.completion_ns for r in recs) / 1e3,
                     ";".join(f"{r.label}={r.collective}:"
                              f"{r.completion_ns/1e3:.2f}us"
                              for r in recs)))
    return rows


# fig18 disaggregation grid: the fig15 arch with prompts pinned near the
# 4096-token cap (prompt_mean far above it, so ~80% of shards are the full
# ~12.6MB and the decode pod's KV arena ring wraps within the stream —
# transfers reach their steady-state regime), short outputs so the decode
# pods drain within the step budget.
_FIG18_BASE = dict(arch="granite-moe-1b-a400m", n_requests=32, seed=7,
                   prompt_mean=16384, output_mean=8)
_FIG18_TOPOS = {
    "single_clos": {},
    "two_tier": dict(topology="two_tier", leaf_size=8, oversubscription=2.0),
}
_FIG18_RPS = (4.0, 16.0)
_FIG18_SMALL_L2 = 8
# L2-axis arena: 6 full-prompt slots (84MB) — several ring laps within 32
# requests.  At the Table-1 L2 (512 x 2MB = 1GB reach) the whole arena
# stays resident after lap 1; at 8 entries (16MB reach) steady-state
# transfers keep re-walking.
_FIG18_ARENA = 6 * 14 * MB


def fig18_disaggregation() -> List[Row]:
    """Fig 18 (ours, beyond the paper): prefill/decode disaggregation.

    Disaggregated serving (repro.serving.disagg, DESIGN.md §16) routes
    every request through an explicit KV-cache transfer across the
    ``multi_pod`` scale-out hop, priced at the decode pod's Link-MMU —
    TTFT gains a reverse-translation term the colocated deployment never
    pays.  The grid crosses rps x topology for colocated-vs-disagg TTFT
    and ITL percentiles (the crossover is reported as measured — disagg
    wins only where prefill/decode interference outweighs the hop), and
    an L2-reach axis isolates the two-regime claim: with the Table-1 L2
    the transfer working set stays resident and the cold-RAT excess is
    <2% of TTFT; shrinking the L2 below the KV shard's page footprint
    makes every transfer re-walk, and the excess becomes visible in the
    TTFT decomposition.
    """
    from repro.serving import TrafficPoint, sweep_traffic
    from repro.serving.disagg import DisaggPoint, sweep_disagg

    co_pts, dg_pts = {}, {}
    for rps in _FIG18_RPS:
        for topo, kw in _FIG18_TOPOS.items():
            t = TrafficPoint(rps=rps, **kw, **_FIG18_BASE)
            name = f"{topo}/rps{rps:g}"
            co_pts[name] = t
            dg_pts[name] = DisaggPoint(traffic=t)
    for l2, tag in ((_FIG18_SMALL_L2, f"l2_{_FIG18_SMALL_L2}"),
                    (0, "l2_default")):
        dg_pts[f"{tag}/rps16"] = DisaggPoint(
            traffic=TrafficPoint(rps=16.0, l2_entries=l2, **_FIG18_BASE),
            kv_arena_bytes=_FIG18_ARENA)
    co = sweep_traffic(list(co_pts.values()))
    dg = sweep_disagg(list(dg_pts.values()))

    def steady_cold(r):
        # Handoffs landing at an already-visited arena offset: their pages
        # were translated a lap ago, so any walk is a reach/retention
        # re-walk, not first-contact warmup.
        seen, cold = set(), 0
        for h in sorted(r.handoffs, key=lambda h: h.start_ns):
            if h.offset in seen and h.walks > 0:
                cold += 1
            seen.add(h.offset)
        return cold

    rows = []
    frac = {}
    for name, dp in dg_pts.items():
        r = dg[dp]
        ttft = r.ttft_percentiles()
        itl = r.itl_percentiles()
        bd = r.ttft_breakdown()
        frac[name] = bd["kv_excess_ns"] / bd["ttft_ns"]
        rows.append((f"fig18/disagg/{name}", ttft[50.0] / 1e3,
                     f"ttft_p99_us={ttft[99.0]/1e3:.1f};"
                     f"itl_p50_us={itl[50.0]/1e3:.2f};"
                     f"prefill_us={bd['prefill_ns']/1e3:.1f};"
                     f"kv_transfer_us={bd['kv_transfer_ns']/1e3:.2f};"
                     f"kv_excess_us={bd['kv_excess_ns']/1e3:.2f};"
                     f"decode_wait_us={bd['decode_wait_ns']/1e3:.1f};"
                     f"kv_excess_frac={frac[name]:.5f};"
                     f"cold_handoffs={r.kv_cold_handoffs};"
                     f"steady_cold={steady_cold(r)};"
                     f"kv_walks={r.kv_walks}"))
    for name, tp in co_pts.items():
        r = co[tp]
        ttft = r.ttft_percentiles()
        itl = r.itl_percentiles()
        d = dg[dg_pts[name]].ttft_percentiles()
        rows.append((f"fig18/colocated/{name}", ttft[50.0] / 1e3,
                     f"ttft_p99_us={ttft[99.0]/1e3:.1f};"
                     f"itl_p50_us={itl[50.0]/1e3:.2f};"
                     f"disagg_ttft_p50_us={d[50.0]/1e3:.1f};"
                     f"disagg_wins_p50={d[50.0] < ttft[50.0]}"))
    # Two-regime split: at default L2 reach the whole arena is resident
    # after lap 1 — repeat-offset transfers never walk again; at small
    # reach the steady state keeps re-walking and the cold excess recurs.
    # (The excess stays a tiny fraction of TTFT in both regimes: a multi-MB
    # KV transfer amortizes its walks exactly like the paper's large
    # collectives — the split is in the *recurrence*, and the warm-reach
    # fraction bound is the honest "vanishes" criterion.)
    small_r = dg[dg_pts[f"l2_{_FIG18_SMALL_L2}/rps16"]]
    default_r = dg[dg_pts["l2_default/rps16"]]
    small, default = frac[f"l2_{_FIG18_SMALL_L2}/rps16"], \
        frac["l2_default/rps16"]
    rows.append(("fig18/check_two_regime_split", 0.0,
                 f"small_l2_excess_frac={small:.6f};"
                 f"default_excess_frac={default:.6f};"
                 f"small_l2_steady_cold={steady_cold(small_r)};"
                 f"default_steady_cold={steady_cold(default_r)};"
                 f"small_l2_walks={small_r.kv_walks};"
                 f"default_walks={default_r.kv_walks};"
                 f"cold_recurs_at_small_reach="
                 f"{steady_cold(small_r) > steady_cold(default_r)};"
                 f"vanishes_at_default_reach={default < 0.02}"))
    return rows


def sched_costmodel() -> List[Row]:
    """Framework integration: cost model accuracy + warm-up chunk plans."""
    from repro.core.cost_model import CostModel
    from repro.core.scheduler import TranslationAwareScheduler
    rows = []
    m = CostModel(paper_config(16))
    for s, (mod, sim, err) in m.validate(
            [1 * MB, 4 * MB, 16 * MB, 64 * MB, 256 * MB]).items():
        rows.append((f"costmodel/size{s//MB}MB", sim / 1e3,
                     f"model_us={mod/1e3:.2f};err={err:.3f};ok={err < 0.1}"))
    sch = TranslationAwareScheduler(n_gpus=16, overlap_compute_ns=5e3)
    for s in (1 * MB, 8 * MB, 64 * MB):
        plan = sch.plan_all_to_all(s)
        rows.append((f"scheduler/size{s//MB}MB", plan.est_time_ns / 1e3,
                     f"warmup_B={plan.warmup_chunk_bytes};chunks={plan.n_chunks};"
                     f"est_speedup={plan.est_speedup:.3f}"))
    return rows


ALL = [fig4_overhead, fig5_latency, fig6_breakdown, fig7_hier, fig8_hum,
       fig9_10_traces, fig11_l2_sweep, fig12_collective_sweep,
       fig13_workload_replay, fig13_workload_replay_calibrated,
       fig14_topology_scaling, fig15_serving_tail_latency,
       fig16_fleet_scaling, fig17_algorithm_selection,
       fig18_disaggregation, opt_pretranslation, opt_prefetch,
       sched_costmodel]
