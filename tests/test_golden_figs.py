"""Golden regression locks for benchmarks/paper_figs.py row values.

Captured from the pre-session (PR 1) engine at the seed configuration; the
session refactor (and anything after it) must reproduce these bit-for-bit —
``completion_ns`` values are exact float equality, ratios are pinned to
1e-12.  If a change legitimately alters the physics, recapture deliberately.
"""
import dataclasses

import pytest

from repro.core import ratsim, paper_config, MB
from repro.core.config import TLBConfig

# (n_gpus, size) -> (baseline_ns, ideal_ns, mean_rat_ns, requests, walks)
FIG45_GOLDEN = {
    (8, 1 * MB): (3890.0, 2762.32, 1413.8399999999995, 3584, 1),
    (16, 1 * MB): (3890.0, 2802.0, 1394.0, 3840, 1),
    (64, 1 * MB): (3890.0, 2825.04, 1382.4799999999975, 4032, 1),
    (16, 16 * MB): (13342.48, 12018.0, 76.39673828124994, 61440, 8),
    (32, 16 * MB): (13642.64, 12343.119999999999, 71.62859248991907, 63488, 8),
}


@pytest.mark.parametrize("n,size", sorted(FIG45_GOLDEN))
def test_fig4_fig5_rows_bit_for_bit(n, size):
    base, ideal, mean_rat, reqs, walks = FIG45_GOLDEN[(n, size)]
    c = ratsim.compare(size, n)
    assert c.baseline.completion_ns == base
    assert c.ideal.completion_ns == ideal
    assert c.baseline.mean_rat_ns == pytest.approx(mean_rat, rel=1e-12)
    assert c.baseline.counters.requests == reqs
    assert c.baseline.counters.walks == walks


# fig11: L2-TLB size sweep at 16 MB / 32 GPUs — flat beyond 32 entries.
FIG11_GOLDEN = {32: 13642.64, 512: 13642.64, 32768: 13642.64}
FIG11_DEG = 1.1052829430484352


@pytest.mark.parametrize("entries", sorted(FIG11_GOLDEN))
def test_fig11_rows_bit_for_bit(entries):
    cfg = paper_config(32)
    tr = dataclasses.replace(
        cfg.translation,
        l2=TLBConfig(entries=entries, assoc=2, hit_latency_ns=100.0,
                     mshr_entries=512))
    c = ratsim.compare(16 * MB, 32, cfg=cfg.replace(translation=tr))
    assert c.baseline.completion_ns == FIG11_GOLDEN[entries]
    assert c.degradation == pytest.approx(FIG11_DEG, rel=1e-12)


def test_sweep_matches_compare_rows():
    # The figure grid is produced through the (parallel) sweep executor;
    # its values must equal the direct compare() calls above.
    grid = ratsim.sweep([1 * MB, 16 * MB], [16])
    for size in (1 * MB, 16 * MB):
        c = ratsim.compare(size, 16)
        g = grid[(16, size)]
        assert g.baseline.completion_ns == c.baseline.completion_ns
        assert g.ideal.completion_ns == c.ideal.completion_ns


# --------------------------------------------------------------------------
# Calibration-off replay goldens (PR 2 values): threading compute_profile
# through derive/replay/SimSession must leave the default path bit-for-bit.
# --------------------------------------------------------------------------

# Pure-simulator lock (no jax): TinyMoE decode_32k on 8 GPUs, 3 steps ->
# (step, comm_ns, ideal_comm_ns, walks, requests).
TINY_REPLAY_GOLDEN = [
    (0, 151804.15999999968, 141288.96000000002, 12, 7168),
    (1, 144488.95999999967, 141288.96000000002, 0, 7168),
    (2, 144488.9600000009, 141288.96000000002, 0, 7168),
]


def test_replay_calibration_off_bit_for_bit():
    from repro.workloads import derive_workload, replay

    class TinyMoE:
        name = "tiny-moe"
        n_layers = 4
        d_model = 512
        n_heads = 8
        n_kv_heads = 4
        d_head = 64
        d_ff = 0
        n_experts = 16
        top_k = 2
        d_ff_expert = 256
        moe_every = 1
        capacity_factor = 1.25

    rep = replay(derive_workload(TinyMoE(), "decode_32k", n_gpus=8,
                                 n_steps=3))
    got = [(s.step, s.comm_ns, s.ideal_comm_ns, s.walks, s.requests)
           for s in rep.steps]
    assert got == TINY_REPLAY_GOLDEN


# fig13 rows exactly as PR 2 emitted them (needs jax: real arch configs).
FIG13_GOLDEN = [
    ("fig13/granite-moe-1b-a400m/token0", 1769.4556799999705,
     "degradation=1.0385;walks=72"),
    ("fig13/granite-moe-1b-a400m/token1", 1744.4236800000135,
     "degradation=1.0238;walks=0"),
    ("fig13/granite-moe-1b-a400m/token2", 1744.4236800000476,
     "degradation=1.0238;walks=0"),
    ("fig13/granite-moe-1b-a400m/token3", 1744.423680000052,
     "degradation=1.0238;walks=0"),
    ("fig13/granite-moe-1b-a400m/check_cold_above_steady", 0.0,
     "cold=1.0385;steady=1.0238;warms_up=True"),
    ("fig13/qwen3-moe-235b-a22b/token0", 7828.577360000854,
     "degradation=1.0265;walks=846"),
    ("fig13/qwen3-moe-235b-a22b/token1", 7826.327200001521,
     "degradation=1.0262;walks=796"),
    ("fig13/qwen3-moe-235b-a22b/check_cold_above_steady", 0.0,
     "cold=1.0265;steady=1.0262;warms_up=True"),
]


def test_fig13_rows_bit_for_bit():
    jax = pytest.importorskip("jax")  # noqa: F841 - arch configs need jax
    from benchmarks.paper_figs import fig13_workload_replay
    assert fig13_workload_replay() == FIG13_GOLDEN


# --------------------------------------------------------------------------
# fig14/fig15 small-grid goldens (PR 6 values), captured from the scalar
# per-epoch engine; the engine reproduces its floats exactly.
# --------------------------------------------------------------------------

# fig14 topology-scaling rows at 1 MB (the full figure sweeps to 1024
# GPUs; the golden keeps the 16/64-GPU columns, enough to lock the
# degenerate-tier agreement at 16 and the per-topology split at 64):
# (topology, n_gpus) -> (cold_ns, warm_ns, ideal_cold_ns, ideal_warm_ns,
# walks) with the figure's tier parameters (16-GPU leaves, 2x spine
# oversubscription, 16-GPU pods) and iterations=2 (cold then warm).
FIG14_GOLDEN = {
    ("single_clos", 16): (3890.0, 2852.0, 2802.0, 2802.0, 1),
    ("single_clos", 64): (3890.0, 2875.04, 2825.04, 2825.04, 1),
    ("two_tier", 16): (3890.0, 2852.0, 2802.0, 2802.0, 1),
    ("two_tier", 64): (4490.0, 4407.68, 4357.68, 4357.68, 1),
    ("multi_pod", 16): (3890.0, 2852.0, 2802.0, 2802.0, 1),
    ("multi_pod", 64): (5975.360000000001, 5975.359999999999,
                        5925.360000000001, 5925.359999999999, 1),
}


def _fig14_cfg(topo, n):
    from repro.core.config import FabricConfig, SimConfig
    return SimConfig(fabric=FabricConfig(n_gpus=n, topology=topo,
                                         leaf_size=16, oversubscription=2.0,
                                         pod_size=16),
                     iterations=2)


@pytest.mark.parametrize("topo,n", sorted(FIG14_GOLDEN))
def test_fig14_rows_bit_for_bit(topo, n):
    cold, warm, i_cold, i_warm, walks = FIG14_GOLDEN[(topo, n)]
    c = ratsim.compare(1 * MB, n, cfg=_fig14_cfg(topo, n))
    b, i = c.baseline.iterations, c.ideal.iterations
    assert (b[0].completion_ns, b[1].completion_ns) == (cold, warm)
    assert (i[0].completion_ns, i[1].completion_ns) == (i_cold, i_warm)
    assert c.baseline.counters.walks == walks
    # The figure's headline: warm TLBs never cost more than the cold pass.
    assert b[1].completion_ns <= b[0].completion_ns + 1e-9


# One fig15 bursty serving point (scaled down from _FIG15_BASE: 12
# requests, 60-step cap — the cold-burst tail regime survives intact:
# p99 TTFT degradation well above the mean).
FIG15_POINT = dict(arch="granite-moe-1b-a400m", rps=16.0, arrival="bursty",
                   n_requests=12, seed=7, retention_ns=50_000.0,
                   steps_cap=60, burst_size=4, burstiness=24.0,
                   prompt_mean=128, output_mean=8)
FIG15_GOLDEN = dict(
    p50=2432782.6667737663,
    p95=3432839.485653756,
    p99=3478109.9026029403,
    mean_deg=1.0583494148024755,
    p99_deg=1.1010624819242405,
    cold_comm_ns=7072922.8800069485,
    warm_comm_ns=66063141.120014586,
    cold_steps=4, steps=42, walks=288, served=12,
)


def test_fig15_bursty_point_bit_for_bit():
    from repro.serving.simulate import TrafficPoint, _traffic_point

    r = _traffic_point((TrafficPoint(**FIG15_POINT),))
    ttft = r.ttft_percentiles()
    assert ttft[50.0] == FIG15_GOLDEN["p50"]
    assert ttft[95.0] == FIG15_GOLDEN["p95"]
    assert ttft[99.0] == FIG15_GOLDEN["p99"]
    assert r.mean_ttft_degradation == FIG15_GOLDEN["mean_deg"]
    assert r.p99_ttft_degradation == FIG15_GOLDEN["p99_deg"]
    assert r.cold_comm_ns == FIG15_GOLDEN["cold_comm_ns"]
    assert r.warm_comm_ns == FIG15_GOLDEN["warm_comm_ns"]
    assert r.cold_steps == FIG15_GOLDEN["cold_steps"]
    assert len(r.steps) == FIG15_GOLDEN["steps"]
    assert sum(s.walks for s in r.steps) == FIG15_GOLDEN["walks"]
    assert len(r.first_token_served) == FIG15_GOLDEN["served"]
    # Bursty cold-miss tail: p99 degradation clears the mean.
    assert r.p99_ttft_degradation > r.mean_ttft_degradation
