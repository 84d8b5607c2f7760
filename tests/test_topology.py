"""Tests for the fabric topology layer (repro.core.topology).

Four contracts:
  1. Geometry — tier classification, per-path latencies, tier capacities and
     validation of the registered topologies.
  2. Bandwidth shaping — flows crossing an oversubscribed tier split the
     tier capacity, not the flat station pool; the flat default keeps the
     exact pre-topology spacing arithmetic.
  3. Oracle equivalence — the page-epoch engine matches the request-level
     reference DES request-for-request on ``two_tier`` and ``multi_pod``
     at small sizes, for every pattern and for the pre-translation /
     prefetch probe schedules (the same contract the per-pattern suite
     pins on the flat default).
  4. The API — ``topology=`` axes on ratsim.run/compare/session/sweep, the
     session warm-vs-cold story per topology, and the workload-derivation
     tier mapping (TP intra-leaf, EP cross-tier).
"""
import math

import pytest

from repro.core import (ratsim, paper_config, simulate, simulate_ref,
                        get_pattern, get_topology, analytic_volume,
                        SimSession, RefSession, TOPOLOGIES, KB, MB)
from repro.core.config import (FabricConfig, SimConfig, PreTranslationConfig,
                               PrefetchConfig)
from repro.core.ref_des import flows_for_dst
from repro.core.patterns import FlowSpec


def two_tier(n=8, leaf=4, ov=2.0, **kw) -> SimConfig:
    return SimConfig(fabric=FabricConfig(
        n_gpus=n, topology="two_tier", leaf_size=leaf, oversubscription=ov),
        **kw)


def multi_pod(n=8, pod=4, ov=4.0, **kw) -> SimConfig:
    return SimConfig(fabric=FabricConfig(
        n_gpus=n, topology="multi_pod", pod_size=pod,
        interpod_oversubscription=ov), **kw)


# ---------------------------------------------------------------- geometry
class TestGeometry:
    def test_single_clos_is_flat(self):
        fab = FabricConfig(n_gpus=16)
        t = get_topology(fab)
        assert t.flat and t.name == "single_clos"
        assert t.tier(0, 15) == 0
        assert t.path_latency_ns(0, 15) == fab.oneway_ns
        assert t.return_latency_ns(15, 0) == fab.return_ns
        assert t.tier_capacity(0) is None
        assert t.tier0_group() == 16
        assert t.local_group() == fab.gpus_per_node

    def test_two_tier_tiers_and_latency(self):
        fab = two_tier(n=8, leaf=4).fabric
        t = get_topology(fab)
        assert not t.flat
        assert t.tier(0, 3) == 0 and t.tier(0, 4) == 1
        assert t.path_latency_ns(0, 3) == fab.oneway_ns
        # spine crossing + the second leaf switch
        assert t.path_latency_ns(0, 4) == (fab.oneway_ns
                                           + fab.spine_latency_ns
                                           + fab.switch_latency_ns)
        assert t.return_latency_ns(4, 0) == t.path_latency_ns(0, 4)
        assert t.tier_capacity(0) is None
        assert t.tier_capacity(1) == fab.gpu_bw / fab.oversubscription
        assert t.tier0_group() == t.local_group() == 4

    def test_multi_pod_tiers_and_latency(self):
        fab = multi_pod(n=8, pod=4).fabric
        t = get_topology(fab)
        assert t.tier(1, 2) == 0 and t.tier(1, 6) == 1
        assert t.path_latency_ns(1, 6) == (fab.oneway_ns
                                           + fab.interpod_latency_ns)
        assert t.tier_capacity(1) == (fab.gpu_bw
                                      / fab.interpod_oversubscription)
        assert t.tier0_group() == t.pod_group() == 4

    def test_leaf_defaults_to_gpus_per_node(self):
        fab = FabricConfig(n_gpus=8, topology="two_tier")  # leaf_size=0
        assert get_topology(fab).local_group() == fab.gpus_per_node

    def test_small_group_fits_one_leaf(self):
        # Session subgroups smaller than a leaf degenerate to a single leaf.
        fab = FabricConfig(n_gpus=4, topology="two_tier", leaf_size=16)
        t = get_topology(fab)
        assert t.tier(0, 3) == 0 and t.tier0_group() == 4

    def test_indivisible_leaf_raises(self):
        with pytest.raises(ValueError, match="divisible"):
            get_topology(FabricConfig(n_gpus=12, topology="two_tier",
                                      leaf_size=8))
        with pytest.raises(ValueError, match="divisible"):
            get_topology(FabricConfig(n_gpus=12, topology="multi_pod",
                                      pod_size=8))

    def test_unknown_topology_raises(self):
        with pytest.raises(ValueError, match="unknown topology"):
            get_topology(FabricConfig(topology="hypercube"))

    def test_registry(self):
        assert set(TOPOLOGIES) == {"single_clos", "two_tier", "multi_pod"}


# ------------------------------------------------------- bandwidth shaping
class TestBandwidthShaping:
    def _a2a_specs(self, n, nbytes):
        chunk = nbytes // n
        return [FlowSpec(src=s, dst=d, nbytes=chunk, offset=s * chunk)
                for d in range(n) for s in range(n) if s != d]

    def test_flat_spacing_is_pre_topology_arithmetic(self):
        cfg = paper_config(8)
        fab = cfg.fabric
        specs = self._a2a_specs(8, 1 * MB)
        for f in flows_for_dst(specs, cfg, 0, 0.0):
            assert f.delta_ns == fab.request_bytes * 7 / fab.gpu_bw
            assert f.oneway_ns == fab.oneway_ns
            assert f.return_ns == fab.return_ns

    def test_oversubscribed_tier_splits_uplink(self):
        cfg = two_tier(n=8, leaf=4, ov=4.0)
        fab = cfg.fabric
        specs = self._a2a_specs(8, 1 * MB)
        flows = flows_for_dst(specs, cfg, 0, 0.0)
        base = fab.request_bytes * 7 / fab.gpu_bw
        uplink = fab.gpu_bw / 4.0
        # src 1..3 are intra-leaf to dst 0; src 4..7 cross the spine and
        # each has 4 cross-tier flows (to GPUs 0..3) sharing its uplink.
        for f in flows:
            if f.src < 4:
                assert f.delta_ns == base
            else:
                assert f.delta_ns == max(base,
                                         fab.request_bytes * 4 / uplink)
                assert f.delta_ns > base

    def test_unity_oversubscription_only_changes_latency(self):
        cfg = two_tier(n=8, leaf=4, ov=1.0)
        fab = cfg.fabric
        specs = self._a2a_specs(8, 1 * MB)
        for f in flows_for_dst(specs, cfg, 0, 0.0):
            assert f.delta_ns == fab.request_bytes * 7 / fab.gpu_bw
            if f.src < 4:
                assert f.oneway_ns == fab.oneway_ns
            else:
                assert f.oneway_ns > fab.oneway_ns

    def test_degenerate_two_tier_bit_for_bit(self):
        # leaf == pod: every pair is intra-leaf, so the numbers are exactly
        # the single-Clos ones.
        a = simulate(1 * MB, two_tier(n=8, leaf=8, ov=2.0))
        b = simulate(1 * MB, paper_config(8))
        assert a.completion_ns == b.completion_ns
        assert a.counters.requests == b.counters.requests
        assert a.counters.walks == b.counters.walks


# ------------------------------------------------------- oracle equivalence
TOPO_CFGS = [("two_tier", two_tier), ("multi_pod", multi_pod)]
PATTERN_NAMES = ["all_to_all", "ring_allreduce", "rd_allreduce",
                 "all_gather", "broadcast", "hier_all_to_all",
                 "multipod_all_to_all"]


class TestOracleEquivalence:
    @pytest.mark.parametrize("topo,mk", TOPO_CFGS)
    @pytest.mark.parametrize("name", PATTERN_NAMES)
    def test_engine_matches_reference_des(self, topo, mk, name):
        cfg = mk(n=8).replace(collective=name)
        a = simulate(1 * MB, cfg)
        b = simulate_ref(1 * MB, cfg)
        assert a.completion_ns == pytest.approx(b.completion_ns, rel=0.05)
        assert a.counters.walks == b.counters.walks
        assert a.counters.requests == b.counters.requests

    @pytest.mark.parametrize("topo,mk", TOPO_CFGS)
    def test_multipage_matches_reference_des(self, topo, mk):
        cfg = mk(n=8).replace(collective="hier_all_to_all")
        a = simulate(4 * MB, cfg)
        b = simulate_ref(4 * MB, cfg)
        assert a.completion_ns == pytest.approx(b.completion_ns, rel=0.05)
        assert a.counters.walks == b.counters.walks

    @pytest.mark.parametrize("topo,mk", TOPO_CFGS)
    def test_ideal_matches_reference_des(self, topo, mk):
        cfg = mk(n=8).ideal()
        a = simulate(1 * MB, cfg)
        b = simulate_ref(1 * MB, cfg)
        assert a.completion_ns == pytest.approx(b.completion_ns, rel=0.005)

    @pytest.mark.parametrize("topo,mk", TOPO_CFGS)
    def test_pretranslation_probe_schedule_equivalent(self, topo, mk):
        cfg = mk(n=8).replace(pretranslation=PreTranslationConfig(
            enabled=True, lead_time_ns=3000.0, pages_per_flow=0))
        a = simulate(1 * MB, cfg)
        b = simulate_ref(1 * MB, cfg)
        assert a.counters.probes == b.counters.probes > 0
        assert a.counters.walks == b.counters.walks
        assert a.completion_ns == pytest.approx(b.completion_ns, rel=0.05)

    @pytest.mark.parametrize("topo,mk", TOPO_CFGS)
    def test_prefetch_probe_schedule_equivalent(self, topo, mk):
        # 32 MB / 8 GPUs = 4 MB per flow = 2 pages: next-page prefetches
        # fire mid-stream on every flow.  Unity oversubscription: latency
        # tiers only, the regime where the engine/DES completion contract
        # binds tightly (paper-default ingress buffering, DESIGN.md §7).
        cfg = mk(n=8, ov=1.0).replace(
            prefetch=PrefetchConfig(enabled=True, depth=2))
        a = simulate(32 * MB, cfg)
        b = simulate_ref(32 * MB, cfg)
        assert a.counters.probes == b.counters.probes > 0
        assert a.counters.walks == b.counters.walks
        # Long heterogeneous-latency streams: the epoch tail diverges by at
        # most one end-of-stream walk window (absolute), tight relative
        # otherwise (DESIGN.md §10.3).
        assert a.completion_ns == pytest.approx(b.completion_ns, rel=0.05,
                                                abs=2e3)

    def test_prefetch_under_shaping_schedule_stays_exact(self):
        # With an oversubscribed uplink the same flows run at two rates;
        # the epoch engine's closed-form tail expansion then diverges from
        # the slot-accurate DES by a bounded end-of-stream window
        # (DESIGN.md §10.3) — but the probe schedule, walk count and
        # request count remain request-for-request identical.
        cfg = two_tier(n=8, leaf=4, ov=2.0).replace(
            prefetch=PrefetchConfig(enabled=True, depth=2))
        a = simulate(32 * MB, cfg)
        b = simulate_ref(32 * MB, cfg)
        assert a.counters.probes == b.counters.probes > 0
        assert a.counters.walks == b.counters.walks
        assert a.counters.requests == b.counters.requests
        assert a.completion_ns == pytest.approx(b.completion_ns, rel=0.08)

    @pytest.mark.parametrize("topo,mk", TOPO_CFGS)
    def test_session_sequence_equivalent(self, topo, mk):
        # Heterogeneous session replay: the RefSession mirror stays
        # request-for-request equivalent on hierarchical topologies.
        cfg = mk(n=8)
        s, r = SimSession(cfg), RefSession(cfg)
        for sess in (s, r):
            sess.run(512 * KB)
            sess.run(512 * KB)                      # warm rerun
            sess.run(256 * KB, collective="all_gather", n_gpus=4)
            sess.run(512 * KB, base_offset=32 * MB)  # fresh buffer
        for a, b in zip(s.records, r.records):
            assert a.completion_ns == pytest.approx(b.completion_ns,
                                                    rel=0.05)
            assert a.counters.walks == b.counters.walks
            assert a.counters.requests == b.counters.requests


# ------------------------------------------------------------------ physics
class TestTopologyPhysics:
    def test_two_tier_slower_than_flat(self):
        flat = simulate(1 * MB, paper_config(8))
        tiered = simulate(1 * MB, two_tier(n=8, leaf=4, ov=2.0))
        assert tiered.completion_ns > flat.completion_ns

    def test_oversubscription_monotone(self):
        prev = None
        for ov in (1.0, 2.0, 4.0):
            t = simulate(4 * MB, two_tier(n=8, leaf=4, ov=ov))
            if prev is not None:
                assert t.completion_ns >= prev
            prev = t.completion_ns

    def test_hier_stages_on_leaf_group(self):
        fab = two_tier(n=8, leaf=4).fabric
        steps = get_pattern("hier_all_to_all").steps(1 * MB, fab)
        assert len(steps) == 2
        # Phase 1 flows never leave the leaf; phase 2 always crosses it.
        t = get_topology(fab)
        assert all(t.tier(s.src, s.dst) == 0 for s in steps[0])
        assert all(t.tier(s.src, s.dst) == 1 for s in steps[1])

    def test_multipod_pattern_stages_on_pod_group(self):
        fab = multi_pod(n=8, pod=4).fabric
        steps = get_pattern("multipod_all_to_all").steps(1 * MB, fab)
        t = get_topology(fab)
        assert all(t.tier(s.src, s.dst) == 0 for s in steps[0])
        assert all(t.tier(s.src, s.dst) == 1 for s in steps[1])
        emitted = sum(s.nbytes for step in steps for s in step)
        assert emitted == analytic_volume("multipod_all_to_all", 1 * MB, fab)

    def test_hier_beats_direct_a2a_crossings(self):
        # The point of staging: per GPU, hier crosses the spine (m-1) times
        # with aggregated chunks vs (n - g) direct crossings.
        fab = two_tier(n=16, leaf=4, ov=4.0).fabric
        t = get_topology(fab)
        direct = get_pattern("all_to_all").steps(1 * MB, fab)
        hier = get_pattern("hier_all_to_all").steps(1 * MB, fab)
        cross = lambda steps: sum(1 for step in steps for s in step
                                  if t.tier(s.src, s.dst) == 1 and s.src == 0)
        assert cross(hier) == 3 < cross(direct) == 12


# ---------------------------------------------------------------- the API
class TestTopologyAPI:
    def test_run_compare_session_topology_kwarg(self):
        r = ratsim.run(1 * MB, 8, topology="two_tier")
        assert r.config.fabric.topology == "two_tier"
        c = ratsim.compare(1 * MB, 8, topology="two_tier")
        assert c.degradation >= 1.0 - 1e-12
        s = ratsim.session(8, topology="two_tier")
        cold = s.run(1 * MB)
        warm = s.run(1 * MB)
        assert warm.completion_ns < cold.completion_ns
        assert warm.counters.walks == 0

    def test_default_topology_kwarg_is_noop(self):
        a = ratsim.run(1 * MB, 16)
        b = ratsim.run(1 * MB, 16, topology="single_clos")
        assert a.completion_ns == b.completion_ns

    def test_sweep_topology_axis_keys(self):
        out = ratsim.sweep([1 * MB], [8],
                           topologies=["single_clos", "two_tier"], workers=0)
        assert set(out) == {("single_clos", 8, 1 * MB),
                            ("two_tier", 8, 1 * MB)}
        both = ratsim.sweep([1 * MB], [8], topologies=["two_tier"],
                            collectives=["all_to_all", "ring_allreduce"],
                            workers=0)
        assert set(both) == {("two_tier", "all_to_all", 8, 1 * MB),
                             ("two_tier", "ring_allreduce", 8, 1 * MB)}

    def test_sweep_topology_matches_compare(self):
        out = ratsim.sweep([1 * MB], [8], topologies=["two_tier"], workers=0)
        c = ratsim.compare(1 * MB, 8, topology="two_tier")
        g = out[("two_tier", 8, 1 * MB)]
        assert g.baseline.completion_ns == c.baseline.completion_ns
        assert g.ideal.completion_ns == c.ideal.completion_ns

    def test_sweep_base_cfg_keeps_tier_params(self):
        base = two_tier(n=8, leaf=4, ov=4.0)
        out = ratsim.sweep([1 * MB], [8, 16], base_cfg=base, workers=0)
        direct = ratsim.compare(
            1 * MB, 16,
            cfg=two_tier(n=16, leaf=4, ov=4.0))
        assert (out[(16, 1 * MB)].baseline.completion_ns
                == direct.baseline.completion_ns)


# ------------------------------------------------------ workload placement
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


class TestWorkloadTierMapping:
    def test_two_tier_tp_intra_leaf_ep_cross_tier(self):
        from repro.workloads import PodSpec, derive_workload, pod_fabric

        pod = PodSpec(topology="two_tier", leaf_size=4, oversubscription=2.0)
        tr = derive_workload(TinyMoE(), "decode_32k", pod=pod, n_gpus=8,
                             n_steps=1)
        assert tr.pod.tp == 4          # one leaf
        assert tr.pod.ep == 8          # spans both leaves (cross-tier a2a)
        assert tr.pod.dp == 2
        groups = {(c.collective, c.group) for c in tr.calls}
        assert ("all_gather", 4) in groups and ("all_to_all", 8) in groups
        assert pod_fabric(tr.pod).topology == "two_tier"

    def test_single_clos_defaults_unchanged(self):
        from repro.workloads import PodSpec, derive_workload

        tr = derive_workload(TinyMoE(), "decode_32k", pod=PodSpec(),
                             n_gpus=8, n_steps=1)
        assert tr.pod.tp == 8 and tr.pod.dp == 1   # whole pod, as before

    def test_replay_simulates_pod_topology(self):
        from repro.workloads import PodSpec, derive_workload, replay

        pod = PodSpec(topology="two_tier", leaf_size=4, oversubscription=2.0)
        tr = derive_workload(TinyMoE(), "decode_32k", pod=pod, n_gpus=8,
                             n_steps=2)
        rep = replay(tr)
        assert rep.cfg.fabric.topology == "two_tier"
        assert rep.cold_degradation > rep.steady_degradation
        assert rep.steps[1].walks == 0             # warmth carries per-tier


# ------------------------------------------------------------ strided groups
class TestStridedGroups:
    def test_strided_ring_crosses_tiers(self):
        # DP ring over ranks {0, 4} in a leaf-4 pod: every hop is
        # inter-leaf, so cold completion exceeds the contiguous placement's.
        cfg = two_tier(n=8, leaf=4, ov=2.0)
        contiguous = SimSession(cfg).run(
            1 * MB, collective="ring_allreduce", n_gpus=2)
        strided = SimSession(cfg).run(
            1 * MB, collective="ring_allreduce", n_gpus=2, rank_stride=4)
        assert strided.completion_ns > contiguous.completion_ns

    def test_strided_oracle_equivalence(self):
        cfg = two_tier(n=8, leaf=4, ov=2.0)
        s, r = SimSession(cfg), RefSession(cfg)
        for sess in (s, r):
            sess.run(1 * MB, collective="ring_allreduce", n_gpus=2,
                     rank_stride=4)
            sess.run(512 * KB, collective="all_to_all", n_gpus=2,
                     rank_stride=4, base_offset=16 * MB)
        for a, b in zip(s.records, r.records):
            assert a.completion_ns == pytest.approx(b.completion_ns,
                                                    rel=0.05)
            assert a.counters.walks == b.counters.walks
            assert a.counters.requests == b.counters.requests

    def test_stride_noop_on_flat_topology(self):
        # Flat Clos: rank labeling is isomorphic up to station striping of
        # a symmetric fabric — same walk/request counts, same completion.
        s1 = SimSession(paper_config(8)).run(
            1 * MB, collective="ring_allreduce", n_gpus=2)
        s2 = SimSession(paper_config(8)).run(
            1 * MB, collective="ring_allreduce", n_gpus=2, rank_stride=4)
        assert s2.completion_ns == s1.completion_ns
        assert s2.counters.walks == s1.counters.walks

    def test_misaligned_stride_simulates_every_target(self):
        # Stride 2 on a leaf-4 block mixes intra/inter pairs per target:
        # the symmetric single-target shortcut must switch off.
        cfg = two_tier(n=8, leaf=4, ov=2.0)
        rec = SimSession(cfg).run(1 * MB, collective="ring_allreduce",
                                  n_gpus=4, rank_stride=2)
        rb = cfg.fabric.request_bytes
        n_req_flow = math.ceil((1 * MB // 4) / rb)
        n_steps = 2 * (4 - 1)
        assert rec.counters.requests == n_steps * 4 * n_req_flow  # all dsts

    def test_block_straddling_subgroup_simulates_every_target(self):
        # A contiguous group of 5 on leaf-4 blocks straddles a partial
        # leaf: target 0 (leaf 0, 3 intra-peers) and target 4 (leaf 1,
        # alone) see different tier mixes, so the shortcut must switch off
        # and completion must equal the explicit every-target run.
        cfg = two_tier(n=8, leaf=4, ov=2.0)
        rec = SimSession(cfg).run(1 * MB, n_gpus=5)
        full = SimSession(cfg.replace(symmetric=False)).run(1 * MB, n_gpus=5)
        assert rec.completion_ns == full.completion_ns
        assert rec.counters.requests == full.counters.requests

    def test_whole_block_multiples_keep_single_target_shortcut(self):
        # g a multiple of the block (or inside one block): every target is
        # loaded identically, the shortcut stays exact.
        cfg = two_tier(n=8, leaf=4, ov=2.0)
        for g in (2, 4, 8):
            short = SimSession(cfg).run(1 * MB, n_gpus=g)
            full = SimSession(cfg.replace(symmetric=False)).run(
                1 * MB, n_gpus=g)
            assert short.completion_ns == full.completion_ns, g

    def test_stride_overflow_raises(self):
        with pytest.raises(ValueError, match="strided group"):
            SimSession(paper_config(8)).run(
                1 * MB, collective="ring_allreduce", n_gpus=4, rank_stride=4)

    def test_train_grad_ring_strided_on_two_tier(self):
        from repro.workloads import PodSpec, derive_workload, replay

        pod = PodSpec(topology="two_tier", leaf_size=4, oversubscription=2.0)
        tr = derive_workload(TinyMoE(), "train_4k", pod=pod, n_gpus=8,
                             n_steps=1)
        assert tr.pod.tp == 4 and tr.pod.dp == 2
        grads = [c for c in tr.calls if c.collective == "ring_allreduce"]
        assert grads and all(c.stride == tr.pod.tp for c in grads)
        # Flat default keeps contiguous ranks (bit-for-bit pre-topology).
        flat = derive_workload(TinyMoE(), "train_4k", pod=PodSpec(),
                               n_gpus=8, n_steps=1)
        assert all(c.stride == 1 for c in flat.calls)
        rep = replay(tr)                     # strided replay runs end-to-end
        assert rep.steps[0].walks > 0

    def test_train_tp_cap_not_power_of_two(self):
        # leaf 6 in a 24-GPU pod: tp must stop at 4, not overshoot to 8
        # across two leaves.
        from repro.workloads import PodSpec, resolve_pod

        pod = PodSpec(n_gpus=24, topology="two_tier", leaf_size=6)
        r = resolve_pod(pod, TinyMoE(), "train")
        assert r.tp == 4 and r.tp <= 6 and r.tp * r.dp == 24


# ---------------------------------------------------------------- figures
@pytest.mark.slow
def test_fig14_topology_scaling_runs_to_1024():
    from benchmarks.paper_figs import fig14_topology_scaling

    rows = fig14_topology_scaling()
    names = {r[0] for r in rows}
    assert "fig14/two_tier/gpus1024/size1MB" in names
    checks = {r[0]: r[2] for r in rows if "check" in r[0]}
    assert checks["fig14/check_16gpu_topologies_degenerate"] == "agree=True"
    assert checks["fig14/check_warm_never_worse_than_cold"] == "ok=True"
