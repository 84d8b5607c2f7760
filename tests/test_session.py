"""Tests for persistent-TLB replay sessions (repro.core.session / ref_des).

Covers the session API contracts (warm-vs-cold across invocations, idle-gap
aging, engine-session == simulate(iterations=k) equivalence, per-call
counter deltas), the session-mode oracle (RefSession mirrors SimSession),
and the oracle-equivalence of the optimization paths (pre-translation and
prefetch probes are now replayed identically by the reference DES).
"""
import pytest

from repro.core import (RefSession, SimSession, paper_config, simulate,
                        simulate_ref, ratsim, KB, MB)
from repro.core.config import (FabricConfig, PreTranslationConfig,
                               PrefetchConfig)
from repro.core.tlb import Counters


# ------------------------------------------------------------ warm vs cold
class TestSessionWarmth:
    def test_second_identical_collective_warmer(self):
        s = SimSession(paper_config(16))
        cold = s.run(1 * MB)
        warm = s.run(1 * MB)
        assert warm.completion_ns < cold.completion_ns
        assert cold.counters.walks > 0
        assert warm.counters.walks == 0

    @pytest.mark.parametrize("coll", ["ring_allreduce", "broadcast",
                                      "hier_all_to_all"])
    def test_warmth_holds_across_patterns(self, coll):
        s = SimSession(paper_config(16).replace(collective=coll))
        cold = s.run(1 * MB)
        warm = s.run(1 * MB)
        assert warm.completion_ns <= cold.completion_ns + 1e-9

    def test_distinct_buffers_walk_again(self):
        # base_offset moves the collective to fresh pages: the Link-TLB
        # warmth does not carry (cold walks fire again), though the
        # page-walk caches legitimately stay warm (shorter walks).
        s = SimSession(paper_config(16))
        a = s.run(1 * MB)
        same = s.run(1 * MB)
        moved = s.run(1 * MB, base_offset=64 * MB)
        assert same.counters.walks == 0
        assert moved.counters.walks == a.counters.walks > 0

    def test_subgroup_collective_inside_pod(self):
        # An 8-GPU TP collective inside a 16-GPU pod is legal and warms the
        # same per-target state a later pod-wide collective reuses.
        s = SimSession(paper_config(16))
        sub = s.run(1 * MB, collective="all_gather", n_gpus=8)
        assert sub.n_gpus == 8
        assert sub.counters.requests > 0
        with pytest.raises(ValueError, match="exceeds pod size"):
            s.run(1 * MB, n_gpus=32)


# ----------------------------------------------------------- idle-gap aging
class TestIdleGaps:
    def test_gap_without_retention_keeps_warmth(self):
        s = SimSession(paper_config(16))
        s.run(1 * MB)
        warm = s.run(1 * MB, gap_ns=1e9)   # a full second of idle
        assert warm.counters.walks == 0

    def test_gap_beyond_retention_flushes(self):
        cfg = paper_config(16).replace(tlb_retention_ns=1e6)
        s = SimSession(cfg)
        cold = s.run(1 * MB)
        aged = s.run(1 * MB, gap_ns=2e6)   # gap >= retention: flushed
        assert aged.counters.walks == cold.counters.walks
        assert aged.completion_ns == pytest.approx(cold.completion_ns)
        warm = s.run(1 * MB, gap_ns=0.5e6)  # short gap: stays warm
        assert warm.counters.walks == 0


# ------------------------------------------- session == simulate(iterations)
class TestSessionSimulateEquivalence:
    @pytest.mark.parametrize("k", [1, 2, 3])
    def test_k_runs_equal_iterations_k(self, k):
        sess = SimSession(paper_config(16))
        for _ in range(k):
            sess.run(1 * MB)
        one = simulate(1 * MB, paper_config(16).replace(iterations=k))
        got = sess.result()
        assert ([i.completion_ns for i in got.iterations]
                == [i.completion_ns for i in one.iterations])
        assert got.counters.requests == one.counters.requests
        assert got.counters.by_class == one.counters.by_class
        assert got.mean_stall_ns == one.mean_stall_ns

    def test_trace_first_run_only(self):
        cfg = paper_config(16).replace(collect_trace=True)
        sess = SimSession(cfg)
        sess.run(1 * MB)
        sess.run(1 * MB)
        ref = simulate(1 * MB, cfg.replace(iterations=2))
        got = sess.result()
        assert got.trace is not None
        assert (got.trace == ref.trace).all()

    def test_per_call_counter_deltas_sum_to_total(self):
        sess = SimSession(paper_config(16))
        r1 = sess.run(1 * MB)
        r2 = sess.run(4 * MB)
        total = sess.result().counters
        assert r1.counters.requests + r2.counters.requests == total.requests
        assert r1.counters.walks + r2.counters.walks == total.walks
        for k in total.by_class:
            assert (r1.counters.by_class[k] + r2.counters.by_class[k]
                    == total.by_class[k])


# --------------------------------------------------- trace target selection
class TestTraceDstSelection:
    """Regression for the trace-target pick: ``SimSession.run`` used to
    trace ``dsts[0]`` unconditionally; it must be the first destination
    that actually *produces flows* (``None`` when no destination does)."""

    def test_asymmetric_group_traces_first_flowing_dst(self):
        # broadcast is an asymmetric pattern: the root (GPU 0) never
        # receives, so the simulated target set excludes it and the trace
        # must land on the first *receiving* target.
        cfg = paper_config(8).replace(collect_trace=True)
        s = SimSession(cfg)
        s.run(1 * MB, collective="broadcast")
        assert s._trace_dst == 1
        r = s.result()
        assert r.trace is not None and (r.trace > 0).any()

    def test_all_zero_byte_collective_traces_none(self):
        # A collective smaller than the group size chunks to zero bytes on
        # every destination — no destination produces flows.  The trace
        # target must fall back to None (the old dsts[0] pick pointed the
        # trace bookkeeping at a flowless engine) and the result trace
        # stays a well-formed all-zeros vector.
        cfg = paper_config(8).replace(collect_trace=True)
        s = SimSession(cfg)
        s.run(4)                    # 4 B / 8 GPUs -> zero-byte chunks
        assert s._trace_dst is None
        r = s.result()
        assert r.trace is not None and (r.trace == 0).all()


# ------------------------------------------------------ session-mode oracle
class TestRefSessionOracle:
    def test_session_sequence_matches_oracle(self):
        cfg = paper_config(8)
        eng, ref = SimSession(cfg), RefSession(cfg)
        seq = [(256 * KB, {}), (256 * KB, {}),
               (512 * KB, {"collective": "ring_allreduce"}),
               (256 * KB, {"gap_ns": 5e3})]
        for nbytes, kw in seq:
            eng.run(nbytes, **kw)
            ref.run(nbytes, **kw)
        a, b = eng.result(), ref.result()
        for ia, ib in zip(a.iterations, b.iterations):
            assert ia.completion_ns == pytest.approx(ib.completion_ns,
                                                     rel=0.05)
        assert a.counters.walks == b.counters.walks
        assert a.counters.requests == b.counters.requests

    def test_oracle_session_warms_too(self):
        s = RefSession(paper_config(8))
        cold = s.run(512 * KB)
        warm = s.run(512 * KB)
        assert warm.counters.walks == 0
        assert warm.completion_ns < cold.completion_ns

    def test_oracle_retention_flush(self):
        cfg = paper_config(8).replace(tlb_retention_ns=1e6)
        s = RefSession(cfg)
        cold = s.run(512 * KB)
        aged = s.run(512 * KB, gap_ns=2e6)
        assert aged.counters.walks == cold.counters.walks

    def test_oracle_rejects_oversized_group_like_engine(self):
        # Mirrored validation: identical call sequences must behave
        # identically on both sides, including the error path.
        for sess in (SimSession(paper_config(8)), RefSession(paper_config(8))):
            with pytest.raises(ValueError, match="exceeds pod size"):
                sess.run(256 * KB, n_gpus=32)


# ------------------------------------- oracle equivalence: optimization paths
class TestOptimizationOracleEquivalence:
    """Engine vs reference DES with the paper's §6 optimizations enabled:
    the DES now replays the identical probe schedule, so completion, walk
    and probe counts must agree (TestOptimizations in test_core_sim.py only
    checks directional behavior)."""

    @pytest.mark.parametrize("n,size", [(8, 1 * MB), (8, 4 * MB),
                                        (16, 1 * MB)])
    def test_pretranslation_equivalence(self, n, size):
        cfg = paper_config(n).replace(
            pretranslation=PreTranslationConfig(
                enabled=True, lead_time_ns=3000.0, pages_per_flow=0))
        a, b = simulate(size, cfg), simulate_ref(size, cfg)
        assert a.completion_ns == pytest.approx(b.completion_ns, rel=0.05)
        assert a.counters.walks == b.counters.walks
        assert a.counters.probes == b.counters.probes
        assert a.counters.probes > 0

    @pytest.mark.parametrize("n,size", [(8, 32 * MB)])
    def test_prefetch_equivalence(self, n, size):
        # Multi-page flows so next-page probes actually fire; paper-default
        # ingress buffering (the regime where the engine/DES contract binds,
        # DESIGN.md §7).
        cfg = paper_config(n).replace(
            prefetch=PrefetchConfig(enabled=True, depth=2))
        a, b = simulate(size, cfg), simulate_ref(size, cfg)
        assert a.completion_ns == pytest.approx(b.completion_ns, rel=0.05)
        assert a.counters.walks == b.counters.walks
        assert a.counters.probes == b.counters.probes
        assert a.counters.probes > 0


# -------------------------------------------------- probe striping (fixed)
class TestProbeStriping:
    def test_prefetched_page_first_request_is_l1_hit(self):
        """Regression for the probe-striping fix: probes must land on the
        station where the page's first data request lands, so that request
        classifies ``l1_hit`` (it previously warmed the wrong L1 and the
        first touch fell through to the L2)."""
        cfg = paper_config(8).replace(
            prefetch=PrefetchConfig(enabled=True, depth=2),
            collect_trace=True)
        r = simulate(32 * MB, cfg)
        l1_lat = cfg.translation.l1.hit_latency_ns
        # 32 MB / 8 GPUs = 4 MB per flow = two 2 MB pages; page 1's first
        # request is request 8192 (= 2 MB / 256 B) of each flow.
        b = r.trace_flow_bounds
        page1_first = 4 * MB // 2 // cfg.fabric.request_bytes
        for fi in range(7):
            assert r.trace[b[fi] + page1_first] == l1_lat
        assert r.counters.probes == 7       # one next-page probe per flow

    def test_pretranslation_probe_alignment(self):
        # Multi-page flows: the old striping sent the page-1 probe to
        # station (stripe + 1) while page 1's first request lands back on
        # station stripe (8192 requests per 2 MB page = a whole number of
        # 16-station rounds).  Aligned probes make that request an L1 hit.
        cfg = paper_config(8).replace(
            pretranslation=PreTranslationConfig(
                enabled=True, lead_time_ns=3000.0, pages_per_flow=0),
            collect_trace=True)
        r = simulate(32 * MB, cfg)
        l1_lat = cfg.translation.l1.hit_latency_ns
        b = r.trace_flow_bounds
        page1_first = 4 * MB // 2 // cfg.fabric.request_bytes
        for fi in range(7):
            assert r.trace[b[fi] + page1_first] == l1_lat
        assert r.counters.probes == 14       # two pages per flow, warmed all


# ------------------------------------------------------------ ratsim helper
def test_ratsim_session_helper():
    s = ratsim.session(16, collective="ring_allreduce")
    assert isinstance(s, SimSession)
    assert s.cfg.collective == "ring_allreduce"
    rec = s.run(1 * MB)
    assert rec.collective == "ring_allreduce"


# ------------------------------------------------------------- counter math
class TestCounterMath:
    def test_merge_accumulates_every_field(self):
        a, b = Counters(), Counters()
        a.add_request("l1_hit", 100.0, n=2)
        a.note_max(60.0)
        a.walks, a.walk_mem_reads, a.pwc_hits, a.probes = 3, 5, 7, 2
        b.add_request("walk", 1700.0)
        b.note_max(1700.0)
        b.walks, b.pwc_misses, b.mshr_stall_ns = 1, 4, 12.5
        a.merge(b)
        assert a.requests == 3
        assert a.by_class["l1_hit"] == 2 and a.by_class["walk"] == 1
        assert a.rat_ns_sum == 1800.0
        assert a.rat_ns_max == 1700.0
        assert (a.walks, a.walk_mem_reads, a.pwc_hits, a.pwc_misses,
                a.probes, a.mshr_stall_ns) == (4, 5, 7, 4, 2, 12.5)

    def test_copy_and_delta(self):
        a = Counters()
        a.add_request("l1_hit", 50.0)
        snap = a.copy()
        a.add_request("walk", 1700.0)
        a.walks += 1
        d = a.delta(snap)
        assert d.requests == 1
        assert d.by_class == {"l1_hit": 0, "l1_mshr_hum": 0, "l2_hit": 0,
                              "l2_hum": 0, "walk": 1}
        assert d.rat_ns_sum == 1700.0
        assert d.walks == 1
        snap.add_request("l2_hit", 1.0)      # copy is independent
        assert a.by_class["l2_hit"] == 0

    def test_mean_stall_denominator_is_merged_requests(self):
        # PR 1 fixed mean_stall_ns to divide by the merged request count;
        # golden value at the scarce-ingress stall config.
        cfg = paper_config(16).replace(
            fabric=FabricConfig(n_gpus=16, ingress_entries=64))
        r = simulate(64 * MB, cfg)
        assert r.counters.requests == 245760
        assert r.mean_stall_ns == pytest.approx(0.9237597656249985,
                                                rel=1e-9)
