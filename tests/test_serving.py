"""Tests for the request-level serving layer (repro.serving).

Covers: seeded arrival generation (bit-for-bit determinism, also across the
serial vs pooled sweep executors — the arrival stream is data derived from
the seed, not a side effect of execution), continuous-batching invariants
(slot bounds, chunked prefill interleaving, token/latency-sample
conservation), live-batch collective sizing, the TLB-retention contract
(an idle gap longer than ``tlb_retention_ns`` between bursts re-pays the
cold walks), per-request accounting (causality, cold-vs-warm split), and
the offline (jax-free) CLI.
"""
import os
import pathlib
import subprocess
import sys

import pytest

from repro.core.config import SimConfig
from repro.serving import (DisaggPoint, Request, TrafficPoint,
                           bursty_requests, poisson_requests,
                           simulate_disagg, simulate_traffic, sweep_disagg,
                           sweep_traffic, trace_requests)
from repro.workloads import PodSpec, pod_fabric, resolve_pod
from repro.workloads.derive import StepEmitter


class TinyServeMoE:
    """Duck-typed stand-in for ModelConfig (only the fields derive reads)."""
    name = "tiny-serve-moe"
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


TINY = TinyServeMoE()


def tiny_requests(arrivals, prompt=24, output=3):
    return [Request(i, float(t), prompt, output)
            for i, t in enumerate(arrivals)]


# ---------------------------------------------------------------- arrivals
class TestArrivals:
    def test_poisson_deterministic_for_seed(self):
        a = poisson_requests(32, 100.0, seed=11)
        b = poisson_requests(32, 100.0, seed=11)
        assert a == b
        assert a != poisson_requests(32, 100.0, seed=12)

    def test_bursty_deterministic_and_bursty(self):
        a = bursty_requests(32, 100.0, seed=3, burst_size=4)
        assert a == bursty_requests(32, 100.0, seed=3, burst_size=4)
        gaps = sorted(y.arrival_ns - x.arrival_ns
                      for x, y in zip(a, a[1:]))
        # 8 bursts of 4 -> the 7 largest gaps are the off periods; their
        # mean dwarfs the mean intra-burst gap (draws are exponential, so
        # compare means, not extremes).
        inter, intra = gaps[-7:], gaps[:-7]
        assert (sum(inter) / len(inter)) > 5 * (sum(intra) / len(intra))

    def test_streams_sorted_with_positive_lengths(self):
        for reqs in (poisson_requests(64, 50.0, seed=0),
                     bursty_requests(64, 50.0, seed=0)):
            assert all(x.arrival_ns <= y.arrival_ns
                       for x, y in zip(reqs, reqs[1:]))
            assert all(r.prompt_tokens >= 1 and r.output_tokens >= 1
                       for r in reqs)
            assert [r.rid for r in reqs] == list(range(64))

    def test_trace_roundtrip(self, tmp_path):
        p = tmp_path / "trace.csv"
        p.write_text("# t,prompt,output\n1000,8,2\n\n2000,16,4\n")
        reqs = trace_requests(str(p))
        assert reqs == [Request(0, 1000.0, 8, 2), Request(1, 2000.0, 16, 4)]

    def test_trace_rids_assigned_after_sort_with_ties(self, tmp_path):
        # Regression: rids used to be assigned in *file* order before the
        # arrival sort, so an out-of-order trace produced rid sequences
        # like [2, 0, 1] — leaking file order into every rid-based
        # tie-break downstream (admission order, router affinity).
        p = tmp_path / "trace.csv"
        p.write_text("3000,32,8\n1000,8,2\n1000,16,4\n2000,24,6\n")
        reqs = trace_requests(str(p))
        assert [r.rid for r in reqs] == [0, 1, 2, 3]
        assert ([r.arrival_ns for r in reqs]
                == [1000.0, 1000.0, 2000.0, 3000.0])
        # The t=1000 tie keeps file order (stable sort).
        assert (reqs[0].prompt_tokens, reqs[1].prompt_tokens) == (8, 16)
        # limit truncates in file order first, then sorts what was kept.
        head = trace_requests(str(p), limit=2)
        assert ([(r.rid, r.arrival_ns) for r in head]
                == [(0, 1000.0), (1, 3000.0)])

    def test_invalid_parameters_raise(self):
        with pytest.raises(ValueError):
            poisson_requests(4, 0.0)
        with pytest.raises(ValueError):
            bursty_requests(4, 10.0, burstiness=1.0)
        with pytest.raises(ValueError):
            poisson_requests(4, 10.0, prompt_mean=0)


# ------------------------------------------------- batch-derived collectives
class TestLiveBatchSizing:
    def test_tp_activation_bytes_track_live_tokens(self):
        pod = resolve_pod(PodSpec(n_gpus=16), TINY, "decode")
        em = StepEmitter(TINY, pod)
        em.step(0, 1)
        em.step(1, 64)
        ag0 = next(c for c in em.calls if c.step == 0
                   and c.collective == "all_gather")
        ag1 = next(c for c in em.calls if c.step == 1
                   and c.collective == "all_gather")
        assert ag1.nbytes == 64 * ag0.nbytes
        assert ag0.nbytes == TINY.d_model * pod.dtype_bytes

    def test_ep_dispatch_bytes_track_live_tokens(self):
        pod = resolve_pod(PodSpec(n_gpus=16), TINY, "decode")
        em = StepEmitter(TINY, pod)
        em.step(0, 128)     # t_loc 8 -> capacity floor
        em.step(1, 4096)    # t_loc 256 -> capacity 40
        a2a = [c for c in em.calls if c.collective == "all_to_all"]
        small = next(c.nbytes for c in a2a if c.step == 0)
        large = next(c.nbytes for c in a2a if c.step == 1)
        assert large > small

    def test_buffers_stable_across_steps(self):
        pod = resolve_pod(PodSpec(n_gpus=16), TINY, "decode")
        em = StepEmitter(TINY, pod)
        em.step(0, 3)
        em.step(1, 17)
        bufs0 = {c.buffer for c in em.calls if c.step == 0}
        bufs1 = {c.buffer for c in em.calls if c.step == 1}
        assert bufs0 == bufs1    # same pages -> steady steps stay warm


# ------------------------------------------------------------- scheduling
class TestContinuousBatching:
    def test_slots_bound_and_conservation(self):
        reqs = tiny_requests([0.0] * 7, prompt=16, output=4)
        res = simulate_traffic(TINY, reqs, n_gpus=16, max_decode_slots=2,
                               prefill_chunk_tokens=16)
        assert all(s.decode_tokens <= 2 for s in res.steps)
        assert len(res.finished) == 7
        for r in res.requests:
            # One TTFT sample plus output_tokens-1 inter-token samples.
            assert r.tokens_out == r.req.output_tokens
            assert len(r.itl_ns) == r.req.output_tokens - 1
            assert r.ttft_ns is not None and r.ttft_ns > 0

    def test_prefill_interleaves_with_decode(self):
        reqs = tiny_requests([0.0, 1.0], prompt=64, output=8)
        res = simulate_traffic(TINY, reqs, n_gpus=16,
                               prefill_chunk_tokens=16)
        assert any(s.decode_tokens and s.prefill_tokens for s in res.steps)

    def test_chunked_prefill_spans_steps(self):
        reqs = tiny_requests([0.0], prompt=100, output=1)
        res = simulate_traffic(TINY, reqs, n_gpus=16,
                               prefill_chunk_tokens=32)
        pre = [s.prefill_tokens for s in res.steps if s.prefill_tokens]
        assert pre == [32, 32, 32, 4]
        assert len(res.finished) == 1

    def test_steps_cap_leaves_requests_unfinished(self):
        reqs = tiny_requests([0.0] * 4, prompt=16, output=50)
        res = simulate_traffic(TINY, reqs, n_gpus=16, steps_cap=5)
        assert res.steps_capped and len(res.steps) == 5
        assert len(res.finished) < 4

    def test_ideal_timeline_causal(self):
        reqs = tiny_requests([0.0, 5e8, 1e9], prompt=16, output=2)
        res = simulate_traffic(TINY, reqs, n_gpus=16)
        for r in res.requests:
            assert r.ideal_first_token_ns > r.req.arrival_ns
            assert r.ttft_degradation >= 1.0 - 1e-9

    def test_single_output_token_finishes_at_prefill_commit(self):
        # output_tokens == 1: the prefill commit *is* the finish.  The
        # request never enters the decode set, so it contributes a TTFT
        # sample but zero inter-token samples, and finish == first token.
        import math
        reqs = tiny_requests([0.0, 1000.0], prompt=16, output=1)
        res = simulate_traffic(TINY, reqs, n_gpus=16)
        assert len(res.finished) == 2
        for r in res.requests:
            assert r.tokens_out == 1
            assert r.itl_ns == [] and r.mean_itl_ns is None
            assert r.first_token_ns == r.finish_ns
            assert r.ideal_first_token_ns == r.ideal_finish_ns
        assert all(s.decode_tokens == 0 for s in res.steps)
        assert math.isnan(res.itl_percentiles()[99.0])

    def test_steps_cap_mid_prefill_excluded_from_percentiles(self):
        # A steps_cap hit mid-prefill leaves a partial RequestStats: no
        # first token, no finish — it must be excluded from finished /
        # first_token_served and every percentile, not counted as a
        # zero-latency sample.
        import math
        reqs = tiny_requests([0.0], prompt=100, output=4)
        res = simulate_traffic(TINY, reqs, n_gpus=16,
                               prefill_chunk_tokens=32, steps_cap=2)
        assert res.steps_capped and len(res.steps) == 2
        (r,) = res.requests
        assert 0 < r.prefill_done < r.req.prompt_tokens
        assert r.first_token_ns is None and not r.finished
        assert r.ttft_ns is None and r.ttft_degradation is None
        assert res.finished == [] and res.first_token_served == []
        assert res.ttft_degradations() == []
        assert math.isnan(res.ttft_percentiles()[99.0])
        assert math.isnan(res.p99_ttft_degradation)


# ------------------------------------------------------ degradation ratios
class TestDegradationAccounting:
    def _commit_one(self, arrival, t_end, ideal_t_end):
        from repro.serving import ContinuousBatcher
        b = ContinuousBatcher([Request(0, arrival, 4, 1)],
                              prefill_chunk_tokens=8)
        plan = b.plan(arrival)
        b.commit(plan, t_end, ideal_t_end, 500.0, 100.0, 1)
        return b.stats[0]

    def test_zero_ideal_ttft_is_infinite_degradation(self):
        # Regression: the ideal step can end exactly at the arrival (the
        # counterfactual serves the first token the instant the request
        # exists).  `not ideal_ttft` treated that legitimate 0.0 as a
        # missing sample, silently dropping the *worst*-degraded requests
        # from the percentiles.
        r = self._commit_one(arrival=1000.0, t_end=2000.0,
                             ideal_t_end=1000.0)
        assert r.ideal_ttft_ns == 0.0 and r.ttft_ns == 1000.0
        assert r.ttft_degradation == float("inf")
        assert r.e2e_degradation == float("inf")
        # ...and it flows into the aggregates instead of vanishing.
        from repro.serving.simulate import TrafficResult
        res = TrafficResult(arch="t", pod=None, cfg=None,
                            requests=[r], steps=[])
        assert res.ttft_degradations() == [float("inf")]
        assert res.p99_ttft_degradation == float("inf")

    def test_zero_over_zero_ttft_is_unit_degradation(self):
        r = self._commit_one(arrival=1000.0, t_end=1000.0,
                             ideal_t_end=1000.0)
        assert r.ttft_ns == 0.0 and r.ideal_ttft_ns == 0.0
        assert r.ttft_degradation == 1.0 and r.e2e_degradation == 1.0

    def test_unserved_request_still_reports_none(self):
        from repro.serving import RequestStats
        r = RequestStats(req=Request(0, 0.0, 4, 1))
        assert r.ttft_degradation is None and r.e2e_degradation is None


# --------------------------------------------------------- TLB interaction
class TestRetentionContract:
    def _run(self, retention):
        cfg = SimConfig(fabric=pod_fabric(resolve_pod(
            PodSpec(n_gpus=16), TINY, "decode")),
            tlb_retention_ns=retention)
        # Two widely separated single-request bursts; the 1s gap between
        # them dwarfs any retention window under test.
        reqs = tiny_requests([0.0, 1e9], prompt=16, output=3)
        return simulate_traffic(TINY, reqs, n_gpus=16, cfg=cfg)

    def test_idle_gap_beyond_retention_repays_cold_misses(self):
        res = self._run(retention=100_000.0)
        # First step of each burst pays the walks; the steps in between
        # ride warm entries.
        walks = [s.walks for s in res.steps]
        burst2_first = next(i for i, s in enumerate(res.steps)
                            if s.t_start >= 1e9)
        assert walks[0] > 0
        assert walks[burst2_first] == walks[0]   # full cold re-pay
        assert all(w == 0 for w in walks[1:burst2_first])
        # The split shows up per request: both requests saw cold comm.
        assert all(r.cold_comm_ns > 0 for r in res.requests)

    def test_no_retention_keeps_entries_across_gap(self):
        res = self._run(retention=None)
        burst2_first = next(i for i, s in enumerate(res.steps)
                            if s.t_start >= 1e9)
        assert res.steps[0].walks > 0
        assert res.steps[burst2_first].walks == 0
        second = res.requests[1]
        assert second.cold_comm_ns == 0 and second.warm_comm_ns > 0

    def test_cold_warm_split_partitions_comm(self):
        res = self._run(retention=100_000.0)
        # Only one request is ever active at a time here, so per-request
        # attributions partition the total comm exactly.
        total = sum(s.comm_ns for s in res.steps)
        attributed = sum(r.cold_comm_ns + r.warm_comm_ns
                         for r in res.requests)
        assert attributed == pytest.approx(total)

    def test_degradation_concentrates_after_flush(self):
        res = self._run(retention=100_000.0)
        cold = [s.degradation for s in res.steps if s.walks > 0]
        warm = [s.degradation for s in res.steps if s.walks == 0]
        assert min(cold) > max(warm)


# ------------------------------------------------- warm-fast-path engagement
class TestWarmFastPath:
    """DESIGN.md §15.2: steady-state decode runs all-warm.

    Acceptance criterion for the serving hot path: once prefill is done and
    the Link-TLBs hold every decode page, the warm fast path should serve
    essentially every step — surfaced per step through
    ``ServingStep.fastpath_calls``.
    """

    def _run(self):
        cfg = SimConfig(fabric=pod_fabric(resolve_pod(
            PodSpec(n_gpus=16), TINY, "decode")))
        # One long-decode request: a single prefill chunk, then ~63 pure
        # decode steps re-touching the same warmed pages.
        reqs = tiny_requests([0.0], prompt=16, output=64)
        return simulate_traffic(TINY, reqs, n_gpus=16, cfg=cfg)

    def test_steady_state_decode_engages_fastpath(self):
        res = self._run()
        assert len(res.steps) > 20
        assert res.fastpath_step_fraction > 0.9
        assert res.fastpath_calls > 0


# ----------------------------------------------------------------- sweeps
class TestSweepDeterminism:
    def _points(self):
        base = dict(arch=TINY, n_requests=6, steps_cap=24,
                    prompt_mean=16, output_mean=3, retention_ns=100_000.0,
                    max_decode_slots=4, prefill_chunk_tokens=32)
        return [TrafficPoint(rps=200.0, arrival="poisson", seed=5, **base),
                TrafficPoint(rps=200.0, arrival="bursty", seed=5,
                             burst_size=3, **base)]

    def test_serial_and_pool_bit_for_bit(self):
        pts = self._points()
        serial = sweep_traffic(pts, workers=0)
        pooled = sweep_traffic(pts, workers=2)
        for pt in pts:
            a, b = serial[pt], pooled[pt]
            # Arrival generation is bit-for-bit identical...
            assert [r.req for r in a.requests] == [r.req for r in b.requests]
            # ...and so is everything priced from it.
            assert ([(s.t_start, s.t_end, s.comm_ns, s.ideal_comm_ns,
                      s.walks) for s in a.steps]
                    == [(s.t_start, s.t_end, s.comm_ns, s.ideal_comm_ns,
                         s.walks) for s in b.steps])
            assert a.ttft_percentiles() == b.ttft_percentiles()
            assert a.itl_percentiles() == b.itl_percentiles()

    def test_point_regenerates_identical_arrivals(self):
        pt = self._points()[1]
        assert pt.requests() == pt.requests()

    def test_duplicate_points_priced_once(self, monkeypatch):
        # Regression: the serial path priced duplicate points once each —
        # a sweep grid with repeated points paid for every repetition even
        # though equal points are, by construction, identical work.
        import repro.serving.simulate as sim_mod
        pts = self._points()
        calls = []
        orig = sim_mod._traffic_point

        def counting(task):
            calls.append(task)
            return orig(task)

        monkeypatch.setattr(sim_mod, "_traffic_point", counting)
        out = sim_mod.sweep_traffic([pts[0], pts[0], pts[1], pts[0]],
                                    workers=0)
        assert len(calls) == 2
        # The mapping still covers every input point (equal points are
        # equal keys) and matches a duplicate-free sweep bit-for-bit.
        assert set(out) == set(pts)
        clean = sweep_traffic(pts, workers=0)
        for pt in pts:
            assert ([(s.t_start, s.t_end) for s in out[pt].steps]
                    == [(s.t_start, s.t_end) for s in clean[pt].steps])


# ------------------------------------------------------ compute profiles
class TestProfileThreading:
    def _profile(self, calibrated_ns):
        from repro.workloads.calibrate import ComputeProfile, PhaseWindow
        phases = {ph: PhaseWindow(phase=ph, kernels=(), roofline_ns=1000.0,
                                  measured_wall_ns=1000.0,
                                  measured_flops=1.0,
                                  calibrated_ns=calibrated_ns)
                  for ph in ("attn_mixer", "moe_ffn")}
        return ComputeProfile(arch=TINY.name, shape="serving", n_gpus=16,
                              ep=16, tp=1, dp=1, phases=phases)

    def test_profile_path_threads_through_pool(self, tmp_path):
        # Regression: TrafficPoint silently dropped the compute profile —
        # the pooled worker rebuilt the point without it, so calibrated
        # sweeps diverged between the serial and pooled executors.
        path = self._profile(50_000.0).save(tmp_path / "prof.json")
        base = dict(arch=TINY, rps=200.0, arrival="poisson", seed=5,
                    n_requests=4, steps_cap=16, prompt_mean=16,
                    output_mean=2, max_decode_slots=4,
                    prefill_chunk_tokens=32)
        pt = TrafficPoint(profile_path=str(path), **base)
        bare = TrafficPoint(**base)
        serial = sweep_traffic([pt, bare], workers=0)
        pooled = sweep_traffic([pt, bare], workers=2)
        for p in (pt, bare):
            assert ([(s.t_start, s.t_end, s.compute_ns, s.comm_ns)
                     for s in serial[p].steps]
                    == [(s.t_start, s.t_end, s.compute_ns, s.comm_ns)
                        for s in pooled[p].steps])
        # The profile actually reached the session: calibrated compute
        # windows change the step timing vs the bare (roofline) point.
        assert ([s.compute_ns for s in serial[pt].steps]
                != [s.compute_ns for s in serial[bare].steps])

    def test_profile_affects_ideal_timeline_consistently(self, tmp_path):
        # Both the baseline and the ideal counterfactual see the same
        # calibrated windows, so degradation stays a pure-RAT ratio.
        path = self._profile(200_000.0).save(tmp_path / "p.json")
        reqs = tiny_requests([0.0], prompt=16, output=2)
        from repro.workloads.calibrate import ComputeProfile
        res = simulate_traffic(TINY, reqs, n_gpus=16,
                               compute_profile=ComputeProfile.load(path))
        (r,) = res.requests
        assert r.ttft_degradation is not None
        # Huge calibrated windows dominate both timelines equally, so
        # degradation is pinned near 1 even on the cold first step.
        assert 1.0 - 1e-9 <= r.ttft_degradation < 1.5


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**32 - 1),
           rps=st.floats(0.5, 1000.0),
           n=st.integers(1, 64),
           arrival=st.sampled_from(["poisson", "bursty"]))
    def test_property_arrival_generation_deterministic(seed, rps, n,
                                                       arrival):
        gen = (poisson_requests if arrival == "poisson"
               else bursty_requests)
        a, b = gen(n, rps, seed=seed), gen(n, rps, seed=seed)
        assert a == b
        assert all(x.arrival_ns <= y.arrival_ns for x, y in zip(a, a[1:]))
        assert all(r.prompt_tokens >= 1 and r.output_tokens >= 1
                   for r in a)


# -------------------------------------------------------------------- CLI
class TestCLI:
    def test_cli_runs_offline_without_jax(self):
        # The acceptance command, scaled down: must resolve the registry
        # arch, simulate, and print the percentile summary with the
        # cold-vs-warm split — all without jax ever being imported.
        code = (
            "import sys\n"
            "from repro.serving.__main__ import main\n"
            "rc = main(['--arch', 'granite-moe-1b-a400m', '--rps', '8',\n"
            "           '--steps-cap', '8', '--requests', '2',\n"
            "           '--prompt-mean', '8', '--output-mean', '2'])\n"
            "assert rc == 0, rc\n"
            "assert 'jax' not in sys.modules, 'CLI must stay jax-free'\n"
        )
        root = pathlib.Path(__file__).resolve().parent.parent
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300,
            env={**os.environ, "PYTHONPATH": str(root / "src")},
            cwd=str(root))
        assert out.returncode == 0, out.stderr
        assert "metric,p50_us,p95_us,p99_us" in out.stdout
        assert "ttft," in out.stdout and "inter_token," in out.stdout
        assert "cold-vs-warm comm split" in out.stdout


# ------------------------------------------------------------------ fig15
@pytest.mark.slow
def test_fig15_bursty_tail_exceeds_mean():
    from benchmarks.paper_figs import fig15_serving_tail_latency
    rows = {name: derived for name, _us, derived
            in fig15_serving_tail_latency()}
    assert "p99_exceeds_mean=True" in rows[
        "fig15/check_bursty_tail_concentration"]
    assert "claws_back=True" in rows[
        "fig15/check_pretranslation_claws_back_tail"]


# ---------------------------------------------------------- disaggregation
class TinyDisaggMoE(TinyServeMoE):
    """TinyServeMoE plus the KV-sizing hook the disagg handoff reads."""
    name = "tiny-disagg-moe"

    def kv_bytes_per_token(self, dtype_bytes: int = 2) -> int:
        return (self.n_kv_heads * self.d_head * 2 * dtype_bytes
                * self.n_layers)


TINY_KV = TinyDisaggMoE()


def _disagg_cfg(retention=None):
    cfg = SimConfig(fabric=pod_fabric(resolve_pod(
        PodSpec(n_gpus=16), TINY_KV, "decode")))
    if retention is not None:
        cfg = cfg.replace(tlb_retention_ns=retention)
    return cfg


class TestDisaggHandoff:
    def test_every_multi_token_request_hands_off(self):
        reqs = tiny_requests([0.0, 1000.0, 2000.0], prompt=16, output=3)
        reqs.append(Request(3, 3000.0, 16, 1))       # single-token request
        res = simulate_disagg(TINY_KV, reqs, n_gpus=16, cfg=_disagg_cfg())
        assert sorted(h.rid for h in res.handoffs) == [0, 1, 2]
        # output_tokens <= 1 finishes at prefill, never crosses the hop
        one = res.requests[3]
        assert one.req.rid == 3 and one.kv_start_ns is None
        assert one.finished and one.first_token_ns is not None

    def test_ttft_decomposition_sums(self):
        reqs = tiny_requests([0.0, 1000.0, 2000.0], prompt=16, output=3)
        res = simulate_disagg(TINY_KV, reqs, n_gpus=16, cfg=_disagg_cfg())
        bd = res.ttft_breakdown()
        assert bd["n"] == 3
        assert bd["ttft_ns"] == pytest.approx(
            bd["prefill_ns"] + bd["kv_wait_ns"] + bd["kv_transfer_ns"]
            + bd["decode_wait_ns"])
        # per request: the transfer lands on TTFT (DESIGN.md §16.1)
        for r in res.requests[:3]:
            assert r.kv_transfer_ns > 0
            assert (r.req.arrival_ns + r.first_token_ns
                    >= r.kv_start_ns + r.kv_transfer_ns)

    def test_transfer_serialization_keeps_decode_arrivals_sorted(self):
        # A burst of simultaneous prompts: every handoff routes to the one
        # decode pod, whose link serializes them — admission must still be
        # nondecreasing (ContinuousBatcher.add asserts this itself).
        reqs = tiny_requests([0.0] * 6, prompt=16, output=3)
        res = simulate_disagg(TINY_KV, reqs, n_gpus=16, cfg=_disagg_cfg())
        starts = [h.start_ns for h in res.handoffs]
        assert starts == sorted(starts)
        assert all(r.finished for r in res.requests)

    def test_bad_split_and_router_raise(self):
        reqs = tiny_requests([0.0], prompt=16, output=2)
        with pytest.raises(ValueError):
            simulate_disagg(TINY_KV, reqs, n_gpus=16, prefill_pods=0)
        with pytest.raises(ValueError):
            simulate_disagg(TINY_KV, reqs, n_gpus=16, router="nope")


def test_disagg_retention():
    """An idle decode pod re-pays the KV-transfer walks (DESIGN.md §16.3).

    One-slot arena (kv_arena_bytes == one page-aligned shard), so both
    transfers hit the same arena offset: without retention the second
    rides the first's warmed translations; with the 5 s gap past
    ``tlb_retention_ns`` the link session flushes and re-pays in full.
    """
    reqs = tiny_requests([0.0, 5e9], prompt=64, output=3)
    arena = 2 * 2**20                                # exactly one 2 MB slot
    warm = simulate_disagg(TINY_KV, reqs, n_gpus=16, cfg=_disagg_cfg(None),
                           kv_arena_bytes=arena)
    cold = simulate_disagg(TINY_KV, reqs, n_gpus=16,
                           cfg=_disagg_cfg(1_000_000.0),
                           kv_arena_bytes=arena)
    w = {h.rid: h for h in warm.handoffs}
    c = {h.rid: h for h in cold.handoffs}
    assert w[0].offset == w[1].offset == 0           # same arena region
    assert w[0].walks > 0 and c[0].walks > 0         # first contact walks
    assert w[1].walks == 0                           # retained: warm
    assert c[1].walks == c[0].walks > 0              # flushed: full re-pay
    assert warm.kv_cold_handoffs == 1 and cold.kv_cold_handoffs == 2
    assert cold.kv_excess_total_ns > warm.kv_excess_total_ns


def _disagg_points():
    base = dict(arch=TINY_KV, n_requests=6, steps_cap=80, prompt_mean=16,
                output_mean=3, retention_ns=100_000.0, max_decode_slots=4,
                prefill_chunk_tokens=32)
    return [DisaggPoint(traffic=TrafficPoint(rps=200.0, seed=5, **base)),
            DisaggPoint(traffic=TrafficPoint(rps=200.0, arrival="bursty",
                                             seed=5, burst_size=3, **base),
                        prefill_pods=2, decode_pods=1)]


def _disagg_fingerprint(res):
    return (
        [(h.rid, h.decode_idx, h.offset, h.start_ns, h.transfer_ns,
          h.ideal_ns, h.walks) for h in res.handoffs],
        [(s.t_start, s.t_end, s.comm_ns, s.ideal_comm_ns, s.walks)
         for s in res.steps],
        res.ttft_percentiles(), res.itl_percentiles())


def test_disagg_serial_equals_pooled():
    """sweep_disagg's executors are bit-for-bit identical (DESIGN.md §16.4)."""
    pts = _disagg_points()
    serial = sweep_disagg(pts, workers=0)
    pooled = sweep_disagg(pts, workers=2)
    for pt in pts:
        assert _disagg_fingerprint(serial[pt]) == \
            _disagg_fingerprint(pooled[pt])


def test_disagg_engines_agree():
    """Disagg prices bit-for-bit what the scalar per-epoch engine the
    engine was ported from recorded (a ``test_engine_diff.digest`` of the
    fingerprint): prefill, decode and transfer sessions interleave
    (DESIGN.md §16.4)."""
    from test_engine_diff import digest
    reqs = tiny_requests([0.0, 500.0, 1500.0], prompt=24, output=4)
    res = simulate_disagg(TINY_KV, reqs, n_gpus=16, cfg=_disagg_cfg())
    assert digest(_disagg_fingerprint(res)) == "94bad909d1994f9b"


def test_disagg_off_colocated_bit_for_bit():
    # Regression for the colocated path: pricing a disagg deployment in
    # between must not perturb simulate_traffic (no shared mutable state);
    # the absolute colocated numbers themselves are locked by the goldens
    # (tests/test_golden_figs.py).
    pt = TrafficPoint(arch=TINY_KV, n_requests=5, rps=300.0, seed=9,
                      steps_cap=40, prompt_mean=16, output_mean=3)

    def price():
        res = sweep_traffic([pt], workers=0)[pt]
        return ([(s.t_start, s.t_end, s.comm_ns, s.walks)
                 for s in res.steps], res.ttft_percentiles())

    before = price()
    simulate_disagg(TINY_KV, pt.requests(), n_gpus=16, cfg=_disagg_cfg())
    assert price() == before


class TestDisaggCLI:
    def test_disagg_cli_offline_and_fleet_exclusive(self):
        code = (
            "import sys\n"
            "from repro.serving.__main__ import main\n"
            "rc = main(['--arch', 'granite-moe-1b-a400m', '--rps', '8',\n"
            "           '--disagg', '1:1', '--steps-cap', '40',\n"
            "           '--requests', '3', '--prompt-mean', '64',\n"
            "           '--output-mean', '2'])\n"
            "assert rc == 0, rc\n"
            "assert 'jax' not in sys.modules, 'CLI must stay jax-free'\n"
        )
        root = pathlib.Path(__file__).resolve().parent.parent
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300,
            env={**os.environ, "PYTHONPATH": str(root / "src")},
            cwd=str(root))
        assert out.returncode == 0, out.stderr
        assert "# disagg: 1 prefill + 1 decode pods" in out.stdout
        assert "kv_transfer" in out.stdout
        bad = subprocess.run(
            [sys.executable, "-m", "repro.serving", "--arch",
             "granite-moe-1b-a400m", "--disagg", "1:1", "--fleet", "2"],
            capture_output=True, text=True, timeout=60,
            env={**os.environ, "PYTHONPATH": str(root / "src")},
            cwd=str(root))
        assert bad.returncode != 0
        assert "mutually exclusive" in bad.stderr
