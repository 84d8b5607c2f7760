"""Tests for the fleet-scale serving layer (repro.serving.fleet).

Covers: router policies (round-robin distribution, least-loaded balancing,
deterministic rid-hash affinity), the bounded admission queue (rejections
recorded and excluded from percentiles), the queue-depth autoscaler
(spin-ups under pressure, the live-replica cap across churn, idle
retirement) and its central accounting contract — a freshly spun replica
starts with stone-cold TLBs and re-pays the full cold-walk warmup even
when the rest of the fleet is warm — plus request conservation, the total
steps cap, serial-vs-pooled sweep determinism, and the fleet's priced
steps against a record.
"""
import os
import pathlib
import subprocess
import sys

import pytest

from repro.core.config import SimConfig
from repro.serving import (FleetPoint, Request, TrafficPoint,
                           simulate_fleet, sweep_fleet)
from repro.serving.fleet import _rid_hash
from repro.workloads import PodSpec, pod_fabric, resolve_pod


class TinyFleetMoE:
    """Duck-typed stand-in for ModelConfig (only the fields derive reads)."""
    name = "tiny-fleet-moe"
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


TINY = TinyFleetMoE()


def tiny_requests(arrivals, prompt=16, output=2):
    return [Request(i, float(t), prompt, output)
            for i, t in enumerate(arrivals)]


def burst_times(n_bursts, per_burst, gap_ns, intra_ns=1000.0):
    """n_bursts tight clumps separated by gap_ns."""
    out = []
    for b in range(n_bursts):
        t0 = b * gap_ns
        out.extend(t0 + i * intra_ns for i in range(per_burst))
    return out


# ----------------------------------------------------------------- routing
class TestRouting:
    def _run(self, router, n=8, replicas=2, **kw):
        reqs = tiny_requests([i * 1000.0 for i in range(n)])
        return simulate_fleet(TINY, reqs, n_gpus=16, replicas=replicas,
                              router=router, **kw)

    def test_round_robin_distributes_cyclically(self):
        res = self._run("round_robin", n=8, replicas=2)
        assert [rep.routed for rep in res.replicas] == [4, 4]
        # Strict alternation: even rids on replica 0, odd on replica 1.
        rids = [sorted(r.rid for r in rep.stats)
                for rep in res.replicas]
        assert rids == [[0, 2, 4, 6], [1, 3, 5, 7]]

    def test_least_loaded_balances(self):
        res = self._run("least_loaded", n=12, replicas=3)
        routed = [rep.routed for rep in res.replicas]
        assert sum(routed) == 12
        assert max(routed) - min(routed) <= 2

    def test_affinity_is_deterministic_rid_hash(self):
        res = self._run("affinity", n=8, replicas=2)
        for rep in res.replicas:
            for r in rep.stats:
                assert _rid_hash(r.rid) % 2 == rep.idx
        # And reproducible run to run.
        res2 = self._run("affinity", n=8, replicas=2)
        assert ([rep.routed for rep in res.replicas]
                == [rep.routed for rep in res2.replicas])

    def test_unknown_router_rejected(self):
        with pytest.raises(ValueError):
            self._run("random")

    def test_all_requests_finish_and_are_rid_sorted(self):
        res = self._run("round_robin", n=8, replicas=3)
        assert len(res.finished) == 8
        assert [r.rid for r in res.requests] == list(range(8))


# ------------------------------------------------------------- admission
class TestAdmissionQueue:
    def test_overflow_rejected_and_excluded(self):
        # One slow replica, a clump of simultaneous arrivals, queue of 3:
        # the clump exceeds capacity while nothing has started prefill.
        reqs = tiny_requests([0.0] * 8, prompt=64, output=4)
        res = simulate_fleet(TINY, reqs, n_gpus=16, replicas=1,
                             max_queue=3, max_decode_slots=2)
        assert len(res.rejected) > 0
        assert len(res.requests) + len(res.rejected) == 8
        # Rejected requests never appear in latency accounting.
        served_rids = {r.rid for r in res.requests}
        assert all(q.rid not in served_rids for q in res.rejected)
        assert len(res.finished) == len(res.requests)

    def test_unbounded_queue_rejects_nothing(self):
        reqs = tiny_requests([0.0] * 8, prompt=64, output=4)
        res = simulate_fleet(TINY, reqs, n_gpus=16, replicas=1,
                             max_decode_slots=2)
        assert res.rejected == [] and len(res.finished) == 8


# ------------------------------------------------------------- autoscaler
class TestAutoscaler:
    GAP = 5e7                            # 50 ms between bursts

    def _bursty(self, n_bursts=3, per_burst=6):
        return tiny_requests(burst_times(n_bursts, per_burst, self.GAP),
                             prompt=16, output=2)

    def test_scales_up_under_queue_pressure(self):
        res = simulate_fleet(TINY, self._bursty(1), n_gpus=16, replicas=4,
                             autoscale=True, min_replicas=1,
                             scale_up_queued=2)
        assert res.spin_ups >= 1
        assert len(res.finished) == 6

    def test_live_cap_respected_across_churn(self):
        res = simulate_fleet(TINY, self._bursty(4), n_gpus=16, replicas=2,
                             autoscale=True, min_replicas=1,
                             scale_up_queued=1,
                             scale_down_idle_ns=self.GAP / 4)
        assert res.retired >= 1                  # churn actually happened
        assert res.spin_ups >= 2                 # ...and re-spun later
        # At no arrival instant did live replicas exceed the cap of 2:
        # verify via lifecycle intervals.
        events = []
        for rep in res.replicas:
            events.append((rep.spun_up_ns, 1))
            if rep.retired_ns is not None:
                events.append((rep.retired_ns, -1))
        live = peak = 0
        for _t, d in sorted(events):
            live += d
            peak = max(peak, live)
        assert peak <= 2
        assert res.peak_replicas == peak
        assert len(res.finished) == len(res.requests)

    def test_min_replicas_never_retired(self):
        res = simulate_fleet(TINY, self._bursty(3), n_gpus=16, replicas=3,
                             autoscale=True, min_replicas=2,
                             scale_up_queued=1,
                             scale_down_idle_ns=self.GAP / 4)
        live_at_end = sum(1 for rep in res.replicas if rep.live)
        assert live_at_end >= 2

    def test_cold_spinup_repays_walks_while_fleet_is_warm(self):
        """The fleet-scale RAT event: a replica spun mid-run starts with
        stone-cold TLBs and performs page walks on its first step, even
        though the incumbent replica is fully warm by then (no retention —
        warmth only ever disappears by being born without it)."""
        res = simulate_fleet(TINY, self._bursty(2, 8), n_gpus=16,
                             replicas=2, autoscale=True, min_replicas=1,
                             scale_up_queued=1)
        assert res.spin_ups >= 1
        spun = [rep for rep in res.replicas if rep.spun_up_ns > 0.0
                and rep.steps]
        assert spun, "a spun replica must have served traffic"
        for rep in spun:
            assert rep.steps[0].walks > 0
        # The incumbent replica is warm on every post-warmup step of the
        # second burst (retention is None, so its warmth persists).
        first = res.replicas[0].steps
        second_burst = [s for s in first if s.t_start >= self.GAP]
        assert second_burst and all(s.walks == 0 for s in second_burst)

    def test_spinup_latency_delays_availability(self):
        lat = 1e6
        res = simulate_fleet(TINY, self._bursty(1, 8), n_gpus=16,
                             replicas=2, autoscale=True, min_replicas=1,
                             scale_up_queued=1, spinup_latency_ns=lat)
        spun = [rep for rep in res.replicas if rep.spun_up_ns > 0.0]
        assert spun
        for rep in spun:
            assert rep.spun_up_ns >= lat
            for s in rep.steps:
                assert s.t_start >= rep.spun_up_ns


# ------------------------------------------------------------------ bounds
class TestStepsCap:
    def test_total_fleet_steps_bounded(self):
        reqs = tiny_requests([0.0] * 12, prompt=16, output=40)
        res = simulate_fleet(TINY, reqs, n_gpus=16, replicas=3,
                             steps_cap=9)
        assert res.steps_capped
        assert len(res.steps) == 9               # fleet-wide, not per pod
        assert len(res.finished) < 12


# ------------------------------------------------------------------ sweeps
class TestFleetSweepDeterminism:
    def _points(self):
        base = TrafficPoint(arch=TINY, rps=300.0, arrival="bursty", seed=9,
                            n_requests=10, burst_size=4, steps_cap=60,
                            prompt_mean=16, output_mean=2,
                            retention_ns=100_000.0, max_decode_slots=4,
                            prefill_chunk_tokens=32)
        return [
            FleetPoint(traffic=base, replicas=2, router="round_robin"),
            FleetPoint(traffic=base, replicas=2, router="least_loaded",
                       autoscale=True, min_replicas=1, scale_up_queued=1,
                       scale_down_idle_ns=1e6, spinup_latency_ns=1e5),
        ]

    def test_serial_and_pool_bit_for_bit(self):
        pts = self._points()
        serial = sweep_fleet(pts, workers=0)
        pooled = sweep_fleet(pts, workers=2)
        for pt in pts:
            a, b = serial[pt], pooled[pt]
            assert ([(s.t_start, s.t_end, s.comm_ns, s.ideal_comm_ns,
                      s.walks) for s in a.steps]
                    == [(s.t_start, s.t_end, s.comm_ns, s.ideal_comm_ns,
                         s.walks) for s in b.steps])
            assert ([(rep.spun_up_ns, rep.retired_ns, rep.routed)
                     for rep in a.replicas]
                    == [(rep.spun_up_ns, rep.retired_ns, rep.routed)
                        for rep in b.replicas])
            assert a.ttft_percentiles() == b.ttft_percentiles()
            assert ([r.rid for r in a.rejected]
                    == [r.rid for r in b.rejected])

    # Fingerprints (test_engine_diff.digest) of each point's priced steps
    # and TTFT percentiles, recorded from the scalar per-epoch engine the
    # engine was ported from.  Replicas run interleaved sessions that share
    # process-cached plans (DESIGN.md §15.1).
    RECORDED = ["729b5117437de13e", "75ceacdd7151987a"]

    def test_engines_agree_bit_for_bit(self):
        from test_engine_diff import digest
        res = sweep_fleet(self._points(), workers=0)
        assert [digest(([(s.t_start, s.t_end, s.comm_ns, s.walks)
                         for s in r.steps], r.ttft_percentiles()))
                for r in res.values()] == self.RECORDED

    def test_duplicate_points_priced_once(self, monkeypatch):
        import repro.serving.fleet as fleet_mod
        pts = self._points()
        calls = []
        orig = fleet_mod._fleet_point

        def counting(task):
            calls.append(task)
            return orig(task)

        monkeypatch.setattr(fleet_mod, "_fleet_point", counting)
        out = fleet_mod.sweep_fleet([pts[0], pts[0], pts[1]], workers=0)
        assert len(calls) == 2
        assert set(out) == set(pts)


# -------------------------------------------------------------------- CLI
class TestFleetCLI:
    def test_fleet_cli_runs_offline_without_jax(self):
        code = (
            "import sys\n"
            "from repro.serving.__main__ import main\n"
            "rc = main(['--arch', 'granite-moe-1b-a400m', '--rps', '20',\n"
            "           '--arrival', 'bursty', '--requests', '8',\n"
            "           '--steps-cap', '40', '--prompt-mean', '16',\n"
            "           '--output-mean', '2', '--fleet', '2',\n"
            "           '--router', 'least_loaded', '--autoscale',\n"
            "           '--min-replicas', '1', '--scale-up-queued', '1'])\n"
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
        assert "# fleet: autoscale 1..2 replicas" in out.stdout
        assert "replica,spun_up_us,retired_us,routed,steps,walks," in out.stdout
        assert "metric,p50_us,p95_us,p99_us" in out.stdout


# ------------------------------------------------------------------ fig16
@pytest.mark.slow
def test_fig16_autoscale_cold_spinups_tax_the_tail():
    from benchmarks.paper_figs import fig16_fleet_scaling
    rows = {name: derived for name, _us, derived in fig16_fleet_scaling()}
    tax = rows["fig16/check_cold_spinup_tax"]
    assert "taxed=True" in tax
    assert "equal_capacity=True" in tax
    assert "any_fit=True" in rows["fig16/check_static_provisioning"]


# --------------------------------------------------------------- retention
class TestFleetRetention:
    def test_idle_fleet_repays_cold_walks_per_replica(self):
        """Each replica's TLB ages independently: after a fleet-wide quiet
        period beyond retention, every replica re-pays its own cold walks."""
        pod = resolve_pod(PodSpec(n_gpus=16), TINY, "decode")
        cfg = SimConfig(fabric=pod_fabric(pod), tlb_retention_ns=100_000.0)
        reqs = tiny_requests([0.0, 1000.0, 1e9, 1e9 + 1000.0],
                             prompt=16, output=2)
        res = simulate_fleet(TINY, reqs, n_gpus=16, cfg=cfg, replicas=2,
                             router="round_robin")
        for rep in res.replicas:
            steps = rep.steps
            assert steps[0].walks > 0
            late = [s for s in steps if s.t_start >= 1e9]
            assert late and late[0].walks == steps[0].walks
