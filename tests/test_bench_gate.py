"""Tests for the engine micro-benchmark regression gate (benchmarks/run.py).

The CI job times the fixed grid, uploads it as an artifact, then gates it
against the committed ``benchmarks/BENCH_baseline.json``; these tests pin
the gate's semantics — most importantly that a synthetic 2x-slower point
demonstrably fails — without ever timing anything, and hold the committed
baseline's recorded results to what the engine computes today.
"""
import copy
import json
import pathlib

import pytest

from benchmarks.run import BASELINE_PATH, _bench_points, check_against

ROOT = pathlib.Path(__file__).resolve().parent.parent


def payload(walls):
    """A BENCH_engine.json-shaped dict over the real grid."""
    return {"grid": "engine-v3",
            "points": [{"topology": t, "n_gpus": n, "nbytes": b, "wall_s": w}
                       for (t, n, b), w in zip(_bench_points(), walls)]}


WALLS = [0.5, 1.0, 0.8, 0.9, 1.2, 0.3, 0.6, 2.0]


class TestCheckAgainst:
    def test_identical_passes(self):
        base = payload(WALLS)
        assert check_against(copy.deepcopy(base), base, 0.35) == []

    def test_2x_slower_point_fails(self):
        base = payload(WALLS)
        cur = copy.deepcopy(base)
        cur["points"][1]["wall_s"] = 2.0          # 2x the 1.0s baseline
        failures = check_against(cur, base, 0.35)
        assert len(failures) == 1
        assert "gpus64" in failures[0] and "+100.0%" in failures[0]

    def test_within_tolerance_passes(self):
        base = payload(WALLS)
        cur = copy.deepcopy(base)
        cur["points"][1]["wall_s"] = 1.3          # +30% < 35%
        assert check_against(cur, base, 0.35) == []

    def test_small_absolute_jitter_ignored(self):
        # A 5ms point doubling is timer noise, not an engine regression:
        # the absolute floor keeps the relative gate from flaking.
        base = payload([0.005] + WALLS[1:])
        cur = copy.deepcopy(base)
        cur["points"][0]["wall_s"] = 0.010
        assert check_against(cur, base, 0.35) == []
        cur["points"][0]["wall_s"] = 0.500        # a real 100x blowup fails
        assert len(check_against(cur, base, 0.35)) == 1

    def test_faster_never_fails(self):
        base = payload(WALLS)
        cur = payload([w / 5 for w in WALLS])
        assert check_against(cur, base, 0.35) == []

    def test_grid_mismatch_fails_both_ways(self):
        base = payload(WALLS)
        cur = copy.deepcopy(base)
        dropped = cur["points"].pop()             # missing point
        failures = check_against(cur, base, 0.35)
        assert any("not measured" in f for f in failures)
        extra = copy.deepcopy(base)
        extra["points"].append(dict(dropped, topology="ring"))
        failures = check_against(extra, base, 0.35)
        assert any("not in baseline" in f for f in failures)

    def test_fleet_point_gates(self):
        # The fleet serving point gates against the baseline like any
        # grid point.
        base = payload(WALLS)
        cur = copy.deepcopy(base)
        assert cur["points"][-1]["topology"] == "fleet"
        assert check_against(copy.deepcopy(cur), base, 0.35) == []
        cur["points"][-1]["wall_s"] = 4.0         # 2x the 2.0s baseline
        failures = check_against(cur, base, 0.35)
        assert len(failures) == 1
        assert "fleet/gpus16/serving" in failures[0]


class TestCommittedBaseline:
    def test_baseline_matches_bench_grid(self):
        """The committed baseline covers exactly the current grid, so the
        CI gate can never silently skip a point."""
        with open(ROOT / BASELINE_PATH) as f:
            base = json.load(f)
        keys = {(p["topology"], p["n_gpus"], p["nbytes"])
                for p in base["points"]}
        assert keys == set(_bench_points())
        assert all(p["wall_s"] > 0 for p in base["points"])

    @pytest.mark.parametrize("point", [p for p in _bench_points()
                                       if p[0] != "fleet"],
                             ids=lambda p: f"{p[0]}-{p[1]}-{p[2] >> 20}MB")
    def test_baseline_records_current_results(self, point):
        """Each committed grid point's simulated results are what the
        engine computes today: the timed grid prices what it claims to."""
        from repro.core import ratsim
        from repro.core.config import FabricConfig, SimConfig

        with open(ROOT / BASELINE_PATH) as f:
            base = {(p["topology"], p["n_gpus"], p["nbytes"]): p
                    for p in json.load(f)["points"]}
        topo, n, nbytes = point
        fab = FabricConfig(n_gpus=n, topology=topo, leaf_size=16,
                           oversubscription=2.0, pod_size=16)
        c = ratsim.compare(nbytes, n, cfg=SimConfig(fabric=fab))
        rec = base[point]
        assert c.baseline.completion_ns == rec["completion_ns"]
        assert c.degradation == rec["degradation"]
        assert c.baseline.counters.requests == rec["requests"]
