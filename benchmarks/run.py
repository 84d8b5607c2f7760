# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV.  ``--bench-engine`` instead times a fixed sweep grid through the
# simulator and writes BENCH_engine.json (uploaded as a CI artifact so the
# engine's performance trajectory is tracked PR over PR);
# ``--check-against benchmarks/BENCH_baseline.json`` turns that grid into a
# regression gate: any point whose wall time exceeds the committed
# baseline by more than ``--tolerance`` fails the run (use
# ``--update-baseline`` for intentional resets, ``--current`` to gate a
# pre-measured JSON without re-running the grid).  Wall times are host
# times of the machine that ran the grid.
import argparse
import json
import sys
import time
import traceback

BASELINE_PATH = "benchmarks/BENCH_baseline.json"


def figures() -> int:
    from . import paper_figs

    print("name,us_per_call,derived")
    failures = 0
    for fn in paper_figs.ALL:
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            # The CSV cell keeps the one-line summary; the full traceback
            # goes to stderr so CI logs are actionable.
            traceback.print_exc(file=sys.stderr)
            print(f"{fn.__name__},0.0,ERROR:{type(e).__name__}:{e}")
            failures += 1
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.3f},{derived}")
        print(f"#{fn.__name__} done in {time.time()-t0:.1f}s",
              file=sys.stderr)
    return 1 if failures else 0


# Fixed micro-benchmark grid: (topology, n_gpus, nbytes).  Serial, one
# simulate pair per point — wall times measure the engine itself, not the
# sweep pool.  Includes the paper-scale 1 GB point (epoch expansion),
# tier-shaped two-tier points, and the pod-scale 512/256-GPU points where
# O(n^2) flow materialization would dominate a scalar engine.  The special
# ("fleet", 16, 0) point times an autoscaled fleet serving run
# (repro.serving.fleet): thousands of tiny decode collectives, the regime
# of the serving hot path (geometry memoization + warm fast path,
# DESIGN.md §15).
def _bench_points():
    from repro.core import GB, MB
    return [
        ("single_clos", 16, 16 * MB),
        ("single_clos", 64, 1 * GB),
        ("two_tier", 256, 16 * MB),
        ("two_tier", 256, 256 * MB),
        ("two_tier", 512, 16 * MB),
        ("multi_pod", 64, 64 * MB),
        ("multi_pod", 256, 64 * MB),
        ("fleet", 16, 0),
    ]


def _fleet_bench_point():
    from repro.serving import FleetPoint, TrafficPoint
    traffic = TrafficPoint(
        arch="granite-moe-1b-a400m", rps=16.0, arrival="bursty",
        n_requests=10, seed=7, steps_cap=40, burst_size=4,
        burstiness=24.0, prompt_mean=64, output_mean=4)
    return FleetPoint(traffic=traffic, replicas=2, router="least_loaded",
                      autoscale=True, min_replicas=1, max_replicas=2,
                      scale_up_queued=1, scale_down_idle_ns=5e7)


def _measure_fleet(n_gpus: int, reps: int, profile: bool = False) -> dict:
    """Time the fleet serving point, best-of-``reps``."""
    from repro.serving.fleet import _fleet_point

    wall = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        res = _fleet_point((_fleet_bench_point(),))
        wall = min(wall, time.perf_counter() - t0)
    if profile:
        _profile_point(f"fleet/gpus{n_gpus}/serving",
                       lambda: _fleet_point((_fleet_bench_point(),)))
    comm = sum(s.comm_ns for s in res.steps)
    print(f"# fleet/gpus{n_gpus}/serving: {wall:.3f}s "
          f"({len(res.steps)} steps, {res.spin_ups} spin-ups, "
          f"fastpath {res.fastpath_step_fraction:.0%} of steps, "
          f"p99_deg={res.p99_ttft_degradation:.4f})", file=sys.stderr)
    return {"topology": "fleet", "n_gpus": n_gpus, "nbytes": 0,
            "wall_s": round(wall, 4),
            "completion_ns": round(comm, 2),
            "degradation": res.p99_ttft_degradation,
            "requests": len(res.requests)}


def _profile_point(name: str, fn) -> None:
    """Run ``fn`` once under cProfile; print the top-15 cumulative table.

    Emitted on stderr as ``#``-prefixed lines so a profiled bench run
    still produces a machine-readable JSON/CSV stream on stdout.
    """
    import cProfile
    import io
    import pstats

    pr = cProfile.Profile()
    pr.enable()
    fn()
    pr.disable()
    buf = io.StringIO()
    st = pstats.Stats(pr, stream=buf)
    st.sort_stats("cumulative").print_stats(15)
    print(f"# --- profile {name}: top 15 by cumulative time ---",
          file=sys.stderr)
    for line in buf.getvalue().splitlines():
        if line.strip():
            print(f"#   {line}", file=sys.stderr)


def measure_engine(reps: int = 3, profile: bool = False) -> dict:
    """Time the fixed grid; returns the JSON payload.

    Each point is best-of-``reps``: the minimum wall time is the least
    noise-contaminated estimate of the engine's cost, which is what a
    cross-run regression gate must compare (means absorb scheduler noise
    and flake the gate).
    """
    from repro.core import ratsim
    from repro.core.config import FabricConfig, SimConfig

    points = []
    t_all = time.perf_counter()
    for topo, n, nbytes in _bench_points():
        if topo == "fleet":
            points.append(_measure_fleet(n, reps, profile=profile))
            continue
        fab = FabricConfig(n_gpus=n, topology=topo, leaf_size=16,
                           oversubscription=2.0, pod_size=16)
        cfg = SimConfig(fabric=fab)
        wall = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            c = ratsim.compare(nbytes, n, cfg=cfg)
            wall = min(wall, time.perf_counter() - t0)
        if profile:
            _profile_point(f"{topo}/gpus{n}/{nbytes >> 20}MB",
                           lambda: ratsim.compare(nbytes, n, cfg=cfg))
        points.append({
            "topology": topo, "n_gpus": n, "nbytes": nbytes,
            "wall_s": round(wall, 4),
            "completion_ns": c.baseline.completion_ns,
            "degradation": c.degradation,
            "requests": c.baseline.counters.requests,
        })
        print(f"# {topo}/gpus{n}/{nbytes >> 20}MB: {wall:.3f}s "
              f"(deg={c.degradation:.4f})", file=sys.stderr)
    return {"grid": "engine-v3",
            "total_wall_s": round(time.perf_counter() - t_all, 4),
            "points": points}


def _point_key(p: dict) -> tuple:
    return (p["topology"], p["n_gpus"], p["nbytes"])


def _point_name(key: tuple) -> str:
    topo, n, nbytes = key
    if topo == "fleet":
        return f"fleet/gpus{n}/serving"
    return f"{topo}/gpus{n}/{nbytes >> 20}MB"


def check_against(current: dict, baseline: dict, tolerance: float,
                  min_delta_s: float = 0.05) -> list:
    """Per-point wall-time regression gate.

    Returns the list of failure messages (empty = gate passes) and prints
    the full delta table either way, so CI logs always show the trajectory.
    A grid point fails when its wall (``wall_s``) is both ``tolerance``
    slower than the committed baseline *and* at least ``min_delta_s``
    seconds slower — millisecond points jitter past any relative
    tolerance.  A grid mismatch (missing or extra points, e.g. a stale
    committed baseline after the grid changed) also fails — reset
    intentionally with ``--update-baseline``.
    """
    base = {_point_key(p): p for p in baseline.get("points", [])}
    cur = {_point_key(p): p for p in current.get("points", [])}
    failures = []
    print(f"# bench gate: wall-time tolerance +{tolerance:.0%} per point")
    print(f"{'point':<34s} {'base_s':>8s} {'cur_s':>8s} {'delta':>8s}")
    for key, cp in cur.items():
        name = _point_name(key)
        bp = base.get(key)
        if bp is None:
            print(f"{name:<34s} {'-':>8s} {cp['wall_s']:>8.3f} {'new':>8s}")
            failures.append(f"{name}: not in baseline "
                            f"(grid changed? --update-baseline)")
            continue
        b, c = bp["wall_s"], cp["wall_s"]
        delta = (c - b) / b if b else float("inf")
        regressed = delta > tolerance and c - b > min_delta_s
        flag = " REGRESSED" if regressed else ""
        print(f"{name:<34s} {b:>8.3f} {c:>8.3f} {delta:>+7.1%}{flag}")
        if regressed:
            failures.append(f"{name}: {b:.3f}s -> {c:.3f}s "
                            f"({delta:+.1%} > +{tolerance:.0%})")
    for key in base:
        if key not in cur:
            failures.append(f"{_point_name(key)}: in baseline but not "
                            f"measured (grid changed? --update-baseline)")
    for msg in failures:
        print(f"# FAIL {msg}", file=sys.stderr)
    return failures


def main() -> None:
    p = argparse.ArgumentParser(prog="python -m benchmarks.run")
    p.add_argument("--bench-engine", action="store_true",
                   help="time the fixed simulator grid and write a JSON "
                        "artifact instead of printing the figure CSV")
    p.add_argument("--out", default="BENCH_engine.json",
                   help="output path for --bench-engine")
    p.add_argument("--profile", action="store_true",
                   help="with --bench-engine: run each grid point once "
                        "more under cProfile and print a per-point top-15 "
                        "cumulative hotspot table on stderr, so perf work "
                        "can cite measured hotspots")
    p.add_argument("--check-against", default=None, metavar="BASELINE",
                   help="gate the engine grid against this committed "
                        "baseline JSON (fails on per-point wall-time "
                        "regressions beyond --tolerance)")
    p.add_argument("--current", default=None, metavar="JSON",
                   help="use a pre-measured BENCH_engine.json for "
                        "--check-against / --update-baseline instead of "
                        "re-running the grid")
    p.add_argument("--tolerance", type=float, default=0.35,
                   help="allowed fractional wall-time regression per "
                        "point (default 0.35)")
    p.add_argument("--min-delta-s", type=float, default=0.05,
                   help="absolute wall-time floor: a point fails only "
                        "when it is also at least this many seconds "
                        "slower (default 0.05)")
    p.add_argument("--update-baseline", action="store_true",
                   help="write the measured grid to the baseline path "
                        "(intentional reset); combine with --check-against "
                        "to choose the path")
    args = p.parse_args()

    if not (args.bench_engine or args.check_against
            or args.update_baseline):
        if args.current:
            p.error("--current requires --check-against or "
                    "--update-baseline (it would otherwise be ignored)")
        if args.profile:
            p.error("--profile requires --bench-engine (the figure CSV "
                    "path does not run the grid)")
        sys.exit(figures())

    if args.current:
        if args.profile:
            p.error("--profile needs a live measurement; it cannot "
                    "profile a pre-measured --current JSON")
        with open(args.current) as f:
            payload = json.load(f)
    else:
        payload = measure_engine(profile=args.profile)
        if args.bench_engine:
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=2)
            print(f"# wrote {args.out} (total {payload['total_wall_s']}s)",
                  file=sys.stderr)

    rc = 0
    if args.update_baseline:
        path = args.check_against or BASELINE_PATH
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# baseline updated: {path}", file=sys.stderr)
    elif args.check_against:
        with open(args.check_against) as f:
            baseline = json.load(f)
        rc = 1 if check_against(payload, baseline, args.tolerance,
                                args.min_delta_s) else 0
    sys.exit(rc)


if __name__ == '__main__':
    main()
