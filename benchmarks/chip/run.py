"""Chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 benchmarks/chip/run.py --workload granite_decode --seed 7 \
        --seconds 30 --trace 0

Everything a cell needs is found by name: the cell's configuration file
(``configs/<config>.json``), its traffic file (``traffic/<mix>.json``),
the driver the traffic names (``drivers/<driver>.py``) and each per-layer
metric's reader (``metrics/<metric>.py``).  One process does the whole
run: it refuses to run without a TPU or with fewer chips than the cell
asks for, sets up (weights from the seed, compile, warm-up), runs the
timed window (``--trace 0``) or a traced slice (``--trace 1``), reads the
device's peak memory, frees the program's state, compares what the timed
path produced with the plain reference, and prints one JSON line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_ROOT = ROOT / ".bench_trace"


def _paths() -> None:
    """Import the benchmark as ``benchmarks.chip`` and the program from
    ``src``; this directory itself is not on the path (no module here may
    shadow one of the standard library)."""
    if sys.path and Path(sys.path[0]).resolve() == HERE:
        sys.path.pop(0)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_file_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return bench, cell, config, traffic


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU, but JAX found platform "
                         f"{devices[0].platform!r}")
    if len(devices) < n:
        raise SystemExit(f"run.py: the cell needs {n} chips, JAX found "
                         f"{len(devices)}")
    return devices[:n]


def enable_compile_cache() -> None:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` if set,
    else at the fixed path ``<checkout>/.jax_cache``; every program is
    cached, however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class GcPauses:
    """Counts the collector's runs and their longest pause."""

    def __init__(self):
        self.runs, self.max_s, self._t = 0, 0.0, 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.runs += 1
            self.max_s = max(self.max_s, time.perf_counter() - self._t)


def reported(bench: dict, cell: dict, trace: bool):
    """The metrics this cell reports in this mode: end-to-end ones that
    list it (or list no cells), or per-layer ones that do."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in moved
                             else [])]


def reduce_trace(ctx, records: dict, metrics: list, peaks: dict):
    """Per-layer metrics, device busy time and breakdown from the trace."""
    from benchmarks.chip import xplane

    tr = xplane.load(xplane.find_xplane(str(ctx.trace_dir)),
                     records.get("spans", ()))
    lo, hi = tr.window()
    devs = [d.id for d in ctx.devices]
    busy = [xplane.busy_s(tr.ops.get(d, []), lo, hi) for d in devs]
    reader_ctx = {"trace": tr, "window": (lo, hi), "device": devs[0],
                  "devices": devs, "records": records, "config": ctx.config,
                  "traffic": ctx.traffic, "peaks": peaks}
    values = {}
    for m in metrics:
        mod = load_file_module(HERE / "metrics" / f"{m['name']}.py",
                               f"metric_{m['name'].replace('.', '_')}")
        v = mod.read(reader_ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = {"device_ops": xplane.top_ops(tr.ops.get(devs[0], []), lo, hi),
                 "idle_gaps": xplane.named_gaps(tr, devs[0], lo, hi)}
    return values, sum(busy) / len(busy), hi - lo, breakdown


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             devices=None, config=None, traffic=None) -> dict:
    """One run.  ``devices``, ``config`` and ``traffic`` replace the chip
    check and the files (tests drive a small cell on the CPU this way)."""
    _paths()
    from benchmarks.chip import work
    from benchmarks.chip.common import Context

    bench, cell, cfg_file, traffic_file = load_cell(workload)
    if devices is None:
        devices = require_chips(cell["chips"])
    enable_compile_cache()
    ctx = Context(workload=workload, config=config or cfg_file,
                  traffic=traffic or traffic_file, seed=seed,
                  devices=list(devices), trace_dir=TRACE_ROOT / workload,
                  t_start=T_START)
    driver_path = HERE / "drivers" / f"{ctx.traffic['driver']}.py"
    driver = load_file_module(driver_path, f"driver_{ctx.traffic['driver']}"
                              ).Driver(ctx)
    driver.setup()
    # What set-up made lives to the end: keep the collector off it, so a
    # full collection in the window walks only the window's own objects.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - ctx.t_start
    print(f"setup {json.dumps(getattr(driver, 'setup_detail', {}))}",
          file=sys.stderr, flush=True)

    dev0 = ctx.devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(ctx.devices)}
    extra = {}
    if trace:
        records = driver.traced()
        peaks = work.peaks_for(dev0.device_kind)
        metrics, busy, window, breakdown = reduce_trace(
            ctx, records, reported(bench, cell, True), peaks)
        device.update(busy_s=busy, window_s=window)
        extra["breakdown"] = breakdown
        attempted, failed = records["attempted"], records["failed"]
    else:
        pauses = GcPauses()
        gc.callbacks.append(pauses)
        try:
            got = driver.window(seconds)
        finally:
            gc.callbacks.remove(pauses)
        detail = dict(got.get("detail", {}), gc_runs=pauses.runs,
                      gc_max_ms=pauses.max_s * 1e3)
        print(f"window {json.dumps(detail)}", file=sys.stderr, flush=True)
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = {n: {"value": v, "unit": units[n]}
                   for n, v in got["metrics"].items()}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}
        attempted, failed = got["attempted"], got["failed"]
    stats = [d.memory_stats() or {} for d in ctx.devices]
    device["memory_peak_bytes"] = max(s.get("peak_bytes_in_use", 0)
                                      for s in stats)
    driver.release()

    checks, detail = driver.checks()
    correct = all(lim is not None and val <= lim for _, val, lim in checks)
    print(f"readings {json.dumps(detail)}", file=sys.stderr, flush=True)
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, **extra,
            "checks": {n: {"value": v, "limit": lim} for n, v, lim in checks}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"run.py: no program under {ROOT / 'src'}")
    res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
