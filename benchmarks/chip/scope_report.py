"""One run of a cell, with what the harness's result line does not hold yet.

    python3 benchmarks/chip/scope_report.py --workload granite_decode \
        --seed 7 --seconds 30 --trace 1 [--out DIR]

Runs the cell through ``run.run_cell`` exactly as ``run.py`` does, and
adds to the result line it prints:

- ``compiles``: JAX's traces, compiles and persistent-cache hits over the
  driver's set-up and over its window (``--trace 0``) or traced slice
  (``repro.launch.compile_cache.CompileCounter``; left out for a program
  that has none);
- with ``--trace 1``, ``breakdown["scopes"]``: per program, the device ms
  per run by named scope (``unscoped`` included), the idle ms inside the
  runs and each scope's top ops (``scopes.breakdown``);
- with ``--out``, each program's compiled text (``<program>.hlo``) and
  that text without its metadata and ``scope`` attribute
  (``<program>.stripped.hlo``), to compare the programs of two commits.

A diagnostic: the benchmark's own runs are ``run.py``'s.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _compile_counter():
    """A fresh ``CompileCounter``, or a null context for a program older
    than it."""
    try:
        from repro.launch.compile_cache import CompileCounter
    except ImportError:
        return contextlib.nullcontext()
    return CompileCounter()


def _instrument(driver_cls, compiles: dict, out) -> None:
    """Count compiles around the driver's set-up, window and traced slice;
    after set-up, write its programs' texts to ``out``."""
    from benchmarks.chip import scopes

    def counted(name):
        method = getattr(driver_cls, name)

        def wrapped(self, *args, **kwargs):
            with _compile_counter() as c:
                got = method(self, *args, **kwargs)
            if c is not None:
                compiles[name] = c.counts()
                print(f"compiles {name} {json.dumps(c.counts())}",
                      file=sys.stderr, flush=True)
            if name == "setup" and out is not None:
                out.mkdir(parents=True, exist_ok=True)
                texts = scopes.live_texts(self.programs.values())
                for key, module in self.programs.items():
                    (out / f"{key}.hlo").write_text(texts[module])
                    (out / f"{key}.stripped.hlo").write_text(
                        scopes.strip_metadata(texts[module]))
            return got
        setattr(driver_cls, name, wrapped)

    for name in ("setup", "window", "traced"):
        counted(name)


def report(workload: str, seed: int, seconds: float, trace: bool,
           out=None, **run_kwargs) -> dict:
    """``run.run_cell``'s result plus ``compiles`` and, traced, the
    breakdown by scope.  ``run_kwargs`` go to ``run_cell`` (tests pass
    devices, config and traffic)."""
    from benchmarks.chip import run, scopes, xplane

    compiles: dict = {}
    load, reduce = run.load_file_module, run.reduce_trace

    def load_counted(path, name):
        mod = load(path, name)
        if name.startswith("driver_"):
            _instrument(mod.Driver, compiles, out)
        return mod

    def reduce_by_scope(ctx, records, metrics, peaks):
        values, busy, window, breakdown = reduce(ctx, records, metrics, peaks)
        tr = xplane.load(xplane.find_xplane(str(ctx.trace_dir)),
                         records.get("spans", ()))
        lo, hi = tr.window()
        breakdown["scopes"] = scopes.breakdown(
            tr, ctx.devices[0].id, lo, hi, records.get("programs", {}),
            scopes.scope_maps(records) or {})
        return values, busy, window, breakdown

    run.load_file_module, run.reduce_trace = load_counted, reduce_by_scope
    try:
        res = run.run_cell(workload, seed, seconds, trace, **run_kwargs)
    finally:
        run.load_file_module, run.reduce_trace = load, reduce
    res["compiles"] = compiles
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", type=Path, default=None,
                    help="directory for the programs' compiled texts")
    args = ap.parse_args(argv)
    if sys.path and Path(sys.path[0]).resolve() == HERE:
        sys.path.pop(0)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    res = report(args.workload, args.seed, args.seconds, bool(args.trace),
                 out=args.out)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
