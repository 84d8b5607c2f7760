"""Readings that set a cell's correctness limits, many seeds in one process.

    python3 benchmarks/chip/readings.py --workload granite_decode \
        --seeds 11,12,13 --seconds 5 [--control N] [--out FILE]

For each seed: set up the cell, run a short window at the cell's own
load, free the program's state, and read the numbers the cell compares
(the program's), and on the first ``--control`` seeds the same numbers of
the float8 reference put in the program's place.  One JSON line per seed on stdout
(and appended to ``--out``).  The benchmark's own runs never run this;
the limits in the traffic files were set from its output (see PERF.md).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

# The checkout's root in place of this directory, which must not shadow
# the standard library.
sys.path[0] = str(Path(__file__).resolve().parents[2])

from benchmarks.chip import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", type=int, default=0,
                    help="read the float8 control on the first N seeds")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    run._paths()
    from benchmarks.chip.common import Context

    _, cell, config, traffic = run.load_cell(args.workload)
    devices = run.require_chips(cell["chips"])
    run.enable_compile_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = Context(workload=args.workload, config=config, traffic=traffic,
                      seed=seed, devices=devices, t_start=t0)
        driver = run.load_file_module(
            run.HERE / "drivers" / f"{traffic['driver']}.py",
            f"driver_{traffic['driver']}").Driver(ctx)
        driver.setup()
        t1 = time.perf_counter()
        got = driver.window(args.seconds)
        driver.release()
        t2 = time.perf_counter()
        line = {"workload": args.workload, "seed": seed, **got["metrics"],
                **driver.readings(control=i < args.control),
                "setup_s": t1 - t0, "check_s": time.perf_counter() - t2}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        del driver
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
