"""Decode step's share of the chip's peak: the least time its required work
(``work.decode_step_work`` at the traced steps' mean fill) takes at the
peaks, over the step program's mean device time."""
from statistics import mean

from benchmarks.chip import work, xplane


def read(ctx):
    rec = ctx["records"]
    name = rec.get("programs", {}).get("decode")
    if name is None or not rec.get("decode_fills"):
        return None
    lo, hi = ctx["window"]
    runs = xplane.module_runs(ctx["trace"], ctx["device"], name, lo, hi)
    if not runs:
        return None
    w = work.decode_step_work(ctx["config"], rec["batch"],
                              mean(rec["decode_fills"]))
    return 100.0 * work.least_time_s(w["flops"], w["bytes"],
                                     ctx["peaks"]) / mean(runs)
