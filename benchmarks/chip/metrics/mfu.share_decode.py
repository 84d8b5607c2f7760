"""Decode step's share of the chip's peak for an expert-parallel rank's
share: the least time of its required work (``work_share.
share_decode_work`` at the traced steps' mean fill) over the step
program's mean device time."""
from statistics import mean

from benchmarks.chip import work, work_share, xplane


def read(ctx):
    rec = ctx["records"]
    name = rec.get("programs", {}).get("decode")
    if name is None or not rec.get("decode_fills"):
        return None
    lo, hi = ctx["window"]
    runs = xplane.module_runs(ctx["trace"], ctx["device"], name, lo, hi)
    if not runs:
        return None
    w = work_share.share_decode_work(ctx["config"], rec["batch"],
                                     mean(rec["decode_fills"]))
    return 100.0 * work.least_time_s(w["flops"], w["bytes"],
                                     ctx["peaks"]) / mean(runs)
