"""Prefill's share of the chip's peak: the least time of its required work
(``work_prefill.prefill_work`` at the traffic's batch and prompt length)
over the prefill program's mean device time."""
from statistics import mean

from benchmarks.chip import work, work_prefill, xplane


def read(ctx):
    name = ctx["records"].get("programs", {}).get("prefill")
    if name is None:
        return None
    lo, hi = ctx["window"]
    runs = xplane.module_runs(ctx["trace"], ctx["device"], name, lo, hi)
    if not runs:
        return None
    t = ctx["traffic"]
    w = work_prefill.prefill_work(ctx["config"], t["batch"], t["prompt_len"])
    return 100.0 * work.least_time_s(w["flops"], w["bytes"],
                                     ctx["peaks"]) / mean(runs)
