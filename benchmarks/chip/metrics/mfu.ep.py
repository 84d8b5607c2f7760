"""Expert-parallel step's share of the chip's peak, on the first chip: the
least time of its required work per chip (``work.ep_step_work``) at the
peaks, over the step program's mean device time there."""
from statistics import mean

from benchmarks.chip import work, xplane


def read(ctx):
    name = ctx["records"].get("programs", {}).get("step")
    if name is None:
        return None
    lo, hi = ctx["window"]
    runs = xplane.module_runs(ctx["trace"], ctx["device"], name, lo, hi)
    if not runs:
        return None
    w = work.ep_step_work(ctx["config"], ctx["traffic"]["tokens_per_chip"],
                          len(ctx["devices"]))
    return 100.0 * work.least_time_s(w["flops"], w["bytes"],
                                     ctx["peaks"]) / mean(runs)
