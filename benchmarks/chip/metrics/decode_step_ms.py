"""Device time of one run of the decode-step program (mean over the traced runs)."""
from statistics import mean

from benchmarks.chip import xplane


def read(ctx):
    name = ctx["records"].get("programs", {}).get("decode")
    if name is None:
        return None
    lo, hi = ctx["window"]
    runs = xplane.module_runs(ctx["trace"], ctx["device"], name, lo, hi)
    return 1e3 * mean(runs) if runs else None
