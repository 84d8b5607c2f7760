"""Share of the traced serving slice in which no operation ran on the chip."""
from benchmarks.chip import xplane


def read(ctx):
    lo, hi = ctx["window"]
    busy = xplane.busy_s(ctx["trace"].ops.get(ctx["device"], []), lo, hi)
    return 100.0 * (1.0 - busy / (hi - lo)) if busy > 0 else None
