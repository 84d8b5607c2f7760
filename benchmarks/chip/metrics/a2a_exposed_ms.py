"""All-to-all time per step on the first chip during which no other
operation runs there."""
from benchmarks.chip import xplane


def read(ctx):
    steps = ctx["records"].get("steps")
    if not steps:
        return None
    lo, hi = ctx["window"]
    ops = ctx["trace"].ops.get(ctx["device"], [])
    if not any(xplane.is_collective(n) for n, _, _ in ops):
        return None
    return 1e3 * xplane.exposed_collective_s(ops, lo, hi) / steps
