"""Device time of the slot-metadata all-to-alls (collective ops of the
``ep.meta`` scope, about 10 KB a chip each) per expert-parallel step, on
the first chip."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_run_ms(ctx, "step", ("ep.meta",), collectives=True)
