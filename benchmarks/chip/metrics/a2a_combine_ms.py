"""Device time of the combine all-to-alls (collective ops of the
``ep.combine`` scope) per expert-parallel step, on the first chip."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_run_ms(ctx, "step", ("ep.combine",), collectives=True)
