"""Device time of the dispatch all-to-alls (collective ops of the
``ep.dispatch`` scope) per expert-parallel step, on the first chip."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_run_ms(ctx, "step", ("ep.dispatch",), collectives=True)
