"""Device time of the ``ep.experts`` scope (one-hot buffer, local experts'
SwiGLU, the einsum back to rows) per expert-parallel step, on the first
chip."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_run_ms(ctx, "step", ("ep.experts",))
