"""Device time of the ``moe.experts`` scope (the experts' SwiGLU over the
capacity buffer) per run of the decode-step program, on the chip."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_run_ms(ctx, "decode", ("moe.experts",))
