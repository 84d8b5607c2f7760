"""Device time of the ``attn`` scope (ln1, attention with its cache update,
residual add) per run of the decode-step program, on the chip."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_run_ms(ctx, "decode", ("attn",))
