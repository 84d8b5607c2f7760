"""Device time of the loop over layers itself per run of the decode-step
program: the ``layer_scan`` scope (per-layer slices of weights and cache,
the stacked new cache) and the ops with no scope, which inside a decode
step are the ones XLA inserts, chiefly the copies of the carried cache."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_run_ms(ctx, "decode", ("layer_scan", scopes.UNSCOPED))
