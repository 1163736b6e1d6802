"""90th percentile of the per-request mean gap between tokens, over the
requests that finished in the window."""
from benchmark import harness


def read(ctx):
    return harness.quantile(ctx["tpots"], 0.9)
