"""90th percentile of the time from a request's due time to its first
token, over all requests due in the window (the tail of what ``ttft_p50_s``
is the median of; too unsteady from run to run to carry a bound, PERF.md 2)."""
from benchmark import harness


def read(ctx):
    return harness.quantile(ctx["ttfts"], 0.9)
