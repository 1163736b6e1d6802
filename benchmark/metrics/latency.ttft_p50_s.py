"""Median time from a request's due time to its first token, over all
requests due in the window, a refused or unanswered one counting as the
worst.  Not an end-to-end metric: two runs of one seed differ by 4 to 10 %
(PERF.md 2)."""
from benchmark import harness


def read(ctx):
    return harness.quantile(ctx["ttfts"], 0.5)
