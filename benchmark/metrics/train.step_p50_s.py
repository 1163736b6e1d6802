"""Median wall time of a training step, each ended by a fetched loss."""
from benchmark import harness


def read(ctx):
    return harness.quantile([t1 - t0 for t0, t1, _ in ctx["steps"]], 0.5)
