"""Median wall time of an engine step that ran only a decode or verify
program (host clock around ``engine.step()``, which fetches the logits)."""
from benchmark import harness


def read(ctx):
    t = [s["t1"] - s["t0"] for s in ctx["steps"] if s["calls"] and all(
        k == "decode" or k.startswith("verify") for k in s["calls"])]
    return harness.quantile(t, 0.5)
