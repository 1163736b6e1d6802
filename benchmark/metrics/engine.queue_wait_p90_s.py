"""90th percentile of the time from submit to admission, over the requests
the engine finished (request ledger, ``queue_s``)."""
from benchmark import harness


def read(ctx):
    if not ctx.get("ledger"):
        return None
    return harness.quantile([s["queue_s"] for s in ctx["ledger"]
                             if s.get("outcome") == "ok" or "queue_s" in s], 0.9)
