"""99th percentile of how late the generator submitted a request against
its due time (it shares one thread with the engine's loop)."""
from benchmark import harness


def read(ctx):
    return harness.quantile(ctx["lateness"], 0.99) if ctx["mix"]["mode"] == "open_loop" else None
