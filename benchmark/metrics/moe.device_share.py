"""Share of the device's busy time in the traced slice that the expert
products take, in percent: the grouped-product events
(``benchmark/moe_trace.py``) over the union of all op intervals
(``benchmark/xplane.py``)."""
from benchmark import moe_trace


def read(ctx):
    tr = ctx.get("trace")
    secs = moe_trace.experts_seconds(ctx)
    if not secs or not tr["busy_s"]:
        return None
    return 100.0 * secs / tr["busy_s"]
