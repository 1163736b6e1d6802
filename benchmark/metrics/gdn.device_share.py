"""Share of the device's busy time in the traced slice that the two
delta-rule kernels take, in percent: the events of
``tdx_gdn_decode_update`` and ``tdx_gdn_chunk``
(``benchmark/moe_trace.py`` ``named_seconds``) over the union of all op
intervals (``benchmark/xplane.py``)."""
from benchmark import moe_trace


def read(ctx):
    tr = ctx.get("trace")
    secs = moe_trace.named_seconds(ctx, "tdx_gdn_decode_update",
                                   "tdx_gdn_chunk")
    if not secs or not tr["busy_s"]:
        return None
    return 100.0 * secs / tr["busy_s"]
