"""The delta rule's decode update's share of its roofline over the traced
slice: the least time the chip could take for the state and the vectors
that the live lanes of the slice's decode ticks need
(``rooflines/gdn.py`` ``decode_update_needs``) over the time of the
``tdx_gdn_decode_update`` kernel's events (``benchmark/moe_trace.py``
``named_seconds``).  A trace without them (a parent commit, another cell)
gives None."""
from benchmark import moe_trace
from benchmark.rooflines import gdn


def read(ctx):
    secs = moe_trace.named_seconds(ctx, "tdx_gdn_decode_update")
    if not secs or not ctx.get("peaks") or "lin_heads" not in ctx["c"]:
        return None
    lane_ticks = sum(s.get("decode_lanes", 0)
                     for s in ctx.get("traced_steps", []))
    if not lane_ticks:
        return None
    least, _bound = gdn.least_seconds(
        gdn.decode_update_needs(ctx["c"], lane_ticks), ctx["peaks"])
    return 100.0 * least / secs
