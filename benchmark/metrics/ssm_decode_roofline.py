"""The Mamba decode update's share of its roofline over the traced slice:
the least time the chip could take for the state, the conv tail and the
vectors that the live lanes of the slice's decode ticks need
(``rooflines/ssm.py`` ``decode_update_needs``) over the time of the events
under ``tdx_ssm_decode_update`` (``benchmark/ssm_trace.py``)."""
from benchmark.rooflines import ssm


def read(ctx):
    tr = (ctx.get("ssm_trace") or {}).get("tdx_ssm_decode_update")
    if not tr or not tr["seconds"] or not ctx.get("peaks"):
        return None
    lane_ticks = sum(s.get("decode_lanes", 0)
                     for s in ctx.get("traced_steps", []))
    if not lane_ticks:
        return None
    least, _bound = ssm.least_seconds(
        ssm.decode_update_needs(ctx["c"], lane_ticks), ctx["peaks"])
    return 100.0 * least / tr["seconds"]
